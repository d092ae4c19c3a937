"""Brumby-14B-Base (``brumby``): every layer a power-retention layer (gated
power attention of degree 2), several query heads reading ONE state, the
state the symmetric square of rotated, normalised keys against their values
with a normaliser beside it, and no page anywhere. The engine (a float32
state a slot and layer, laid by rotation as ``ops/retention.py`` keeps it; the
recurrence through its two kernels, in interpret mode here) against the
benchmark's plain reference ``benchmarks/architectures/brumby.py``, which is
the ATTENTION form: squared weights, decayed, divided by their row sums.

The model runs in float32 at a small size (2 layers, hidden 64, 4 query heads
over 2 states of head_dim 16: D = 136 features a key, 10 slabs of 16 x 16 a
head; pages of 4 that address nothing). In float32 the kernels multiply at
the highest precision, so the two sides differ by float32's own rounding
summed over two layers: 1e-4 of the logits' norm admits it, and every
spoiled reference moves the logits by more than ten times that.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.architectures import brumby as ref
from benchmarks.registry import REPO, Cell
from ray_tpu.llm import LLMConfig
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import JaxLLMEngine
from ray_tpu.llm.kinds import KINDS
from ray_tpu.models.transformer import CONFIGS, Transformer, TransformerConfig
from ray_tpu.ops import retention as rt

TOL = 1e-4
VOCAB, PAGE, BUCKET = 128, 4, 16
H, KVH, HD = 4, 2, 16
# the small model under the published key names
PUBLISHED = dict(
    name="brumby-tiny", model_type="brumby", hidden_act="silu",
    hidden_size=64, intermediate_size=96, num_hidden_layers=2,
    num_attention_heads=H, num_key_value_heads=KVH, head_dim=HD,
    attention_bias=False, rope_scaling=None, rope_theta=10000,
    sliding_window=None, use_sliding_window=False, max_window_layers=2,
    max_position_embeddings=64, rms_norm_eps=1e-6, tie_word_embeddings=False,
    vocab_size=VOCAB, torch_dtype="float32",
    initializer={"retention": 0.3, "mlp": 0.2, "embedding": 1.0,
                 "gate_bias": [3.0, 1.0, 0.1], "head_norms": 0.3})
OVERRIDES = dict(ref.program_overrides(PUBLISHED, 64), dtype=jnp.float32,
                 remat=False)
RCFG = ref.reference_cfg(PUBLISHED)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _engine(overrides=OVERRIDES, **engine):
    geometry = dict(max_num_seqs=3, max_model_len=64, page_size=PAGE,
                    prefill_bucket_min=BUCKET, expect_state_layers=2,
                    expect_retention_heads=KVH,
                    # too few for three requests at once: one is preempted
                    num_pages=14)
    return JaxLLMEngine(LLMConfig(
        model_id="tiny", model_overrides=overrides,
        engine_config=EngineConfig(**dict(geometry, **engine))))


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _reference(eng, toks, published=PUBLISHED, without=()):
    """The reference's logits [len(toks), vocab]; ``without``: the parts of
    the mathematics it is told to leave out or get wrong."""
    params = ref.to_reference_params(eng.params["params"], published)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(
            params, jnp.asarray(toks)[None],
            dict(ref.reference_cfg(published), without=tuple(without)))[0])


class _Run:
    """The engine's own programs on one cache, as the engine calls them: a
    ``[1, bucket]`` prefill told its slot, and decode steps over every slot.
    The block tables are handed over as for any model and address nothing."""

    def __init__(self, eng):
        e = eng.ecfg
        self.eng, self.e = eng, e
        self.cache = mr.init_cache(eng.mcfg, e.num_pages, e.page_size,
                                   e.max_num_seqs)
        self.tables = np.zeros((e.max_num_seqs, e.pages_per_seq), np.int32)
        self.active = np.zeros(e.max_num_seqs, bool)
        self.last = np.zeros(e.max_num_seqs, np.int32)
        self.lens = np.zeros(e.max_num_seqs, np.int32)

    def prefill(self, slot, toks, bucket=BUCKET, told=None):
        """``told``: the length the call is told (None: the prompt's own)."""
        batch = np.zeros((1, bucket), np.int32)
        batch[0, :len(toks)] = toks
        logits, self.cache = mr.prefill(
            self.eng.params, self.eng.mcfg, self.cache, jnp.asarray(batch),
            jnp.asarray([told or len(toks)], jnp.int32),
            jnp.asarray(self.tables[slot:slot + 1]),
            jnp.asarray([slot], jnp.int32))
        self.active[slot], self.lens[slot] = True, len(toks)
        return np.asarray(logits[0])

    def decode(self, tokens):
        for s, t in tokens.items():
            self.last[s] = t
        logits, self.cache = mr.decode_step(
            self.eng.params, self.eng.mcfg, self.cache,
            jnp.asarray(self.last), jnp.asarray(self.lens),
            jnp.asarray(self.tables), jnp.asarray(self.active))
        out = {s: np.asarray(logits[s]) for s in tokens}
        # only now: on the CPU the program may read the host's arrays in place
        self.lens[self.active] += 1
        return out

    def sequence(self, slot, toks, n, bucket=BUCKET):
        """Prefill ``toks[:n]`` and feed the rest: [len(toks) - n + 1, vocab]."""
        got = [self.prefill(slot, toks[:n], bucket)]
        got += [self.decode({slot: t})[slot] for t in toks[n:]]
        return np.stack(got)


# -- (a) the feature map, the two forms, the kernels -------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_feature_map_squares_the_inner_product(seed):
    x, y = jax.random.normal(jax.random.PRNGKey(seed), (2, 5, HD))
    assert rt.phi(x).shape == (5, HD * (HD + 1) // 2)
    assert _rel(jnp.sum(rt.phi(x) * rt.phi(y), -1), jnp.sum(x * y, -1) ** 2) < 1e-6


def _operands(R, S, seed, lengths=None, dtype=jnp.float32, bias=3.0):
    """q, k a unit apart from a common direction (so that no weight is a
    difference of large terms in either form), v, log-gates around a
    half-life of a dozen positions."""
    key = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(key[0], (R, S, H, HD)) + 0.5
    k = jax.random.normal(key[1], (R, S, KVH, HD)) + 0.5
    v = jax.random.normal(key[2], (R, S, KVH, HD))
    log_g = jax.nn.log_sigmoid(jax.random.normal(key[3], (R, S, KVH)) + bias)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), log_g, (
        None if lengths is None else jnp.asarray(lengths, jnp.int32))


def _masked(k, log_g, lengths):
    """What the recurrence is given behind a prompt's end: no key, no decay."""
    if lengths is None:
        return k, log_g
    real = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    return (jnp.where(real[..., None, None], k, 0),
            jnp.where(real[..., None], log_g, 0.0))


def test_recurrence_is_the_attention_form():
    """``retention_reference`` (phi, the state, the normaliser) against the
    adapter's ``power_attention`` (squared scores, decayed, divided by their
    row sums): one function, two evaluations that share nothing."""
    q, k, v, log_g, _ = _operands(2, 24, 0)
    o, S, z = rt.retention_reference(q, k, v, log_g)
    want = ref.power_attention(q, k, v, log_g).reshape(o.shape)
    assert S.shape == (2, KVH, 136, HD) and z.shape == (2, KVH, 136)
    assert _rel(o, want) < 1e-5
    with pytest.raises(ValueError, match="degree 3"):
        rt.retention_reference(q, k, v, log_g, degree=3)


# rows of a bucket of four chunks of 8: the prompt ends inside a chunk, AT a
# chunk's edge, one position behind it; a whole row; a padding row
ENDS = [13, 16, 17, 32, 0]


@pytest.mark.parametrize("R,S,chunk,lengths", [
    (1, 32, 8, None),            # four chunks: the state over three edges
    (1, 32, 32, None),           # one chunk: the quadratic form alone
    (1, 32, 8, [13]), (1, 32, 8, [16]), (1, 32, 8, [17]),
    (2, 16, 8, [5, 0]),          # a row of length 0 writes a zero state
    (5, 32, 8, ENDS),            # rows of several lengths in one call
], ids=["edges", "one-chunk", "inside", "at-edge", "behind-edge", "empty-row",
        "several"])
def test_scan_kernel_matches_the_recurrence(R, S, chunk, lengths):
    """o at every real position and the state after ``lengths - 1`` in the
    kernels' layout, float32 against float32; zeros behind a prompt's end;
    what a chunk that is passed over holds is never read."""
    q, k, v, log_g, n = _operands(R, S, S + R, lengths)
    k_real, g_real = _masked(k, log_g, n)
    want_o, S_, z_ = rt.retention_reference(q, k_real, v, g_real)
    scan = jax.jit(lambda *a: rt.retention_scan(*a, chunk=chunk))
    o, state = scan(q, k, v, log_g, n)
    assert state.dtype == jnp.float32
    assert state.shape == (R, KVH, *rt.state_shape(HD))
    real = np.arange(S)[None] < np.asarray(lengths or [S] * R)[:, None]
    live = real[..., None, None]
    assert _rel(jnp.where(live, o, 0), jnp.where(live, want_o, 0)) < 1e-5
    assert _rel(state, rt.rolled_state(S_, z_)) < 1e-5
    assert not np.asarray(jnp.where(live, 0, o)).any()
    if lengths and 0 in lengths:
        assert not np.asarray(state[lengths.index(0)]).any()
    if lengths:   # behind the chunk that holds the end nothing is read
        T = chunk if S % chunk == 0 else S
        over = np.arange(S)[None] >= -(-np.asarray(lengths)[:, None] // T) * T
        nan = lambda t: jnp.where(   # noqa: E731
            over.reshape(R, S, *[1] * (t.ndim - 2)), jnp.nan, t)
        o2, state2 = scan(nan(q), nan(k), nan(v), nan(log_g), n)
        assert (np.asarray(o2) == np.asarray(o)).all()
        assert (np.asarray(state2) == np.asarray(state)).all()


def test_scan_kernel_in_bfloat16_is_one_rounding_a_product():
    """bfloat16 operands into the large products, float32 accumulation, the
    decay, the normaliser, the quotient and the state float32."""
    q, k, v, log_g, _ = _operands(1, 64, 3, dtype=jnp.bfloat16)
    want_o, S_, z_ = rt.retention_reference(q, k, v, log_g)
    o, state = jax.jit(lambda *a: rt.retention_scan(*a, chunk=16))(
        q, k, v, log_g)
    assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    assert _rel(o.astype(jnp.float32), want_o) < 2e-2
    assert _rel(state, rt.rolled_state(S_, z_)) < 2e-2


def _step_case():
    """Three slots' states after eight positions, one more position each, and
    what the recurrence gives for it."""
    q, k, v, log_g, _ = _operands(3, 9, 1)
    want_o, S_, z_ = rt.retention_reference(q, k, v, log_g)
    _, S0, z0 = rt.retention_reference(q[:, :8], k[:, :8], v[:, :8],
                                       log_g[:, :8])
    step = (q[:, 8], k[:, 8], v[:, 8], log_g[:, 8])
    return rt.rolled_state(S0, z0), step, want_o[:, 8], rt.rolled_state(S_, z_)


def test_step_kernel_steps_one_layer_in_place():
    """One position for every slot on ONE layer of the leaf: to float32's own
    rounding the recurrence's step, a slot that is not kept to the bit what
    it was, the other layer untouched."""
    s0, step, want_o, want_s = _step_case()
    leaf = jnp.stack([jnp.full_like(s0, 7.0), s0])
    keep = jnp.asarray([True, False, True])
    o, out = jax.jit(lambda leaf, *a: rt.retention_step(leaf, 1, *a),
                     donate_argnums=0)(leaf, *step, keep)
    assert out.dtype == jnp.float32
    assert _rel(o[keep], want_o[keep]) < 1e-5
    assert _rel(out[1][keep], want_s[keep]) < 1e-5
    assert (np.asarray(out[1][1]) == np.asarray(s0[1])).all()
    assert (np.asarray(out[0]) == 7.0).all()


def test_step_kernel_tells_a_state_kept_in_bfloat16():
    """The step's 1e-5 case: from a state rounded to bfloat16's mantissa the
    same step is a hundred tolerances off."""
    s0, step, want_o, _ = _step_case()
    coarse = jax.lax.reduce_precision(s0, exponent_bits=8, mantissa_bits=7)
    o, _ = rt.retention_step(coarse[None], 0, *step)
    assert _rel(o, want_o) > 1e-3


def _leaves(S0, z0, slots):
    """The state [slots, KVH, D, V] in layer 1 of a leaf of two layers (the
    other full of sevens) with its scratch slot, and no position pending."""
    s0 = rt.rolled_state(S0, z0)
    leaf = jnp.stack([jnp.full_like(s0, 7.0), s0])
    leaf = jnp.concatenate([leaf, jnp.zeros_like(leaf[:, :1])], axis=1)
    return leaf, jnp.zeros((2, rt.FOLD - 1, 3, slots, *s0.shape[1:2], HD))


_decode = jax.jit(rt.retention_decode, donate_argnums=(0, 1))


@pytest.mark.parametrize("rep", [1, 5])
@pytest.mark.parametrize("first", range(rt.FOLD))
def test_read_read_read_fold_is_the_recurrence(rep, first):
    """Twelve decode steps of one layer from the state after eight
    positions, the first finding ``first`` steps already counted: the read
    pass (the state as of the last fold, the pending positions and its own by
    ``(x . y)^2``) three times and then the fold, ``rep`` query heads a state,
    against ``retention_reference`` at float32's own rounding: ``o`` at every
    step, and after each fold the written state, no position left pending
    and the other layer untouched. Between two folds the state is to the bit
    what the last one wrote."""
    key = jax.random.split(jax.random.PRNGKey(10 * rep + first), 4)
    S, B = 8 + 12, 3
    q = jax.random.normal(key[0], (B, S, rep * KVH, HD)) + 0.5
    k = jax.random.normal(key[1], (B, S, KVH, HD)) + 0.5
    v = jax.random.normal(key[2], (B, S, KVH, HD))
    log_g = jax.nn.log_sigmoid(jax.random.normal(key[3], (B, S, KVH)) + 3.0)
    want_o = rt.retention_reference(q, k, v, log_g)[0]
    ssm, pending = _leaves(*rt.retention_reference(
        q[:, :8], k[:, :8], v[:, :8], log_g[:, :8])[1:], B)
    count, folds = jnp.asarray(first, jnp.int32), 0
    for t in range(8, S):
        before = np.asarray(ssm[1])
        o, ssm, pending = _decode(ssm, pending, count, 1, q[:, t], k[:, t],
                                  v[:, t], log_g[:, t])
        assert _rel(o, want_o[:, t]) < 1e-5, t
        if int(count) == rt.FOLD - 1:
            _, S_, z_ = rt.retention_reference(
                q[:, :t + 1], k[:, :t + 1], v[:, :t + 1], log_g[:, :t + 1])
            assert _rel(ssm[1, :B], rt.rolled_state(S_, z_)) < 1e-5, t
            assert not np.asarray(pending).any()
            folds += 1
        else:
            assert (np.asarray(ssm[1, :B]) == before[:B]).all()
            assert np.asarray(pending[1, int(count)]).any()
        count = rt.advance(count)
    assert folds == 3 and (np.asarray(ssm[0, :B]) == 7.0).all()
    assert not np.asarray(pending[0]).any()


def test_slot_that_does_not_step_holds_state_and_pending_to_the_bit():
    """Four decode steps across a fold, under ``keep`` (a slot that does not
    decode, alone or beside a prompt): slot 0 steps; slot 1, with nothing
    pending, takes null positions and is to the bit what it was, state and
    pending rows, through the reads AND through the fold; slot 2 went quiet
    with one position pending: the fold takes that position and nothing else
    (its state is the recurrence's over the nine positions it saw)."""
    q, k, v, log_g, _ = _operands(3, 8 + 4, 5)
    ssm, pending = _leaves(*rt.retention_reference(
        q[:, :8], k[:, :8], v[:, :8], log_g[:, :8])[1:], 3)
    count = jnp.zeros((), jnp.int32)
    start = np.asarray(ssm[1])
    for t in range(8, 12):
        keep = jnp.asarray([True, False, t == 8])
        o, ssm, pending = _decode(ssm, pending, count, 1, q[:, t], k[:, t],
                                  v[:, t], log_g[:, t], keep)
        count = rt.advance(count)
        assert not np.asarray(pending[1, :, :, 1]).any()
        if t < 11:
            assert (np.asarray(ssm[1]) == start).all()
            assert np.asarray(pending[1, :, :, 2]).any()
    assert int(count) == 0 and not np.asarray(pending).any()
    assert (np.asarray(ssm[1, 1]) == start[1]).all()
    for slot, seen in ((0, 12), (2, 9)):
        _, S_, z_ = rt.retention_reference(*(
            t[slot:slot + 1, :seen] for t in (q, k, v, log_g)))
        assert _rel(ssm[1, slot], rt.rolled_state(S_, z_)[0]) < 1e-5
    # the same fold as a carried step makes it, whatever the count
    _, again, _ = rt.retention_decode(
        jnp.asarray(start)[None], jnp.zeros_like(pending[:1]), count, 0,
        q[:, 8], k[:, 8], v[:, 8], log_g[:, 8],
        jnp.asarray([False, False, False]), riding=True)
    assert (np.asarray(again[0]) == start).all()


# -- (b) the engine against the reference -------------------------------------------


@pytest.mark.parametrize("prompt_len,bucket", [
    (1, 16),    # one position: its own value comes back
    (15, 16),   # one short of the bucket: a padded row behind the prompt
    (16, 16),   # a bucket with no padding
    (21, 32),   # the next bucket
])
def test_engine_matches_reference(engine, prompt_len, bucket):
    """Prefill's last-position logits and then ten decode steps through the
    state, in a slot that is not the first: logits, not tokens. The cache
    holds one leaf and no page."""
    toks = np.random.default_rng(prompt_len).integers(0, VOCAB, prompt_len + 10)
    run = _Run(engine)
    got = run.sequence(2, toks, prompt_len, bucket)
    want = _reference(engine, toks)[prompt_len - 1:]
    assert _rel(got, want) < TOL, _rel(got, want)
    c = run.cache
    state, pending, count = c["retention"]
    assert state.shape == (2, 3 + 1, KVH, HD // 2 + 2, HD, HD)
    assert state.dtype == jnp.float32
    assert not any(KINDS[kind].paged for kind in c.states)
    assert set(c.states) == {"retention"} and c.moe_load is None
    assert pending.shape == (2, rt.FOLD - 1, 3, 3, KVH, HD)
    assert pending.dtype == jnp.float32 and count.dtype == jnp.int32
    assert int(count) == 10 % rt.FOLD    # ten steps: two folds
    assert mr._page_size(c) == 0
    held = np.abs(np.asarray(state)).max(axis=(2, 3, 4, 5))
    assert held[:, 2].min() > 0


def test_slot_used_again_after_a_longer_request(engine):
    """A slot handed to a second, shorter request: prefill overwrites the
    state from the prompt alone."""
    rng = np.random.default_rng(5)
    long, short = rng.integers(0, VOCAB, 27), rng.integers(0, VOCAB, 9)
    run = _Run(engine)
    run.sequence(1, long, 21, 32)
    run.active[1] = False
    got = run.sequence(1, short, 2)
    assert _rel(got, _reference(engine, short)[1:]) < TOL


def test_preempted_request_prefilled_again(engine):
    """Recompute preemption: a request that decoded five tokens is prefilled
    again from prompt + generated into another slot, and goes on as if
    nothing had happened; meanwhile its old slot decodes garbage."""
    toks = np.random.default_rng(6).integers(0, VOCAB, 6 + 5 + 6)
    want = _reference(engine, toks)
    run = _Run(engine)
    first = run.sequence(0, toks[:11], 6)
    assert _rel(first, want[5:11]) < TOL
    again = run.sequence(2, toks, 11)
    assert _rel(again, want[10:]) < TOL


def test_every_slot_prefill_call(engine):
    """The benchmark's check calls prefill with every slot's row and no slot
    argument: row b fills slot b, and a row of length 0 leaves zeros."""
    e, cfg = engine.ecfg, engine.mcfg
    toks = np.random.default_rng(3).integers(0, VOCAB, 11 + 3)
    run = _Run(engine)
    batch = np.zeros((e.max_num_seqs, BUCKET), np.int32)
    batch[0, :11] = toks[:11]
    logits, run.cache = mr.prefill(
        engine.params, cfg, run.cache, jnp.asarray(batch),
        jnp.asarray([11, 0, 0], jnp.int32), jnp.asarray(run.tables))
    assert not np.asarray(run.cache["retention"].state)[:, 1:].any()
    run.active[0], run.lens[0] = True, 11
    got = [np.asarray(logits[0])] + [run.decode({0: t})[0] for t in toks[11:]]
    assert _rel(np.stack(got), _reference(engine, toks)[10:]) < TOL


def test_bfloat16_engine_is_one_rounding_a_product():
    """The same model with bfloat16 products (weights stay float32 here):
    every product's operands rounded once, the stream, the state, the gates
    and the quotient float32, against the float32 reference."""
    eng = _engine(dict(OVERRIDES, dtype=jnp.bfloat16))
    toks = np.random.default_rng(8).integers(0, VOCAB, 13 + 4)
    run = _Run(eng)
    got = run.sequence(1, toks, 13)
    assert run.cache["retention"].state.dtype == jnp.float32
    assert _rel(got, _reference(eng, toks)[12:]) < 1e-1


# -- (c) every part shows in the logits -------------------------------------------------


# the program's own faults with what a slot keeps between two folds: the
def _with(cache, **leaves):
    """``cache`` with these leaves of its "retention" state replaced."""
    return cache.replace({"retention": cache["retention"]._replace(**leaves)})


# pending positions dropped before the fold reads them, and left standing
# behind it (read again until the next positions overwrite them)
PENDING_FAULTS = ("pending_dropped", "pending_twice")


class _FaultyRun(_Run):
    def __init__(self, eng, fault):
        super().__init__(eng)
        self.fault = fault

    def decode(self, tokens):
        c = self.cache["retention"]
        folds = int(c.count) == rt.FOLD - 1
        if folds and self.fault == "pending_dropped":
            self.cache = _with(self.cache, pending=jnp.zeros_like(c.pending))
        held = jnp.copy(c.pending)
        out = super().decode(tokens)
        if folds and self.fault == "pending_twice":
            self.cache = _with(self.cache, pending=held)
        return out


@pytest.mark.parametrize("wrong", ref.WITHOUT + PENDING_FAULTS)
def test_wrong_part_fails_the_comparison(engine, wrong):
    """A reference that leaves a part of the mathematics out or does it
    wrong is ten tolerances away: the degree, the off-diagonal pairs'
    sqrt(2), the gate (none, twice, over the new term too), the normaliser
    (none, undecayed), which state a query head reads, the rotation, the
    head norms and their order with the rotation. So is a PROGRAM that
    loses the positions a slot keeps between two folds, or folds them
    twice, against the reference as it stands."""
    toks = np.random.default_rng(7).integers(0, VOCAB, 13 + 6)
    got = _Run(engine).sequence(0, toks, 13)
    assert _rel(got, _reference(engine, toks)[12:]) < TOL
    if wrong in PENDING_FAULTS:
        bad = _FaultyRun(engine, wrong).sequence(0, toks, 13)
        moved = _rel(bad, _reference(engine, toks)[12:])
    else:
        moved = _rel(got, _reference(engine, toks, without=(wrong,))[12:])
    print(f"without {wrong}: the logits move by {moved:.3g} of their norm")
    assert moved > 10 * TOL


def test_state_from_the_padded_end_fails_the_comparison(engine):
    """A call told that its row fills the bucket carries the state from the
    padded end and not from ``lengths - 1``: the decode steps behind it are
    not the reference's."""
    toks = np.random.default_rng(9).integers(0, VOCAB, 9 + 4)
    want = _reference(engine, toks)[9:]
    run = _Run(engine)
    run.prefill(1, toks[:9])
    good = np.stack([run.decode({1: t})[1] for t in toks[9:]])
    run = _Run(engine)
    run.prefill(1, toks[:9], told=BUCKET)
    bad = np.stack([run.decode({1: t})[1] for t in toks[9:]])
    assert _rel(good, want) < TOL < 10 * TOL < _rel(bad, want)


def test_another_slots_state_fails_the_comparison(engine):
    """Decoding from the slot beside the one the prompt filled."""
    toks = np.random.default_rng(10).integers(0, VOCAB, 9 + 4)
    run = _Run(engine)
    run.prefill(1, toks[:9])
    run.active[[1, 2]], run.lens[2] = [False, True], 9
    bad = np.stack([run.decode({2: t})[2] for t in toks[9:]])
    assert _rel(bad, _reference(engine, toks)[9:]) > 10 * TOL


@pytest.mark.parametrize("emptied", [True, False],
                         ids=["emptied", "left-standing"])
def test_slot_refilled_between_two_folds(engine, emptied):
    """A slot handed on between two folds, its last tenant's two positions
    still pending: the prefill call empties them with the state it writes,
    and the new request's steps are the reference's; with the emptying taken
    back (the rows the call found, put where it left zeros) the fold takes
    a stranger's positions and the comparison FAILS."""
    rng = np.random.default_rng(12)
    gone, toks = rng.integers(0, VOCAB, 9 + 2), rng.integers(0, VOCAB, 5 + 6)
    run = _Run(engine)
    run.sequence(1, gone, 9)
    found = jnp.copy(run.cache["retention"].pending)   # the call donates it
    assert int(run.cache["retention"].count) == 2
    assert np.asarray(found[:, :2, :, 1]).any()
    first = run.prefill(1, toks[:5])
    assert int(run.cache["retention"].count) == 2    # a call alone counts nothing
    assert not np.asarray(run.cache["retention"].pending[:, :, :, 1]).any()
    if not emptied:
        run.cache = _with(run.cache, pending=found)
    got = np.stack([first] + [run.decode({1: t})[1] for t in toks[5:]])
    err = _rel(got, _reference(engine, toks)[4:])
    assert err < TOL if emptied else err > 10 * TOL, err


# -- (d) decode rows ride a prefill call --------------------------------------------------


def _settled(cache):
    """``cache`` with every slot's pending positions folded into its state
    (a null position for everybody: what a step beside a prompt does to a
    slot that does not decode) and none left: the form a carried step leaves
    a cache in, so that a cache a step alone left can be laid beside it."""
    ssm, pending, count = cache["retention"]
    B = pending.shape[3]
    nobody = jnp.zeros((B,), bool)
    for layer in range(ssm.shape[0]):
        _, ssm, pending = rt.retention_decode(
            ssm, pending, count, layer,
            jnp.ones((B, H, HD)), *jnp.zeros((2, B, KVH, HD)),
            jnp.zeros((B, KVH)), nobody, riding=True)
    return _with(cache, state=ssm, pending=pending,
                 count=jnp.zeros_like(count))


def test_decode_rows_ride_a_prefill_call(engine):
    """``prefill`` with ``riders`` is the call and then ``decode_step``: the
    prompt's logits, the step's logits, the states of the slot that decodes
    and of the slot that is filled again, beside a padding row and a slot
    that is not active (left as it was found: ``keep``)."""
    import prefill_rows

    assert mr.rides(engine.mcfg)
    prefill_rows.riders_equal_a_step_after_the_call(
        engine, np.random.default_rng(7), 1e-4, settle=_settled)


def test_riding_calls_match_reference():
    """Three requests through the calls the engine makes when it admits
    beside decoding slots (what the chip test runs at the published widths):
    every position's logits against the reference."""
    import prefill_rows

    eng = _engine(num_pages=40)
    rng = np.random.default_rng(11)
    seqs = {1: (rng.integers(0, VOCAB, 11 + 7), 11, 3),
            0: (rng.integers(0, VOCAB, 2 + 6), 2, 9),
            2: (rng.integers(0, VOCAB, 19 + 4), 19, 20)}
    got = prefill_rows.teacher_forced_riding(eng, seqs, gap=2)
    for slot, (toks, n, _) in seqs.items():
        assert got[slot].shape == (len(toks) - n + 1, VOCAB)
        assert _rel(got[slot], _reference(eng, toks)[n - 1:]) < TOL, slot


# -- the engine, its counters, the training module ----------------------------------------


def test_engine_serves_preempts_and_counts_the_states_it_moves():
    """Requests through ``JaxLLMEngine.step()`` of a model with NO paged leaf,
    with too few pages for all of them (the accounting is every model's: the
    pages are handed out, given back and address nothing): one is preempted
    and prefilled again, every greedy token is the reference's own argmax, and
    the state counters move with every decode step, riding ones too."""
    eng = _engine()
    assert eng._page_leaves() == []
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (11, 2, 19)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=9), decode_text=False)
    for prompt, out in zip(prompts, outs):
        assert len(out.token_ids) == 9
        want = _reference(eng, prompt + out.token_ids)[len(prompt) - 1:-1]
        top = np.sort(want, axis=-1)
        sure = top[:, -1] - top[:, -2] > 1e-3
        assert (np.asarray(out.token_ids) == np.argmax(want, axis=-1))[sure].all()
    m = eng.metrics
    assert m["preempted"] >= 1
    assert m["retention_state_slots"] == 2 * 3 * m["decode_steps"]
    # a step in FOLD writes the states back, and every riding step does
    assert m["retention_steps"] == m["decode_steps"] > 2 * rt.FOLD
    plain = m["decode_steps"] - m["riding_steps"]
    assert m["riding_steps"] <= m["retention_fold_steps"] \
        <= m["riding_steps"] + plain // rt.FOLD
    assert m["retention_fold_steps"] >= m["decode_steps"] // rt.FOLD
    assert int(eng.cache["retention"].count) == eng._kinds["retention"].pending
    assert 0 < m["retention_live_slots"] <= m["retention_state_slots"]
    assert m["kda_step_slots"] == m["ssd_step_slots"] == 0
    assert m["shared_kv_live_tokens"] == m["mla_decode_live_tokens"] == 0
    assert m["flash_q_blocks"] == 0           # no attention layer to count
    assert len(eng._free_pages) == eng.ecfg.num_pages - 1
    with pytest.raises(ValueError, match="power-retention layers of 0 key"):
        _engine(expect_retention_heads=0)
    with pytest.raises(ValueError, match="layers with recurrent state"):
        _engine(expect_state_layers=0)
    with pytest.raises(ValueError, match="export_kv"):
        eng.export_kv("nobody")


def test_admitted_at_every_count_preempted_and_ridden_between_folds():
    """Requests through ``step()`` admitted when the count of pending
    positions stands at 0, 1, 2 and 3: by a call alone, which leaves the
    count and the other slots' pending positions as they are and empties its
    own slot's, and by a call that carries the others' step, which folds
    whatever is pending; one runs out of pages between two folds and is
    prefilled again from all its tokens. Every request gets the tokens it
    got alone, the host's count is the cache's own after every step, and a
    step in four folds where nothing rides."""
    eng = _engine(num_pages=24)
    rng = np.random.default_rng(13)
    # 36 tokens: the bucket of 64, which carries no step here; 5: one of 16.
    # The two in the middle answer at length, so that the last finds slots
    # decoding through a whole round of counts
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (36, 37, 5, 38)]
    sps = [SamplingParams(max_tokens=n) for n in (6, 16, 16, 6)]
    assert [eng._carries(1, eng._prefill_bucket(len(p))) for p in prompts] \
        == [False, False, True, False]
    alone = [eng.generate([p], sp, decode_text=False)[0].token_ids
             for p, sp in zip(prompts, sps)]
    before, got, found = dict(eng.metrics), {}, []
    left, start = list(enumerate(prompts)), eng._kinds["retention"].pending
    for _ in range(400):
        if not (left or eng.has_unfinished()):
            break
        count = eng._kinds["retention"].pending
        wanted = (start + len(prompts) - len(left)) % rt.FOLD
        if left and count == wanted and None in eng._slots \
                and not eng._waiting:
            i, p = left.pop(0)
            eng.add_request(f"r{i}", p, sps[i])
        admitted = eng.metrics["admitted"]
        for out in eng.step():
            if out.finished:
                got[out.request_id] = out.token_ids
        found += [count] * (eng.metrics["admitted"] - admitted)
        assert int(eng.cache["retention"].count) == eng._kinds["retention"].pending
    assert not left and not eng.has_unfinished()
    assert [got[f"r{i}"] for i in range(len(prompts))] == alone
    d = {k: eng.metrics[k] - v for k, v in before.items()}
    assert set(found) == set(range(rt.FOLD)), found
    assert d["preempted"] >= 1 and d["riding_steps"] >= 1
    assert d["retention_steps"] == d["decode_steps"]
    plain = d["decode_steps"] - d["riding_steps"]
    assert d["riding_steps"] <= d["retention_fold_steps"] \
        <= d["riding_steps"] + plain // rt.FOLD + 1


def test_fold_metrics_read_a_window_as_data():
    """The two per-layer metrics this mechanism brought are data files that
    ``benchmarks.registry``'s cell reads: the share of the decode steps that
    wrote the states back, from the engine's two counters, and the read
    pass's device time a decode step, from the trace's ``retention_read``
    operations; beside them ``retention.step_dev_ms`` now holds the folds
    over ALL steps. A program without the counters and the kernel (the
    parent's) leaves both out and nothing is raised."""
    cell_name = "brumby-14b-base.completion-saturated-b32"
    cell = Cell(cell_name, os.path.join(REPO, "BENCHMARK.json"))
    listed = {m["name"]: m for m in cell.per_layer()}
    for name, layer, source in (
            ("retention.fold_share", "engine (llm/engine.py)",
             "program_counter"),
            ("retention.read_dev_ms", "kernels (ops/retention.py)",
             "device_trace")):
        assert listed[name]["workloads"] == [cell_name]
        assert listed[name]["moves"] == "serve_tokens_per_s"
        assert (listed[name]["layer"], listed[name]["source"]) == (layer, source)
        assert set(cell.reader(name)) == {"reduce", "args"}
    ops = {"retention_read (f32[32,8,128,16], f32[6,33,8,66,128,128])":
           [732 * 1.466e-3, 732.0],
           "retention_step (f32[32,8,128,16], f32[6,33,8,66,128,128])":
           [244 * 3.39e-3, 244.0]}
    trace = {"modules": {"jit_decode_step": {"count": 164, "total_s": 3.28}},
             "window_s": 4.0, "busy_s": 3.5, "op_kinds": ops}
    facts = {"max_num_seqs": 32, "peak_flops_per_s": 197e12,
             "peak_hbm_bytes_per_s": 819e9}
    window = {"decode_steps": 1631, "retention_steps": 1631,
              "retention_fold_steps": 409, "riding_steps": 4}
    got = {k: v["value"] for k, v in cell.per_layer_values(
        {"trace": trace, "spans": {}, "counters": window, "facts": facts}
    ).items()}
    assert got["retention.fold_share"] == 409 / 1631
    assert got["retention.read_dev_ms"] == pytest.approx(732 * 1.466 / 164)
    assert got["retention.step_dev_ms"] == pytest.approx(244 * 3.39 / 164)
    assert 70 < got["retention_step_roofline"] < 100   # on fold calls alone
    # the parent: a fold every step under the one name, no such counter
    parent = dict(trace, op_kinds={
        "retention_step (f32[32,8,128,8], f32[6,33,8,66,128,128])":
        [984 * 3.405e-3, 984.0]})
    got = cell.per_layer_values(
        {"trace": parent, "spans": {}, "facts": facts,
         "counters": {"decode_steps": 1631, "riding_steps": 4}})
    assert "retention.fold_share" not in got
    assert "retention.read_dev_ms" not in got
    assert got["retention.step_dev_ms"]["value"] == pytest.approx(
        984 * 3.405 / 164)


def test_engine_counts_the_chunks_the_scan_passes_over():
    """The engine's two counters hold what ``retention_scan``'s grid has a
    head and layer (a call's ``R x S / CHUNK``) and what of it lies wholly
    behind a prompt's end, as counted by hand."""
    C = rt.CHUNK
    for S, lens, want in ((2 * C, [100], (2, 1)), (2 * C, [C + 1], (2, 0)),
                          (8 * C, [1], (8, 7)), (4 * C, [C + 1, 3 * C, 0], (12, 7)),
                          (C // 2, [5, 0], (2, 1))):   # one chunk: the row
        assert rt.scan_chunks(S, lens) == want
    eng = _engine(max_num_seqs=2, max_model_len=4 * C, num_pages=None,
                  prefill_bucket_min=4 * C, page_size=C)
    rng = np.random.default_rng(4)
    lengths = (100, C + 50, 4 * C - 10)
    eng.generate([rng.integers(0, VOCAB, n).tolist() for n in lengths],
                 SamplingParams(max_tokens=2), decode_text=False)
    m = eng.metrics
    assert m["preempted"] == 0 and m["prefill_calls"] == len(lengths)
    assert m["retention_scan_chunks"] == 12
    assert m["retention_scan_chunks_skipped"] == 12 - (1 + 2 + 4)
    assert not eng._row_shapes.wanted


def test_training_module_is_the_reference():
    """``Transformer`` (``Block`` with the kind "retention") over a whole
    sequence against the reference, ``num_params`` against the tree and the
    adapter's count, and the mixer's 62.96 M at the published widths."""
    cfg = dataclasses.replace(CONFIGS["tiny"], **OVERRIDES)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, (1, 12)))
    params = Transformer(cfg).init(jax.random.PRNGKey(0), toks)
    tree = jax.tree.map(lambda x: getattr(x, "value", x), params["params"],
                        is_leaf=lambda x: hasattr(x, "value"))
    got = Transformer(cfg).apply({"params": tree}, toks)[0]
    with jax.default_matmul_precision("highest"):
        want = ref.forward(ref.to_reference_params(tree, PUBLISHED), toks, RCFG)[0]
    assert _rel(got, want) < 1e-4
    stored = sum(x.size for x in jax.tree.leaves(tree))
    assert cfg.num_params() == stored == ref.total_params(PUBLISHED)
    m = tree["layer_0"]["retention"]
    assert m["g_proj"]["kernel"].shape == (64, KVH)
    assert m["g_proj"]["bias"].shape == (KVH,)
    assert abs(float(jnp.mean(m["g_proj"]["bias"])) - 3.0) < 3.0
    assert float(jnp.std(m["q_norm"]["scale"])) > 0.05   # drawn away from 1
    wide = dict(d_model=5120, n_heads=40, n_kv_heads=8, head_size=128,
                d_ff=17408, vocab_size=151936, qk_head_norm=True, block="rms",
                tie_embeddings=False)
    one, two = (TransformerConfig(n_layers=n, layer_kinds=("retention",) * n,
                                  **wide).num_params() for n in (1, 2))
    assert two - one - 3 * 5120 * 17408 - 2 * 5120 == 62_955_784
    with pytest.raises(ValueError, match="degree 3"):
        mr.init_cache(dataclasses.replace(cfg, retention_degree=3), 4, 4, 2)


def test_adapter_refuses_what_the_program_does_not_express():
    for key, value in (("rope_scaling", {"type": "yarn"}),
                       ("use_sliding_window", True), ("sliding_window", 4096),
                       ("attention_bias", True),
                       ("tie_word_embeddings", True), ("model_type", "qwen3")):
        with pytest.raises(ValueError, match=key):
            ref.program_overrides(dict(PUBLISHED, **{key: value}), 64)
    with pytest.raises(ValueError, match="query heads"):
        ref.program_overrides(dict(PUBLISHED, num_key_value_heads=3), 64)
