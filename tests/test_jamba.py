"""AI21-Jamba2-3B (``jamba``): Mamba-1 mixers with the family's three inner
norms in thirteen layers of fourteen, one multi-query attention layer without
positions, a dense SwiGLU in every layer. The paged engine (the scan's state
and a convolution tail a slot for each Mamba layer beside one layer of pages,
the "mamba" kind of ``_forward``; the recurrence through ``ops/ssm.py``, in
interpret mode here) against the benchmark's plain reference
``benchmarks/architectures/jamba.py``.

The model runs in float32 at a small size with the published pattern (14
layers, attention at 7; hidden 64, inner 128, a state of 16, rank 8, 4 taps,
4 query heads over ONE key head of 16, pages of 4). Both sides are float32
here and differ by the order of their sums alone: 1e-4 of the logits' norm
admits that (the engine's scan and step are the reference's recurrence, its
attention a blocked softmax), and every spoiled reference (the state, D, one
inner norm, dt_proj's bias, the convolution's bias, the gate, the attention
layer, float8 weights) stands a thousand tolerances away.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.architectures import jamba as ref
from ray_tpu.llm import LLMConfig
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import JaxLLMEngine
from ray_tpu.models.transformer import CONFIGS, Transformer
from ray_tpu.ops import ssm

TOL = 1e-4
VOCAB, PAGE, BUCKET = 128, 4, 16
# the small model under the published key names
PUBLISHED = dict(
    name="jamba-tiny", model_type="jamba", hidden_act="silu",
    mamba_conv_bias=True, mamba_proj_bias=False, num_experts=1,
    num_experts_per_tok=1, sliding_window=None, tie_word_embeddings=True,
    attn_layer_period=14, attn_layer_offset=7, expert_layer_period=2,
    expert_layer_offset=1, hidden_size=64, intermediate_size=96,
    num_attention_heads=4, num_key_value_heads=1, num_hidden_layers=14,
    mamba_d_conv=4, mamba_d_state=16, mamba_dt_rank=8, mamba_expand=2,
    rms_norm_eps=1e-6, vocab_size=VOCAB, torch_dtype="float32",
    initializer={"attention": 0.1, "mlp": 0.05, "ssm_proj": 0.08,
                 "ssm_x": 0.1, "embedding": 0.3})
OVERRIDES = dict(ref.program_overrides(PUBLISHED, 64), dtype=jnp.float32,
                 remat=False)
MAMBA_LAYERS = 13


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _engine(**engine):
    geometry = dict(max_num_seqs=3, max_model_len=64, page_size=PAGE,
                    prefill_bucket_min=BUCKET,
                    expect_state_layers=MAMBA_LAYERS,
                    expect_ssm_inner_norms=True,
                    # too few for three requests at once: one is preempted
                    num_pages=14)
    return JaxLLMEngine(LLMConfig(
        model_id="tiny", model_overrides=OVERRIDES,
        engine_config=EngineConfig(**dict(geometry, **engine))))


@pytest.fixture(scope="module")
def engine():
    """ONE engine for the whole file (its parameters are half of what a test
    here costs): the tests that call its programs bring a cache of their
    own, and only the last one drives ``step()``."""
    return _engine()


@functools.lru_cache(maxsize=None)
def _forward(without):
    rcfg = dict(ref.reference_cfg(PUBLISHED), without=without)

    @jax.jit
    def forward(params, toks):
        with jax.default_matmul_precision("highest"):
            return ref.forward(ref.to_reference_params(params, PUBLISHED),
                               toks[None], rcfg)[0]
    return forward


def _reference(eng, toks, params=None, without=()):
    """The reference's logits [len(toks), vocab]; ``without``: parts of the
    model it is told to leave out."""
    return np.asarray(_forward(without)(params or eng.params["params"],
                                        jnp.asarray(toks)))


class _Run:
    """The engine's own programs on one cache, as the engine calls them: a
    ``[1, bucket]`` prefill told its slot, and decode steps over every slot."""

    def __init__(self, eng):
        e = eng.ecfg
        self.eng, self.e = eng, e
        self.cache = mr.init_cache(eng.mcfg, e.num_pages, e.page_size,
                                   e.max_num_seqs)
        self.tables = np.zeros((e.max_num_seqs, e.pages_per_seq), np.int32)
        self.active = np.zeros(e.max_num_seqs, bool)
        self.last = np.zeros(e.max_num_seqs, np.int32)
        self.lens = np.zeros(e.max_num_seqs, np.int32)

    def prefill(self, slot, toks, pages, bucket=BUCKET):
        self.tables[slot] = 0
        self.tables[slot, :len(pages)] = pages
        batch = np.zeros((1, bucket), np.int32)
        batch[0, :len(toks)] = toks
        logits, self.cache = mr.prefill(
            self.eng.params, self.eng.mcfg, self.cache, jnp.asarray(batch),
            jnp.asarray([len(toks)], jnp.int32),
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

    def sequence(self, slot, toks, n, pages, bucket=BUCKET):
        """Prefill ``toks[:n]`` and feed the rest: [len(toks) - n + 1, vocab]."""
        got = [self.prefill(slot, toks[:n], pages, bucket)]
        got += [self.decode({slot: t})[slot] for t in toks[n:]]
        return np.stack(got)


def _pages(first, positions):
    return np.arange(first, first + -(-positions // PAGE))


# -- (a) the step kernel against the recurrence as it reads -------------------------


def _operands(B, S, inner, N, seed):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.nn.softplus(jax.random.normal(k[0], (B, S, inner)) - 3),
            jax.random.normal(k[1], (B, S, inner)),
            jax.random.normal(k[2], (B, S, N)),
            jax.random.normal(k[3], (B, S, N)),
            -jnp.exp(jax.random.normal(k[4], (inner, N))),
            jax.random.normal(k[5], (B, inner, N)))


@pytest.mark.parametrize("slots,inner,keeps", [
    (3, 256, None),       # every slot steps: one block of slots, one of lanes
    (3, 256, "some"),     # a slot sits each step out, another one each time
    (16, 1024, "some"),   # two blocks of eight slots, lanes 512 at a time
], ids=["all", "kept", "blocks"])
def test_step_kernel_is_the_recurrence_one_position_at_a_time(slots, inner,
                                                              keeps):
    """Four positions through ``ssm_step`` (interpret) on ONE layer of a leaf
    of two, from a state that is not zero, against
    ``selective_scan_reference`` fed the same positions, a slot that is not
    kept given ``dt = 0`` there: ``y`` of the slots that step and every
    slot's state at float32's own rounding; a slot that sits a step out keeps
    its state to the bit; the other layer is never touched."""
    S, N = 4, 16
    dt, a, Bm, Cm, A, s0 = _operands(slots, S, inner, N, slots)
    keep = np.ones((S, slots), bool)
    if keeps:
        keep[np.arange(S), np.arange(S) % slots] = False
    want_y, want_s = ssm.selective_scan_reference(
        jnp.where(keep.T[..., None], dt, 0.0), a, Bm, Cm, A, s0)
    leaf = jnp.stack([jnp.full((slots, N, inner), 7.0),
                      jnp.swapaxes(s0, 1, 2)])
    step = jax.jit(lambda leaf, *o: ssm.ssm_step(leaf, 1, *o),
                   donate_argnums=0)
    for t in range(S):
        before = np.asarray(leaf[1])
        y, leaf = step(leaf, dt[:, t], a[:, t], Bm[:, t], Cm[:, t], A,
                       jnp.asarray(keep[t]) if keeps else None)
        assert y.dtype == leaf.dtype == jnp.float32
        assert _rel(y[keep[t]], want_y[:, t][keep[t]]) < 1e-6, t
        out = ~keep[t]
        assert (np.asarray(leaf[1])[out] == before[out]).all()
        assert np.abs(np.asarray(leaf[1])[keep[t]] - before[keep[t]]).max() > 0
    assert _rel(leaf[1], jnp.swapaxes(want_s, 1, 2)) < 1e-6
    assert (np.asarray(leaf[0]) == 7.0).all()


def test_scan_leaves_the_state_the_step_goes_on_from():
    """``selective_scan`` over a padded bucket (padding passed over by ``dt =
    0``) and then ``ssm_step``: the recurrence over prompt + 1 positions."""
    B, S, inner, N = 2, 16, 256, 16
    dt, a, Bm, Cm, A, _ = _operands(B, S + 1, inner, N, 9)
    lengths = jnp.asarray([16, 5])
    real = jnp.arange(S)[None] < lengths[:, None]
    _, state = ssm.selective_scan(jnp.where(real[..., None], dt[:, :S], 0.0),
                                  a[:, :S], Bm[:, :S], Cm[:, :S], A)
    y, leaf = ssm.ssm_step(state[None], 0, dt[:, S], a[:, S], Bm[:, S],
                           Cm[:, S], A)
    for row, n in enumerate((16, 5)):
        at = np.r_[np.arange(n), S]
        want_y, want_s = ssm.selective_scan_reference(
            dt[row:row + 1, at], a[row:row + 1, at], Bm[row:row + 1, at],
            Cm[row:row + 1, at], A, jnp.zeros((1, inner, N)))
        assert _rel(y[row], want_y[0, -1]) < 1e-5
        assert _rel(leaf[0, row], want_s[0].T) < 1e-5


# -- (b) the engine against the reference ---------------------------------------------


@pytest.mark.parametrize("prompt_len,bucket", [
    (1, 16),    # shorter than the taps: one real row of the tail, two zeros
    (3, 16),    # the tail exactly
    (15, 16),   # one short of the bucket: a padded row behind the prompt
    (16, 16),   # a bucket with no padding
    (21, 32),   # the next bucket, six pages
])
def test_engine_matches_reference(engine, prompt_len, bucket):
    """Prefill's last-position logits and then eight decode steps through the
    state, the tail and over page boundaries (pages of 4), in a slot that is
    not the first and on pages that are not the first."""
    toks = np.random.default_rng(prompt_len).integers(0, VOCAB, prompt_len + 8)
    run = _Run(engine)
    got = run.sequence(2, toks, prompt_len, _pages(5, len(toks)), bucket)
    want = _reference(engine, toks)[prompt_len - 1:]
    assert _rel(got, want) < TOL, _rel(got, want)
    c = run.cache
    state, tail = c["mamba"]
    assert state.shape == (MAMBA_LAYERS, 3, 16, 128)
    assert state.dtype == jnp.float32
    assert tail.shape == (MAMBA_LAYERS, 3, 3, 128)
    assert set(c.states) == {"full", "mamba"} and c.moe_load is None
    assert c["full"].shape == (1, 14, PAGE, 2 * 16)
    assert np.abs(np.asarray(state)[:, 2]).max(axis=(1, 2)).min() > 0


def test_slot_used_again_after_a_longer_request(engine):
    """A slot and its pages handed to a second, shorter request: prefill
    overwrites the state and the tail from the prompt alone."""
    rng = np.random.default_rng(5)
    long, short = rng.integers(0, VOCAB, 27), rng.integers(0, VOCAB, 9)
    run = _Run(engine)
    run.sequence(1, long, 21, _pages(3, 27), 32)
    run.active[1] = False
    got = run.sequence(1, short, 2, _pages(3, 9))
    assert _rel(got, _reference(engine, short)[1:]) < TOL


def test_every_slot_prefill_call(engine):
    """The benchmark's check calls prefill with every slot's row and no slot
    argument: row b fills slot b, and a row of length 0 leaves zeros."""
    e, cfg = engine.ecfg, engine.mcfg
    toks = np.random.default_rng(3).integers(0, VOCAB, 11 + 3)
    run = _Run(engine)
    run.tables[0, :4] = np.arange(1, 5)
    batch = np.zeros((e.max_num_seqs, BUCKET), np.int32)
    batch[0, :11] = toks[:11]
    logits, run.cache = mr.prefill(
        engine.params, cfg, run.cache, jnp.asarray(batch),
        jnp.asarray([11, 0, 0], jnp.int32), jnp.asarray(run.tables))
    assert not np.asarray(run.cache["mamba"].state)[:, 1:].any()
    assert not np.asarray(run.cache["mamba"].tail)[:, :, 1:].any()
    run.active[0], run.lens[0] = True, 11
    got = [np.asarray(logits[0])] + [run.decode({0: t})[0] for t in toks[11:]]
    assert _rel(np.stack(got), _reference(engine, toks)[10:]) < TOL


# -- (c) every part shows in the logits -------------------------------------------------


@pytest.mark.parametrize("wrong", [
    "state", "D", "dt_layernorm", "b_layernorm", "c_layernorm", "dt_bias",
    "conv_bias", "gate", "attention", "float8"])
def test_wrong_part_fails_the_comparison(engine, wrong):
    """A reference that leaves a part of the model out (the state: ``B = 0``;
    the skip; one of the three inner norms; dt_proj's bias; the convolution's;
    the gate; the attention layer), or one computed from weights rounded to
    float8, is a thousand tolerances away."""
    toks = np.random.default_rng(7).integers(0, VOCAB, 13 + 4)
    got = _Run(engine).sequence(0, toks, 13, _pages(1, len(toks)))
    assert _rel(got, _reference(engine, toks)[12:]) < TOL
    if wrong == "float8":
        rounded = jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype),
            engine.params["params"])
        bad = _reference(engine, toks, params=rounded)
    else:
        bad = _reference(engine, toks, without=(wrong,))
    moved = _rel(got, bad[12:])
    print(f"{wrong}: the logits move by {moved:.3g} of their norm")
    assert moved > 1000 * TOL


# -- (d) decode rows ride a prefill call --------------------------------------------------


def test_decode_rows_ride_a_prefill_call(engine):
    """``prefill`` with ``riders`` is the call and then ``decode_step``: the
    prompt's logits, the step's logits, the pages, the states and the tails
    of the slot that decodes and of the slot that is filled again, beside a
    padding row and a slot that is not active (left to the bit as it was
    found: ``keep``)."""
    import prefill_rows

    assert mr.rides(engine.mcfg)
    prefill_rows.riders_equal_a_step_after_the_call(
        engine, np.random.default_rng(7), 1e-4)


def test_riding_calls_match_reference(engine):
    """Three requests through the calls the engine makes when it admits
    beside decoding slots (what the chip test runs at the published widths):
    every position's logits against the reference."""
    import prefill_rows

    rng = np.random.default_rng(11)
    seqs = {1: (rng.integers(0, VOCAB, 11 + 6), 11, 1),
            0: (rng.integers(0, VOCAB, 2 + 6), 2, 6),
            2: (rng.integers(0, VOCAB, 13 + 4), 13, 8)}
    got = prefill_rows.teacher_forced_riding(engine, seqs, gap=2)
    for slot, (toks, n, _) in seqs.items():
        assert got[slot].shape == (len(toks) - n + 1, VOCAB)
        assert _rel(got[slot], _reference(engine, toks)[n - 1:]) < TOL, slot


# -- the engine, its counters, the training module ----------------------------------------


def test_engine_serves_preempts_and_counts_the_states_it_moves(engine):
    """Requests through ``JaxLLMEngine.step()`` with too few pages for all of
    them: one is preempted and prefilled again, every greedy token is the
    reference's own argmax, and the three counters move with every decode
    step, riding ones too."""
    eng = engine
    rng = np.random.default_rng(3)
    # each in the bucket of 16; 6 + 4 + 7 pages of 13 by their last token
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (11, 2, 13)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=12), decode_text=False)
    for prompt, out in zip(prompts, outs):
        assert out.finish_reason == "stop" or len(out.token_ids) == 12
        want = _reference(eng, prompt + out.token_ids)[len(prompt) - 1:-1]
        top = np.sort(want, axis=-1)
        sure = top[:, -1] - top[:, -2] > 1e-3
        assert (np.asarray(out.token_ids) == np.argmax(want, axis=-1))[sure].all()
    m = eng.metrics
    assert m["preempted"] >= 1
    assert m["ssm_steps"] == m["decode_steps"] > 0
    assert m["ssm_step_slots"] == MAMBA_LAYERS * 3 * m["decode_steps"]
    assert 0 < m["ssm_step_live_slots"] <= m["ssm_step_slots"]
    assert m["ssm_step_live_slots"] % MAMBA_LAYERS == 0
    assert m["ssd_steps"] == m["retention_steps"] == 0
    with pytest.raises(ValueError, match="layers with recurrent state"):
        _engine(expect_state_layers=0)
    with pytest.raises(ValueError, match="inner norms"):
        _engine(expect_ssm_inner_norms=False)


def test_training_module_is_the_reference(engine):
    """``Transformer`` (``Block`` with the kind "mamba", the inner norms) over
    a whole sequence against the reference, and ``num_params`` against the
    tree and the adapter's count."""
    cfg = dataclasses.replace(CONFIGS["tiny"], **OVERRIDES)
    assert cfg.ssm_inner_norms and cfg.rope_kinds == ()
    assert cfg.layer_kinds == ("mamba",) * 7 + ("full",) + ("mamba",) * 6
    toks = jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, (1, 17)))
    tree = engine.params["params"]     # Transformer.init's own, seeded
    assert set(tree["layer_0"]["mamba"]) == {
        "in_proj", "conv_kernel", "conv_bias", "x_proj", "dt_norm", "b_norm",
        "c_norm", "dt_proj", "A_log", "D", "out_proj"}
    got = jax.jit(Transformer(cfg).apply)({"params": tree}, toks)[0]
    assert _rel(got, _reference(engine, toks[0])) < 1e-5
    stored = sum(x.size for x in jax.tree.leaves(tree))
    assert cfg.num_params() == stored == ref.total_params(PUBLISHED)
    # without the flag the tree holds no norm and the count follows
    plain = dataclasses.replace(cfg, ssm_inner_norms=False)
    assert plain.num_params() == stored - MAMBA_LAYERS * (8 + 2 * 16)
