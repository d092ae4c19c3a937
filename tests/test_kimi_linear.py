"""Kimi-Linear-48B-A3B-Instruct (``kimi_linear``): delta-rule linear attention
with a decay per key lane (KDA) in three layers of four, latent attention
WITHOUT positions in the fourth, a sigmoid router with a selection bias over
experts of which one rank of four holds a quarter, a shared expert and a
leading dense layer. The paged engine (a float32 matrix state and the three
convolutions' tail a slot for each KDA layer, one latent row a position for
each latent layer, found by its rank among them; the recurrence through
``ops/kda.py``, in interpret mode here) against the benchmark's plain
reference ``benchmarks/architectures/kimi_linear.py``.

The model runs in float32 at a small size (layers ``K M K K M``, so a latent
layer's rank among its kind differs from its number; hidden 64, 4 KDA heads of
16, 4 taps, 4 latent heads of 16 + 8 | 16 over a rank of 32, 8 experts top-3
of which 2 are held, pages of 4). In float32 the kernels multiply at the
highest precision, so the two sides differ by float32's own rounding summed
over five layers: 1e-4 of the logits' norm admits it, and every spoiled
reference moves the logits by more than a hundred times that.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.architectures import kimi_linear as ref
from ray_tpu.llm import LLMConfig
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import JaxLLMEngine
from ray_tpu.llm.kinds import kda as kda_kind
from ray_tpu.models.transformer import CONFIGS, Transformer
from ray_tpu.ops import kda

TOL = 1e-4
VOCAB, PAGE, BUCKET = 128, 4, 16
# the small model under the published key names
PUBLISHED = dict(
    name="kimi-tiny", model_type="kimi_linear", hidden_act="silu",
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=5, first_k_dense_replace=1, moe_layer_freq=1,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    q_lora_rank=None, mla_use_nope=True, rope_scaling=None, rope_theta=10000,
    linear_attn_config={"kda_layers": [1, 3, 4, 6, 7],
                        "full_attn_layers": [2, 5, 8], "num_heads": 4,
                        "head_dim": 16, "short_conv_kernel_size": 4},
    num_experts=2, num_experts_per_token=3, num_shared_experts=1,
    expert_parallel={"routed_experts": 8, "ranks": 4, "rank": 0},
    moe_renormalize=True, moe_router_activation_func="sigmoid",
    num_expert_group=1, topk_group=1, use_grouped_topk=True,
    routed_scaling_factor=2.446, num_nextn_predict_layers=0,
    rms_norm_eps=1e-5, tie_word_embeddings=False, vocab_size=VOCAB,
    torch_dtype="float32",
    initializer={"attention": 0.3, "kda": 0.3, "mlp": 0.2, "experts": 0.3,
                 "embedding": 1.0})
OVERRIDES = dict(ref.program_overrides(PUBLISHED, 64), dtype=jnp.float32,
                 remat=False)
RCFG = ref.reference_cfg(PUBLISHED)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _engine(overrides=OVERRIDES, **engine):
    geometry = dict(max_num_seqs=3, max_model_len=64, page_size=PAGE,
                    prefill_bucket_min=BUCKET, expect_experts=2,
                    expect_routed_experts=8, expect_latent_rank=32,
                    expect_state_layers=3, expect_kda_heads=4,
                    # too few for three requests at once: one is preempted
                    num_pages=14)
    return JaxLLMEngine(LLMConfig(
        model_id="tiny", model_overrides=overrides,
        engine_config=EngineConfig(**dict(geometry, **engine))))


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _reference(eng, toks, published=PUBLISHED, **wrong):
    """The reference's logits [len(toks), vocab]; ``wrong``: facts of the
    model it is told to get wrong."""
    params = ref.to_reference_params(eng.params["params"], published)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(
            params, jnp.asarray(toks)[None],
            dict(ref.reference_cfg(published), **wrong))[0])


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


# -- (a) the kernels against the recurrence as it reads ----------------------------

# the fastest decay the seeded constants give a lane: A = 16 (A_log's upper
# end) times a step of softplus(log(0.1) + 1) = 0.24, the largest dt_bias
# under a gate four deviations out
EXTREME = -16 * 0.24


# rows of a bucket of eight chunks of 16: the prompt ends in the first chunk,
# in a middle one, in the last; a padding row
ENDS = [5, 60, 121, 0]


def _operands(R, S, H, K, seed, lengths=None, g=None, dtype=jnp.float32):
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(key[0], (R, S, H, K))) * K ** -0.5
    k = unit(jax.random.normal(key[1], (R, S, H, K)))
    v = jax.random.normal(key[2], (R, S, H, K))
    if g is None:  # lanes from nearly no decay to the seeded extreme
        g = EXTREME * jax.random.uniform(key[3], (R, S, H, K)) ** 4
    else:
        g = jnp.full((R, S, H, K), g, jnp.float32)
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (R, S, H)))
    if lengths is not None:
        real = jnp.arange(S)[None, :] < jnp.asarray(lengths)[:, None]
        g = jnp.where(real[..., None, None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.mark.parametrize("R,S,chunk,lengths,g", [
    (1, 64, 16, None, None),        # four chunks: the state over three edges
    (1, 64, 64, None, None),        # one chunk of four sub-chunks: the series
    (2, 32, 16, [32, 13], None),    # padding behind a prompt, inside a chunk
    (3, 16, 64, [2, 16, 0], None),  # shorter than the taps; a padding row
    (1, 8, 64, [5], None),          # a bucket shorter than a sub-chunk
    (1, 128, 64, None, EXTREME),    # every lane at the extreme, whole chunks
    # eight chunks: rows that end in the first, a middle and the last, a
    # padding row (on prepared operands nothing is passed over)
    (4, 128, 16, ENDS, None),
], ids=["edges", "series", "padding", "short-and-empty", "one-block",
        "extreme-decay", "ends-by-chunk"])
def test_scan_kernel_matches_the_recurrence(R, S, chunk, lengths, g):
    """o at every real position and the state after ``lengths - 1``, float32
    against float32: no exponent leaves float32's range (the extreme decay
    over a whole chunk is e^-246, its inverse would be infinite)."""
    ops = _operands(R, S, 2, 16, S, lengths, g)
    want_o, want_s = kda.kda_reference(*ops)
    o, s = jax.jit(lambda *a: kda.kda_scan(*a, chunk=chunk))(*ops)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
    real = np.arange(S)[None] < np.asarray(lengths or [S] * R)[:, None]
    real = real[..., None, None]
    assert _rel(jnp.where(real, o, 0), jnp.where(real, want_o, 0)) < 1e-5
    assert _rel(s, want_s) < 1e-5
    if lengths and 0 in lengths:   # a padding row leaves a zero state
        assert not np.asarray(s[lengths.index(0)]).any()


def test_scan_kernel_in_bfloat16_is_one_rounding_a_product():
    """bfloat16 operands into the large products, float32 accumulation, the
    solve and the state float32: the result stays within what rounding the
    operands once costs."""
    ops = _operands(1, 128, 2, 16, 3, dtype=jnp.bfloat16)
    want_o, want_s = kda.kda_reference(*ops)
    o, s = jax.jit(kda.kda_scan)(*ops)
    assert o.dtype == s.dtype == jnp.float32
    assert _rel(o, want_o) < 2e-2 and _rel(s, want_s) < 2e-2


def _layer_arrays(R, S, H, K, seed, extreme, dtype):
    """What a "kda" layer hands its prefill kernel: the convolutions' output
    ``q | k | v`` before the silu, the decay gate's ``f``, beta, the output
    gate (the products' in ``dtype``) and the layer's own constants as they
    are seeded (``A`` up to 16, steps log-uniform in [1e-3, 1e-1]);
    ``extreme``: every lane at the fastest decay those give."""
    key = jax.random.split(jax.random.PRNGKey(seed), 7)
    a = jax.random.normal(key[0], (R, S, 3 * H * K))
    gate = jax.random.normal(key[1], (R, S, H * K))
    beta = jax.nn.sigmoid(jax.random.normal(key[2], (R, S, H)))
    dt = jnp.exp(jax.random.uniform(key[3], (H * K,)) * np.log(100.0)
                 + np.log(1e-3))
    m = {"dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
         "A_log": jnp.log(jax.random.uniform(key[4], (H,), minval=1.0,
                                             maxval=16.0)),
         "o_norm": {"scale": 1.0 + 0.2 * jax.random.normal(key[5], (K,))}}
    f = jax.random.normal(key[6], (R, S, H * K))
    if extreme:  # softplus(f + dt_bias) = 0.24 under A = 16
        m["A_log"] = jnp.full((H,), np.log(16.0))
        f = jnp.full_like(f, np.log(np.expm1(-EXTREME / 16))) - m["dt_bias"]
    return a.astype(dtype), f.astype(dtype), beta, gate.astype(dtype), m


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("R,S,chunk,lengths,extreme", [
    (1, 64, 16, None, False), (1, 64, 64, None, False),
    (2, 32, 16, [32, 13], False), (3, 16, 64, [2, 16, 0], False),
    (1, 8, 64, [5], False), (1, 128, 64, None, True),
    (2, 512, kda.CHUNK, [400, 270], False),  # one call, two prompts
    (4, 128, 16, ENDS, False),      # eight chunks, most of them passed over
], ids=["edges", "series", "padding", "short-and-empty", "one-block",
        "extreme-decay", "two-rows", "ends-by-chunk"])
def test_prefill_kernel_is_the_layer_between_conv_and_o_proj(
        R, S, chunk, lengths, extreme, dtype, tol):
    _prefill_is_the_layer(R, S, chunk, lengths, extreme, dtype, tol, H=2)


def _prefill_is_the_layer(R, S, chunk, lengths, extreme, dtype, tol, H):
    """``kda_prefill`` on a layer's arrays as its products left them against
    the composition it replaces, in float32 on the same values: the
    operands made in XLA (``_kda_operands``), the recurrence as it reads with
    no decay and no update behind a prompt's end, the head's norm and the
    gate. In float32 to float32's own rounding; in bfloat16 within what
    rounding a product's operands once costs, o in the stored type as
    ``o_proj`` takes it. The state is the one after ``lengths - 1``; o behind
    a prompt's end is nobody's but finite, and zero in the chunks that lie
    wholly behind it: those the kernel passes over, and reads nothing of."""
    import types

    K, eps = 16, 1e-5
    cfg = types.SimpleNamespace(kda_heads=H, kda_head_dim=K)
    a, f, beta, gate, m = _layer_arrays(R, S, H, K, S + R, extreme, dtype)
    n = jnp.asarray(lengths or [S] * R, jnp.int32)
    real = jnp.arange(S)[None, :] < n[:, None]
    wide = lambda t: t.astype(jnp.float32)   # noqa: E731
    q, k, v, g = kda_kind._operands(wide(a), wide(f), m, cfg)
    want_o, want_s = kda.kda_reference(
        q, k, v, jnp.where(real[..., None, None], g, 0.0),
        jnp.where(real[..., None], beta, 0.0))
    want_o = mr._rmsnorm(want_o, m["o_norm"]["scale"], eps) * jax.nn.sigmoid(
        wide(gate).reshape(want_o.shape))
    prefill = jax.jit(lambda a, f, beta, gate: kda.kda_prefill(
        a, f, beta, gate, m["dt_bias"], m["A_log"], m["o_norm"]["scale"], n,
        eps=eps, chunk=chunk))
    o, s = prefill(jax.nn.silu(a), f, beta, gate)
    assert o.dtype == dtype and o.shape == (R, S, H * K)
    assert s.dtype == jnp.float32 and np.isfinite(np.asarray(s)).all()
    assert np.isfinite(np.asarray(wide(o))).all()
    T = chunk if S % chunk == 0 else S
    over = (jnp.arange(S)[None, :] >= -(-n[:, None] // T) * T)[..., None]
    assert not np.asarray(jnp.where(over, wide(o), 0)).any()
    if over.any():   # whatever a chunk that is passed over holds is not read
        nan = lambda t: jnp.where(over, jnp.nan, t)   # noqa: E731
        o_nan, s_nan = prefill(nan(jax.nn.silu(a)), nan(f), nan(beta),
                               nan(gate))
        assert (np.asarray(wide(o_nan)) == np.asarray(wide(o))).all()
        assert (np.asarray(s_nan) == np.asarray(s)).all()
    live = real[..., None]
    assert _rel(jnp.where(live, wide(o), 0),
                jnp.where(live, want_o.reshape(o.shape), 0)) < tol
    assert _rel(s, want_s) < tol
    if lengths and 0 in lengths:   # a padding row leaves a zero state
        assert not np.asarray(s[lengths.index(0)]).any()


@pytest.mark.parametrize("H", [1, 3, 4])
def test_kernels_take_any_number_of_heads(H):
    """A grid step takes four heads where four divide the layer's, else two,
    else one (``_heads_a_step``): a layer's own arrays at an odd number of
    heads, prepared operands at four (the engine tests' model has four, the
    tests above two), over chunk edges, a prompt's end inside a chunk and, in
    the layer's call, chunks passed over."""
    assert [kda._heads_a_step(n) for n in (1, 2, 3, 4, 6, 32)] == [
        1, 2, 1, 4, 2, 4]
    lengths = [64, 21]
    if H % 4:
        _prefill_is_the_layer(2, 64, 16, lengths, False, jnp.float32, 1e-5, H)
    else:
        ops = _operands(2, 64, H, 16, H, lengths)
        want_o, want_s = kda.kda_reference(*ops)
        o, s = jax.jit(lambda *a: kda.kda_scan(*a, chunk=16))(*ops)
        real = np.arange(64)[None] < np.asarray(lengths)[:, None]
        real = real[..., None, None]
        assert _rel(jnp.where(real, o, 0), jnp.where(real, want_o, 0)) < 1e-5
        assert _rel(s, want_s) < 1e-5


def test_step_kernel_steps_one_layer_in_place():
    """One position for every slot on ONE layer of the leaf: to float32's
    own rounding the recurrence's step, a slot that is not kept to the bit
    what it was, the other layer untouched."""
    R, H, K = 3, 2, 16
    _, s0 = kda.kda_reference(*_operands(R, 8, H, K, 1))
    q, k, v, g, beta = _operands(R, 1, H, K, 2)
    want_o, want_s = kda.kda_reference(q, k, v, g, beta, s0)
    leaf = jnp.stack([jnp.full_like(s0, 7.0), s0])
    keep = jnp.asarray([True, False, True])
    o, out = jax.jit(lambda leaf, *a: kda.kda_step(leaf, 1, *a),
                     donate_argnums=0)(leaf, q[:, 0], k[:, 0], v[:, 0],
                                       g[:, 0], beta[:, 0], keep)
    assert out.dtype == jnp.float32
    assert _rel(o[keep], want_o[:, 0][keep]) < 1e-6
    assert _rel(out[1][keep], want_s[keep]) < 1e-6
    assert (np.asarray(out[1][1]) == np.asarray(s0[1])).all()
    assert (np.asarray(out[0]) == 7.0).all()


# -- (b) the engine against the reference ---------------------------------------------


@pytest.mark.parametrize("prompt_len,bucket", [
    (1, 16),    # shorter than the taps: one real row of the tail, two zeros
    (3, 16),    # the tail exactly
    (15, 16),   # one short of the bucket: a padded row behind the prompt
    (16, 16),   # a bucket with no padding
    (21, 32),   # the next bucket: two sub-chunks, six pages
])
def test_engine_matches_reference(engine, prompt_len, bucket):
    """Prefill's last-position logits and then ten decode steps through the
    state, the tail and over page boundaries (pages of 4), in a slot that is
    not the first and on pages that are not the first; the latent rows lie in
    as many layers as the model has latent ones."""
    toks = np.random.default_rng(prompt_len).integers(0, VOCAB, prompt_len + 10)
    run = _Run(engine)
    got = run.sequence(2, toks, prompt_len, _pages(5, len(toks)), bucket)
    want = _reference(engine, toks)[prompt_len - 1:]
    assert _rel(got, want) < TOL, _rel(got, want)
    c = run.cache
    state, tail = c["kda"]
    assert state.shape == (3, 3, 4, 16, 16) and state.dtype == jnp.float32
    assert tail.shape == (3, 3, 3, 3 * 64)
    assert c["latent"].shape == (2, 14, PAGE, 128)
    assert set(c.states) == {"latent", "kda"}
    assert np.abs(np.asarray(state)[:, 2]).max(axis=(1, 2, 3)).min() > 0
    used = np.abs(np.asarray(c["latent"], np.float32)).sum(axis=(2, 3)) > 0
    assert used[:, 5:5 + -(-len(toks) // PAGE)].all() and not used[:, 1:5].any()
    load = np.asarray(c.moe_load)     # the last step: one row, top-3 of 8
    assert load.shape == (4, 2) and (load.sum(1) <= 3).all()


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


def test_preempted_request_prefilled_again(engine):
    """Recompute preemption: a request that decoded five tokens is prefilled
    again from prompt + generated into another slot and other pages, and goes
    on as if nothing had happened; meanwhile its old slot decodes garbage."""
    toks = np.random.default_rng(6).integers(0, VOCAB, 6 + 5 + 6)
    want = _reference(engine, toks)
    run = _Run(engine)
    first = run.sequence(0, toks[:11], 6, _pages(1, 11))
    assert _rel(first, want[5:11]) < TOL
    run.active[0] = False
    again = run.sequence(2, toks, 11, _pages(9, len(toks)))
    assert _rel(again, want[10:]) < TOL


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
    assert not np.asarray(run.cache["kda"].state)[:, 1:].any()
    assert not np.asarray(run.cache["kda"].tail)[:, :, 1:].any()
    run.active[0], run.lens[0] = True, 11
    got = [np.asarray(logits[0])] + [run.decode({0: t})[0] for t in toks[11:]]
    assert _rel(np.stack(got), _reference(engine, toks)[10:]) < TOL


def test_bfloat16_engine_is_one_rounding_a_product():
    """The same model with bfloat16 products (weights stay float32 here):
    every product's operands rounded once, the stream, the state and the
    statistics float32, against the float32 reference: 2^-9 a product,
    some forty products deep at deviations of 0.3, reads 5e-2 here."""
    eng = _engine(dict(OVERRIDES, dtype=jnp.bfloat16))
    toks = np.random.default_rng(8).integers(0, VOCAB, 13 + 4)
    got = _Run(eng).sequence(1, toks, 13, _pages(2, len(toks)))
    assert _Run(eng).cache["kda"].state.dtype == jnp.float32
    assert _rel(got, _reference(eng, toks)[12:]) < 1e-1


# -- (c) every part shows in the logits -------------------------------------------------

WRONG = [
    {"without": ("float32_state",)}, {"without": ("beta",)},
    {"without": ("decay",)}, {"without": ("conv",)},
    {"without": ("out_gate",)}, {"without": ("k_norm",)},
    {"rotate_latent": True}, {"latent_scale": 16 ** -0.5},
    {"bias_in_gates": True}, {"first_expert": 2},
]


@pytest.mark.parametrize(
    "wrong", WRONG, ids=lambda w: "-".join(f"{k}={v}" for k, v in w.items()))
def test_wrong_part_fails_the_comparison(engine, wrong):
    toks = np.random.default_rng(7).integers(0, VOCAB, 13 + 6)
    got = _Run(engine).sequence(0, toks, 13, _pages(1, len(toks)))
    assert _rel(got, _reference(engine, toks)[12:]) < TOL
    moved = _rel(got, _reference(engine, toks, **wrong)[12:])
    print(f"{wrong}: the logits move by {moved:.3g} of their norm")
    assert moved > 10 * TOL


def test_four_ranks_and_the_shared_expert_once_are_the_uncut_layer(engine):
    """The share: one expert layer's routed part from each of the four ranks'
    matrices, summed, plus the shared expert counted once, is what a
    reference holding all eight experts gives (the program's routed part
    against rank 0's besides)."""
    rng = np.random.default_rng(4)
    lp = ref.to_reference_params(engine.params["params"], PUBLISHED)["layers"][1]
    m = jnp.asarray(rng.normal(size=(1, 9, 64)), jnp.float32)
    names = ("gate_proj", "up_proj", "down_proj")
    ranks = [{n: lp[n] for n in names}] + [
        {n: jnp.asarray(rng.normal(size=lp[n].shape) * 0.3, jnp.float32)
         for n in names} for _ in range(3)]
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(m, lp["shared_gate_proj"], lp["shared_up_proj"],
                            lp["shared_down_proj"])
        parts = [ref.routed_experts(m, dict(lp, **held),
                                    dict(RCFG, first_expert=2 * r))
                 for r, held in enumerate(ranks)]
        whole = ref.routed_experts(
            m, dict(lp, **{n: jnp.concatenate([held[n] for held in ranks])
                           for n in names}), dict(RCFG, first_expert=0))
    assert _rel(sum(parts) + shared, whole + shared) < 1e-6
    assert all(_rel(part, whole) > 0.1 for part in parts)
    y, load = mr._ffn(m, engine.params["params"]["layer_1"], engine.mcfg,
                      jnp.ones((1, 9), bool), "moe_gmm_prefill")
    assert _rel(y, parts[0] + shared) < 1e-5
    assert int(load.sum()) < 9 * 3            # some assignments fell elsewhere


def test_leading_layer_is_dense_at_its_own_width(engine):
    tree = engine.params["params"]
    assert "moe" not in tree["layer_0"]
    assert tree["layer_0"]["mlp"]["gate_proj"]["kernel"].shape == (64, 96)
    assert all("moe" in tree[f"layer_{i}"] for i in range(1, 5))


# -- (d) decode rows ride a prefill call --------------------------------------------------


def test_decode_rows_ride_a_prefill_call(engine):
    """``prefill`` with ``riders`` is the call and then ``decode_step``: the
    prompt's logits, the step's logits, the latent rows, the states and the
    tails of the slot that decodes and of the slot that is filled again,
    beside a padding row and a slot that is not active (left as it was
    found). The latent mixer runs both sides in one program here."""
    import prefill_rows

    assert mr.rides(engine.mcfg)
    prefill_rows.riders_equal_a_step_after_the_call(
        engine, np.random.default_rng(7), 1e-4)


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


# -- the engine, its counters, the training module, the programs of others ---------------


def test_engine_serves_preempts_and_counts_the_states_it_moves():
    """Requests through ``JaxLLMEngine.step()`` with too few pages for all of
    them: one is preempted and prefilled again, every greedy token is the
    reference's own argmax, and the state counters move with every decode
    step, riding ones too; the latent layers count as a latent model's."""
    eng = _engine()
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
    assert m["kda_step_slots"] == 3 * 3 * m["decode_steps"]
    assert 0 < m["kda_step_live_slots"] <= m["kda_step_slots"]
    assert m["ssd_step_slots"] == 0 and m["shared_kv_live_tokens"] == 0
    assert 0 < m["mla_decode_live_tokens"] <= m["mla_decode_read_tokens"]
    assert m["moe_decode_assignments"] < m["moe_decode_routed_assignments"]
    with pytest.raises(ValueError, match="delta-rule layers of 0 heads"):
        _engine(expect_kda_heads=0)
    with pytest.raises(ValueError, match="export_kv"):
        eng.export_kv("nobody")


def test_engine_counts_the_chunks_the_scan_passes_over():
    """A window of prompts that end in the first, second, third and last
    chunk of their bucket: the engine's two counters hold what ``kda_scan``'s
    grid has a head and layer (a call's ``R x S / CHUNK``) and what of it
    lies wholly behind a prompt's end, as counted by hand from the prompts'
    lengths and buckets; the count itself over calls of other buckets and of
    several rows, a padding row among them."""
    for S, lens, want in ((256, [100], (2, 1)), (256, [129], (2, 0)),
                          (1024, [1], (8, 7)), (512, [257, 384, 0], (12, 6)),
                          (64, [5, 0], (2, 1))):    # one chunk of 64: the row
        assert kda.scan_chunks(S, lens) == want
    eng = _engine(max_num_seqs=2, max_model_len=4 * kda.CHUNK, num_pages=None,
                  prefill_bucket_min=4 * kda.CHUNK)
    rng = np.random.default_rng(4)
    lengths = (100, 200, 300, 500)
    eng.generate([rng.integers(0, VOCAB, n).tolist() for n in lengths],
                 SamplingParams(max_tokens=2), decode_text=False)
    m = eng.metrics
    assert m["preempted"] == 0 and m["prefill_calls"] == len(lengths)
    chunks = sum(eng._prefill_bucket(n) for n in lengths) // kda.CHUNK
    real = sum(-(-n // kda.CHUNK) for n in lengths)      # 1 2 3 4
    assert m["kda_scan_chunks"] == chunks == 16
    assert m["kda_scan_chunks_skipped"] == chunks - real == 6
    assert m["prefill_batch_tokens"] == chunks * kda.CHUNK
    # no call of several rows fits the longest bucket: nothing is being made
    # off this thread when the test ends
    assert not eng._row_shapes.wanted


def test_training_module_is_the_reference():
    """``Transformer`` (``Block`` with the kinds "kda" and "latent", the
    leading dense layer) over a whole sequence against the reference, and
    ``num_params`` against the tree and the adapter's count."""
    # room for every token in every expert: the training side drops none
    cfg = dataclasses.replace(CONFIGS["tiny"], **OVERRIDES, capacity_factor=8.0)
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


def test_adapter_refuses_what_the_program_does_not_express():
    for key, value in (("num_expert_group", 2), ("q_lora_rank", 1536),
                       ("rope_scaling", {"type": "yarn"}),
                       ("mla_use_nope", False)):
        with pytest.raises(ValueError, match=key):
            ref.program_overrides(dict(PUBLISHED, **{key: value}), 64)
    with pytest.raises(ValueError, match="kda_layers"):
        ref.layer_types(dict(PUBLISHED, linear_attn_config=dict(
            PUBLISHED["linear_attn_config"], kda_layers=[1, 3])))
