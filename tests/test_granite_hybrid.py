"""Granite-4.0-H-Small (``granitemoehybrid``): Mamba-2 mixers in nine layers
of ten, one attention layer without positions under a published softmax
scale, a softmax router over experts of which one rank of two holds half, a
shared MLP, and the family's four multipliers. The paged engine (a matrix
state and a convolution tail a slot for each Mamba-2 layer beside one layer
of pages, ONE cache manager shared with cells 7-9; the recurrence through
``ops/ssd.py``, in interpret mode here) against the benchmark's plain
reference ``benchmarks/architectures/granitemoehybrid.py``.

The model runs in float32 at a small size with the published ratios (two
groups of ``m m a m``; hidden 64, 8 Mamba heads of 16 with a state of 16, 4
taps, 4 query and 2 key heads of 16, 8 experts top-3 of which 4 are held,
pages of 4). The chunked scan multiplies in bfloat16 as on the chip, so the
two sides differ by that rounding: 5e-3 of the logits' norm admits it, and
the spoiled references (a dropped D, dt_bias, convolution bias, gate, state
or multiplier) each move the logits by more than six times that.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.architectures import granitemoehybrid as ref
from ray_tpu.llm import LLMConfig
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import JaxLLMEngine
from ray_tpu.models.transformer import CONFIGS, Transformer
from ray_tpu.ops import ssd

TOL = 5e-3
VOCAB, PAGE, BUCKET = 128, 4, 16
TYPES = ["mamba", "mamba", "attention", "mamba"] * 2
# the small model under the published key names
PUBLISHED = dict(
    name="granite-tiny", model_type="granitemoehybrid", hidden_act="silu",
    attention_bias=False, mamba_proj_bias=False, mamba_conv_bias=True,
    mamba_n_groups=1, normalization_function="rmsnorm",
    position_embedding_type="nope", rope_scaling=None, rope_theta=10000,
    tie_word_embeddings=True, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=32, shared_intermediate_size=64,
    vocab_size=VOCAB, num_hidden_layers=8, layer_types=TYPES,
    num_local_experts=4, num_experts_per_tok=3,
    expert_parallel={"routed_experts": 8, "ranks": 2, "rank": 0},
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_chunk_size=256, rms_norm_eps=1e-5,
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.125, logits_scaling=16, torch_dtype="float32",
    initializer={"attention": 0.3, "mlp": 0.2, "experts": 0.3, "mamba": 0.15,
                 "embedding": 0.1})
OVERRIDES = dict(ref.program_overrides(PUBLISHED, 64), dtype=jnp.float32,
                 remat=False)
RCFG = ref.reference_cfg(PUBLISHED)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _engine(**engine):
    geometry = dict(max_num_seqs=3, max_model_len=64, page_size=PAGE,
                    prefill_bucket_min=BUCKET, expect_experts=4,
                    expect_routed_experts=8, expect_state_layers=6,
                    expect_ssm_heads=8,
                    # too few for three requests at once: one is preempted
                    num_pages=14)
    return JaxLLMEngine(LLMConfig(
        model_id="tiny", model_overrides=OVERRIDES,
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


def _operands(R, S, H, P, N, seed, lengths=None):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = jax.nn.softplus(jax.random.normal(k[0], (R, S, H)) - 3)
    if lengths is not None:
        dt = jnp.where(jnp.arange(S)[None, :, None]
                       < jnp.asarray(lengths)[:, None, None], dt, 0.0)
    bf16 = lambda key, *shape: jax.random.normal(   # noqa: E731
        key, shape).astype(jnp.bfloat16)
    return (dt, bf16(k[1], R, S, H * P), bf16(k[2], R, S, N),
            bf16(k[3], R, S, N), -jnp.exp(jax.random.uniform(k[4], (H,)) * 2.7))


@pytest.mark.parametrize("R,S,chunk,lengths", [
    (1, 32, 8, None),            # four chunks: the state carried over three edges
    (2, 32, 8, [32, 13]),        # padding behind a prompt, in the middle of a chunk
    (3, 24, 8, [2, 24, 0]),      # a prompt shorter than the taps, a padding row
    (1, 16, 256, [9]),           # a bucket shorter than the chunk: one chunk
], ids=["edges", "padding", "short-and-empty", "one-chunk"])
def test_scan_kernel_matches_the_recurrence(R, S, chunk, lengths):
    """y at every real position and the state after ``lengths - 1``: the
    chunked form multiplies in bfloat16, the recurrence in float32."""
    ops = _operands(R, S, 4, 8, 16, S, lengths)
    want_y, want_s = ssd.ssd_reference(*ops)
    y, s = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=chunk))(*ops)
    real = np.arange(S)[None] < np.asarray(lengths or [S] * R)[:, None]
    assert _rel(jnp.where(real[..., None], y, 0),
                jnp.where(real[..., None], want_y, 0)) < 4e-3
    assert _rel(s, want_s) < 4e-3
    if lengths and 0 in lengths:   # a padding row leaves a zero state
        assert not np.asarray(s[lengths.index(0)]).any()


def test_step_kernel_steps_one_layer_in_place():
    """One position for every slot on ONE layer of the leaf: to float32's
    own rounding the recurrence's step, a slot that is not kept to the bit
    what it was, the other layer untouched."""
    R, H, P, N = 3, 4, 8, 16
    _, s0 = ssd.ssd_reference(*_operands(R, 8, H, P, N, 1))
    dt, x, Bm, Cm, A = _operands(R, 1, H, P, N, 2)
    want_y, want_s = ssd.ssd_reference(dt, x, Bm, Cm, A, s0)
    leaf = jnp.stack([jnp.full_like(s0, 7.0), s0])
    keep = jnp.asarray([True, False, True])
    y, out = jax.jit(lambda leaf, *a: ssd.ssd_step(leaf, 1, *a),
                     donate_argnums=0)(leaf, dt[:, 0], x[:, 0], Bm[:, 0],
                                       Cm[:, 0], A, keep)
    assert _rel(y[keep], want_y[:, 0][keep]) < 1e-6
    assert _rel(out[1][keep], want_s[keep]) < 1e-6
    assert (np.asarray(out[1][1]) == np.asarray(s0[1])).all()
    assert (np.asarray(out[0]) == 7.0).all()


# -- (b) the engine against the reference ---------------------------------------------


@pytest.mark.parametrize("prompt_len,bucket", [
    (1, 16),    # shorter than the taps: one real row of the tail, two zeros
    (3, 16),    # the tail exactly
    (15, 16),   # one short of the bucket: a padded row behind the prompt
    (16, 16),   # a bucket with no padding
    (21, 32),   # the next bucket, six pages
])
def test_engine_matches_reference(engine, prompt_len, bucket):
    """Prefill's last-position logits and then ten decode steps through the
    state, the tail and over page boundaries (pages of 4), in a slot that is
    not the first and on pages that are not the first."""
    toks = np.random.default_rng(prompt_len).integers(0, VOCAB, prompt_len + 10)
    run = _Run(engine)
    got = run.sequence(2, toks, prompt_len, _pages(5, len(toks)), bucket)
    want = _reference(engine, toks)[prompt_len - 1:]
    assert _rel(got, want) < TOL, _rel(got, want)
    c = run.cache
    state, tail = c["mamba2"]
    assert state.shape == (6, 3, 16, 128) and state.dtype == jnp.float32
    assert tail.shape == (6, 3, 3, 128 + 2 * 16) and "window" not in c
    assert c["full"].shape[0] == 2
    assert np.abs(np.asarray(state)[:, 2]).max(axis=(1, 2)).min() > 0
    load = np.asarray(c.moe_load)     # the last step: one row, top-3 of 8
    assert load.shape == (8, 4) and (load.sum(1) <= 3).all()


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
    assert not np.asarray(run.cache["mamba2"].state)[:, 1:].any()
    assert not np.asarray(run.cache["mamba2"].tail)[:, :, 1:].any()
    run.active[0], run.lens[0] = True, 11
    got = [np.asarray(logits[0])] + [run.decode({0: t})[0] for t in toks[11:]]
    assert _rel(np.stack(got), _reference(engine, toks)[10:]) < TOL


# -- (c) every part shows in the logits -------------------------------------------------


@pytest.mark.parametrize("wrong", [
    {"without": ("D",)}, {"without": ("dt_bias",)},
    {"without": ("conv_bias",)}, {"without": ("gate",)},
    {"without": ("state",)}, {"embedding_multiplier": 1.0},
    {"residual_multiplier": 1.0}, {"attention_multiplier": 0.25},
    {"logits_scaling": 1.0}, {"first_expert": 4},
], ids=lambda w: "-".join(f"{k}={v}" for k, v in w.items()))
def test_wrong_part_fails_the_comparison(engine, wrong):
    toks = np.random.default_rng(7).integers(0, VOCAB, 13 + 6)
    got = _Run(engine).sequence(0, toks, 13, _pages(1, len(toks)))
    assert _rel(got, _reference(engine, toks)[12:]) < TOL
    assert _rel(got, _reference(engine, toks, **wrong)[12:]) > 6 * TOL


def test_two_ranks_and_the_shared_mlp_once_are_the_uncut_layer(engine):
    """The share: one expert layer's routed part from rank 0's matrices and
    from rank 1's, summed, plus the shared MLP counted once, is what a
    reference holding all eight experts gives (the program's routed part
    against rank 0's besides)."""
    rng = np.random.default_rng(4)
    lp = ref.to_reference_params(engine.params["params"], PUBLISHED)["layers"][0]
    m = jnp.asarray(rng.normal(size=(1, 9, 64)), jnp.float32)
    other = {n: jnp.asarray(rng.normal(size=lp[n].shape) * 0.3, jnp.float32)
             for n in ("gate_proj", "up_proj", "down_proj")}
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(m, lp["shared_gate_proj"], lp["shared_up_proj"],
                            lp["shared_down_proj"])
        rank0 = ref.routed_experts(m, lp, dict(RCFG, first_expert=0))
        rank1 = ref.routed_experts(m, dict(lp, **other),
                                   dict(RCFG, first_expert=4))
        whole = ref.routed_experts(
            m, dict(lp, **{n: jnp.concatenate([lp[n], other[n]])
                           for n in other}), dict(RCFG, first_expert=0))
    assert _rel(rank0 + rank1 + shared, whole + shared) < 1e-6
    assert _rel(rank0, whole) > 0.1 and _rel(rank1, whole) > 0.1
    y, load = mr._ffn(m, engine.params["params"]["layer_0"], engine.mcfg,
                      jnp.ones((1, 9), bool), "moe_gmm_prefill")
    assert _rel(y, rank0 + shared) < 1e-5
    assert int(load.sum()) < 9 * 3            # some assignments fell elsewhere


# -- (d) decode rows ride a prefill call --------------------------------------------------


def test_decode_rows_ride_a_prefill_call(engine):
    """``prefill`` with ``riders`` is the call and then ``decode_step``: the
    prompt's logits, the step's logits, the pages, the states and the tails
    of the slot that decodes and of the slot that is filled again, beside a
    padding row and a slot that is not active (left as it was found)."""
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
    step, riding ones too."""
    eng = _engine()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (11, 2, 19)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=9), decode_text=False)
    for prompt, out in zip(prompts, outs):
        assert len(out.token_ids) == 9
        want = _reference(eng, prompt + out.token_ids)[len(prompt) - 1:-1]
        top = np.sort(want, axis=-1)
        sure = top[:, -1] - top[:, -2] > 1e-2     # bfloat16 products in the scan
        assert (np.asarray(out.token_ids) == np.argmax(want, axis=-1))[sure].all()
    m = eng.metrics
    assert m["preempted"] >= 1
    assert m["ssd_step_slots"] == 6 * 3 * m["decode_steps"]
    assert 0 < m["ssd_step_live_slots"] <= m["ssd_step_slots"]
    assert m["moe_decode_assignments"] < m["moe_decode_routed_assignments"]
    with pytest.raises(ValueError, match="Mamba-2 layers of 0 heads"):
        _engine(expect_ssm_heads=0)


def test_training_module_is_the_reference():
    """``Transformer`` (``Block`` with the kind "mamba2", the multipliers)
    over a whole sequence against the reference, and ``num_params`` against
    the tree and the adapter's count."""
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


@pytest.mark.parametrize("model", ["afmoe", "lfm2"])
def test_neutral_multipliers_leave_no_operation(model):
    """At 1.0 / 0 the residual, attention and logit multipliers trace
    nothing: the decode step of cell 8's and cell 9's tiny models holds
    exactly the multiplications it held without the fields, and with every
    multiplier set one more for each place that reads one."""
    import test_afmoe
    import test_lfm2

    eng = {"afmoe": test_afmoe._engine, "lfm2": test_lfm2._engine}[model]()
    cfg, e = eng.mcfg, eng.ecfg
    assert (cfg.residual_scale, cfg.attn_scale, cfg.logit_scale) == (1.0, 0.0, 1.0)
    B = e.max_num_seqs
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
    rows = (i32(B), i32(B), i32(B, e.pages_per_seq),
            jax.ShapeDtypeStruct((B,), jnp.bool_))

    def multiplies(cfg):
        return mr.decode_step.lower(eng.params, cfg, eng.cache, *rows
                                    ).as_text().count("stablehlo.multiply")

    attention = sum(k != "conv" for k in cfg.layer_kinds)
    scaled = dataclasses.replace(cfg, residual_scale=0.5, attn_scale=0.1,
                                 logit_scale=0.5)
    assert multiplies(scaled) - multiplies(cfg) \
        == 2 * cfg.n_layers + attention + 1
