"""The expert layer: ``ops/moe.py`` against a dense masked loop, the paged
engine against the benchmark's plain OLMoE reference, the training module's
dispatch against ``ops/moe.py``, and the engine's routing counters.

Everything runs in float32 at a small size (hidden 64, 4 heads, 8 experts of
width 32, top-2, 2 layers), where the only differences left between the two
sides are the order of float32 sums: 1e-4 of the logits' norm admits that and
nothing else. The three spoiled references at the bottom show that it does
not admit a missing q/k norm, renormalised router weights or another eps.
"""

import dataclasses
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.architectures import olmoe
from ray_tpu.llm import LLMConfig
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import JaxLLMEngine
from ray_tpu.models.transformer import MoEMLP, TransformerConfig
from ray_tpu.ops import moe
from ray_tpu.ops.moe import expert_layer, route

TOL = 1e-4
D, E, F, K = 64, 8, 32, 2


def _weights(seed, scale=0.1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (D, E)),
            jax.random.normal(ks[1], (E, D, F)) * scale,
            jax.random.normal(ks[2], (E, D, F)) * scale,
            jax.random.normal(ks[3], (E, F, D)) * scale)


def _dense_loop(x, valid, router, w_gate, w_up, w_down, norm, first=0):
    """Every expert on every row, weighted by the row's router weight for it
    (0 where the row did not choose it or is not valid), in float32 whatever
    the inputs are. The matrices are those of experts ``first .. first +
    len(w_gate)`` of the router's outputs: all of them unless told."""
    f32 = lambda a: a.astype(jnp.float32)
    weights, experts = route(x, router, K, norm)
    x = f32(x)
    y = jnp.zeros_like(x)
    for e in range(w_gate.shape[0]):
        w = jnp.where(experts == first + e, weights, 0.0).sum(-1) * valid
        y = y + w[:, None] * ((jax.nn.silu(x @ f32(w_gate[e]))
                               * (x @ f32(w_up[e]))) @ f32(w_down[e]))
    return y


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# -- (a) ops/moe.py against the dense loop ---------------------------------------


@pytest.mark.parametrize("rows,norm", [(5, False), (40, False), (40, True),
                                       (300, False)])
def test_expert_layer_matches_a_dense_masked_loop(rows, norm):
    router, *experts = _weights(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, D))
    valid = jax.random.bernoulli(jax.random.PRNGKey(2), 0.7, (rows,))
    y, load = expert_layer(x, valid, router, *experts, top_k=K,
                           norm_topk_prob=norm)
    assert _rel(y, _dense_loop(x, valid, router, *experts, norm)) < TOL
    # invalid rows contribute nothing and are not counted
    assert not np.asarray(y)[~np.asarray(valid)].any()
    assert int(load.sum()) == int(valid.sum()) * K
    _, chosen = route(x, router, K, norm)
    want = np.bincount(np.asarray(chosen)[np.asarray(valid)].ravel(), minlength=E)
    assert np.array_equal(np.asarray(load), want)


def test_every_row_to_one_expert_and_nothing_is_dropped():
    """A router that sends all 200 rows to experts 3 and 5: a capacity of
    1.25 x 200 x 2 / 8 = 62 would drop two thirds of them; here none is."""
    _, *experts = _weights(3)
    router = jnp.zeros((D, E)).at[:, 3].set(1.0).at[:, 5].set(0.5)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (200, D)))
    valid = jnp.ones(200, bool)
    y, load = expert_layer(x, valid, router, *experts, top_k=K,
                           norm_topk_prob=False)
    assert list(np.asarray(load)) == [0, 0, 0, 200, 0, 200, 0, 0]
    assert _rel(y, _dense_loop(x, valid, router, *experts, False)) < TOL


def test_norm_topk_prob_true_and_false_differ():
    router, *experts = _weights(5)
    router = router * 0.05       # near-uniform: a top-2 of 8 sums to about 0.3
    x = jax.random.normal(jax.random.PRNGKey(6), (24, D))
    valid = jnp.ones(24, bool)
    plain, _ = expert_layer(x, valid, router, *experts, top_k=K,
                            norm_topk_prob=False)
    renorm, _ = expert_layer(x, valid, router, *experts, top_k=K,
                             norm_topk_prob=True)
    assert _rel(plain, renorm) > 0.1
    w, _ = route(x, router, K, True)
    assert np.allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)


# -- (a2) at prefill sizes the way back sums choice by choice --------------------

LEAST = moe._BY_CHOICE_MIN // K      # rows of the smallest call that does


def _case(name):
    """(x, valid, router, held) of a named call; the experts are seed 0's."""
    rows, routed, held = LEAST + 88, E, None
    if name == "half_elsewhere":
        routed, held = 2 * E, (E // 2, E)
    elif name == "seven_eighths_elsewhere":
        routed, held = 8 * E, (3 * E, E)
    elif name == "one_row_under":
        rows = LEAST - 1
    elif name == "at_the_least":
        rows = LEAST
    x = jax.random.normal(jax.random.PRNGKey(11), (rows, D))
    valid = jax.random.bernoulli(jax.random.PRNGKey(12), 0.7, (rows,))
    router = jax.random.normal(jax.random.PRNGKey(13), (D, routed))
    if name == "padding_and_an_inactive_slot":
        # a bucket's padding behind the prompt and every row of one slot
        valid = (jnp.arange(rows) < rows - 70) & ~(
            (jnp.arange(rows) >= 128) & (jnp.arange(rows) < 256))
    elif name == "one_expert":
        router = jnp.zeros((D, E)).at[:, 3].set(1.0).at[:, 5].set(0.5)
        x, valid = jnp.abs(x), jnp.ones(rows, bool)
    elif name == "nothing_valid":
        valid = jnp.zeros(rows, bool)
    return x, valid, router, held


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, TOL), (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", [
    "all_here", "half_elsewhere", "seven_eighths_elsewhere",
    "padding_and_an_inactive_slot", "one_expert", "nothing_valid",
    "one_row_under", "at_the_least"])
def test_prefill_sizes_match_a_dense_masked_loop(name, dtype, tol):
    x, valid, router, held = _case(name)
    x = x.astype(dtype)
    experts = [w.astype(dtype) for w in _weights(0)[1:]]
    assert (x.shape[0] * K >= moe._BY_CHOICE_MIN) == (name != "one_row_under")
    y, load = expert_layer(x, valid, router, *experts, top_k=K,
                           norm_topk_prob=False, held=held)
    assert y.dtype == dtype and y.shape == x.shape
    first = held[0] if held else 0
    want = _dense_loop(x, valid, router, *experts, False, first=first)
    _, chosen = route(x, router, K, False)
    chosen = np.asarray(chosen)[np.asarray(valid)].ravel() - first
    here = chosen[(chosen >= 0) & (chosen < E)]
    assert np.array_equal(np.asarray(load), np.bincount(here, minlength=E))
    assert not np.asarray(y, np.float32)[~np.asarray(valid)].any()
    if name == "nothing_valid":
        assert not np.asarray(y, np.float32).any() and not int(load.sum())
        return
    if name == "one_expert":
        assert list(np.asarray(load)) == [0, 0, 0, len(x), 0, len(x), 0, 0]
    if held:   # the share of the routed choices that fell on this rank
        share = len(here) / len(chosen)
        assert abs(share - E / router.shape[1]) < 0.05
    assert _rel(y.astype(jnp.float32), want) < tol


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 2e-3)],
                         ids=["float32", "bfloat16"])
def test_the_two_ways_back_agree_to_rounding(monkeypatch, dtype, tol):
    """The same call summed the decode step's way (one gather of float32
    ``[T, top_k, D]``) and the prefill call's (choice by choice): the same
    terms in float32, so they differ by the order of a row's additions and
    by whether a compiler contracts a product and a sum, and in nothing the
    activations' last bit does not cover."""
    x, valid, router, held = _case("half_elsewhere")
    experts = [w.astype(dtype) for w in _weights(0)[1:]]
    args = (x.astype(dtype), valid, router, *experts)
    new, load = expert_layer(*args, top_k=K, norm_topk_prob=True, held=held)
    monkeypatch.setattr(moe, "_BY_CHOICE_MIN", 1 << 30)
    old, load_old = expert_layer(*args, top_k=K, norm_topk_prob=True, held=held)
    assert np.array_equal(np.asarray(load), np.asarray(load_old))
    assert _rel(new.astype(jnp.float32), old.astype(jnp.float32)) < tol


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_row_reads_the_same_alone_and_beside_other_rows(dtype):
    """Rows 100-139 of a call whose other rows are all padding, and of the
    same call with every row real: bit for bit the same (the equalities
    between a request served alone and beside others rest on it)."""
    x, _, router, held = _case("half_elsewhere")
    experts = [w.astype(dtype) for w in _weights(0)[1:]]
    mine = (jnp.arange(len(x)) >= 100) & (jnp.arange(len(x)) < 140)
    kw = dict(top_k=K, norm_topk_prob=True, held=held)
    alone, _ = expert_layer(x.astype(dtype), mine, router, *experts, **kw)
    beside, _ = expert_layer(x.astype(dtype), jnp.ones(len(x), bool), router,
                             *experts, **kw)
    alone, beside = (np.asarray(a, np.float32) for a in (alone, beside))
    assert alone[100:140].any()
    assert np.array_equal(alone[100:140], beside[100:140])


def _lowered(rows, top_k, experts=16):
    """StableHLO of ``expert_layer`` over bfloat16 rows, and the sizes of its
    rank-3 float32 values."""
    f = lambda *a: expert_layer(*a, top_k=top_k, norm_topk_prob=False)
    sds = jax.ShapeDtypeStruct
    text = jax.jit(f).lower(
        sds((rows, D), jnp.bfloat16), sds((rows,), jnp.bool_),
        sds((D, experts), jnp.float32), sds((experts, D, F), jnp.bfloat16),
        sds((experts, D, F), jnp.bfloat16),
        sds((experts, F, D), jnp.bfloat16)).as_text()
    sizes = {tuple(int(n) for n in dims) for dims in
             re.findall(r"tensor<(\d+)x(\d+)x(\d+)xf32>", text)}
    return text, sizes


@pytest.mark.parametrize("rows,top_k", [(16, 8), (32, 6), (40, 10), (128, 4)],
                         ids=["128", "192", "400", "512"])
def test_a_decode_steps_assignments_go_back_by_one_gather(rows, top_k):
    """The sparse serve cells' decode steps (16 slots x top-8, 32 x 6, 40 x
    10, 128 x 4; 32 x 4 is the first again): the shape rule leaves them the
    ``[T, top_k, D]`` float32 gather and sum they had."""
    assert rows * top_k < moe._BY_CHOICE_MIN
    _, sizes = _lowered(rows, top_k)
    assert (rows, top_k, D) in sizes


@pytest.mark.parametrize("rows,top_k", [(128, 8), (512, 2), (296, 10)],
                         ids=["1024", "1024_top2", "2960"])
def test_a_prefill_calls_assignments_hold_no_float32_row_a_choice(rows, top_k):
    """At and over the least size no float32 value has a row for every
    (token, choice): the rows come back in the activations' type, ``[top_k,
    T, D]``, and the float32 sum is ``[T, D]``."""
    assert rows * top_k >= moe._BY_CHOICE_MIN
    text, sizes = _lowered(rows, top_k)
    assert not [s for s in sizes if s[0] * s[1] * s[2] >= rows * top_k * D]
    assert f"tensor<{top_k}x{rows}x{D}xbf16>" in text
    assert f"tensor<{rows}x{D}xf32>" in text


# -- (c) the training module at a capacity that drops nothing ------------------


@pytest.mark.parametrize("norm", [True, False])
def test_moemlp_without_drops_agrees_with_ops_moe(norm):
    cfg = TransformerConfig(
        vocab_size=64, d_model=D, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=F,
        n_experts=E, experts_per_token=K, norm_topk_prob=norm,
        capacity_factor=float(E), dtype=jnp.float32, remat=False)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 12, D))
    layer = MoEMLP(cfg)
    variables = layer.init(jax.random.PRNGKey(8), x)
    p = jax.tree_util.tree_map(
        lambda a: a * 5.0, nn.meta.unbox(variables["params"]))
    got = layer.apply({"params": p}, x, mutable=["losses"])[0]
    want, _ = expert_layer(
        x.reshape(-1, D), jnp.ones(24, bool), p["router"]["kernel"],
        p["gate_proj"], p["up_proj"], p["down_proj"], top_k=K,
        norm_topk_prob=norm)
    assert _rel(got.reshape(-1, D), want) < TOL


# -- (b), (d) the engine ---------------------------------------------------------

OVERRIDES = dict(vocab_size=256, d_model=D, n_layers=2, n_heads=4, n_kv_heads=4,
                 d_ff=F, n_experts=E, experts_per_token=K, norm_topk_prob=False,
                 qk_norm=True, norm_eps=1e-5, dtype=jnp.float32,
                 max_seq_len=64, remat=False)
# the same widths under the published key names, for the reference
PUBLISHED = {"num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 4, "head_dim": 16, "rope_theta": 10000.0,
             "rms_norm_eps": 1e-5, "num_experts_per_tok": K,
             "norm_topk_prob": False, "tie_word_embeddings": False,
             "model_type": "olmoe"}


def _engine(**overrides):
    return JaxLLMEngine(LLMConfig(
        model_id="tiny", model_overrides=dict(OVERRIDES, **overrides),
        engine_config=EngineConfig(max_num_seqs=3, max_model_len=64,
                                   page_size=8, prefill_bucket_min=16,
                                   expect_experts=E)))


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    # random weights are small (0.02): the embedding is scaled down further,
    # to where 1e-5 against 1e-6 under a square root shows, and the experts
    # up, to where the expert layer carries the logits
    p = eng.params["params"]
    p["embed"] = p["embed"] * 0.25
    for i in range(2):
        moe = p[f"layer_{i}"]["moe"]
        for name in ("gate_proj", "up_proj", "down_proj"):
            moe[name] = moe[name] * 20.0
        moe["router"]["kernel"] = moe["router"]["kernel"] * 50.0
    return eng


def _engine_logits(eng, seqs, prompt_lens, steps):
    """Prefill then ``steps`` teacher-forced decode steps through the paged
    cache, with the engine's own programs: slot i holds ``seqs[i]``; the last
    slot stays inactive. Returns {slot: [1 + steps, vocab] logits}."""
    e, cfg = eng.ecfg, eng.mcfg
    B, MP = e.max_num_seqs, e.pages_per_seq
    tables = np.zeros((B, MP), np.int32)
    batch = np.zeros((B, 16), np.int32)
    lens = np.zeros(B, np.int32)
    active = np.zeros(B, bool)
    page = 1
    for s, (toks, n) in enumerate(zip(seqs, prompt_lens)):
        need = -(-len(toks) // e.page_size)
        tables[s, :need] = np.arange(page, page + need)
        page += need
        batch[s, :n] = toks[:n]
        lens[s] = n
        active[s] = True
    cache = mr.init_cache(cfg, e.num_pages, e.page_size)
    logits, cache = mr.prefill(eng.params, cfg, cache, jnp.asarray(batch),
                               jnp.asarray(lens), jnp.asarray(tables))
    got = {s: [np.asarray(logits[s])] for s in range(len(seqs))}
    loads = [np.asarray(cache.moe_load)]
    last = np.zeros(B, np.int32)
    seq_lens = np.zeros(B, np.int32)
    for i in range(steps):
        for s, (toks, n) in enumerate(zip(seqs, prompt_lens)):
            last[s] = toks[n + i]
            seq_lens[s] = n + i
        logits, cache = mr.decode_step(
            eng.params, cfg, cache, jnp.asarray(last), jnp.asarray(seq_lens),
            jnp.asarray(tables), jnp.asarray(active))
        loads.append(np.asarray(cache.moe_load))
        for s in got:
            got[s].append(np.asarray(logits[s]))
    return {s: np.stack(v) for s, v in got.items()}, loads


def _reference_logits(eng, toks, n, **spoil):
    rcfg = dict(olmoe.reference_cfg(PUBLISHED), **spoil)
    full = olmoe.forward(
        olmoe.to_reference_params(eng.params["params"], PUBLISHED),
        jnp.asarray(toks)[None], rcfg)[0]
    return np.asarray(full[n - 1:])


STEPS = 8
LENS = (5, 11)


@pytest.fixture(scope="module")
def run(engine):
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 256, n + STEPS, dtype=np.int32) for n in LENS]
    got, loads = _engine_logits(engine, seqs, LENS, STEPS)
    return seqs, got, loads


def _worst(engine, run, **spoil):
    seqs, got, _ = run
    return max(_rel(got[s], _reference_logits(engine, seqs[s], n, **spoil))
               for s, n in enumerate(LENS))


def test_engine_agrees_with_the_plain_reference(engine, run):
    """Prefill then eight decode steps through the paged cache, two slots of
    different lengths and one inactive: logits, every position."""
    assert _worst(engine, run) < TOL


@pytest.mark.parametrize("spoil", [
    {"qk_norm": False},            # the q/k norm left out
    {"norm_topk_prob": True},      # top-k weights renormalised
    {"rms_norm_eps": 1e-6},        # the dense models' eps
], ids=lambda s: next(iter(s)))
def test_a_spoiled_reference_fails(engine, run, spoil):
    """A check of the check: each of these is one line of the model."""
    assert _worst(engine, run, **spoil) > 10 * TOL


def test_the_programs_report_what_routing_did(run):
    """``Cache.moe_load``: per expert layer, the real rows of each expert;
    padding and the inactive slot are not in it."""
    _, _, loads = run
    assert loads[0].shape == (2, E)
    assert (loads[0].sum(axis=1) == sum(LENS) * K).all()       # prefill
    for load in loads[1:]:                                      # decode
        assert (load.sum(axis=1) == len(LENS) * K).all()
        assert load.max() <= len(LENS)


# -- (d) counters -----------------------------------------------------------------


def test_moe_decode_counters_after_a_known_number_of_steps():
    eng = _engine()
    before = dict(eng.metrics)
    assert all(before[k] == 0 for k in before if k.startswith("moe_"))
    eng.generate([[5, 6, 7, 8], [9, 10, 11]],
                 SamplingParams(max_tokens=6, stop_token_ids=()),
                 decode_text=False)
    m = eng.metrics
    layers = 2
    assert m["moe_decode_layer_steps"] == m["decode_steps"] * layers > 0
    # a decode step emits one token an active slot and routes it top-k in
    # every expert layer; the first token of a request comes from prefill
    decoded = m["generated_tokens"] - m["admitted"]
    assert m["moe_decode_assignments"] == decoded * K * layers
    assert m["moe_decode_layer_steps"] <= m["moe_decode_max_load"] \
        <= m["moe_decode_experts_touched"] <= m["moe_decode_assignments"]
    assert eng._experts_attr["experts"] <= 2 * K


def test_a_dense_engine_counts_no_experts():
    eng = JaxLLMEngine(LLMConfig(
        model_id="tiny", model_overrides={"attention_impl": "xla"},
        engine_config=EngineConfig(max_num_seqs=2, max_model_len=64,
                                   page_size=8, prefill_bucket_min=16)))
    assert eng.cache.moe_load is None
    eng.generate([[1, 2, 3]], SamplingParams(max_tokens=4), decode_text=False)
    assert eng.metrics["decode_steps"] > 0
    assert all(v == 0 for k, v in eng.metrics.items() if k.startswith("moe_"))
    assert eng._experts_attr == {}
    # a deployment that expects experts refuses a model without them
    with pytest.raises(ValueError, match="expects 8 experts"):
        JaxLLMEngine(LLMConfig(model_id="tiny", engine_config=EngineConfig(
            max_num_seqs=2, max_model_len=64, page_size=8, expect_experts=8)))


def test_config_defaults_are_todays_behaviour():
    cfg = TransformerConfig()
    assert (cfg.norm_eps, cfg.qk_norm, cfg.norm_topk_prob) == (1e-6, False, True)
    assert dataclasses.replace(cfg, norm_eps=1e-5).norm_eps == 1e-5
