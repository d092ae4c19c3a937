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
from ray_tpu.ops.moe import expert_layer, route

TOL = 1e-4
D, E, F, K = 64, 8, 32, 2


def _weights(seed, scale=0.1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (D, E)),
            jax.random.normal(ks[1], (E, D, F)) * scale,
            jax.random.normal(ks[2], (E, D, F)) * scale,
            jax.random.normal(ks[3], (E, F, D)) * scale)


def _dense_loop(x, valid, router, w_gate, w_up, w_down, norm):
    """Every expert on every row, weighted by the row's router weight for it
    (0 where the row did not choose it or is not valid)."""
    weights, experts = route(x, router, K, norm)
    y = jnp.zeros_like(x)
    for e in range(router.shape[1]):
        w = jnp.where(experts == e, weights, 0.0).sum(-1) * valid
        y = y + w[:, None] * (
            (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e])
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
