"""A decoder with latent attention (MLA), a sigmoid router with a selection
bias, shared experts and a leading dense layer: the paged engine (expanded
prefill, absorbed decode through ``ops/mla.py``), the training module and the
two kernels against the benchmark's plain reference
``benchmarks/architectures/deepseek_v3.py``, and what the benchmark's files
say about the model (Moonlight-16B-A3B) against counts made by hand.

The model runs in float32 at a small size (hidden 64, 4 heads of 16 + 8 over a
32-wide latent, values 16 wide, 8 experts of width 32, top-2, 2 shared, a dense
layer of 96 and two sparse ones), where the only differences left between the
two sides are the order of float32 sums: 1e-4 of the logits' norm admits that
and nothing else, as the six spoiled references show.
"""

import dataclasses
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import prefill_rows
from benchmarks.architectures import deepseek_v3 as ref
from benchmarks.registry import REPO, Cell
from ray_tpu.llm import LLMConfig
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import JaxLLMEngine
from ray_tpu.llm.kinds import attention
from ray_tpu.models.transformer import Transformer, TransformerConfig
from ray_tpu.ops.attention import flash_attention_fwd, reference_attention
from ray_tpu.ops.mla import live_pages, mla_decode
from ray_tpu.ops.moe import route

TOL = 1e-4
D, H, R, NOPE, ROPE, DV = 64, 4, 32, 16, 8, 16
E, F, K, SHARED, DENSE = 8, 32, 2, 2, 96
LAYERS, VOCAB, SCALING = 3, 256, 2.446

OVERRIDES = dict(
    vocab_size=VOCAB, d_model=D, n_layers=LAYERS, n_heads=H, n_kv_heads=H,
    d_ff=F, d_ff_dense=DENSE, first_k_dense=1, n_experts=E,
    experts_per_token=K, n_shared_experts=SHARED, norm_topk_prob=True,
    router_kind="sigmoid", routed_scaling_factor=SCALING, norm_eps=1e-5,
    kv_latent_rank=R, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
    v_head_dim=DV, rope_theta=50000.0, dtype=jnp.float32, max_seq_len=64,
    remat=False)
# the same widths under the published key names, for the reference
PUBLISHED = {
    "num_hidden_layers": LAYERS, "num_attention_heads": H, "kv_lora_rank": R,
    "qk_nope_head_dim": NOPE, "qk_rope_head_dim": ROPE, "v_head_dim": DV,
    "rope_theta": 50000.0, "rms_norm_eps": 1e-5, "num_experts_per_tok": K,
    "norm_topk_prob": True, "routed_scaling_factor": SCALING}


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _engine(**overrides):
    return JaxLLMEngine(LLMConfig(
        model_id="tiny", model_overrides=dict(OVERRIDES, **overrides),
        engine_config=EngineConfig(max_num_seqs=3, max_model_len=64,
                                   page_size=8, prefill_bucket_min=16,
                                   expect_experts=E, expect_latent_rank=R)))


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    # random weights are small (0.02): the embedding is scaled down, to where
    # an eps shows under the square root, and the MLPs and the attention
    # projections up, to where each carries the logits
    p = eng.params["params"]
    p["embed"] = p["embed"] * 0.25
    for i in range(LAYERS):
        lp = p[f"layer_{i}"]
        for name in ("q_proj", "kv_a_proj", "kv_b_proj", "o_proj"):
            lp["attn"][name]["kernel"] = lp["attn"][name]["kernel"] * 8.0
        if "moe" not in lp:
            for name in ("gate_proj", "up_proj", "down_proj"):
                lp["mlp"][name]["kernel"] = lp["mlp"][name]["kernel"] * 20.0
            continue
        moe = lp["moe"]
        for name in ("gate_proj", "up_proj", "down_proj"):
            moe[name] = moe[name] * 20.0
            moe["shared"][name]["kernel"] = moe["shared"][name]["kernel"] * 20.0
        moe["router"]["kernel"] = moe["router"]["kernel"] * 50.0
    return eng


def _engine_logits(eng, seqs, prompt_lens, steps):
    """Prefill then ``steps`` teacher-forced decode steps through the latent
    pages, with the engine's own programs: slot i holds ``seqs[i]``; the last
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
    assert prefill_rows.held(cache) == {"latent", "moe_load"}
    logits, cache = mr.prefill(eng.params, cfg, cache, jnp.asarray(batch),
                               jnp.asarray(lens), jnp.asarray(tables))
    got = {s: [np.asarray(logits[s])] for s in range(len(seqs))}
    last = np.zeros(B, np.int32)
    seq_lens = np.zeros(B, np.int32)
    for i in range(steps):
        for s, (toks, n) in enumerate(zip(seqs, prompt_lens)):
            last[s] = toks[n + i]
            seq_lens[s] = n + i
        logits, cache = mr.decode_step(
            eng.params, cfg, cache, jnp.asarray(last), jnp.asarray(seq_lens),
            jnp.asarray(tables), jnp.asarray(active))
        for s in got:
            got[s].append(np.asarray(logits[s]))
    return {s: np.stack(v) for s, v in got.items()}, cache


# each is one line of the model, taken out of the REFERENCE's parameters or
# keys (the reference itself has no switch for them)
def _no_bias(p, rcfg):
    for lp in p["layers"][1:]:
        lp["e_score_correction_bias"] = lp["e_score_correction_bias"] * 0.0


def _no_scaling_factor(p, rcfg):
    rcfg["routed_scaling_factor"] = 1.0


def _no_shared_expert(p, rcfg):
    for lp in p["layers"][1:]:
        lp["shared_down_proj"] = lp["shared_down_proj"] * 0.0


def _scale_of_the_nope_part_alone(p, rcfg):
    # scores / sqrt(nope) in place of / sqrt(nope + rope): 1/sqrt(128) for
    # the published 1/sqrt(192)
    for lp in p["layers"]:
        lp["q_proj"] = lp["q_proj"] * ((NOPE + ROPE) / NOPE) ** 0.5


def _dense_layer_taken_as_sparse(p, rcfg):
    sparse = {k: v for k, v in p["layers"][1].items()
              if k not in p["layers"][0]
              or k in ("gate_proj", "up_proj", "down_proj")}
    p["layers"][0] = {**p["layers"][0], **sparse}


def _latent_norm_scale_tripled(p, rcfg):
    for lp in p["layers"]:
        lp["kv_a_layernorm"] = lp["kv_a_layernorm"] * 3.0


SPOILS = [_no_bias, _no_scaling_factor, _no_shared_expert,
          _scale_of_the_nope_part_alone, _dense_layer_taken_as_sparse,
          _latent_norm_scale_tripled]


def _reference_logits(params, toks, n, spoil=None):
    rcfg = ref.reference_cfg(PUBLISHED)
    p = ref.to_reference_params(params["params"], PUBLISHED)
    if spoil is not None:
        spoil(p, rcfg)
    full = ref.forward(p, jnp.asarray(toks)[None], rcfg)[0]
    return np.asarray(full[n - 1:])


STEPS = 8
LENS = (5, 11)


@pytest.fixture(scope="module")
def run(engine):
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, VOCAB, n + STEPS, dtype=np.int32) for n in LENS]
    got, cache = _engine_logits(engine, seqs, LENS, STEPS)
    return seqs, got, cache


def _worst(engine, run, spoil=None):
    seqs, got, _ = run
    return max(_rel(got[s], _reference_logits(engine.params, seqs[s], n, spoil))
               for s, n in enumerate(LENS))


# -- the engine -------------------------------------------------------------------


def test_engine_agrees_with_the_plain_reference(engine, run):
    """Prefill (expanded attention) then eight decode steps (absorbed, over
    the latent pages, two of them crossing a page), two slots of different
    lengths and one inactive: logits, every position."""
    assert _worst(engine, run) < TOL
    cache = run[2]
    # one 128-lane row a position and layer for all heads: c | k_pe | 0
    assert cache["latent"].shape == (LAYERS, engine.ecfg.num_pages, 8, 128)
    assert not np.asarray(cache["latent"][..., R + ROPE:]).any()
    assert cache.moe_load.shape == (LAYERS - 1, E)


@pytest.mark.parametrize("spoil", SPOILS, ids=lambda f: f.__name__.strip("_"))
def test_a_spoiled_reference_fails(engine, run, spoil):
    """A check of the check."""
    assert _worst(engine, run, spoil) > 10 * TOL


def test_reference_returns_the_last_positions_alone(engine, run):
    seqs = run[0]
    rcfg = ref.reference_cfg(PUBLISHED)
    p = ref.to_reference_params(engine.params["params"], PUBLISHED)
    toks = jnp.asarray(seqs[1])[None]
    full = ref.forward(p, toks, rcfg)
    assert full.shape == (1, len(seqs[1]), VOCAB)
    assert np.allclose(np.asarray(ref.forward(p, toks, rcfg, last=3)),
                       np.asarray(full[:, -3:]), atol=1e-6)
    # the head in vocabulary blocks is the head
    w = jax.random.normal(jax.random.PRNGKey(0), (D, VOCAB))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, D))
    assert np.allclose(np.asarray(ref.head(x, w)), np.asarray(x @ w), atol=1e-4)


def test_absorbed_decode_equals_expanded_attention():
    """The same mathematics twice: the last position's attention through
    keys and values expanded to heads, and through the latent rows with
    ``kv_b_proj`` absorbed into the query and the output."""
    cfg = dataclasses.replace(TransformerConfig(), **OVERRIDES,
                              attention_impl="xla")
    S, P = 21, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    p = {"q_proj": {"kernel": jax.random.normal(ks[0], (D, H, NOPE + ROPE)) * 0.2},
         "kv_a_proj": {"kernel": jax.random.normal(ks[1], (D, R + ROPE)) * 0.2},
         "kv_a_norm": {"scale": 1.0 + 0.1 * jax.random.normal(ks[2], (R,))},
         "kv_b_proj": {"kernel": jax.random.normal(ks[3], (R, H, NOPE + DV)) * 0.2}}
    x = jax.random.normal(ks[4], (1, S, D))
    positions = jnp.arange(S, dtype=jnp.int32)[None]
    q_nope, q_pe, c, k_pe, row = attention._latent_qkv(x, p, cfg, positions)
    want = attention._latent_attention_expanded(q_nope, q_pe, c, k_pe, p, cfg)[0, -1]
    pages = jnp.zeros((2, 5, P, row.shape[-1])).at[1, 1:4].set(
        jnp.pad(row[0], ((0, 3 * P - S), (0, 0))).reshape(3, P, -1))
    tables = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    work = live_pages(jnp.asarray([S - 1]), jnp.asarray([True]), tables, P)
    got = attention._latent_attention_absorbed(q_nope[:, -1], q_pe[:, -1], pages, work,
                                        1, p, cfg)[0]
    assert got.shape == want.shape == (H, DV)
    assert _rel(got, want) < 1e-5


# -- the kernels, in interpret mode ---------------------------------------------------


def test_mla_decode_reads_live_pages_and_matches_reference_attention():
    """Five slots: one position, a page less one, a page exactly, an inactive
    slot, six pages less one; pages scattered over the pool. Against
    ``reference_attention`` over each slot's live rows, every head seeing
    the same keys (the rows) and values (their first R lanes)."""
    B, W, P, MP, L = 5, 128, 8, 6, 2
    NP = 1 + B * MP
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jax.random.normal(ks[0], (B, H, W))
    pages = jax.random.normal(ks[1], (L, NP, P, W))
    tables = np.random.default_rng(0).permutation(
        np.arange(1, NP)).reshape(B, MP).astype(np.int32)
    tables[3] = 0
    seq_lens = np.array([0, 6, 7, 0, 46], np.int32)
    active = np.array([True, True, True, False, True])
    work = live_pages(jnp.asarray(seq_lens), jnp.asarray(active),
                      jnp.asarray(tables), P)
    slot_of, page_of, starts, lengths, used = (np.asarray(w) for w in work)
    assert used == 1 + 1 + 1 + 1 + 6
    assert list(starts) == [0, 1, 2, 3, 4, 10]
    assert list(lengths) == [1, 7, 8, 0, 47]
    assert list(page_of[:used]) == [tables[0, 0], tables[1, 0], tables[2, 0],
                                    0, *tables[4]]
    got = mla_decode(q, pages, work, rank=R, layer=1, sm_scale=W ** -0.5)
    assert got.shape == (B, H, R)
    assert not np.asarray(got[3]).any()         # the inactive slot: zeros
    for b in (0, 1, 2, 4):
        n = seq_lens[b] + 1
        rows = pages[1][tables[b]].reshape(MP * P, W)[:n]
        keys = jnp.broadcast_to(rows[None, :, None, :], (1, n, H, W))
        want = reference_attention(q[b][None, None], keys, keys[..., :R],
                                   causal=False)[0, 0]
        assert _rel(got[b], want) < 1e-5, b


@pytest.mark.parametrize("S", [128, 384])
def test_flash_forward_takes_a_value_head_size_of_its_own(S):
    """q . k over 24 and p . v over 16 (192 and 128 at the published widths),
    one block and three: against ``reference_attention``, which scales by the
    key head size too."""
    ks = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(ks[0], (2, S, 2, 24))
    k = jax.random.normal(ks[1], (2, S, 2, 24))
    v = jax.random.normal(ks[2], (2, S, 2, 16))
    got = flash_attention_fwd(q, k, v, causal=True, interpret=True)
    assert got.shape == (2, S, 2, 16)
    assert _rel(got, reference_attention(q, k, v, True)) < 1e-5


# -- routing ---------------------------------------------------------------------------


def test_sigmoid_route_is_the_references_routing_and_the_bias_chooses():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (200, D))
    router = jax.random.normal(ks[1], (D, E)) * 0.3
    bias = jax.random.normal(ks[2], (E,)) * 0.3
    w, e = route(x, router, K, True, "sigmoid", bias, SCALING)
    rw, re = ref.routing(x, router, bias, ref.reference_cfg(PUBLISHED))
    assert np.array_equal(np.asarray(e), np.asarray(re))
    assert np.allclose(np.asarray(w), np.asarray(rw), atol=1e-6)
    assert np.allclose(np.asarray(w.sum(-1)), SCALING, atol=1e-5)
    # the bias changes who wins for many rows, and weighs nothing: where the
    # winners are the same, so are the weights
    w0, e0 = route(x, router, K, True, "sigmoid", jnp.zeros(E), SCALING)
    same = np.asarray((jnp.sort(e, -1) == jnp.sort(e0, -1)).all(-1))
    assert 0.1 < 1 - same.mean() < 0.9
    assert np.allclose(np.sort(np.asarray(w)[same], -1),
                       np.sort(np.asarray(w0)[same], -1), atol=1e-6)
    # the softmax kind is what it was
    ws, es = route(x, router, K, False)
    probs = jax.nn.softmax(x @ router, axis=-1)
    assert np.array_equal(np.asarray(es), np.asarray(jax.lax.top_k(probs, K)[1]))
    with pytest.raises(ValueError, match="router kind"):
        route(x, router, K, True, "tanh")


def test_the_drawn_bias_changes_the_winners_at_the_published_widths():
    """64 experts, top-6, a 2048-wide normalised row, the router drawn as
    ``Transformer.init`` draws it (normal 0.02) and the bias at
    ``ROUTER_BIAS_STD``: the share of rows whose six experts differ from those
    of a zero bias (a count, the same on any backend). The configuration
    file's ``departures`` quotes it."""
    from ray_tpu.models.transformer import ROUTER_BIAS_STD

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (4096, 2048))
    router = jax.random.normal(ks[1], (2048, 64)) * 0.02
    bias = jax.random.normal(ks[2], (64,)) * ROUTER_BIAS_STD
    _, with_bias = route(x, router, 6, True, "sigmoid", bias, 2.446)
    _, without = route(x, router, 6, True, "sigmoid", jnp.zeros(64), 2.446)
    changed = float((jnp.sort(with_bias, -1) != jnp.sort(without, -1)
                     ).any(-1).mean())
    print(f"rows whose experts the bias changes: {changed:.3f}")
    assert 0.4 < changed < 0.7


# -- the training module ------------------------------------------------------------------


def test_transformer_apply_agrees_with_the_reference_when_nothing_drops(engine):
    """``Transformer.apply`` (expanded attention, the one-hot dispatch with
    its capacity) on the engine's own tree, at a capacity that drops nothing."""
    cfg = dataclasses.replace(engine.mcfg, capacity_factor=float(E),
                              attention_impl="xla")
    toks = np.random.default_rng(1).integers(0, VOCAB, (2, 12), dtype=np.int32)
    got = Transformer(cfg).apply(engine.params, jnp.asarray(toks),
                                 mutable=["losses"])[0]
    rcfg = ref.reference_cfg(PUBLISHED)
    want = ref.forward(ref.to_reference_params(engine.params["params"],
                                               PUBLISHED), jnp.asarray(toks), rcfg)
    assert _rel(got, want) < TOL


def test_the_parameter_tree_and_its_count(engine):
    p = engine.params["params"]
    assert set(p["layer_0"]) == {"attn", "attn_norm", "mlp", "mlp_norm"}
    assert set(p["layer_1"]) == {"attn", "attn_norm", "moe", "mlp_norm"}
    assert set(p["layer_1"]["moe"]) == {"router", "router_bias", "gate_proj",
                                        "up_proj", "down_proj", "shared"}
    shapes = {k: v["kernel"].shape if "kernel" in v else v["scale"].shape
              for k, v in p["layer_1"]["attn"].items()}
    assert shapes == {"q_proj": (D, H, NOPE + ROPE), "kv_a_proj": (D, R + ROPE),
                      "kv_a_norm": (R,), "kv_b_proj": (R, H, NOPE + DV),
                      "o_proj": (H, DV, D)}
    assert p["layer_0"]["mlp"]["gate_proj"]["kernel"].shape == (D, DENSE)
    assert p["layer_1"]["moe"]["shared"]["gate_proj"]["kernel"].shape == (
        D, SHARED * F)
    assert np.asarray(p["layer_1"]["moe"]["router_bias"]).any()
    leaves = sum(a.size for a in jax.tree_util.tree_leaves(p))
    assert leaves == engine.mcfg.num_params()


def test_config_defaults_select_nothing_new():
    cfg = TransformerConfig()
    assert (cfg.kv_latent_rank, cfg.first_k_dense, cfg.d_ff_dense,
            cfg.n_shared_experts, cfg.router_kind,
            cfg.routed_scaling_factor) == (0, 0, 0, 0, "softmax", 1.0)
    assert EngineConfig().expect_latent_rank == 0
    moe = dataclasses.replace(cfg, n_experts=4, moe_every=2, n_layers=4)
    assert [moe.is_moe_layer(i) for i in range(4)] == [True, False, True, False]
    assert not any(cfg.is_moe_layer(i) for i in range(8))
    assert prefill_rows.held(mr.init_cache(cfg, 3, 8)) == {"dense"}


# -- the engine's accounting ------------------------------------------------------------


def test_mla_counters_after_a_known_number_of_steps():
    eng = _engine()
    assert eng.metrics["mla_decode_live_tokens"] == 0
    prompts = [[5, 6, 7, 8], [9, 10, 11, 12, 13, 14, 15]]
    eng.generate(prompts, SamplingParams(max_tokens=6, stop_token_ids=()),
                 decode_text=False)
    m = eng.metrics
    assert m["decode_steps"] == 5
    # step j (0-based) writes its row at position prompt + j and attends over
    # prompt + j + 1 positions a slot; every slot reads whole pages of 8, the
    # idle third slot one step over the scratch page
    live = sum(len(p) + j + 1 for p in prompts for j in range(5))
    assert m["mla_decode_live_tokens"] == live
    read = sum(((len(p) + j) // 8 + 1) * 8 for p in prompts
               for j in range(5)) + 5 * 8
    assert m["mla_decode_read_tokens"] == read
    assert m["moe_decode_layer_steps"] == 5 * (LAYERS - 1)


@pytest.mark.parametrize("lens,rows,bucket", [((11, 5, 14), 4, 16),
                                              ((19, 9), 2, 32)])
def test_burst_admitted_in_one_step_shares_a_prefill_call(lens, rows, bucket):
    """Requests admitted in one step are rows of ONE prefill call, each row's
    latent rows written through its own block table, its tokens' experts
    chosen row by row: the tokens are those the same requests generate one a
    step."""
    eng = _engine()
    rng = np.random.default_rng(5)
    d = prefill_rows.burst_equals_one_a_step(
        eng, [rng.integers(0, VOCAB, n).tolist() for n in lens])
    assert (d["prefill_calls"], d["prefill_batch_tokens"]) == (1, rows * bucket)


def test_padding_row_changes_no_latent_row_or_load(engine):
    rng = np.random.default_rng(6)
    prefill_rows.padding_rows_write_nothing(
        engine, rng.integers(0, VOCAB, 7).tolist())


def test_routing_is_counted_once_a_step_with_the_next_step_dispatched():
    """A decode step's ``moe_load`` comes back inside the cache the NEXT
    dispatch donates, before the host has read it: the engine takes a copy
    out of that chain, and every step's routing is counted, one step late."""
    eng = _engine()
    m = eng.metrics
    prompts = [[5, 6, 7, 8], [9, 10, 11, 12, 13, 14, 15]]
    for i, (prompt, most) in enumerate(zip(prompts, (3, 6))):
        eng.add_request(f"r{i}", prompt, SamplingParams(
            max_tokens=most, stop_token_ids=()))
    rows = 0  # slots x steps whose routing the host has read
    while eng.has_unfinished():
        eng.step()
        unread = [u for u in eng._unread if u.moe_load is not None]
        assert len(unread) == (1 if eng._unread else 0)
        assert m["moe_decode_layer_steps"] == (
            (m["decode_steps"] - len(unread)) * (LAYERS - 1))
        # real rows: every active slot reaches K experts a layer
        rows = m["moe_decode_assignments"] // (K * (LAYERS - 1))
        assert m["moe_decode_assignments"] == rows * K * (LAYERS - 1)
    # five decode steps: both requests in the first two, r1 alone after
    assert (m["decode_steps"], rows) == (5, 2 + 2 + 1 + 1 + 1)
    assert m["moe_decode_layer_steps"] == 5 * (LAYERS - 1)
    assert m["overlapped_steps"] == m["steps"] - 1 == 4
    assert 1 <= m["moe_decode_max_load"] / m["moe_decode_layer_steps"] <= 2


def test_a_deployment_states_its_latent_rank():
    with pytest.raises(ValueError, match="latent cache of rank 0"):
        JaxLLMEngine(LLMConfig(
            model_id="tiny", model_overrides=OVERRIDES,
            engine_config=EngineConfig(max_num_seqs=2, max_model_len=64,
                                       page_size=8, expect_experts=E)))
    with pytest.raises(ValueError, match="latent cache of rank 512"):
        JaxLLMEngine(LLMConfig(model_id="tiny", engine_config=EngineConfig(
            max_num_seqs=2, max_model_len=64, page_size=8,
            expect_latent_rank=512)))
    # the parent's EngineConfig has no such key: a job block that names it
    # fails there in the driver process, at once
    assert "expect_latent_rank" in {f.name for f in
                                    dataclasses.fields(EngineConfig)}


def test_export_kv_round_trip_carries_the_latent_rows():
    """Prefill on one engine, decode on another: the same tokens as one
    engine alone, and the state names the latent leaf."""
    params = SamplingParams(max_tokens=6, stop_token_ids=())
    prompt = list(range(3, 14))
    whole = _engine().generate([prompt], params, decode_text=False)[0].token_ids
    a, b = _engine(), _engine()
    state = a.prefill_only("r", prompt, params)
    assert "latent" in state and "dense" not in state
    assert state["latent"].shape == (LAYERS, 2, 8, 128)
    b.add_request_with_kv(state)
    done = []
    while b.has_unfinished():
        done += [o for o in b.step() if o.finished]
    assert done[0].token_ids == whole


# -- what the benchmark's files say about the model ---------------------------------------

CELL = "moonlight-16b-a3b.longdoc-saturated-b32"
# config.json of moonshotai/Moonlight-16B-A3B as the catalog holds it
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 163840}


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL, os.path.join(REPO, "BENCHMARK.json"))


def test_the_configuration_file_holds_the_published_keys(cell):
    c = cell.config
    changed = {k for k, v in CATALOG.items() if c[k] != v or type(c[k]) != type(v)}
    assert changed == {"num_hidden_layers"} == set(c["reduced"])
    cut = c["reduced"]["num_hidden_layers"]
    assert (cut["from"], cut["to"]) == (27, c["num_hidden_layers"])
    # the floors: the dense layer and at least four of the layers after it
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    entry = {e["name"]: e for e in cell.benchmark["configs"]}[c["name"]]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == c["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "Moonlight-16B-A3B"][0]
        assert row["config"] == CATALOG and row["source_url"] == c["source"]
    e = c["job"]["engine"]
    assert (e["max_num_seqs"], e["max_model_len"], e["expect_experts"],
            e["expect_latent_rank"], e["prefill_bucket_min"]) == (
        32, 8192, 64, 512, 512)
    assert e["max_model_len"] == c["max_position_embeddings"]


def test_the_mix_is_the_issues(cell):
    from benchmarks import traffic

    mix = cell.mix
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 0.7, "min": 512, "max": 7680}
    assert mix["max_tokens"] == {"dist": "lognormal", "median": 96,
                                 "sigma": 0.6, "min": 16, "max": 512}
    assert (mix["temperature"], mix["lead_s"], mix["end"]) == (0.0, 12.0,
                                                               "abandon")
    e = cell.config["job"]["engine"]
    assert traffic.serve_prefill_buckets(
        mix, e["prefill_bucket_min"], e["max_model_len"]) == [
        512, 1024, 2048, 4096, 8192]
    sizes = traffic.stratified(mix["prompt_tokens"], 512)
    assert 2500 < sum(sizes) / 512 < 2600
    rate = mix["arrival"]["rate_per_s"]
    assert rate * 2 == int(rate * 2)            # rounded to 0.5/s
    assert f"{rate:g} requests/s" in cell.entry["why"]


def test_program_overrides_and_the_parameter_counts(cell):
    arch = cell.architecture()
    with open(arch.__file__) as f:
        source = f.read()
    assert "import ray_tpu" not in source and "from ray_tpu" not in source
    c = cell.config
    o = arch.program_overrides(c, 8192)
    cfg = dataclasses.replace(TransformerConfig(), **o)
    assert (cfg.kv_latent_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.d_ff, cfg.d_ff_dense, cfg.first_k_dense,
            cfg.n_shared_experts, cfg.router_kind, cfg.routed_scaling_factor,
            cfg.experts_per_token, cfg.param_dtype) == (
        512, 128, 64, 128, 1408, 11264, 1, 2, "sigmoid", 2.446, 6, jnp.bfloat16)
    # by hand: attention 13.77 M a layer, the dense layer's MLP 69.21 M, an
    # expert layer's 571.08 M, table and head 671.09 M
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256 + 16 * 128 * 2048 \
        + 2 * 2048
    sparse = 64 * 3 * 2048 * 1408 + 3 * 2048 * 2816 + 2048 * 64 + 64
    rest = 2 * 163840 * 2048 + 2048
    for layers in (9, 27):
        want = layers * attn + 3 * 2048 * 11264 + (layers - 1) * sparse + rest
        cut = dict(c, num_hidden_layers=layers)
        assert arch.total_params(cut) == want
        assert dataclasses.replace(cfg, n_layers=layers).num_params() == want
    assert arch.total_params(c) == 5_432_847_360              # 10.87 GB
    assert arch.total_params(dict(c, num_hidden_layers=27)) == 15_960_110_208
    for key, value in (("q_lora_rank", 1536), ("n_group", 8),
                       ("rope_scaling", {"type": "yarn"}),
                       ("num_nextn_predict_layers", 1),
                       ("scoring_func", "softmax")):
        with pytest.raises(ValueError, match=key):
            arch.program_overrides(dict(c, **{key: value}), 8192)
    # a token multiplies by 6 routed experts and the shared one, not by 64
    assert arch.total_params(c) - arch.active_matmul_params(c) > \
        8 * 58 * 3 * 2048 * 1408


def test_kernel_cost_by_hand(cell):
    arch, c = cell.architecture(), cell.config
    facts = {"max_num_seqs": 32}
    # 32 slots x 512 positions, all 16 heads against each 576-wide row and
    # its 512-wide latent; 1,152 bytes a row
    ops, nbytes = arch.kernel_cost("mla_decode", c, facts)
    assert ops == 32 * 512 * 16 * 2 * (576 + 512) == 570_425_344
    assert nbytes == 32 * 512 * 1152 == 18_874_368
    assert nbytes / 819e9 > 5 * ops / 197e12          # bound by bytes
    ops, nbytes = arch.kernel_cost("flash_fwd", c, facts)
    assert ops == 16 * (4096 * 4097 // 2) * 2 * (192 + 128)
    assert nbytes == 2 * 4096 * 16 * (192 + 128) * 2
    assert ops / 197e12 > 4 * nbytes / 819e9           # bound by operations
    # decode: 32 rows x top-6; 64 x (1 - (58/64)^32) = 61.26 -> 61 experts
    ops, nbytes = arch.kernel_cost("moe_gmm_decode", c, facts)
    assert arch.experts_touched(c, 32) == 61
    assert ops == 2 * 192 * 2048 * 1408
    assert nbytes == (61 * 2048 * 1408 + 192 * (2048 + 1408)) * 2
    assert arch.kernel_cost("moe_gmm_decode", c, {}) == (ops, nbytes)
    # prefill: the 512-row bucket, every expert's matrix once
    ops, nbytes = arch.kernel_cost("moe_gmm_prefill", c, facts)
    assert ops == 2 * 512 * 6 * 2048 * 1408
    assert nbytes == (64 * 2048 * 1408 + 3072 * (2048 + 1408)) * 2
    assert nbytes / 819e9 > ops / 197e12
    with pytest.raises(KeyError, match="flash_bwd_dq"):
        arch.kernel_cost("flash_bwd_dq", c, facts)


NEW_METRICS = ["mla_decode_roofline", "mla.decode_attn_dev_ms",
               "mla.live_tokens_per_step", "mla.read_per_live",
               "mla_prefill_flash_roofline"]


def test_the_cells_metrics_read_a_window_as_data(cell):
    """Counters and a traced window give every new per-layer metric; with
    no trace and no decode step there is nothing to read, and nothing is
    raised."""
    listed = {m["name"]: m for m in cell.per_layer()}
    for name in NEW_METRICS:
        assert CELL in listed[name]["workloads"]   # in the list: later cells join
        assert listed[name]["moves"] == "serve_tokens_per_s"
        assert set(cell.reader(name)) == {"reduce", "args"}
    assert {"moe_gmm_decode_roofline", "moe.expert_dev_ms", "moe.max_load",
            "model.decode_dev_ms", "device.idle_share.saturated",
            "engine.tokens_per_step"} <= set(listed)
    assert "engine.host_ms_per_step" not in listed      # pinned to cell 3
    window = {"mla_decode_live_tokens": 8_000_000, "decode_steps": 100,
              "mla_decode_read_tokens": 8_400_000, "generated_tokens": 3200}
    trace = {"modules": {"jit_decode_step": {"count": 10, "total_s": 0.2}},
             "window_s": 1.0, "busy_s": 0.8,
             "op_kinds": {
                 "mla_decode bf16[32,16,512]": [90 * 0.4e-3, 90.0],
                 "flash_fwd (bf16[16,4096,128], f32[16,1,4096])": [27 * 4e-3, 27.0],
                 "flash_fwd (bf16[16,512,128], f32[16,1,512])": [1.0, 9.0]}}
    ctx = {"trace": trace, "spans": {}, "counters": window,
           "facts": {"max_num_seqs": 32, "peak_flops_per_s": 197e12,
                     "peak_hbm_bytes_per_s": 819e9}}
    got = {k: v["value"] for k, v in cell.per_layer_values(ctx).items()}
    assert got["mla.live_tokens_per_step"] == 80_000
    assert got["mla.read_per_live"] == 1.05
    assert got["mla.decode_attn_dev_ms"] == pytest.approx(9 * 0.4)
    assert got["mla_decode_roofline"] == pytest.approx(
        100 * (18_874_368 / 819e9) / 0.4e-3)
    flash_ops = 16 * (4096 * 4097 // 2) * 2 * 320
    assert got["mla_prefill_flash_roofline"] == pytest.approx(
        100 * (flash_ops / 197e12) / 4e-3)
    ctx = {"trace": None, "spans": {}, "counters": {}, "facts": {}}
    assert cell.per_layer_values(ctx) == {}
