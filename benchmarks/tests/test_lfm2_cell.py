"""``benchmarks/architectures/lfm2_moe.py`` reached the way the harness reaches
it (through the resolver, from the committed configuration file), against
counts made by hand from the published shapes and ISSUE 40's numbers, and its
plain reference against the properties the equations promise (no program is
imported: the program is held to this reference in ``tests/test_lfm2.py``)."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import registry
from benchmarks.registry import HERE, REPO, Cell
from benchmarks.trace import reduce

CELL = "lfm2-8b-a1b.chat-saturated-b128"
D, HD, H, KVH, F, DENSE, V, E = 2048, 64, 32, 8, 1792, 7168, 65536, 32
CONV = 4 * D * D + 3 * D + 2 * D                    # the two norms included
ATTN = 2 * D * H * HD + 2 * D * KVH * HD + 2 * HD + 2 * D
SPARSE = E * 3 * D * F + D * E + E
TOTAL = 2 * (CONV + 3 * D * DENSE) + 3 * (ATTN + SPARSE) \
    + 9 * (CONV + SPARSE) + V * D + D


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL, os.path.join(REPO, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def arch(cell):
    return cell.architecture()


def test_the_module_has_the_eight_members_and_imports_no_program(arch):
    assert all(callable(getattr(arch, m)) for m in registry.MEMBERS)
    with open(arch.__file__) as f:
        source = f.read()
    assert "import ray_tpu" not in source and "from ray_tpu" not in source


def test_the_configuration_keeps_every_published_width(cell, arch):
    c = cell.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = [r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B"][0]
    cut = set(c["reduced"])
    assert cut == {"num_hidden_layers", "max_position_embeddings"}
    for key, value in row["config"].items():   # every other key as published
        if key not in cut:
            assert c[key] == value, key
    assert c["source"] == row["source_url"]
    entry = {e["name"]: e for e in cell.benchmark["configs"]}[c["name"]]
    assert set(entry["reduced"]) == cut
    for key, r in c["reduced"].items():
        assert r["to"] == c[key] < r["from"] == row["config"][key], key
    assert (c["num_experts"], c["num_experts_per_tok"], c["vocab_size"]) == (
        32, 4, 65536)
    types = arch.layer_types(c)
    assert types == ("conv", "conv") + ("full_attention", "conv", "conv",
                                        "conv") * 3
    assert (types.count("full_attention"), types.count("conv")) == (3, 11)
    assert arch.total_params(c) == TOTAL == 4_667_077_376    # 9.33 GB
    e = c["job"]["engine"]
    assert e == {"max_num_seqs": 128, "max_model_len": 2560, "page_size": 256,
                 "prefill_bucket_min": 128, "expect_experts": 32,
                 "expect_state_layers": 11, "expect_conv_taps": 3}
    assert e["max_model_len"] == c["max_position_embeddings"]
    # the state ISSUE 40 reckoned: 6,144 B a position, 2.01 GB of pages
    assert 3 * 2 * KVH * HD * 2 == 6144
    assert round(128 * 2560 * 6144 / 1e9, 2) == 2.01
    assert 11 * 2 * D * 2 * 128 == 11_534_336
    for key in ("torch_dtype", "tie_word_embeddings", "hidden_act",
                "gated short convolution", "attention", "router",
                "initializer", "page_size"):
        assert key in c["assumed"], key
    assert set(c["initializer"]) == {"attention", "conv", "mlp", "experts",
                                     "embedding"}


def test_the_mix_is_the_one_the_issue_named(cell):
    from benchmarks import traffic

    mix = cell.mix
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.8, "min": 32, "max": 2048}
    assert mix["max_tokens"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.6, "min": 16, "max": 512}
    assert (mix["lead_s"], mix["end"], mix["temperature"]) == (30.0, "abandon", 0.0)
    assert round(sum(traffic.stratified(mix["prompt_tokens"], 2250)) / 2250) == 667
    assert round(sum(traffic.stratified(mix["max_tokens"], 2250)) / 2250) == 152
    assert traffic.serve_prefill_buckets(mix, 128, 2560) == [
        128, 256, 512, 1024, 2048]
    assert mix["prompt_tokens"]["min"] == cell.architecture().LEAST_PROMPT
    assert mix["prompt_tokens"]["max"] + mix["max_tokens"]["max"] == 2560
    assert "sweep" in mix["rate_why"] and mix["arrival"]["rate_per_s"] > 0


def test_kernel_costs_are_counted_from_the_shapes(cell, arch):
    c = cell.config
    # 128 slots x the least prompt of 32; 2 x (64 + 64) operations a head and
    # position, 2,048 bytes a position
    ops, nbytes = arch.kernel_cost("paged_gqa_decode", c, {"max_num_seqs": 128})
    assert (ops, nbytes) == (128 * 32 * H * 2 * (64 + 64), 128 * 32 * 2048)
    assert arch.experts_touched(c, 128) == 32     # 32 x (1 - (7/8)^128)
    assert arch.experts_touched(c, 1) == 4
    # 128 rows x top-4 over all 32 experts: 234.9 MB of matrices (0.287 ms at
    # 819 GB/s, ISSUE 40) + 3.9 MB of rows in and out; bound by bytes
    ops, nbytes = arch.kernel_cost("moe_gmm_decode", c, {})
    assert ops == 2 * 512 * D * F
    assert nbytes == 32 * D * F * 2 + 512 * (D + F) * 2 == 238_813_184
    assert 32 * D * F * 2 == 234_881_024
    assert ops / 197e12 < nbytes / 819e9
    ops, nbytes = arch.kernel_cost("moe_gmm_prefill", c, {})
    assert ops == 2 * 4 * D * F and nbytes == (4 * D * F + 4 * (D + F)) * 2
    ops, nbytes = arch.kernel_cost("flash_fwd", c, {})
    assert ops == H * (512 * 513 // 2) * 2 * 2 * HD
    assert nbytes == 2 * 512 * (H + KVH) * HD * 2
    with pytest.raises(KeyError):
        arch.kernel_cost("window_gqa_decode", c, {})


def test_the_new_metric_reads_through_the_cell(cell):
    name = "conv.step_dev_ms"
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        raw = json.load(f)
    assert raw["reduce"] == "device_op_ms_per_exec" in reduce.REDUCTIONS
    assert raw["args"]["module"] == "jit_decode_step" and cell.reader(name) == raw
    entry = {m["name"]: m for m in cell.per_layer()}[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["layer"] in {m["layer"] for m in cell.benchmark["per_layer"]
                              if m["name"] != name}
    # the decode step's own operations at the published widths (AOT, PR 40),
    # and none of a prefill call's or of the state's write, which both have
    kinds = {"slice_bitcast_fusion bf16[2,128,2048]": [0.011, 110.0],
             "fusion bf16[2048,128]": [0.022, 110.0],
             "copy-start (bf16[11,2,128,2048], bf16[11,2,128,2048], u32[])":
             [0.001, 10.0],
             "copy-done bf16[11,2,128,2048]": [0.002, 10.0],
             "fusion bf16[11,2,128,2048]": [5.0, 500.0],
             "fusion bf16[128,6144]": [7.0, 110.0],
             # a prefill call's, at the 2048 bucket: the slots are pinned so
             # that a width in their place is not taken for them
             "fusion bf16[2048,2048]": [3.0, 90.0],
             "fusion bf16[2048,7168]": [2.0, 9.0],
             "fusion bf16[1,512,2048]": [9.0, 50.0]}
    ctx = {"trace": {"op_kinds": kinds, "window_s": 1.0, "busy_s": 0.9,
                     "modules": {"jit_decode_step": {"count": 10.0,
                                                     "total_s": 0.12}}},
           "spans": {}, "counters": {}, "facts": {}}
    got = cell.per_layer_values(ctx)
    assert got[name]["value"] == pytest.approx(3.6)
    # a program without such layers (the parent's): left out, no raise
    ctx["trace"]["op_kinds"] = {"fusion bf16[128,6144]": [7.0, 110.0]}
    assert name not in cell.per_layer_values(ctx)
    # every metric the cell lists has its reader's file
    for m in cell.per_layer():
        assert cell.reader(m["name"])["reduce"] in reduce.REDUCTIONS


@pytest.fixture(scope="module")
def tiny(arch):
    """A small model under the reference's own parameter names, drawn here."""
    rng = np.random.default_rng(0)
    d, Hh, KV, hd, f, R = 32, 4, 2, 8, 24, 8
    w = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
    types = ("conv", "conv", "full_attention", "conv", "conv", "conv")
    layers = []
    for i, kind in enumerate(types):
        lp = {"operator_norm": 1 + w(d), "ffn_norm": 1 + w(d)}
        if kind == "conv":
            lp.update(in_proj=w(d, 3 * d), conv=w(d, 3), out_proj=w(d, d))
        else:
            lp.update(q_proj=w(d, Hh * hd), k_proj=w(d, KV * hd),
                      v_proj=w(d, KV * hd), out_proj=w(Hh * hd, d),
                      q_layernorm=1 + w(hd), k_layernorm=1 + w(hd))
        if i < 2:
            lp.update(w1=w(d, 2 * f), w3=w(d, 2 * f), w2=w(2 * f, d))
        else:
            lp.update(router=w(d, R), expert_bias=w(R) * 0.3, w1=w(R, d, f),
                      w3=w(R, d, f), w2=w(R, f, d))
        layers.append(lp)
    params = {"embed_tokens": w(64, d), "embedding_norm": 1 + w(d),
              "layers": layers}
    rcfg = {"num_attention_heads": Hh, "num_key_value_heads": KV, "head_dim": hd,
            "rope_theta": 1000000, "norm_eps": 1e-5, "num_experts_per_tok": 2,
            "norm_topk_prob": True, "routed_scaling_factor": 1,
            "conv_L_cache": 3, "layer_types": types, "taps_reversed": False,
            "output_gate": True, "gate_before_conv": True, "state_lag": 0,
            "qk_norm_before_rope": True}
    return params, rcfg


def test_reference_is_causal_and_looks_back_through_both_mixers(arch, tiny):
    params, rcfg = tiny
    toks = np.random.default_rng(1).integers(0, 64, (1, 20))
    full = arch.forward(params, jnp.asarray(toks), rcfg)
    assert full.shape == (1, 20, 64)
    other = toks.copy()
    other[0, 15] = (other[0, 15] + 1) % 64
    moved = arch.forward(params, jnp.asarray(other), rcfg)
    np.testing.assert_allclose(moved[:, :15], full[:, :15], atol=1e-5)
    assert float(jnp.abs(moved[:, 15:] - full[:, 15:]).max()) > 1e-3
    np.testing.assert_allclose(
        arch.forward(params, jnp.asarray(toks), rcfg, last=3), full[:, -3:],
        atol=1e-6)
    loss = arch.loss(params, jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:]),
                     rcfg)
    assert np.isfinite(float(loss)) and float(loss) > 0


def test_short_convolution_is_the_equation_written_out(arch, tiny):
    """``short_conv`` against the same sum in numpy, a position at a time:
    c[t] = w[:, 0] s[t-2] + w[:, 1] s[t-1] + w[:, 2] s[t], zeros before the
    first position, gated going in (B * z) and coming out (C)."""
    params, rcfg = tiny
    lp = params["layers"][0]
    u = np.random.default_rng(2).normal(size=(1, 6, 32)).astype(np.float32)
    got = np.asarray(arch.short_conv(jnp.asarray(u), lp, rcfg))[0]
    bcz = u[0] @ np.asarray(lp["in_proj"])
    b, c, z = bcz[:, :32], bcz[:, 32:64], bcz[:, 64:]
    s, w = b * z, np.asarray(lp["conv"])
    want = np.zeros_like(s)
    for t in range(6):
        for k in range(3):
            if t - (2 - k) >= 0:
                want[t] += w[:, k] * s[t - (2 - k)]
    want = (c * want) @ np.asarray(lp["out_proj"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_reference_routing_is_the_published_one_written_out(arch, tiny):
    """``sparse_mlp`` against the same sum in numpy, a token and an expert at
    a time: top-2 of sigmoid + bias, the weights over their sum + 1e-6."""
    params, rcfg = tiny
    lp = params["layers"][2]
    h = np.random.default_rng(3).normal(size=(7, 32)).astype(np.float32)
    got = np.asarray(arch.sparse_mlp(jnp.asarray(h), lp, rcfg))
    s = 1 / (1 + np.exp(-(h @ np.asarray(lp["router"]))))
    silu = lambda t: t / (1 + np.exp(-t))   # noqa: E731
    want = np.zeros_like(h)
    moved = 0
    for t in range(7):
        top = np.argsort(-(s[t] + np.asarray(lp["expert_bias"])))[:2]
        moved += set(top) != set(np.argsort(-s[t])[:2])
        for e in top:
            w1, w3, w2 = (np.asarray(lp[n][e]) for n in ("w1", "w3", "w2"))
            want[t] += s[t, e] / (s[t, top].sum() + 1e-6) * (
                (silu(h[t] @ w1) * (h[t] @ w3)) @ w2)
    assert moved > 0          # the bias chose for some token
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
