"""``benchmarks/architectures/afmoe.py`` reached the way the harness reaches it
(through the resolver, from the committed configuration file), against counts
made by hand from the published shapes, and its plain reference against the
properties the equations promise (no program is imported: the program is held
to this reference in ``tests/test_afmoe.py``)."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import registry
from benchmarks.registry import HERE, REPO, Cell
from benchmarks.trace import reduce

CELL = "trinity-large-preview.shortlong-saturated-b32"
D, HD, H, KVH, F, DENSE, V = 3072, 128, 48, 8, 3072, 12288, 25024
ATTN = 3 * D * H * HD + 2 * D * KVH * HD + 2 * HD + 4 * D   # norms included
SPARSE = ATTN + 32 * 3 * D * F + 3 * D * F + D * 256 + 256
TOTAL = ATTN + 3 * D * DENSE + 4 * SPARSE + 2 * V * D + D


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
        row = [r for r in map(json.loads, f)
               if r["name"] == "Trinity-Large-Preview"][0]
    cut = set(c["reduced"])
    assert cut == {"num_hidden_layers", "num_dense_layers", "num_experts",
                   "vocab_size", "max_position_embeddings"}
    for key, value in row["config"].items():   # every other key as published
        if key not in cut:
            assert c[key] == value, key
    assert c["source"] == row["source_url"]
    entry = {e["name"]: e for e in cell.benchmark["configs"]}[c["name"]]
    assert set(entry["reduced"]) == cut
    for key, r in c["reduced"].items():
        assert r["to"] == c[key] < r["from"] == row["config"][key], key
    assert c["expert_parallel"] == {"routed_experts": 256, "ranks": 8, "rank": 0}
    assert arch.share(c) == (256, 0, 32)
    assert arch.layer_types(c) == ("sliding_attention",) * 3 + (
        "full_attention", "sliding_attention")
    assert arch.total_params(c) == TOTAL == 4_321_903_872
    e = c["job"]["engine"]
    assert (e["max_num_seqs"], e["max_model_len"], e["prefill_bucket_min"],
            e["expect_experts"], e["expect_routed_experts"]) == (
        32, 16896, 256, 32, 256)
    assert e["max_model_len"] == c["max_position_embeddings"]
    for key in ("embedding scale", "per-head q/k norm", "attention gate",
                "position embedding", "sandwich norm", "router", "initializer",
                "page_size", "torch_dtype"):
        assert key in c["assumed"], key


def test_the_mix_is_the_one_the_issue_named(cell):
    from benchmarks import traffic

    mix = cell.mix
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 1.0, "min": 128, "max": 16384}
    assert mix["max_tokens"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.5, "min": 32, "max": 512}
    assert (mix["lead_s"], mix["end"], mix["temperature"]) == (30.0, "abandon", 0.0)
    sizes = traffic.stratified(mix["prompt_tokens"], 450)
    assert round(sum(sizes) / 450) == 3211
    assert traffic.serve_prefill_buckets(mix, 256, 16896) == [
        256, 512, 1024, 2048, 4096, 8192, 16384]


def test_kernel_costs_are_counted_from_the_shapes(cell, arch):
    c = cell.config
    for kernel in ("paged_gqa_decode", "window_gqa_decode"):
        ops, nbytes = arch.kernel_cost(kernel, c, {"max_num_seqs": 32})
        assert (ops, nbytes) == (32 * 128 * H * 4 * HD, 32 * 128 * 2 * KVH * HD * 2)
    assert arch.experts_touched(c, 32) == 12      # 32 x (1 - (252/256)^32)
    assert arch.experts_touched(c, 128) == 27
    ops, nbytes = arch.kernel_cost("moe_gmm_decode", c, {})
    assert ops == 2 * 16 * D * F and nbytes == (12 * D * F + 16 * (D + F)) * 2
    ops, nbytes = arch.kernel_cost("moe_gmm_prefill", c, {})
    assert ops == 2 * 64 * D * F and nbytes == (27 * D * F + 64 * (D + F)) * 2
    with pytest.raises(KeyError):
        arch.kernel_cost("mla_decode", c, {})


def test_the_new_metrics_read_through_the_cell(cell):
    for name, num, den in (
            ("moe.held_share", "moe_decode_assignments",
             "moe_decode_routed_assignments"),
            ("window.live_tokens_per_step", "window_live_tokens",
             "decode_steps")):
        with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
            raw = json.load(f)
        assert raw == {"reduce": "counter_ratio", "args": {"num": num, "den": den}}
        assert raw["reduce"] in reduce.REDUCTIONS and cell.reader(name) == raw
        entry = {m["name"]: m for m in cell.per_layer()}[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["layer"] in {m["layer"] for m in cell.benchmark["per_layer"]
                                  if m["name"] != name}
    window = {"moe_decode_assignments": 16 * 4000, "decode_steps": 1000,
              "moe_decode_routed_assignments": 128 * 4000,
              "window_live_tokens": 4 * 1000 * 60000,
              "shared_kv_live_tokens": 1000 * 100000}
    ctx = {"trace": None, "spans": {}, "counters": window, "facts": {}}
    got = {k: v["value"] for k, v in cell.per_layer_values(ctx).items()}
    assert got["moe.held_share"] == 0.125
    assert got["window.live_tokens_per_step"] == 240000
    assert got["attn.live_tokens_per_step"] == 100000
    # the parent's engine has no routed count: the ratio is left out, no raise
    ctx["counters"] = {"decode_steps": 10, "moe_decode_assignments": 5}
    got = cell.per_layer_values(ctx)
    assert "moe.held_share" not in got
    assert got["window.live_tokens_per_step"]["value"] == 0


@pytest.fixture(scope="module")
def tiny(arch):
    """A small model under the reference's own parameter names, drawn here:
    16 routed experts of which experts 4-7 are held."""
    rng = np.random.default_rng(0)
    d, Hh, KV, hd, f, R, held = 32, 4, 2, 16, 24, 16, 4
    w = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)  # noqa: E731
    layers = []
    for i in range(5):
        lp = {n: 1 + w(d) for n in ("input_layernorm", "post_attention_layernorm",
                                    "pre_mlp_layernorm", "post_mlp_layernorm")}
        lp.update(q_proj=w(d, Hh * hd), k_proj=w(d, KV * hd), v_proj=w(d, KV * hd),
                  gate_proj_attn=w(d, Hh * hd), o_proj=w(Hh * hd, d),
                  q_norm=1 + w(hd), k_norm=1 + w(hd))
        if i == 0:
            lp.update(gate_proj=w(d, 2 * f), up_proj=w(d, 2 * f),
                      down_proj=w(2 * f, d))
        else:
            lp.update(router=w(d, R), expert_bias=w(R) * 0.1,
                      gate_proj=w(held, d, f), up_proj=w(held, d, f),
                      down_proj=w(held, f, d), shared_gate_proj=w(d, f),
                      shared_up_proj=w(d, f), shared_down_proj=w(f, d))
        layers.append(lp)
    params = {"embed_tokens": w(64, d), "norm": 1 + w(d), "lm_head": w(d, 64),
              "layers": layers}
    rcfg = {"num_attention_heads": Hh, "num_key_value_heads": KV, "head_dim": hd,
            "rope_theta": 10000, "rms_norm_eps": 1e-5, "num_experts_per_tok": 2,
            "route_norm": True, "route_scale": 2.448, "sliding_window": 4,
            "layer_types": ("sliding_attention",) * 3 + (
                "full_attention", "sliding_attention"),
            "first_expert": 4, "embed_scale": d ** 0.5,
            "rotated": ("sliding_attention",), "qk_norm": True,
            "attention_gate": True, "sandwich_norm": True}
    return params, rcfg


def test_reference_is_causal_and_sees_past_the_window_through_the_full_layer(
        arch, tiny):
    params, rcfg = tiny
    toks = np.random.default_rng(1).integers(0, 64, (1, 20))
    full = arch.forward(params, jnp.asarray(toks), rcfg)
    assert full.shape == (1, 20, 64)
    other = toks.copy()
    other[0, 15] = (other[0, 15] + 1) % 64
    moved = arch.forward(params, jnp.asarray(other), rcfg)
    np.testing.assert_allclose(moved[:, :15], full[:, :15], atol=1e-5)
    assert float(jnp.abs(moved[:, 15:] - full[:, 15:]).max()) > 1e-3
    early = toks.copy()
    early[0, 0] = (early[0, 0] + 1) % 64
    assert float(jnp.abs(arch.forward(params, jnp.asarray(early), rcfg)[:, -1]
                         - full[:, -1]).max()) > 1e-5
    np.testing.assert_allclose(
        arch.forward(params, jnp.asarray(toks), rcfg, last=3), full[:, -3:],
        atol=1e-6)
    loss = arch.loss(params, jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:]),
                     rcfg)
    assert np.isfinite(float(loss)) and float(loss) > 0


def test_reference_share_is_the_routing_written_out(arch, tiny):
    """``routed_experts`` against the same sum in numpy, a token and an expert
    at a time: top-2 of sigmoid + bias over all 16, the weights renormalised
    and scaled, and only experts 4-7 computed."""
    params, rcfg = tiny
    lp = params["layers"][2]
    h = np.random.default_rng(2).normal(size=(7, 32)).astype(np.float32)
    got = np.asarray(arch.routed_experts(jnp.asarray(h), lp, rcfg))
    s = 1 / (1 + np.exp(-(h @ np.asarray(lp["router"]))))
    silu = lambda t: t / (1 + np.exp(-t))   # noqa: E731
    want = np.zeros_like(h)
    held_rows = 0
    for t in range(7):
        top = np.argsort(-(s[t] + np.asarray(lp["expert_bias"])))[:2]
        for e in top:
            if 4 <= e < 8:
                held_rows += 1
                g, u, dn = (np.asarray(lp[n][e - 4]) for n in (
                    "gate_proj", "up_proj", "down_proj"))
                want[t] += s[t, e] / (s[t, top].sum() + 1e-20) * 2.448 * (
                    (silu(h[t] @ g) * (h[t] @ u)) @ dn)
    assert 0 < held_rows < 14
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
