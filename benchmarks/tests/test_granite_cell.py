"""``benchmarks/architectures/granitemoehybrid.py`` reached the way the harness
reaches it (through the resolver, from the committed configuration file),
against counts made by hand from the published shapes and ISSUE 45's numbers,
and its plain reference against the properties the equations promise (no
program is imported: the program is held to this reference in
``tests/test_granite_hybrid.py``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import registry
from benchmarks.registry import HERE, REPO, Cell
from benchmarks.trace import reduce

CELL = "granite-4.0-h-small.ragchat-saturated"
D, H, KVH, HD, F, FS, V = 4096, 32, 8, 128, 768, 1536, 50176
MH, MP, N, K, I = 128, 64, 128, 4, 8192
MIXER = D * (2 * I + 2 * N + MH) + I * D + (I + 2 * N) * (K + 1) + 3 * MH + I
ATTN = 2 * D * (H + KVH) * HD
MLP = D * 72 + 36 * 3 * D * F + 3 * D * FS
TOTAL = 9 * (MIXER + MLP + 2 * D) + (ATTN + MLP + 2 * D) + V * D + D
NEW = ("ssd_scan_roofline", "ssd.scan_dev_ms", "ssd_step_roofline",
       "ssd.step_dev_ms", "ssd.live_slot_share")


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
               if r["name"] == "granite-4.0-h-small"][0]
    cut = set(c["reduced"])
    assert cut == {"num_hidden_layers", "num_local_experts", "vocab_size",
                   "max_position_embeddings"}
    for key, value in row["config"].items():   # every other key as published
        if key not in cut:
            assert c[key] == value, key
    assert c["source"] == row["source_url"]
    entry = {e["name"]: e for e in cell.benchmark["configs"]}[c["name"]]
    assert set(entry["reduced"]) == cut
    for key, r in c["reduced"].items():
        assert r["to"] == c[key] < r["from"] == row["config"][key], key
    assert (c["hidden_size"], c["mamba_n_heads"], c["mamba_d_head"],
            c["mamba_d_state"], c["mamba_d_conv"]) == (D, MH, MP, N, K)
    assert (c["num_attention_heads"], c["num_key_value_heads"],
            c["intermediate_size"], c["num_experts_per_tok"],
            c["shared_intermediate_size"]) == (H, KVH, F, 10, FS)
    assert (c["embedding_multiplier"], c["residual_multiplier"],
            c["attention_multiplier"], c["logits_scaling"]) == (
                12, 0.22, 0.0078125, 16)
    assert arch.share(c) == (72, 0, 36)
    types = arch.layer_types(c)
    assert types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    over = arch.program_overrides(c, 4608)
    assert over["layer_kinds"] == ("mamba2",) * 5 + ("full",) + ("mamba2",) * 4
    assert (over["ssm_inner"], over["ssm_heads"], over["n_shared_experts"],
            over["experts_held"], over["logit_scale"]) == (I, MH, 2, (0, 36),
                                                           1 / 16)
    # ISSUE 45's arithmetic: 102.29 M a mixer, 461.2 M a mamba layer, 4,757 M
    assert round(MIXER / 1e6, 2) == 102.29 and round(ATTN / 1e6, 2) == 41.94
    assert round((MIXER + MLP + 2 * D) / 1e6, 1) == 461.2
    assert arch.total_params(c) == TOTAL and round(TOTAL / 1e6) == 4757
    e = c["job"]["engine"]
    assert e == {"max_num_seqs": 40, "max_model_len": 4608, "page_size": 512,
                 "prefill_bucket_min": 256, "expect_experts": 36,
                 "expect_routed_experts": 72, "expect_state_layers": 9,
                 "expect_ssm_heads": 128}
    assert e["max_model_len"] == c["max_position_embeddings"]
    # the state ISSUE 45 reckoned: 4.19 MB a slot and layer, 1.51 GB in all;
    # 4,096 B a position of the one attention layer, 0.76 GB of pages
    assert MH * MP * N * 4 == 4_194_304
    assert round(9 * 40 * MH * MP * N * 4 / 1e9, 2) == 1.51
    assert 2 * KVH * HD * 2 == 4096
    assert round(40 * 9 * 512 * 4096 / 1e9, 2) == 0.75
    for key in ("torch_dtype", "state precision", "mamba2 mixer", "attention",
                "multipliers", "experts", "initializer", "page_size"):
        assert key in c["assumed"], key
    assert set(c["initializer"]) == {"attention", "mlp", "experts", "mamba",
                                     "embedding"}


def test_the_mix_is_the_one_the_issue_named(cell, arch):
    from benchmarks import traffic

    mix = cell.mix
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.7, "min": 128, "max": 4096}
    assert mix["max_tokens"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.5, "min": 16, "max": 512}
    assert (mix["lead_s"], mix["end"], mix["temperature"]) == (30.0, "abandon", 0.0)
    assert traffic.serve_prefill_buckets(mix, 256, 4608) == [
        256, 512, 1024, 2048, 4096]
    assert mix["prompt_tokens"]["min"] == arch.LEAST_PROMPT
    assert traffic.serve_prefill_buckets(mix, 256, 4608)[0] == arch.LEAST_BUCKET
    assert mix["prompt_tokens"]["max"] + mix["max_tokens"]["max"] == 4608
    assert "sweep" in mix["rate_why"] and mix["arrival"]["rate_per_s"] > 0


def test_kernel_costs_are_counted_from_the_shapes(cell, arch):
    c = cell.config
    state = MH * MP * N
    # a position's operands: x | B | C in bfloat16, dt and y in float32
    operands = (I + 2 * N) * 2 + 4 * (MH + I)
    # 40 slots x 4.19 MB read and written: 335.5 MB of state (ISSUE 45: 0.41
    # ms at 819 GB/s) + 2 MB of operands; five operations a state element
    ops, nbytes = arch.kernel_cost("ssd_step", c, {})
    assert (ops, nbytes) == (5 * 40 * state, 40 * (8 * state + operands))
    assert 40 * 8 * state == 335_544_320 and round(nbytes / 819e9 * 1e3, 2) == 0.41
    assert ops / 197e12 < nbytes / 819e9
    # the least bucket, one row of 256 positions: the final state out once
    ops, nbytes = arch.kernel_cost("ssd_scan", c, {})
    assert (ops, nbytes) == (5 * 256 * state, 256 * operands + 4 * state)
    assert ops / 197e12 < nbytes / 819e9
    # 40 slots x the least prompt of 128; 4,096 bytes a position
    ops, nbytes = arch.kernel_cost("paged_gqa_decode", c, {"max_num_seqs": 40})
    assert (ops, nbytes) == (40 * 128 * H * 2 * 2 * HD, 40 * 128 * 4096)
    # 40 rows x top-10, half of them held: 200 rows over 35 of the 36 held
    assert arch.experts_touched(c, 40) == 35
    assert arch.experts_touched(c, 128) == 35      # 36 x (1 - 4.9e-9), down
    ops, nbytes = arch.kernel_cost("moe_gmm_decode", c, {})
    assert ops == 2 * 200 * D * F
    assert nbytes == (35 * D * F + 200 * (D + F)) * 2
    assert ops / 197e12 < nbytes / 819e9
    ops, nbytes = arch.kernel_cost("moe_gmm_prefill", c, {})
    assert ops == 2 * 640 * D * F
    with pytest.raises(KeyError):
        arch.kernel_cost("window_gqa_decode", c, {})


def test_the_new_metrics_read_through_the_cell(cell):
    per_layer = {m["name"]: m for m in cell.per_layer()}
    for name in NEW:
        with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
            assert cell.reader(name) == json.load(f)
        entry = per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
    assert [m["name"] for m in cell.benchmark["per_layer"]][-5:] == list(NEW)
    kinds = {"ssd_step (f32[40,1,8192], f32[9,40,128,8192])": [0.9, 1800.0],
             "ssd_scan (f32[1,1024,8192], f32[1,128,8192])": [0.09, 450.0],
             "ssd_riding (f32[40,1,8192], f32[9,40,128,8192])": [0.2, 400.0],
             "fusion bf16[40,16768]": [7.0, 110.0]}
    ctx = {"trace": {"op_kinds": kinds, "window_s": 2.0, "busy_s": 1.9,
                     "modules": {"jit_decode_step": {"count": 200.0,
                                                     "total_s": 1.0},
                                 "jit_prefill": {"count": 50.0,
                                                 "total_s": 0.8}}},
           "spans": {}, "facts": {"peak_flops_per_s": 197e12,
                                  "peak_hbm_bytes_per_s": 819e9},
           "counters": {"ssd_step_slots": 360 * 7, "ssd_step_live_slots": 360 * 6}}
    got = cell.per_layer_values(ctx)
    assert got["ssd.step_dev_ms"]["value"] == pytest.approx(4.5)
    assert got["ssd.scan_dev_ms"]["value"] == pytest.approx(1.8)
    assert got["ssd.live_slot_share"]["value"] == pytest.approx(6 / 7)
    arch = cell.architecture()
    step = arch.kernel_cost("ssd_step", cell.config, {})[1] / 819e9
    assert got["ssd_step_roofline"]["value"] == pytest.approx(
        100 * 1800 * step / 0.9)
    assert 0 < got["ssd_scan_roofline"]["value"] < 100
    # a program without such layers (the parent's): left out, no raise
    ctx["trace"]["op_kinds"] = {"fusion bf16[40,16768]": [7.0, 110.0]}
    ctx["counters"] = {}
    assert not set(NEW) & set(cell.per_layer_values(ctx))
    # every metric the cell lists has its reader's file
    for m in cell.per_layer():
        assert cell.reader(m["name"])["reduce"] in reduce.REDUCTIONS


@pytest.fixture(scope="module")
def tiny(arch):
    """One small Mamba-2 layer under the reference's own parameter names."""
    rng = np.random.default_rng(0)
    d, heads, head, state, taps = 16, 4, 8, 8, 4
    inner, xbc = heads * head, heads * head + 2 * state
    draw = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)   # noqa: E731
    lp = {"in_proj": draw(d, inner + xbc + heads),
          "conv_weight": draw(taps, xbc), "conv_bias": draw(xbc),
          "dt_bias": draw(heads) - 2.0, "A_log": draw(heads), "D": draw(heads) + 1,
          "mixer_norm": draw(inner) + 1, "out_proj": draw(inner, d)}
    rcfg = {"mamba_n_heads": heads, "mamba_d_state": state,
            "rms_norm_eps": 1e-5, "without": ()}
    return lp, rcfg, draw(2, 11, d)


def test_mixer_is_the_equations_written_out(arch, tiny):
    """``mamba2`` against the same sums in numpy, a position, a head and a
    channel at a time."""
    lp, rcfg, h = tiny
    p = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    H_, N_, x_in = 4, 8, np.asarray(h, np.float64)
    inner = p["out_proj"].shape[0]
    P_ = inner // H_
    want = np.zeros(x_in.shape)
    silu = lambda a: a / (1 + np.exp(-a))   # noqa: E731
    for b in range(x_in.shape[0]):
        zxd = x_in[b] @ p["in_proj"]
        z, raw, dt = zxd[:, :inner], zxd[:, inner:2 * inner + 2 * N_], \
            zxd[:, 2 * inner + 2 * N_:]
        S = np.zeros((H_, P_, N_))
        for t in range(x_in.shape[1]):
            conv = p["conv_bias"].copy()
            for k in range(4):          # tap 3 is the position itself
                if t - 3 + k >= 0:
                    conv += p["conv_weight"][k] * raw[t - 3 + k]
            a = silu(conv)
            x, B, C = a[:inner].reshape(H_, P_), a[inner:inner + N_], a[inner + N_:]
            step = np.log1p(np.exp(dt[t] + p["dt_bias"]))
            y = np.zeros((H_, P_))
            for n in range(H_):
                S[n] = np.exp(-step[n] * np.exp(p["A_log"][n])) * S[n] \
                    + step[n] * np.outer(x[n], B)
                y[n] = S[n] @ C + p["D"][n] * x[n]
            y = y.reshape(-1) * silu(z[t])
            y = y / np.sqrt(np.mean(y * y) + 1e-5) * p["mixer_norm"]
            want[b, t] = y @ p["out_proj"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(arch.mamba2(h, lp, rcfg))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_mixer_is_causal_and_every_part_shows(arch, tiny):
    lp, rcfg, h = tiny
    base = np.asarray(arch.mamba2(h, lp, rcfg))
    later = np.asarray(arch.mamba2(h.at[:, 7:].add(1.0), lp, rcfg))
    np.testing.assert_allclose(later[:, :7], base[:, :7], rtol=1e-5, atol=1e-6)
    assert np.abs(later[:, 7:] - base[:, 7:]).max() > 1e-2
    for part in ("D", "dt_bias", "conv_bias", "gate", "state", "float32_state"):
        spoiled = np.asarray(arch.mamba2(h, lp, dict(rcfg, without=(part,))))
        assert np.abs(spoiled - base).max() > 1e-3, part


def test_routing_is_the_top_k_logits_softmaxed(arch):
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(5, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 12)), jnp.float32)
    gates, experts = arch.routing(h, router, {"num_experts_per_tok": 3})
    logits = np.asarray(h) @ np.asarray(router)
    for t in range(5):
        top = np.argsort(-logits[t])[:3]
        assert set(np.asarray(experts[t]).tolist()) == set(top.tolist())
        w = np.exp(logits[t][np.asarray(experts[t])])
        np.testing.assert_allclose(gates[t], w / w.sum(), rtol=1e-5)
