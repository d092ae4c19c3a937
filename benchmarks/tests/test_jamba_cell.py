"""``benchmarks/architectures/jamba.py`` reached the way the harness reaches it
(through the resolver, from the committed configuration file), against counts
made by hand from the published shapes and ISSUE 60's numbers, and its Mamba-1
mixer against the sums written out in numpy (no program is imported: the
program is held to this reference in ``tests/test_jamba.py``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import registry
from benchmarks.registry import HERE, REPO, Cell
from benchmarks.trace import reduce

CELL = "ai21-jamba2-3b.reasoning-saturated"
D, INNER, N, R, K, F, V = 2560, 5120, 16, 160, 4, 8192, 65536
H, KVH, HD, SLOTS = 20, 1, 128, 192
MAMBA = (D * 2 * INNER + INNER * (R + 2 * N) + R * INNER + INNER * D
         + INNER * (K + 1 + 1 + N + 1) + R + 2 * N)
ATTENTION = 2 * D * (H + KVH) * HD
MLP = 3 * D * F + 2 * D
TOTAL = 26 * (MAMBA + MLP) + 2 * (ATTENTION + MLP) + V * D + D
STATE = INNER * N                                  # floats a slot and layer
NEW = ("ssm_step_roofline", "mamba.step_dev_ms", "mamba.live_slot_share")


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
               if r["name"] == "AI21-Jamba2-3B"][0]
    assert set(c["reduced"]) == {"max_position_embeddings"}   # context alone
    for key, value in row["config"].items():   # every other key as published
        if key not in c["reduced"]:
            assert c[key] == value, key
    assert c["source"] == row["source_url"]
    entry = {e["name"]: e for e in cell.benchmark["configs"]}[c["name"]]
    assert entry["reduced"] == ["max_position_embeddings"]
    assert entry["source"] == c["source"]
    cut = c["reduced"]["max_position_embeddings"]
    assert cut["to"] == c["max_position_embeddings"] == 3072
    assert cut["from"] == row["config"]["max_position_embeddings"] == 262144
    over = arch.program_overrides(c, 3072)
    assert over["layer_kinds"] == tuple(
        "full" if i in (7, 21) else "mamba" for i in range(28))
    assert over["block"] == "rms" and over["rope_kinds"] == ()
    assert over["ssm_inner_norms"] is True and over["tie_embeddings"]
    assert (over["n_layers"], over["d_model"], over["n_heads"],
            over["n_kv_heads"], over["d_ff"], over["ssm_inner"],
            over["ssm_state"], over["ssm_conv"], over["ssm_dt_rank"],
            over["vocab_size"], over["norm_eps"]) == (
                28, D, H, KVH, F, INNER, N, K, R, V, 1e-6)
    # ISSUE 60's arithmetic: 104.1 M a Mamba layer, 76.7 M an attention
    # layer, 167.8 M the tied table, 3.03 B parameters, 6.06 GB
    assert round((MAMBA + MLP) / 1e6, 1) == 104.2
    assert round((ATTENTION + MLP) / 1e6, 1) == 76.7
    assert round(V * D / 1e6, 1) == 167.8
    assert arch.total_params(c) == TOTAL and round(TOTAL / 1e9, 2) == 3.03
    assert round(2 * TOTAL / 1e9, 2) == 6.06
    e = c["job"]["engine"]
    assert e == {"max_num_seqs": SLOTS, "max_model_len": 3072,
                 "page_size": 512, "prefill_bucket_min": 128,
                 "expect_state_layers": 26, "expect_ssm_inner_norms": True}
    assert e["max_model_len"] == c["max_position_embeddings"]
    # what the fullest device holds by the arguments alone: over 8 GB
    state = SLOTS * 26 * STATE * 4
    tails = SLOTS * 26 * (K - 1) * INNER * 2
    pages = (1 + SLOTS * 6) * 512 * 2 * (2 * KVH * HD * 2)
    assert round(state / 1e9, 2) == 1.64 and round(tails / 1e9, 2) == 0.15
    assert round(pages / 1e9, 2) == 0.60
    assert 2 * TOTAL + state + tails + pages > 8.4e9
    for key in ("torch_dtype", "layer pattern", "mamba mixer", "inner norms",
                "attention", "state precision", "initializer", "page_size",
                "max_num_seqs"):
        assert key in c["assumed"], key
    assert set(c["initializer"]) == {"attention", "mlp", "ssm_proj", "ssm_x",
                                     "embedding"}
    assert c["departures"] and c["stands_for"]


def test_the_cell_is_in_the_lists_it_reports_to(cell):
    b = cell.benchmark
    assert CELL in {w["name"] for w in b["workloads"]} and cell.chips == 1
    serve = {m["name"]: m for m in b["end_to_end"]}["serve_tokens_per_s"]
    assert CELL in serve["workloads"]
    listed = {m["name"] for m in cell.per_layer()}
    assert set(NEW) <= listed
    assert {"serve.replica_up_s", "engine.tokens_per_step",
            "engine.host_ms_per_step", "model.decode_dev_ms",
            "model.prefill_dev_ms", "model.sample_dev_ms",
            "engine.decode_riding_share", "engine.slot_live_share",
            "device.idle_share.saturated", "flash.q_skipped_share",
            "ssm_scan_roofline", "ssm.scan_dev_ms",
            "paged_gqa_decode_roofline", "attn.shared_decode_dev_ms",
            "attn.live_tokens_per_step", "attn.read_per_live"} <= listed
    # cell 7's XLA step is found by its shapes: not this cell's
    assert "ssm.step_dev_ms" not in listed
    assert not any(name.split(".")[0].split("_")[0] in (
        "kda", "ssd", "moe", "mla", "retention", "window", "conv")
        for name in listed)


def test_the_mix_is_the_one_the_issue_named(cell, arch):
    from benchmarks import traffic

    mix = cell.mix
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["max_tokens"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.7, "min": 64, "max": 2048}
    assert (mix["end"], mix["temperature"], mix["lead_s"]) == (
        "abandon", 0.0, 30.0)
    assert traffic.serve_prefill_buckets(mix, 128, 3072) == [
        128, 256, 512, 1024]
    assert mix["prompt_tokens"]["min"] == arch.LEAST_PROMPT
    assert arch.LEAST_BUCKET == 128
    assert mix["prompt_tokens"]["max"] + mix["max_tokens"]["max"] == 3072
    assert "sweep" in mix["rate_why"] and mix["arrival"]["rate_per_s"] > 0


def test_kernel_costs_are_counted_from_the_shapes(cell, arch):
    c = cell.config
    # 327,680 B a slot and layer; 1.64 GB of state each way a step
    assert 4 * STATE == 327_680
    assert round(26 * SLOTS * 4 * STATE / 1e9, 2) == 1.64
    ops, nbytes = arch.kernel_cost("ssm_step", c, {"max_num_seqs": SLOTS})
    operands = 4 * (3 * INNER + 2 * N)           # dt, a, y rows; B, C
    assert nbytes == SLOTS * (2 * 4 * STATE + operands)
    assert round(nbytes / 1e6, 2) == 137.65
    assert round(nbytes / 819e9 * 1e3, 3) == 0.168            # ms a layer
    assert ops == 7 * SLOTS * STATE
    assert ops / 197e12 < nbytes / 819e9                      # bound by bytes
    assert arch.kernel_cost("ssm_step", c, {})[1] == nbytes   # the file's slots
    ops, nbytes = arch.kernel_cost("ssm_scan", c, {})
    assert ops == 7 * 128 * STATE
    assert nbytes == 128 * operands + 4 * STATE
    ops, nbytes = arch.kernel_cost("paged_gqa_decode", c, {})
    assert nbytes == SLOTS * 32 * 512            # 512 B a position, ONE key head
    assert ops == SLOTS * 32 * H * 4 * HD
    with pytest.raises(KeyError):
        arch.kernel_cost("ssd_step", c, {})


def test_the_new_metrics_read_through_the_cell(cell):
    per_layer = {m["name"]: m for m in cell.per_layer()}
    for name in NEW:
        with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
            assert cell.reader(name) == json.load(f)
        entry = per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
    kinds = {"ssm_step (f32[192,5120], f32[26,192,16,5120])": [1.3, 5200.0],
             "ssm_riding (f32[192,5120], f32[26,192,16,5120])": [0.13, 520.0],
             "ssm_scan (f32[1,128,5120], f32[1,16,5120])": [0.5, 1040.0],
             "paged_gqa_decode (bf16[192,1,32,128])": [0.02, 400.0],
             "fusion bf16[192,8192]": [3.0, 5600.0]}
    ctx = {"trace": {"op_kinds": kinds, "window_s": 4.0, "busy_s": 3.9,
                     "modules": {"jit_decode_step": {"count": 200.0,
                                                     "total_s": 3.0},
                                 "jit_prefill": {"count": 40.0,
                                                 "total_s": 0.8}}},
           "spans": {}, "facts": {"peak_flops_per_s": 197e12,
                                  "peak_hbm_bytes_per_s": 819e9,
                                  "max_num_seqs": SLOTS},
           "counters": {"ssm_step_live_slots": 26 * 180 * 220,
                        "ssm_step_slots": 26 * SLOTS * 220, "ssm_steps": 220}}
    got = cell.per_layer_values(ctx)
    arch = cell.architecture()
    assert got["mamba.live_slot_share"]["value"] == 180 / SLOTS
    assert got["mamba.step_dev_ms"]["value"] == pytest.approx(1.3 / 200 * 1e3)
    assert got["ssm.scan_dev_ms"]["value"] == pytest.approx(0.5 / 40 * 1e3)
    least = arch.kernel_cost("ssm_step", cell.config, ctx["facts"])[1] / 819e9
    # the decode steps' calls alone: a riding call is not this count's
    assert got["ssm_step_roofline"]["value"] == pytest.approx(
        100 * 5200 * least / 1.3)
    assert 0 < got["ssm_step_roofline"]["value"] < 100
    assert 0 < got["ssm_scan_roofline"]["value"] < 100
    assert 0 < got["paged_gqa_decode_roofline"]["value"] < 100
    # a program without them (the parent's): left out, no raise
    ctx["trace"]["op_kinds"] = {"fusion bf16[192,8192]": [3.0, 5600.0]}
    ctx["counters"] = {}
    assert not set(NEW) & set(cell.per_layer_values(ctx))
    # every metric the cell lists has its reader's file
    for m in cell.per_layer():
        assert cell.reader(m["name"])["reduce"] in reduce.REDUCTIONS


def test_the_adapter_refuses_what_it_does_not_implement(cell, arch):
    c = cell.config
    for key, value in (("num_experts", 16), ("num_experts_per_tok", 2),
                       ("sliding_window", 4096), ("mamba_proj_bias", True),
                       ("mamba_conv_bias", False),
                       ("tie_word_embeddings", False), ("hidden_act", "gelu"),
                       ("model_type", "mamba")):
        with pytest.raises(ValueError, match=key):
            arch.program_overrides(dict(c, **{key: value}), 3072)
    with pytest.raises(ValueError, match="no layer is attention"):
        arch.program_overrides(dict(c, attn_layer_offset=14), 3072)


# -- the reference against the sums written out ---------------------------------------


def test_mamba_mixer_is_the_sums_it_says(arch):
    """``mamba`` against numpy, a position and a channel at a time: the taps
    over the last four inputs with zeros before the first, the three norms,
    softplus, the recurrence with its decay, ``D``, the gate."""
    rng = np.random.default_rng(0)
    S, d, inner, n, r, k = 6, 5, 8, 3, 2, 4
    lp = {"in_proj": rng.normal(size=(d, 2 * inner)),
          "conv_weight": rng.normal(size=(k, inner)),
          "conv_bias": rng.normal(size=inner),
          "x_proj": rng.normal(size=(inner, r + 2 * n)),
          "dt_layernorm": 1 + 0.1 * rng.normal(size=r),
          "b_layernorm": 1 + 0.1 * rng.normal(size=n),
          "c_layernorm": 1 + 0.1 * rng.normal(size=n),
          "dt_proj": rng.normal(size=(r, inner)),
          "dt_bias": rng.normal(size=inner) - 2,
          "A_log": rng.normal(size=(inner, n)), "D": rng.normal(size=inner),
          "out_proj": rng.normal(size=(inner, d))}
    h = rng.normal(size=(1, S, d))
    silu = lambda x: x / (1 + np.exp(-x))   # noqa: E731
    norm = lambda x, w: x / np.sqrt(np.mean(x * x) + 1e-6) * w   # noqa: E731
    uz = h[0] @ lp["in_proj"]
    u, z = uz[:, :inner], uz[:, inner:]
    s, want = np.zeros((inner, n)), np.zeros((S, d))
    for t in range(S):
        a = silu(sum(lp["conv_weight"][j] * u[t - k + 1 + j]
                     for j in range(k) if t - k + 1 + j >= 0)
                 + lp["conv_bias"])
        x = a @ lp["x_proj"]
        dt = np.log1p(np.exp(norm(x[:r], lp["dt_layernorm"]) @ lp["dt_proj"]
                             + lp["dt_bias"]))
        Bm = norm(x[r:r + n], lp["b_layernorm"])
        Cm = norm(x[r + n:], lp["c_layernorm"])
        s = np.exp(dt[:, None] * -np.exp(lp["A_log"])) * s \
            + (dt * a)[:, None] * Bm[None]
        want[t] = ((s @ Cm + lp["D"] * a) * silu(z[t])) @ lp["out_proj"]
    f32 = lambda a: jnp.asarray(a, jnp.float32)   # noqa: E731
    rcfg = {"rms_norm_eps": 1e-6, "mamba_d_state": n, "mamba_dt_rank": r,
            "without": ()}
    with jax.default_matmul_precision("highest"):
        got = arch.mamba(f32(h), jax.tree.map(f32, lp), rcfg)
        np.testing.assert_allclose(np.asarray(got)[0], want, rtol=2e-4,
                                   atol=2e-4)
        for part in ("state", "D", "dt_layernorm", "b_layernorm",
                     "c_layernorm", "dt_bias", "conv_bias", "gate"):
            spoiled = arch.mamba(f32(h), jax.tree.map(f32, lp),
                                 dict(rcfg, without=(part,)))
            assert np.abs(np.asarray(spoiled)[0] - want).max() > 1e-2, part
