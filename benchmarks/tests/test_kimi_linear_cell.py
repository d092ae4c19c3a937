"""``benchmarks/architectures/kimi_linear.py`` reached the way the harness
reaches it (through the resolver, from the committed configuration file),
against counts made by hand from the published shapes and ISSUE 49's numbers,
and its plain reference against the properties the equations promise (no
program is imported: the program is held to this reference in
``tests/test_kimi_linear.py``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import registry
from benchmarks.registry import HERE, REPO, Cell
from benchmarks.trace import reduce

CELL = "kimi-linear-48b-a3b.longctx-saturated-b64"
D, H, F, FD, V, R, NOPE, ROPE, DV = 2304, 32, 1024, 9216, 40960, 512, 128, 64, 128
KH, KD, TAPS, RANK = 32, 128, 4, 128
WIDE = KH * KD
KDA = (4 * D * WIDE + 2 * (D * RANK + RANK * WIDE) + D * KH
       + 3 * WIDE * TAPS + WIDE + KH + KD)
MLA = D * H * (NOPE + ROPE) + D * (R + ROPE) + R * H * (NOPE + DV) + H * DV * D + R
SPARSE = D * 256 + 256 + 3 * D * F * (64 + 1)
DENSE = 3 * D * FD
TOTAL = (6 * KDA + 2 * MLA + DENSE + 7 * SPARSE + 8 * 2 * D + 2 * V * D + D)
NEW = ("kda_scan_roofline", "kda.scan_dev_ms", "kda_step_roofline",
       "kda.step_dev_ms", "kda.live_slot_share")


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
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct"][0]
    cut = set(c["reduced"])
    assert cut == {"num_hidden_layers", "num_experts", "vocab_size",
                   "model_max_length"}
    for key, value in row["config"].items():   # every other key as published
        if key not in cut:
            assert c[key] == value, key
    assert c["source"] == row["source_url"]
    entry = {e["name"]: e for e in cell.benchmark["configs"]}[c["name"]]
    assert set(entry["reduced"]) == cut and entry["source"] == c["source"]
    for key, r in c["reduced"].items():
        assert r["to"] == c[key] < r["from"] == row["config"][key], key
    lin = c["linear_attn_config"]
    assert (c["hidden_size"], lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (D, KH, KD, TAPS)
    assert (c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["num_attention_heads"]) == (R, NOPE, ROPE, DV, H)
    assert (c["moe_intermediate_size"], c["intermediate_size"],
            c["num_experts_per_token"], c["num_shared_experts"],
            c["routed_scaling_factor"]) == (F, FD, 8, 1, 2.446)
    assert arch.share(c) == (256, 0, 64)
    kinds = ("kda", "kda", "kda", "latent") * 2
    assert arch.layer_types(c) == kinds
    over = arch.program_overrides(c, 16896)
    assert over["layer_kinds"] == kinds and over["rope_kinds"] == ()
    assert (over["kda_heads"], over["kda_head_dim"], over["kda_conv"],
            over["kda_gate_rank"], over["experts_held"], over["first_k_dense"],
            over["d_ff_dense"]) == (KH, KD, TAPS, RANK, (0, 64), 1, FD)
    # ISSUE 49's arithmetic: 39.51 M a KDA mixer, 29.11 M a latent one, 3,772 M
    assert round(KDA / 1e6, 2) == 39.51 and round(MLA / 1e6, 2) == 29.11
    assert arch.total_params(c) == TOTAL and round(TOTAL / 1e6) == 3772
    e = c["job"]["engine"]
    assert e == {"max_num_seqs": 64, "max_model_len": 16896, "page_size": 512,
                 "prefill_bucket_min": 256, "expect_experts": 64,
                 "expect_routed_experts": 256, "expect_latent_rank": 512,
                 "expect_state_layers": 6, "expect_kda_heads": 32}
    assert e["max_model_len"] == c["model_max_length"]
    # the state ISSUE 49 reckoned: 2.097 MB a slot and layer and 73.7 KB of
    # tails, 0.83 GB in all; 1,280 B a position and latent layer, 2.77 GB
    assert KH * KD * KD * 4 == 2_097_152 and 3 * 3 * WIDE * 2 == 73_728
    assert round(6 * 64 * (2_097_152 + 73_728) / 1e9, 2) == 0.83
    assert round(2 * 64 * 16896 * 640 * 2 / 1e9, 2) == 2.77
    for key in ("torch_dtype", "state precision", "kda mixer",
                "latent attention", "experts", "initializer", "page_size"):
        assert key in c["assumed"], key
    assert set(c["initializer"]) == {"attention", "kda", "mlp", "experts",
                                     "embedding"}


def test_the_cell_is_in_the_lists_it_reports_to(cell):
    b = cell.benchmark
    assert CELL in {w["name"] for w in b["workloads"]} and cell.chips == 1
    serve = {m["name"]: m for m in b["end_to_end"]}["serve_tokens_per_s"]
    assert CELL in serve["workloads"]
    listed = {m["name"] for m in cell.per_layer()}
    assert set(NEW) <= listed
    assert {"mla_decode_roofline", "mla.live_tokens_per_step",
            "moe_gmm_decode_roofline", "moe.held_share",
            "engine.decode_riding_share"} <= listed
    assert "mla_prefill_flash_roofline" not in listed
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1


def test_the_mix_is_the_one_the_issue_named(cell, arch):
    from benchmarks import traffic

    mix = cell.mix
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                    "sigma": 0.9, "min": 512, "max": 16384}
    assert mix["max_tokens"] == {"dist": "lognormal", "median": 160,
                                 "sigma": 0.6, "min": 32, "max": 512}
    assert (mix["end"], mix["temperature"]) == ("abandon", 0.0)
    assert traffic.serve_prefill_buckets(mix, 256, 16896) == [
        512, 1024, 2048, 4096, 8192, 16384]
    assert mix["prompt_tokens"]["min"] == arch.LEAST_PROMPT
    assert mix["prompt_tokens"]["max"] + mix["max_tokens"]["max"] == 16896
    assert "sweep" in mix["rate_why"] and mix["arrival"]["rate_per_s"] > 0


def test_kernel_costs_are_counted_from_the_shapes(cell, arch):
    c = cell.config
    state = WIDE * KD
    # a position's operands: q, k, v in and o out in bfloat16, g and beta
    # float32
    operands = 4 * WIDE * 2 + 4 * (WIDE + KH)
    # 64 slots x 2.1 MB read and written: 268.4 MB of state (0.33 ms at 819
    # GB/s) + 3.2 MB of operands; seven operations a state element
    ops, nbytes = arch.kernel_cost("kda_step", c, {})
    assert (ops, nbytes) == (7 * 64 * state, 64 * (8 * state + operands))
    assert 64 * 8 * state == 268_435_456 and round(nbytes / 819e9 * 1e3, 2) == 0.33
    assert ops / 197e12 < nbytes / 819e9
    # the least bucket the mix reaches, one row of 512 positions
    ops, nbytes = arch.kernel_cost("kda_scan", c, {})
    assert (ops, nbytes) == (7 * 512 * state, 512 * operands + 4 * state)
    assert ops / 197e12 < nbytes / 819e9
    # 64 slots x the least prompt of 512; 1,152 bytes a position
    ops, nbytes = arch.kernel_cost("mla_decode", c, {"max_num_seqs": 64})
    assert (ops, nbytes) == (64 * 512 * H * 2 * (576 + 512), 64 * 512 * 1152)
    ops, nbytes = arch.kernel_cost("flash_fwd", c, {})
    assert ops == H * 4096 * 4097 // 2 * 2 * (192 + 128)
    # 64 rows x top-8, a quarter of them held: 128 rows; uniform routing of
    # 64 rows apart touches 64 x (1 - (31/32)^64) = 55.6 held experts, of the
    # 16 the count takes for apart 25.5: greedy slots repeat each other
    assert arch.experts_touched(c, 64) == 55 and arch.experts_touched(c, 512) == 63
    assert arch.experts_touched(c, 16) == 25
    ops, nbytes = arch.kernel_cost("moe_gmm_decode", c, {})
    assert ops == 2 * 128 * D * F
    assert nbytes == (25 * D * F + 128 * (D + F)) * 2
    assert ops / 197e12 < nbytes / 819e9
    ops, nbytes = arch.kernel_cost("moe_gmm_prefill", c, {})
    assert ops == 2 * 1024 * D * F
    with pytest.raises(KeyError):
        arch.kernel_cost("ssd_scan", c, {})


def test_the_new_metrics_read_through_the_cell(cell):
    per_layer = {m["name"]: m for m in cell.per_layer()}
    for name in NEW:
        with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
            assert cell.reader(name) == json.load(f)
        entry = per_layer[name]
        assert CELL in entry["workloads"]
        assert entry["moves"] == "serve_tokens_per_s"
    kinds = {"kda_step (f32[64,32,128], f32[6,64,32,128,128])": [0.6, 1200.0],
             "kda_scan (f32[1,4096,4096], f32[1,32,128,128])": [0.9, 300.0],
             "kda_riding (f32[64,32,128], f32[6,64,32,128,128])": [0.2, 400.0],
             "fusion bf16[64,12288]": [7.0, 110.0]}
    ctx = {"trace": {"op_kinds": kinds, "window_s": 2.0, "busy_s": 1.9,
                     "modules": {"jit_decode_step": {"count": 200.0,
                                                     "total_s": 1.0},
                                 "jit_prefill": {"count": 50.0,
                                                 "total_s": 0.8}}},
           "spans": {}, "facts": {"peak_flops_per_s": 197e12,
                                  "peak_hbm_bytes_per_s": 819e9},
           "counters": {"kda_step_slots": 384 * 7, "kda_step_live_slots": 384 * 6}}
    got = cell.per_layer_values(ctx)
    assert got["kda.step_dev_ms"]["value"] == pytest.approx(3.0)
    assert got["kda.scan_dev_ms"]["value"] == pytest.approx(18.0)
    assert got["kda.live_slot_share"]["value"] == pytest.approx(6 / 7)
    arch = cell.architecture()
    step = arch.kernel_cost("kda_step", cell.config, {})[1] / 819e9
    assert got["kda_step_roofline"]["value"] == pytest.approx(
        100 * 1200 * step / 0.6)
    assert 0 < got["kda_scan_roofline"]["value"] < 100
    # a program without such layers (the parent's): left out, no raise
    ctx["trace"]["op_kinds"] = {"fusion bf16[64,12288]": [7.0, 110.0]}
    ctx["counters"] = {}
    assert not set(NEW) & set(cell.per_layer_values(ctx))
    # every metric the cell lists has its reader's file
    for m in cell.per_layer():
        assert cell.reader(m["name"])["reduce"] in reduce.REDUCTIONS


def test_the_adapter_refuses_what_it_does_not_implement(cell, arch):
    c = cell.config
    for key, value in (("num_expert_group", 8), ("topk_group", 4),
                       ("q_lora_rank", 1536), ("mla_use_nope", False),
                       ("rope_scaling", {"type": "yarn", "factor": 32}),
                       ("moe_router_activation_func", "softmax")):
        with pytest.raises(ValueError, match=key):
            arch.program_overrides(dict(c, **{key: value}), 16896)
    with pytest.raises(ValueError, match="expert_parallel"):
        arch.share(dict(c, num_experts=32))


@pytest.fixture(scope="module")
def tiny(arch):
    """One small delta-rule layer under the reference's own parameter names."""
    rng = np.random.default_rng(0)
    d, heads, head, taps = 16, 2, 8, 4
    wide = heads * head
    draw = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)   # noqa: E731
    lp = {n + "_proj": draw(d, wide) for n in "qkv"}
    lp.update({n + "_conv1d": draw(taps, wide) for n in "qkv"})
    lp.update({"f_a_proj": draw(d, head), "f_b_proj": draw(head, wide),
               "dt_bias": draw(wide) - 2.0, "A_log": draw(heads),
               "b_proj": draw(d, heads), "g_a_proj": draw(d, head),
               "g_b_proj": draw(head, wide), "o_norm": draw(head) + 1,
               "o_proj": draw(wide, d)})
    rcfg = {"kda_heads": heads, "rms_norm_eps": 1e-5, "without": ()}
    return lp, rcfg, draw(2, 11, d)


def test_mixer_is_the_equations_written_out(arch, tiny):
    """``kda`` against the same sums in numpy, a position and a head at a
    time."""
    lp, rcfg, h = tiny
    p = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    x_in = np.asarray(h, np.float64)
    heads, head = 2, 8
    silu = lambda a: a / (1 + np.exp(-a))       # noqa: E731
    sigmoid = lambda a: 1 / (1 + np.exp(-a))    # noqa: E731
    want = np.zeros(x_in.shape)
    for b in range(x_in.shape[0]):
        raw = {n: x_in[b] @ p[n + "_proj"] for n in "qkv"}
        f = (x_in[b] @ p["f_a_proj"]) @ p["f_b_proj"] + p["dt_bias"]
        gate = (x_in[b] @ p["g_a_proj"]) @ p["g_b_proj"]
        beta = sigmoid(x_in[b] @ p["b_proj"])
        S = np.zeros((heads, head, head))
        for t in range(x_in.shape[1]):
            conv = {}
            for n in "qkv":
                acc = np.zeros(heads * head)
                for tap in range(4):          # tap 3 is the position itself
                    if t - 3 + tap >= 0:
                        acc += p[n + "_conv1d"][tap] * raw[n][t - 3 + tap]
                conv[n] = silu(acc).reshape(heads, head)
            out = np.zeros((heads, head))
            for n in range(heads):
                q = conv["q"][n] / np.sqrt(np.sum(conv["q"][n] ** 2) + 1e-6) \
                    * head ** -0.5
                k = conv["k"][n] / np.sqrt(np.sum(conv["k"][n] ** 2) + 1e-6)
                g = -np.exp(p["A_log"][n]) * np.log1p(
                    np.exp(f[t].reshape(heads, head)[n]))
                S[n] = np.exp(g)[:, None] * S[n]
                S[n] = S[n] + beta[t, n] * np.outer(
                    k, conv["v"][n] - S[n].T @ k)
                o = S[n].T @ q
                o = o / np.sqrt(np.mean(o * o) + 1e-5) * p["o_norm"]
                out[n] = o * sigmoid(gate[t].reshape(heads, head)[n])
            want[b, t] = out.reshape(-1) @ p["o_proj"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(arch.kda(h, lp, rcfg))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_mixer_is_causal_and_every_part_shows(arch, tiny):
    lp, rcfg, h = tiny
    base = np.asarray(arch.kda(h, lp, rcfg))
    later = np.asarray(arch.kda(h.at[:, 7:].add(1.0), lp, rcfg))
    np.testing.assert_allclose(later[:, :7], base[:, :7], rtol=1e-5, atol=1e-6)
    assert np.abs(later[:, 7:] - base[:, 7:]).max() > 1e-2
    for part in ("beta", "decay", "conv", "out_gate", "k_norm",
                 "float32_state"):
        spoiled = np.asarray(arch.kda(h, lp, dict(rcfg, without=(part,))))
        assert np.abs(spoiled - base).max() > 1e-3, part


def test_routing_is_sigmoid_scores_chosen_with_a_bias_that_does_not_weigh(arch):
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(5, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 12)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=12) * 0.3, jnp.float32)
    rcfg = {"num_experts_per_token": 3, "moe_renormalize": True,
            "routed_scaling_factor": 2.446, "bias_in_gates": False}
    gates, experts = arch.routing(h, router, bias, rcfg)
    scores = 1 / (1 + np.exp(-(np.asarray(h) @ np.asarray(router))))
    for t in range(5):
        top = np.argsort(-(scores[t] + np.asarray(bias)))[:3]
        assert set(np.asarray(experts[t]).tolist()) == set(top.tolist())
        w = scores[t][np.asarray(experts[t])]
        np.testing.assert_allclose(gates[t], 2.446 * w / w.sum(), rtol=1e-5)
