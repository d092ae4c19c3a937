"""``benchmarks/architectures/longcat_flash.py`` reached the way the harness
reaches it (through the resolver, from the committed configuration file),
against counts made by hand from the published shapes and ISSUE 53's numbers,
and its plain reference against the properties the equations promise (no
program is imported: the program is held to this reference in
``tests/test_longcat.py``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import registry
from benchmarks.registry import HERE, REPO, Cell
from benchmarks.trace import reduce

CELL = "longcat-flash-omni.agentturns-saturated-b32"
D, H, F, FD, V, R, RQ, NOPE, ROPE, DV = (6144, 64, 2048, 12288, 16384, 512,
                                         1536, 128, 64, 128)
MLA = (D * RQ + RQ * H * (NOPE + ROPE) + D * (R + ROPE)
       + R * H * (NOPE + DV) + H * DV * D)
MLP, EXPERT, ROUTER = 3 * D * FD, 3 * D * F, D * 768
LAYER = 2 * MLA + 2 * MLP + ROUTER + 16 * EXPERT
TOTAL = 4 * (LAYER + 768 + 2 * (RQ + R) + 4 * D) + 2 * V * D + D
NEW = ("moe.zero_share", "mla_prefill_flash_h64_roofline")


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
               if r["name"] == "LongCat-Flash-Omni"][0]
    cut = set(c["reduced"])
    assert cut == {"num_layers", "n_routed_experts", "vocab_size",
                   "max_position_embeddings"}
    for key, value in row["config"].items():   # every other key as published
        if key not in cut:
            assert c[key] == value, key
    assert c["source"] == row["source_url"]
    entry = {e["name"]: e for e in cell.benchmark["configs"]}[c["name"]]
    assert set(entry["reduced"]) == cut and entry["source"] == c["source"]
    for key, r in c["reduced"].items():
        assert r["to"] == c[key] < r["from"] == row["config"][key], key
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"]) == (D, H, RQ, R, NOPE, ROPE, DV)
    assert (c["ffn_hidden_size"], c["expert_ffn_hidden_size"], c["moe_topk"],
            c["zero_expert_num"], c["routed_scaling_factor"]) == (
                FD, F, 12, 256, 6)
    assert arch.share(c) == (512, 256, 0, 16)
    over = arch.program_overrides(c, 8704)
    assert over["layer_kinds"] == ("latent",) * 8 == over["rope_kinds"] * 8
    assert (over["n_layers"], over["n_experts"], over["zero_experts"],
            over["experts_held"], over["experts_per_token"], over["d_ff"],
            over["d_ff_dense"], over["q_latent_rank"]) == (
                8, 512, 256, (0, 16), 12, F, FD, RQ)
    assert over["shortcut_moe"] and over["latent_lora_scale"]
    assert over["router_bias"] and not over["norm_topk_prob"]
    # ISSUE 53's arithmetic: 90.57 M a sublayer's attention, 226.49 M a dense
    # MLP, 37.75 M an expert, 638.8 M a layer outside its experts, 5,173 M
    assert round(MLA / 1e6, 2) == 90.57 and round(MLP / 1e6, 2) == 226.49
    assert round(EXPERT / 1e6, 2) == 37.75
    assert round((LAYER - 16 * EXPERT) / 1e6, 1) == 638.8
    assert arch.total_params(c) == TOTAL and round(TOTAL / 1e6) == 5173
    e = c["job"]["engine"]
    assert e == {"max_num_seqs": 32, "max_model_len": 8704, "page_size": 512,
                 "prefill_bucket_min": 256, "expect_experts": 16,
                 "expect_routed_experts": 512, "expect_zero_experts": 256,
                 "expect_latent_rank": 512}
    assert e["max_model_len"] == c["max_position_embeddings"]
    # the rows ISSUE 53 reckoned: 10,240 B a position, 2.85 GB for 32 slots
    assert 8 * 640 * 2 == 10240
    assert round(32 * 8704 * 10240 / 1e9, 2) == 2.85
    for key in ("torch_dtype", "head", "double layer", "latent attention",
                "experts", "initializer", "page_size"):
        assert key in c["assumed"], key
    assert set(c["initializer"]) == {"attention", "mlp", "experts",
                                     "embedding", "router", "router_bias"}


def test_the_cell_is_in_the_lists_it_reports_to(cell):
    b = cell.benchmark
    assert CELL in {w["name"] for w in b["workloads"]} and cell.chips == 1
    serve = {m["name"]: m for m in b["end_to_end"]}["serve_tokens_per_s"]
    assert CELL in serve["workloads"]
    listed = {m["name"] for m in cell.per_layer()}
    assert set(NEW) <= listed
    assert {"mla_decode_roofline", "mla.decode_attn_dev_ms",
            "mla.live_tokens_per_step", "mla.read_per_live",
            "moe_gmm_decode_roofline", "moe_gmm_prefill_roofline",
            "moe.expert_dev_ms", "moe.experts_touched", "moe.max_load",
            "moe.tokens_per_expert", "moe.held_share",
            "engine.decode_riding_share",
            "device.idle_share.saturated"} <= listed
    assert "mla_prefill_flash_roofline" not in listed
    assert not any(name.startswith("kda") for name in listed)
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1


def test_the_mix_is_the_one_the_issue_named(cell, arch):
    from benchmarks import traffic

    mix = cell.mix
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1536,
                                    "sigma": 0.9, "min": 128, "max": 8192}
    assert mix["max_tokens"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.6, "min": 64, "max": 512}
    assert (mix["end"], mix["temperature"]) == ("abandon", 0.0)
    assert traffic.serve_prefill_buckets(mix, 256, 8704) == [
        256, 512, 1024, 2048, 4096, 8192]
    assert mix["prompt_tokens"]["min"] == arch.LEAST_PROMPT
    assert mix["prompt_tokens"]["max"] + mix["max_tokens"]["max"] == 8704
    sizes = traffic.stratified(mix["prompt_tokens"], 1000)
    assert 2100 < sum(sizes) / 1000 < 2250
    assert arch.FLASH_BUCKET == 2048     # the median prompt's bucket
    assert "sweep" in mix["rate_why"] and mix["arrival"]["rate_per_s"] > 0


def test_kernel_costs_are_counted_from_the_shapes(cell, arch):
    c = cell.config
    # 32 slots x the least prompt of 128; 1,152 bytes a position; 64 heads
    ops, nbytes = arch.kernel_cost("mla_decode", c, {"max_num_seqs": 32})
    assert (ops, nbytes) == (32 * 128 * H * 2 * (576 + 512), 32 * 128 * 1152)
    ops, nbytes = arch.kernel_cost("flash_fwd", c, {})
    assert ops == H * 2048 * 2049 // 2 * 2 * (192 + 128)
    assert nbytes == 2 * 2048 * H * (192 + 128) * 2
    assert ops / 197e12 > nbytes / 819e9          # bound by operations
    # half of 32 slots x top-12 = 192 assignments over 768 outputs: 4 held,
    # 16 x (1 - (767/768)^192) = 3.54 of 16 experts touched
    assert round(arch.experts_touched(c, 192), 2) == 3.54
    ops, nbytes = arch.kernel_cost("moe_gmm_decode", c, {})
    assert ops == 2 * 4 * D * F
    assert nbytes == pytest.approx(
        (arch.experts_touched(c, 192) * D * F + 4 * (D + F)) * 2)
    assert ops / 197e12 < nbytes / 819e9          # bound by bytes
    # the least a call with a real row needs: one row, one matrix
    ops, nbytes = arch.kernel_cost("moe_gmm_prefill", c, {})
    assert (ops, nbytes) == (2 * D * F, (D * F + D + F) * 2)
    with pytest.raises(KeyError):
        arch.kernel_cost("kda_scan", c, {})


def test_the_new_metrics_read_through_the_cell(cell):
    per_layer = {m["name"]: m for m in cell.per_layer()}
    for name in NEW:
        with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
            assert cell.reader(name) == json.load(f)
        entry = per_layer[name]
        assert CELL in entry["workloads"]
        assert entry["moves"] == "serve_tokens_per_s"
    kinds = {"flash_fwd (bf16[64,2048,128], f32[64,2048,128])": [0.4, 80.0],
             "flash_fwd (bf16[64,8192,128], f32[64,8192,128])": [3.0, 40.0],
             "fusion bf16[32,12288]": [7.0, 110.0]}
    ctx = {"trace": {"op_kinds": kinds, "window_s": 2.0, "busy_s": 1.9,
                     "modules": {"jit_decode_step": {"count": 200.0,
                                                     "total_s": 1.0},
                                 "jit_prefill": {"count": 50.0,
                                                 "total_s": 0.8}}},
           "spans": {}, "facts": {"peak_flops_per_s": 197e12,
                                  "peak_hbm_bytes_per_s": 819e9},
           "counters": {"moe_decode_zero_assignments": 500,
                        "moe_decode_routed_assignments": 1536}}
    got = cell.per_layer_values(ctx)
    assert got["moe.zero_share"]["value"] == pytest.approx(500 / 1536)
    arch = cell.architecture()
    least = arch.kernel_cost("flash_fwd", cell.config, {})[0] / 197e12
    # the [64, 2048, 128] calls alone: the other bucket is not this count's
    assert got["mla_prefill_flash_h64_roofline"]["value"] == pytest.approx(
        100 * 80 * least / 0.4)
    assert 0 < got["mla_prefill_flash_h64_roofline"]["value"] < 100
    # a program without them (the parent's): left out, no raise
    ctx["trace"]["op_kinds"] = {"fusion bf16[32,12288]": [7.0, 110.0]}
    ctx["counters"] = {}
    assert not set(NEW) & set(cell.per_layer_values(ctx))
    # every metric the cell lists has its reader's file
    for m in cell.per_layer():
        assert cell.reader(m["name"])["reduce"] in reduce.REDUCTIONS


def test_the_adapter_refuses_what_it_does_not_implement(cell, arch):
    c = cell.config
    for key, value in (("zero_expert_type", "copy"),
                       ("attention_method", "GQA"),
                       ("rope_scaling", {"type": "yarn", "factor": 32}),
                       ("attention_bias", True), ("n_shared_experts", 1),
                       ("q_lora_rank", None), ("mla_scale_kv_lora", False)):
        with pytest.raises(ValueError, match=key):
            arch.program_overrides(dict(c, **{key: value}), 8704)
    with pytest.raises(ValueError, match="expert_parallel"):
        arch.share(dict(c, n_routed_experts=32))


# -- the reference against the equations written out ---------------------------------


@pytest.fixture(scope="module")
def tiny(arch):
    """One small double layer under the reference's own parameter names."""
    rng = np.random.default_rng(0)
    d, heads, nope, rp, dv, r, rq, f, fd = 16, 2, 4, 4, 4, 8, 6, 8, 24
    draw = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)   # noqa: E731

    def sub():
        return {"input_layernorm": draw(d) + 1,
                "post_attention_layernorm": draw(d) + 1,
                "q_a_proj": draw(d, rq), "q_a_layernorm": draw(rq) + 1,
                "q_b_proj": draw(rq, heads * (nope + rp)),
                "kv_a_proj_with_mqa": draw(d, r + rp),
                "kv_a_layernorm": draw(r) + 1,
                "kv_b_proj": draw(r, heads * (nope + dv)),
                "o_proj": draw(heads * dv, d), "mlp_gate_proj": draw(d, fd),
                "mlp_up_proj": draw(d, fd), "mlp_down_proj": draw(fd, d)}

    lp = {"sub": [sub(), sub()], "router": draw(d, 9) * 3,
          "e_score_correction_bias": draw(9) * 0.1, "gate_proj": draw(2, d, f),
          "up_proj": draw(2, d, f), "down_proj": draw(2, f, d)}
    rcfg = {"num_attention_heads": heads, "kv_lora_rank": r, "q_lora_rank": rq,
            "qk_nope_head_dim": nope, "qk_rope_head_dim": rp, "v_head_dim": dv,
            "rope_theta": 100.0, "rms_norm_eps": 1e-5, "moe_topk": 3,
            "routed_scaling_factor": 6, "mla_scale_q_lora": True,
            "mla_scale_kv_lora": True, "routed": 6, "first_expert": 2,
            "without": ()}
    return lp, rcfg, draw(2, 7, d)


def test_routing_is_one_softmax_over_experts_and_zero_experts(arch, tiny):
    lp, rcfg, x = tiny
    gates, outputs = arch.routing(x, lp["router"],
                                  lp["e_score_correction_bias"], rcfg)
    logits = np.asarray(x, np.float64) @ np.asarray(lp["router"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    bias = np.asarray(lp["e_score_correction_bias"], np.float64)
    for b in range(2):
        for t in range(7):
            top = np.argsort(-(p[b, t] + bias))[:3]
            assert np.asarray(outputs[b, t]).tolist() == top.tolist()
            np.testing.assert_allclose(gates[b, t], 6 * p[b, t][top],
                                       rtol=1e-5)
    assert (np.asarray(outputs) >= 6).any()      # some chose a zero expert


def test_branch_is_the_held_experts_and_gate_times_u(arch, tiny):
    lp, rcfg, x = tiny
    gates, outputs = (np.asarray(a) for a in arch.routing(
        x, lp["router"], lp["e_score_correction_bias"], rcfg))
    u = np.asarray(x, np.float64)
    silu = lambda a: a / (1 + np.exp(-a))       # noqa: E731
    want = np.zeros(u.shape)
    for b in range(2):
        for t in range(7):
            for g, e in zip(gates[b, t], outputs[b, t]):
                if e >= 6:                      # a zero expert: the identity
                    want[b, t] += g * u[b, t]
                elif 2 <= e < 4:                # held here: experts 2 and 3
                    w = [np.asarray(lp[n][e - 2], np.float64)
                         for n in ("gate_proj", "up_proj", "down_proj")]
                    want[b, t] += g * (
                        (silu(u[b, t] @ w[0]) * (u[b, t] @ w[1])) @ w[2])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(arch.expert_branch(x, lp, rcfg))
        parts = (arch.expert_branch(x, lp, rcfg, zero_part=False)
                 + arch.expert_branch(x, lp, rcfg, routed_part=False))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got, parts, rtol=1e-5, atol=1e-6)


def test_double_layer_is_causal_and_every_part_shows(arch, tiny):
    lp, rcfg, x = tiny
    with jax.default_matmul_precision("highest"):
        base = np.asarray(arch.double_layer(x, lp, rcfg))
        later = np.asarray(arch.double_layer(x.at[:, 5:].add(1.0), lp, rcfg))
        np.testing.assert_allclose(later[:, :5], base[:, :5], rtol=1e-5,
                                   atol=1e-6)
        assert np.abs(later[:, 5:] - base[:, 5:]).max() > 1e-2
        for part in ("zero_experts", "zero_renorm", "gate_renorm",
                     "routed_scale", "bias_in_gates", "branch_after_first",
                     "branch_from_second", "second_attention", "s_q", "s_kv",
                     "s_kv_on_keys", "q_a_norm", "q_lora", "latent_scale"):
            spoiled = np.asarray(arch.double_layer(
                x, lp, dict(rcfg, without=(part,))))
            assert np.abs(spoiled - base).max() > 1e-3, part
        other = np.asarray(arch.double_layer(x, lp, dict(rcfg, first_expert=0)))
        assert np.abs(other - base).max() > 1e-3


def test_attention_scales_the_queries_and_the_latent(arch, tiny):
    """``latent_attention`` against the sums in numpy, a head and a position
    at a time: s_q on all of q, s_kv on the normalised latent (keys AND
    values), the rotation on the rope lanes of q and on the one shared key."""
    lp, rcfg, x = tiny
    sp = lp["sub"][0]
    p = {k: np.asarray(v, np.float64) for k, v in sp.items()}
    h = np.asarray(x, np.float64)
    heads, nope, rp, dv, r, rq, d = 2, 4, 4, 4, 8, 6, 16
    s_q, s_kv = (d / rq) ** 0.5, (d / r) ** 0.5

    def norm(a, w):
        return a / np.sqrt(np.mean(a * a, -1, keepdims=True) + 1e-5) * w

    def rot(a, t):   # pairs (i, i + half)
        half = a.shape[-1] // 2
        ang = t * 100.0 ** (-np.arange(half) / half)
        a1, a2 = a[..., :half], a[..., half:]
        return np.concatenate([a1 * np.cos(ang) - a2 * np.sin(ang),
                               a2 * np.cos(ang) + a1 * np.sin(ang)], -1)

    want = np.zeros(h.shape)
    for b in range(2):
        q = (norm(h[b] @ p["q_a_proj"], p["q_a_layernorm"]) @ p["q_b_proj"]
             * s_q).reshape(7, heads, nope + rp)
        a = h[b] @ p["kv_a_proj_with_mqa"]
        c = norm(a[:, :r], p["kv_a_layernorm"]) * s_kv
        kv = (c @ p["kv_b_proj"]).reshape(7, heads, nope + dv)
        k_r = np.stack([rot(a[t, r:], t) for t in range(7)])
        out = np.zeros((7, heads, dv))
        for n in range(heads):
            for t in range(7):
                qt = np.concatenate([q[t, n, :nope], rot(q[t, n, nope:], t)])
                keys = np.concatenate([kv[:t + 1, n, :nope], k_r[:t + 1]], -1)
                s = keys @ qt * (nope + rp) ** -0.5
                w = np.exp(s - s.max())
                out[t, n] = (w / w.sum()) @ kv[:t + 1, n, nope:]
        want[b] = out.reshape(7, -1) @ p["o_proj"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(arch.latent_attention(x, sp, rcfg))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
