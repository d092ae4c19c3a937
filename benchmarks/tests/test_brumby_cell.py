"""``benchmarks/architectures/brumby.py`` reached the way the harness reaches
it (through the resolver, from the committed configuration file), against
counts made by hand from the published shapes and ISSUE 55's numbers, and its
plain reference against the sums written out in numpy (no program is
imported: the program is held to this reference in ``tests/test_brumby.py``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import registry
from benchmarks.registry import HERE, REPO, Cell
from benchmarks.trace import reduce

CELL = "brumby-14b-base.completion-saturated-b32"
D, H, KVH, HD, F, V = 5120, 40, 8, 128, 17408, 151936
MIXER = 2 * D * H * HD + 2 * D * KVH * HD + D * KVH + KVH + 2 * HD
LAYER = MIXER + 3 * D * F + 2 * D
TOTAL = 6 * LAYER + 2 * V * D + D
STATE = KVH * (HD * (HD + 1) // 2) * (HD + 1)          # floats, exact
NEW = ("retention_step_roofline", "retention_scan_roofline",
       "retention.step_dev_ms", "retention.scan_dev_ms",
       "retention.live_slot_share", "retention.scan_skipped_share")


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
               if r["name"] == "Brumby-14B-Base"][0]
    cut = set(c["reduced"])
    assert cut == {"num_hidden_layers", "max_position_embeddings"}
    for key, value in row["config"].items():   # every other key as published
        if key not in cut:
            assert c[key] == value, key
    assert c["source"] == row["source_url"]
    entry = {e["name"]: e for e in cell.benchmark["configs"]}[c["name"]]
    assert set(entry["reduced"]) == cut and entry["source"] == c["source"]
    for key, r in c["reduced"].items():
        assert r["to"] == c[key] < r["from"] == row["config"][key], key
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"], c["rope_theta"], c["rms_norm_eps"]) == (
                D, H, KVH, HD, F, V, 1000000, 1e-6)
    over = arch.program_overrides(c, 9216)
    assert over["layer_kinds"] == ("retention",) * 6
    assert over["rope_kinds"] == ("retention",) and over["qk_head_norm"]
    assert (over["n_layers"], over["n_heads"], over["n_kv_heads"],
            over["head_size"], over["d_ff"], over["retention_degree"]) == (
                6, H, KVH, HD, F, 2)
    # ISSUE 55's arithmetic: 62.96 M a mixer, 267.39 M the MLP, 330.35 M a
    # layer, 777.9 M table and head each, 3,538 M parameters, 7.08 GB
    assert MIXER == 62_955_784 and round(3 * D * F / 1e6, 2) == 267.39
    assert round(LAYER / 1e6, 2) == 330.35 and round(V * D / 1e6, 1) == 777.9
    assert arch.total_params(c) == TOTAL and round(TOTAL / 1e6) == 3538
    assert round(2 * TOTAL / 1e9, 2) == 7.08
    e = c["job"]["engine"]
    assert e == {"max_num_seqs": 32, "max_model_len": 9216, "page_size": 512,
                 "prefill_bucket_min": 256, "expect_state_layers": 6,
                 "expect_retention_heads": 8}
    assert e["max_model_len"] == c["max_position_embeddings"]
    for key in ("torch_dtype", "degree", "gate", "normaliser",
                "rope and head norms", "tiled symmetric power",
                "state precision", "initializer", "page_size"):
        assert key in c["assumed"], key
    assert set(c["initializer"]) == {"retention", "mlp", "embedding",
                                     "gate_bias", "head_norms"}
    assert c["initializer"]["gate_bias"][:2] == [5.0, 1.5]


def test_the_cell_is_in_the_lists_it_reports_to(cell):
    b = cell.benchmark
    assert CELL in {w["name"] for w in b["workloads"]} and cell.chips == 1
    serve = {m["name"]: m for m in b["end_to_end"]}["serve_tokens_per_s"]
    assert CELL in serve["workloads"]
    listed = {m["name"] for m in cell.per_layer()}
    assert set(NEW) <= listed
    assert {"serve.replica_up_s", "engine.tokens_per_step",
            "model.decode_dev_ms", "model.prefill_dev_ms",
            "model.sample_dev_ms", "engine.decode_riding_share",
            "engine.slot_live_share",
            "device.idle_share.saturated"} <= listed
    assert not any(name.split(".")[0].split("_")[0] in (
        "kda", "ssd", "moe", "mla", "flash", "attn", "window")
        for name in listed)


def test_the_mix_is_the_one_the_issue_named(cell, arch):
    from benchmarks import traffic

    mix = cell.mix
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 0.8, "min": 256, "max": 8192}
    assert mix["max_tokens"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.6, "min": 64, "max": 1024}
    assert (mix["end"], mix["temperature"]) == ("abandon", 0.0)
    assert traffic.serve_prefill_buckets(mix, 256, 9216) == [
        256, 512, 1024, 2048, 4096, 8192]
    assert mix["prompt_tokens"]["min"] == arch.LEAST_BUCKET
    assert mix["prompt_tokens"]["max"] + mix["max_tokens"]["max"] == 9216
    sizes = traffic.stratified(mix["prompt_tokens"], 1000)
    assert 2600 < sum(sizes) / 1000 < 2700
    assert "sweep" in mix["rate_why"] and mix["arrival"]["rate_per_s"] > 0


def test_kernel_costs_are_counted_from_the_shapes(cell, arch):
    c = cell.config
    # 34.08 MB a slot and layer, exact; 2.18 GB a step and layer at 32 slots
    assert arch.state_floats(c) == STATE == 8 * 8256 * 129
    assert round(4 * STATE / 1e6, 2) == 34.08
    ops, nbytes = arch.kernel_cost("retention_step", c, {"max_num_seqs": 32})
    operands = 2 * (H + KVH) * HD * 2 + 4 * KVH
    assert nbytes == 32 * (8 * STATE + operands)
    assert round(nbytes / 1e9, 2) == 2.18
    assert round(nbytes / 819e9 * 1e3, 2) == 2.66            # ms a layer
    assert ops == (3 + 2 * 5) * 32 * STATE
    assert ops / 197e12 < nbytes / 819e9                     # bound by bytes
    # one row of 256 positions: the causal pairs' squared weights and the
    # final state, not the recurrent form's 26 GFLOP
    ops, nbytes = arch.kernel_cost("retention_scan", c, {})
    assert ops == H * 512 * 256 * 257 // 2 + KVH * 2 * 8256 * 129 * 256
    assert round(H * 512 * 256 * 257 // 2 / 1e9, 2) == 0.67
    assert round(KVH * 2 * 8256 * 129 * 256 / 1e9, 2) == 4.36
    assert nbytes == 256 * operands + 4 * STATE
    assert round(nbytes / 819e9 * 1e6) == 49 and round(ops / 197e12 * 1e6) == 26
    assert 256 * arch.retention_flops_per_token(c) > 5 * ops  # 26 GFLOP
    assert round(arch.retention_flops_per_token(c) / 1e6, 1) == 102.1
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
    kinds = {"retention_step (f32[32,8,128,8], f32[6,33,8,66,128,128])":
             [3.6, 1200.0],
             "retention_riding (f32[32,8,128,8], f32[6,33,8,66,128,128])":
             [0.1, 30.0],
             "retention_scan (bf16[1,2048,5120], f32[6,33,8,66,128,128])":
             [0.9, 240.0],
             "fusion bf16[32,17408]": [7.0, 110.0]}
    ctx = {"trace": {"op_kinds": kinds, "window_s": 8.0, "busy_s": 7.9,
                     "modules": {"jit_decode_step": {"count": 200.0,
                                                     "total_s": 5.0},
                                 "jit_prefill": {"count": 40.0,
                                                 "total_s": 2.0}}},
           "spans": {}, "facts": {"peak_flops_per_s": 197e12,
                                  "peak_hbm_bytes_per_s": 819e9,
                                  "max_num_seqs": 32},
           "counters": {"retention_live_slots": 900,
                        "retention_state_slots": 1200,
                        "retention_scan_chunks": 80,
                        "retention_scan_chunks_skipped": 20}}
    got = cell.per_layer_values(ctx)
    arch = cell.architecture()
    assert got["retention.live_slot_share"]["value"] == 0.75
    assert got["retention.scan_skipped_share"]["value"] == 0.25
    assert got["retention.step_dev_ms"]["value"] == pytest.approx(3.6 / 200 * 1e3)
    assert got["retention.scan_dev_ms"]["value"] == pytest.approx(0.9 / 40 * 1e3)
    least = arch.kernel_cost("retention_step", cell.config,
                             ctx["facts"])[1] / 819e9
    # the decode steps' calls alone: a riding call is not this count's
    assert got["retention_step_roofline"]["value"] == pytest.approx(
        100 * 1200 * least / 3.6)
    least = arch.kernel_cost("retention_scan", cell.config, {})[1] / 819e9
    assert got["retention_scan_roofline"]["value"] == pytest.approx(
        100 * 240 * least / 0.9)
    assert all(0 < got[n]["value"] < 100 for n in NEW if "roofline" in n)
    # a program without them (the parent's): left out, no raise
    ctx["trace"]["op_kinds"] = {"fusion bf16[32,17408]": [7.0, 110.0]}
    ctx["counters"] = {}
    assert not set(NEW) & set(cell.per_layer_values(ctx))
    # every metric the cell lists has its reader's file
    for m in cell.per_layer():
        assert cell.reader(m["name"])["reduce"] in reduce.REDUCTIONS


def test_the_adapter_refuses_what_it_does_not_implement(cell, arch):
    c = cell.config
    for key, value in (("rope_scaling", {"type": "yarn", "factor": 4}),
                       ("use_sliding_window", True), ("sliding_window", 4096),
                       ("attention_bias", True),
                       ("tie_word_embeddings", True), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key):
            arch.program_overrides(dict(c, **{key: value}), 9216)
        with pytest.raises(ValueError, match=key):
            arch.reference_cfg(dict(c, **{key: value}))
    with pytest.raises(ValueError, match="query heads"):
        arch.program_overrides(dict(c, num_key_value_heads=7), 9216)


# -- the reference against the sums written out ---------------------------------------


def test_power_attention_is_the_sums_it_says(arch):
    """``power_attention`` against numpy, a head, a position and a key at a
    time: squared scaled scores, the product of the gates from the position
    after the key on, the quotient by the row's sum; query head ``h`` over
    key/value head ``h // 2``."""
    rng = np.random.default_rng(0)
    S, heads, kv, hd = 7, 4, 2, 6
    q, k, v = (rng.normal(size=(1, S, n, hd)) for n in (heads, kv, kv))
    log_g = np.log(1 / (1 + np.exp(-rng.normal(size=(1, S, kv)) - 2)))
    want = np.zeros((S, heads, hd))
    for h in range(heads):
        for t in range(S):
            a = np.array([(q[0, t, h] @ k[0, j, h // 2] * hd ** -0.5) ** 2
                          * np.exp(log_g[0, j + 1:t + 1, h // 2].sum())
                          for j in range(t + 1)])
            want[t, h] = a @ v[0, :t + 1, h // 2] / a.sum()
    f32 = lambda a: jnp.asarray(a, jnp.float32)   # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = arch.power_attention(f32(q), f32(k), f32(v), f32(log_g))
        np.testing.assert_allclose(np.asarray(got)[0], want.reshape(S, -1),
                                   rtol=2e-4, atol=2e-5)
        for part in arch.WITHOUT[:8]:   # those the attention itself takes
            spoiled = arch.power_attention(f32(q), f32(k), f32(v), f32(log_g),
                                           (part,))
            assert np.abs(np.asarray(spoiled)[0] - want.reshape(S, -1)
                          ).max() > 1e-3, part
