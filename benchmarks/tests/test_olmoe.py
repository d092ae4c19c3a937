"""``benchmarks/architectures/olmoe.py`` reached the way the harness reaches it
(through the resolver, from the committed configuration file), against counts
made by hand from the published shapes (multiply-add = 2)."""

import json
import os

import pytest

from benchmarks import registry
from benchmarks.registry import REPO, Cell

CELL = "olmoe-1b-7b.alpaca-saturated-b16"
LAYER = 419_569_664          # parameters of one layer
REST = 206_047_232           # table, head and the final norm


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


def test_the_configuration_keeps_every_published_width(cell):
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["head_dim"],
            c["num_key_value_heads"], c["num_experts"], c["num_experts_per_tok"],
            c["intermediate_size"], c["vocab_size"]) == (
        2048, 16, 128, 16, 64, 8, 1024, 50304)
    assert (c["norm_topk_prob"], c["rms_norm_eps"], c["rope_theta"],
            c["clip_qkv"], c["attention_bias"], c["tie_word_embeddings"]) == (
        False, 1e-5, 10000, None, False, False)
    entry = {e["name"]: e for e in cell.benchmark["configs"]}["olmoe-1b-7b"]
    assert c["num_hidden_layers"] == 12
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers"]
    for key, cut in c["reduced"].items():
        assert cut["to"] == c[key] < cut["from"] and cut["why"]
    assert c["job"]["engine"]["max_model_len"] == c["max_position_embeddings"]


@pytest.mark.parametrize("layers", [16, 12, 1])
def test_total_params_by_hand(cell, arch, layers):
    # a layer: q, k, v, o 2048 x 2048; the q and k norms 2048 each; two layer
    # norms; the router 2048 x 64; 64 experts of three 2048 x 1024 matrices
    layer = 4 * 2048 * 2048 + 2 * 2048 + 2 * 2048 + 2048 * 64 \
        + 64 * 3 * 2048 * 1024
    assert layer == LAYER
    assert 2 * 50304 * 2048 + 2048 == REST
    c = dict(cell.config, num_hidden_layers=layers)
    assert arch.total_params(c) == LAYER * layers + REST
    if layers == 16:
        assert arch.total_params(c) == 6_919_161_856      # the published size


def test_train_flops_count_the_eight_active_experts(cell, arch):
    c = cell.config
    L = c["num_hidden_layers"]
    # a token multiplies by the attention matrices, the router and 8 experts
    layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert layer == 67_239_936
    head = 2048 * 50304
    assert arch.active_matmul_params(c) == L * layer + head
    assert L == 12
    attn = L * 4 * 16 * 128 * 1024.5          # causal, 2048 positions
    assert arch.train_flops_per_token(c, 2048) == 3 * (2 * (L * layer + head) + attn)
    # all 64 experts are stored: 8 x the expert share a token multiplies by
    assert arch.total_params(c) - arch.active_matmul_params(c) > \
        L * 56 * 3 * 2048 * 1024


def test_kernel_cost_by_hand(cell, arch):
    # decode: 16 rows x top-8 = 128 assignments by one 2048 x 1024 matrix each
    ops, nbytes = arch.kernel_cost("moe_gmm_decode", cell.config,
                                   {"max_num_seqs": 16})
    assert ops == 2 * 128 * 2048 * 1024 == 536_870_912
    # uniform routing touches 64 x (1 - (7/8)^16) = 56.44 -> 56 experts
    assert arch.experts_touched(cell.config, 16) == 56
    assert nbytes == (56 * 2048 * 1024 + 128 * (2048 + 1024)) * 2 == 235_667_456
    # memory-bound on a v5e: 0.288 ms of bytes against 0.0027 ms of operations
    assert nbytes / 819e9 > 100 * ops / 197e12
    # without the run's facts the configuration's own slots are counted
    assert arch.kernel_cost("moe_gmm_decode", cell.config, {}) == (ops, nbytes)
    # prefill: the least a call with a real row needs, one token's 8 experts
    ops, nbytes = arch.kernel_cost("moe_gmm_prefill", cell.config,
                                   {"max_num_seqs": 16})
    assert ops == 2 * 8 * 2048 * 1024
    assert nbytes == (8 * 2048 * 1024 + 8 * (2048 + 1024)) * 2 == 33_603_584
    with pytest.raises(KeyError, match="flash_fwd"):
        arch.kernel_cost("flash_fwd", cell.config, {})


def test_program_overrides_map_the_published_keys_and_refuse_the_rest(cell, arch):
    import jax.numpy as jnp

    o = arch.program_overrides(cell.config, 1024)
    assert (o["n_experts"], o["experts_per_token"], o["d_ff"], o["norm_eps"],
            o["norm_topk_prob"], o["qk_norm"], o["param_dtype"]) == (
        64, 8, 1024, 1e-5, False, True, jnp.bfloat16)
    for key, value in (("clip_qkv", 8.0), ("attention_bias", True),
                       ("model_type", "mixtral"), ("head_dim", 64)):
        with pytest.raises(ValueError, match=key):
            arch.program_overrides(dict(cell.config, **{key: value}), 1024)


def test_the_cell_reads_the_routing_counters_as_data(cell):
    """Six metrics, each a file naming a reduction that is there; an engine
    without the counters (the parent's) leaves them out and raises nothing."""
    window = {"moe_decode_layer_steps": 1200, "moe_decode_assignments": 153600,
              "moe_decode_experts_touched": 67200, "moe_decode_max_load": 7200,
              "generated_tokens": 1600, "decode_steps": 100}
    ctx = {"trace": None, "spans": {}, "counters": window, "facts": {}}
    got = {k: v["value"] for k, v in cell.per_layer_values(ctx).items()}
    assert got["moe.experts_touched"] == 56.0
    assert got["moe.tokens_per_expert"] == 153600 / 67200
    assert got["moe.max_load"] == 6.0
    parent = {"generated_tokens": 1600, "decode_steps": 100}
    ctx = {"trace": None, "spans": {}, "counters": parent, "facts": {}}
    assert set(cell.per_layer_values(ctx)) == {"engine.tokens_per_step"}
    for name in ("moe_gmm_decode_roofline", "moe_gmm_prefill_roofline",
                 "moe.expert_dev_ms"):
        with open(os.path.join(cell.root, "layer_metrics", name + ".json")) as f:
            assert set(json.load(f)) == {"reduce", "args"}


def test_a_traced_window_gives_the_kernel_shares(cell):
    """3 products a layer: 36 calls of the decode kernel in a step of 12
    layers. 10 such steps whose calls took 0.4 ms each read 72 % of the
    roofline."""
    ctx = {"trace": {"modules": {"jit_decode_step": {"count": 10, "total_s": 0.2}},
                     "window_s": 1.0, "busy_s": 0.8,
                     "op_kinds": {
                         "moe_gmm_decode bf16[128,1024]": [240 * 0.4e-3, 240.0],
                         "moe_gmm_decode bf16[128,2048]": [120 * 0.4e-3, 120.0],
                         "moe_gmm_prefill bf16[16384,1024]": [1.0, 1.0]}},
           "spans": {}, "counters": {},
           "facts": {"max_num_seqs": 16, "peak_flops_per_s": 197e12,
                     "peak_hbm_bytes_per_s": 819e9}}
    got = {k: v["value"] for k, v in cell.per_layer_values(ctx).items()}
    assert got["moe_gmm_decode_roofline"] == pytest.approx(
        100 * (235_667_456 / 819e9) / 0.4e-3)
    assert got["moe.expert_dev_ms"] == pytest.approx(36 * 0.4)
    assert got["moe_gmm_prefill_roofline"] == pytest.approx(
        100 * (33_603_584 / 819e9) / 1.0)
