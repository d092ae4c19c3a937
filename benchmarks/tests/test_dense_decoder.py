"""The counts of ``benchmarks/architectures/dense_decoder.py`` (reached the
way the harness reaches them, through the resolver) against counts made by
hand from the published shapes of the two models (multiply-add = 2)."""

import json
import os

import pytest

from benchmarks import registry

flops = registry.architecture({})      # no "adapter" key: the dense decoder

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_smollm2_8_layers_by_hand():
    c = cfg("smollm2-1.7b")
    # per layer: q, k, v, o are each 2048 x 2048 (MHA, 32 heads x 64) and the
    # MLP has three 2048 x 8192 matrices
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert layer == 67_108_864
    head = 2048 * 49152                       # tied: one table, still a matmul
    assert flops.matmul_params(c) == 8 * layer + head == 637_534_208
    # stored parameters: the table once (tied) + 17 norm vectors
    assert flops.total_params(c) == 8 * layer + head + 17 * 2048 == 637_569_024
    # causal attention at 2048: mean 1024.5 keys, 4 * 32 * 64 operations each
    attn = 8 * 4 * 32 * 64 * 1024.5
    assert flops.attention_flops_per_token_fwd(c, 2048) == attn == 67_141_632.0
    fwd = 2 * 637_534_208 + attn
    assert flops.train_flops_per_token(c, 2048) == 3 * fwd == 4_026_630_144.0


@pytest.mark.parametrize("name,layers,total", [
    ("internlm2-1.8b", 24, 1_889_110_016),
    ("internlm2-1.8b-dp4", 8, 882_411_520),
])
def test_internlm2_by_hand(name, layers, total):
    c = cfg(name)
    assert c["num_hidden_layers"] == layers
    # GQA 16/8 at head_dim 128: q and o are 2048 x 2048, k and v 2048 x 1024
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert layer == 62_914_560
    table = 2048 * 92544
    assert flops.matmul_params(c) == layers * layer + table
    # untied: embedding table and head both stored
    assert flops.total_params(c) == layers * layer + 2 * table + (2 * layers + 1) * 2048 == total
    attn = layers * 4 * 16 * 128 * 1024.5
    assert flops.train_flops_per_token(c, 2048) == 3 * (2 * (layers * layer + table) + attn)


def test_dp4_matches_the_issue_reckoning():
    # 4.36 GFLOP/token, 17.9 TFLOP per chip per 4096-token step
    per_token = flops.train_flops_per_token(cfg("internlm2-1.8b-dp4"), 2048)
    assert round(per_token / 1e9, 2) == 4.36
    assert round(per_token * 4096 / 1e12, 1) == 17.9


# -- one call of a flash kernel (``kernel_cost``) ------------------------------

CELL1 = {"per_chip_batch": 4, "seq_len": 2048}    # smollm2-1.7b.train-2k
CELL4 = {"per_chip_batch": 2, "seq_len": 2048}    # internlm2-1.8b-dp4.train-2k, a chip


def test_flash_kernels_at_cell_1_by_hand():
    c = cfg("smollm2-1.7b")
    # 2048 queries, query i sees i + 1 keys: 2048 * 2049 / 2 pairs a head
    pairs = 2_098_176
    rows = 4 * 32                              # sequences x heads, MHA
    product = 2 * 64 * pairs * rows            # one [.,64] x [64,.] over the pairs
    assert product == 34_376_515_584
    arr = rows * 2048 * 64 * 2                 # one bf16 [rows, S, 64] array
    vec = rows * 2048 * 4                      # one f32 [rows, S, 1] vector
    assert (arr, vec) == (33_554_432, 1_048_576)
    # forward: QK^T and PV; reads q, k, v, writes o and the log-sum-exp
    assert flops.kernel_cost("flash_fwd", c, CELL1) == (2 * product, 4 * arr + vec)
    # dq: the score again, dO V^T, dS K; reads q, k, v, dO, lse, delta, writes dq
    assert flops.kernel_cost("flash_bwd_dq", c, CELL1) == (3 * product, 5 * arr + 2 * vec)
    # dk/dv: the score again, P^T dO, dO V^T, dS^T Q; writes dk and dv
    assert flops.kernel_cost("flash_bwd_dkv", c, CELL1) == (4 * product, 6 * arr + 2 * vec)
    # the issue's reckoning: 0.35 / 0.52 / 0.70 ms of compute at 197 TFLOP/s,
    # each above its bytes at 819 GB/s (0.17 / 0.21 / 0.25 ms)
    ms = [round(flops.kernel_cost(k, c, CELL1)[0] / 197e12 * 1e3, 2)
          for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")]
    assert ms == [0.35, 0.52, 0.70]
    # the forward kernels of a step are the attention term of the model's count
    assert 8 * flops.kernel_cost("flash_fwd", c, CELL1)[0] == \
        flops.attention_flops_per_token_fwd(c, 2048) * 4 * 2048


def test_flash_forward_at_cell_4_by_hand():
    c = cfg("internlm2-1.8b-dp4")
    # a chip holds 2 sequences x 16 query heads of 128; keys and values have 8
    ops, nbytes = flops.kernel_cost("flash_fwd", c, CELL4)
    assert ops == 2 * (2 * 128 * 2_098_176 * 32) == 34_376_515_584
    q_or_o, k_or_v = 32 * 2048 * 128 * 2, 16 * 2048 * 128 * 2
    assert nbytes == 2 * q_or_o + 2 * k_or_v + 32 * 2048 * 4 == 50_593_792


def test_a_kernel_nobody_counted_fails_by_name():
    with pytest.raises(KeyError, match="flash_bwd_dkv"):
        flops.kernel_cost("paged_attention", cfg("smollm2-1.7b"), CELL1)
