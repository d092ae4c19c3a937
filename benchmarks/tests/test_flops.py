"""``benchmarks/flops.py`` against counts made by hand from the published
shapes of the two models (multiply-add = 2)."""

import json
import os

import pytest

from benchmarks import flops

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
