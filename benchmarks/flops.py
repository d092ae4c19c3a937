"""Required operations of the Llama-shaped dense decoder, from its shapes.

The count is what the algorithm needs, not what a program happens to execute:
causal attention counts only the keys at or before each query, the embedding
lookup is a gather (no operations), recomputation under remat is not counted.
A multiply-add is two operations. ``cfg`` is a configuration file's dict with
the published key names.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix multiplication for every token:
    the four attention projections and three MLP matrices of each layer, and
    the output head (tied or not, it is a [d, vocab] product)."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    q = d * cfg["num_attention_heads"] * hd
    kv = 2 * d * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * d
    mlp = 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (q + kv + o + mlp) + d * cfg["vocab_size"]


def attention_flops_per_token_fwd(cfg: dict, seq_len: int) -> float:
    """QK^T and PV of causal attention, forward, averaged over the positions
    of one sequence of ``seq_len``: position i attends to i+1 keys, so the mean
    is (seq_len+1)/2 keys, each costing 2*hd for the score and 2*hd for the
    value, per head and layer."""
    per_key = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    return cfg["num_hidden_layers"] * per_key * (seq_len + 1) / 2


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 2.0 * matmul_params(cfg) + attention_flops_per_token_fwd(cfg, seq_len)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward: the backward pass needs two products for each
    one of the forward pass (gradient w.r.t. input and w.r.t. weight)."""
    return 3.0 * forward_flops_per_token(cfg, seq_len)


def total_params(cfg: dict) -> int:
    """Every stored parameter (the embedding table once when tied)."""
    d = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * d
    table = cfg["vocab_size"] * d
    head = 0 if cfg["tie_word_embeddings"] else table
    return matmul_params(cfg) - d * cfg["vocab_size"] + table + head + norms
