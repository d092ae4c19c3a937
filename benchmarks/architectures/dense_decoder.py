"""The Llama-shaped dense decoder: everything the benchmark knows about one
architecture, in the one module a configuration file names with
``"adapter"`` (a file without the key means this one).

Four parts, and the harness asks for nothing else (``registry.MEMBERS``):

1. the plain float32 reference (``forward``, ``loss``), written from the
   published description of the architecture: pre-norm residual blocks;
   RMSNorm; rotary position embedding in the half-split "rotate_half"
   convention; grouped-query attention with a causal mask; SwiGLU MLP; tied or
   untied output head; mean next-token cross-entropy. It uses no kernel, cache
   or batching trick. Callers wrap it in
   ``jax.default_matmul_precision("highest")``: on a TPU a float32 product
   otherwise runs in reduced precision;
2. the way from a configuration file's published keys to the program and to
   the reference (``program_overrides``, ``reference_cfg``,
   ``to_reference_params``);
3. required operations per token (``train_flops_per_token``,
   ``total_params``);
4. operations and bytes of one call of each kernel the program runs for this
   architecture (``kernel_cost``).

Nothing here imports the program under test. ``cfg`` is a configuration
file's dict with the published key names; ``rcfg`` is ``reference_cfg(cfg)``.

Reference parameters are a plain dict:
  embed_tokens [V, d]; norm [d]; lm_head [d, V] (absent when tied);
  layers: list of {input_layernorm [d], q_proj [d, H*hd], k_proj [d, KV*hd],
  v_proj [d, KV*hd], o_proj [H*hd, d], post_attention_layernorm [d],
  gate_proj [d, f], up_proj [d, f], down_proj [f, d]}
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# -- 1. the plain reference ------------------------------------------------------


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope(x, positions, theta):
    """x [B, S, heads, hd]; rotate pairs (i, i + hd/2) by position * theta^(-2i/hd)."""
    hd = x.shape[-1]
    half = hd // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / hd))
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v):
    """Causal softmax attention. q [B,S,H,hd]; k, v [B,S,KV,hd], H = G*KV."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    q = q.reshape(B, S, KV, H // KV, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, S, H * hd)


def forward(params, tokens, rcfg):
    """tokens [B, S] int -> logits [B, S, V], float32 throughout."""
    H, KV, hd = (rcfg["num_attention_heads"], rcfg["num_key_value_heads"],
                 rcfg["head_dim"])
    eps, theta = rcfg["rms_norm_eps"], rcfg["rope_theta"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = params["embed_tokens"].astype(jnp.float32)[tokens]
    for lp in params["layers"]:
        h = rms_norm(x, lp["input_layernorm"], eps)
        q = (h @ lp["q_proj"]).reshape(B, S, H, hd)
        k = (h @ lp["k_proj"]).reshape(B, S, KV, hd)
        v = (h @ lp["v_proj"]).reshape(B, S, KV, hd)
        q, k = rope(q, positions, theta), rope(k, positions, theta)
        x = x + attention(q, k, v) @ lp["o_proj"]
        h = rms_norm(x, lp["post_attention_layernorm"], eps)
        x = x + (jax.nn.silu(h @ lp["gate_proj"]) * (h @ lp["up_proj"])) @ lp["down_proj"]
    x = rms_norm(x, params["norm"], eps)
    if rcfg["tie_word_embeddings"]:
        return x @ params["embed_tokens"].astype(jnp.float32).T
    return x @ params["lm_head"]


def loss(params, tokens, targets, rcfg):
    """Mean next-token cross-entropy; ``targets`` are ``tokens`` shifted by one."""
    logp = jax.nn.log_softmax(forward(params, tokens, rcfg), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# -- 2. from the published keys to the program and to the reference -------------


def program_overrides(cfg: dict, max_seq_len: int) -> dict:
    """Published keys -> the program's ``TransformerConfig`` fields: public
    widths go in as data (``dataclasses.replace`` / ``LLMConfig
    .model_overrides``), no preset's sizes are used."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    if hd != cfg["head_dim"]:
        raise ValueError("the program derives head_dim = hidden/heads; "
                         f"{cfg['name']} publishes {cfg['head_dim']}")
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                d_ff=cfg["intermediate_size"], max_seq_len=max_seq_len,
                rope_theta=float(cfg["rope_theta"]),
                tie_embeddings=bool(cfg["tie_word_embeddings"]), remat=True)


def reference_cfg(cfg: dict) -> dict:
    """What the plain reference needs. ``rms_norm_eps`` is the program's
    hard-coded 1e-6, not the published 1e-5: a departure the configuration
    file lists, and one the program offers no way around."""
    return {k: cfg[k] for k in ("num_attention_heads", "num_key_value_heads",
                                "head_dim", "rope_theta",
                                "tie_word_embeddings")} | {"rms_norm_eps": 1e-6}


def to_reference_params(p: dict, cfg: dict) -> dict:
    """The program's parameter tree under the reference's plain names.
    Reshapes only (heads folded into one axis); called inside a jit so no
    copy of the weights outlives the check."""
    def flat_in(k):   # [d, heads, hd] -> [d, heads*hd]
        return k.reshape(k.shape[0], -1)

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = p[f"layer_{i}"]
        a, m = lp["attn"], lp["mlp"]
        o = a["o_proj"]["kernel"]
        layers.append({
            "input_layernorm": lp["attn_norm"]["scale"],
            "q_proj": flat_in(a["q_proj"]["kernel"]),
            "k_proj": flat_in(a["k_proj"]["kernel"]),
            "v_proj": flat_in(a["v_proj"]["kernel"]),
            "o_proj": o.reshape(-1, o.shape[-1]),
            "post_attention_layernorm": lp["mlp_norm"]["scale"],
            "gate_proj": m["gate_proj"]["kernel"],
            "up_proj": m["up_proj"]["kernel"],
            "down_proj": m["down_proj"]["kernel"]})
    out = {"embed_tokens": p["embed"], "norm": p["final_norm"]["scale"],
           "layers": layers}
    if "lm_head" in p:
        out["lm_head"] = p["lm_head"]
    return out


# -- 3. required operations, from the shapes --------------------------------------
#
# The count is what the algorithm needs, not what a program happens to execute:
# causal attention counts only the keys at or before each query, the embedding
# lookup is a gather (no operations), recomputation under remat is not counted.
# A multiply-add is two operations.


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


# -- 4. one call of a kernel: operations and bytes ---------------------------------

# What one call of each flash kernel of ``ops/attention.py`` needs. First, the
# products of [block, hd] by [hd, block] (or its transpose) it must form for a
# (query, key) pair, at 2*hd operations each: the forward kernel the score
# and the value product; the dq kernel the score again (only its log-sum-exp
# was kept), dO V^T and dS K; the dk/dv kernel the score again, P^T dO, dO V^T
# and dS^T Q. Then the arrays it reads or writes once: bfloat16 [rows, S, hd]
# with every head (q, o, dO, dq), the same with the key/value heads alone
# (k, v, dk, dv), and float32 [rows, S, 1] vectors (log-sum-exp, delta).
_FLASH = {"flash_fwd": (2, 2, 2, 1), "flash_bwd_dq": (3, 3, 2, 2),
          "flash_bwd_dkv": (4, 2, 4, 2)}


def kernel_cost(kernel: str, cfg: dict, facts: dict) -> Tuple[float, float]:
    """(operations, bytes) that ONE call of ``kernel`` on one chip needs at
    this run's shapes (``facts``: ``per_chip_batch``, ``seq_len``). A train
    step calls each attention kernel once a layer on ``per_chip_batch``
    sequences. What the algorithm needs, so that a roofline share errs low:
    the (seq_len + 1) * seq_len / 2 causal pairs and not the masked half of a
    diagonal block; every array once, keys and values at their own head count
    (a kernel handed them repeated per query head moves more)."""
    if kernel not in _FLASH:
        raise KeyError(f"dense_decoder counts no kernel {kernel!r}; "
                       f"known: {sorted(_FLASH)}")
    products, per_q, per_kv, vectors = _FLASH[kernel]
    B, S = facts["per_chip_batch"], facts["seq_len"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    pairs = S * (S + 1) // 2
    operations = products * 2 * hd * pairs * B * H
    nbytes = B * S * ((per_q * H + per_kv * KV) * hd * 2 + vectors * H * 4)
    return float(operations), float(nbytes)
