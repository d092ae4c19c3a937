"""Plain float32 reference of the Llama-shaped dense decoder.

Written from the published description of the architecture (pre-norm
residual blocks; RMSNorm; rotary position embedding in the half-split
"rotate_half" convention; grouped-query attention with a causal mask; SwiGLU
MLP; tied or untied output head; mean next-token cross-entropy). It imports
nothing from the program under test and uses no kernel, cache or batching
trick. Callers wrap it in ``jax.default_matmul_precision("highest")``: on a
TPU a float32 product otherwise runs in reduced precision.

Parameters are a plain dict:
  embed_tokens [V, d]; norm [d]; lm_head [d, V] (absent when tied);
  layers: list of {input_layernorm [d], q_proj [d, H*hd], k_proj [d, KV*hd],
  v_proj [d, KV*hd], o_proj [H*hd, d], post_attention_layernorm [d],
  gate_proj [d, f], up_proj [d, f], down_proj [f, d]}
``cfg`` carries num_attention_heads, num_key_value_heads, head_dim,
rope_theta, rms_norm_eps and tie_word_embeddings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def forward(params, tokens, cfg):
    """tokens [B, S] int -> logits [B, S, V], float32 throughout."""
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
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
    if cfg["tie_word_embeddings"]:
        return x @ params["embed_tokens"].astype(jnp.float32).T
    return x @ params["lm_head"]


def loss(params, tokens, targets, cfg):
    """Mean next-token cross-entropy; ``targets`` are ``tokens`` shifted by one."""
    logp = jax.nn.log_softmax(forward(params, tokens, cfg), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()
