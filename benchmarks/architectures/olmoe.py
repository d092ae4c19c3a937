"""The OLMoE decoder: everything the benchmark knows about this architecture,
in the one module a configuration file names with ``"adapter": "olmoe"``.

Written from the published configuration keys and model code of OLMoE-1B-7B
(Muennighoff et al., arXiv 2409.02060; ``model_type: olmoe``): a pre-norm
decoder in which every layer's MLP is a mixture of experts.

1. The plain float32 reference (``forward``, ``loss``): embedding; per layer
   RMSNorm, q/k/v projections, RMSNorm of q and of k over the WHOLE
   projection (all heads as one vector, before the split into heads), rotary
   position embedding in the half-split "rotate_half" convention (pairs
   (i, i + hd/2) turned by position * theta^(-2i/hd), as ``dense_decoder.py``
   states it and as the program pairs dimensions), causal softmax attention,
   output projection, residual; RMSNorm, router logits, float32 softmax over
   the experts, the ``num_experts_per_tok`` largest, renormalised only with
   ``norm_topk_prob``, the sum over them of weight x ``down(silu(gate(x)) *
   up(x))``, residual; final norm; output head. DROPLESS: every token reaches
   every expert it chose. The experts are computed densely, one after the
   other on every token with the token's weight for that expert (0 where it
   did not choose it): no sort, no groups, no cache, no batching trick, so it
   shares nothing with the program's dispatch. Callers wrap it in
   ``jax.default_matmul_precision("highest")``.
2. The way from the published keys to the program and to the reference
   (``program_overrides``, ``reference_cfg``, ``to_reference_params``);
   what the program cannot express is refused there.
3. Required operations per token and stored parameters.
4. Operations and bytes of one call of the expert layer's grouped matmul
   (``kernel_cost``).

Nothing here imports the program under test. ``cfg`` is a configuration
file's dict with the published key names; ``rcfg`` is ``reference_cfg(cfg)``.

Reference parameters are a plain dict:
  embed_tokens [V, d]; norm [d]; lm_head [d, V] (absent when tied);
  layers: list of {input_layernorm [d], q_proj [d, H*hd], k_proj [d, KV*hd],
  v_proj [d, KV*hd], q_norm [H*hd], k_norm [KV*hd], o_proj [H*hd, d],
  post_attention_layernorm [d], router [d, E], gate_proj [E, d, f],
  up_proj [E, d, f], down_proj [E, f, d]}
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


def routing(h, router, top_k, norm_topk_prob):
    """h [..., d] -> (weights [..., top_k], experts [..., top_k])."""
    probs = jax.nn.softmax(h @ router.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights, experts


def experts_mlp(h, lp, rcfg):
    """Sum over a token's experts of weight x SwiGLU, every expert computed on
    every token."""
    weights, experts = routing(h, lp["router"], rcfg["num_experts_per_tok"],
                               rcfg["norm_topk_prob"])

    def one(y, e):
        index, gate, up, down = e
        w = jnp.where(experts == index, weights, 0.0).sum(-1)   # [...]: 0 if not chosen
        act = jax.nn.silu(h @ gate.astype(jnp.float32)) * (h @ up.astype(jnp.float32))
        return y + w[..., None] * (act @ down.astype(jnp.float32)), None

    E = lp["gate_proj"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(E), lp["gate_proj"], lp["up_proj"],
                         lp["down_proj"]))
    return y


def forward(params, tokens, rcfg):
    """tokens [B, S] int -> logits [B, S, V], float32 throughout."""
    H, KV, hd = (rcfg["num_attention_heads"], rcfg["num_key_value_heads"],
                 rcfg["head_dim"])
    eps, theta = rcfg["rms_norm_eps"], rcfg["rope_theta"]
    B, S = tokens.shape
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = f32(params["embed_tokens"][tokens])
    for lp in params["layers"]:
        h = rms_norm(x, lp["input_layernorm"], eps)
        q, k, v = (h @ f32(lp[n]) for n in ("q_proj", "k_proj", "v_proj"))
        if rcfg["qk_norm"]:
            q = rms_norm(q, lp["q_norm"], eps)
            k = rms_norm(k, lp["k_norm"], eps)
        q = rope(q.reshape(B, S, H, hd), positions, theta)
        k = rope(k.reshape(B, S, KV, hd), positions, theta)
        x = x + attention(q, k, v.reshape(B, S, KV, hd)) @ f32(lp["o_proj"])
        h = rms_norm(x, lp["post_attention_layernorm"], eps)
        x = x + experts_mlp(h, lp, rcfg)
    x = rms_norm(x, params["norm"], eps)
    if rcfg["tie_word_embeddings"]:
        return x @ f32(params["embed_tokens"]).T
    return x @ f32(params["lm_head"])


def loss(params, tokens, targets, rcfg):
    """Mean next-token cross-entropy; ``targets`` are ``tokens`` shifted by one."""
    logp = jax.nn.log_softmax(forward(params, tokens, rcfg), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# -- 2. from the published keys to the program and to the reference -------------


def program_overrides(cfg: dict, max_seq_len: int) -> dict:
    """Published keys -> the program's ``TransformerConfig`` fields. Refuses
    what the program cannot express rather than run something else under this
    model's name."""
    name = cfg.get("name")
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    if hd != cfg["head_dim"]:
        raise ValueError("the program derives head_dim = hidden/heads; "
                         f"{name} states {cfg['head_dim']}")
    for key, must in (("clip_qkv", None), ("attention_bias", False),
                      ("rope_scaling", None), ("hidden_act", "silu"),
                      ("model_type", "olmoe")):
        if cfg[key] != must:
            raise ValueError(f"{name}: {key} = {cfg[key]!r}; the program "
                             f"expresses only {must!r}")
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                # config.json has no key of its own for an expert's width
                d_ff=cfg["intermediate_size"], max_seq_len=max_seq_len,
                rope_theta=float(cfg["rope_theta"]),
                tie_embeddings=bool(cfg["tie_word_embeddings"]), remat=True,
                n_experts=cfg["num_experts"], moe_every=1,
                experts_per_token=cfg["num_experts_per_tok"],
                norm_topk_prob=bool(cfg["norm_topk_prob"]),
                norm_eps=float(cfg["rms_norm_eps"]),
                qk_norm=True,                    # what model_type olmoe means
                param_dtype=getattr(jnp, cfg["torch_dtype"]))


def reference_cfg(cfg: dict) -> dict:
    """What the plain reference needs, all as published."""
    return {k: cfg[k] for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
        "rms_norm_eps", "num_experts_per_tok", "norm_topk_prob",
        "tie_word_embeddings")} | {"qk_norm": cfg["model_type"] == "olmoe"}


def to_reference_params(p: dict, cfg: dict) -> dict:
    """The program's parameter tree under the reference's plain names.
    Reshapes only (heads folded into one axis); called inside a jit so no
    copy of the weights outlives the check."""
    def flat_in(k):   # [d, heads, hd] -> [d, heads*hd]
        return k.reshape(k.shape[0], -1)

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = p[f"layer_{i}"]
        a, m = lp["attn"], lp["moe"]
        o = a["o_proj"]["kernel"]
        layers.append({
            "input_layernorm": lp["attn_norm"]["scale"],
            "q_proj": flat_in(a["q_proj"]["kernel"]),
            "k_proj": flat_in(a["k_proj"]["kernel"]),
            "v_proj": flat_in(a["v_proj"]["kernel"]),
            "q_norm": a["q_norm"]["scale"], "k_norm": a["k_norm"]["scale"],
            "o_proj": o.reshape(-1, o.shape[-1]),
            "post_attention_layernorm": lp["mlp_norm"]["scale"],
            "router": m["router"]["kernel"],
            "gate_proj": m["gate_proj"], "up_proj": m["up_proj"],
            "down_proj": m["down_proj"]})
    out = {"embed_tokens": p["embed"], "norm": p["final_norm"]["scale"],
           "layers": layers}
    if "lm_head" in p:
        out["lm_head"] = p["lm_head"]
    return out


# -- 3. required operations, from the shapes --------------------------------------
#
# What the algorithm needs: a token multiplies by the four attention matrices,
# the router, the three matrices of each of its num_experts_per_tok experts
# (not of the experts it did not choose) and the head; causal attention counts
# the keys at or before each query; the embedding lookup, the softmax over the
# experts and the top-k are no matrix products. A multiply-add is two.


def _layer_matmul_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    attn = 2 * d * cfg["num_attention_heads"] * hd \
        + 2 * d * cfg["num_key_value_heads"] * hd
    return attn + d * cfg["num_experts"]


def active_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by."""
    d = cfg["hidden_size"]
    experts = cfg["num_experts_per_tok"] * 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (_layer_matmul_params(cfg) + experts) \
        + d * cfg["vocab_size"]


def attention_flops_per_token_fwd(cfg: dict, seq_len: int) -> float:
    """QK^T and PV, forward, averaged over one sequence: position i attends
    to i + 1 keys, (seq_len + 1) / 2 in the mean, each 2*hd for the score and
    2*hd for the value, per head and layer."""
    per_key = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    return cfg["num_hidden_layers"] * per_key * (seq_len + 1) / 2


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 2.0 * active_matmul_params(cfg) \
        + attention_flops_per_token_fwd(cfg, seq_len)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward (two products for each one of the forward)."""
    return 3.0 * forward_flops_per_token(cfg, seq_len)


def total_params(cfg: dict) -> int:
    """Every stored parameter: all experts, the q/k norm scales, the two layer
    norms, the final norm, the table once when tied."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    experts = cfg["num_experts"] * 3 * d * cfg["intermediate_size"]
    norms = 2 * d + (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) * hd
    table = cfg["vocab_size"] * d
    head = 0 if cfg["tie_word_embeddings"] else table
    return cfg["num_hidden_layers"] * (_layer_matmul_params(cfg) + experts + norms) \
        + table + head + d


# -- 4. one call of a kernel: operations and bytes ---------------------------------

_GMM = ("moe_gmm_decode", "moe_gmm_prefill")


def experts_touched(cfg: dict, rows: int) -> int:
    """Experts that get at least one of ``rows`` tokens when each token's
    num_experts_per_tok experts are uniform over the layer's: E x (1 - (1 -
    k/E)^rows), rounded down."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return int(E * (1.0 - (1.0 - k / E) ** rows))


def kernel_cost(kernel: str, cfg: dict, facts: dict) -> Tuple[float, float]:
    """(operations, bytes) that ONE call of the expert layer's grouped matmul
    needs. A call is one of the layer's three products (gate, up, down), each
    rows x top_k assignments by a [d, f] or [f, d] matrix: 2 x rows x top_k x
    d x f operations, and as bytes the touched experts' matrices once plus the
    assignments' rows in and out, in the stored type.

    Decode: one token a slot (``max_num_seqs`` rows: the cell is saturated),
    from sequences that have nothing to do with each other, so the experts
    touched are those of uniform routing. Prefill: a call holds the prompts
    admitted in that step, which no fact of the run gives (4 to 16 x 128
    positions), and the tokens of one prompt share most of their experts
    (attention mixes them: a 20-token prompt touched about 31 on the chip,
    where uniform routing gives 60). So a prefill call is counted at the
    least any call with a real row needs, ONE token: its top_k experts'
    matrices. Its share therefore errs low, by as much as a call holds more
    than one token's experts; it still moves with the kernel's speed."""
    if kernel not in _GMM:
        raise KeyError(f"olmoe counts no kernel {kernel!r}; known: {sorted(_GMM)}")
    d, f, k = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_experts_per_tok"])
    if kernel == "moe_gmm_decode":
        rows = facts.get("max_num_seqs") or cfg["job"]["engine"]["max_num_seqs"]
        touched = experts_touched(cfg, rows)
    else:
        rows, touched = 1, k
    itemsize = jnp.dtype(cfg["torch_dtype"]).itemsize
    operations = 2 * rows * k * d * f
    nbytes = (touched * d * f + rows * k * (d + f)) * itemsize
    return float(operations), float(nbytes)
