"""The DeepSeek-V3 family's decoder as Moonlight-16B-A3B configures it:
everything the benchmark knows about this architecture, in the one module a
configuration file names with ``"adapter": "deepseek_v3"``.

Written from the published configuration keys (``model_type: deepseek_v3``;
DeepSeek-V2, arXiv 2405.04434, section 2.1 for the latent attention;
DeepSeek-V3, arXiv 2412.19437, section 2.1.2 for the sigmoid router with a
selection bias; Moonlight, arXiv 2502.16982), with ``q_lora_rank`` null,
``n_group`` = ``topk_group`` = 1, no ``rope_scaling`` and no multi-token
prediction: what the file refuses otherwise is listed in ``program_overrides``.

1. The plain float32 reference (``forward``, ``loss``). Per layer, pre-norm:
   ``q = h W_q`` -> heads of ``qk_nope_head_dim + qk_rope_head_dim``; ``a = h
   W_kva`` = ``c_raw`` [kv_lora_rank] | ``k_pe_raw`` [qk_rope_head_dim, one for
   all heads]; ``c = RMSNorm(c_raw)``; rotary position embedding over the
   rope parts only, in the half-split "rotate_half" pairing (pairs (i, i +
   hd/2); the checkpoint interleaves, a column permutation of seeded
   weights); ``kv = c W_kvb`` -> heads of ``k_nope`` | ``v``; ``k = k_nope |
   k_pe``; causal softmax attention scaled by ``1 / sqrt(nope + rope)``, the
   values ``v_head_dim`` wide; output projection; residual. The first
   ``first_k_dense_replace`` layers have a SwiGLU of ``intermediate_size``;
   the others a mixture of experts: ``s = sigmoid(h W_gate)``, the experts
   are the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``,
   the weights are ``s`` of the chosen WITHOUT the bias, divided by their sum
   (+ 1e-20) with ``norm_topk_prob``, times ``routed_scaling_factor``; ``y =
   sum_e w_e down_e(silu(gate_e h) * up_e h) + shared(h)``, ``shared`` one
   SwiGLU of width ``n_shared_experts x moe_intermediate_size``. DROPLESS.
   The attention is EXPANDED only (no cache, no absorbed products) and
   computed a head at a time; the experts are computed densely, one after
   the other on every token with the token's weight for that expert (0 where
   it did not choose it): no sort, no groups; the head is computed in blocks
   of the vocabulary. None of that changes the mathematics: it keeps the
   float32 temporaries of a 4,104-position check beside 14 GB of program
   state. Callers wrap it in ``jax.default_matmul_precision("highest")``.
2. The way from the published keys to the program and to the reference
   (``program_overrides``, ``reference_cfg``, ``to_reference_params``).
3. Required operations per token and stored parameters.
4. Operations and bytes of one call of each kernel (``kernel_cost``).

Nothing here imports the program under test. ``cfg`` is a configuration
file's dict with the published key names; ``rcfg`` is ``reference_cfg(cfg)``.

Reference parameters are a plain dict:
  embed_tokens [V, d]; norm [d]; lm_head [d, V];
  layers: list of {input_layernorm [d], q_proj [d, H*(nope+rope)],
  kv_a_proj_with_mqa [d, R+rope], kv_a_layernorm [R], kv_b_proj [R,
  H*(nope+v)] (per head k_nope | v), o_proj [H*v, d],
  post_attention_layernorm [d]} plus, dense: {gate_proj [d, F], up_proj,
  down_proj [F, d]}; sparse: {router [d, E], e_score_correction_bias [E],
  gate_proj [E, d, f], up_proj, down_proj [E, f, d], shared_gate_proj [d,
  n_shared*f], shared_up_proj, shared_down_proj}
"""

from __future__ import annotations

from typing import Optional, Tuple

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


def attention(q, k, v, scale):
    """Causal softmax attention, a head at a time. q, k [B, S, H, dk];
    v [B, S, H, dv] -> [B, S, H*dv]."""
    B, S, H, _ = q.shape
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(qkv):
        qh, kh, vh = qkv                                  # [B, S, d]
        scores = jnp.einsum("bqd,bsd->bqs", qh, kh) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqs,bsd->bqd", probs, vh)

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(out, 0, 2).reshape(B, S, -1)


def latent_attention(h, lp, positions, rcfg):
    """The layer's attention on normalised ``h`` [B, S, d], before o_proj."""
    B, S, _ = h.shape
    H, R = rcfg["num_attention_heads"], rcfg["kv_lora_rank"]
    nope, rp, dv = (rcfg["qk_nope_head_dim"], rcfg["qk_rope_head_dim"],
                    rcfg["v_head_dim"])
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    q = (h @ f32(lp["q_proj"])).reshape(B, S, H, nope + rp)
    a = h @ f32(lp["kv_a_proj_with_mqa"])
    c = rms_norm(a[..., :R], lp["kv_a_layernorm"], rcfg["rms_norm_eps"])
    k_pe = rope(a[..., None, R:], positions, rcfg["rope_theta"])  # [B,S,1,rp]
    q_pe = rope(q[..., nope:], positions, rcfg["rope_theta"])
    kv = (c @ f32(lp["kv_b_proj"])).reshape(B, S, H, nope + dv)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, q_pe.shape)],
                        axis=-1)
    return attention(q, k, kv[..., nope:], (nope + rp) ** -0.5)


def swiglu(h, gate, up, down):
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)


def routing(h, router, bias, rcfg):
    """h [..., d] -> (weights [..., top_k], experts [..., top_k]): sigmoid
    scores; the bias chooses and does not weigh."""
    scores = jax.nn.sigmoid(h @ router.astype(jnp.float32))
    _, experts = jax.lax.top_k(scores + bias, rcfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if rcfg["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * rcfg["routed_scaling_factor"], experts


def experts_mlp(h, lp, rcfg):
    """Sum over a token's experts of weight x SwiGLU, every expert computed on
    every token, plus the shared expert."""
    weights, experts = routing(h, lp["router"], lp["e_score_correction_bias"],
                               rcfg)

    def one(y, e):
        index, gate, up, down = e
        w = jnp.where(experts == index, weights, 0.0).sum(-1)   # 0 if not chosen
        return y + w[..., None] * swiglu(h, gate, up, down), None

    E = lp["gate_proj"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(E), lp["gate_proj"], lp["up_proj"],
                         lp["down_proj"]))
    return y + swiglu(h, lp["shared_gate_proj"], lp["shared_up_proj"],
                      lp["shared_down_proj"])


_HEAD_BLOCKS = 16


def head(x, w):
    """x [..., d] @ w [d, V] in float32, ``_HEAD_BLOCKS`` blocks of the
    vocabulary after each other where it divides: the float32 copy of a
    163,840-row head is 1.3 GB at once."""
    d, V = w.shape
    if V % _HEAD_BLOCKS:
        return x @ w.astype(jnp.float32)
    blocks = jnp.moveaxis(w.reshape(d, _HEAD_BLOCKS, V // _HEAD_BLOCKS), 1, 0)
    out = jax.lax.map(lambda b: x @ b.astype(jnp.float32), blocks)
    return jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], V)


def forward(params, tokens, rcfg, last: Optional[int] = None):
    """tokens [B, S] int -> logits [B, S, V], float32 throughout; with
    ``last`` only those of the last ``last`` positions ([B, last, V]: every
    position is still computed through the layers)."""
    eps = rcfg["rms_norm_eps"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for lp in params["layers"]:
        h = rms_norm(x, lp["input_layernorm"], eps)
        x = x + latent_attention(h, lp, positions, rcfg) \
            @ lp["o_proj"].astype(jnp.float32)
        h = rms_norm(x, lp["post_attention_layernorm"], eps)
        if "router" in lp:      # a sparse layer
            x = x + experts_mlp(h, lp, rcfg)
        else:
            x = x + swiglu(h, lp["gate_proj"], lp["up_proj"], lp["down_proj"])
    if last is not None:
        x = x[:, S - last:]
    return head(rms_norm(x, params["norm"], eps), params["lm_head"])


def loss(params, tokens, targets, rcfg):
    """Mean next-token cross-entropy; ``targets`` are ``tokens`` shifted by one."""
    logp = jax.nn.log_softmax(forward(params, tokens, rcfg), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# -- 2. from the published keys to the program and to the reference -------------

# what the program (ray_tpu/) cannot express of this family: refused, so that
# nothing else runs under the model's name
_ONLY = (("q_lora_rank", None), ("n_group", 1), ("topk_group", 1),
         ("rope_scaling", None), ("num_nextn_predict_layers", 0),
         ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
         ("attention_bias", False), ("hidden_act", "silu"),
         ("tie_word_embeddings", False), ("ep_size", 1),
         ("model_type", "deepseek_v3"))


def program_overrides(cfg: dict, max_seq_len: int) -> dict:
    """Published keys -> the program's ``TransformerConfig`` fields."""
    name = cfg.get("name")
    stated = {"rope_scaling": None, **cfg}   # the published file leaves it out
    for key, must in _ONLY:
        if stated[key] != must:
            raise ValueError(f"{name}: {key} = {stated[key]!r}; the program "
                             f"expresses only {must!r}")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError(f"{name}: num_key_value_heads: latent attention has "
                         "one latent for all heads, and as many keys as heads")
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                d_ff=cfg["moe_intermediate_size"],
                d_ff_dense=cfg["intermediate_size"],
                first_k_dense=cfg["first_k_dense_replace"],
                moe_every=cfg["moe_layer_freq"], max_seq_len=max_seq_len,
                rope_theta=float(cfg["rope_theta"]), tie_embeddings=False,
                remat=True, n_experts=cfg["n_routed_experts"],
                experts_per_token=cfg["num_experts_per_tok"],
                n_shared_experts=cfg["n_shared_experts"],
                norm_topk_prob=bool(cfg["norm_topk_prob"]),
                router_kind="sigmoid",
                routed_scaling_factor=float(cfg["routed_scaling_factor"]),
                norm_eps=float(cfg["rms_norm_eps"]),
                kv_latent_rank=cfg["kv_lora_rank"],
                qk_nope_head_dim=cfg["qk_nope_head_dim"],
                qk_rope_head_dim=cfg["qk_rope_head_dim"],
                v_head_dim=cfg["v_head_dim"],
                attn_init_std=float(cfg["initializer_range"]),
                mlp_init_std=float(cfg["mlp_initializer_range"]),
                param_dtype=getattr(jnp, cfg["torch_dtype"]))


def reference_cfg(cfg: dict) -> dict:
    """What the plain reference needs, all as published; which layers are
    sparse it reads off the parameters (a layer with a ``router``)."""
    return {k: cfg[k] for k in (
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rope_theta", "rms_norm_eps",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")}


def to_reference_params(p: dict, cfg: dict) -> dict:
    """The program's parameter tree under the reference's plain names.
    Reshapes only (heads folded into one axis); called inside a jit so no
    copy of the weights outlives the check."""
    def flat_in(k):   # [d, heads, hd] -> [d, heads*hd]
        return k.reshape(k.shape[0], -1)

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = p[f"layer_{i}"]
        a = lp["attn"]
        o = a["o_proj"]["kernel"]
        layer = {
            "input_layernorm": lp["attn_norm"]["scale"],
            "q_proj": flat_in(a["q_proj"]["kernel"]),
            "kv_a_proj_with_mqa": a["kv_a_proj"]["kernel"],
            "kv_a_layernorm": a["kv_a_norm"]["scale"],
            "kv_b_proj": flat_in(a["kv_b_proj"]["kernel"]),
            "o_proj": o.reshape(-1, o.shape[-1]),
            "post_attention_layernorm": lp["mlp_norm"]["scale"]}
        if "moe" in lp:
            m = lp["moe"]
            layer.update({
                "router": m["router"]["kernel"],
                "e_score_correction_bias": m["router_bias"],
                "gate_proj": m["gate_proj"], "up_proj": m["up_proj"],
                "down_proj": m["down_proj"]})
            layer.update({"shared_" + n: m["shared"][n]["kernel"]
                          for n in ("gate_proj", "up_proj", "down_proj")})
        else:
            layer.update({n: lp["mlp"][n]["kernel"]
                          for n in ("gate_proj", "up_proj", "down_proj")})
        layers.append(layer)
    return {"embed_tokens": p["embed"], "norm": p["final_norm"]["scale"],
            "lm_head": p["lm_head"], "layers": layers}


# -- 3. required operations, from the shapes --------------------------------------
#
# What the algorithm needs: a token multiplies by the attention matrices (the
# expanded form: q, the latent down-projection, the up-projection to keys and
# values, o), then by the dense MLP, or by the router, its num_experts_per_tok
# experts and the shared expert, and by the head; causal attention counts the
# keys at or before each query, nope + rope for the score and v_head_dim for
# the value. A multiply-add is two.


def _sparse(cfg: dict, i: int) -> bool:
    return (i >= cfg["first_k_dense_replace"]
            and i % cfg["moe_layer_freq"] == 0)


def _attention_params(cfg: dict) -> int:
    d, H, R = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rp, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    return d * H * (nope + rp) + d * (R + rp) + R * H * (nope + dv) + H * dv * d


def _mlp_params(cfg: dict, i: int, active: bool) -> int:
    """The layer's MLP matrices: stored, or those a token multiplies by."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    if not _sparse(cfg, i):
        return 3 * d * cfg["intermediate_size"]
    experts = cfg["num_experts_per_tok"] if active else cfg["n_routed_experts"]
    return d * cfg["n_routed_experts"] + 3 * d * f * (
        experts + cfg["n_shared_experts"])


def active_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by."""
    return sum(_attention_params(cfg) + _mlp_params(cfg, i, True)
               for i in range(cfg["num_hidden_layers"])) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops_per_token_fwd(cfg: dict, seq_len: int) -> float:
    per_key = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    return cfg["num_hidden_layers"] * per_key * (seq_len + 1) / 2


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward (two products for each one of the forward)."""
    return 3.0 * (2.0 * active_matmul_params(cfg)
                  + attention_flops_per_token_fwd(cfg, seq_len))


def total_params(cfg: dict) -> int:
    """Every stored parameter: all experts, the selection bias, the latent's
    norm, the two layer norms, the final norm, table and head."""
    d = cfg["hidden_size"]
    layers = 0
    for i in range(cfg["num_hidden_layers"]):
        bias = cfg["n_routed_experts"] if _sparse(cfg, i) else 0
        layers += _attention_params(cfg) + cfg["kv_lora_rank"] + 2 * d \
            + _mlp_params(cfg, i, False) + bias
    return layers + 2 * cfg["vocab_size"] * d + d


# -- 4. one call of a kernel: operations and bytes ---------------------------------

# the prefill bucket whose flash calls ``mla_prefill_flash_roofline`` reads,
# and the mix's least prompt, which is also the least prefill bucket
FLASH_BUCKET = 4096
LEAST_PROMPT = 512
_KERNELS = ("mla_decode", "flash_fwd", "moe_gmm_decode", "moe_gmm_prefill")


def experts_touched(cfg: dict, rows: int) -> int:
    """Experts that get at least one of ``rows`` tokens when each token's
    experts are uniform over the layer's: E x (1 - (1 - k/E)^rows), rounded
    down."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return int(E * (1.0 - (1.0 - k / E) ** rows))


def kernel_cost(kernel: str, cfg: dict, facts: dict) -> Tuple[float, float]:
    """(operations, bytes) that ONE call of a kernel needs, in the stored type.

    ``mla_decode`` (a layer of a decode step): all heads against each live
    row once, scores over kv_lora_rank + qk_rope_head_dim and values over
    kv_lora_rank, and as bytes the live rows at their unpadded width (1,152
    bytes; the device holds 1,280, so even a perfect kernel reads 90%). The
    live positions of a call are no fact of a run, so a call is counted at
    the LEAST the mix allows: every slot full at the shortest prompt, 512
    positions. Bound by bytes; errs low by live / 512 (about 5 x at the
    mix's mean), and can never pass 100% whatever a traced slice holds:
    ``mla.live_tokens_per_step`` scales it by hand.

    ``flash_fwd`` (the [1, 4096] prefill bucket): causal pairs x heads x 2 x
    (score width + value width); q, k, v, o once each.

    ``moe_gmm_decode``: one of the three products over max_num_seqs x top_k
    assignments; the experts uniform routing touches, once, plus the rows in
    and out. ``moe_gmm_prefill``: the least a call holds, the 512-row bucket
    full of real rows: every expert's matrix once (512 x 6 assignments
    leave none out) plus the rows; a longer prompt multiplies more and
    streams the same, so the share errs low above it."""
    if kernel not in _KERNELS:
        raise KeyError(f"deepseek_v3 counts no kernel {kernel!r}; known: "
                       f"{sorted(_KERNELS)}")
    itemsize = jnp.dtype(cfg["torch_dtype"]).itemsize
    H = cfg["num_attention_heads"]
    slots = facts.get("max_num_seqs") or cfg["job"]["engine"]["max_num_seqs"]
    if kernel == "mla_decode":
        score = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
        rows = slots * LEAST_PROMPT
        return (float(rows * H * 2 * (score + cfg["kv_lora_rank"])),
                float(rows * score * itemsize))
    if kernel == "flash_fwd":
        S = FLASH_BUCKET
        dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        dv = cfg["v_head_dim"]
        return (float(H * S * (S + 1) // 2 * 2 * (dk + dv)),
                float(2 * S * H * (dk + dv) * itemsize))
    d, f, k = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts_per_tok"])
    if kernel == "moe_gmm_decode":
        rows, touched = slots, experts_touched(cfg, slots)
    else:
        rows, touched = LEAST_PROMPT, cfg["n_routed_experts"]
    return (float(2 * rows * k * d * f),
            float((touched * d * f + rows * k * (d + f)) * itemsize))
