"""Arcee's AFMoE decoder as Trinity-Large-Preview configures it, ONE RANK of an
expert-parallel deployment: everything the benchmark knows about this
architecture, in the one module a configuration file names with ``"adapter":
"afmoe"``.

Written from the published configuration keys (``model_type: afmoe``) and from
what the catalog says of the family ("SWA(4096) gated; global every 4th",
"sigmoid routing, ... bias", "depth-scaled sandwich norm"); what the keys do
not state is recalled, there being no network here, and listed under the
configuration file's ``assumed``.

1. The plain float32 reference (``forward``, ``loss``), per layer::

     x0 = table[t] * sqrt(hidden_size)                      (mup_enabled)
     h  = RMSNorm(x)                                        input_layernorm
     q  = RMSNorm_hd(split(h Wq)); k = RMSNorm_hd(split(h Wk))   per head
     v  = split(h Wv);  g = sigmoid(h Wg)                   the attention gate
     sliding_attention: q, k = RoPE(q, k)      full_attention: no position
     a  = softmax(q k^T / sqrt(hd) + mask) v   embedding at all. causal; a
          sliding query sees its sliding_window newest keys, its own included
     x1 = x + RMSNorm((a * g) Wo)                           post_attention_layernorm
     m  = RMSNorm(x1)                                       pre_mlp_layernorm
     layer < num_dense_layers: y = SwiGLU(m), intermediate_size wide
     else: s = sigmoid(m Wr), num_experts wide; E = top-k of s + expert_bias;
           w = s[E] / (sum s[E] + 1e-20) * route_scale      route_norm
           y = SwiGLU_shared(m) + sum_{e in E} w_e SwiGLU_e(m)
     x2 = x1 + RMSNorm(y)                                   post_mlp_layernorm
     logits = RMSNorm(x_last) W_head

   THE SHARE. ``num_experts`` in a configuration file is how many routed
   experts this rank HOLDS; ``expert_parallel`` gives the deployment:
   ``{"routed_experts": 256, "ranks": 8, "rank": r}``. The router has
   ``routed_experts`` outputs and every token its top-k of ALL of them; the
   reference is given the matrices of experts ``r * num_experts .. (r + 1) *
   num_experts`` and the vocabulary's slice, and leaves out what an expert
   held elsewhere would add, as the program does: with all ranks' routed parts
   summed and the shared expert once it is the uncut layer
   (``tests/test_afmoe.py`` holds that). Attention a head at a time, experts
   one after the other on every token (weight 0 where a token did not choose
   it), the head in blocks of the vocabulary: no kernel, cache or batching.
   Callers wrap it in ``jax.default_matmul_precision("highest")``.
2. The way from the published keys to the program and to the reference
   (``program_overrides``, ``reference_cfg``, ``to_reference_params``).
3. Required operations per token and stored parameters.
4. Operations and bytes of one call of each kernel (``kernel_cost``).

Nothing here imports the program under test. ``cfg`` is a configuration
file's dict with the published key names; ``rcfg`` is ``reference_cfg(cfg)``.

Reference parameters are a plain dict: embed_tokens [V, d]; norm [d]; lm_head
[d, V]; layers: list of {input_layernorm, post_attention_layernorm,
pre_mlp_layernorm, post_mlp_layernorm [d], q_proj, gate_proj_attn [d, H hd],
k_proj, v_proj [d, KVH hd], q_norm, k_norm [hd], o_proj [H hd, d]} plus, dense:
{gate_proj [d, F], up_proj, down_proj [F, d]}; sparse: {router [d, R],
expert_bias [R], gate_proj [E, d, f], up_proj, down_proj [E, f, d],
shared_gate_proj [d, n_shared f], shared_up_proj, shared_down_proj}.
"""

from __future__ import annotations

import math
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


def attention(q, k, v, window: int):
    """Causal softmax attention, a query head at a time (head ``h`` reads key
    head ``h // (H / KVH)``); with ``window`` a query sees its ``window``
    newest keys, its own included. q [B, S, H, hd]; k, v [B, S, KVH, hd] ->
    [B, S, H, hd]."""
    B, S, H, hd = q.shape
    rep = H // k.shape[2]
    i = jnp.arange(S)
    seen = i[:, None] >= i[None, :]
    if window:
        seen &= i[None, :] > i[:, None] - window

    def head(args):
        qh, n = args                                       # [B, S, hd], head
        kh, vh = k[:, :, n // rep], v[:, :, n // rep]
        scores = jnp.einsum("bqd,bsd->bqs", qh, kh) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqs,bsd->bqd", probs, vh)

    out = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), jnp.arange(H)))
    return jnp.moveaxis(out, 0, 2)


def gated_attention(h, lp, positions, kind, rcfg):
    """The layer's attention on normalised ``h`` [B, S, d], gated, before
    o_proj: [B, S, H hd]."""
    B, S, _ = h.shape
    H, KVH, hd = (rcfg["num_attention_heads"], rcfg["num_key_value_heads"],
                  rcfg["head_dim"])
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    q = (h @ f32(lp["q_proj"])).reshape(B, S, H, hd)
    k = (h @ f32(lp["k_proj"])).reshape(B, S, KVH, hd)
    v = (h @ f32(lp["v_proj"])).reshape(B, S, KVH, hd)
    if rcfg["qk_norm"]:
        q = rms_norm(q, lp["q_norm"], rcfg["rms_norm_eps"])
        k = rms_norm(k, lp["k_norm"], rcfg["rms_norm_eps"])
    if kind in rcfg["rotated"]:
        q = rope(q, positions, rcfg["rope_theta"])
        k = rope(k, positions, rcfg["rope_theta"])
    a = attention(q, k, v, rcfg["sliding_window"]
                  if kind == "sliding_attention" else 0).reshape(B, S, H * hd)
    if rcfg["attention_gate"]:
        a = a * jax.nn.sigmoid(h @ f32(lp["gate_proj_attn"]))
    return a


def swiglu(h, gate, up, down):
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)


def routing(h, router, bias, rcfg):
    """h [..., d] -> (weights [..., top_k], experts [..., top_k]) over ALL the
    router's outputs: sigmoid scores; the bias chooses and does not weigh."""
    scores = jax.nn.sigmoid(h @ router.astype(jnp.float32))
    _, experts = jax.lax.top_k(scores + bias, rcfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if rcfg["route_norm"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * rcfg["route_scale"], experts


def routed_experts(h, lp, rcfg):
    """The part of ``sum_e w_e SwiGLU_e(h)`` that the experts held here give:
    expert ``j`` of the matrices is expert ``first_expert + j`` of the
    router's. Every held expert is computed on every token, with the token's
    weight for it (0 where it did not choose it)."""
    weights, experts = routing(h, lp["router"], lp["expert_bias"], rcfg)

    def one(y, e):
        index, gate, up, down = e
        w = jnp.where(experts == index, weights, 0.0).sum(-1)
        return y + w[..., None] * swiglu(h, gate, up, down), None

    held = lp["gate_proj"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (rcfg["first_expert"] + jnp.arange(held),
                         lp["gate_proj"], lp["up_proj"], lp["down_proj"]))
    return y


_HEAD_BLOCKS = 16


def head(x, w):
    """x [..., d] @ w [d, V] in float32, in blocks of the vocabulary where it
    divides."""
    d, V = w.shape
    if V % _HEAD_BLOCKS:
        return x @ w.astype(jnp.float32)
    blocks = jnp.moveaxis(w.reshape(d, _HEAD_BLOCKS, V // _HEAD_BLOCKS), 1, 0)
    out = jax.lax.map(lambda b: x @ b.astype(jnp.float32), blocks)
    return jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], V)


def forward(params, tokens, rcfg, last: Optional[int] = None):
    """tokens [B, S] int -> logits [B, S, V], float32 throughout; with
    ``last`` only those of the last ``last`` positions."""
    eps = rcfg["rms_norm_eps"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    post = (lambda y, w: rms_norm(y, w, eps)) if rcfg["sandwich_norm"] \
        else (lambda y, w: y)
    x = params["embed_tokens"][tokens].astype(jnp.float32) * rcfg["embed_scale"]
    for kind, lp in zip(rcfg["layer_types"], params["layers"]):
        h = rms_norm(x, lp["input_layernorm"], eps)
        a = gated_attention(h, lp, positions, kind, rcfg) \
            @ lp["o_proj"].astype(jnp.float32)
        x = x + post(a, lp["post_attention_layernorm"])
        m = rms_norm(x, lp["pre_mlp_layernorm"], eps)
        if "router" in lp:      # a sparse layer
            y = routed_experts(m, lp, rcfg) + swiglu(
                m, lp["shared_gate_proj"], lp["shared_up_proj"],
                lp["shared_down_proj"])
        else:
            y = swiglu(m, lp["gate_proj"], lp["up_proj"], lp["down_proj"])
        x = x + post(y, lp["post_mlp_layernorm"])
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
_ONLY = (("model_type", "afmoe"), ("hidden_act", "silu"), ("n_group", 1),
         ("topk_group", 1), ("num_expert_groups", 1),
         ("num_limited_groups", 1), ("rope_scaling", None),
         ("score_func", "sigmoid"), ("tie_word_embeddings", False),
         ("mup_enabled", True), ("num_shared_experts", 1))
_KINDS = {"sliding_attention": "window", "full_attention": "full"}


def layer_types(cfg: dict) -> Tuple[str, ...]:
    """The layers this file runs: the first ``num_hidden_layers`` of the
    published pattern (the file keeps the whole published list)."""
    types = tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])
    if len(types) != cfg["num_hidden_layers"] or set(types) - set(_KINDS):
        raise ValueError(f"{cfg.get('name')}: layer_types must name at least "
                         f"num_hidden_layers layers, each one of {sorted(_KINDS)}")
    return types


def share(cfg: dict) -> Tuple[int, int, int]:
    """(routed experts of the deployment, the first held here, how many)."""
    ep = cfg["expert_parallel"]
    held = cfg["num_experts"]
    if ep["routed_experts"] != ep["ranks"] * held or not 0 <= ep["rank"] < ep["ranks"]:
        raise ValueError(f"{cfg.get('name')}: expert_parallel {ep} does not "
                         f"share {ep['routed_experts']} experts into ranks of "
                         f"{held}")
    return ep["routed_experts"], ep["rank"] * held, held


def program_overrides(cfg: dict, max_seq_len: int) -> dict:
    """Published keys -> the program's ``TransformerConfig`` fields."""
    name = cfg.get("name")
    for key, must in _ONLY:
        if cfg[key] != must:
            raise ValueError(f"{name}: {key} = {cfg[key]!r}; the program "
                             f"expresses only {must!r}")
    routed, first, held = share(cfg)
    init = cfg["initializer"]
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                head_size=cfg["head_dim"],
                d_ff=cfg["moe_intermediate_size"],
                d_ff_dense=cfg["intermediate_size"],
                first_k_dense=cfg["num_dense_layers"], moe_every=1,
                max_seq_len=max_seq_len, rope_theta=float(cfg["rope_theta"]),
                tie_embeddings=False, remat=True, n_experts=routed,
                experts_held=(first, held),
                experts_per_token=cfg["num_experts_per_tok"],
                n_shared_experts=cfg["num_shared_experts"],
                norm_topk_prob=bool(cfg["route_norm"]), router_kind="sigmoid",
                routed_scaling_factor=float(cfg["route_scale"]),
                norm_eps=float(cfg["rms_norm_eps"]),
                layer_kinds=tuple(_KINDS[t] for t in layer_types(cfg)),
                block="rms", rope_kinds=("window",),
                window=cfg["sliding_window"], qk_head_norm=True,
                attn_gate=True, sandwich_norm=True,
                embed_scale=math.sqrt(cfg["hidden_size"]),
                attn_init_std=float(init["attention"]),
                mlp_init_std=float(init["mlp"]),
                expert_init_std=float(init["experts"]),
                embed_init_std=float(init["embedding"]),
                param_dtype=getattr(jnp, cfg["torch_dtype"]))


def reference_cfg(cfg: dict) -> dict:
    """What the plain reference needs: the published keys, the layers it runs,
    where this rank's experts begin, and the recalled parts of the layer as
    facts it can be asked to leave out (``tests/test_afmoe.py`` does)."""
    out = {k: cfg[k] for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
        "rms_norm_eps", "num_experts_per_tok", "route_norm", "route_scale",
        "sliding_window")}
    out.update(layer_types=layer_types(cfg), first_expert=share(cfg)[1],
               embed_scale=math.sqrt(cfg["hidden_size"]),
               rotated=("sliding_attention",), qk_norm=True,
               attention_gate=True, sandwich_norm=True)
    return out


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
            "post_attention_layernorm": lp["post_attn_norm"]["scale"],
            "pre_mlp_layernorm": lp["mlp_norm"]["scale"],
            "post_mlp_layernorm": lp["post_mlp_norm"]["scale"],
            "q_proj": flat_in(a["q_proj"]["kernel"]),
            "k_proj": flat_in(a["k_proj"]["kernel"]),
            "v_proj": flat_in(a["v_proj"]["kernel"]),
            "gate_proj_attn": flat_in(a["gate_proj"]["kernel"]),
            "q_norm": a["q_norm"]["scale"], "k_norm": a["k_norm"]["scale"],
            "o_proj": o.reshape(-1, o.shape[-1])}
        if "moe" in lp:
            m = lp["moe"]
            layer.update({
                "router": m["router"]["kernel"],
                "expert_bias": m["router_bias"],
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


# -- 3. required operations and stored parameters, from the shapes ---------------


def _attention_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 3 * d * H * hd + 2 * d * KVH * hd       # q, gate, o; k, v


def _mlp_params(cfg: dict, i: int, active: bool) -> int:
    """The layer's MLP matrices: stored HERE, or those a token multiplies by
    (its top-k routed experts wherever they are held, and the shared one)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    if i < cfg["num_dense_layers"]:
        return 3 * d * cfg["intermediate_size"]
    routed = share(cfg)[0]
    experts = cfg["num_experts_per_tok"] if active else cfg["num_experts"]
    return d * routed + 3 * d * f * (experts + cfg["num_shared_experts"])


def active_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by in the whole deployment's layers."""
    return sum(_attention_params(cfg) + _mlp_params(cfg, i, True)
               for i in range(cfg["num_hidden_layers"])) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward; a sliding layer's query counts the keys its
    window shows it."""
    per_key = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    attn = sum(per_key * (min(cfg["sliding_window"], (seq_len + 1) / 2)
                          if t == "sliding_attention" else (seq_len + 1) / 2)
               for t in layer_types(cfg))
    return 3.0 * (2.0 * active_matmul_params(cfg) + attn)


def total_params(cfg: dict) -> int:
    """Every parameter stored on this rank: the held experts, the whole
    router and its bias, the four norms a layer, the q and k head norms, the
    final norm, the vocabulary's slice of table and head."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    layers = 0
    for i in range(cfg["num_hidden_layers"]):
        bias = share(cfg)[0] if i >= cfg["num_dense_layers"] else 0
        layers += _attention_params(cfg) + 2 * hd + 4 * d \
            + _mlp_params(cfg, i, False) + bias
    return layers + 2 * cfg["vocab_size"] * d + d


# -- 4. one call of a kernel: operations and bytes ---------------------------------

# the mix's least prompt: the least a slot's pages and rings hold in a decode
# step. The live positions of a call are no fact of a run, so the attention
# kernels are counted there: their shares err low by live / 128 and can never
# pass 100% (as deepseek_v3 and phi4flash count theirs);
# attn.live_tokens_per_step and window.live_tokens_per_step scale them by hand
LEAST_PROMPT = 128
_KERNELS = ("paged_gqa_decode", "window_gqa_decode", "moe_gmm_decode",
            "moe_gmm_prefill")


def experts_touched(cfg: dict, rows: int) -> int:
    """HELD experts that get at least one of ``rows`` tokens when each
    token's experts are uniform over all the routed ones: held x (1 - (1 -
    k / routed)^rows), rounded down."""
    routed, _, held = share(cfg)
    k = cfg["num_experts_per_tok"]
    return int(held * (1.0 - (1.0 - k / routed) ** rows))


def kernel_cost(kernel: str, cfg: dict, facts: dict) -> Tuple[float, float]:
    """(operations, bytes) that ONE call of a kernel needs, in the stored type.

    ``paged_gqa_decode`` (the full layer's live pages, once a decode step) and
    ``window_gqa_decode`` (a sliding layer's rings, the same kernel): every
    query head's head_dim-wide score and value against each live position,
    and as bytes the live rows (keys and values of all key heads: 4,096 bytes
    a position), at max_num_seqs slots x the mix's least prompt. Bound by
    bytes.

    ``moe_gmm_decode``: one of the three products of a decode step's expert
    layer on THIS rank: of max_num_seqs x top_k assignments the share that
    uniform routing gives the held experts (held / routed), and the held
    experts those touch (12 of 32 at 32 slots), each matrix once, plus the
    rows in and out. ``moe_gmm_prefill``: the least a call holds, the mix's
    least prompt of real rows, counted the same way (27 of 32); a longer
    prompt multiplies more and streams at most 32, so the share errs low."""
    if kernel not in _KERNELS:
        raise KeyError(f"afmoe counts no kernel {kernel!r}; known: "
                       f"{sorted(_KERNELS)}")
    itemsize = jnp.dtype(cfg["torch_dtype"]).itemsize
    H, KVH, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    slots = facts.get("max_num_seqs") or cfg["job"]["engine"]["max_num_seqs"]
    if kernel in ("paged_gqa_decode", "window_gqa_decode"):
        rows = slots * min(LEAST_PROMPT, cfg["sliding_window"])
        return (float(rows * H * 2 * 2 * hd),
                float(rows * 2 * KVH * hd * itemsize))
    d, f, k = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts_per_tok"])
    routed, _, held = share(cfg)
    tokens = slots if kernel == "moe_gmm_decode" else LEAST_PROMPT
    rows = tokens * k * held / routed
    return (float(2 * rows * d * f),
            float((experts_touched(cfg, tokens) * d * f + rows * (d + f))
                  * itemsize))
