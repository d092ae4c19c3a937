"""Liquid AI's LFM2 sparse decoder as LFM2-8B-A1B configures it (``model_type:
lfm2_moe``): everything the benchmark knows about this architecture, in the
one module a configuration file names with ``"adapter": "lfm2_moe"``.

Written from the published configuration keys and from the published code
``modeling_lfm2_moe.py`` as recalled, there being no network here; what the
keys do not state is listed under the configuration file's ``assumed``.

1. The plain float32 reference (``forward``, ``loss``). Block ``i``, RMSNorm
   with ``norm_eps``, no bias anywhere (``conv_bias: false``)::

     h = x + mixer_i(RMSNorm(x))                            operator_norm
     y = h + ffn_i(RMSNorm(h))                              ffn_norm
     logits = RMSNorm(y_last) table^T                       embedding_norm, tied

   ``layer_types[i] == "conv"``, a gated short convolution::

     B | C | z = u W_in              (hidden -> 3 hidden, split in that order)
     s    = B * z
     c[t] = w[:, 0] s[t-2] + w[:, 1] s[t-1] + w[:, 2] s[t]     conv_L_cache = 3
            depthwise, causal, zeros before the first position
     out  = (C * c) W_out

   written as ``conv_L_cache`` shifted products and a sum; no activation, no
   position embedding. ``"full_attention"``: q, k, v projections, RMSNorm over
   the lanes of every q and k head (one scale for q, one for k) BEFORE RoPE
   (``rope_theta``; every attention layer rotates), causal softmax attention a
   query head at a time, ``out_proj``.

   ``ffn_i``: ``i < num_dense_layers``: SwiGLU at ``intermediate_size``. Else
   ``scores = sigmoid(h W_r)`` in float32; the experts are the top-k of
   ``scores + expert_bias`` (``use_expert_bias``: it chooses and does not
   weigh); the weights are the chosen scores over their sum + 1e-6
   (``norm_topk_prob``) times ``routed_scaling_factor``; every expert a
   SwiGLU at ``moe_intermediate_size``, computed here one after the other on
   every token (weight 0 where a token did not choose it); no shared expert,
   no groups, dropless. No kernel, cache or batching. Callers wrap it in
   ``jax.default_matmul_precision("highest")``.
2. The way from the published keys to the program and to the reference
   (``program_overrides``, ``reference_cfg``, ``to_reference_params``).
3. Required operations per token and stored parameters.
4. Operations and bytes of one call of each kernel (``kernel_cost``).

Nothing here imports the program under test. ``cfg`` is a configuration
file's dict with the published key names; ``rcfg`` is ``reference_cfg(cfg)``.

Reference parameters are a plain dict: embed_tokens [V, d]; embedding_norm
[d]; layers: list of {operator_norm, ffn_norm [d]} plus, conv: {in_proj [d,
3 d], conv [d, L] (the published ``conv.weight`` [d, 1, L] without its middle
axis: tap L-1 multiplies the position itself), out_proj [d, d]}; attention:
{q_proj [d, H hd], k_proj, v_proj [d, KVH hd], q_layernorm, k_layernorm [hd],
out_proj [H hd, d]}; dense: {w1, w3 [d, F], w2 [F, d]}; sparse: {router [d,
E], expert_bias [E], w1, w3 [E, d, f], w2 [E, f, d]}.
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


def _f32(a):
    return a.astype(jnp.float32)


def shifted(s, by: int):
    """s [B, S, d] moved ``by`` positions later, zeros before the first."""
    if by == 0:
        return s
    return jnp.pad(s, ((0, 0), (by, 0), (0, 0)))[:, :s.shape[1]]


def short_conv(u, lp, rcfg):
    """The gated short convolution on normalised ``u`` [B, S, d]."""
    L = rcfg["conv_L_cache"]
    b, c, z = jnp.split(u @ _f32(lp["in_proj"]), 3, axis=-1)
    w = _f32(lp["conv"])                                   # [d, L]
    if rcfg["taps_reversed"]:
        w = w[:, ::-1]
    s = b * z if rcfg["gate_before_conv"] else z
    # tap L-1 is the position itself, tap L-1-j the one j earlier; a lag puts
    # every earlier tap one position further back (a state kept a row late)
    y = sum(w[:, L - 1 - j] * shifted(s, j + (rcfg["state_lag"] if j else 0))
            for j in range(L))
    if not rcfg["gate_before_conv"]:
        y = b * y
    if rcfg["output_gate"]:
        y = c * y
    return y @ _f32(lp["out_proj"])


def attention(q, k, v):
    """Causal softmax attention, a query head at a time (head ``h`` reads key
    head ``h // (H / KVH)``). q [B, S, H, hd]; k, v [B, S, KVH, hd] -> [B, S,
    H, hd]."""
    B, S, H, hd = q.shape
    rep = H // k.shape[2]
    i = jnp.arange(S)
    seen = i[:, None] >= i[None, :]

    def head(args):
        qh, n = args                                       # [B, S, hd], head
        kh, vh = k[:, :, n // rep], v[:, :, n // rep]
        scores = jnp.einsum("bqd,bsd->bqs", qh, kh) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqs,bsd->bqd", probs, vh)

    out = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), jnp.arange(H)))
    return jnp.moveaxis(out, 0, 2)


def full_attention(u, lp, positions, rcfg):
    B, S, _ = u.shape
    H, KVH, hd = (rcfg["num_attention_heads"], rcfg["num_key_value_heads"],
                  rcfg["head_dim"])
    eps, theta = rcfg["norm_eps"], rcfg["rope_theta"]
    q = (u @ _f32(lp["q_proj"])).reshape(B, S, H, hd)
    k = (u @ _f32(lp["k_proj"])).reshape(B, S, KVH, hd)
    v = (u @ _f32(lp["v_proj"])).reshape(B, S, KVH, hd)
    if rcfg["qk_norm_before_rope"]:
        q = rope(rms_norm(q, lp["q_layernorm"], eps), positions, theta)
        k = rope(rms_norm(k, lp["k_layernorm"], eps), positions, theta)
    else:
        q = rms_norm(rope(q, positions, theta), lp["q_layernorm"], eps)
        k = rms_norm(rope(k, positions, theta), lp["k_layernorm"], eps)
    return attention(q, k, v).reshape(B, S, H * hd) @ _f32(lp["out_proj"])


def swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ _f32(w1)) * (h @ _f32(w3))) @ _f32(w2)


def routing(h, router, bias, rcfg):
    """h [..., d] -> (weights [..., top_k], experts [..., top_k]): sigmoid
    scores; the bias chooses and does not weigh."""
    scores = jax.nn.sigmoid(h @ _f32(router))
    _, experts = jax.lax.top_k(scores + bias, rcfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if rcfg["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    return weights * rcfg["routed_scaling_factor"], experts


def sparse_mlp(h, lp, rcfg):
    """``sum_e w_e SwiGLU_e(h)``: every expert on every token, with the
    token's weight for it (0 where it did not choose it)."""
    weights, experts = routing(h, lp["router"], lp["expert_bias"], rcfg)

    def one(y, e):
        index, w1, w3, w2 = e
        w = jnp.where(experts == index, weights, 0.0).sum(-1)
        return y + w[..., None] * swiglu(h, w1, w3, w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(lp["w1"].shape[0]), lp["w1"], lp["w3"], lp["w2"]))
    return y


_HEAD_BLOCKS = 16


def head(x, table):
    """x [..., d] @ table [V, d]^T in float32, in blocks of the vocabulary
    where it divides."""
    V, d = table.shape
    if V % _HEAD_BLOCKS:
        return x @ _f32(table).T
    out = jax.lax.map(lambda b: x @ _f32(b).T,
                      table.reshape(_HEAD_BLOCKS, V // _HEAD_BLOCKS, d))
    return jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], V)


def forward(params, tokens, rcfg, last: Optional[int] = None):
    """tokens [B, S] int -> logits [B, S, V], float32 throughout; with
    ``last`` only those of the last ``last`` positions."""
    eps = rcfg["norm_eps"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = _f32(params["embed_tokens"][tokens])
    for kind, lp in zip(rcfg["layer_types"], params["layers"]):
        u = rms_norm(x, lp["operator_norm"], eps)
        x = x + (short_conv(u, lp, rcfg) if kind == "conv"
                 else full_attention(u, lp, positions, rcfg))
        m = rms_norm(x, lp["ffn_norm"], eps)
        x = x + (sparse_mlp(m, lp, rcfg) if "router" in lp
                 else swiglu(m, lp["w1"], lp["w3"], lp["w2"]))
    if last is not None:
        x = x[:, S - last:]
    return head(rms_norm(x, params["embedding_norm"], eps),
                params["embed_tokens"])


def loss(params, tokens, targets, rcfg):
    """Mean next-token cross-entropy; ``targets`` are ``tokens`` shifted by one."""
    logp = jax.nn.log_softmax(forward(params, tokens, rcfg), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# -- 2. from the published keys to the program and to the reference -------------

# what this file's equations and the program (ray_tpu/) do not express of the
# family: refused, so that nothing else runs under the model's name
_ONLY = (("model_type", "lfm2_moe"), ("hidden_act", "silu"),
         ("conv_bias", False), ("use_expert_bias", True),
         ("tie_word_embeddings", True))
_KINDS = {"conv": "conv", "full_attention": "full"}


def layer_types(cfg: dict) -> Tuple[str, ...]:
    """The layers this file runs: the first ``num_hidden_layers`` of the
    published pattern (the file keeps the whole published list)."""
    types = tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])
    if len(types) != cfg["num_hidden_layers"] or set(types) - set(_KINDS):
        raise ValueError(f"{cfg.get('name')}: layer_types must name at least "
                         f"num_hidden_layers layers, each one of {sorted(_KINDS)}")
    return types


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def program_overrides(cfg: dict, max_seq_len: int) -> dict:
    """Published keys -> the program's ``TransformerConfig`` fields."""
    for key, must in _ONLY:
        if cfg[key] != must:
            raise ValueError(f"{cfg.get('name')}: {key} = {cfg[key]!r}; this "
                             f"architecture is written for {must!r} only")
    init = cfg["initializer"]
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                d_ff=cfg["moe_intermediate_size"],
                d_ff_dense=cfg["intermediate_size"],
                first_k_dense=cfg["num_dense_layers"], moe_every=1,
                max_seq_len=max_seq_len, rope_theta=float(cfg["rope_theta"]),
                tie_embeddings=True, remat=True, n_experts=cfg["num_experts"],
                experts_per_token=cfg["num_experts_per_tok"],
                norm_topk_prob=bool(cfg["norm_topk_prob"]),
                router_kind="sigmoid", router_norm_eps=1e-6,
                routed_scaling_factor=float(cfg["routed_scaling_factor"]),
                norm_eps=float(cfg["norm_eps"]),
                layer_kinds=tuple(_KINDS[t] for t in layer_types(cfg)),
                block="rms", rope_kinds=("full",),
                conv_taps=cfg["conv_L_cache"], qk_head_norm=True,
                attn_init_std=float(init["attention"]),
                conv_init_std=float(init["conv"]),
                mlp_init_std=float(init["mlp"]),
                expert_init_std=float(init["experts"]),
                embed_init_std=float(init["embedding"]),
                param_dtype=getattr(jnp, cfg["torch_dtype"]))


def reference_cfg(cfg: dict) -> dict:
    """What the plain reference needs: the published keys, the layers it
    runs, and the recalled parts of the layer as facts it can be asked to
    get wrong (``tests/test_lfm2.py`` does)."""
    out = {k: cfg[k] for k in (
        "num_attention_heads", "num_key_value_heads", "rope_theta", "norm_eps",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
        "conv_L_cache")}
    out.update(layer_types=layer_types(cfg), head_dim=_head_dim(cfg),
               taps_reversed=False, output_gate=True, gate_before_conv=True,
               state_lag=0, qk_norm_before_rope=True)
    return out


def to_reference_params(p: dict, cfg: dict) -> dict:
    """The program's parameter tree under the reference's plain names.
    Reshapes and a transpose of the taps only; called inside a jit so no copy
    of the weights outlives the check."""
    def flat_in(k):   # [d, heads, hd] -> [d, heads*hd]
        return k.reshape(k.shape[0], -1)

    layers = []
    for i, kind in enumerate(layer_types(cfg)):
        lp = p[f"layer_{i}"]
        layer = {"operator_norm": lp["attn_norm"]["scale"],
                 "ffn_norm": lp["mlp_norm"]["scale"]}
        if kind == "conv":
            m = lp["conv"]
            layer.update(in_proj=m["in_proj"]["kernel"],
                         conv=m["conv_kernel"].T,
                         out_proj=m["out_proj"]["kernel"])
        else:
            a = lp["attn"]
            o = a["o_proj"]["kernel"]
            layer.update(q_proj=flat_in(a["q_proj"]["kernel"]),
                         k_proj=flat_in(a["k_proj"]["kernel"]),
                         v_proj=flat_in(a["v_proj"]["kernel"]),
                         q_layernorm=a["q_norm"]["scale"],
                         k_layernorm=a["k_norm"]["scale"],
                         out_proj=o.reshape(-1, o.shape[-1]))
        if "moe" in lp:
            m = lp["moe"]
            layer.update(router=m["router"]["kernel"],
                         expert_bias=m["router_bias"], w1=m["gate_proj"],
                         w3=m["up_proj"], w2=m["down_proj"])
        else:
            layer.update(w1=lp["mlp"]["gate_proj"]["kernel"],
                         w3=lp["mlp"]["up_proj"]["kernel"],
                         w2=lp["mlp"]["down_proj"]["kernel"])
        layers.append(layer)
    return {"embed_tokens": p["embed"],
            "embedding_norm": p["final_norm"]["scale"], "layers": layers}


# -- 3. required operations and stored parameters, from the shapes ---------------


def _mixer_matrices(cfg: dict, kind: str) -> int:
    d = cfg["hidden_size"]
    if kind == "conv":           # in_proj (three outputs) and out_proj
        return 4 * d * d
    hd = _head_dim(cfg)          # q, o; k, v
    return (2 * d * cfg["num_attention_heads"] * hd
            + 2 * d * cfg["num_key_value_heads"] * hd)


def _mixer_vectors(cfg: dict, kind: str) -> int:
    """The taps of a convolution, or the two head norms of an attention."""
    return cfg["conv_L_cache"] * cfg["hidden_size"] if kind == "conv" \
        else 2 * _head_dim(cfg)


def _mlp_params(cfg: dict, i: int, active: bool) -> int:
    """The layer's MLP: what is stored, or what a token multiplies by."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    if i < cfg["num_dense_layers"]:
        return 3 * d * cfg["intermediate_size"]
    experts = cfg["num_experts_per_tok"] if active else cfg["num_experts"]
    return d * cfg["num_experts"] + 3 * d * f * experts


def active_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by (the taps and norms left out)."""
    return sum(_mixer_matrices(cfg, kind) + _mlp_params(cfg, i, True)
               for i, kind in enumerate(layer_types(cfg))) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward; attention layers alone look back."""
    per_key = 4 * cfg["num_attention_heads"] * _head_dim(cfg)
    attn = layer_types(cfg).count("full_attention") * per_key \
        * (seq_len + 1) / 2
    return 3.0 * (2.0 * active_matmul_params(cfg) + attn)


def total_params(cfg: dict) -> int:
    """Every stored parameter: all experts, the router and its bias, the two
    norms a layer, the taps, the q and k head norms, the final norm, the table
    once (tied)."""
    d = cfg["hidden_size"]
    layers = 0
    for i, kind in enumerate(layer_types(cfg)):
        bias = cfg["num_experts"] if i >= cfg["num_dense_layers"] else 0
        layers += _mixer_matrices(cfg, kind) + _mixer_vectors(cfg, kind) \
            + 2 * d + _mlp_params(cfg, i, False) + bias
    return layers + cfg["vocab_size"] * d + d


# -- 4. one call of a kernel: operations and bytes ---------------------------------

# the least of the mix's prompts (benchmarks/traffic/chat-saturated-b128.json):
# the least a slot's pages hold in a decode step. The live positions of a call
# are no fact of a run, so the attention kernel is counted there: its share
# errs low by live / 32 and can never pass 100% (as afmoe and phi4flash count
# theirs); attn.live_tokens_per_step scales it by hand
LEAST_PROMPT = 32
# the prefill bucket whose flash call is counted: the one the mix's median
# prompt takes
FLASH_BUCKET = 512
_KERNELS = ("paged_gqa_decode", "flash_fwd", "moe_gmm_decode",
            "moe_gmm_prefill")


def experts_touched(cfg: dict, rows: int) -> int:
    """Experts that get at least one of ``rows`` tokens when each token's
    experts are uniform over the layer's: E x (1 - (1 - k / E)^rows), to the
    nearest (at 128 rows all but 4e-8 of the 32)."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return round(E * (1.0 - (1.0 - k / E) ** rows))


def kernel_cost(kernel: str, cfg: dict, facts: dict) -> Tuple[float, float]:
    """(operations, bytes) that ONE call of a kernel needs, in the stored type.

    ``paged_gqa_decode`` (an attention layer's live pages, once a decode
    step): every query head's head_dim-wide score and value against each live
    position (2 x (64 + 64) operations a head and position), and as bytes the
    live rows (keys and values of all key heads: 2,048 bytes a position), at
    max_num_seqs slots x the mix's least prompt. Bound by bytes.

    ``flash_fwd`` (the [1, 512] prefill bucket): causal pairs x heads x 2 x
    (score width + value width); q and o at the query heads, k and v at the
    key heads, once each.

    ``moe_gmm_decode``: one of the three products of a decode step's expert
    layer: max_num_seqs x top_k assignments, the experts uniform routing
    touches (32 of 32 at 128 slots), each matrix once, plus the rows in and
    out. Bound by bytes. ``moe_gmm_prefill``: the least any call with a real
    row holds, ONE token: its top_k experts' matrices (as olmoe counts it: the
    tokens of one prompt share experts, so no count from uniform routing is a
    floor). Its share errs low by as much as a call touches more than 4 of the
    32; it still moves with the kernel's speed."""
    if kernel not in _KERNELS:
        raise KeyError(f"lfm2_moe counts no kernel {kernel!r}; known: "
                       f"{sorted(_KERNELS)}")
    itemsize = jnp.dtype(cfg["torch_dtype"]).itemsize
    H, KVH, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  _head_dim(cfg))
    slots = facts.get("max_num_seqs") or cfg["job"]["engine"]["max_num_seqs"]
    if kernel == "paged_gqa_decode":
        rows = slots * LEAST_PROMPT
        return (float(rows * H * 2 * 2 * hd),
                float(rows * 2 * KVH * hd * itemsize))
    if kernel == "flash_fwd":
        S = FLASH_BUCKET
        return (float(H * S * (S + 1) // 2 * 2 * 2 * hd),
                float(2 * S * (H + KVH) * hd * itemsize))
    d, f, k = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts_per_tok"])
    if kernel == "moe_gmm_decode":
        rows, touched = slots, experts_touched(cfg, slots)
    else:
        rows, touched = 1, k
    return (float(2 * rows * k * d * f),
            float((touched * d * f + rows * k * (d + f)) * itemsize))
