"""Moonshot's Kimi Linear decoder as Kimi-Linear-48B-A3B-Instruct configures
it, ONE RANK of a four-way expert-parallel deployment: everything the
benchmark knows about this architecture, in the one module a configuration
file names with ``"adapter": "kimi_linear"``.

Written from the published configuration keys (``model_type: kimi_linear``)
and from the technical report (Kimi Linear, arXiv 2510.26692: Kimi Delta
Attention, section 3; the hybrid of three such layers to one of latent
attention without positions, section 4) and the family's modelling code as
the builder recalls them, there being no network here; what the keys do not
state is listed under the configuration file's ``assumed``.

1. The plain float32 reference (``forward``, ``loss``)::

     x = table[t]
     x = x + Mixer(RMSNorm(x))                input_layernorm
     x = x + FFN(RMSNorm(x))                  post_attention_layernorm
     logits = RMSNorm(x_last) W_head          (untied)

   - ``"kda"`` (layers ``linear_attn_config.kda_layers``, counted from 1): ``q,
     k, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))``, each
     [H, K] a position, the convolution causal, depthwise,
     ``short_conv_kernel_size`` taps, zeros before the first position, no
     bias; ``q = q / |q| * K^-0.5``, ``k = k / |k|`` a head; the log-decay a
     head and KEY LANE ``g_t = -exp(A_log[h]) * softplus((h W_fa) W_fb +
     dt_bias)``; ``beta_t[h] = sigmoid(h W_b)``; the state ``S[h]`` [K, K],
     float32: ``S~ = diag(exp(g_t)) S_{t-1}``, ``S_t = S~ + beta_t k_t (v_t -
     S~^T k_t)^T``, ``o_t = S_t^T q_t``; ``o_t = RMSNorm_K(o_t; w) *
     sigmoid((h W_ga) W_gb)`` a head; ``concat_heads(o_t) W_o``. The state is
     computed by a sequential ``lax.scan``, one position a step: the chunked
     form is a way to evaluate the same recurrence and no term of it.
   - ``"latent"`` (layers ``full_attn_layers``): the DeepSeek family's latent
     attention with ``q_lora_rank`` null and, under ``mla_use_nope``, NO
     rotation: ``q = h W_q`` [H, nope + rope]; ``c | k_r = h W_kva``, ``c =
     RMSNorm(c)``; ``k[h] = (c W_kb)[h, :nope] | k_r`` (the ``qk_rope_head_dim``
     lanes are plain lanes, one key for all heads), ``v[h] = (c W_kb)[h,
     nope:]``; causal softmax of ``q . k * (nope + rope)^-0.5``, a head at a
     time and in blocks of queries; ``W_o``.
   - FFN: the first ``first_k_dense_replace`` layers a SwiGLU of
     ``intermediate_size``; the others ``s = sigmoid(h W_r)`` over ALL the
     router's outputs, the top-k of ``s + e_score_correction_bias`` (one
     group: no group limit), gates = the chosen ``s`` (WITHOUT the bias)
     divided by their sum under ``moe_renormalize``, times
     ``routed_scaling_factor``; expert ``e`` is ``(silu(h Wg_e) * (h Wu_e))
     Wd_e``; the shared expert the same at ``num_shared_experts x
     moe_intermediate_size`` for every token.

   THE SHARE. ``num_experts`` in a configuration file is how many routed
   experts this rank HOLDS; ``expert_parallel`` gives the deployment:
   ``{"routed_experts": 256, "ranks": 4, "rank": r}``. The router has
   ``routed_experts`` outputs and every token its top-k of ALL of them; the
   reference is given the matrices of experts ``r * held .. (r + 1) * held``
   and the vocabulary's slice, and leaves out what an expert held elsewhere
   would add, as the program does: with the four ranks' routed parts summed
   and the shared expert once it is the uncut layer
   (``tests/test_kimi_linear.py`` holds that). Experts one after the other on
   every token (weight 0 where a token did not choose it), the head in blocks
   of the vocabulary: no kernel, cache or batching. Callers wrap it in
   ``jax.default_matmul_precision("highest")``.
2. The way from the published keys to the program and to the reference
   (``program_overrides``, ``reference_cfg``, ``to_reference_params``).
3. Required operations per token and stored parameters.
4. Operations and bytes of one call of each kernel (``kernel_cost``).

Nothing here imports the program under test. ``cfg`` is a configuration
file's dict with the published key names; ``rcfg`` is ``reference_cfg(cfg)``.

Reference parameters are a plain dict: embed_tokens [V, d]; norm [d]; lm_head
[d, V]; layers: list of {input_layernorm, post_attention_layernorm [d]} plus,
kda: {q_proj, k_proj, v_proj [d, H K], q_conv1d, k_conv1d, v_conv1d [taps, H
K], f_a_proj [d, r], f_b_proj [r, H K], dt_bias [H K], A_log [H], b_proj [d,
H], g_a_proj [d, r], g_b_proj [r, H K], o_norm [K], o_proj [H K, d]}; latent:
{q_proj [d, H (nope + rope)], kv_a_proj_with_mqa [d, R + rope],
kv_a_layernorm [R], kv_b_proj [R, H (nope + v)], o_proj [H v, d]}; dense:
{gate_proj [d, F], up_proj, down_proj [F, d]}; sparse: {router [d, routed],
e_score_correction_bias [routed], gate_proj [held, d, f], up_proj, down_proj
[held, f, d], shared_gate_proj [d, fs], shared_up_proj, shared_down_proj}.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# -- 1. the plain reference ------------------------------------------------------


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def swiglu(h, gate, up, down):
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)


def rope(x, positions, theta):
    """x [B, S, heads, hd]: pairs (i, i + hd/2) turned by position x
    theta^(-2i/hd). Only the spoiled reference ``rotate_latent`` calls it."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_conv(x, taps):
    """x [B, S, C], taps [K, C]: depthwise, tap K - 1 the position itself,
    zeros before the first position, no bias."""
    K, S = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(taps[k] * padded[:, k:k + S] for k in range(K))


def kda(h, lp, rcfg):
    """A delta-rule mixer on normalised ``h`` [B, S, d] -> [B, S, d]. What
    ``rcfg["without"]`` names ("beta", "decay", "conv", "out_gate", "k_norm",
    "float32_state": the state rounded to bfloat16 after every position) is
    left out or done wrong: the spoiled references of the tests."""
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    without = rcfg["without"]
    H = rcfg["kda_heads"]
    B, S, _ = h.shape

    def branch(name):
        t = h @ f32(lp[name + "_proj"])
        if "conv" not in without:
            t = causal_conv(t, f32(lp[name + "_conv1d"]))
        return jax.nn.silu(t).reshape(B, S, H, -1)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q, k, v = branch("q"), branch("k"), branch("v")
    K = q.shape[-1]
    q = unit(q) * K ** -0.5
    if "k_norm" not in without:
        k = unit(k)
    step = jax.nn.softplus((h @ f32(lp["f_a_proj"])) @ f32(lp["f_b_proj"])
                           + f32(lp["dt_bias"]))
    g = -jnp.exp(f32(lp["A_log"]))[:, None] * step.reshape(B, S, H, K)
    if "decay" in without:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(h @ f32(lp["b_proj"]))                # [B, S, H]
    if "beta" in without:
        beta = jnp.ones_like(beta)

    def one(s, t):
        q_t, k_t, v_t, g_t, b_t = t        # [B, H, K] x 4, [B, H]
        s = jnp.exp(g_t)[..., None] * s
        d = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, d)
        if "float32_state" in without:   # kept in bfloat16 from step to step
            # (a cast there and back is folded away on the chip: XLA allows
            # itself the excess precision)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    _, o = jax.lax.scan(one, jnp.zeros((B, H, K, K), jnp.float32), tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    o = rms_norm(jnp.moveaxis(o, 0, 1), f32(lp["o_norm"]),
                 rcfg["rms_norm_eps"])                          # [B, S, H, K]
    if "out_gate" not in without:
        gate = (h @ f32(lp["g_a_proj"])) @ f32(lp["g_b_proj"])
        o = o * jax.nn.sigmoid(gate.reshape(o.shape))
    return o.reshape(B, S, -1) @ f32(lp["o_proj"])


_QUERY_BLOCK = 1024


def attention(q, k, v, scale):
    """Causal softmax attention, a head at a time and ``_QUERY_BLOCK`` queries
    at a time where that divides the length (a head's [S, S] scores are 1 GB
    at 16,384 positions). q, k [B, S, H, dk]; v [B, S, H, dv] -> [B, S, H dv]."""
    B, S, H, _ = q.shape
    block = _QUERY_BLOCK if S % _QUERY_BLOCK == 0 else S
    at = jnp.arange(S)

    def head(qkv):
        qh, kh, vh = qkv                                  # [B, S, d]

        def rows(first):
            qb = jax.lax.dynamic_slice_in_dim(qh, first, block, axis=1)
            scores = jnp.einsum("bqd,bsd->bqs", qb, kh) * scale
            seen = (first + jnp.arange(block))[:, None] >= at[None, :]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("bqs,bsd->bqd", probs, vh)

        out = jax.lax.map(rows, jnp.arange(0, S, block))  # [S / block, B, ..]
        return jnp.moveaxis(out, 0, 1).reshape(B, S, -1)

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(out, 0, 2).reshape(B, S, -1)


def latent_attention(h, lp, rcfg):
    """The latent layer's attention on normalised ``h`` [B, S, d], before
    o_proj. Nothing is rotated (``mla_use_nope``) unless the spoiled
    reference ``rotate_latent`` asks for it."""
    B, S, _ = h.shape
    H, R = rcfg["num_attention_heads"], rcfg["kv_lora_rank"]
    nope, rp, dv = (rcfg["qk_nope_head_dim"], rcfg["qk_rope_head_dim"],
                    rcfg["v_head_dim"])
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    q = (h @ f32(lp["q_proj"])).reshape(B, S, H, nope + rp)
    a = h @ f32(lp["kv_a_proj_with_mqa"])
    c = rms_norm(a[..., :R], lp["kv_a_layernorm"], rcfg["rms_norm_eps"])
    k_r, q_r = a[..., None, R:], q[..., nope:]
    if rcfg["rotate_latent"]:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        k_r = rope(k_r, positions, rcfg["rope_theta"])
        q_r = rope(q_r, positions, rcfg["rope_theta"])
    kv = (c @ f32(lp["kv_b_proj"])).reshape(B, S, H, nope + dv)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, q_r.shape)],
                        axis=-1)
    return attention(q, k, kv[..., nope:],
                     rcfg["latent_scale"] or (nope + rp) ** -0.5)


def routing(h, router, bias, rcfg):
    """h [..., d] -> (gates [..., top_k], experts [..., top_k]) over ALL the
    router's outputs: sigmoid scores; the bias chooses and does not weigh
    (the spoiled reference ``bias_in_gates`` lets it weigh)."""
    scores = jax.nn.sigmoid(h @ router.astype(jnp.float32))
    _, experts = jax.lax.top_k(scores + bias, rcfg["num_experts_per_token"])
    gates = jnp.take_along_axis(
        scores + bias if rcfg["bias_in_gates"] else scores, experts, axis=-1)
    if rcfg["moe_renormalize"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return gates * rcfg["routed_scaling_factor"], experts


def routed_experts(h, lp, rcfg):
    """The part of ``sum_e g_e SwiGLU_e(h)`` that the experts held here give:
    expert ``j`` of the matrices is expert ``first_expert + j`` of the
    router's. Every held expert is computed on every token, with the token's
    gate for it (0 where it did not choose it)."""
    gates, experts = routing(h, lp["router"], lp["e_score_correction_bias"],
                             rcfg)

    def one(y, e):
        index, gate, up, down = e
        g = jnp.where(experts == index, gates, 0.0).sum(-1)
        return y + g[..., None] * swiglu(h, gate, up, down), None

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
    ``last`` only those of the last ``last`` positions (every position is
    still computed through every layer)."""
    eps = rcfg["rms_norm_eps"]
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for kind, lp in zip(rcfg["layer_types"], params["layers"]):
        h = rms_norm(x, lp["input_layernorm"], eps)
        if kind == "kda":
            x = x + kda(h, lp, rcfg)
        else:
            x = x + latent_attention(h, lp, rcfg) \
                @ lp["o_proj"].astype(jnp.float32)
        h = rms_norm(x, lp["post_attention_layernorm"], eps)
        if "router" in lp:      # a sparse layer
            x = x + routed_experts(h, lp, rcfg) + swiglu(
                h, lp["shared_gate_proj"], lp["shared_up_proj"],
                lp["shared_down_proj"])
        else:
            x = x + swiglu(h, lp["gate_proj"], lp["up_proj"], lp["down_proj"])
    if last is not None:
        x = x[:, x.shape[1] - last:]
    return head(rms_norm(x, params["norm"], eps), params["lm_head"])


def loss(params, tokens, targets, rcfg):
    """Mean next-token cross-entropy; ``targets`` are ``tokens`` shifted by one."""
    logp = jax.nn.log_softmax(forward(params, tokens, rcfg), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# -- 2. from the published keys to the program and to the reference -------------

# what the program (ray_tpu/) cannot express of this family: refused, so that
# nothing else runs under the model's name
_ONLY = (("model_type", "kimi_linear"), ("hidden_act", "silu"),
         ("q_lora_rank", None), ("rope_scaling", None),
         ("mla_use_nope", True), ("num_expert_group", 1), ("topk_group", 1),
         ("moe_router_activation_func", "sigmoid"),
         ("num_nextn_predict_layers", 0), ("tie_word_embeddings", False))


def layer_types(cfg: dict) -> Tuple[str, ...]:
    """The layers this file runs, "kda" or "latent" each: the first
    ``num_hidden_layers`` of the published lists, which count from 1 (the
    file keeps both lists whole)."""
    lin = cfg["linear_attn_config"]
    kinds = {**{i: "kda" for i in lin["kda_layers"]},
             **{i: "latent" for i in lin["full_attn_layers"]}}
    if set(lin["kda_layers"]) & set(lin["full_attn_layers"]) or any(
            i not in kinds for i in range(1, cfg["num_hidden_layers"] + 1)):
        raise ValueError(f"{cfg.get('name')}: kda_layers and full_attn_layers "
                         f"must name each of layers 1..num_hidden_layers once")
    return tuple(kinds[i] for i in range(1, cfg["num_hidden_layers"] + 1))


def share(cfg: dict) -> Tuple[int, int, int]:
    """(routed experts of the deployment, the first held here, how many)."""
    ep = cfg["expert_parallel"]
    held = cfg["num_experts"]
    if ep["routed_experts"] != ep["ranks"] * held or not 0 <= ep["rank"] < ep["ranks"]:
        raise ValueError(f"{cfg.get('name')}: expert_parallel {ep} does not "
                         f"share {ep['routed_experts']} experts into ranks of "
                         f"{held}")
    return ep["routed_experts"], ep["rank"] * held, held


def kda_sizes(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    return {"heads": lin["num_heads"], "head": lin["head_dim"],
            "wide": lin["num_heads"] * lin["head_dim"],
            "conv": lin["short_conv_kernel_size"],
            # the two low-rank gates' inner width: a head's (assumed)
            "rank": lin["head_dim"]}


def program_overrides(cfg: dict, max_seq_len: int) -> dict:
    """Published keys -> the program's ``TransformerConfig`` fields."""
    name = cfg.get("name")
    for key, must in _ONLY:
        if cfg[key] != must:
            raise ValueError(f"{name}: {key} = {cfg[key]!r}; the program "
                             f"expresses only {must!r}")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError(f"{name}: num_key_value_heads: latent attention has "
                         "one latent for all heads, and as many keys as heads")
    routed, first, held = share(cfg)
    s, init = kda_sizes(cfg), cfg["initializer"]
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                d_ff=cfg["moe_intermediate_size"],
                d_ff_dense=cfg["intermediate_size"],
                first_k_dense=cfg["first_k_dense_replace"],
                moe_every=cfg["moe_layer_freq"], max_seq_len=max_seq_len,
                rope_theta=float(cfg["rope_theta"]), tie_embeddings=False,
                remat=True, n_experts=routed, experts_held=(first, held),
                experts_per_token=cfg["num_experts_per_token"],
                n_shared_experts=cfg["num_shared_experts"],
                norm_topk_prob=bool(cfg["moe_renormalize"]),
                router_kind="sigmoid",
                routed_scaling_factor=float(cfg["routed_scaling_factor"]),
                norm_eps=float(cfg["rms_norm_eps"]),
                kv_latent_rank=cfg["kv_lora_rank"],
                qk_nope_head_dim=cfg["qk_nope_head_dim"],
                qk_rope_head_dim=cfg["qk_rope_head_dim"],
                v_head_dim=cfg["v_head_dim"],
                layer_kinds=layer_types(cfg), block="rms", rope_kinds=(),
                kda_heads=s["heads"], kda_head_dim=s["head"],
                kda_conv=s["conv"], kda_gate_rank=s["rank"],
                attn_init_std=float(init["attention"]),
                kda_init_std=float(init["kda"]),
                mlp_init_std=float(init["mlp"]),
                expert_init_std=float(init["experts"]),
                embed_init_std=float(init["embedding"]),
                param_dtype=getattr(jnp, cfg["torch_dtype"]))


def reference_cfg(cfg: dict) -> dict:
    """What the plain reference needs: the published keys, the layers it
    runs, where this rank's experts begin, and what the tests' spoiled
    references get wrong: ``without`` (parts of the delta-rule mixer),
    ``rotate_latent``, ``latent_scale`` (0: the published ``(nope +
    rope)^-0.5``), ``bias_in_gates``."""
    out = {k: cfg[k] for k in (
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rope_theta", "rms_norm_eps",
        "num_experts_per_token", "moe_renormalize", "routed_scaling_factor")}
    out.update(layer_types=layer_types(cfg), first_expert=share(cfg)[1],
               kda_heads=kda_sizes(cfg)["heads"], without=(),
               rotate_latent=False, latent_scale=0.0, bias_in_gates=False)
    return out


def to_reference_params(p: dict, cfg: dict) -> dict:
    """The program's parameter tree under the reference's plain names.
    Reshapes and slices only (heads folded into one axis, the fused ``q | k |
    v`` apart); called inside a jit so no copy of the weights outlives the
    check."""
    def flat_in(k):   # [d, heads, hd] -> [d, heads*hd]
        return k.reshape(k.shape[0], -1)

    layers = []
    for i, kind in enumerate(layer_types(cfg)):
        lp = p[f"layer_{i}"]
        layer = {"input_layernorm": lp["attn_norm"]["scale"],
                 "post_attention_layernorm": lp["mlp_norm"]["scale"]}
        if kind == "kda":
            m = lp["kda"]
            for name, proj, taps in zip(
                    "qkv", jnp.split(m["qkv_proj"]["kernel"], 3, axis=-1),
                    jnp.split(m["conv_kernel"], 3, axis=-1)):
                layer[name + "_proj"], layer[name + "_conv1d"] = proj, taps
            layer.update({n + "_proj": m[n]["kernel"]
                          for n in ("f_a", "f_b", "g_a", "g_b")})
            layer.update({"dt_bias": m["dt_bias"], "A_log": m["A_log"],
                          "b_proj": m["b_proj"]["kernel"],
                          "o_norm": m["o_norm"]["scale"],
                          "o_proj": m["o_proj"]["kernel"]})
        else:
            a = lp["attn"]
            o = a["o_proj"]["kernel"]
            layer.update({
                "q_proj": flat_in(a["q_proj"]["kernel"]),
                "kv_a_proj_with_mqa": a["kv_a_proj"]["kernel"],
                "kv_a_layernorm": a["kv_a_norm"]["scale"],
                "kv_b_proj": flat_in(a["kv_b_proj"]["kernel"]),
                "o_proj": o.reshape(-1, o.shape[-1])})
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


# -- 3. required operations and stored parameters, from the shapes ---------------


def _sparse(cfg: dict, i: int) -> bool:
    return (i >= cfg["first_k_dense_replace"]
            and i % cfg["moe_layer_freq"] == 0)


def _mixer_params(cfg: dict, kind: str, matrices_only: bool) -> int:
    d = cfg["hidden_size"]
    if kind == "latent":
        H, R = cfg["num_attention_heads"], cfg["kv_lora_rank"]
        nope, rp, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
        mats = d * H * (nope + rp) + d * (R + rp) + R * H * (nope + dv) \
            + H * dv * d
        return mats if matrices_only else mats + R
    s = kda_sizes(cfg)
    mats = 4 * d * s["wide"] + 2 * (d * s["rank"] + s["rank"] * s["wide"]) \
        + d * s["heads"]
    # the three convolutions' taps, dt_bias, A_log, the output norm
    return mats if matrices_only else mats + 3 * s["wide"] * s["conv"] \
        + s["wide"] + s["heads"] + s["head"]


def _mlp_params(cfg: dict, i: int, active: bool) -> int:
    """Layer ``i``'s MLP matrices: stored HERE, or those a token multiplies
    by (its top-k routed experts wherever they are held)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    if not _sparse(cfg, i):
        return 3 * d * cfg["intermediate_size"]
    experts = cfg["num_experts_per_token"] if active else cfg["num_experts"]
    return d * share(cfg)[0] + 3 * d * f * (experts + cfg["num_shared_experts"])


def active_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by in the whole deployment's layers."""
    return sum(_mixer_params(cfg, t, True) + _mlp_params(cfg, i, True)
               for i, t in enumerate(layer_types(cfg))) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward; a latent layer's query counts the keys it sees,
    a delta-rule layer seven operations a state element."""
    s = kda_sizes(cfg)
    per_key = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    mix = sum(per_key * (seq_len + 1) / 2 if t == "latent"
              else 7 * s["wide"] * s["head"] for t in layer_types(cfg))
    return 3.0 * (2.0 * active_matmul_params(cfg) + mix)


def total_params(cfg: dict) -> int:
    """Every parameter stored on this rank: the held experts, the whole
    router and its selection bias, the shared expert, two norms a layer, the
    final norm, the vocabulary's slice of table and head."""
    d = cfg["hidden_size"]
    layers = 0
    for i, t in enumerate(layer_types(cfg)):
        bias = share(cfg)[0] if _sparse(cfg, i) else 0
        layers += _mixer_params(cfg, t, False) + _mlp_params(cfg, i, False) \
            + bias + 2 * d
    return layers + 2 * cfg["vocab_size"] * d + d


# -- 4. one call of a kernel: operations and bytes ---------------------------------

# the mix's least prompt, which is also the least prefill bucket it reaches:
# the least a slot's pages hold in a decode step, and the one row kda_scan and
# the grouped matmul of a prefill call are counted at (a longer one takes
# longer and is counted the same: the shares err low by bucket / 512)
LEAST_PROMPT = 512
# the prefill bucket the flash kernel is counted at: the mix's median prompt
FLASH_BUCKET = 4096
_KERNELS = ("kda_scan", "kda_step", "mla_decode", "flash_fwd",
            "moe_gmm_decode", "moe_gmm_prefill")


def experts_touched(cfg: dict, rows: int) -> int:
    """HELD experts that get at least one of ``rows`` tokens when each
    token's experts are uniform over all the routed ones: held x (1 - (1 -
    k / routed)^rows), rounded down."""
    routed, _, held = share(cfg)
    k = cfg["num_experts_per_token"]
    return int(held * (1.0 - (1.0 - k / routed) ** rows))


def kernel_cost(kernel: str, cfg: dict, facts: dict) -> Tuple[float, float]:
    """(operations, bytes) that ONE call of a kernel needs, whatever
    implements it, in the stored type.

    ``kda_step`` (one delta-rule layer's decode step for every slot): seven
    operations a state element (the decay, the multiply-add of ``S^T k``,
    the outer product's multiply-add, the multiply-add into ``o``) and as
    bytes the float32 state of max_num_seqs slots READ AND WRITTEN (2 x 2.1
    MB a slot) plus a slot's operands (q, k, v in the stored type, g and
    beta float32, o out in the stored type). Bound by bytes: 270.5 MB, 0.33
    ms at 64 slots.

    ``kda_scan`` (one delta-rule layer's recurrence over the LEAST bucket the
    mix reaches, one row of 512 positions): the same seven operations a
    state element and position (the recurrence's own count; the chunked
    form's triangular solve and products are its way, not its need), and as
    bytes q, k, v in, g and beta in float32, o out in the stored type and the
    final state out. Bound by bytes on paper (27.4 MB, 33 us, against 1.9
    GFLOP, 10 us): whatever the chunked form takes beyond that shows as a
    share under 100%.

    ``mla_decode`` (a latent layer of a decode step): all heads against each
    live row once, scores over kv_lora_rank + qk_rope_head_dim and values
    over kv_lora_rank, and as bytes the live rows at their unpadded width,
    at max_num_seqs slots x the mix's least prompt: bound by bytes, errs low
    by live / 512 (``mla.live_tokens_per_step`` scales it by hand).

    ``flash_fwd`` (a latent layer of the [1, 4096] prefill bucket): causal
    pairs x heads x 2 x (score width + value width); q, k, v, o once each.
    Whatever bucket a traced call ran at is counted as this one.

    ``moe_gmm_decode``: one of the three products of a decode step's expert
    layer on THIS rank: of max_num_seqs x top_k assignments the share that
    uniform routing gives the held experts (held / routed) as rows in and
    out, and as matrices the held experts that a QUARTER of the slots' rows
    touch (25 of 64 where all 64 rows, routed apart, would touch 55): greedy
    decoding of seeded weights sends slots to the same tokens and their rows
    to the same experts (28-48 touched a layer and step, by the table's
    deviation: my chip runs, PR 49), and a share counted at 55 read 149%
    where 28 were streamed. Counted so it errs low by touched / 25 and passes
    100% only under 23 touched; ``moe.experts_touched`` scales it by hand.
    ``moe_gmm_prefill``: the least a call holds, the mix's least prompt of
    real rows routed apart (63 of 64 touched); a longer prompt multiplies
    more and streams no more, so the share errs low."""
    if kernel not in _KERNELS:
        raise KeyError(f"kimi_linear counts no kernel {kernel!r}; known: "
                       f"{sorted(_KERNELS)}")
    itemsize = jnp.dtype(cfg["torch_dtype"]).itemsize
    slots = facts.get("max_num_seqs") or cfg["job"]["engine"]["max_num_seqs"]
    s = kda_sizes(cfg)
    state = s["wide"] * s["head"]
    # a position's operands: q, k, v in and o out stored, g and beta float32
    operands = 4 * s["wide"] * itemsize + 4 * (s["wide"] + s["heads"])
    if kernel == "kda_step":
        return float(7 * slots * state), float(slots * (8 * state + operands))
    if kernel == "kda_scan":
        return (float(7 * LEAST_PROMPT * state),
                float(LEAST_PROMPT * operands + 4 * state))
    H = cfg["num_attention_heads"]
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
               cfg["num_experts_per_token"])
    routed, _, held = share(cfg)
    tokens = slots if kernel == "moe_gmm_decode" else LEAST_PROMPT
    apart = slots // 4 if kernel == "moe_gmm_decode" else LEAST_PROMPT
    rows = tokens * k * held / routed
    return (float(2 * rows * d * f),
            float((experts_touched(cfg, apart) * d * f + rows * (d + f))
                  * itemsize))
