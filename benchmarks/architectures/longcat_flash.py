"""Meituan's LongCat-Flash decoder as LongCat-Flash-Omni configures its
language model, ONE RANK of a 32-way expert-parallel deployment: everything
the benchmark knows about this architecture, in the one module a
configuration file names with ``"adapter": "longcat_flash"``.

Written from the published configuration keys (the catalog's row; ``model_type:
longcat_flash`` in the family's code) and from the technical report
(LongCat-Flash, arXiv 2509.01322: zero-computation experts, section 2.1; the
shortcut-connected MoE, section 2.2; the scale-corrected latent attention,
section 2.3) and the family's modelling code as the builder recalls them,
there being no network here; what the keys do not state is listed under the
configuration file's ``assumed``. The audio and vision encoders and the codec
decoder of the Omni model are no part of this.

1. The plain float32 reference (``forward``, ``loss``). ``num_layers``
   published layers, each TWO sublayers j = 0, 1 with their own attention,
   dense MLP and norms, and ONE expert branch::

     x = table[t]
     h = x + MLA_0(RMSNorm(x; in_0))
     u = RMSNorm(h; post_0);  m = MoE(u)        the shortcut branch
     h = h + MLP_0(u)
     h = h + MLA_1(RMSNorm(h; in_1))
     x = h + MLP_1(RMSNorm(h; post_1)) + m
     logits = RMSNorm(x_last) W_head            (untied)

   - ``MLA_j``: ``cq = RMSNorm(h W_qa)``, ``q = (cq W_qb) * s_q`` as [H, nope
     | rope], ``s_q = sqrt(hidden / q_lora_rank)``; ``c | k_r = h W_kva``;
     ``c = RMSNorm(c) * s_kv``, ``s_kv = sqrt(hidden / kv_lora_rank)`` (the
     scaled latent feeds BOTH halves of ``W_kb``; ``k_r`` is not scaled); the
     rope lanes of q and the one ``k_r`` rotated, pairs (i, i + rope / 2);
     ``k[h] = (c W_kb)[h, :nope] | k_r``, ``v[h] = (c W_kb)[h, nope:]``; causal
     softmax of ``q . k * (nope + rope)^-0.5``, a head at a time and in blocks
     of queries; ``W_o``. ``MLP_j``: SwiGLU at ``ffn_hidden_size``.
   - ``MoE``: ``p = softmax(u W_r)`` over ALL the router's outputs,
     ``n_routed_experts`` of the deployment + ``zero_expert_num``; the
     ``moe_topk`` of ``p + e_score_correction_bias`` (chooses, does not
     weigh); gates = the chosen ``p`` times ``routed_scaling_factor``, NOT
     renormalised; output ``e`` under the routed count is ``(silu(u Wg_e) *
     (u Wu_e)) Wd_e`` at ``expert_ffn_hidden_size``, an output past it is ``u``
     itself (``zero_expert_type: identity``).

   THE SHARE. ``n_routed_experts`` in a configuration file is how many routed
   experts this rank HOLDS; ``expert_parallel`` gives the deployment:
   ``{"routed_experts": 512, "zero_experts": 256, "ranks": 32, "rank": r}``.
   The router has ``routed_experts + zero_experts`` outputs and every token
   its top-k of ALL of them; the reference is given the matrices of experts
   ``r * held .. (r + 1) * held`` and the vocabulary's slice, and leaves out
   what an expert held elsewhere would add, as the program does. The zero
   experts cost nothing and every rank computes them for its own tokens:
   with the 32 ranks' routed parts summed and the zero-expert part ONCE it
   is the uncut layer (``tests/test_longcat.py`` holds that). Experts one
   after the other on every token (weight 0 where a token did not choose
   it), the head in blocks of the vocabulary: no kernel, cache or batching.
   Callers wrap it in ``jax.default_matmul_precision("highest")``.
2. The way from the published keys to the program and to the reference
   (``program_overrides``, ``reference_cfg``, ``to_reference_params``).
3. Required operations per token and stored parameters.
4. Operations and bytes of one call of each kernel (``kernel_cost``).

Nothing here imports the program under test. ``cfg`` is a configuration
file's dict with the published key names; ``rcfg`` is ``reference_cfg(cfg)``.

Reference parameters are a plain dict: embed_tokens [V, d]; norm [d]; lm_head
[d, V]; layers: list of {router [d, routed + zero], e_score_correction_bias
[routed + zero], gate_proj [held, d, f], up_proj, down_proj [held, f, d],
sub: two of {input_layernorm, post_attention_layernorm [d], q_a_proj [d, rq],
q_a_layernorm [rq], q_b_proj [rq, H (nope + rope)], kv_a_proj_with_mqa [d, R +
rope], kv_a_layernorm [R], kv_b_proj [R, H (nope + v)], o_proj [H v, d],
mlp_gate_proj [d, F], mlp_up_proj, mlp_down_proj [F, d]}}.
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
    theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


_QUERY_BLOCK = 1024


def attention(q, k, v, scale):
    """Causal softmax attention, a head at a time and ``_QUERY_BLOCK`` queries
    at a time where that divides the length (a head's [S, S] scores are 0.3 GB
    at 8,704 positions). q, k [B, S, H, dk]; v [B, S, H, dv] -> [B, S, H dv]."""
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


def latent_attention(h, sp, rcfg):
    """One sublayer's latent attention on normalised ``h`` [B, S, d], through
    o_proj. What ``rcfg["without"]`` names is left out or done wrong, the
    spoiled references of the tests: "s_q", "s_kv", "q_a_norm", "q_lora" (the
    queries a plain product of the two matrices: full rank, no norm, no
    scale), "s_kv_on_keys" (the scale on the key half alone),
    "latent_scale" (``nope^-0.5`` for ``(nope + rope)^-0.5``)."""
    B, S, _ = h.shape
    without = rcfg["without"]
    H, R = rcfg["num_attention_heads"], rcfg["kv_lora_rank"]
    nope, rp, dv = (rcfg["qk_nope_head_dim"], rcfg["qk_rope_head_dim"],
                    rcfg["v_head_dim"])
    d = h.shape[-1]
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    s_q = 1.0 if {"s_q", "q_lora"} & set(without) or not rcfg[
        "mla_scale_q_lora"] else (d / rcfg["q_lora_rank"]) ** 0.5
    s_kv = 1.0 if "s_kv" in without or not rcfg["mla_scale_kv_lora"] \
        else (d / R) ** 0.5
    cq = h @ f32(sp["q_a_proj"])
    if not {"q_a_norm", "q_lora"} & set(without):
        cq = rms_norm(cq, sp["q_a_layernorm"], rcfg["rms_norm_eps"])
    q = ((cq @ f32(sp["q_b_proj"])) * s_q).reshape(B, S, H, nope + rp)
    a = h @ f32(sp["kv_a_proj_with_mqa"])
    c = rms_norm(a[..., :R], sp["kv_a_layernorm"], rcfg["rms_norm_eps"])
    kv = ((c * s_kv) @ f32(sp["kv_b_proj"])).reshape(B, S, H, nope + dv)
    values = kv[..., nope:]
    if "s_kv_on_keys" in without:
        values = values / s_kv
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    k_r = rope(a[..., None, R:], positions, rcfg["rope_theta"])
    q_r = rope(q[..., nope:], positions, rcfg["rope_theta"])
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, q_r.shape)],
                        axis=-1)
    scale = (nope if "latent_scale" in without else nope + rp) ** -0.5
    return attention(q, k, values, scale) @ f32(sp["o_proj"])


def routing(u, router, bias, rcfg):
    """u [..., d] -> (gates [..., top_k], outputs [..., top_k]) over ALL the
    router's outputs, experts and zero experts in one softmax and one top-k;
    the bias chooses and does not weigh. Spoiled: "bias_in_gates" lets it
    weigh, "gate_renorm" divides the chosen gates by their sum,
    "routed_scale" leaves ``routed_scaling_factor`` out, "zero_renorm" takes
    the softmax over the routed outputs alone (the zero experts renormalised
    away: they can no longer be chosen)."""
    without = rcfg["without"]
    logits = u @ router.astype(jnp.float32)
    if "zero_renorm" in without:
        logits = jnp.where(jnp.arange(logits.shape[-1]) < rcfg["routed"],
                           logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    _, outputs = jax.lax.top_k(p + bias, rcfg["moe_topk"])
    gates = jnp.take_along_axis(
        p + bias if "bias_in_gates" in without else p, outputs, axis=-1)
    if "gate_renorm" in without:
        gates = gates / gates.sum(-1, keepdims=True)
    if "routed_scale" not in without:
        gates = gates * rcfg["routed_scaling_factor"]
    return gates, outputs


def expert_branch(u, lp, rcfg, routed_part=True, zero_part=True):
    """The part of ``sum_k gate_k E_k(u)`` that is computed HERE: the experts
    held here (expert ``j`` of the matrices is expert ``first_expert + j`` of
    the router's; every held expert on every token, with the token's gate for
    it, 0 where it did not choose it) and the zero experts (``gate * u``).
    ``routed_part`` / ``zero_part`` leave one of the two out (the share
    test). Spoiled: "zero_experts" drops their gates."""
    gates, outputs = routing(u, lp["router"], lp["e_score_correction_bias"],
                             rcfg)
    y = jnp.zeros_like(u)
    if routed_part:
        def one(y, e):
            index, gate, up, down = e
            g = jnp.where(outputs == index, gates, 0.0).sum(-1)
            return y + g[..., None] * swiglu(u, gate, up, down), None

        held = lp["gate_proj"].shape[0]
        y, _ = jax.lax.scan(one, y, (
            rcfg["first_expert"] + jnp.arange(held), lp["gate_proj"],
            lp["up_proj"], lp["down_proj"]))
    if zero_part and "zero_experts" not in rcfg["without"]:
        g = jnp.where(outputs >= rcfg["routed"], gates, 0.0).sum(-1)
        y = y + g[..., None] * u
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


def double_layer(x, lp, rcfg):
    """One published layer on the stream x [B, S, d]. Spoiled:
    "branch_after_first" adds the branch where the FIRST sublayer ends,
    "branch_from_second" computes it from the second sublayer's normed MLP
    input, "second_attention" runs the first sublayer's attention weights
    in both sublayers."""
    eps, without = rcfg["rms_norm_eps"], rcfg["without"]
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    s0, s1 = lp["sub"]

    def mlp(u, sp):
        return swiglu(u, sp["mlp_gate_proj"], sp["mlp_up_proj"],
                      sp["mlp_down_proj"])

    h = x + latent_attention(rms_norm(x, f32(s0["input_layernorm"]), eps),
                             s0, rcfg)
    u = rms_norm(h, f32(s0["post_attention_layernorm"]), eps)
    m = expert_branch(u, lp, rcfg)
    h = h + mlp(u, s0)
    if "branch_after_first" in without:
        h, m = h + m, 0.0
    attn = s0 if "second_attention" in without else s1
    h = h + latent_attention(rms_norm(h, f32(s1["input_layernorm"]), eps),
                             attn, rcfg)
    u = rms_norm(h, f32(s1["post_attention_layernorm"]), eps)
    if "branch_from_second" in without:
        m = expert_branch(u, lp, rcfg)
    return h + mlp(u, s1) + m


def forward(params, tokens, rcfg, last: Optional[int] = None):
    """tokens [B, S] int -> logits [B, S, V], float32 throughout; with
    ``last`` only those of the last ``last`` positions (every position is
    still computed through every layer)."""
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for lp in params["layers"]:
        x = double_layer(x, lp, rcfg)
    if last is not None:
        x = x[:, x.shape[1] - last:]
    return head(rms_norm(x, params["norm"], rcfg["rms_norm_eps"]),
                params["lm_head"])


def loss(params, tokens, targets, rcfg):
    """Mean next-token cross-entropy; ``targets`` are ``tokens`` shifted by one."""
    logp = jax.nn.log_softmax(forward(params, tokens, rcfg), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# -- 2. from the published keys to the program and to the reference -------------

# what the program (ray_tpu/) cannot express of this family: refused, so that
# nothing else runs under the model's name
_ONLY = (("attention_method", "MLA"), ("zero_expert_type", "identity"),
         ("attention_bias", False), ("rope_scaling", None),
         ("n_shared_experts", 0), ("num_nextn_predict_layers", 0),
         ("tie_word_embeddings", False), ("norm_topk_prob", False))


def share(cfg: dict) -> Tuple[int, int, int, int]:
    """(routed experts of the deployment, zero experts, the first routed one
    held here, how many are)."""
    ep = cfg["expert_parallel"]
    held = cfg["n_routed_experts"]
    if (ep["routed_experts"] != ep["ranks"] * held
            or not 0 <= ep["rank"] < ep["ranks"]
            or ep["zero_experts"] != cfg["zero_expert_num"]):
        raise ValueError(f"{cfg.get('name')}: expert_parallel {ep} does not "
                         f"share {ep['routed_experts']} experts into ranks of "
                         f"{held} beside {cfg['zero_expert_num']} zero experts")
    return (ep["routed_experts"], ep["zero_experts"], ep["rank"] * held, held)


def program_overrides(cfg: dict, max_seq_len: int) -> dict:
    """Published keys -> the program's ``TransformerConfig`` fields. A key
    the catalog's row does not have (``rope_scaling``, a shared expert,
    ``norm_topk_prob``...) counts as its ``_ONLY`` value when absent."""
    name = cfg.get("name")
    for key, must in _ONLY:
        if cfg.get(key, must) != must:
            raise ValueError(f"{name}: {key} = {cfg[key]!r}; the program "
                             f"expresses only {must!r}")
    if cfg["mla_scale_q_lora"] != cfg["mla_scale_kv_lora"]:
        raise ValueError(f"{name}: mla_scale_q_lora and mla_scale_kv_lora "
                         "differ; the program scales both latents or neither")
    if not cfg["q_lora_rank"]:
        raise ValueError(f"{name}: q_lora_rank: this family's queries are "
                         "low-rank")
    routed, zero, first, held = share(cfg)
    init = cfg["initializer"]
    sublayers = 2 * cfg["num_layers"]
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=sublayers, n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_attention_heads"],
                d_ff=cfg["expert_ffn_hidden_size"],
                d_ff_dense=cfg["ffn_hidden_size"], max_seq_len=max_seq_len,
                rope_theta=float(cfg["rope_theta"]), tie_embeddings=False,
                remat=True, n_experts=routed, experts_held=(first, held),
                zero_experts=zero, experts_per_token=cfg["moe_topk"],
                norm_topk_prob=False, router_kind="softmax", router_bias=True,
                routed_scaling_factor=float(cfg["routed_scaling_factor"]),
                norm_eps=float(cfg["rms_norm_eps"]),
                kv_latent_rank=cfg["kv_lora_rank"],
                q_latent_rank=cfg["q_lora_rank"],
                latent_lora_scale=bool(cfg["mla_scale_kv_lora"]),
                qk_nope_head_dim=cfg["qk_nope_head_dim"],
                qk_rope_head_dim=cfg["qk_rope_head_dim"],
                v_head_dim=cfg["v_head_dim"], shortcut_moe=True,
                layer_kinds=("latent",) * sublayers, block="rms",
                rope_kinds=("latent",),
                attn_init_std=float(init["attention"]),
                mlp_init_std=float(init["mlp"]),
                expert_init_std=float(init["experts"]),
                embed_init_std=float(init["embedding"]),
                router_init_std=float(init["router"]),
                router_bias_init_std=float(init["router_bias"]),
                param_dtype=getattr(jnp, cfg["torch_dtype"]))


def reference_cfg(cfg: dict) -> dict:
    """What the plain reference needs: the published keys, the router's
    routed width, where this rank's experts begin, and ``without``: what the
    tests' spoiled references leave out or get wrong (``latent_attention``,
    ``routing``, ``expert_branch``, ``double_layer`` say which)."""
    out = {k: cfg[k] for k in (
        "num_attention_heads", "kv_lora_rank", "q_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
        "rms_norm_eps", "moe_topk", "routed_scaling_factor",
        "mla_scale_q_lora", "mla_scale_kv_lora")}
    routed, _, first, _ = share(cfg)
    out.update(routed=routed, first_expert=first, without=())
    return out


def to_reference_params(p: dict, cfg: dict) -> dict:
    """The program's parameter tree under the reference's plain names: a
    published layer is the program's sublayers ``2 i`` and ``2 i + 1``, the
    expert branch the even one's. Reshapes only (heads folded into one
    axis); called inside a jit so no copy of the weights outlives the check."""
    def flat_in(k):   # [d, heads, hd] -> [d, heads*hd]
        return k.reshape(k.shape[0], -1)

    def sub(lp):
        a, o = lp["attn"], lp["attn"]["o_proj"]["kernel"]
        out = {"input_layernorm": lp["attn_norm"]["scale"],
               "post_attention_layernorm": lp["mlp_norm"]["scale"],
               "q_a_proj": a["q_a_proj"]["kernel"],
               "q_a_layernorm": a["q_a_norm"]["scale"],
               "q_b_proj": flat_in(a["q_b_proj"]["kernel"]),
               "kv_a_proj_with_mqa": a["kv_a_proj"]["kernel"],
               "kv_a_layernorm": a["kv_a_norm"]["scale"],
               "kv_b_proj": flat_in(a["kv_b_proj"]["kernel"]),
               "o_proj": o.reshape(-1, o.shape[-1])}
        out.update({"mlp_" + n: lp["mlp"][n]["kernel"]
                    for n in ("gate_proj", "up_proj", "down_proj")})
        return out

    layers = []
    for i in range(cfg["num_layers"]):
        even, odd = p[f"layer_{2 * i}"], p[f"layer_{2 * i + 1}"]
        m = even["moe"]
        layers.append({
            "sub": [sub(even), sub(odd)], "router": m["router"]["kernel"],
            "e_score_correction_bias": m["router_bias"],
            "gate_proj": m["gate_proj"], "up_proj": m["up_proj"],
            "down_proj": m["down_proj"]})
    return {"embed_tokens": p["embed"], "norm": p["final_norm"]["scale"],
            "lm_head": p["lm_head"], "layers": layers}


# -- 3. required operations and stored parameters, from the shapes ---------------


def _attention_params(cfg: dict, matrices_only: bool) -> int:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    R, rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    nope, rp, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    mats = d * rq + rq * H * (nope + rp) + d * (R + rp) \
        + R * H * (nope + dv) + H * dv * d
    return mats if matrices_only else mats + rq + R


def _layer_params(cfg: dict, active: bool) -> int:
    """A published layer's matrices: stored HERE, or those a token
    multiplies by (its real experts wherever they are held: of ``moe_topk``
    choices the share that uniform choosing gives the routed outputs)."""
    d, f = cfg["hidden_size"], cfg["expert_ffn_hidden_size"]
    routed, zero, _, held = share(cfg)
    experts = cfg["moe_topk"] * routed / (routed + zero) if active else held
    return int(2 * _attention_params(cfg, True)
               + 2 * 3 * d * cfg["ffn_hidden_size"] + d * (routed + zero)
               + 3 * d * f * experts)


def active_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by in the whole deployment's layers."""
    return cfg["num_layers"] * _layer_params(cfg, True) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward; a sublayer's query counts the keys it sees."""
    per_key = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    mix = 2 * cfg["num_layers"] * per_key * (seq_len + 1) / 2
    return 3.0 * (2.0 * active_matmul_params(cfg) + mix)


def total_params(cfg: dict) -> int:
    """Every parameter stored on this rank: both attentions and dense MLPs of
    every layer, the held experts, the whole router and its selection bias,
    four norms a layer, the final norm, the vocabulary's slice of table and
    head."""
    d = cfg["hidden_size"]
    routed, zero, _, _ = share(cfg)
    norms = 2 * (cfg["q_lora_rank"] + cfg["kv_lora_rank"]) + 4 * d
    return cfg["num_layers"] * (_layer_params(cfg, False) + routed + zero
                                + norms) + 2 * cfg["vocab_size"] * d + d


# -- 4. one call of a kernel: operations and bytes ---------------------------------

# the mix's least prompt: the least a slot's pages hold in a decode step
LEAST_PROMPT = 128
# the prefill bucket the flash kernel is counted at: the mix's median prompt's
FLASH_BUCKET = 2048
_KERNELS = ("mla_decode", "flash_fwd", "moe_gmm_decode", "moe_gmm_prefill")


def experts_touched(cfg: dict, assignments: int) -> float:
    """HELD experts that get at least one of ``assignments`` when each falls
    uniformly on one of the router's outputs: held x (1 - (1 - 1 /
    outputs)^assignments)."""
    routed, zero, _, held = share(cfg)
    return held * (1.0 - (1.0 - 1.0 / (routed + zero)) ** assignments)


def kernel_cost(kernel: str, cfg: dict, facts: dict) -> Tuple[float, float]:
    """(operations, bytes) that ONE call of a kernel needs, whatever
    implements it, in the stored type.

    ``mla_decode`` (one sublayer of a decode step): all 64 heads against each
    live row once, scores over kv_lora_rank + qk_rope_head_dim and values
    over kv_lora_rank, and as bytes the live rows at their unpadded width,
    at max_num_seqs slots x the mix's least prompt: bound by bytes, errs low
    by live / 128 (``mla.live_tokens_per_step`` scales it by hand).

    ``flash_fwd`` (one sublayer of the [1, 2048] prefill bucket): causal
    pairs x heads x 2 x (score width + value width); q, k, v, o once each.

    ``moe_gmm_decode``: one of the three products of a decode step's expert
    layer on THIS rank, counted at HALF the slots' assignments (32 x 12 / 2 =
    192 routed, of which uniform choosing over 768 outputs holds 4 here): 4
    rows in and out and the 3.5 of 16 held experts they touch. A saturated
    window's steps are fuller than that and greedy slots repeat each other
    less than by half (cell 5's share, counted at full occupancy, read 112%
    where fewer were streamed): the share errs low at any occupancy such a
    window has; ``moe.experts_touched`` scales it by hand.
    ``moe_gmm_prefill``: the least a call that has a real row needs: one row
    through one expert's matrix. A window of thousands of rows multiplies
    more and streams up to 16 matrices: the share errs low."""
    if kernel not in _KERNELS:
        raise KeyError(f"longcat_flash counts no kernel {kernel!r}; known: "
                       f"{sorted(_KERNELS)}")
    itemsize = jnp.dtype(cfg["torch_dtype"]).itemsize
    slots = facts.get("max_num_seqs") or cfg["job"]["engine"]["max_num_seqs"]
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
    d, f = cfg["hidden_size"], cfg["expert_ffn_hidden_size"]
    routed, zero, _, held = share(cfg)
    if kernel == "moe_gmm_prefill":
        return float(2 * d * f), float((d * f + d + f) * itemsize)
    assignments = slots * cfg["moe_topk"] // 2
    rows = assignments * held / (routed + zero)
    return (float(2 * rows * d * f),
            float((experts_touched(cfg, assignments) * d * f + rows * (d + f))
                  * itemsize))
