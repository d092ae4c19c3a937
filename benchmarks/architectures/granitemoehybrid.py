"""IBM's GraniteMoeHybrid decoder as Granite-4.0-H-Small configures it, ONE
RANK of a two-way expert-parallel deployment: everything the benchmark knows
about this architecture, in the one module a configuration file names with
``"adapter": "granitemoehybrid"``.

Written from the published configuration keys (``model_type:
granitemoehybrid``) and from Mamba-2's paper (Dao and Gu, "Transformers are
SSMs", arXiv 2405.21060) and the family's modelling code as the builder
recalls them, there being no network here; what the keys do not state is
listed under the configuration file's ``assumed``.

1. The plain float32 reference (``forward``, ``loss``). With ``r =
   residual_multiplier``::

     x0 = table[t] * embedding_multiplier
     x1 = x + r Mixer(RMSNorm(x))            input_layernorm
     m  = RMSNorm(x1)                         post_attention_layernorm
     x2 = x1 + r (Experts(m) + Shared(m))
     logits = RMSNorm(x_last) table^T / logits_scaling           (tied)

   - ``"attention"``: q, k, v, o without bias, NO position embedding
     (``position_embedding_type: nope``), causal softmax of ``q . k *
     attention_multiplier`` (a published number, not 1 / sqrt(head size)), a
     query head at a time, head ``h`` reading key head ``h // (H / KVH)``.
   - ``"mamba"`` (Mamba-2): ``z | xBC | dt = h W_in`` (widths I, I + 2N, H
     with I = mamba_expand x hidden = H x P); ``xBC = silu(conv(xBC) +
     bias)``, a causal depthwise convolution of ``mamba_d_conv`` taps with
     zeros before the first position; ``x_t [H, P], B_t [N], C_t [N] =
     split(xBC)`` (one group: B and C shared by all heads); ``dt_t[h] =
     softplus(dt_t[h] + dt_bias[h])``; ``A[h] = -exp(A_log[h])``; ``S_t[h] =
     exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t`` (S [H, P, N]);
     ``y_t[h] = S_t[h] C_t + D[h] x_t[h]``; the gated norm ``RMSNorm(y *
     silu(z))`` over all I channels; ``W_out``. The state is computed by a
     sequential ``lax.scan``, one position a step: ``mamba_chunk_size`` names
     a way to evaluate the same recurrence and is no term of it.
   - experts: ``logits = m W_r`` over ALL the router's outputs, the top-k
     logits, gates = softmax over those k; expert ``e`` is ``(silu(m Wg_e) *
     (m Wu_e)) Wd_e``; the shared MLP the same at ``shared_intermediate_size``
     for every token.

   THE SHARE. ``num_local_experts`` in a configuration file is how many
   routed experts this rank HOLDS; ``expert_parallel`` gives the deployment:
   ``{"routed_experts": 72, "ranks": 2, "rank": r}``. The router has
   ``routed_experts`` outputs and every token its top-k of ALL of them; the
   reference is given the matrices of experts ``r * held .. (r + 1) * held``
   and the vocabulary's slice, and leaves out what an expert held elsewhere
   would add, as the program does: with both ranks' routed parts summed and
   the shared MLP once it is the uncut layer (``tests/test_granite_hybrid.py``
   holds that). Experts one after the other on every token (weight 0 where a
   token did not choose it), the head in blocks of the vocabulary: no
   kernel, cache or batching. Callers wrap it in
   ``jax.default_matmul_precision("highest")``.
2. The way from the published keys to the program and to the reference
   (``program_overrides``, ``reference_cfg``, ``to_reference_params``).
3. Required operations per token and stored parameters.
4. Operations and bytes of one call of each kernel (``kernel_cost``).

Nothing here imports the program under test. ``cfg`` is a configuration
file's dict with the published key names; ``rcfg`` is ``reference_cfg(cfg)``.

Reference parameters are a plain dict: embed_tokens [V, d]; norm [d]; layers:
list of {input_layernorm, post_attention_layernorm [d], router [d, R],
gate_proj [E, d, f], up_proj, down_proj [E, f, d], shared_gate_proj [d, fs],
shared_up_proj, shared_down_proj [fs, d]} plus, attention: {q_proj [d, H hd],
k_proj, v_proj [d, KVH hd], o_proj [H hd, d]}; mamba: {in_proj [d, 2I + 2N +
H], conv_weight [K, I + 2N], conv_bias [I + 2N], dt_bias, A_log, D [H],
mixer_norm [I], out_proj [I, d]}.
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


def attention(h, lp, rcfg):
    """Causal softmax attention without positions on normalised ``h`` [B, S,
    d], a query head at a time, before o_proj: [B, S, H hd]."""
    B, S, _ = h.shape
    H, KVH = rcfg["num_attention_heads"], rcfg["num_key_value_heads"]
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    q = (h @ f32(lp["q_proj"])).reshape(B, S, H, -1)
    k = (h @ f32(lp["k_proj"])).reshape(B, S, KVH, -1)
    v = (h @ f32(lp["v_proj"])).reshape(B, S, KVH, -1)
    rep = H // KVH
    i = jnp.arange(S)
    seen = i[:, None] >= i[None, :]

    def head(args):
        qh, n = args                                       # [B, S, hd], head
        kh, vh = k[:, :, n // rep], v[:, :, n // rep]
        scores = jnp.einsum("bqd,bsd->bqs", qh, kh) * rcfg["attention_multiplier"]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqs,bsd->bqd", probs, vh)

    out = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), jnp.arange(H)))
    return jnp.moveaxis(out, 0, 2).reshape(B, S, -1)


def mamba2(h, lp, rcfg):
    """A Mamba-2 mixer on normalised ``h`` [B, S, d] -> [B, S, d]. What
    ``rcfg["without"]`` names ("D", "dt_bias", "conv_bias", "gate", "state",
    "float32_state": the state rounded to bfloat16 after every position) is
    left out: the spoiled references of the tests."""
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    without = rcfg["without"]
    H, N = rcfg["mamba_n_heads"], rcfg["mamba_d_state"]
    B, S, _ = h.shape
    zxd = h @ f32(lp["in_proj"])
    inner = (zxd.shape[-1] - 2 * N - H) // 2
    z, xbc, dt = jnp.split(zxd, [inner, 2 * inner + 2 * N], axis=-1)
    w = f32(lp["conv_weight"])                              # [K, I + 2N]
    K = w.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = sum(w[k] * padded[:, k:k + S] for k in range(K))
    if "conv_bias" not in without:
        xbc = xbc + f32(lp["conv_bias"])
    xbc = jax.nn.silu(xbc)
    x, Bm, Cm = jnp.split(xbc, [inner, inner + N], axis=-1)
    x = x.reshape(B, S, H, -1)                              # [B, S, H, P]
    if "dt_bias" not in without:
        dt = dt + f32(lp["dt_bias"])
    dt = jax.nn.softplus(dt)                                # [B, S, H]
    A = -jnp.exp(f32(lp["A_log"]))                          # [H]

    def step(s, t):
        dt_t, x_t, b_t, c_t = t          # [B, H], [B, H, P], [B, N], [B, N]
        s = jnp.exp(dt_t * A)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        if "float32_state" in without:   # kept in bfloat16 from step to step
            # (a cast there and back is folded away on the chip: XLA allows
            # itself the excess precision)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t)

    s0 = jnp.zeros((B, H, x.shape[-1], N), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (dt, x, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1)
    if "state" in without:
        y = jnp.zeros_like(y)
    if "D" not in without:
        y = y + f32(lp["D"])[:, None] * x
    y = y.reshape(B, S, inner)
    if "gate" not in without:
        y = y * jax.nn.silu(z)
    return rms_norm(y, f32(lp["mixer_norm"]), rcfg["rms_norm_eps"]) \
        @ f32(lp["out_proj"])


def routing(h, router, rcfg):
    """h [..., d] -> (gates [..., top_k], experts [..., top_k]) over ALL the
    router's outputs: the top-k logits, a softmax over those k."""
    logits = h @ router.astype(jnp.float32)
    top, experts = jax.lax.top_k(logits, rcfg["num_experts_per_tok"])
    return jax.nn.softmax(top, axis=-1), experts


def routed_experts(h, lp, rcfg):
    """The part of ``sum_e g_e SwiGLU_e(h)`` that the experts held here give:
    expert ``j`` of the matrices is expert ``first_expert + j`` of the
    router's. Every held expert is computed on every token, with the token's
    gate for it (0 where it did not choose it)."""
    gates, experts = routing(h, lp["router"], rcfg)

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


def head(x, table):
    """x [..., d] @ table^T [d, V] in float32, in blocks of the vocabulary
    where it divides."""
    V, d = table.shape
    if V % _HEAD_BLOCKS:
        return x @ table.astype(jnp.float32).T
    blocks = table.reshape(_HEAD_BLOCKS, V // _HEAD_BLOCKS, d)
    out = jax.lax.map(lambda b: x @ b.astype(jnp.float32).T, blocks)
    return jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], V)


def forward(params, tokens, rcfg, last: Optional[int] = None):
    """tokens [B, S] int -> logits [B, S, V], float32 throughout; with
    ``last`` only those of the last ``last`` positions (every position is
    still computed through every layer)."""
    eps, r = rcfg["rms_norm_eps"], rcfg["residual_multiplier"]
    x = params["embed_tokens"][tokens].astype(jnp.float32) \
        * rcfg["embedding_multiplier"]
    for kind, lp in zip(rcfg["layer_types"], params["layers"]):
        h = rms_norm(x, lp["input_layernorm"], eps)
        if kind == "mamba":
            a = mamba2(h, lp, rcfg)
        else:
            a = attention(h, lp, rcfg) @ lp["o_proj"].astype(jnp.float32)
        x = x + r * a
        m = rms_norm(x, lp["post_attention_layernorm"], eps)
        x = x + r * (routed_experts(m, lp, rcfg) + swiglu(
            m, lp["shared_gate_proj"], lp["shared_up_proj"],
            lp["shared_down_proj"]))
    if last is not None:
        x = x[:, x.shape[1] - last:]
    return head(rms_norm(x, params["norm"], eps), params["embed_tokens"]) \
        / rcfg["logits_scaling"]


def loss(params, tokens, targets, rcfg):
    """Mean next-token cross-entropy; ``targets`` are ``tokens`` shifted by one."""
    logp = jax.nn.log_softmax(forward(params, tokens, rcfg), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# -- 2. from the published keys to the program and to the reference -------------

# what the program (ray_tpu/) cannot express of this family: refused, so that
# nothing else runs under the model's name
_ONLY = (("model_type", "granitemoehybrid"), ("hidden_act", "silu"),
         ("attention_bias", False), ("mamba_proj_bias", False),
         ("mamba_conv_bias", True), ("mamba_n_groups", 1),
         ("normalization_function", "rmsnorm"),
         ("position_embedding_type", "nope"), ("rope_scaling", None),
         ("tie_word_embeddings", True))
_KINDS = {"mamba": "mamba2", "attention": "full"}


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
    held = cfg["num_local_experts"]
    if ep["routed_experts"] != ep["ranks"] * held or not 0 <= ep["rank"] < ep["ranks"]:
        raise ValueError(f"{cfg.get('name')}: expert_parallel {ep} does not "
                         f"share {ep['routed_experts']} experts into ranks of "
                         f"{held}")
    return ep["routed_experts"], ep["rank"] * held, held


def ssm_sizes(cfg: dict) -> dict:
    heads, head = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    if heads * head != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError(f"{cfg.get('name')}: mamba_n_heads x mamba_d_head is "
                         f"not mamba_expand x hidden_size")
    return {"inner": heads * head, "heads": heads, "head": head,
            "state": cfg["mamba_d_state"], "conv": cfg["mamba_d_conv"]}


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def program_overrides(cfg: dict, max_seq_len: int) -> dict:
    """Published keys -> the program's ``TransformerConfig`` fields."""
    name = cfg.get("name")
    for key, must in _ONLY:
        if cfg[key] != must:
            raise ValueError(f"{name}: {key} = {cfg[key]!r}; the program "
                             f"expresses only {must!r}")
    routed, first, held = share(cfg)
    ssm, init = ssm_sizes(cfg), cfg["initializer"]
    if cfg["shared_intermediate_size"] % cfg["intermediate_size"]:
        raise ValueError(f"{name}: the shared MLP's width must be a multiple "
                         f"of an expert's")
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                d_ff=cfg["intermediate_size"], moe_every=1,
                max_seq_len=max_seq_len, rope_theta=float(cfg["rope_theta"]),
                tie_embeddings=True, remat=True, n_experts=routed,
                experts_held=(first, held),
                experts_per_token=cfg["num_experts_per_tok"],
                n_shared_experts=(cfg["shared_intermediate_size"]
                                  // cfg["intermediate_size"]),
                norm_topk_prob=True, router_kind="softmax",
                norm_eps=float(cfg["rms_norm_eps"]),
                layer_kinds=tuple(_KINDS[t] for t in layer_types(cfg)),
                block="rms", rope_kinds=(),
                ssm_inner=ssm["inner"], ssm_state=ssm["state"],
                ssm_conv=ssm["conv"], ssm_heads=ssm["heads"],
                embed_scale=float(cfg["embedding_multiplier"]),
                residual_scale=float(cfg["residual_multiplier"]),
                attn_scale=float(cfg["attention_multiplier"]),
                logit_scale=1.0 / float(cfg["logits_scaling"]),
                attn_init_std=float(init["attention"]),
                mlp_init_std=float(init["mlp"]),
                expert_init_std=float(init["experts"]),
                ssm_proj_init_std=float(init["mamba"]),
                embed_init_std=float(init["embedding"]),
                param_dtype=getattr(jnp, cfg["torch_dtype"]))


def reference_cfg(cfg: dict) -> dict:
    """What the plain reference needs: the published keys, the layers it
    runs, where this rank's experts begin, and ``without``: parts of the
    Mamba-2 mixer it can be asked to leave out (the tests' spoiled
    references)."""
    out = {k: cfg[k] for k in (
        "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
        "num_experts_per_tok", "mamba_n_heads", "mamba_d_state",
        "embedding_multiplier", "residual_multiplier", "attention_multiplier",
        "logits_scaling")}
    out.update(layer_types=layer_types(cfg), first_expert=share(cfg)[1],
               without=())
    return out


def to_reference_params(p: dict, cfg: dict) -> dict:
    """The program's parameter tree under the reference's plain names.
    Reshapes only (heads folded into one axis); called inside a jit so no
    copy of the weights outlives the check."""
    def flat_in(k):   # [d, heads, hd] -> [d, heads*hd]
        return k.reshape(k.shape[0], -1)

    layers = []
    for i, kind in enumerate(layer_types(cfg)):
        lp = p[f"layer_{i}"]
        m = lp["moe"]
        layer = {"input_layernorm": lp["attn_norm"]["scale"],
                 "post_attention_layernorm": lp["mlp_norm"]["scale"],
                 "router": m["router"]["kernel"],
                 "gate_proj": m["gate_proj"], "up_proj": m["up_proj"],
                 "down_proj": m["down_proj"]}
        layer.update({"shared_" + n: m["shared"][n]["kernel"]
                      for n in ("gate_proj", "up_proj", "down_proj")})
        if kind == "mamba":
            s = lp["mamba"]
            layer.update({
                "in_proj": s["in_proj"]["kernel"],
                "conv_weight": s["conv_kernel"], "conv_bias": s["conv_bias"],
                "dt_bias": s["dt_bias"], "A_log": s["A_log"], "D": s["D"],
                "mixer_norm": s["norm"]["scale"],
                "out_proj": s["out_proj"]["kernel"]})
        else:
            a = lp["attn"]
            o = a["o_proj"]["kernel"]
            layer.update({"q_proj": flat_in(a["q_proj"]["kernel"]),
                          "k_proj": flat_in(a["k_proj"]["kernel"]),
                          "v_proj": flat_in(a["v_proj"]["kernel"]),
                          "o_proj": o.reshape(-1, o.shape[-1])})
        layers.append(layer)
    return {"embed_tokens": p["embed"], "norm": p["final_norm"]["scale"],
            "layers": layers}


# -- 3. required operations and stored parameters, from the shapes ---------------


def _mixer_params(cfg: dict, kind: str, matrices_only: bool) -> int:
    d = cfg["hidden_size"]
    if kind == "attention":
        hd = _head_dim(cfg)
        return 2 * d * (cfg["num_attention_heads"]
                        + cfg["num_key_value_heads"]) * hd
    s = ssm_sizes(cfg)
    xbc = s["inner"] + 2 * s["state"]
    mats = d * (s["inner"] + xbc + s["heads"]) + s["inner"] * d
    # the taps and their bias, dt_bias, A_log and D a head, the gated norm
    return mats if matrices_only else mats + xbc * (s["conv"] + 1) \
        + 3 * s["heads"] + s["inner"]


def _mlp_params(cfg: dict, active: bool) -> int:
    """A layer's router, routed experts and shared MLP: stored HERE, or those
    a token multiplies by (its top-k routed experts wherever they are held)."""
    d = cfg["hidden_size"]
    experts = cfg["num_experts_per_tok"] if active else cfg["num_local_experts"]
    return d * share(cfg)[0] + 3 * d * (
        experts * cfg["intermediate_size"] + cfg["shared_intermediate_size"])


def active_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by in the whole deployment's layers."""
    return sum(_mixer_params(cfg, t, True) + _mlp_params(cfg, True)
               for t in layer_types(cfg)) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward; an attention layer's query counts the keys it
    sees, a Mamba-2 layer six operations a state element."""
    s = ssm_sizes(cfg)
    mix = sum(4 * cfg["num_attention_heads"] * _head_dim(cfg) * (seq_len + 1) / 2
              if t == "attention" else 6 * s["inner"] * s["state"]
              for t in layer_types(cfg))
    return 3.0 * (2.0 * active_matmul_params(cfg) + mix)


def total_params(cfg: dict) -> int:
    """Every parameter stored on this rank: the held experts, the whole
    router, the shared MLP, two norms a layer, the final norm, the
    vocabulary's slice of the tied table."""
    d = cfg["hidden_size"]
    return sum(_mixer_params(cfg, t, False) + _mlp_params(cfg, False) + 2 * d
               for t in layer_types(cfg)) + cfg["vocab_size"] * d + d


# -- 4. one call of a kernel: operations and bytes ---------------------------------

# the mix's least prompt: the least a slot's pages hold in a decode step. The
# live positions of a call are no fact of a run, so the attention kernel is
# counted there: its share errs low by live / 128 and can never pass 100% (as
# afmoe counts its own); attn.live_tokens_per_step scales it by hand
LEAST_PROMPT = 128
# the prefill bucket whose scan calls ssd_scan_roofline is counted at: the
# least (a longer one takes longer and is counted the same: errs low by
# bucket / 256, as phi4flash's ssm_scan)
LEAST_BUCKET = 256
_KERNELS = ("ssd_scan", "ssd_step", "paged_gqa_decode", "moe_gmm_decode",
            "moe_gmm_prefill")


def experts_touched(cfg: dict, rows: int) -> int:
    """HELD experts that get at least one of ``rows`` tokens when each
    token's experts are uniform over all the routed ones: held x (1 - (1 -
    k / routed)^rows), rounded down."""
    routed, _, held = share(cfg)
    k = cfg["num_experts_per_tok"]
    return int(held * (1.0 - (1.0 - k / routed) ** rows))


def kernel_cost(kernel: str, cfg: dict, facts: dict) -> Tuple[float, float]:
    """(operations, bytes) that ONE call of a kernel needs, whatever
    implements it, in the stored type.

    ``ssd_step`` (one Mamba-2 layer's decode step for every slot): five
    operations a state element (the decay, the outer product's
    multiply-add, the multiply-add into y) and as bytes the float32 state of
    max_num_seqs slots READ AND WRITTEN (2 x 4.19 MB a slot) plus a slot's
    operands (x, B, C in the stored type, dt and y in float32). Bound by
    bytes: 337.6 MB, 0.41 ms at 40 slots.

    ``ssd_scan`` (one Mamba-2 layer's recurrence over the LEAST prefill
    bucket, one row of 256 positions): the same five operations a state
    element and position, and as bytes x, B, C in, dt in and y out in
    float32 and the final state out. Bound by bytes on paper (17.0 MB, 21 us,
    against 1.3 GFLOP, 7 us): whatever time the chunked form's products take
    beyond that shows as a share under 100%.

    ``paged_gqa_decode`` (the one attention layer's live pages, once a decode
    step): every query head's head_dim-wide score and value against each live
    position, and as bytes the live rows (keys and values of all key heads:
    4,096 bytes a position), at max_num_seqs slots x the mix's least prompt.
    Bound by bytes.

    ``moe_gmm_decode``: one of the three products of a decode step's expert
    layer on THIS rank: of max_num_seqs x top_k assignments the share that
    uniform routing gives the held experts (held / routed), and the held
    experts those touch (35 of 36 at 40 slots), each matrix once, plus the
    rows in and out. ``moe_gmm_prefill``: the least a call holds, the mix's
    least prompt of real rows, counted the same way (all 36); a longer prompt
    multiplies more and streams no more, so the share errs low."""
    if kernel not in _KERNELS:
        raise KeyError(f"granitemoehybrid counts no kernel {kernel!r}; known: "
                       f"{sorted(_KERNELS)}")
    itemsize = jnp.dtype(cfg["torch_dtype"]).itemsize
    slots = facts.get("max_num_seqs") or cfg["job"]["engine"]["max_num_seqs"]
    s = ssm_sizes(cfg)
    state = s["inner"] * s["state"]
    # a position's operands: x, B, C stored, dt and y float32
    operands = (s["inner"] + 2 * s["state"]) * itemsize \
        + 4 * (s["heads"] + s["inner"])
    if kernel == "ssd_step":
        return float(5 * slots * state), float(slots * (8 * state + operands))
    if kernel == "ssd_scan":
        return (float(5 * LEAST_BUCKET * state),
                float(LEAST_BUCKET * operands + 4 * state))
    if kernel == "paged_gqa_decode":
        H, KVH, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      _head_dim(cfg))
        rows = slots * LEAST_PROMPT
        return (float(rows * H * 2 * 2 * hd),
                float(rows * 2 * KVH * hd * itemsize))
    d, f, k = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_experts_per_tok"])
    routed, _, held = share(cfg)
    tokens = slots if kernel == "moe_gmm_decode" else LEAST_PROMPT
    rows = tokens * k * held / routed
    return (float(2 * rows * d * f),
            float((experts_touched(cfg, tokens) * d * f + rows * (d + f))
                  * itemsize))
