"""AI21's Jamba decoder as Jamba2-3B configures it (``model_type: jamba``):
Mamba-1 mixers in 26 layers of 28, multi-query attention without positions in
layers 7 and 21, a dense SwiGLU in every layer: everything the benchmark
knows about this architecture, in the one module a configuration file names
with ``"adapter": "jamba"``.

Written from the published configuration keys and from Mamba's paper (Gu and
Dao, "Mamba: Linear-Time Sequence Modeling with Selective State Spaces", arXiv
2312.00752), Jamba's (Lieber et al., arXiv 2403.19887) and the family's
modelling code as the builder recalls them, there being no network here; what
the keys do not state is listed under the configuration file's ``assumed``.

1. The plain float32 reference (``forward``, ``loss``). With ``d`` the hidden
   size, ``I = mamba_expand x d``, ``N = mamba_d_state``, ``R =
   mamba_dt_rank``::

     x0 = table[t]
     x1 = x + Mixer(RMSNorm(x))               input_layernorm
     x2 = x1 + W_down(silu(W_gate g) * W_up g),  g = RMSNorm(x1)   pre_ff_layernorm
     logits = RMSNorm(x_last) table^T                              (tied)

   - layer ``i`` is attention where ``i % attn_layer_period ==
     attn_layer_offset``, else Mamba; ``num_experts`` 1: every layer's
     feed-forward part is the one dense SwiGLU.
   - *Mamba-1*: ``[u | z] = h W_in``; ``a_t = silu(sum_k w_k u_{t-K+1+k} +
     b)``, a causal depthwise convolution of ``mamba_d_conv`` taps with zeros
     before the first position; ``[r | B | C] = a W_x``; ``r = RMSNorm_dt(r)``,
     ``B = RMSNorm_b(B)``, ``C = RMSNorm_c(C)`` (learned scales, the model's
     eps); ``dt = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)`` [I, N];
     ``s_t = exp(dt_t A) * s_{t-1} + (dt_t a_t) B_t^T``; ``y_t = s_t C_t + D *
     a_t``; out ``= (y * silu(z)) W_out``. The state is computed by a
     sequential ``lax.scan``, one position a step.
   - *attention*: ``q = h W_q`` (H heads of hd), ``k, v = h W_k, h W_v`` (KVH
     heads), no rotation, no norm, no bias; causal softmax of ``q . k /
     sqrt(hd)``, a query head at a time, head ``n`` reading key head ``n //
     (H / KVH)``; ``W_o``.

   The head in blocks of the vocabulary: no kernel, cache or batching.
   Callers wrap it in ``jax.default_matmul_precision("highest")``.
2. The way from the published keys to the program and to the reference
   (``program_overrides``, ``reference_cfg``, ``to_reference_params``).
3. Required operations per token and stored parameters.
4. Operations and bytes of one call of each kernel (``kernel_cost``).

Nothing here imports the program under test. ``cfg`` is a configuration
file's dict with the published key names; ``rcfg`` is ``reference_cfg(cfg)``.

Reference parameters are a plain dict: embed_tokens [V, d]; norm [d]; layers:
list of {input_layernorm, pre_ff_layernorm [d], gate_proj [d, F], up_proj,
down_proj [F, d]} plus, attention: {q_proj [d, H hd], k_proj, v_proj [d, KVH
hd], o_proj [H hd, d]}; mamba: {in_proj [d, 2I], conv_weight [K, I], conv_bias
[I], x_proj [I, R + 2N], dt_layernorm [R], b_layernorm, c_layernorm [N],
dt_proj [R, I], dt_bias [I], A_log [I, N], D [I], out_proj [I, d]}.
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
    rep, scale = H // KVH, q.shape[-1] ** -0.5
    i = jnp.arange(S)
    seen = i[:, None] >= i[None, :]

    def head(args):
        qh, n = args                                       # [B, S, hd], head
        kh, vh = k[:, :, n // rep], v[:, :, n // rep]
        scores = jnp.einsum("bqd,bsd->bqs", qh, kh) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqs,bsd->bqd", probs, vh)

    out = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), jnp.arange(H)))
    return jnp.moveaxis(out, 0, 2).reshape(B, S, -1)


def mamba(h, lp, rcfg):
    """A Mamba-1 mixer on normalised ``h`` [B, S, d] -> [B, S, d]. What
    ``rcfg["without"]`` names ("state": B = 0, "D", "dt_layernorm",
    "b_layernorm", "c_layernorm", "dt_bias", "conv_bias", "gate") is left out:
    the spoiled references of the tests."""
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    without, eps = rcfg["without"], rcfg["rms_norm_eps"]
    N, R = rcfg["mamba_d_state"], rcfg["mamba_dt_rank"]
    uz = h @ f32(lp["in_proj"])
    inner = uz.shape[-1] // 2
    u, z = uz[..., :inner], uz[..., inner:]
    w = f32(lp["conv_weight"])                              # [K, I]
    K, S = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    a = sum(w[k] * padded[:, k:k + S] for k in range(K))
    if "conv_bias" not in without:
        a = a + f32(lp["conv_bias"])
    a = jax.nn.silu(a)
    x = a @ f32(lp["x_proj"])
    parts = {"dt_layernorm": x[..., :R], "b_layernorm": x[..., R:R + N],
             "c_layernorm": x[..., R + N:]}
    r, Bm, Cm = (t if n in without else rms_norm(t, f32(lp[n]), eps)
                 for n, t in parts.items())
    dt = r @ f32(lp["dt_proj"])
    if "dt_bias" not in without:
        dt = dt + f32(lp["dt_bias"])
    dt = jax.nn.softplus(dt)
    if "state" in without:
        Bm = jnp.zeros_like(Bm)
    A = -jnp.exp(f32(lp["A_log"]))                          # [I, N]

    def step(s, t):
        dt_t, a_t, b_t, c_t = t                 # [B, I], [B, I], [B, N] x 2
        s = jnp.exp(dt_t[..., None] * A) * s \
            + (dt_t * a_t)[..., None] * b_t[:, None, :]
        return s, jnp.einsum("bin,bn->bi", s, c_t)

    s0 = jnp.zeros((h.shape[0], inner, N), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (dt, a, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1)
    if "D" not in without:
        y = y + f32(lp["D"]) * a
    if "gate" not in without:
        y = y * jax.nn.silu(z)
    return y @ f32(lp["out_proj"])


_HEAD_BLOCKS = 16


def head(x, table):
    """x [..., d] @ table^T [d, V] in float32, ``_HEAD_BLOCKS`` blocks of the
    vocabulary after each other where it divides."""
    V, d = table.shape
    if V % _HEAD_BLOCKS:
        return x @ table.astype(jnp.float32).T
    blocks = table.reshape(_HEAD_BLOCKS, V // _HEAD_BLOCKS, d)
    out = jax.lax.map(lambda b: x @ b.astype(jnp.float32).T, blocks)
    return jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], V)


def forward(params, tokens, rcfg, last: Optional[int] = None):
    """tokens [B, S] int -> logits [B, S, V], float32 throughout; with
    ``last`` only those of the last ``last`` positions (every position is
    still computed through every layer). ``rcfg["without"]`` may also name
    "attention": the FIRST attention layer then adds nothing."""
    eps = rcfg["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    first_attention = rcfg["layer_types"].index("attention")
    for i, (kind, lp) in enumerate(zip(rcfg["layer_types"], params["layers"])):
        h = rms_norm(x, f32(lp["input_layernorm"]), eps)
        if kind == "mamba":
            x = x + mamba(h, lp, rcfg)
        elif i != first_attention or "attention" not in rcfg["without"]:
            x = x + attention(h, lp, rcfg) @ f32(lp["o_proj"])
        g = rms_norm(x, f32(lp["pre_ff_layernorm"]), eps)
        x = x + swiglu(g, lp["gate_proj"], lp["up_proj"], lp["down_proj"])
    if last is not None:
        x = x[:, x.shape[1] - last:]
    return head(rms_norm(x, f32(params["norm"]), eps), params["embed_tokens"])


def loss(params, tokens, targets, rcfg):
    """Mean next-token cross-entropy; ``targets`` are ``tokens`` shifted by one."""
    logp = jax.nn.log_softmax(forward(params, tokens, rcfg), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# -- 2. from the published keys to the program and to the reference -------------

# what the program (ray_tpu/) cannot express of this family: refused, so that
# nothing else runs under the model's name
_ONLY = (("model_type", "jamba"), ("hidden_act", "silu"),
         ("mamba_conv_bias", True), ("mamba_proj_bias", False),
         ("num_experts", 1), ("num_experts_per_tok", 1),
         ("sliding_window", None), ("tie_word_embeddings", True))
_KINDS = {"mamba": "mamba", "attention": "full"}


def layer_types(cfg: dict) -> Tuple[str, ...]:
    """Each layer's type by the published period and offset."""
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return tuple("attention" if i % period == offset else "mamba"
                 for i in range(cfg["num_hidden_layers"]))


def ssm_sizes(cfg: dict) -> dict:
    return {"inner": cfg["mamba_expand"] * cfg["hidden_size"],
            "state": cfg["mamba_d_state"], "conv": cfg["mamba_d_conv"],
            "dt_rank": cfg["mamba_dt_rank"]}


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def program_overrides(cfg: dict, max_seq_len: int) -> dict:
    """Published keys -> the program's ``TransformerConfig`` fields."""
    name = cfg.get("name")
    for key, must in _ONLY:
        if cfg[key] != must:
            raise ValueError(f"{name}: {key} = {cfg[key]!r}; the program "
                             f"expresses only {must!r}")
    if "attention" not in layer_types(cfg):
        raise ValueError(f"{name}: no layer is attention at period "
                         f"{cfg['attn_layer_period']}, offset "
                         f"{cfg['attn_layer_offset']}")
    ssm, init = ssm_sizes(cfg), cfg["initializer"]
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                d_ff=cfg["intermediate_size"], max_seq_len=max_seq_len,
                tie_embeddings=True, remat=True,
                norm_eps=float(cfg["rms_norm_eps"]),
                layer_kinds=tuple(_KINDS[t] for t in layer_types(cfg)),
                block="rms", rope_kinds=(), ssm_inner=ssm["inner"],
                ssm_state=ssm["state"], ssm_conv=ssm["conv"],
                ssm_dt_rank=ssm["dt_rank"], ssm_inner_norms=True,
                attn_init_std=float(init["attention"]),
                mlp_init_std=float(init["mlp"]),
                ssm_proj_init_std=float(init["ssm_proj"]),
                ssm_x_init_std=float(init["ssm_x"]),
                embed_init_std=float(init["embedding"]),
                param_dtype=getattr(jnp, cfg["torch_dtype"]))


def reference_cfg(cfg: dict) -> dict:
    """What the plain reference needs: the published keys, each layer's type,
    and ``without``: parts it can be asked to leave out (the tests' spoiled
    references)."""
    out = {k: cfg[k] for k in (
        "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
        "mamba_d_state", "mamba_dt_rank")}
    out.update(layer_types=layer_types(cfg), without=())
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
        layer = {"input_layernorm": lp["attn_norm"]["scale"],
                 "pre_ff_layernorm": lp["mlp_norm"]["scale"]}
        layer.update({n: lp["mlp"][n]["kernel"]
                      for n in ("gate_proj", "up_proj", "down_proj")})
        if kind == "mamba":
            m = lp["mamba"]
            layer.update({
                "in_proj": m["in_proj"]["kernel"],
                "conv_weight": m["conv_kernel"], "conv_bias": m["conv_bias"],
                "x_proj": m["x_proj"]["kernel"],
                "dt_layernorm": m["dt_norm"]["scale"],
                "b_layernorm": m["b_norm"]["scale"],
                "c_layernorm": m["c_norm"]["scale"],
                "dt_proj": m["dt_proj"]["kernel"],
                "dt_bias": m["dt_proj"]["bias"],
                "A_log": m["A_log"], "D": m["D"],
                "out_proj": m["out_proj"]["kernel"]})
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
        return 2 * d * (cfg["num_attention_heads"]
                        + cfg["num_key_value_heads"]) * _head_dim(cfg)
    s = ssm_sizes(cfg)
    inner, N, K, R = s["inner"], s["state"], s["conv"], s["dt_rank"]
    mats = d * 2 * inner + inner * (R + 2 * N) + R * inner + inner * d
    # the taps and their bias, dt_proj's bias, A_log, D, the three inner norms
    return mats if matrices_only else mats + inner * (K + 1 + 1 + N + 1) \
        + R + 2 * N


def active_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by in a full forward (the head included)."""
    d = cfg["hidden_size"]
    return sum(_mixer_params(cfg, t, True) + 3 * d * cfg["intermediate_size"]
               for t in layer_types(cfg)) + cfg["vocab_size"] * d


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward; an attention layer's query counts the keys it
    sees, a Mamba layer six operations a state element."""
    s = ssm_sizes(cfg)
    mix = sum(4 * cfg["num_attention_heads"] * _head_dim(cfg) * (seq_len + 1) / 2
              if t == "attention" else 6 * s["inner"] * s["state"]
              for t in layer_types(cfg))
    return 3.0 * (2.0 * active_matmul_params(cfg) + mix)


def total_params(cfg: dict) -> int:
    """Every stored parameter: two norms a layer, the final norm, the tied
    table once."""
    d = cfg["hidden_size"]
    return sum(_mixer_params(cfg, t, False) + 3 * d * cfg["intermediate_size"]
               + 2 * d for t in layer_types(cfg)) + cfg["vocab_size"] * d + d


# -- 4. one call of a kernel: operations and bytes ---------------------------------

# the mix's least prompt: the least a slot's pages hold in a decode step. The
# live positions of a call are no fact of a run, so the attention kernel is
# counted there: its share errs low by live / 32 and can never pass 100% (as
# phi4flash counts its own); attn.live_tokens_per_step scales it by hand
LEAST_PROMPT = 32
# the prefill bucket whose scan calls ssm_scan_roofline is counted at: ONE row
# of the least bucket (a call of more rows or a longer bucket takes longer and
# is counted the same: errs low, as phi4flash's)
LEAST_BUCKET = 128
_KERNELS = ("ssm_step", "ssm_scan", "paged_gqa_decode")


def kernel_cost(kernel: str, cfg: dict, facts: dict) -> Tuple[float, float]:
    """(operations, bytes) that ONE call of a kernel needs, whatever
    implements it, in the stored type.

    ``ssm_step`` (one Mamba layer's decode step for every slot): seven
    operations a state element (``dt A``, its exponential counted as one, the
    decay's multiply, ``(dt a) B``'s multiply and its add, the multiply by
    ``C`` and the add into y) and as bytes the float32 state of max_num_seqs
    slots READ AND WRITTEN (2 x 327,680 B a slot at the published widths)
    plus a slot's operands (dt, a in and y out as float32 rows, B and C).
    Bound by bytes: 137.7 MB, 0.168 ms at 192 slots.

    ``ssm_scan`` (one Mamba layer's scan over ONE row of the least prefill
    bucket, [1, 128]): the same seven operations a state element and
    position; as bytes dt, the input and the output in float32 and the B and
    C columns, and the final state out. Bound by bytes on paper; the kernel is
    bound by the vector unit's serial recurrence, so its share is low by
    construction.

    ``paged_gqa_decode`` (one attention layer's live pages, once a decode
    step): every query head's head_dim-wide score and value against each live
    position, and as bytes the live rows (keys and values of the ONE key head:
    512 bytes a position), at max_num_seqs slots x the mix's least prompt.
    Bound by bytes."""
    if kernel not in _KERNELS:
        raise KeyError(f"jamba counts no kernel {kernel!r}; known: "
                       f"{sorted(_KERNELS)}")
    itemsize = jnp.dtype(cfg["torch_dtype"]).itemsize
    slots = facts.get("max_num_seqs") or cfg["job"]["engine"]["max_num_seqs"]
    s = ssm_sizes(cfg)
    state = s["inner"] * s["state"]
    operands = 4 * (3 * s["inner"] + 2 * s["state"])   # a position's, float32
    if kernel == "ssm_step":
        return float(7 * slots * state), float(slots * (8 * state + operands))
    if kernel == "ssm_scan":
        return (float(7 * LEAST_BUCKET * state),
                float(LEAST_BUCKET * operands + 4 * state))
    H, KVH, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  _head_dim(cfg))
    rows = slots * LEAST_PROMPT
    return float(rows * H * 2 * 2 * hd), float(rows * 2 * KVH * hd * itemsize)
