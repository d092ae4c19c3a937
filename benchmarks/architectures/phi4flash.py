"""Phi-4-mini-flash-reasoning's decoder-hybrid-decoder (SambaY): everything
the benchmark knows about this architecture, in the one module a
configuration file names with ``"adapter": "phi4flash"``.

Written from the published configuration keys (``model_type: phi4flash``) and
from the family's papers as the builder recalls them, there being no network
here (Samba, arXiv 2406.07522: Mamba layers interleaved with sliding-window
attention; YOCO, arXiv 2405.05254: a self-decoder that writes ONE key/value
cache and a cross-decoder whose layers all read it; Differential Transformer,
arXiv 2410.05258; "Decoder-Hybrid-Decoder Architecture for Efficient
Reasoning with Long Generation", arXiv 2507.06607, for the gated memory unit).
What the configuration file lists under ``assumed`` is recalled, not read.

1. The plain float32 reference (``forward``, ``loss``). ``h = LayerNorm(x)``
   with scale and bias. Every layer ``x <- x + Mixer_i(LN1(x))``, ``x <- x +
   W_down(silu(g) * u)`` with ``g = W_gate LN2(x)``, ``u = W_up LN2(x)``. After
   the last layer a final LayerNorm and the tied table as the head. No
   position embedding anywhere. With ``n = num_hidden_layers`` the first
   ``n/2`` layers alternate Mamba (even) and window attention (odd), layer
   ``n/2`` is Mamba and hands its scan output on, layer ``n/2 + 1`` is full
   attention and the one layer whose keys and values later layers read; from
   ``n/2 + 2`` on, even layers are gated memory units and odd layers cross
   attention:

   - *Mamba-1*: ``[a | z] = W_in h``; ``a <- silu(conv1d_causal(a))``
     (depthwise, bias); ``[dt | B | C] = W_x a``; ``dt <- softplus(W_dt dt +
     b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(dt_t A) * s_{t-1} + (dt_t a_t)
     B_t^T``; ``y_t = s_t C_t + D * a_t``; output ``W_out (y * silu(z))``. The
     state is computed by a sequential ``lax.scan``, one position a step.
   - *differential attention*: ``[q | k | v] = W_qkv h + b``; heads paired in
     order (``q1, q2 = q[2j], q[2j+1]``; ``k1, k2`` and ``v1, v2`` likewise,
     query pair ``j`` with key pair ``j // (heads / kv heads)``), ``V = [v1 |
     v2]``; ``o1 = softmax(q1 k1^T / sqrt(hd)) V``, ``o2`` likewise; ``lambda
     = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 -
     0.6 exp(-0.3 i)`` for layer ``i``; ``o = RMSNorm(o1 - lambda o2) * (1 -
     lambda_init)`` over the ``2 hd`` of a pair; ``W_o`` with bias. A window
     layer's position ``t`` sees ``t - window + 1 .. t``.
   - *gated memory unit*: ``W_out (m_t * silu(W_in h_t))``, ``m_t`` the
     handing Mamba layer's ``y_t`` (with the ``D`` term, before its gate).
   - *cross attention*: ``q = W_q h + b`` only; keys and values are the full
     layer's; differential attention, causal over all positions, with the
     layer's own lambda vectors, sub-norm and ``W_o``.

   Attention is computed a head at a time and the head in blocks of the
   vocabulary: neither changes the mathematics. Callers wrap it in
   ``jax.default_matmul_precision("highest")``.
2. The way from the published keys to the program and to the reference
   (``program_overrides``, ``reference_cfg``, ``to_reference_params``).
3. Required operations per token and stored parameters.
4. Operations and bytes of one call of each kernel (``kernel_cost``).

Nothing here imports the program under test. ``cfg`` is a configuration
file's dict with the published key names; ``rcfg`` is ``reference_cfg(cfg)``.

Reference parameters are a plain dict: embed_tokens [V, d]; norm, norm_bias
[d]; layers: list of {kind, ln1, ln1_bias, ln2, ln2_bias, gate_proj [d, F],
up_proj, down_proj [F, d]} plus, by kind: mamba {in_proj [d, 2I], conv_weight
[K, I], conv_bias [I], x_proj [I, R + 2N], dt_proj [R, I], dt_bias [I], A_log
[I, N], D [I], out_proj [I, d]}; window / full {Wqkv [d, (H + 2 KVH) hd],
Wqkv_bias, out_proj [H hd, d], out_bias, lambda_q1, lambda_k1, lambda_q2,
lambda_k2 [hd], subln [2 hd]}; cross {Wq [d, H hd], Wq_bias, out_proj,
out_bias, the lambdas, subln}; gmu {in_proj [d, I], out_proj [I, d]}.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# -- 1. the plain reference ------------------------------------------------------


def layer_kinds(n_layers: int) -> Tuple[str, ...]:
    """The published pattern for ``n_layers`` (a multiple of four): see the
    module docstring."""
    half = n_layers // 2
    kinds = []
    for i in range(n_layers):
        if i <= half:
            kinds.append("mamba" if i % 2 == 0 else "window")
        elif i == half + 1:
            kinds.append("full")
        else:
            kinds.append("gmu" if i % 2 == 0 else "cross")
    return tuple(kinds)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def swiglu(h, gate, up, down):
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)


def mamba(h, lp, rcfg):
    """h [B, S, d] -> (mixer output [B, S, d], y [B, S, I] before the gate)."""
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    N, R = rcfg["ssm_state"], rcfg["dt_rank"]
    az = h @ f32(lp["in_proj"])
    inner = az.shape[-1] // 2
    a, z = az[..., :inner], az[..., inner:]
    w = f32(lp["conv_weight"])                                  # [K, I]
    K, S = w.shape[0], a.shape[1]
    padded = jnp.pad(a, ((0, 0), (K - 1, 0), (0, 0)))
    a = sum(w[k] * padded[:, k:k + S] for k in range(K)) + f32(lp["conv_bias"])
    a = jax.nn.silu(a)
    x = a @ f32(lp["x_proj"])
    dt = jax.nn.softplus(x[..., :R] @ f32(lp["dt_proj"]) + f32(lp["dt_bias"]))
    Bm, Cm = x[..., R:R + N], x[..., R + N:]
    A = -jnp.exp(f32(lp["A_log"]))                              # [I, N]

    def step(s, t):
        dt_t, a_t, b_t, c_t = t                     # [B, I], [B, I], [B, N] x 2
        s = jnp.exp(dt_t[..., None] * A) * s \
            + (dt_t * a_t)[..., None] * b_t[:, None, :]
        return s, jnp.einsum("bin,bn->bi", s, c_t)

    s0 = jnp.zeros((h.shape[0], inner, N), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (dt, a, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1) + f32(lp["D"]) * a
    return (y * jax.nn.silu(z)) @ f32(lp["out_proj"]), y


def diff_attention(q, k, v, lp, layer, window, rcfg):
    """q [B, S, H, hd], k, v [B, S, KVH, hd] -> [B, S, H hd] before W_o:
    differential attention over causal (and windowed) softmax, a query pair
    at a time."""
    B, S, H, hd = q.shape
    rep = H // k.shape[2]
    pos = jnp.arange(S)
    seen = pos[:, None] >= pos[None, :]
    if window:
        seen &= pos[None, :] > pos[:, None] - window
    init = lambda_init(layer)
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    lam = (jnp.exp(jnp.sum(f32(lp["lambda_q1"]) * f32(lp["lambda_k1"])))
           - jnp.exp(jnp.sum(f32(lp["lambda_q2"]) * f32(lp["lambda_k2"])))
           + init)

    def softmax_v(qh, kh, vv):
        scores = jnp.einsum("bqd,bsd->bqs", qh, kh) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqs,bsd->bqd", probs, vv)

    def pair(j):
        m = j // rep
        vv = jnp.concatenate([v[:, :, 2 * m], v[:, :, 2 * m + 1]], axis=-1)
        o1 = softmax_v(q[:, :, 2 * j], k[:, :, 2 * m], vv)
        o2 = softmax_v(q[:, :, 2 * j + 1], k[:, :, 2 * m + 1], vv)
        return rms_norm(o1 - lam * o2, f32(lp["subln"]),
                        rcfg["layer_norm_eps"]) * (1.0 - init)

    return jnp.concatenate([pair(j) for j in range(H // 2)], axis=-1)


def _heads(x, n):
    return x.reshape(*x.shape[:-1], n, x.shape[-1] // n)


_HEAD_BLOCKS = 16


def head(x, table):
    """x [..., d] @ table^T [d, V] in float32, ``_HEAD_BLOCKS`` blocks of the
    vocabulary after each other where it divides: the float32 copy of a
    200,064-row table is 2 GB at once."""
    V, d = table.shape
    if V % _HEAD_BLOCKS:
        return x @ table.astype(jnp.float32).T
    blocks = table.reshape(_HEAD_BLOCKS, V // _HEAD_BLOCKS, d)
    out = jax.lax.map(lambda b: x @ b.astype(jnp.float32).T, blocks)
    return jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], V)


def forward(params, tokens, rcfg, last: Optional[int] = None):
    """tokens [B, S] int -> logits [B, S, V], float32 throughout; with
    ``last`` only those of the last ``last`` positions (every position is
    still computed through every layer: no layer is given one row alone)."""
    eps = rcfg["layer_norm_eps"]
    H, KVH = rcfg["num_attention_heads"], rcfg["num_key_value_heads"]
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    memory = shared_k = shared_v = None
    for i, lp in enumerate(params["layers"]):
        kind = lp["kind"]
        h = layer_norm(x, f32(lp["ln1"]), f32(lp["ln1_bias"]), eps)
        if kind == "mamba":
            out, memory = mamba(h, lp, rcfg)   # the last one's is what is read
        elif kind == "gmu":
            out = (memory * jax.nn.silu(h @ f32(lp["in_proj"]))) \
                @ f32(lp["out_proj"])
        else:
            if kind == "cross":
                q = _heads(h @ f32(lp["Wq"]) + f32(lp["Wq_bias"]), H)
                k, v = shared_k, shared_v
            else:
                hd = lp["Wqkv"].shape[1] // (H + 2 * KVH)
                qkv = h @ f32(lp["Wqkv"]) + f32(lp["Wqkv_bias"])
                q = _heads(qkv[..., :H * hd], H)
                k = _heads(qkv[..., H * hd:(H + KVH) * hd], KVH)
                v = _heads(qkv[..., (H + KVH) * hd:], KVH)
                if kind == "full":
                    shared_k, shared_v = k, v
            o = diff_attention(q, k, v, lp, i,
                               rcfg["sliding_window"] if kind == "window" else 0,
                               rcfg)
            out = o @ f32(lp["out_proj"]) + f32(lp["out_bias"])
        x = x + out
        h = layer_norm(x, f32(lp["ln2"]), f32(lp["ln2_bias"]), eps)
        x = x + swiglu(h, lp["gate_proj"], lp["up_proj"], lp["down_proj"])
    if last is not None:
        x = x[:, x.shape[1] - last:]
    x = layer_norm(x, f32(params["norm"]), f32(params["norm_bias"]), eps)
    return head(x, params["embed_tokens"])


def loss(params, tokens, targets, rcfg):
    """Mean next-token cross-entropy; ``targets`` are ``tokens`` shifted by one."""
    logp = jax.nn.log_softmax(forward(params, tokens, rcfg), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# -- 2. from the published keys to the program and to the reference -------------

# what the program (ray_tpu/) cannot express of this family: refused, so that
# nothing else runs under the model's name
_ONLY = (("model_type", "phi4flash"), ("hidden_act", "silu"),
         ("tie_word_embeddings", True), ("mlp_bias", False),
         ("lm_head_bias", False), ("mb_per_layer", 2), ("embd_pdrop", 0),
         ("resid_pdrop", 0))


def ssm_sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return {"inner": cfg["mamba_expand"] * d, "state": cfg["mamba_d_state"],
            "conv": cfg["mamba_d_conv"], "dt_rank": -(-d // 16)}


def program_overrides(cfg: dict, max_seq_len: int) -> dict:
    """Published keys -> the program's ``TransformerConfig`` fields."""
    name = cfg.get("name")
    for key, must in _ONLY:
        if cfg[key] != must:
            raise ValueError(f"{name}: {key} = {cfg[key]!r}; the program "
                             f"expresses only {must!r}")
    if cfg["num_hidden_layers"] % 4:
        raise ValueError(f"{name}: num_hidden_layers must be a multiple of "
                         "four (the self- and the cross-decoder alternate)")
    ssm, init = ssm_sizes(cfg), cfg["initializer"]
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                d_ff=cfg["intermediate_size"], max_seq_len=max_seq_len,
                tie_embeddings=True, remat=True,
                norm_eps=float(cfg["layer_norm_eps"]),
                layer_kinds=layer_kinds(cfg["num_hidden_layers"]),
                window=cfg["sliding_window"], ssm_inner=ssm["inner"],
                ssm_state=ssm["state"], ssm_conv=ssm["conv"],
                ssm_dt_rank=ssm["dt_rank"],
                attn_init_std=float(init["attention"]),
                mlp_init_std=float(init["mlp"]),
                ssm_proj_init_std=float(init["ssm_proj"]),
                ssm_x_init_std=float(init["ssm_x"]),
                embed_init_std=float(init["embedding"]),
                param_dtype=getattr(jnp, cfg["torch_dtype"]))


def reference_cfg(cfg: dict) -> dict:
    ssm = ssm_sizes(cfg)
    return {"num_attention_heads": cfg["num_attention_heads"],
            "num_key_value_heads": cfg["num_key_value_heads"],
            "layer_norm_eps": cfg["layer_norm_eps"],
            "sliding_window": cfg["sliding_window"],
            "ssm_state": ssm["state"], "dt_rank": ssm["dt_rank"]}


def to_reference_params(p: dict, cfg: dict) -> dict:
    """The program's parameter tree under the reference's plain names:
    renames only; called inside a jit so no copy of the weights outlives the
    check."""
    layers = []
    for i, kind in enumerate(layer_kinds(cfg["num_hidden_layers"])):
        lp = p[f"layer_{i}"]
        layer = {"kind": kind,
                 "ln1": lp["attn_norm"]["scale"], "ln1_bias": lp["attn_norm"]["bias"],
                 "ln2": lp["mlp_norm"]["scale"], "ln2_bias": lp["mlp_norm"]["bias"]}
        layer.update({n: lp["mlp"][n]["kernel"]
                      for n in ("gate_proj", "up_proj", "down_proj")})
        m = lp["mixer"]
        if kind == "mamba":
            layer.update({
                "in_proj": m["in_proj"]["kernel"],
                "conv_weight": m["conv_kernel"], "conv_bias": m["conv_bias"],
                "x_proj": m["x_proj"]["kernel"],
                "dt_proj": m["dt_proj"]["kernel"], "dt_bias": m["dt_proj"]["bias"],
                "A_log": m["A_log"], "D": m["D"],
                "out_proj": m["out_proj"]["kernel"]})
        elif kind == "gmu":
            layer.update({"in_proj": m["in_proj"]["kernel"],
                          "out_proj": m["out_proj"]["kernel"]})
        else:
            name = "Wq" if kind == "cross" else "Wqkv"
            layer.update({
                name: m[name]["kernel"], name + "_bias": m[name]["bias"],
                "out_proj": m["out_proj"]["kernel"],
                "out_bias": m["out_proj"]["bias"], "subln": m["subln"]})
            layer.update({n: m[n] for n in ("lambda_q1", "lambda_k1",
                                            "lambda_q2", "lambda_k2")})
        layers.append(layer)
    return {"embed_tokens": p["embed"], "norm": p["final_norm"]["scale"],
            "norm_bias": p["final_norm"]["bias"], "layers": layers}


# -- 3. required operations and stored parameters, from the shapes ---------------


def _mixer_params(cfg: dict, kind: str, matrices_only: bool) -> int:
    d, H, KVH = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    hd = d // H
    s = ssm_sizes(cfg)
    inner, N, K, R = s["inner"], s["state"], s["conv"], s["dt_rank"]
    if kind == "mamba":
        mats = d * 2 * inner + inner * (R + 2 * N) + R * inner + inner * d
        return mats if matrices_only else mats + inner * (K + 1 + 1 + N + 1)
    if kind == "gmu":
        return 2 * d * inner
    qkv = d * H * hd if kind == "cross" else d * (H + 2 * KVH) * hd
    mats = qkv + H * hd * d
    extra = (qkv // d) + d + 4 * hd + 2 * hd      # biases, lambdas, sub-norm
    return mats if matrices_only else mats + extra


def total_params(cfg: dict) -> int:
    """Every stored parameter (the tied table once)."""
    d = cfg["hidden_size"]
    layers = sum(_mixer_params(cfg, kind, False) + 3 * d * cfg["intermediate_size"]
                 + 4 * d for kind in layer_kinds(cfg["num_hidden_layers"]))
    return layers + cfg["vocab_size"] * d + 2 * d


def active_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by in a full forward (the head included)."""
    d = cfg["hidden_size"]
    return sum(_mixer_params(cfg, kind, True) + 3 * d * cfg["intermediate_size"]
               for kind in layer_kinds(cfg["num_hidden_layers"])) \
        + cfg["vocab_size"] * d


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward; attention counts the keys each query sees (the
    window's at most), 64-wide scores and 128-wide values a head, and the
    scan six operations a state element."""
    H, hd = cfg["num_attention_heads"], cfg["hidden_size"] // cfg["num_attention_heads"]
    s = ssm_sizes(cfg)
    per_key = 2 * H * (hd + 2 * hd)
    attn = 0.0
    for kind in layer_kinds(cfg["num_hidden_layers"]):
        if kind == "window":
            attn += per_key * min(cfg["sliding_window"], (seq_len + 1) / 2)
        elif kind in ("full", "cross"):
            attn += per_key * (seq_len + 1) / 2
        elif kind == "mamba":
            attn += 6 * s["inner"] * s["state"]
    return 3.0 * (2.0 * active_matmul_params(cfg) + attn)


# -- 4. one call of a kernel: operations and bytes ---------------------------------

# the least the mix allows a decode step to hold: every slot at the shortest
# prompt. The live positions of a call are no fact of a run, so the shared
# layer's calls are counted there: the share errs low by live / 256 and can
# never pass 100% whatever a traced slice holds (as deepseek_v3.kernel_cost
# counts mla_decode); attn.live_tokens_per_step scales it by hand
LEAST_PROMPT = 256
# the prefill bucket whose scan calls ssm_scan_roofline is counted at: the
# least (a longer one takes longer and is counted the same: errs low)
LEAST_BUCKET = 256
_KERNELS = ("paged_gqa_decode", "window_gqa_decode", "ssm_scan")


def kernel_cost(kernel: str, cfg: dict, facts: dict) -> Tuple[float, float]:
    """(operations, bytes) that ONE call of a kernel needs, in the stored type.

    ``paged_gqa_decode`` (the shared layer's pages, read by the full layer and
    by each cross layer of a decode step): every head's 64-wide score and
    128-wide value against each live position, and as bytes the live rows
    (keys and values of all 20 heads, 5,120 bytes a position), at the LEAST
    the mix allows: max_num_seqs slots x 256 positions. Bound by bytes.

    ``window_gqa_decode`` (the same kernel over a window layer's rings): the
    same a position, at the least a slot's ring holds in this mix, 256 of its
    512 positions (a prompt is at least 256 long).

    ``ssm_scan`` (one Mamba layer's scan in the least prefill bucket, [1,
    256]): six operations a state element and position plus the exponential
    counted as one; as bytes dt, the input and the output in float32 and the
    B and C columns. Bound by bytes on paper; the kernel is bound by the
    vector unit's serial recurrence, so its share is low by construction."""
    if kernel not in _KERNELS:
        raise KeyError(f"phi4flash counts no kernel {kernel!r}; known: "
                       f"{sorted(_KERNELS)}")
    itemsize = jnp.dtype(cfg["torch_dtype"]).itemsize
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    slots = facts.get("max_num_seqs") or cfg["job"]["engine"]["max_num_seqs"]
    if kernel == "ssm_scan":
        s = ssm_sizes(cfg)
        rows = LEAST_BUCKET
        return (float(rows * s["inner"] * s["state"] * 7),
                float(rows * (3 * s["inner"] + 2 * s["state"]) * 4))
    least = LEAST_PROMPT if kernel == "paged_gqa_decode" \
        else min(LEAST_PROMPT, cfg["sliding_window"])
    rows = slots * least
    return (float(rows * H * 2 * (hd + 2 * hd)),
            float(rows * 2 * KVH * hd * itemsize))
