"""Manifest AI's power-retention decoder as Brumby-14B-Base configures it, ONE
six-layer STAGE of a seven-stage pipeline with the first stage's table and the
last one's head: everything the benchmark knows about this architecture, in
the one module a configuration file names with ``"adapter": "brumby"``.

Written from the published configuration keys (``model_type: brumby``: every
key is the dense decoder's the model was retrained from), from the paper
(*Scaling Context Requires Rethinking Attention*, arXiv 2507.04239: power
attention, its gated recurrent form and the symmetric power) and from the
release note and the family's modelling code as the builder recalls them,
there being no network here; what the keys do not state is listed under the
configuration file's ``assumed``.

1. The plain float32 reference (``forward``, ``loss``), in the ATTENTION form::

     x = table[t]
     x = x + PR(RMSNorm(x))                   input_layernorm
     x = x + SwiGLU(RMSNorm(x))               post_attention_layernorm
     logits = RMSNorm(x_last) W_head          (untied)

   ``PR(u)`` (power retention, degree 2): ``q = RoPE(RMSNorm_hd(u W_q))`` [H,
   hd], ``k = RoPE(RMSNorm_hd(u W_k))`` [KVH, hd], ``v = u W_v`` [KVH, hd], no
   bias; ``log g = log sigmoid(u W_g + b_g)`` [KVH], one gate a key/value
   head; query head ``h`` reads key/value head ``h // (H / KVH)``; for ``j <=
   t`` the weight ``a[t, j] = (hd^-0.5 q_t . k_j)^2 exp(sum_{j < r <= t} log
   g_r)``; ``o_t = sum_j a[t, j] v_j / sum_j a[t, j]``; ``concat_h(o_t) W_o``.
   No softmax, no maximum, no window. The weights are formed ``[t, j]`` by
   ``[t, j]``, squared, decayed by a difference of cumulative sums and divided
   by their row sums, a block of query positions at a time: there is no
   feature map, no state and no chunk here, so the reference shares neither
   the recurrence nor the symmetric power with the system under test.
2. The way from the published keys to the program and to the reference
   (``program_overrides``, ``reference_cfg``, ``to_reference_params``).
3. Required operations per token and stored parameters.
4. Operations and bytes of one call of each kernel (``kernel_cost``).

Nothing here imports the program under test. ``cfg`` is a configuration
file's dict with the published key names; ``rcfg`` is ``reference_cfg(cfg)``.

Reference parameters are a plain dict: embed_tokens [V, d]; norm [d]; lm_head
[d, V]; layers: list of {input_layernorm, post_attention_layernorm [d], q_proj
[d, H hd], k_proj, v_proj [d, KVH hd], q_norm, k_norm [hd], g_proj [d, KVH],
g_bias [KVH], o_proj [H hd, d], gate_proj, up_proj [d, F], down_proj [F, d]}.
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


_QUERY_BLOCK = 512


def power_attention(q, k, v, log_g, without=()):
    """Gated power attention of degree 2, a head at a time and
    ``_QUERY_BLOCK`` queries at a time where that divides the length. q [B, S,
    H, hd]; k, v [B, S, KVH, hd]; log_g [B, S, KVH] (<= 0) -> [B, S, H hd].
    What ``without`` names is left out or done wrong (``reference_cfg``)."""
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    block = _QUERY_BLOCK if S % _QUERY_BLOCK == 0 else S
    at = jnp.arange(S)
    if "gate" in without:
        log_g = jnp.zeros_like(log_g)
    if "gate_twice" in without:
        log_g = 2.0 * log_g
    G = jnp.cumsum(log_g, axis=1)                 # through position t
    # the state a key meets: decayed from the position after it on (or, done
    # wrong, from its own position on)
    start = G - log_g if "gate_on_new" in without else G
    of = (jnp.arange(H) % KVH) if "grouped" in without \
        else jnp.arange(H) // (H // KVH)
    scale = hd ** -0.5

    def head(args):
        qh, kh, vh, Gh, Sh = args                 # [B, S, hd] x 3, [B, S] x 2

        def rows(first):
            qb = jax.lax.dynamic_slice_in_dim(qh, first, block, axis=1)
            Gb = jax.lax.dynamic_slice_in_dim(Gh, first, block, axis=1)
            dot = jnp.einsum("bqd,bsd->bqs", qb, kh) * scale
            if "degree" in without:
                a = dot
            elif "sqrt2" in without:   # the off-diagonal pairs counted once
                a = 0.5 * (dot * dot + jnp.einsum(
                    "bqd,bsd->bqs", qb * qb, kh * kh) * scale * scale)
            else:
                a = dot * dot
            seen = (first + jnp.arange(block))[:, None] >= at[None, :]
            decay = jnp.exp(jnp.minimum(Gb[:, :, None] - Sh[:, None, :], 0.0))
            a = jnp.where(seen, a, 0.0)
            num = jnp.einsum("bqs,bsd->bqd", a * decay, vh)
            if "normaliser" in without:
                return num
            den = a if "normaliser_decay" in without else a * decay
            return num / jnp.sum(den, axis=-1, keepdims=True)

        out = jax.lax.map(rows, jnp.arange(0, S, block))  # [S / block, B, ..]
        return jnp.moveaxis(out, 0, 1).reshape(B, S, -1)

    heads = lambda t: jnp.moveaxis(t, 2, 0)   # noqa: E731
    out = jax.lax.map(head, (heads(q), heads(k)[of], heads(v)[of],
                             heads(G)[of], heads(start)[of]))
    return jnp.moveaxis(out, 0, 2).reshape(B, S, -1)


def retention(h, lp, rcfg):
    """A power-retention mixer on normalised ``h`` [B, S, d] -> [B, S, d]."""
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    without, eps = rcfg["without"], rcfg["rms_norm_eps"]
    B, S, _ = h.shape
    H, KVH = rcfg["num_attention_heads"], rcfg["num_key_value_heads"]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    q = (h @ f32(lp["q_proj"])).reshape(B, S, H, -1)
    k = (h @ f32(lp["k_proj"])).reshape(B, S, KVH, -1)
    v = (h @ f32(lp["v_proj"])).reshape(B, S, KVH, -1)

    def turned(t):
        return t if "rotation" in without else rope(t, positions,
                                                    rcfg["rope_theta"])

    def normed(t, scale):
        return t if "head_norms" in without else rms_norm(t, f32(scale), eps)

    if "norm_order" in without:   # the norms AFTER the rotation
        q, k = normed(turned(q), lp["q_norm"]), normed(turned(k), lp["k_norm"])
    else:
        q, k = turned(normed(q, lp["q_norm"])), turned(normed(k, lp["k_norm"]))
    log_g = jax.nn.log_sigmoid(h @ f32(lp["g_proj"]) + f32(lp["g_bias"]))
    return power_attention(q, k, v, log_g, without) @ f32(lp["o_proj"])


_HEAD_BLOCKS = 16


def head(x, w):
    """x [..., d] @ w [d, V] in float32, in blocks of the vocabulary where it
    divides."""
    d, V = w.shape
    if V % _HEAD_BLOCKS:
        return x @ w.astype(jnp.float32)
    width = V // _HEAD_BLOCKS   # a block is sliced where it lies: at 151,936
    # columns a copy of the head by blocks would be 1.6 GB beside the engine's

    def block(i):
        return x @ jax.lax.dynamic_slice_in_dim(
            w, i * width, width, axis=1).astype(jnp.float32)

    out = jax.lax.map(block, jnp.arange(_HEAD_BLOCKS))
    return jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], V)


def forward(params, tokens, rcfg, last: Optional[int] = None):
    """tokens [B, S] int -> logits [B, S, V], float32 throughout; with
    ``last`` only those of the last ``last`` positions (every position is
    still computed through every layer)."""
    eps = rcfg["rms_norm_eps"]
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for lp in params["layers"]:
        x = x + retention(rms_norm(x, lp["input_layernorm"], eps), lp, rcfg)
        x = x + swiglu(rms_norm(x, lp["post_attention_layernorm"], eps),
                       lp["gate_proj"], lp["up_proj"], lp["down_proj"])
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
# nothing else runs under the model's name. ``attention_bias`` is the bias of
# q, k, v and o; the gate's own bias is under ``assumed`` and is not that key
_ONLY = (("model_type", "brumby"), ("hidden_act", "silu"),
         ("rope_scaling", None), ("use_sliding_window", False),
         ("sliding_window", None), ("attention_bias", False),
         ("tie_word_embeddings", False))

# the parts of the mathematics a spoiled reference leaves out or does wrong
# (``reference_cfg(cfg)["without"]``): the tests' and the chip test's table
WITHOUT = ("degree", "sqrt2", "gate", "gate_twice", "gate_on_new",
           "normaliser", "normaliser_decay", "grouped", "rotation",
           "norm_order", "head_norms")


def _refuse(cfg: dict) -> None:
    name = cfg.get("name")
    for key, must in _ONLY:
        if cfg[key] != must:
            raise ValueError(f"{name}: {key} = {cfg[key]!r}; the program "
                             f"expresses only {must!r}")
    if cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        raise ValueError(f"{name}: {cfg['num_attention_heads']} query heads "
                         f"do not share {cfg['num_key_value_heads']} states")


def program_overrides(cfg: dict, max_seq_len: int) -> dict:
    """Published keys -> the program's ``TransformerConfig`` fields."""
    _refuse(cfg)
    init = cfg["initializer"]
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                head_size=cfg["head_dim"], d_ff=cfg["intermediate_size"],
                max_seq_len=max_seq_len, rope_theta=float(cfg["rope_theta"]),
                tie_embeddings=False, remat=True,
                norm_eps=float(cfg["rms_norm_eps"]),
                layer_kinds=("retention",) * cfg["num_hidden_layers"],
                block="rms", rope_kinds=("retention",), qk_head_norm=True,
                retention_degree=2,
                retention_gate_init=tuple(map(float, init["gate_bias"])),
                retention_norm_init_std=float(init["head_norms"]),
                attn_init_std=float(init["retention"]),
                mlp_init_std=float(init["mlp"]),
                embed_init_std=float(init["embedding"]),
                param_dtype=getattr(jnp, cfg["torch_dtype"]))


def reference_cfg(cfg: dict) -> dict:
    """What the plain reference needs: the published keys and what the
    tests' spoiled references get wrong (``without``, of ``WITHOUT``)."""
    _refuse(cfg)
    out = {k: cfg[k] for k in ("num_attention_heads", "num_key_value_heads",
                               "rope_theta", "rms_norm_eps")}
    out["without"] = ()
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
        m, o = lp["retention"], lp["retention"]["o_proj"]["kernel"]
        layer = {"input_layernorm": lp["attn_norm"]["scale"],
                 "post_attention_layernorm": lp["mlp_norm"]["scale"],
                 "q_norm": m["q_norm"]["scale"], "k_norm": m["k_norm"]["scale"],
                 "g_proj": m["g_proj"]["kernel"], "g_bias": m["g_proj"]["bias"],
                 "o_proj": o.reshape(-1, o.shape[-1])}
        layer.update({n: flat_in(m[n]["kernel"])
                      for n in ("q_proj", "k_proj", "v_proj")})
        layer.update({n: lp["mlp"][n]["kernel"]
                      for n in ("gate_proj", "up_proj", "down_proj")})
        layers.append(layer)
    return {"embed_tokens": p["embed"], "norm": p["final_norm"]["scale"],
            "lm_head": p["lm_head"], "layers": layers}


# -- 3. required operations and stored parameters, from the shapes ---------------


def state_floats(cfg: dict) -> int:
    """A slot's state in one layer, EXACT: per key/value head the symmetric
    square's ``hd (hd + 1) / 2`` rows of ``hd`` values and one of the
    normaliser (8 x 8256 x 129 = 34.08 MB in float32 at the published
    widths), whatever layout a program keeps it in."""
    hd = cfg["head_dim"]
    return cfg["num_key_value_heads"] * (hd * (hd + 1) // 2) * (hd + 1)


def _mixer_params(cfg: dict, matrices_only: bool) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    mats = 2 * d * H * hd + 2 * d * KVH * hd + d * KVH
    # the gate's bias and the two head norms
    return mats if matrices_only else mats + KVH + 2 * hd


def active_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by in this stage's layers and the head."""
    d = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * (
        _mixer_params(cfg, True) + 3 * d * cfg["intermediate_size"]) \
        + d * cfg["vocab_size"]


def retention_flops_per_token(cfg: dict) -> float:
    """A layer's recurrence for one position in its recurrent form: every
    query head reads a state (2 D (hd + 1)), every key/value head writes one
    (2 D hd)."""
    hd = cfg["head_dim"]
    D = hd * (hd + 1) // 2
    return float(cfg["num_attention_heads"] * 2 * D * (hd + 1)
                 + cfg["num_key_value_heads"] * 2 * D * hd)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward, the recurrence in its recurrent form (which
    does not grow with ``seq_len``)."""
    return 3.0 * (2.0 * active_matmul_params(cfg)
                  + cfg["num_hidden_layers"] * retention_flops_per_token(cfg))


def total_params(cfg: dict) -> int:
    """Every parameter stored here: the layers, two norms each, the final
    norm, table and head."""
    d = cfg["hidden_size"]
    layer = _mixer_params(cfg, False) + 3 * d * cfg["intermediate_size"] + 2 * d
    return cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * d + d


# -- 4. one call of a kernel: operations and bytes ---------------------------------

# the least prefill bucket the mix reaches: the one row retention_scan is
# counted at (a longer one takes longer and is counted the same: the share
# errs low by bucket / 256)
LEAST_BUCKET = 256
_KERNELS = ("retention_scan", "retention_step")


def kernel_cost(kernel: str, cfg: dict, facts: dict) -> Tuple[float, float]:
    """(operations, bytes) that ONE call of a kernel needs, whatever
    implements it, in the stored type.

    ``retention_step`` (one layer's decode step for every slot): per state
    element the decay, the update's multiply-add and a multiply-add for each
    of the ``H / KVH`` query heads that read it (3 + 2 x 5 = 13), and as bytes
    the EXACT float32 state (``state_floats``) of max_num_seqs slots READ AND
    WRITTEN plus a slot's operands (q, k, v in and o out in the stored type,
    the gates float32). Bound by bytes: 2.18 GB, 2.66 ms a layer at 32 slots.
    A layout that moves more (the program's rotated one: 1.5%) reads under
    100% for it, which is the truth.

    ``retention_scan`` (one layer's recurrence over ONE row of the least
    bucket the mix reaches, 256 positions), counted as the LEAST work any
    evaluation needs there, so that no honest kernel reads over 100%: the
    squared weights over the causal pairs (score and value product, H x 2 x 2
    hd x S (S + 1) / 2 = 0.67 GFLOP) plus building the final state, which
    decode needs whatever prefill did (KVH x 2 D (hd + 1) x S = 4.36 GFLOP),
    NOT the recurrent form's 26 GFLOP; as bytes q, k, v and the gates in, o out
    and the final state out (34 MB of 40). Bound by bytes: 49 us against 26.
    Whatever bucket a traced call ran at is counted as this one, so the share
    errs low."""
    if kernel not in _KERNELS:
        raise KeyError(f"brumby counts no kernel {kernel!r}; known: "
                       f"{sorted(_KERNELS)}")
    itemsize = jnp.dtype(cfg["torch_dtype"]).itemsize
    slots = facts.get("max_num_seqs") or cfg["job"]["engine"]["max_num_seqs"]
    H, KVH, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    state = state_floats(cfg)
    # a position's operands: q, k, v in and o out stored, the gates float32
    operands = 2 * (H + KVH) * hd * itemsize + 4 * KVH
    if kernel == "retention_step":
        return (float((3 + 2 * H // KVH) * slots * state),
                float(slots * (8 * state + operands)))
    S = LEAST_BUCKET
    return (float(H * 4 * hd * S * (S + 1) // 2 + 2 * state * S),
            float(S * operands + 4 * state))
