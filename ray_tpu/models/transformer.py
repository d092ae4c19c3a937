"""Flagship decoder-only transformer LM (llama-family), TPU-first.

This is the model the framework's north-star path trains (SURVEY.md §3.4):
GSPMD-sharded via logical axis annotations so one definition serves DP, FSDP,
TP, and SP meshes (reference capability: Ray delegates model parallelism to
torch; here it is native — flax linen + ``nn.with_logical_partitioning``).

Design notes for the MXU:
- all matmuls are bf16 with fp32 accumulation (``preferred_element_type``);
- weights are stored fp32 (master) and cast to the compute dtype per step;
- attention goes through ``ray_tpu.ops.attention`` (pallas flash kernel on
  TPU, pure-jax fallback elsewhere);
- remat policy checkpoints per block to trade FLOPs for HBM.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.attention import attention as attention_op

# Logical axis names used across the parallel layer (see
# ray_tpu/parallel/mesh.py for the logical->mesh rules).
BATCH = "batch"
SEQ = "seq"
EMBED = "embed"
HEADS = "heads"
KV_HEADS = "kv_heads"
HEAD_DIM = "head_dim"
MLP = "mlp"
VOCAB = "vocab"

# the sigmoid router's selection bias is drawn, not zero: at this scale it
# changes which experts a token gets for about half the tokens (53% at 64
# experts, top-6, hidden 2048: tests/test_latent_moe.py counts them), so a
# program that drops the bias cannot pass a comparison, and the experts' loads
# stay within 0.6-1.6 x their mean
ROUTER_BIAS_STD = 0.02


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 1408
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    attention_impl: str = "auto"  # auto | flash | xla
    # mixture-of-experts (0 experts = dense MLP); experts shard over the
    # mesh "expert" axis (EP) and tokens reach them via the one-hot
    # dispatch einsums XLA lowers to all-to-alls (GShard style)
    n_experts: int = 0
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    moe_every: int = 1  # every Nth block uses MoE (others stay dense)
    # True: a token's top-k router weights are renormalised to sum to one
    norm_topk_prob: bool = True
    tie_embeddings: bool = False
    norm_eps: float = 1e-6  # every RMSNorm, here and in llm/model_runner.py
    # RMSNorm over the whole q and k projections (all heads together),
    # before the split into heads and RoPE
    qk_norm: bool = False
    # latent attention (MLA; 0 = per-head K/V projections): keys and values
    # come from ONE kv_latent_rank-wide normalised latent a position, through
    # an up-projection per head to qk_nope_head_dim of key and v_head_dim of
    # value; a qk_rope_head_dim-wide rotated part, one for all heads on the
    # key side, is appended to q and k. The serving cache holds the latent
    # and the rotated key, not the heads (llm/model_runner.py).
    kv_latent_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the first layers that stay dense in a model with experts, and the
    # width of a dense layer where it is not the experts' d_ff (0 = d_ff)
    first_k_dense: int = 0
    d_ff_dense: int = 0
    # experts every token goes through beside its routed ones: one SwiGLU of
    # width n_shared_experts * d_ff
    n_shared_experts: int = 0
    # "softmax": top-k of the softmax. "sigmoid": top-k of sigmoid scores plus
    # a per-expert selection bias (a parameter) that chooses and does not
    # weigh; the chosen scores, renormalised with norm_topk_prob, times
    # routed_scaling_factor (ops/moe.py:select_experts). ``router_norm_eps`` is
    # what the sigmoid kind adds to the chosen scores' sum before it divides
    router_kind: str = "softmax"
    routed_scaling_factor: float = 1.0
    router_norm_eps: float = 1e-20
    # the standard deviations the attention's projections and the MLPs' and
    # experts' matrices are drawn at; 0 = 0.02 / sqrt(2 layers). What each
    # part adds to the residual stream goes with its fourth and third power,
    # so seeded weights that are to show every part of a model in its logits
    # (a comparison with a reference) state them
    attn_init_std: float = 0.0
    mlp_init_std: float = 0.0
    # a decoder-hybrid-decoder (SambaY): each layer's mixer by kind, "mamba"
    # (a Mamba-1 selective scan), "window" / "full" (differential attention
    # over the ``window`` newest positions / over all), "gmu" (a gated memory
    # unit: it gates the scan output of the LAST "mamba" layer at the same
    # position) or "cross" (a query projection only: differential attention
    # over the keys and values the "full" layer made). () = attention with
    # rotary embedding in every layer, as above. A kind names a layer's MIXER
    # and the state it keeps in serving ("window": a ring of ``window``
    # positions a slot; "full": pages), and nothing more: what else a block
    # is, ``block`` says. "sambay": LayerNorm with bias for RMSNorm, no
    # position embedding, differential heads, biases on the attention
    # projections and a tied head (``HybridBlock``). "rms": the RMSNorm
    # block of every model without kinds (``Block``), plain grouped-query
    # heads under the causal mask ("full") or the window's ("window"); only
    # "window", "full", "conv", "mamba", "mamba2", "kda", "retention" and
    # "latent" are kinds of such a block. "mamba" there is the same Mamba-1
    # selective scan as a mixer of its own (``Mamba``: ``ssm_inner`` channels,
    # a state [ssm_inner, ssm_state], ``ssm_conv`` taps, step sizes through a
    # rank of ``ssm_dt_rank``, and under ``ssm_inner_norms`` an RMSNorm on
    # each of the step size's low-rank input, B and C). "conv" is
    # a gated short convolution (``ShortConv``): no attention, no position
    # embedding, and in serving ``conv_taps - 1`` rows a slot in place of pages
    # or a ring. "mamba2" is a Mamba-2 mixer (``Mamba2``): ``ssm_heads`` heads
    # of ``ssm_inner / ssm_heads`` channels, a matrix state [head size,
    # ssm_state] a head, ``ssm_conv`` taps; in serving the state and the
    # convolution's last ``ssm_conv - 1`` inputs a slot. "kda" is a delta-rule
    # linear-attention mixer with a decay per key lane (``KDA``): ``kda_heads``
    # heads of ``kda_head_dim`` key and value lanes, a matrix state [head
    # dim, head dim] a head, ``kda_conv`` taps on each of q, k and v; in
    # serving the state and the three convolutions' last ``kda_conv - 1``
    # inputs a slot. "retention" is a power-retention mixer (``Retention``:
    # gated power attention of degree ``retention_degree``): ``n_heads`` query
    # heads over ``n_kv_heads`` states of ``head_dim`` key and value lanes,
    # each state the symmetric power of its keys against their values, float32,
    # with its normaliser; q and k take the per-head norm (``qk_head_norm``)
    # and, where ``rope_kinds`` names the kind, the rotation, as an attention
    # layer's do; in serving the state a slot, and nothing by position: a
    # model of such layers alone has no page. "latent" is latent attention
    # (``kv_latent_rank``) as ONE kind among others: one row a position in
    # each such layer, rotated only where ``rope_kinds`` names it.
    layer_kinds: Tuple[str, ...] = ()
    block: str = "sambay"
    window: int = 0
    ssm_inner: int = 0      # width of the scan and of the gated memory
    ssm_state: int = 16
    ssm_conv: int = 4       # taps of the causal depthwise convolution
    ssm_dt_rank: int = 0
    # a "mamba" layer of an "rms" block normalises what ``x_proj`` gives: the
    # step size's low-rank input, B and C each under an RMSNorm with a learned
    # scale (Jamba's three inner norms)
    ssm_inner_norms: bool = False
    # heads of a "mamba2" layer (0: the model has none); B and C are shared by
    # all of them (one group)
    ssm_heads: int = 0
    # a "kda" layer's heads (0: the model has none), the key and value lanes
    # of one, the taps of its three causal depthwise convolutions, the inner
    # width of its two low-rank gates (the decay's and the output's), and what
    # its projections are drawn at (0 = the attention's)
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_gate_rank: int = 0
    kda_init_std: float = 0.0
    # the degree of a "retention" layer's power attention (0: the model has
    # none; 2: the symmetric square, the only one implemented), the mean and
    # the deviation its gate's bias is drawn at and the deviation of the
    # gate's matrix (0 = the attention's; a sigmoid gate centred on 0 forgets
    # half a state every position: seeded weights that are to show a state's
    # carry state a bias, and a matrix that does not drown it) and the
    # deviation its two head norms' scales are drawn at around 1 (0: ones; the
    # norms and the rotation commute while a scale is uniform)
    retention_degree: int = 0
    retention_gate_init: Tuple[float, float, float] = (0.0, 0.0, 0.0)
    retention_norm_init_std: float = 0.0
    # taps a channel of a "conv" layer's causal depthwise convolution (0: the
    # model has no such layer), and what its in_proj and out_proj are drawn
    # at (0 = the attention's): its output goes with their FOURTH power, the
    # attention's with the second, so one deviation cannot size both
    conv_taps: int = 0
    conv_init_std: float = 0.0
    # what a Mamba layer's and a memory unit's in_proj and out_proj, and the
    # scan's x_proj (dt | B | C), are drawn at: with seeded weights the state
    # shows in the output only where B and C are of order one, which one
    # deviation for all of them cannot give
    ssm_proj_init_std: float = 0.0
    ssm_x_init_std: float = 0.0
    # the table's (0 = 0.02). Under a pre-norm a layer's gain on the stream
    # goes with its output over the stream's size: seeded layers that are to
    # show in the logits without amplifying each other's rounding need a
    # stream that starts out as large as what they add
    embed_init_std: float = 0.0
    # head geometry: the width of a head where it is not d_model // n_heads
    # (q and o are then d_model x n_heads * head_size)
    head_size: int = 0
    # RMSNorm over head_dim of every q and k head (one scale for all heads),
    # before RoPE; ``qk_norm`` above is over the whole projection instead
    qk_head_norm: bool = False
    # a sigmoid gate on the attention's output, head by head and lane by
    # lane, from the layer's own input: o_proj((attention) * sigmoid(h W_g))
    attn_gate: bool = False
    # four norms a block: each of attention and MLP is normalised going in
    # AND coming out, x + norm(f(norm(x)))
    sandwich_norm: bool = False
    # the table's rows times this (a muP model: sqrt(d_model))
    embed_scale: float = 1.0
    # the kinds of layer that rotate q and k; a kind left out has no position
    # embedding at all. Without ``layer_kinds`` every layer rotates
    rope_kinds: Tuple[str, ...] = ("window", "full")
    # one rank of an expert-parallel deployment: (first, count), the routed
    # experts this program holds. The router keeps its ``n_experts`` outputs
    # and top-k; the expert matrices are ``count`` deep and an assignment to
    # an expert held elsewhere adds nothing here. () = all of them
    experts_held: Tuple[int, ...] = ()
    # what the routed experts' matrices are drawn at where it is not the
    # MLPs' (0 = mlp_init_std): the shared expert stays at the MLPs'
    expert_init_std: float = 0.0
    # what a sublayer's output is multiplied by on its way into the stream,
    # the softmax scale where it is not 1 / sqrt(head_dim) (0 = that), and
    # what the logits are multiplied by
    residual_scale: float = 1.0
    attn_scale: float = 0.0
    logit_scale: float = 1.0
    # low-rank queries of latent attention (0 = one full-rank q_proj): the
    # layer's input goes down to ``q_latent_rank`` (``q_a_proj``), under an
    # RMSNorm (``q_a_norm``) and up to the heads (``q_b_proj``)
    q_latent_rank: int = 0
    # the two latents scaled on their way up: the normalised query latent's
    # product by sqrt(d_model / q_latent_rank), the normalised key-value
    # latent (both halves of kv_b_proj read it; the rotated key does not) by
    # sqrt(d_model / kv_latent_rank). Both follow from the widths
    latent_lora_scale: bool = False
    # the router's LAST ``zero_experts`` outputs are no expert but the
    # identity: ``n_experts`` routed ones and these share one softmax and one
    # top-k, and a row that chose one gets ``gate * x`` for it, on every rank
    # of an expert-parallel deployment alike (ops/moe.py). ``router_bias``: a
    # softmax router with a per-output selection bias (a parameter, as the
    # sigmoid kind always has) that chooses and does not weigh
    zero_experts: int = 0
    router_bias: bool = False
    # shortcut-connected double layers: layers come in PAIRS (``n_layers``
    # counts the sublayers, each with its own mixer, dense MLP and two
    # norms). The even one also holds the pair's ONE expert branch (``mlp``
    # AND ``moe``): computed from the normed input its dense MLP takes,
    # carried past the odd one's mixer and MLP, added where the odd one ends
    shortcut_moe: bool = False
    # what the router's matrix and its selection bias are drawn at (0 = 0.02
    # and ROUTER_BIAS_STD): a softmax over hundreds of outputs is flat at
    # 0.02, and a bias of 0.02 would then choose for every row alike
    router_init_std: float = 0.0
    router_bias_init_std: float = 0.0

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_heads

    @property
    def sambay(self) -> bool:
        """Layers by kind in a decoder-hybrid-decoder's block."""
        return bool(self.layer_kinds) and self.block == "sambay"

    @property
    def n_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.n_experts

    def layer_kind(self, i: int) -> str:
        return self.layer_kinds[i] if self.layer_kinds else "full"

    def init_std(self, part: str) -> float:
        """``part``: "attn", "conv", "kda", "mlp", "expert", "ssm_proj",
        "ssm_x" or "embed"."""
        if part == "expert" and not self.expert_init_std:
            part = "mlp"
        if part in ("conv", "kda") and not getattr(self, part + "_init_std"):
            part = "attn"
        return getattr(self, part + "_init_std") or (
            0.02 if part == "embed" else 0.02 / np.sqrt(2 * self.n_layers))

    @property
    def has_router_bias(self) -> bool:
        return self.router_kind == "sigmoid" or self.router_bias

    def is_moe_layer(self, i: int) -> bool:
        """Does layer ``i`` hold experts (under ``shortcut_moe``: beside its
        dense MLP, the even sublayer of each pair)?"""
        every = 2 if self.shortcut_moe else max(self.moe_every, 1)
        return (self.n_experts > 0 and i >= self.first_k_dense
                and i % every == 0)

    def num_params(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        if self.sambay:
            inner, hd = self.ssm_inner, self.head_dim
            qkv = (self.n_heads + 2 * self.n_kv_heads) * hd
            attn = d * d + d + 6 * hd       # o and its bias, lambdas, sub-norm
            mixer = {
                "mamba": d * 2 * inner + inner * (
                    self.ssm_dt_rank + 2 * self.ssm_state)
                + self.ssm_dt_rank * inner + inner * d
                + inner * (self.ssm_conv + 3 + self.ssm_state),
                "gmu": 2 * d * inner,
                "window": (d + 1) * qkv + attn, "full": (d + 1) * qkv + attn,
                "cross": (d + 1) * d + attn}
            return v * d + 2 * d + sum(
                mixer[kind] + 3 * d * f + 4 * d for kind in self.layer_kinds)
        q = d * self.n_heads * self.head_dim
        attn = latent = (
            q * (3 if self.attn_gate else 2)  # q, o and the gate
            + 2 * d * (self.n_kv_heads * self.head_dim)  # k, v
            + (4 if self.sandwich_norm else 2) * d  # norms
            + ((self.n_heads + self.n_kv_heads) * self.head_dim
               if self.qk_norm else 0)
            + (2 * self.head_dim if self.qk_head_norm else 0)
        )
        if self.kv_latent_rank:
            r, rope = self.kv_latent_rank, self.qk_rope_head_dim
            latent = (
                (d * self.n_heads * (self.qk_nope_head_dim + rope)
                 if not self.q_latent_rank else  # q, or its low-rank pair
                 self.q_latent_rank * (d + 1 + self.n_heads * (
                     self.qk_nope_head_dim + rope)))
                + d * (r + rope) + r  # latent down-projection and its norm
                + r * self.n_heads * (self.qk_nope_head_dim + self.v_head_dim)
                + self.n_heads * self.v_head_dim * d  # o
                + 2 * d  # norms
            )
            if not self.layer_kinds:  # every layer is one
                attn = latent
        # in_proj (B | C | z), out_proj, the taps and the block's two norms
        conv = 4 * d * d + self.conv_taps * d + 2 * d
        # in_proj (z | xBC | dt), out_proj, the taps and their bias, dt_bias,
        # A_log and D a head, the gated norm and the block's two norms
        inner, xbc = self.ssm_inner, self.ssm_inner + 2 * self.ssm_state
        mamba2 = (d * (inner + xbc + self.ssm_heads) + inner * d
                  + xbc * (self.ssm_conv + 1) + 3 * self.ssm_heads + inner
                  + 2 * d)
        # in_proj (u | z), x_proj (dt | B | C), dt_proj with its bias,
        # out_proj, the taps and their bias, A_log, D, the three inner norms
        # where the model has them, and the block's two norms
        R, N = self.ssm_dt_rank, self.ssm_state
        mamba = (d * 2 * inner + inner * (R + 2 * N) + (R + 1) * inner
                 + inner * d + inner * (self.ssm_conv + 1 + N + 1)
                 + (R + 2 * N) * self.ssm_inner_norms + 2 * d)
        # q | k | v and o, the three convolutions' taps, the decay's low-rank
        # pair with dt_bias and A_log, beta's projection, the output gate's
        # pair, the output norm and the block's two norms
        wide, r = self.kda_heads * self.kda_head_dim, self.kda_gate_rank
        kda = (4 * d * wide + 3 * wide * self.kda_conv
               + 2 * (d * r + r * wide) + wide + self.kda_heads
               + d * self.kda_heads + self.kda_head_dim + 2 * d)
        # q, o, k, v, the gate's projection with its bias (one gate a state),
        # the two head norms and the block's two norms
        kv = self.n_kv_heads * self.head_dim
        retention = (2 * q + 2 * d * kv + (d + 1) * self.n_kv_heads
                     + 2 * self.head_dim + 2 * d)
        mixer = {"conv": conv, "mamba": mamba, "mamba2": mamba2, "kda": kda,
                 "latent": latent, "retention": retention}
        dense_mlp = 3 * d * (self.d_ff_dense or f)
        outputs = self.n_experts + self.zero_experts
        moe_mlp = (self.n_experts_held * 3 * d * f + d * outputs
                   + 3 * d * self.n_shared_experts * f
                   + (outputs if self.has_router_bias else 0))
        total = 0
        for i in range(self.n_layers):
            total += mixer.get(self.layer_kind(i), attn)
            if self.shortcut_moe:  # every sublayer's MLP, the even one's moe
                total += dense_mlp + moe_mlp * self.is_moe_layer(i)
            else:
                total += moe_mlp if self.is_moe_layer(i) else dense_mlp
        return v * d + total + d + (0 if self.tie_embeddings else d * v)


# preset configs (name -> config); "tiny" is the CI/test config
CONFIGS = {
    "tiny": TransformerConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                              n_kv_heads=2, d_ff=128, max_seq_len=128, remat=False),
    "125m": TransformerConfig(vocab_size=32000, d_model=768, n_layers=12, n_heads=12,
                              n_kv_heads=12, d_ff=2048, max_seq_len=2048),
    "350m": TransformerConfig(vocab_size=32000, d_model=1024, n_layers=24, n_heads=16,
                              n_kv_heads=16, d_ff=2816, max_seq_len=2048),
    "1b": TransformerConfig(vocab_size=32000, d_model=2048, n_layers=16, n_heads=16,
                            n_kv_heads=8, d_ff=5632, max_seq_len=2048),
    "7b": TransformerConfig(vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
                            n_kv_heads=32, d_ff=11008, max_seq_len=4096),
    "moe-tiny": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq_len=128, remat=False, n_experts=4,
        experts_per_token=2),
    "moe-1b": TransformerConfig(
        vocab_size=32000, d_model=1024, n_layers=16, n_heads=16, n_kv_heads=16,
        d_ff=2816, max_seq_len=2048, n_experts=8, experts_per_token=2,
        moe_every=2),
}


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding over the last dim (pairs)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[:, :, None, :]  # (B, S, 1, half)
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    axis: Optional[str] = "embed"  # logical axis of the scale
    init_std: float = 0.0  # the scale is drawn around 1 at this (0: ones)

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.ones if not self.init_std else (
            lambda *a: 1.0 + nn.initializers.normal(self.init_std)(*a))
        scale = self.param(
            "scale", nn.with_logical_partitioning(init, (self.axis,)),
            (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig
    kind: str = "full"  # "window": a query sees its cfg.window newest keys

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        B, S, _ = x.shape
        hd = cfg.head_dim
        dense = lambda feats, axes, name, axis=-1: nn.DenseGeneral(  # noqa: E731
            features=feats, axis=axis, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(cfg.init_std("attn")), axes),
        )
        if cfg.kv_latent_rank and (not cfg.layer_kinds
                                   or self.kind == "latent"):
            q, k, v = self._latent_qkv(x, positions, dense)
            out = attention_op(q, k, v, causal=True, impl=cfg.attention_impl,
                               segment_ids=segment_ids)
            return dense(cfg.d_model, ("heads", "head_dim", "embed"),
                         "o_proj", axis=(-2, -1))(out)
        q = dense((cfg.n_heads, hd), ("embed", "heads", "head_dim"), "q_proj")(x)
        k = dense((cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim"), "k_proj")(x)
        v = dense((cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim"), "v_proj")(x)
        if cfg.qk_norm:
            def whole(t, name):  # the norm sees all heads as one vector
                flat = t.reshape(B, S, -1)
                return RMSNorm(cfg.norm_eps, cfg.dtype, axis=None,
                               name=name)(flat).reshape(t.shape)
            q, k = whole(q, "q_norm"), whole(k, "k_norm")
        if cfg.qk_head_norm:  # head by head, one scale for all of them
            q = RMSNorm(cfg.norm_eps, cfg.dtype, axis=None, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, cfg.dtype, axis=None, name="k_norm")(k)
        if not cfg.layer_kinds or self.kind in cfg.rope_kinds:
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        if cfg.n_kv_heads != cfg.n_heads:
            rep = cfg.n_heads // cfg.n_kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        if cfg.attn_scale:  # the kernels divide by sqrt(head_dim)
            q = q * jnp.asarray(cfg.attn_scale * hd ** 0.5, q.dtype)
        out = attention_op(q, k, v, causal=True, impl=cfg.attention_impl,
                           segment_ids=segment_ids,
                           window=cfg.window if self.kind == "window" else 0)
        if cfg.attn_gate:
            out = out * nn.sigmoid(dense(
                (cfg.n_heads, hd), ("embed", "heads", "head_dim"),
                "gate_proj")(x))
        out = nn.DenseGeneral(
            features=cfg.d_model, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="o_proj",
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(cfg.init_std("attn")),
                ("heads", "head_dim", "embed")),
        )(out)
        return out

    def _latent_qkv(self, x, positions, dense):
        """Latent attention, expanded to heads (the serving engine's prefill
        computes the same; its decode absorbs ``kv_b_proj`` into q and the
        output instead, ``llm/model_runner.py``): q [B, S, H, nope + rope], k
        the same width (the rotated part one for all heads; as a kind of a
        model with ``layer_kinds`` rotated only where ``rope_kinds`` names
        "latent", else plain lanes), v [B, S, H, v_head_dim]."""
        cfg = self.cfg
        H, r = cfg.n_heads, cfg.kv_latent_rank
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        if cfg.q_latent_rank:  # low-rank queries
            cq = RMSNorm(cfg.norm_eps, cfg.dtype, axis=None, name="q_a_norm")(
                dense(cfg.q_latent_rank, ("embed", None), "q_a_proj")(x))
            q = dense((H, nope + rope), (None, "heads", "head_dim"),
                      "q_b_proj")(cq)
        else:
            q = dense((H, nope + rope), ("embed", "heads", "head_dim"),
                      "q_proj")(x)
        a = dense(r + rope, ("embed", None), "kv_a_proj")(x)
        c = RMSNorm(cfg.norm_eps, cfg.dtype, axis=None,
                    name="kv_a_norm")(a[..., :r])
        if cfg.latent_lora_scale:  # in float32: sqrt(12) is no bfloat16
            s_q, s_kv = latent_scales(cfg)
            q = (q.astype(jnp.float32) * s_q).astype(q.dtype)
            c = (c.astype(jnp.float32) * s_kv).astype(c.dtype)
        kv = dense((H, nope + cfg.v_head_dim), (None, "heads", "head_dim"),
                   "kv_b_proj")(c)
        q_pe, k_pe = q[..., nope:], a[..., None, r:]
        if not cfg.layer_kinds or "latent" in cfg.rope_kinds:
            q_pe = _rope(q_pe, positions, cfg.rope_theta)
            k_pe = _rope(k_pe, positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, q_pe.shape)], axis=-1)
        return q, k, kv[..., nope:]


def latent_scales(cfg: TransformerConfig) -> Tuple[float, float]:
    """``latent_lora_scale``'s two factors: what the queries (the normalised
    query latent's product) and the normalised key-value latent are
    multiplied by; 1.0 for queries of full rank."""
    d = cfg.d_model
    return ((d / cfg.q_latent_rank) ** 0.5 if cfg.q_latent_rank else 1.0,
            (d / cfg.kv_latent_rank) ** 0.5)


class ShortConv(nn.Module):
    """A "conv" layer's mixer, a gated short convolution: ``B | C | z =
    in_proj(h)``, ``s = B * z``, a causal depthwise convolution of
    ``cfg.conv_taps`` taps a channel over ``s`` (zeros before the first
    position; tap ``conv_taps - 1`` is the position itself), ``out_proj(C *
    conv(s))``. No bias, no activation, no position embedding. The TRAINING
    side: the whole sequence at once (packed sequences are not kept apart:
    no ``segment_ids``); the serving engine keeps the last ``conv_taps - 1``
    rows of ``s`` a slot (``llm/model_runner.py``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        d = cfg.d_model
        dense = lambda feats, axes, name: nn.DenseGeneral(  # noqa: E731
            features=feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(cfg.init_std("conv")), axes))
        b, c, z = jnp.split(dense(3 * d, ("embed", "mlp"), "in_proj")(h), 3,
                            axis=-1)
        w = self.param("conv_kernel", nn.initializers.normal(
            cfg.conv_taps ** -0.5), (cfg.conv_taps, d), cfg.param_dtype)
        y = c * causal_conv(b * z, w.astype(cfg.dtype), 0)
        return dense(d, ("mlp", "embed"), "out_proj")(y)


class Mamba(nn.Module):
    """A "mamba" layer's mixer under an "rms" block, a Mamba-1 selective scan:
    ``u | z = in_proj(h)`` (each ``ssm_inner`` wide), ``a = silu(causal_conv(u)
    + conv_bias)``, ``r | B | C = x_proj(a)`` (widths ``ssm_dt_rank``,
    ``ssm_state``, ``ssm_state``; float32), under ``ssm_inner_norms`` each
    through its own RMSNorm (``dt_norm``, ``b_norm``, ``c_norm``), ``dt =
    softplus(dt_proj(r))`` with dt_proj's bias, the recurrence of
    ``ops/ssm.py`` with ``A = -exp(A_log)`` [inner, state], ``y = y + D a``,
    ``out_proj(y * silu(z))``. No bias but the convolution's and dt_proj's, no
    position embedding. The TRAINING side: the whole sequence at once through
    ``selective_scan_reference`` (packed sequences are not kept apart); the
    serving engine keeps the state and the last ``ssm_conv - 1`` rows of ``u``
    a slot (``llm/kinds/mamba.py``). The decoder-hybrid-decoder's
    ``HybridMixer`` holds the same arithmetic without the norms."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h):
        from ray_tpu.ops.ssm import selective_scan_reference

        cfg = self.cfg
        inner, N, R = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
        dense = lambda feats, name, std, dtype=None: nn.DenseGeneral(  # noqa: E731
            features=feats, use_bias=False, dtype=dtype or cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=nn.initializers.normal(std))
        uz = dense(2 * inner, "in_proj", cfg.init_std("ssm_proj"))(h)
        u, z = uz[..., :inner], uz[..., inner:]
        w = self.param("conv_kernel", nn.initializers.normal(
            cfg.ssm_conv ** -0.5), (cfg.ssm_conv, inner), cfg.param_dtype)
        b = self.param("conv_bias", nn.initializers.normal(CONV_BIAS_STD),
                       (inner,), cfg.param_dtype)
        a = nn.silu(causal_conv(u, w.astype(cfg.dtype), b.astype(cfg.dtype)))
        x = dense(R + 2 * N, "x_proj", cfg.init_std("ssm_x"), jnp.float32)(a)
        r, Bm, Cm = x[..., :R], x[..., R:R + N], x[..., R + N:]
        if cfg.ssm_inner_norms:
            r, Bm, Cm = (
                RMSNorm(cfg.norm_eps, jnp.float32, axis=None, name=n)(t)
                for t, n in ((r, "dt_norm"), (Bm, "b_norm"), (Cm, "c_norm")))
        dt = jax.nn.softplus(nn.DenseGeneral(
            inner, dtype=jnp.float32, param_dtype=jnp.float32, name="dt_proj",
            kernel_init=nn.initializers.normal(R ** -0.5),
            bias_init=_dt_bias_init)(r))
        A_log = self.param(
            "A_log", lambda *_: jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=jnp.float32)), (inner, N)))
        D = self.param("D", nn.initializers.ones, (inner,), jnp.float32)
        y, _ = selective_scan_reference(
            dt, a, Bm, Cm, -jnp.exp(A_log),
            jnp.zeros((h.shape[0], inner, N), jnp.float32))
        y = y + D * a.astype(jnp.float32)
        return dense(cfg.d_model, "out_proj", cfg.init_std("ssm_proj"))(
            y.astype(cfg.dtype) * nn.silu(z))


class Mamba2(nn.Module):
    """A "mamba2" layer's mixer: ``z | xBC | dt = in_proj(h)`` (widths
    ``ssm_inner``, ``ssm_inner + 2 ssm_state``, ``ssm_heads``), ``xBC =
    silu(causal_conv(xBC) + conv_bias)`` split into ``x | B | C``, ``dt =
    softplus(dt + dt_bias)``, the recurrence of ``ops/ssd.py`` with ``A =
    -exp(A_log)`` a head, ``y = y + D x`` a head, the gated norm ``RMSNorm(y *
    silu(z))`` over all ``ssm_inner`` channels, ``out_proj``. No bias but the
    convolution's, no position embedding. The TRAINING side: the whole
    sequence at once through ``ssd_reference`` (packed sequences are not kept
    apart); the serving engine keeps the state and the last ``ssm_conv - 1``
    rows of the pre-convolution ``xBC`` a slot (``llm/model_runner.py``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h):
        from ray_tpu.ops.ssd import ssd_reference

        cfg = self.cfg
        inner, N, H = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads
        dense = lambda feats, axes, name: nn.DenseGeneral(  # noqa: E731
            features=feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(cfg.init_std("ssm_proj")), axes))
        zxd = dense(2 * inner + 2 * N + H, ("embed", "mlp"), "in_proj")(h)
        z, xbc, dt = jnp.split(zxd, [inner, 2 * inner + 2 * N], axis=-1)
        w = self.param("conv_kernel", nn.initializers.normal(
            cfg.ssm_conv ** -0.5), (cfg.ssm_conv, inner + 2 * N),
            cfg.param_dtype)
        b = self.param("conv_bias", nn.initializers.normal(CONV_BIAS_STD),
                       (inner + 2 * N,), cfg.param_dtype)
        xbc = nn.silu(causal_conv(xbc, w.astype(cfg.dtype),
                                  b.astype(cfg.dtype)))
        x, Bm, Cm = jnp.split(xbc, [inner, inner + N], axis=-1)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), jnp.float32)
        A_log = self.param("A_log", lambda key, shape: jnp.log(
            jax.random.uniform(key, shape, minval=1.0, maxval=16.0)), (H,))
        D = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        y, _ = ssd_reference(
            jax.nn.softplus(dt.astype(jnp.float32) + dt_bias), x, Bm, Cm,
            -jnp.exp(A_log))
        y = y + jnp.repeat(D, inner // H) * x.astype(jnp.float32)
        y = RMSNorm(cfg.norm_eps, cfg.dtype, axis=None, name="norm")(
            y * nn.silu(z.astype(jnp.float32)))
        return dense(cfg.d_model, ("mlp", "embed"), "out_proj")(y)


class KDA(nn.Module):
    """A "kda" layer's mixer, delta-rule linear attention with a decay per
    key lane (Kimi Linear): ``q | k | v = qkv_proj(h)``, each through its own
    causal depthwise convolution of ``kda_conv`` taps (zeros before the first
    position, no bias) and a silu; ``q = q / |q| * head_dim^-0.5`` and ``k = k
    / |k|`` a head; the log-decay ``g = -exp(A_log[head]) * softplus(f_b(f_a(h))
    + dt_bias)`` a head and key lane; ``beta = sigmoid(b_proj(h))`` a head;
    the recurrence of ``ops/kda.py``; ``RMSNorm(o) * sigmoid(g_b(g_a(h)))``
    over each head's lanes (one scale for all heads), ``o_proj``. No bias, no
    position embedding. The TRAINING side: the whole sequence at once through
    ``kda_reference`` (packed sequences are not kept apart); the serving engine
    keeps the state and the last ``kda_conv - 1`` rows of the pre-convolution
    ``q | k | v`` a slot (``llm/model_runner.py``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h):
        from ray_tpu.ops.kda import kda_reference

        cfg = self.cfg
        H, K, r = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_gate_rank
        dense = lambda feats, axes, name: nn.DenseGeneral(  # noqa: E731
            features=feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(cfg.init_std("kda")), axes))
        w = self.param("conv_kernel", nn.initializers.normal(
            cfg.kda_conv ** -0.5), (cfg.kda_conv, 3 * H * K), cfg.param_dtype)
        qkv = nn.silu(causal_conv(
            dense(3 * H * K, ("embed", "mlp"), "qkv_proj")(h),
            w.astype(cfg.dtype), 0))
        q, k, v = (t.reshape(*t.shape[:-1], H, K)
                   for t in jnp.split(qkv, 3, axis=-1))
        q, k = kda_qk_norm(q, k)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H * K,), jnp.float32)
        A_log = self.param("A_log", lambda key, shape: jnp.log(
            jax.random.uniform(key, shape, minval=1.0, maxval=16.0)), (H,))
        g = kda_log_decay(
            dense(H * K, (None, "mlp"), "f_b")(
                dense(r, ("embed", None), "f_a")(h)), dt_bias, A_log)
        beta = nn.sigmoid(dense(H, ("embed", None), "b_proj")(h)
                          .astype(jnp.float32))
        o, _ = kda_reference(q, k, v, g, beta)
        gate = dense(H * K, (None, "mlp"), "g_b")(
            dense(r, ("embed", None), "g_a")(h))
        o = RMSNorm(cfg.norm_eps, cfg.dtype, axis=None, name="o_norm")(o) \
            * nn.sigmoid(gate.reshape(o.shape))
        return dense(cfg.d_model, ("mlp", "embed"), "o_proj")(
            o.reshape(*o.shape[:-2], H * K))


class Retention(nn.Module):
    """A "retention" layer's mixer, gated power attention of degree 2 (power
    retention): ``q, k, v = q_proj(h), k_proj(h), v_proj(h)`` at ``n_heads``,
    ``n_kv_heads`` and ``n_kv_heads`` heads of ``head_dim``, no bias; q and k
    under their per-head RMSNorm (``qk_head_norm``) and then the rotation
    (where ``rope_kinds`` names "retention"), as an attention layer's; ``log
    g = log sigmoid(g_proj(h))`` in float32, one gate a key/value head (kernel
    and bias); the recurrence of ``ops/retention.py``, query head ``h`` reading
    the state of ``h // (n_heads / n_kv_heads)``; ``o_proj``. The TRAINING
    side: the whole sequence at once through ``retention_reference`` (packed
    sequences are not kept apart); the serving engine keeps the state and its
    normaliser a slot (``llm/model_runner.py``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h, positions):
        from ray_tpu.ops.retention import retention_reference

        cfg, hd = self.cfg, self.cfg.head_dim
        dense = lambda feats, axes, name, axis=-1: nn.DenseGeneral(  # noqa: E731
            features=feats, axis=axis, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(cfg.init_std("attn")), axes))
        q = dense((cfg.n_heads, hd), ("embed", "heads", "head_dim"), "q_proj")(h)
        k = dense((cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                  "k_proj")(h)
        v = dense((cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                  "v_proj")(h)
        if cfg.qk_head_norm:  # head by head, one scale for all of them
            norm = lambda name: RMSNorm(   # noqa: E731
                cfg.norm_eps, cfg.dtype, axis=None, name=name,
                init_std=cfg.retention_norm_init_std)
            q, k = norm("q_norm")(q), norm("k_norm")(k)
        if "retention" in cfg.rope_kinds:
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        mean, std, matrix = cfg.retention_gate_init
        gamma = nn.DenseGeneral(
            features=cfg.n_kv_heads, dtype=jnp.float32,
            param_dtype=cfg.param_dtype, name="g_proj",
            kernel_init=nn.with_logical_partitioning(nn.initializers.normal(
                matrix or cfg.init_std("attn")), ("embed", None)),
            bias_init=lambda *a: mean + nn.initializers.normal(std)(*a))(h)
        o, _, _ = retention_reference(q, k, v, jax.nn.log_sigmoid(gamma),
                                      cfg.retention_degree)
        return dense(cfg.d_model, ("heads", "head_dim", "embed"), "o_proj",
                     axis=(-2, -1))(o.astype(cfg.dtype))


def kda_qk_norm(q, k):
    """q, k [.., H, K] -> ``q / |q| * K^-0.5`` and ``k / |k|`` a head, the
    norms in float32, handed on in the type they came in."""
    def unit(t):
        t32 = t.astype(jnp.float32)
        return t32 * jax.lax.rsqrt(
            jnp.sum(t32 * t32, axis=-1, keepdims=True) + 1e-6)
    return ((unit(q) * q.shape[-1] ** -0.5).astype(q.dtype),
            unit(k).astype(k.dtype))


def kda_log_decay(f, dt_bias, A_log):
    """The decay gate's projection f [.., H x K] -> the log-decay g [.., H, K]
    float32: ``-exp(A_log[head]) * softplus(f + dt_bias)``."""
    H = A_log.shape[0]
    step = jax.nn.softplus(f.astype(jnp.float32) + dt_bias)
    return -jnp.exp(A_log)[:, None] * step.reshape(*f.shape[:-1], H, -1)


class MLP(nn.Module):
    cfg: TransformerConfig
    width: int = 0  # 0 = cfg.d_ff

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d_ff = self.width or cfg.d_ff
        dense = lambda feats, axes, name: nn.DenseGeneral(  # noqa: E731
            features=feats, axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(cfg.init_std("mlp")), axes),
        )
        gate = dense(d_ff, ("embed", "mlp"), "gate_proj")(x)
        up = dense(d_ff, ("embed", "mlp"), "up_proj")(x)
        hidden = nn.silu(gate) * up
        return nn.DenseGeneral(
            features=cfg.d_model, axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="down_proj",
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(cfg.init_std("mlp")), ("mlp", "embed")),
        )(hidden)


class MoEMLP(nn.Module):
    """Top-k routed mixture-of-experts MLP (GShard-style dense dispatch),
    the TRAINING side of the expert layer.

    Reference capability: the reference delegates MoE to vLLM/torch user
    code; here EP is native — expert-stacked weights carry the "expert"
    logical axis, the one-hot dispatch/combine einsums keep everything on
    the MXU, and XLA inserts the expert all-to-alls implied by the
    shardings. Token capacity is bounded (capacity_factor); overflow
    tokens fall through the residual (standard token dropping). The
    load-balancing aux loss is sown under the "losses" collection.

    Its parameters (``router/kernel`` [D, E] float32, ``gate_proj`` and
    ``up_proj`` [E, D, F], ``down_proj`` [E, F, D]) are the tree the serving
    engine reads, but the engine computes the layer with ``ops/moe.py``:
    dropless, sorted dispatch, a grouped matmul. The two agree where this
    one drops nothing (``tests/test_moe.py``); that there are two is a debt
    (ROADMAP S5), to be paid when a sparse model is trained on the chip."""

    cfg: TransformerConfig

    GROUP_SIZE = 4096  # tokens per dispatch group (bounds one-hot memory)

    @nn.compact
    def __call__(self, x):
        from ray_tpu.ops.moe import select_experts

        cfg = self.cfg
        B, S, D = x.shape
        E, K = cfg.n_experts, cfg.experts_per_token
        outputs = E + cfg.zero_experts   # the router's: the last are no expert
        N = B * S
        # GShard-style grouping: dispatch/combine one-hots are O(g*E*C) per
        # group with C ~ g*K/E, so memory/FLOPs stay linear in N instead of
        # quadratic (tokens only compete for capacity within their group)
        g = N
        for cand in range(min(self.GROUP_SIZE, N), 0, -1):
            if N % cand == 0:
                g = cand
                break
        G = N // g
        C = max(1, int(cfg.capacity_factor * g * K / E))
        xf = x.reshape(G, g, D)

        router = nn.Dense(outputs, use_bias=False, dtype=jnp.float32,
                          param_dtype=jnp.float32, name="router",
                          kernel_init=nn.with_logical_partitioning(
                              nn.initializers.normal(
                                  cfg.router_init_std or 0.02),
                              ("embed", "expert")))
        logits = router(xf.astype(jnp.float32))  # (G, g, outputs)
        bias = None
        if cfg.has_router_bias:
            bias = self.param("router_bias", nn.with_logical_partitioning(
                nn.initializers.normal(
                    cfg.router_bias_init_std or ROUTER_BIAS_STD),
                ("expert",)), (outputs,), jnp.float32)

        # top-k expert choice per token; (G, g, K) and the scores (G, g, E)
        gate_vals, expert_idx, probs = select_experts(
            logits, K, cfg.norm_topk_prob, cfg.router_kind, bias,
            cfg.routed_scaling_factor, cfg.router_norm_eps)

        # position of each (token, k) within its expert's capacity buffer,
        # per group; k-slots of a token are ordered before later tokens
        onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # (G, g, K, E)
        flat = onehot.reshape(G, g * K, E)
        pos_in_expert = (jnp.cumsum(flat, axis=1) - flat).reshape(G, g, K, E)
        pos = (pos_in_expert * onehot).sum(-1)  # (G, g, K)
        keep = pos < C

        # dispatch/combine (G, g, E, C)
        eh = jax.nn.one_hot(expert_idx, E, dtype=cfg.dtype)[..., None]
        ph = jax.nn.one_hot(pos, C, dtype=cfg.dtype)[..., None, :]
        dispatch = (eh * ph * keep[..., None, None].astype(cfg.dtype)).sum(2)
        combine = (eh * ph
                   * (gate_vals * keep)[..., None, None].astype(cfg.dtype)).sum(2)

        # one rank of an expert-parallel deployment computes its own experts'
        # part: what the others would add is not here
        first, held = cfg.experts_held or (0, E)
        dispatch = dispatch[:, :, first:first + held]
        combine = combine[:, :, first:first + held]
        expert_in = jnp.einsum("gnec,gnd->gecd", dispatch, xf)
        expert_in = nn.with_logical_constraint(
            expert_in, (None, "expert", None, "embed"))

        def stack_param(name, shape, axes):
            return self.param(
                name, nn.with_logical_partitioning(
                    nn.initializers.normal(cfg.init_std("expert")),
                    axes),
                shape, cfg.param_dtype)

        w_gate = stack_param("gate_proj", (held, D, cfg.d_ff),
                             ("expert", "embed", "mlp"))
        w_up = stack_param("up_proj", (held, D, cfg.d_ff),
                           ("expert", "embed", "mlp"))
        w_down = stack_param("down_proj", (held, cfg.d_ff, D),
                             ("expert", "mlp", "embed"))
        h = (nn.silu(jnp.einsum("gecd,edf->gecf", expert_in,
                                w_gate.astype(cfg.dtype)))
             * jnp.einsum("gecd,edf->gecf", expert_in, w_up.astype(cfg.dtype)))
        expert_out = jnp.einsum("gecf,efd->gecd", h, w_down.astype(cfg.dtype))
        expert_out = nn.with_logical_constraint(
            expert_out, (None, "expert", None, "embed"))
        out = jnp.einsum("gnec,gecd->gnd", combine, expert_out)

        # load-balancing loss (Switch/GShard): E * sum_e f_e * p_e
        token_frac = jnp.mean(
            jax.nn.one_hot(expert_idx[..., 0], E, dtype=jnp.float32),
            axis=(0, 1))
        prob_frac = jnp.mean(probs[..., :E], axis=(0, 1))
        aux = E * jnp.sum(token_frac * prob_frac)
        self.sow("losses", "moe_aux", aux)
        out = out.reshape(B, S, D)
        if cfg.zero_experts:  # the identity for each choice past the routed
            # ones (their one-hot rows above are zeros: no capacity, no row)
            passed = jnp.where(expert_idx >= E, gate_vals, 0.0).sum(-1)
            out = out + (passed.reshape(B, S, 1) * x).astype(out.dtype)
        if cfg.n_shared_experts:
            out = out + MLP(cfg, cfg.n_shared_experts * cfg.d_ff,
                            name="shared")(x)
        return out


class LayerNorm(nn.Module):
    """Mean and variance, scale and bias, statistics in float32."""
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.with_logical_partitioning(
            nn.initializers.ones, ("embed",)), (d,), jnp.float32)
        bias = self.param("bias", nn.with_logical_partitioning(
            nn.initializers.normal(NORM_BIAS_STD), ("embed",)), (d,),
            jnp.float32)
        return layer_norm(x, scale, bias, self.eps)


def layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * scale + bias
            ).astype(x.dtype)


# what a hybrid model's seeded biases, lambda vectors and scan constants are
# drawn at, chosen so that each shows in the logits (a comparison with a
# reference that drops one fails): benchmarks/configs/
# phi-4-mini-flash-reasoning.json, "assumed"
NORM_BIAS_STD = 0.02
PROJ_BIAS_STD = 0.02
LAMBDA_STD = 0.1
# a "mamba2" layer's convolution bias: the deviation of the uniform range a
# depthwise Conv1d of four taps is born with (+-0.5), so that it shows beside
# a convolution's output of order one
CONV_BIAS_STD = 0.29


def lambda_init(layer: int) -> float:
    """Differential attention's constant part of lambda at layer ``layer``."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * layer))


def diff_lambda(p, layer: int):
    """lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, float32."""
    f32 = lambda n: p[n].astype(jnp.float32)   # noqa: E731
    return (jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1")))
            - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2")))
            + lambda_init(layer))


def diff_heads(cfg: TransformerConfig):
    """Differential attention as plain attention over ``n_heads`` heads:
    query head ``h`` (of pair ``h // 2``) scores against key head ``key_of[h]``
    and reads the value pair ``value_of[h]``, two value heads side by side."""
    rep = cfg.n_heads // cfg.n_kv_heads
    h = np.arange(cfg.n_heads)
    pair = (h // 2) // rep
    return 2 * pair + h % 2, pair


def diff_combine(o, lam, layer: int, scale, eps):
    """o [..., H, 2 hd] (every head's softmax times its value pair) -> [..., H
    hd]: per query pair ``RMSNorm(o1 - lambda o2) * (1 - lambda_init)``."""
    o = o.astype(jnp.float32)
    d = o[..., 0::2, :] - lam * o[..., 1::2, :]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + eps)
    d = d * scale * (1.0 - lambda_init(layer))
    return d.reshape(*d.shape[:-2], -1)


def causal_conv(a, weight, bias):
    """Depthwise causal convolution along axis 1: a [B, S, I], weight [K, I]
    (tap K-1 is the position itself)."""
    K, S = weight.shape[0], a.shape[1]
    padded = jnp.pad(a, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(weight[k] * padded[:, k:k + S] for k in range(K)) + bias


def _dt_bias_init(key, shape, dtype):
    """softplus^-1 of step sizes log-uniform in [1e-3, 1e-1] (Mamba's own)."""
    dt = jnp.exp(jax.random.uniform(key, shape) * np.log(100.0) + np.log(1e-3))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class HybridMixer(nn.Module):
    """One layer's mixer of a model with ``layer_kinds``, the TRAINING side:
    the whole sequence at once, no cache (the serving engine computes the same
    from the same tree, ``llm/model_runner.py``). Returns (output, memory,
    shared): ``memory`` is a Mamba layer's scan output, ``shared`` the full
    layer's (k, v), each handed on unchanged by the layers that make none."""

    cfg: TransformerConfig
    kind: str
    layer: int

    def _dense(self, feats, name, std, bias=False, dtype=None):
        cfg = self.cfg
        return nn.DenseGeneral(
            features=feats, use_bias=bias, dtype=dtype or cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=nn.initializers.normal(std),
            bias_init=nn.initializers.normal(PROJ_BIAS_STD))

    @nn.compact
    def __call__(self, h, memory, shared):
        cfg, kind = self.cfg, self.kind
        if kind == "mamba":
            out, memory = self._mamba(h)
        elif kind == "gmu":
            gate = nn.silu(self._dense(cfg.ssm_inner, "in_proj",
                                       cfg.init_std("ssm_proj"))(h))
            out = self._dense(cfg.d_model, "out_proj",
                              cfg.init_std("ssm_proj"))(
                memory.astype(cfg.dtype) * gate)
        else:
            out, shared = self._attention(h, shared)
        return out, memory, shared

    def _mamba(self, h):
        from ray_tpu.ops.ssm import selective_scan_reference

        cfg = self.cfg
        inner, N, R = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
        az = self._dense(2 * inner, "in_proj", cfg.init_std("ssm_proj"))(h)
        a, z = az[..., :inner], az[..., inner:]
        w = self.param("conv_kernel", nn.initializers.normal(
            cfg.ssm_conv ** -0.5), (cfg.ssm_conv, inner), cfg.param_dtype)
        b = self.param("conv_bias", nn.initializers.normal(PROJ_BIAS_STD),
                       (inner,), cfg.param_dtype)
        a = nn.silu(causal_conv(a, w.astype(cfg.dtype), b.astype(cfg.dtype)))
        x = self._dense(R + 2 * N, "x_proj", cfg.init_std("ssm_x"),
                        dtype=jnp.float32)(a)
        dt = jax.nn.softplus(nn.DenseGeneral(
            inner, dtype=jnp.float32, param_dtype=jnp.float32, name="dt_proj",
            kernel_init=nn.initializers.normal(R ** -0.5),
            bias_init=_dt_bias_init)(x[..., :R]))
        A_log = self.param(
            "A_log", lambda *_: jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=jnp.float32)), (inner, N)))
        D = self.param("D", nn.initializers.ones, (inner,), jnp.float32)
        y, _ = selective_scan_reference(
            dt, a, x[..., R:R + N], x[..., R + N:], -jnp.exp(A_log),
            jnp.zeros((h.shape[0], inner, N), jnp.float32))
        y = y + D * a.astype(jnp.float32)
        out = self._dense(cfg.d_model, "out_proj", cfg.init_std("ssm_proj"))(
            y.astype(cfg.dtype) * nn.silu(z))
        return out, y

    def _attention(self, h, shared):
        from ray_tpu.ops.attention import reference_attention

        cfg, kind = self.cfg, self.kind
        H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        std = cfg.init_std("attn")
        heads = lambda t, n: t.reshape(*t.shape[:-1], n, hd)   # noqa: E731
        if kind == "cross":
            q = heads(self._dense(H * hd, "Wq", std, bias=True)(h), H)
            k, v = shared
        else:
            qkv = self._dense((H + 2 * KVH) * hd, "Wqkv", std, bias=True)(h)
            q = heads(qkv[..., :H * hd], H)
            k = heads(qkv[..., H * hd:(H + KVH) * hd], KVH)
            v = heads(qkv[..., (H + KVH) * hd:], KVH)
            if kind == "full":
                shared = (k, v)
        lam = {n: self.param(n, nn.initializers.normal(LAMBDA_STD), (hd,),
                             jnp.float32)
               for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")}
        scale = self.param("subln", nn.initializers.ones, (2 * hd,),
                           jnp.float32)
        key_of, value_of = diff_heads(cfg)
        pairs = v.reshape(*v.shape[:-2], KVH // 2, 2 * hd)
        o = reference_attention(
            q, k[..., key_of, :], pairs[..., value_of, :], True, None,
            cfg.window if kind == "window" else 0)
        o = diff_combine(o, diff_lambda(lam, self.layer), self.layer, scale,
                         cfg.norm_eps).astype(cfg.dtype)
        out = self._dense(cfg.d_model, "out_proj", std, bias=True)(o)
        return out, shared


class HybridBlock(nn.Module):
    cfg: TransformerConfig
    kind: str
    layer: int

    @nn.compact
    def __call__(self, x, memory, shared):
        cfg = self.cfg
        out, memory, shared = HybridMixer(
            cfg, self.kind, self.layer, name="mixer")(
            LayerNorm(cfg.norm_eps, cfg.dtype, name="attn_norm")(x), memory,
            shared)
        h = x + out
        h = h + MLP(cfg, name="mlp")(
            LayerNorm(cfg.norm_eps, cfg.dtype, name="mlp_norm")(h))
        return h, memory, shared


class Block(nn.Module):
    cfg: TransformerConfig
    use_moe: bool = False
    kind: str = "full"

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, carried=None):
        """Under ``shortcut_moe`` returns ``(x, carried)``: the even sublayer
        hands on its expert branch's output, the odd one adds what it is
        handed where it ends."""
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype, name=name)  # noqa: E731
        if self.kind == "conv":
            a = ShortConv(cfg, name="conv")(norm("attn_norm")(x))
        elif self.kind == "mamba":
            a = Mamba(cfg, name="mamba")(norm("attn_norm")(x))
        elif self.kind == "mamba2":
            a = Mamba2(cfg, name="mamba")(norm("attn_norm")(x))
        elif self.kind == "kda":
            a = KDA(cfg, name="kda")(norm("attn_norm")(x))
        elif self.kind == "retention":
            a = Retention(cfg, name="retention")(norm("attn_norm")(x),
                                                 positions)
        else:
            a = Attention(cfg, self.kind, name="attn")(
                norm("attn_norm")(x), positions, segment_ids)
        # what a sublayer adds to the stream, under the model's multiplier
        into = lambda t: (t if cfg.residual_scale == 1.0 else   # noqa: E731
                          t * jnp.asarray(cfg.residual_scale, t.dtype))
        h = x + into(norm("post_attn_norm")(a) if cfg.sandwich_norm else a)
        h = nn.with_logical_constraint(h, ("batch", "seq", "embed"))
        if cfg.shortcut_moe:
            u = norm("mlp_norm")(h)
            out = h + into(MLP(cfg, cfg.d_ff_dense, name="mlp")(u))
            if self.use_moe:
                return out, MoEMLP(cfg, name="moe")(u)
            return out + into(carried), None
        mlp = MoEMLP(cfg, name="moe") if self.use_moe else MLP(
            cfg, cfg.d_ff_dense, name="mlp")
        y = mlp(norm("mlp_norm")(h))
        out = h + into(norm("post_mlp_norm")(y) if cfg.sandwich_norm else y)
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))


class Transformer(nn.Module):
    """Decoder-only LM. __call__ returns logits (B, S, V)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions=None, segment_ids=None):
        cfg = self.cfg
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None, :].astype(jnp.int32)
            positions = jnp.broadcast_to(positions, tokens.shape)
        embed = self.param(
            "embed", nn.with_logical_partitioning(
                nn.initializers.normal(cfg.init_std("embed")),
                ("vocab", "embed")),
            (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        x = embed.astype(cfg.dtype)[tokens]
        if cfg.embed_scale != 1.0:
            x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        if cfg.sambay:
            memory = shared = None
            for i, kind in enumerate(cfg.layer_kinds):
                x, memory, shared = HybridBlock(
                    cfg, kind, i, name=f"layer_{i}")(x, memory, shared)
            x = LayerNorm(cfg.norm_eps, cfg.dtype, name="final_norm")(x)
            return jnp.einsum("bsd,vd->bsv", x, embed.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)
        block = Block
        if cfg.remat:
            block = nn.remat(Block, prevent_cse=False,
                             policy=jax.checkpoint_policies.nothing_saveable)
        carried = None
        for i in range(cfg.n_layers):
            x = block(cfg, cfg.is_moe_layer(i), cfg.layer_kind(i),
                      name=f"layer_{i}")(x, positions, segment_ids, carried)
            if cfg.shortcut_moe:
                x, carried = x
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="final_norm")(x)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, embed.astype(cfg.dtype))
        else:
            head = self.param(
                "lm_head", nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), ("embed", "vocab")),
                (cfg.d_model, cfg.vocab_size), cfg.param_dtype)
            logits = jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
        if cfg.logit_scale != 1.0:
            logits = logits * jnp.asarray(cfg.logit_scale, logits.dtype)
        return nn.with_logical_constraint(logits, ("batch", "seq", "vocab"))


def lm_loss(logits: jax.Array, targets: jax.Array,
            mask: Optional[jax.Array] = None) -> jax.Array:
    """Next-token cross entropy; `targets` are the inputs shifted by one."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
