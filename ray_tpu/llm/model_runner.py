"""Inference forward passes with a paged KV cache (TPU-native vLLM core).

Reference capability: ray.llm serves models through vLLM's PagedAttention
engine (llm/_internal/serve/engines/vllm/vllm_engine.py). The TPU redesign
keeps the *cache geometry* idea — KV lives in fixed-shape pages, sequences
own pages through a block table — but implements it as pure-jnp programs so
every prefill bucket and the decode step are each ONE compiled XLA program
with static shapes (no dynamic shapes, no host sync inside the step).

ONE cache type (``Cache``) holds every model's state by KIND of layer, side
by side, a kind the model lacks None; ``block_tables`` [max_num_seqs,
pages_per_seq] int32 hands out the pages of whichever leaves are paged
(``PAGE_LEAVES``), and page 0 is scratch: masked-out writes (padding,
inactive slots) land there. Both programs take the cache donated and write it
in place: per layer one scatter a leaf whose operand is the whole array and
whose indices are (layer, page, offset), B rows in decode, and in prefill the
S positions of each row it is given (the engine gives it the requests
admitted in one step, one row each, alone or in groups of two to four padded
to one length bucket; a row of length 0 is padding and writes the scratch
page). Nothing slices a layer out or writes one back, so a step's cache
traffic is the rows it writes, not the cache.

ONE per-layer composition (``_forward``) is both programs of every model but
the decoder-hybrid-decoder: a prompt side, a step side, or both. It walks the
layers' kinds (``_kinds``), asks the kind for its inputs (``_attn_inputs``,
``_conv_gates``, ``_mamba2_inputs``, ``_kda_inputs``, ``_retention_inputs``),
hands each side's rows to the kind's mixer
(``_prompt_mixer``, ``_step_mixer``) and runs the residual, the norms, the MLP
or the experts and the head once over all rows, so ``prefill`` can carry a
decode step's rows beside its prompts (``riders``; ``rides`` says for which
models, ``llm/engine.py`` when): every held weight read once for the call and
the step. The kinds, and where each keeps what:

- "dense", every layer of a model with neither ``layer_kinds`` nor a latent
  rank: ``k`` / ``v`` [n_layers, num_pages, page_size, n_kv_heads, hd]. Prefill
  attends over the call's own keys and values (the flash kernel); decode
  gathers each slot's pages by (layer, block_tables) into a [B, Lmax] view and
  runs grouped-query attention against it under a mask;
- "latent", every layer of a model with ``kv_latent_rank`` and no
  ``layer_kinds``, or ONE kind among others of a model with them (``rows``
  then holds as many layers as the model has latent ones, a layer found by
  its rank among them; the rotated part is rotated only where ``rope_kinds``
  names "latent", else its lanes are plain ones): ``rows``, one row
  a position and layer for all heads. Prefill attends over keys and values
  expanded to heads (the flash kernel, 192-wide q . k and 128-wide values);
  decode attends over the rows themselves with the up-projection absorbed,
  through ``ops/mla.py:mla_decode``, which reads only the pages that hold
  live positions. With ``q_latent_rank`` the queries are low-rank (down, a
  norm, up to the heads: ``mla.q_lora``), and with ``latent_lora_scale`` the
  queries and the normalised latent are scaled by their widths' ratios; the
  SCALED latent is what a row holds, so both paths read it;
- "full", "window", "conv" of a model with ``layer_kinds``, under ONE
  definition of where each lies (``_prompt_index``, ``_write_rings``,
  ``_decode_index``, ``_ring_blocks``): ``pages`` for each "full" layer's keys
  and values, handed out by the block tables as any page is; in ``rings`` a
  ring of ``window`` positions a slot for each "window" layer, written at
  ``position mod window`` and masked by how many entries are filled; in
  ``conv`` the last ``conv_taps - 1`` gated inputs ``B * z`` a slot for each
  "conv" layer (a gated short convolution,
  ``models/transformer.py:ShortConv``: no attention at all). Keys are rotated
  before they are written where the kind rotates
  (``TransformerConfig.rope_kinds``). Decode attends through
  ``ops/paged_attention.py``: over the rings' filled blocks in the window
  layers and over the live pages in the full ones, two work lists
  (``ops/mla.py:live_pages``) built once a step; nothing is gathered over a
  slot's whole length. Prefill convolves the bucket and leaves the conv rows
  of positions ``lengths - conv_taps + 1 .. lengths - 1`` (zeros where the
  prompt is shorter than that; padding behind the prompt never enters them);
  a decode step convolves the rows with the new input and shifts them by one;
- "mamba2" of such a model (a Mamba-2 mixer, ``models/transformer.py:Mamba2``):
  per layer and slot in ``ssm`` the matrix state of every head, float32, laid
  ``[state, heads x head size]`` as ``ops/ssd.py`` keeps it (4.19 MB a slot
  and layer at 128 heads of 64 and a state of 128: the largest thing a slot
  holds), and in ``conv`` the last ``ssm_conv - 1`` rows of the convolution's
  input ``x | B | C``. Prefill runs the chunked scan over the bucket
  (``ssd_scan``, padding passed over with ``dt = 0``) and WRITES the slot's
  state and tail from the prompt alone, which is how a slot is reset at
  admission, reused, or given back to a preempted request; a decode step
  convolves the tail with the new input, steps every slot's state once, in
  place (``ssd_step``; beside a prompt ``ssd_riding`` with ``keep``), and
  shifts the tail. One product (``in_proj``) makes ``z | xBC | dt`` for all
  rows, the gated norm and ``out_proj`` run once over all rows;
- "kda" of such a model (delta-rule linear attention with a decay per key
  lane, ``models/transformer.py:KDA``): per layer and slot in ``ssm`` the
  matrix state of every head, float32, laid ``[heads, key lanes, value
  lanes]`` as ``ops/kda.py`` keeps it (2.1 MB a slot and layer at 32 heads of
  128 x 128), and in ``conv`` the last ``kda_conv - 1`` rows of the three
  convolutions' input ``q | k | v``. Prefill runs the chunked delta rule
  over the bucket (``kda_scan`` through ``ops/kda.py:kda_prefill``, padding
  passed over from ``lengths`` on, by the whole chunk where a chunk holds
  nothing else) and WRITES the slot's state and tail from
  the prompt alone, which is how a slot is reset at admission, reused, or
  given back to a preempted request; a decode step convolves the tail with
  the new input, steps every slot's state once, in place (``kda_step``;
  beside a prompt ``kda_riding`` with ``keep``), and shifts the tail. One
  product (``qkv_proj``) makes ``q | k | v`` and one the low-rank gates'
  inner halves and ``beta`` for all rows, and ``o_proj`` runs once over all
  rows; what lies between is each side's own. The prompt side: the
  convolutions with the silu behind them stay XLA's (one fusion over ``[R,
  S, 3 H K]``), then ONE kernel takes that array, ``f``, beta and the output
  gate as their products left them and does the l2 norms, the log-decay,
  beta's folds, the recurrence, the head's output norm and the gate in its
  tile, and writes ``o`` in the products' type as ``o_proj`` reads it. The
  step side (``[B, 1]`` rows) does the same arithmetic in XLA around
  ``kda_step`` (``_kda_operands`` before it, the norm and gate after);
- "retention" of such a model (power retention: gated power attention of
  degree 2, ``models/transformer.py:Retention``): per layer and slot in
  ``ssm`` the state of every key/value head, float32, the symmetric square
  of its keys against their values laid by rotation with the normaliser's
  matrix behind it, ``[key/value heads, head_dim / 2 + 2, head_dim,
  head_dim]`` as ``ops/retention.py`` keeps it (34.6 MB a slot and layer at 8
  heads of 128: most of what the chip holds), ``n_heads / n_kv_heads`` query
  heads reading ONE state; no ``conv``, and nothing by position: a model of
  such layers alone has no paged leaf, its block tables stay arguments of
  both programs and address nothing. q, k and v are the attention kinds' three
  products (``_qkv``: the per-head norms, then the rotation by the rows'
  positions, on both sides alike) and one float32 product makes the gates.
  Prefill runs the chunked recurrence over the bucket (``retention_scan``
  through ``ops/retention.py:retention_prefill``, padding passed over from
  ``lengths`` on, by the whole chunk where a chunk holds nothing else) and
  WRITES the slot's state from the prompt alone and empties its pending
  positions, which is how a slot is reset
  at admission, reused, or given back to a preempted request; a decode step
  READS every slot's state once and keeps its position beside it, in
  ``pending`` (``retention_read``: half the bytes of a step that writes),
  and every ``FOLD``-th step, counted on the device in ``pending_count``,
  folds the pending positions and its own into the state, in place
  (``retention_step``; beside a prompt always, as ``retention_riding`` with
  ``keep``): the same function, re-associated; ``o_proj`` runs once over all
  rows.

``prefill`` is told the slot a row fills (``slots``), overwrites the slot's
rings and rows from the prompt alone (which is how a slot is reset at
admission and how a preempted request comes back) and leaves them at position
``lengths - 1``. Every attention call of its prompt side hands the flash
kernel ``lengths``: the query blocks wholly behind a row's end are passed
over and come back zeros (``ops/attention.py``), as a "kda" layer's chunks
do; nobody reads a position behind its row's end. Whatever of a per-head q/k
norm, an attention gate, sandwich
norms, a scaled embedding, a multiplier on what a sublayer adds to the stream
(``residual_scale``), a softmax scale of its own (``attn_scale``: the queries
are scaled before the kernels, which divide by sqrt(head_dim)), a multiplier on
the logits (``logit_scale``) and experts (all of them, or the share
``experts_held`` of an expert-parallel rank, a router whose last outputs are
zero-compute identity experts, ``zero_experts``) the config asks for is a
field the block reads; at 1.0 / 0 the multipliers trace nothing.

A block that is not mixer + FFN: under ``shortcut_moe`` the layers come in
PAIRS (``n_layers`` counts sublayers, each with its own mixer, dense MLP and
two norms, so a published layer keeps two rows a position); the even one
also holds the pair's ONE expert branch, computed from the same normed input
its dense MLP takes (``moe.shortcut``), carried past the odd one's mixer and
MLP and added where the odd one ends (``_paired_rest``). ``moe_load`` has one
entry a PAIR, and where the router has zero experts a last column that
counts the valid assignments that fell on one.

The decoder-hybrid-decoder ("sambay": Mamba layers, window and full
DIFFERENTIAL attention, gated memory units, cross layers; LayerNorm, no
position embedding) is a composition of its own (``_hybrid_prefill``,
``_hybrid_decode``) over the same cache and the same index: one paged layer,
which the full layer writes and every cross layer reads, rings, and in
``ssm`` / ``conv`` a recurrent row a slot for each Mamba layer (the scan's
state in float32 and the convolution's last inputs). Its prefill runs the
self-decoder over the prompt (the scan through ``ops/ssm.py``, padding passed
over with ``dt = 0``; the window through the flash kernel, blocks left of it
skipped) and the cross-decoder on the ONE last position, since those layers
write no state and the engine reads one row of logits.

Weights come from ``ray_tpu.models.transformer.Transformer`` — this module
reads the same param pytree (checkpoint-compatible with training). The dense
layer math (norms, projections, RoPE, SwiGLU) is written out again here and
must stay the arithmetic of ``models/transformer.py``; a layer with experts
(``n_experts > 0``: the tree has ``moe`` where a dense layer has ``mlp``) is
NOT the training module's capacity-bound dispatch but ``ops/moe.py``:
dropless, rows that are padding or belong to an inactive slot reach no
expert. What routing did in a call comes back beside the pages, as
``Cache.moe_load`` (per expert layer, how many real rows each expert got).
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import TransformerConfig, _rope


class Cache(NamedTuple):
    """What a program takes donated and hands back: the model's state by KIND
    of layer, side by side, ONE type for every model; a kind the model lacks
    is None and adds nothing to its programs.

    ``k``, ``v``: per "dense" layer (every layer of a model with neither
    ``layer_kinds`` nor a latent rank) the keys and the values by head, pages
    addressed through the block tables. ``rows``: per "latent" layer
    (``kv_latent_rank``) and position ONE row ``c | k_pe | 0`` for all heads,
    the normalised latent, the rotated key and padding to whole 128-lane
    tiles (512 + 64 -> 640; ``ops/mla.py`` says why), in place of 2 x heads x
    head_dim. ``pages``: a layer of keys and values for each "full" layer of
    a model with ``layer_kinds``, a row ``k | v`` of all heads a position,
    addressed through the block tables as any page is (a
    decoder-hybrid-decoder has one such layer, which its "cross" layers read
    too). ``rings``: per "window" layer and slot the ``window`` newest
    positions' rows, position ``t`` at entry ``t mod window`` (keys as
    attention reads them: rotated, in a model that rotates). ``ssm``,
    ``conv``: per "mamba" layer and slot the scan's state (float32, ``inner``
    along the lanes as ``ops/ssm.py`` keeps it: [.., N, inner] is whole tiles
    where [.., inner, N] would pad 16 lanes to 128) and the convolution's last
    ``ssm_conv - 1`` inputs. ``conv`` alone, ``ssm`` None: per "conv" layer (a
    gated short convolution) and slot the last ``conv_taps - 1`` gated inputs
    ``B * z``, oldest first, ``d_model`` wide. ``ssm`` and ``conv`` of a model
    with "mamba2" layers: per such layer and slot every head's matrix state,
    float32, [N, heads x head size] (``ops/ssd.py``'s layout: the channels
    along the lanes), and the last ``ssm_conv - 1`` rows of the convolution's
    input ``x | B | C``, ``ssm_inner + 2 ssm_state`` wide. Of a model with
    "kda" layers: per such layer and slot every head's matrix state, float32,
    [heads, key lanes, value lanes] (``ops/kda.py``'s layout), and the last
    ``kda_conv - 1`` rows of the convolutions' input ``q | k | v``, ``3 x
    heads x head dim`` wide; its "latent" layers' rows lie in ``rows``, which
    then has as many layers as the model has latent ones. Of a model with
    "retention" layers: ``ssm`` alone, per such layer and slot (and one slot
    past the last, where a prefill call's padding rows land) every
    key/value head's state with its normaliser, float32, [key/value heads,
    head_dim / 2 + 2, head_dim, head_dim] (``ops/retention.py``'s layout: the
    normaliser is the last of those slabs, one leaf, so that one alias moves
    both in place and every tile is whole), which is the state as of the
    slot's last WRITE-BACK (``ops/retention.py``: a decode step reads it
    every position and writes it every ``FOLD``-th); beside it ``pending``,
    per such layer the ``FOLD - 1`` positions since (each one's key, value
    and log-gate, float32, [FOLD - 1, 3, slots, key/value heads, head_dim]; a
    null one, ``k = 0`` and ``log g = 0``, where a slot has fewer), and
    ``pending_count``, ONE int32 for all slots and layers: how many decode
    steps' positions lie there, which is what decides on the device whether
    a step reads or folds; a model of NO paged kind (such
    layers alone) holds no ``pages``, ``rows``, ``k`` or ``v`` at all, not
    even an empty one: its block tables address nothing and ``_page_size`` is
    0. ``moe_load``: for a model with
    experts, what the call's routing did. Rings and rows by slot belong to a
    SLOT: prefill overwrites all of a slot's from the prompt alone, which is
    also how a slot is reset at admission; a slot that is not active computes
    into its own rows and nobody reads them."""
    k: Optional[jax.Array] = None  # [L, NP, P, KVH, HD]
    v: Optional[jax.Array] = None
    rows: Optional[jax.Array] = None  # [L or latent layers, NP, P, W]
    pages: Optional[jax.Array] = None  # [full layers, NP, P, 2 KVH hd]
    rings: Optional[jax.Array] = None  # [window layers, B, window, 2 KVH hd]
    # [mamba layers, B, N, inner], [kda layers, B, H, K, K] or [retention
    # layers, B + 1, KVH, hd / 2 + 2, hd, hd], float32
    ssm: Optional[jax.Array] = None
    # [mamba layers, ssm_conv - 1, B, inner (+ 2 N: "mamba2")], [conv layers,
    # conv_taps - 1, B, d_model] or [kda layers, kda_conv - 1, B, 3 H K]
    conv: Optional[jax.Array] = None
    moe_load: Optional[jax.Array] = None  # [expert layers, E] int32
    # [retention layers, FOLD - 1, 3, B, KVH, hd] float32, and an int32
    pending: Optional[jax.Array] = None
    pending_count: Optional[jax.Array] = None


# the leaves that block tables address: what a request's pages are gathered
# from and scattered into when it moves between engines
PAGE_LEAVES = ("k", "v", "rows", "pages")


def _page_size(cache: Cache) -> int:
    """The positions of a page; 0 for a model that keeps nothing by position
    (no leaf of ``PAGE_LEAVES``)."""
    return next((getattr(cache, name).shape[2] for name in PAGE_LEAVES
                 if getattr(cache, name) is not None), 0)


def _latent_width(cfg: TransformerConfig) -> int:
    return -(-(cfg.kv_latent_rank + cfg.qk_rope_head_dim) // 128) * 128


def _latent_row(parts, width):
    """``parts`` side by side along the last axis, zeros up to ``width``."""
    row = jnp.concatenate(parts, axis=-1)
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1)
                   + [(0, width - row.shape[-1])])


def init_cache(cfg: TransformerConfig, num_pages: int, page_size: int,
               max_num_seqs: int = 0) -> Cache:
    """``max_num_seqs``: the engine's slots, which only a model that keeps
    state by slot (``layer_kinds``) needs."""
    load = None
    layers = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    if layers:  # a model with zero experts counts them in a last column
        load = jnp.zeros((layers, cfg.n_experts_held + bool(cfg.zero_experts)),
                         jnp.int32)
    if cfg.layer_kinds:
        if not max_num_seqs:
            raise ValueError("a model with layer_kinds keeps window rings and "
                             "recurrent rows by slot: init_cache needs "
                             "max_num_seqs")
        kinds, row = cfg.layer_kinds, 2 * cfg.n_kv_heads * cfg.head_dim
        window, full = kinds.count("window"), kinds.count("full")
        latent, kda = kinds.count("latent"), kinds.count("kda")
        mamba = kinds.count("mamba") + kinds.count("mamba2")
        retention = kinds.count("retention")
        rows = state = pending = None
        if mamba:  # a "mamba2" layer convolves x | B | C, a "mamba" layer x
            rows = (mamba, cfg.ssm_conv - 1, max_num_seqs, cfg.ssm_inner
                    + ("mamba2" in kinds) * 2 * cfg.ssm_state)
            state = (mamba, max_num_seqs, cfg.ssm_state, cfg.ssm_inner)
        elif "conv" in kinds:
            rows = (kinds.count("conv"), cfg.conv_taps - 1, max_num_seqs,
                    cfg.d_model)
        elif kda:
            H, K = cfg.kda_heads, cfg.kda_head_dim
            rows = (kda, cfg.kda_conv - 1, max_num_seqs, 3 * H * K)
            state = (kda, max_num_seqs, H, K, K)
        elif retention:
            from ray_tpu.ops.retention import FOLD, check_degree, state_shape

            check_degree(cfg.retention_degree)
            # a slot past the last: where a padding row's state lands
            state = (retention, max_num_seqs + 1, cfg.n_kv_heads,
                     *state_shape(cfg.head_dim))
            pending = (retention, FOLD - 1, 3, max_num_seqs, cfg.n_kv_heads,
                       cfg.head_dim)
        return Cache(
            rows=jnp.zeros((latent, num_pages, page_size, _latent_width(cfg)),
                           cfg.dtype) if latent else None,
            pages=jnp.zeros((full, num_pages, page_size, row), cfg.dtype)
            if full or not (latent or retention) else None,
            rings=jnp.zeros((window, max_num_seqs, cfg.window, row), cfg.dtype)
            if window else None,
            ssm=jnp.zeros(state, jnp.float32) if state else None,
            conv=jnp.zeros(rows, cfg.dtype) if rows else None, moe_load=load,
            pending=jnp.zeros(pending, jnp.float32) if pending else None,
            pending_count=jnp.zeros((), jnp.int32) if pending else None)
    shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_latent_rank:
        return Cache(rows=jnp.zeros(
            (cfg.n_layers, num_pages, page_size, _latent_width(cfg)),
            cfg.dtype), moe_load=load)
    return Cache(k=jnp.zeros(shape, cfg.dtype), v=jnp.zeros(shape, cfg.dtype),
                 moe_load=load)


# ---------------------------------------------------------------------------
# shared layer math (mirrors models/transformer.py, reading its param tree)
# ---------------------------------------------------------------------------


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (norm * scale).astype(x.dtype)


def _mlp(x, p, dtype):
    gate = jnp.einsum("...d,df->...f", x, p["gate_proj"]["kernel"].astype(dtype))
    up = jnp.einsum("...d,df->...f", x, p["up_proj"]["kernel"].astype(dtype))
    hidden = jax.nn.silu(gate) * up
    return jnp.einsum("...f,fd->...d", hidden, p["down_proj"]["kernel"].astype(dtype))


def _ffn(x, lp, cfg, valid, name):
    """The layer's MLP on x [B, S, D]: SwiGLU, or the expert layer for the
    rows ``valid`` [B, S] marks real. Returns (y, load [E] or None)."""
    if "moe" not in lp:
        return _mlp(x, lp["mlp"], cfg.dtype), None
    return _experts(x, lp, cfg, valid, name)


def _experts(x, lp, cfg, valid, name):
    """``lp``'s expert layer on x [B, S, D] for the rows ``valid`` [B, S]
    marks real -> (y, load: ``ops/moe.py:expert_layer``'s)."""
    from ray_tpu.ops.moe import expert_layer

    p = lp["moe"]
    y, load = expert_layer(
        x.reshape(-1, x.shape[-1]), valid.reshape(-1), p["router"]["kernel"],
        p["gate_proj"], p["up_proj"], p["down_proj"],
        top_k=cfg.experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
        name=name, router_kind=cfg.router_kind,
        router_bias=p.get("router_bias"),
        router_scale=cfg.routed_scaling_factor,
        router_norm_eps=cfg.router_norm_eps,
        held=tuple(cfg.experts_held) or None, zero_experts=cfg.zero_experts)
    y = y.reshape(x.shape)
    if "shared" in p:  # the experts every row goes through
        with jax.named_scope("moe.shared"):
            y = y + _mlp(x, p["shared"], cfg.dtype)
    return y, load


def _qkv(x, p, cfg, positions, rotate=True):
    dtype = cfg.dtype
    q = jnp.einsum("...d,dhk->...hk", x, p["q_proj"]["kernel"].astype(dtype))
    k = jnp.einsum("...d,dhk->...hk", x, p["k_proj"]["kernel"].astype(dtype))
    v = jnp.einsum("...d,dhk->...hk", x, p["v_proj"]["kernel"].astype(dtype))
    if cfg.qk_norm:
        def whole(t, scale):  # the norm sees all heads as one vector
            flat = t.reshape(*t.shape[:-2], -1)
            return _rmsnorm(flat, scale, cfg.norm_eps).reshape(t.shape)
        q = whole(q, p["q_norm"]["scale"])
        k = whole(k, p["k_norm"]["scale"])
    if cfg.qk_head_norm:  # head by head, one scale for all of them
        q = _rmsnorm(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = _rmsnorm(k, p["k_norm"]["scale"], cfg.norm_eps)
    if rotate:
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    if cfg.attn_scale:  # every kernel and gather divides by sqrt(head_dim)
        q = q * jnp.asarray(cfg.attn_scale * cfg.head_dim ** 0.5, q.dtype)
    return q, k, v


def _latent_qkv(x, p, cfg, positions):
    """Latent attention's projections of x [B, S, D]: q_nope [B, S, H, nope],
    q_pe [B, S, H, rope] rotated, the normalised latent c [B, S, R], the
    rotated key k_pe [B, S, rope] (one for all heads), and the cache row
    ``c | k_pe | 0`` [B, S, W]. As a kind of a model with ``layer_kinds``
    that does not name "latent" in ``rope_kinds`` nothing is rotated: q_pe and
    k_pe are the same lanes, plain."""
    dtype = cfg.dtype
    r, nope = cfg.kv_latent_rank, cfg.qk_nope_head_dim
    if cfg.q_latent_rank:  # low-rank queries: down, a norm, up to the heads
        with jax.named_scope("mla.q_lora"):
            cq = _rmsnorm(jnp.einsum(
                "...d,dr->...r", x, p["q_a_proj"]["kernel"].astype(dtype)),
                p["q_a_norm"]["scale"], cfg.norm_eps)
            q = jnp.einsum("...r,rhk->...hk", cq,
                           p["q_b_proj"]["kernel"].astype(dtype))
    else:
        q = jnp.einsum("...d,dhk->...hk", x,
                       p["q_proj"]["kernel"].astype(dtype))
    a = jnp.einsum("...d,dr->...r", x, p["kv_a_proj"]["kernel"].astype(dtype))
    c_scale = p["kv_a_norm"]["scale"]
    if cfg.latent_lora_scale:
        # the queries times s_q; the normalised latent times s_kv, in the
        # norm's own float32 (sqrt(12) is no bfloat16 number). The SCALED
        # latent is what the cache row holds: both halves of kv_b_proj read
        # it, expanded in prefill and absorbed in decode alike
        from ray_tpu.models.transformer import latent_scales

        s_q, s_kv = latent_scales(cfg)
        q, c_scale = q * jnp.asarray(s_q, q.dtype), c_scale * s_kv
    c = _rmsnorm(a[..., :r], c_scale, cfg.norm_eps)
    if cfg.layer_kinds and "latent" not in cfg.rope_kinds:
        q_pe, k_pe = q[..., nope:], a[..., r:]
    else:
        q_pe = _rope(q[..., nope:], positions, cfg.rope_theta)
        k_pe = _rope(a[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    return (q[..., :nope], q_pe, c, k_pe,
            _latent_row([c, k_pe], _latent_width(cfg)))


def _latent_attention_expanded(q_nope, q_pe, c, k_pe, p, cfg, lengths=None):
    """Prefill's path: keys and values up-projected from the latent to heads,
    then plain causal attention over 192-wide q . k and 128-wide values,
    which passes over what lies behind the rows' ``lengths``."""
    from ray_tpu.ops.attention import attention as attention_op

    nope = cfg.qk_nope_head_dim
    kv = jnp.einsum("...r,rhk->...hk", c,
                    p["kv_b_proj"]["kernel"].astype(cfg.dtype))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[..., None, :], q_pe.shape)],
        axis=-1)
    return attention_op(q, k, kv[..., nope:], causal=True,
                        impl=cfg.attention_impl, lens=lengths)


def _latent_attention_absorbed(q_nope, q_pe, rows, work, layer, p, cfg):
    """Decode's path, the same mathematics with ``kv_b_proj`` absorbed: the
    query goes up to the latent (``q_lat[h] = q_nope[h] W_k[h]^T``), all heads
    attend over the cache rows themselves (``ops/mla.py:mla_decode``), and the
    result comes down through the value half (``out[h] = o_lat[h] W_v[h]``).
    q_nope [B, H, nope], q_pe [B, H, rope] -> [B, H, v_head_dim]."""
    from ray_tpu.ops.mla import mla_decode

    r, nope = cfg.kv_latent_rank, cfg.qk_nope_head_dim
    w = p["kv_b_proj"]["kernel"].astype(cfg.dtype)        # [R, H, nope + v]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w[..., :nope])
    o_lat = mla_decode(
        _latent_row([q_lat, q_pe], rows.shape[-1]), rows, work, rank=r,
        layer=layer, sm_scale=1.0 / ((nope + q_pe.shape[-1]) ** 0.5))
    return jnp.einsum("bhr,rhv->bhv", o_lat, w[..., nope:])


# ---------------------------------------------------------------------------
# a model with layer_kinds (models/transformer.py:HybridBlock): its layers'
# arithmetic over the three kinds of state. The residual stream is float32
# here (each layer's products take and give cfg.dtype): 64 additions in
# bfloat16 lose a percent and a half of a 32-layer stream by themselves
# ---------------------------------------------------------------------------


def _dense(x, p, dtype):
    y = jnp.einsum("...d,df->...f", x.astype(dtype), p["kernel"].astype(dtype))
    return y + p["bias"].astype(dtype) if "bias" in p else y


def _layer_norm(x, n, cfg):
    """LayerNorm of the float32 stream, handed on in the products' type."""
    from ray_tpu.models.transformer import layer_norm

    return layer_norm(x, n["scale"], n["bias"], cfg.norm_eps).astype(cfg.dtype)


def _memory_unit(h, memory, m, cfg):
    """A gated memory unit: the handing Mamba layer's scan output at the same
    position, gated by this layer's own projection of ``h``."""
    gate = jax.nn.silu(_dense(h, m["in_proj"], cfg.dtype))
    return _dense(memory.astype(cfg.dtype) * gate, m["out_proj"], cfg.dtype)


def _mamba_inputs(a, m, cfg):
    """The convolved, activated input a [.., I] -> (a, dt, B, C), the scan's
    operands in float32."""
    N, R = cfg.ssm_state, cfg.ssm_dt_rank
    a = jax.nn.silu(a)
    x = _dense(a, m["x_proj"], jnp.float32)
    dt = jax.nn.softplus(_dense(x[..., :R], m["dt_proj"], jnp.float32))
    return a, dt, x[..., R:R + N], x[..., R + N:]


def _mamba_output(y, a, z, m, cfg):
    """The scan's y [.., I] float32 -> (the mixer's output, the memory a
    gated memory unit reads: y with the D term, before the gate)."""
    y = y + m["D"] * a.astype(jnp.float32)
    return _dense(y.astype(cfg.dtype) * jax.nn.silu(z), m["out_proj"],
                  cfg.dtype), y


def _diff_qkv(h, m, cfg):
    """h [.., D] -> q [.., H, hd] and, for a layer with keys of its own, the
    cache row ``k | v`` [.., 2 KVH hd] (None for a cross layer)."""
    H, hd = cfg.n_heads, cfg.head_dim
    if "Wq" in m:
        return _dense(h, m["Wq"], cfg.dtype).reshape(*h.shape[:-1], H, hd), None
    qkv = _dense(h, m["Wqkv"], cfg.dtype)
    return qkv[..., :H * hd].reshape(*h.shape[:-1], H, hd), qkv[..., H * hd:]


def _diff_out(o, m, layer, cfg):
    """o [.., H, 2 hd], every head's softmax times its value pair -> the
    mixer's output [.., D]."""
    from ray_tpu.models.transformer import diff_combine, diff_lambda

    o = diff_combine(o, diff_lambda(m, layer), layer, m["subln"], cfg.norm_eps)
    return _dense(o.astype(cfg.dtype), m["out_proj"], cfg.dtype)


def _row_heads(row, cfg):
    """A cache row [.., 2 KVH hd] -> keys [.., H, hd] and value pairs [.., H,
    2 hd] as each query head reads them (prefill's dense attention)."""
    from ray_tpu.models.transformer import diff_heads

    KVH, hd = cfg.n_kv_heads, cfg.head_dim
    key_of, value_of = diff_heads(cfg)
    k = row[..., :KVH * hd].reshape(*row.shape[:-1], KVH, hd)
    v = row[..., KVH * hd:].reshape(*row.shape[:-1], KVH // 2, 2 * hd)
    return k[..., key_of, :], v[..., value_of, :]


def _keys_per_group(cfg):
    """Plain key heads a group of ``ops/paged_attention.py``: as many as lie
    in one 128-lane tile of a page's row (two of 64 lanes, one of 128)."""
    return math.gcd(max(128 // cfg.head_dim, 1), cfg.n_kv_heads)


def _grouped_query(q, cfg):
    """q [B, H, hd] -> [B, G, R, W] for ``ops/paged_attention.py``, scaled:
    the query heads of a group as rows (padded to 16). Differential heads: a
    group is a key PAIR, each query laid where its key lies in the pair's ``k1
    | k2`` (``W = 2 hd``), zeros beside it. Plain heads: a group is the
    ``_keys_per_group`` key heads of one tile, ``k1 | .. | kn``, the queries
    of key ``j`` laid at its ``hd`` lanes with zeros beside them (``W = n
    hd``); at ``head_dim`` 128 that is one key head and ``W = hd``."""
    B, H, hd = q.shape
    q = q * (hd ** -0.5)
    if cfg.sambay:
        G = cfg.n_kv_heads // 2
        q = q.reshape(B, G, H // G, hd)
        second = (jnp.arange(H // G) % 2 == 1)[None, None, :, None]
        zero = jnp.zeros_like(q)
        q = jnp.concatenate([jnp.where(second, zero, q),
                             jnp.where(second, q, zero)], axis=-1)
    else:
        n = _keys_per_group(cfg)
        G = cfg.n_kv_heads // n
        q = q.reshape(B, G, n, H // cfg.n_kv_heads, hd)
        q = jnp.concatenate([jnp.pad(q[:, :, j], (
            (0, 0), (0, 0), (0, 0), (j * hd, (n - 1 - j) * hd)))
            for j in range(n)], axis=2)
    return jnp.pad(q, ((0, 0), (0, 0), (0, -(H // G) % 16), (0, 0)))


def _paged_attention(q, pages, work, layer, name, cfg):
    """Decode's attention: q [B, H, hd] against the live rows of ``pages`` [L,
    NP, P, row] -> [B, H, W]: differential heads keep the pair's ``2 hd``
    lanes, a plain head takes its own key's ``hd`` of its group's."""
    from ray_tpu.ops.paged_attention import paged_gqa_decode

    B, H, hd = q.shape
    o = paged_gqa_decode(_grouped_query(q, cfg), pages, work, layer=layer,
                         name=name)
    G = o.shape[1]
    o = o[:, :, :H // G]
    n = 1 if cfg.sambay else _keys_per_group(cfg)
    if n > 1:   # rows of key j hold their own result at key j's lanes
        o = o.reshape(B, G, n, H // G // n, n, hd)
        o = jnp.stack([o[:, :, j, :, j] for j in range(n)], axis=2)
    return o.reshape(B, H, -1)


# -- where a model with layer_kinds keeps what: ONE definition of the page and
# ring arithmetic for every such model ---------------------------------------


def _rows_at(t, pos):
    """t [B, S, F] at positions pos [B, n]; zeros where pos < 0."""
    got = jnp.take_along_axis(t, jnp.maximum(pos, 0)[..., None], axis=1)
    return jnp.where((pos >= 0)[..., None], got, 0)


def _write_rings(rings, layer, slots, row, ring_pos):
    """A prefill call's rows ``row`` [B, S, F] into the rings of ``slots``. A
    bucket no longer than the window cannot wrap: position t is entry t, and
    the entries past the prompt are left as they are, since a decode step
    counts a ring's filled entries and writes an entry before it first reads
    it. A longer bucket gathers each entry's newest position."""
    S, W = row.shape[1], rings.shape[2]
    if S <= W:
        return rings.at[layer, slots, :S].set(row)
    return rings.at[layer, slots].set(_rows_at(row, ring_pos))


def _prompt_index(cfg, cache, S, lengths, block_tables):
    """Where a prefill call's ``[B, S]`` positions go: ``in_prompt`` [B, S],
    the ``page`` and ``offset`` of each (padding -> the scratch page), ``last``
    [B, 1] the last real position, and ``ring_pos`` [B, window]: ring entry j
    holds the newest prompt position that is j mod window (< 0: none; None in
    a model without rings)."""
    B = lengths.shape[0]
    P, W = _page_size(cache), cfg.window
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    in_prompt = positions < lengths[:, None]
    page = offset = None  # of a model that keeps nothing by position
    if P:
        page_for = jnp.take_along_axis(block_tables, positions // P, axis=1)
        page = jnp.where(in_prompt, page_for, 0)
        offset = jnp.where(in_prompt, positions % P, 0)
    last = jnp.maximum(lengths - 1, 0).astype(jnp.int32)[:, None]
    ring_pos = None
    if cache.rings is not None:
        ring_pos = last - (last - jnp.arange(W, dtype=jnp.int32)[None]) % W
    return positions, in_prompt, page, offset, last, ring_pos


def _ring_block(cfg, page_size):
    """The block a ring is read in: a page's positions where the window is
    whole pages (a block of ``paged_gqa_decode`` then has a page's size
    whichever it reads), else the whole ring."""
    return page_size if cfg.window % page_size == 0 else cfg.window


def _decode_index(cfg, cache, seq_lens, block_tables, active):
    """A decode step's row: ``slot`` [B], ``positions`` [B], the ``page`` and
    ``offset`` it is written at (inactive slots -> the scratch page), and what
    its attention reads, built once a step. For the paged kernels two work
    lists (``ops/mla.py:live_pages``): the pages that hold live positions, and
    the ring blocks that hold filled entries (a ring is its slot's own run of
    blocks, its live entries the filled ones, in whatever order it holds them;
    None in a model without rings). For the "dense" kind, which gathers every
    page of a slot, the block tables and the mask of the live positions among
    their ``Lmax``. Last, the positions as the tables took them, [B, 1]."""
    from ray_tpu.ops.mla import live_pages

    B = seq_lens.shape[0]
    P, W = _page_size(cache), cfg.window
    slot = jnp.arange(B, dtype=jnp.int32)
    positions = seq_lens.astype(jnp.int32)
    at = positions[:, None]
    if not P:  # nothing is kept by position: no page, no work list
        return slot, positions, None, None, None, None, at
    cur_page = jnp.take_along_axis(block_tables, at // P, axis=1)[:, 0]
    page = jnp.where(active, cur_page, 0)
    offset = jnp.where(active, positions % P, 0)
    if cache.k is not None:
        Lmax = block_tables.shape[1] * P
        work = block_tables, (jnp.arange(Lmax, dtype=jnp.int32)[None]
                              <= seq_lens[:, None]) & active[:, None]
    else:
        work = live_pages(positions, active, block_tables, P)
    if cache.rings is None:
        return slot, positions, page, offset, work, None, at
    blocks = W // _ring_block(cfg, P)
    ring_tables = slot[:, None] * blocks + jnp.arange(
        blocks, dtype=jnp.int32)[None]
    ring_work = live_pages(jnp.minimum(positions, W - 1), active, ring_tables,
                           W // blocks)
    return slot, positions, page, offset, work, ring_work, at


def _ring_blocks(rings, cfg, page_size):
    """rings [layers, B, window, row] as the kernel reads them: [layers, B x
    blocks, block, row], the same bytes."""
    return rings.reshape(rings.shape[0], -1, _ring_block(cfg, page_size),
                         rings.shape[-1])


def _hybrid_head(x, p, cfg):
    """The final LayerNorm and the tied table on [B, d] -> float32 logits."""
    x = _layer_norm(x, p["final_norm"], cfg)
    return jnp.einsum("bd,vd->bv", x, p["embed"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def _hybrid_prefill(p, cfg, cache, tokens, lengths, block_tables, slots):
    from ray_tpu.models.transformer import causal_conv
    from ray_tpu.ops.attention import attention as attention_op
    from ray_tpu.ops.ssm import selective_scan

    B, S = tokens.shape
    W, K = cfg.window, cfg.ssm_conv
    _, in_prompt, page, offset, last, ring_pos = _prompt_index(
        cfg, cache, S, lengths, block_tables)
    tail_pos = lengths[:, None] - (K - 1) + jnp.arange(K - 1)[None]     # [B, K-1]

    pages, rings, ssm, conv = cache.pages, cache.rings, cache.ssm, cache.conv
    x = p["embed"][tokens].astype(jnp.float32)   # the residual stream: float32
    memory = shared = None
    mamba_i = window_i = 0
    first_cross = min(i for i, k in enumerate(cfg.layer_kinds)
                      if k in ("gmu", "cross"))
    for i, kind in enumerate(cfg.layer_kinds):
        if i == first_cross:
            # the cross-decoder writes no state and the engine reads one
            # position's logits: from here on, the last real position alone
            x = jnp.take_along_axis(x, last[..., None], axis=1)
            memory = jnp.take_along_axis(memory, last[..., None], axis=1)
            seen = in_prompt[:, None, None, :]
        lp = p[f"layer_{i}"]
        m = lp["mixer"]
        h = _layer_norm(x, lp["attn_norm"], cfg)
        if kind == "mamba":
            with jax.named_scope("ssm.prefill"):
                az = _dense(h, m["in_proj"], cfg.dtype)
                raw, z = az[..., :cfg.ssm_inner], az[..., cfg.ssm_inner:]
                a, dt, Bm, Cm = _mamba_inputs(causal_conv(
                    raw, m["conv_kernel"].astype(cfg.dtype),
                    m["conv_bias"].astype(cfg.dtype)), m, cfg)
                # padding neither advances the state nor enters the tail
                y, state = selective_scan(
                    jnp.where(in_prompt[..., None], dt, 0.0), a, Bm, Cm,
                    -jnp.exp(m["A_log"]))
                out, memory = _mamba_output(y, a, z, m, cfg)
                ssm = ssm.at[mamba_i, slots].set(state)
                # [layer, tap, slot]: the indexed axes come first, [B, K-1, I]
                conv = conv.at[mamba_i, :, slots].set(_rows_at(raw, tail_pos))
            mamba_i += 1
        elif kind == "gmu":
            out = _memory_unit(h, memory, m, cfg)
        elif kind == "cross":
            with jax.named_scope("prefill.cross_row"):
                q, _ = _diff_qkv(h, m, cfg)                # [B, 1, H, hd]
                k, v = shared
                scores = jnp.einsum("bqhd,bshd->bhqs", q, k,
                                    preferred_element_type=jnp.float32)
                scores = jnp.where(seen, scores * cfg.head_dim ** -0.5, -1e30)
                probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
                out = _diff_out(jnp.einsum("bhqs,bshd->bqhd", probs, v), m, i,
                                cfg)
        else:
            q, row = _diff_qkv(h, m, cfg)
            k, v = _row_heads(row, cfg)
            out = _diff_out(attention_op(
                q, k, v, causal=True, impl=cfg.attention_impl,
                window=W if kind == "window" else 0, lens=lengths), m, i, cfg)
            if kind == "window":
                rings = _write_rings(rings, window_i, slots, row, ring_pos)
                window_i += 1
            else:
                pages = pages.at[0, page, offset].set(row, mode="drop")
                shared = (k, v)
        x = x + out
        x = x + _mlp(_layer_norm(x, lp["mlp_norm"], cfg), lp["mlp"], cfg.dtype)
    return _hybrid_head(x[:, 0], p, cfg), Cache(
        pages=pages, rings=rings, ssm=ssm, conv=conv)


def _hybrid_decode(p, cfg, cache, last_tokens, seq_lens, block_tables, active):
    P, W = cache.pages.shape[2], cfg.window
    # one work list for the full layer and every cross layer, one for the rings
    slot, positions, page, offset, work, ring_work, _ = _decode_index(
        cfg, cache, seq_lens, block_tables, active)

    pages, rings, ssm, conv = cache.pages, cache.rings, cache.ssm, cache.conv
    x = p["embed"][last_tokens].astype(jnp.float32)        # [B, d]
    memory = None
    mamba_i = window_i = 0
    for i, kind in enumerate(cfg.layer_kinds):
        lp = p[f"layer_{i}"]
        m = lp["mixer"]
        h = _layer_norm(x, lp["attn_norm"], cfg)
        if kind == "mamba":
            with jax.named_scope("ssm.step"):
                az = _dense(h, m["in_proj"], cfg.dtype)
                raw, z = az[..., :cfg.ssm_inner], az[..., cfg.ssm_inner:]
                taps = jnp.concatenate([conv[mamba_i], raw[None]], axis=0)
                a, dt, Bm, Cm = _mamba_inputs(
                    jnp.einsum("kbi,ki->bi", taps,
                               m["conv_kernel"].astype(cfg.dtype))
                    + m["conv_bias"].astype(cfg.dtype), m, cfg)
                state = jnp.exp(dt[:, None] * -jnp.exp(m["A_log"]).T) \
                    * ssm[mamba_i] + (dt * a)[:, None] * Bm[..., None]
                out, memory = _mamba_output(
                    jnp.einsum("bni,bn->bi", state, Cm), a, z, m, cfg)
                ssm = ssm.at[mamba_i].set(state)
                conv = conv.at[mamba_i].set(taps[1:])
            mamba_i += 1
        elif kind == "gmu":
            out = _memory_unit(h, memory, m, cfg)
        else:
            q, row = _diff_qkv(h, m, cfg)
            if kind == "window":
                rings = rings.at[window_i, slot, positions % W].set(row)
                o = _paged_attention(q, _ring_blocks(rings, cfg, P), ring_work,
                                     window_i, "window_gqa_decode", cfg)
                window_i += 1
            else:
                if row is not None:   # the full layer writes the shared row
                    pages = pages.at[0, page, offset].set(row, mode="drop")
                o = _paged_attention(q, pages, work, 0, "paged_gqa_decode",
                                     cfg)
            out = _diff_out(o, m, i, cfg)
        x = x + out
        x = x + _mlp(_layer_norm(x, lp["mlp_norm"], cfg), lp["mlp"], cfg.dtype)
    return _hybrid_head(x, p, cfg), Cache(
        pages=pages, rings=rings, ssm=ssm, conv=conv)


# ---------------------------------------------------------------------------
# the RMSNorm block (models/transformer.py:Block), every model's but the
# decoder-hybrid-decoder's: ONE per-layer composition over the layer's KIND.
# "dense" (keys and values by head, by layer number) or "latent" (one row a
# position) in every layer of a model without layer_kinds; with them, plain
# grouped-query heads, rotated or not by kind, pages for each "full" layer, a
# ring for each "window" layer, conv_taps - 1 rows a slot for each "conv"
# layer (no heads at all), a matrix state with a convolution tail a slot
# for each "mamba2" and each "kda" layer, the symmetric square's state a slot
# for each "retention" layer and one row a position for each
# "latent" layer; with the per-head q/k norm, the attention gate,
# the sandwich norms, the multipliers and the experts the config asks for. ``_embed`` decides
# the residual stream's type
# ---------------------------------------------------------------------------


def _kinds(cfg: TransformerConfig) -> Tuple[str, ...]:
    """Each layer's kind as this module keeps its state. A model without
    ``layer_kinds`` is ``n_layers`` layers of ONE kind; not "full": that is
    ``k | v`` rows in ``pages`` under the paged kernel, here."""
    return cfg.layer_kinds or (
        ("latent" if cfg.kv_latent_rank else "dense",) * cfg.n_layers)


def attends(cfg: TransformerConfig) -> bool:
    """Does a prefill call of this model run attention over its rows: has it
    a layer of a kind whose ``_prompt_mixer`` is ``attention`` (the
    decoder-hybrid-decoder's differential attention too)? A model of "conv",
    "mamba", "mamba2", "kda" or "retention" layers alone does not."""
    return cfg.sambay or any(
        kind in ("dense", "latent", "full", "window") for kind in _kinds(cfg))


def _embed(p, cfg, tokens):
    """The residual stream at its start, of tokens [R, S] or a step's [B] (->
    [B, 1, D]), and with it the stream's type for the whole block. With
    ``layer_kinds`` float32 (each layer's products take and give
    ``cfg.dtype``): a normalised sublayer adds a whole unit to it, which
    bfloat16 would round at every layer. Without, ``cfg.dtype``, the table
    cast before it is read."""
    table = p["embed"] if cfg.layer_kinds else p["embed"].astype(cfg.dtype)
    x = table[tokens if tokens.ndim == 2 else tokens[:, None]]
    return x.astype(jnp.float32) * cfg.embed_scale if cfg.layer_kinds else x


def _normed(x, norm, cfg):
    """The stream under an RMSNorm, in the products' type."""
    return _rmsnorm(x, norm["scale"], cfg.norm_eps).astype(cfg.dtype)


def _attn_inputs(x, lp, cfg, positions, kind):
    """The stream x [B, S, D] -> the layer's normalised input h, its queries
    and what it keeps of these positions. "full", "window": q [B, S, H, hd]
    and the cache row ``k | v`` [B, S, 2 KVH hd]; "dense": q and (k, v) by
    head; "latent": (q_nope, q_pe, c, k_pe) and the row ``c | k_pe | 0``."""
    h = _normed(x, lp["attn_norm"], cfg)
    if kind == "latent":
        *q, row = _latent_qkv(h, lp["attn"], cfg, positions)
        return h, q, row
    if kind == "dense":
        q, k, v = _qkv(h, lp["attn"], cfg, positions)
        return h, q, (k, v)
    q, k, v = _qkv(h, lp["attn"], cfg, positions, kind in cfg.rope_kinds)
    flat = lambda t: t.reshape(*t.shape[:-2], -1)   # noqa: E731
    return h, q, jnp.concatenate([flat(k), flat(v)], axis=-1)


def _attn_out(h, o, lp, cfg):
    """The attention ``o`` [B, S, H, hd] of a layer with input ``h`` -> what
    the mixer adds to the stream: the gate, then o_proj."""
    a = lp["attn"]
    if cfg.attn_gate:
        with jax.named_scope("attn.gate"):
            o = o * jax.nn.sigmoid(jnp.einsum(
                "...d,dhk->...hk", h, a["gate_proj"]["kernel"].astype(cfg.dtype)))
    return jnp.einsum("...hk,hkd->...d", o,
                      a["o_proj"]["kernel"].astype(cfg.dtype))


def _conv_gates(x, lp, cfg):
    """A "conv" layer's ``in_proj`` on the normalised stream x [.., D]: the
    gated input ``s = B * z`` that is convolved (and kept) and the gate ``C``
    on the convolution's output."""
    h = _normed(x, lp["attn_norm"], cfg)
    b, c, z = jnp.split(_dense(h, lp["conv"]["in_proj"], cfg.dtype), 3, axis=-1)
    return b * z, c


def _conv_out(c, y, lp, cfg):
    return _dense(c * y, lp["conv"]["out_proj"], cfg.dtype)


def _conv_prefill(s, lp, cfg, conv, layer, slots, lengths):
    """A "conv" layer's taps over a prefill call's gated inputs s [R, S, D],
    and ``conv`` with the rows of ``slots`` left at the prompt's last
    ``conv_taps - 1`` positions (zeros where it has none)."""
    from ray_tpu.models.transformer import causal_conv

    tail = cfg.conv_taps - 1
    tail_pos = lengths[:, None] - tail + jnp.arange(tail)[None]
    with jax.named_scope("conv.prefill"):
        y = causal_conv(s, lp["conv"]["conv_kernel"].astype(cfg.dtype), 0)
        # [layer, tap, slot]: the indexed axes come first, [R, K-1, D]
        return y, conv.at[layer, :, slots].set(_rows_at(s, tail_pos))


def _conv_step(s, lp, cfg, conv, layer, keep=None):
    """A "conv" layer's taps over a decode step's gated inputs s [B, 1, D]
    and the kept rows, and the rows shifted by one: every slot's, or with
    ``keep`` [B] those of the slots it marks alone (a prefill call's prompts
    beside this step have just written others)."""
    with jax.named_scope("conv.step"):
        taps = jnp.concatenate([conv[layer], s[:, 0][None]], axis=0)
        y = jnp.einsum("kbd,kd->bd", taps,
                       lp["conv"]["conv_kernel"].astype(cfg.dtype))
        rows = taps[1:]
        if keep is not None:
            rows = jnp.where(keep[None, :, None], rows, conv[layer])
        return y[:, None], conv.at[layer].set(rows)


def _mamba2_inputs(x, lp, cfg):
    """A "mamba2" layer's ``in_proj`` on the normalised stream x [.., D], ONE
    product for all rows: the step sizes before their bias ``dt`` [.., H],
    the convolution's input ``x | B | C`` (what a slot keeps the tail of) and
    the gate ``z`` on the recurrence's output."""
    h = _normed(x, lp["attn_norm"], cfg)
    z, xbc, dt = jnp.split(
        _dense(h, lp["mamba"]["in_proj"], cfg.dtype),
        [cfg.ssm_inner, 2 * cfg.ssm_inner + 2 * cfg.ssm_state], axis=-1)
    return dt, (xbc, z)


def _mamba2_operands(a, dt, m, cfg):
    """The convolved input a [.., I + 2N] and the raw step sizes dt [.., H]
    -> what the recurrence takes: (dt after bias and softplus in float32, x,
    B, C, A [H])."""
    a = jax.nn.silu(a)
    x, Bm, Cm = jnp.split(a, [cfg.ssm_inner, cfg.ssm_inner + cfg.ssm_state],
                          axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + m["dt_bias"])
    return dt, x, Bm, Cm, -jnp.exp(m["A_log"])


def _mamba2_skip(y, x, m, cfg):
    """y [.., I] float32 with the skip ``D[h] x`` of each head."""
    return y + jnp.repeat(m["D"], cfg.ssm_inner // cfg.ssm_heads) \
        * x.astype(jnp.float32)


def _mamba2_prefill(xbc, dt, lp, cfg, kept, layer, slots, lengths, in_prompt):
    """A "mamba2" layer's recurrence over a prefill call's rows (xbc [R, S, I
    + 2N], dt [R, S, H]) from a zero state, and ``kept`` (the scan's states,
    the convolution's tails) with the rows of ``slots`` left at the prompts'
    last position: the state after it and the ``ssm_conv - 1`` inputs before
    the next (zeros where the prompt has none). Padding behind a prompt
    neither advances the state (``dt = 0``) nor enters the tail."""
    from ray_tpu.models.transformer import causal_conv
    from ray_tpu.ops.ssd import ssd_scan

    ssm, conv = kept
    m, tail = lp["mamba"], cfg.ssm_conv - 1
    tail_pos = lengths[:, None] - tail + jnp.arange(tail)[None]
    with jax.named_scope("ssd.prefill"):
        dt, x, Bm, Cm, A = _mamba2_operands(causal_conv(
            xbc, m["conv_kernel"].astype(cfg.dtype),
            m["conv_bias"].astype(cfg.dtype)), dt, m, cfg)
        y, state = ssd_scan(jnp.where(in_prompt[..., None], dt, 0.0), x, Bm,
                            Cm, A)
        # [layer, tap, slot]: the indexed axes come first, [R, K-1, I + 2N]
        return _mamba2_skip(y, x, m, cfg), (
            ssm.at[layer, slots].set(state),
            conv.at[layer, :, slots].set(_rows_at(xbc, tail_pos)))


def _mamba2_step(xbc, dt, lp, cfg, kept, layer, keep, op):
    """A "mamba2" layer's recurrence over a decode step's rows (xbc [B, 1, I
    + 2N], dt [B, 1, H]): the taps over the kept tail and the new input, one
    step of every slot's state in place (``ops/ssd.py:ssd_step``), the tail
    shifted by one; with ``keep`` [B] only the slots it marks move."""
    from ray_tpu.ops.ssd import ssd_step

    ssm, conv = kept
    m = lp["mamba"]
    with jax.named_scope("ssd.step"):
        taps = jnp.concatenate([conv[layer], xbc[:, 0][None]], axis=0)
        dt, x, Bm, Cm, A = _mamba2_operands(
            jnp.einsum("kbc,kc->bc", taps, m["conv_kernel"].astype(cfg.dtype))
            + m["conv_bias"].astype(cfg.dtype), dt[:, 0], m, cfg)
        y, ssm = ssd_step(ssm, layer, dt, x, Bm, Cm, A, keep,
                          name="ssd_step" if op == "decode" else "ssd_" + op)
        rows = taps[1:]
        if keep is not None:
            rows = jnp.where(keep[None, :, None], rows, conv[layer])
        return _mamba2_skip(y, x, m, cfg)[:, None], (ssm, conv.at[layer].set(rows))


def _mamba2_out(z, y, lp, cfg):
    """The gated norm over all ``ssm_inner`` channels of the recurrence's y
    [.., I] float32, then ``out_proj``."""
    m = lp["mamba"]
    with jax.named_scope("ssd.gate_norm"):
        y = _rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)),
                     m["norm"]["scale"], cfg.norm_eps).astype(cfg.dtype)
    return _dense(y, m["out_proj"], cfg.dtype)


def _kda_inputs(x, lp, cfg):
    """A "kda" layer's projections of the normalised stream x [.., D], for
    all rows: ONE product for ``q | k | v`` before their convolutions (what a
    slot keeps the tail of), one for the inner halves of the two low-rank
    gates and ``beta``. Returns (the decay gate's ``f`` [.., H K], ``beta``
    [.., H] float32, the output gate [.., H K]: each as its product left it),
    ``q | k | v``."""
    m, r = lp["kda"], cfg.kda_gate_rank
    h = _normed(x, lp["attn_norm"], cfg)
    with jax.named_scope("kda.in_proj"):
        qkv = _dense(h, m["qkv_proj"], cfg.dtype)
    with jax.named_scope("kda.gates"):
        inner = jnp.einsum("...d,df->...f", h, jnp.concatenate(
            [m[n]["kernel"] for n in ("f_a", "g_a", "b_proj")],
            axis=-1).astype(cfg.dtype))
        f = _dense(inner[..., :r], m["f_b"], cfg.dtype)
        gate = _dense(inner[..., r:2 * r], m["g_b"], cfg.dtype)
        beta = jax.nn.sigmoid(inner[..., 2 * r:].astype(jnp.float32))
    return (f, beta, gate), qkv


def _kda_operands(a, f, m, cfg):
    """The convolved ``q | k | v`` a [.., 3 H K] and the decay gate's ``f``
    [.., H K] -> what the recurrence takes: q, k (unit length a head, q times
    ``K^-0.5``), v [.., H, K] and the log-decay g [.., H, K] float32. In XLA:
    a decode step's rows and the tests'; a prefill call's are
    ``ops/kda.py:kda_prefill``'s."""
    from ray_tpu.models.transformer import kda_log_decay, kda_qk_norm

    H, K = cfg.kda_heads, cfg.kda_head_dim
    q, k, v = (t.reshape(*t.shape[:-1], H, K)
               for t in jnp.split(jax.nn.silu(a), 3, axis=-1))
    q, k = kda_qk_norm(q, k)
    return q, k, v, kda_log_decay(f, m["dt_bias"], m["A_log"])


def _kda_prefill(qkv, gates, lp, cfg, kept, layer, slots, lengths):
    """A "kda" layer's recurrence over a prefill call's rows (qkv [R, S, 3 H
    K], gates: f, beta and the output gate of those rows) from a zero state,
    and ``kept`` (the states, the convolutions' tails) with the rows of
    ``slots`` left at the prompts' last position: the state after it and the
    ``kda_conv - 1`` inputs before the next (zeros where the prompt has
    none). Between the convolutions (the silu fused behind them) and
    ``o_proj`` there is ONE kernel: the norms of q and k, the log-decay, beta
    and the output's norm and gate happen in its tile
    (``ops/kda.py:kda_prefill``). Padding behind a prompt neither moves the
    state nor enters the tail: the kernel does nothing for a chunk that lies
    wholly behind ``lengths`` (it reads none of these arrays there, leaves
    the state alone and writes zeros to ``o``) and forces no decay and no
    update from ``lengths`` on inside the chunk that holds the end, where
    ``o`` behind the end is nobody's but finite. The padded rows of ``o`` go
    on through ``o_proj`` and the experts like any row."""
    from ray_tpu.models.transformer import causal_conv
    from ray_tpu.ops.kda import kda_prefill

    ssm, conv = kept
    m, tail = lp["kda"], cfg.kda_conv - 1
    tail_pos = lengths[:, None] - tail + jnp.arange(tail)[None]
    with jax.named_scope("kda.conv"):
        a = jax.nn.silu(causal_conv(qkv, m["conv_kernel"].astype(cfg.dtype), 0))
    o, state = kda_prefill(a, *gates, m["dt_bias"], m["A_log"],
                           m["o_norm"]["scale"], lengths, eps=cfg.norm_eps)
    # [layer, tap, slot]: the indexed axes come first, [R, K-1, 3 H K]
    return o, (ssm.at[layer, slots].set(state),
               conv.at[layer, :, slots].set(_rows_at(qkv, tail_pos)))


def _kda_step(qkv, gates, lp, cfg, kept, layer, keep, op):
    """A "kda" layer's recurrence over a decode step's rows (qkv [B, 1, 3 H
    K], gates: f, beta and the output gate of those rows): the taps over the
    kept tail and the new input, one step of every slot's state in place
    (``ops/kda.py:kda_step``), the tail shifted by one, the output's norm and
    gate; with ``keep`` [B] only the slots it marks move."""
    from ray_tpu.ops.kda import kda_step

    ssm, conv = kept
    m = lp["kda"]
    f, beta, gate = gates
    with jax.named_scope("kda.conv"):
        taps = jnp.concatenate([conv[layer], qkv[:, 0][None]], axis=0)
        q, k, v, g = _kda_operands(
            jnp.einsum("kbc,kc->bc", taps, m["conv_kernel"].astype(cfg.dtype)),
            f[:, 0], m, cfg)
    o, ssm = kda_step(ssm, layer, q, k, v, g, beta[:, 0], keep,
                      name="kda_step" if op == "decode" else "kda_" + op)
    with jax.named_scope("kda.out"):  # [B, 1, H K], what o_proj reads
        o = _rmsnorm(o, m["o_norm"]["scale"], cfg.norm_eps) * jax.nn.sigmoid(
            gate.astype(jnp.float32).reshape(o.shape))
        o = o.astype(cfg.dtype).reshape(gate.shape)
    rows = taps[1:]
    if keep is not None:
        rows = jnp.where(keep[None, :, None], rows, conv[layer])
    return o, (ssm, conv.at[layer].set(rows))


def _retention_inputs(x, lp, cfg, positions):
    """A "retention" layer's projections of the normalised stream x [.., D],
    for all rows: q [.., H, hd], k and v [.., KVH, hd] as an attention layer
    makes them (``_qkv``: three products, the per-head norms, then the
    rotation by the rows' ``positions`` where ``rope_kinds`` names the kind)
    and the log-gates [.., KVH], float32 from the product on (one gate a
    key/value head, kernel and bias). Returns (log-gates, [q, k, v])."""
    m = lp["retention"]
    h = _normed(x, lp["attn_norm"], cfg)
    with jax.named_scope("retention.inputs"):
        q, k, v = _qkv(h, m, cfg, positions, "retention" in cfg.rope_kinds)
        log_g = jax.nn.log_sigmoid(_dense(h, m["g_proj"], jnp.float32))
    return log_g, [q, k, v]


def _retention_prefill(qkv, log_g, cfg, kept, layer, slots, lengths):
    """A "retention" layer's recurrence over a prefill call's rows (q [R, S,
    H, hd], k, v [R, S, KVH, hd], log_g [R, S, KVH]) from a zero state, and
    ``kept`` (the state, the pending positions, their count) with the states
    of ``slots`` left at the prompts' last position
    (the kernel writes each row's state into its slot of the leaf itself; a
    padding row's slot is the one past the last, which the leaf has for it)
    and their pending positions EMPTIED: the slot's last tenant's are not
    this request's, and a null position adds nothing whenever it is folded.
    The kernel does nothing for a chunk that lies wholly behind ``lengths``
    and forces no decay and no key from ``lengths`` on inside the chunk that
    holds the end; ``o`` behind a prompt's end is zeros, which go on through
    ``o_proj`` and the MLP like any row."""
    from ray_tpu.ops.retention import retention_prefill

    ssm, pending, count = kept
    q, k, v = (t.reshape(*t.shape[:2], -1) for t in qkv)
    with jax.named_scope("retention.scan"):
        o, ssm = retention_prefill(q, k, v, log_g, lengths, ssm, layer, slots,
                                   heads=(cfg.n_heads, cfg.n_kv_heads))
        # [layer, :, :, slot]: a padding row's slot is past the last, dropped
        pending = pending.at[layer, :, :, slots].set(0.0, mode="drop")
    return o.reshape(qkv[0].shape), (ssm, pending, count)


def _retention_step(qkv, log_g, cfg, kept, layer, keep, op):
    """A "retention" layer's recurrence over a decode step's rows (q [B, 1,
    H, hd], k, v [B, 1, KVH, hd], log_g [B, 1, KVH]), as the count of pending
    positions says (``ops/retention.py:retention_decode``): every slot's
    state read and the position kept beside it (``retention_read``), or, every
    ``FOLD``-th step and whenever a prefill call carries the step, the
    pending positions and this one folded into the state in place
    (``retention_step``, ``retention_riding``). A slot ``keep`` [B] does not
    mark takes a null position."""
    from ray_tpu.ops.retention import retention_decode

    ssm, pending, count = kept
    q, k, v = (t[:, 0] for t in qkv)
    with jax.named_scope("retention.step"):
        o, ssm, pending = retention_decode(
            ssm, pending, count, layer, q, k, v, log_g[:, 0], keep,
            riding=op == "riding")
    return o.astype(cfg.dtype)[:, None], (ssm, pending, count)


def _paired_rest(x, o, lp, cfg, valid, name, carried):
    """``_block_rest`` of a sublayer of a shortcut-connected double layer
    (``shortcut_moe``): every sublayer has a dense MLP; the even one also
    computes the pair's expert branch from the SAME normed input and hands
    it on, and the odd one adds what it was handed where it ends. Returns
    (x, load, carried)."""
    x = x + o.astype(x.dtype)
    u = _normed(x, lp["mlp_norm"], cfg)
    x = x + _mlp(u, lp["mlp"], cfg.dtype).astype(x.dtype)
    if "moe" not in lp:
        return x + carried.astype(x.dtype), None, None
    with jax.named_scope("moe.shortcut"):
        carried, load = _experts(
            u, lp, cfg, valid[:, None] if valid.ndim == 1 else valid, name)
    return x, load, carried


def _block_rest(x, o, lp, cfg, valid, name):
    """The block after its mixer's output ``o`` [.., D], in the stream's
    type: the residual (a norm on the way out under ``sandwich_norm``), the
    MLP or the experts likewise. Returns (x, load)."""
    o = o.astype(x.dtype)
    if cfg.sandwich_norm:
        o = _rmsnorm(o, lp["post_attn_norm"]["scale"], cfg.norm_eps)
    r = cfg.residual_scale  # what a sublayer adds goes in times this
    x = x + (o if r == 1.0 else o * r)
    # a plain step's mask comes as the slots' [B] (``_forward``)
    y, load = _ffn(_normed(x, lp["mlp_norm"], cfg), lp, cfg,
                   valid[:, None] if valid.ndim == 1 else valid, name)
    y = y.astype(x.dtype)
    if cfg.sandwich_norm:
        y = _rmsnorm(y, lp["post_mlp_norm"]["scale"], cfg.norm_eps)
    return x + (y if r == 1.0 else y * r), load


def _prompt_mixer(cfg, index, slots, lengths, kind, at, lp, kept, q, row):
    """Layer ``at`` of its ``kind`` proper, over a prefill call's rows: the
    gated inputs ``row`` [R, S, D] under a "conv" layer's taps, or the queries
    ``q`` over the call's own keys and values (the flash kernel; a "latent"
    layer's expanded to heads). Returns what comes out, [R, S, D] or [R, S, H,
    hd], and ``kept`` (the kind's pages, rings or conv rows) with the
    prompts' state written."""
    from ray_tpu.ops.attention import attention as attention_op

    if kind == "conv":
        return _conv_prefill(row, lp, cfg, kept, at, slots, lengths)
    if kind == "mamba2":
        return _mamba2_prefill(row, q, lp, cfg, kept, at, slots, lengths,
                               index[1])
    if kind == "kda":
        return _kda_prefill(row, q, lp, cfg, kept, at, slots, lengths)
    if kind == "retention":
        return _retention_prefill(row, q, cfg, kept, at, slots, lengths)
    _, _, page, offset, _, ring_pos = index
    if kind == "latent":
        kept = kept.at[at, page, offset].set(row, mode="drop")
        return _latent_attention_expanded(*q, lp["attn"], cfg, lengths), kept
    rep = cfg.n_heads // cfg.n_kv_heads
    if kind == "dense":
        k, v = row
        kept = (kept[0].at[at, page, offset].set(k, mode="drop"),
                kept[1].at[at, page, offset].set(v, mode="drop"))
        if rep != 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return attention_op(q, k, v, causal=True, impl=cfg.attention_impl,
                            lens=lengths), kept
    R, S = row.shape[:2]
    if kind == "window":
        kept = _write_rings(kept, at, slots, row, ring_pos)
    else:
        kept = kept.at[at, page, offset].set(row, mode="drop")
    k, v = (t.reshape(R, S, cfg.n_kv_heads, -1)
            for t in jnp.split(row, 2, axis=-1))
    return attention_op(
        q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
        causal=True, impl=cfg.attention_impl,
        window=cfg.window if kind == "window" else 0, lens=lengths), kept


def _step_mixer(cfg, index, page_size, keep, op, kind, at, lp, kept, q, row):
    """Layer ``at`` of its ``kind`` proper, over a decode step's rows: the
    gated inputs ``row`` [B, 1, D] and the kept rows under the taps, or each
    slot's new row into ``kept`` and its query over what the slot holds
    there: through the kernel ``<paged|window>_gqa_<op>``, through
    ``mla_decode`` over the latent rows, or ("dense") over a gather of the
    slot's every page."""
    if kind == "conv":
        return _conv_step(row, lp, cfg, kept, at, keep)
    if kind == "mamba2":
        return _mamba2_step(row, q, lp, cfg, kept, at, keep, op)
    if kind == "kda":
        return _kda_step(row, q, lp, cfg, kept, at, keep, op)
    if kind == "retention":
        return _retention_step(row, q, cfg, kept, at, keep, op)
    slot, positions, page, offset, work, ring_work, _ = index
    if kind == "latent":
        kept = kept.at[at, page, offset].set(row[:, 0], mode="drop")
        o = _latent_attention_absorbed(q[0][:, 0], q[1][:, 0], kept, work, at,
                                       lp["attn"], cfg)
    elif kind == "dense":
        return _gather_attention(cfg, page, offset, work, at, kept, q, *row)
    elif kind == "window":
        # a slot past the last (beside a prompt: one that is not active) is
        # dropped, as _write_rings drops a padding row's
        kept = kept.at[at, slot, positions % cfg.window].set(row[:, 0])
        o = _paged_attention(q[:, 0], _ring_blocks(kept, cfg, page_size),
                             ring_work, at, "window_gqa_" + op, cfg)
    else:
        kept = kept.at[at, page, offset].set(row[:, 0], mode="drop")
        o = _paged_attention(q[:, 0], kept, work, at, "paged_gqa_" + op, cfg)
    return o[:, None], kept


def _gather_attention(cfg, page, offset, work, layer, kept, q, k, v):
    """The "dense" kind's decode step: the new keys and values k, v [B, 1,
    KVH, HD] into ``kept`` (the 5-D k and v), every slot's pages gathered
    straight from them, [B, Lmax, KVH, HD], and grouped-query attention over
    that under the mask, without materializing repeated heads."""
    block_tables, kv_mask = work
    B, Lmax = kv_mask.shape
    KVH, HD = kept[0].shape[3:]
    new_k = kept[0].at[layer, page, offset].set(k[:, 0], mode="drop")
    new_v = kept[1].at[layer, page, offset].set(v[:, 0], mode="drop")
    k_all = new_k[layer, block_tables].reshape(B, Lmax, KVH, HD)
    v_all = new_v[layer, block_tables].reshape(B, Lmax, KVH, HD)
    qg = q[:, 0].reshape(B, KVH, cfg.n_heads // cfg.n_kv_heads, HD)
    scores = jnp.einsum("bkgd,blkd->bkgl", qg, k_all,
                        preferred_element_type=jnp.float32) * (
                            1.0 / (HD ** 0.5))
    scores = jnp.where(kv_mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    attn = jnp.einsum("bkgl,blkd->bkgd", probs, v_all)
    return attn.reshape(B, 1, cfg.n_heads, HD), (new_k, new_v)


def rides(cfg: TransformerConfig) -> bool:
    """May a decode step's rows ride this model's prefill call (``prefill``'s
    ``riders``)? A policy: both programs of every model but the
    decoder-hybrid-decoder are ``_forward``; the "dense" kind's mixer has not
    been run with both sides yet, the "latent" kind's only as one kind of a
    model with ``layer_kinds``."""
    return bool(cfg.layer_kinds) and not cfg.sambay


def _forward(p, cfg, cache, prompt=None, step=None):
    """The layers of an "rms" block over a prefill call's rows (``prompt``:
    tokens [R, S], lengths, block_tables, slots), over a decode step's
    (``step``: last_tokens [B], seq_lens, block_tables, active), or over both
    in one program: the decode rows RIDE the prefill call. Everything that
    works row by row (norms, projections, gates, the residual, the MLP or the
    experts, the head) runs ONCE over all the rows laid end to end, [1, R S +
    B, D]: every held weight is read once, and the experts sort both sides'
    rows together. Only a layer's mixer proper runs a side at a time, on its
    own rows and its own part of the cache, the prompts' first
    (``_prompt_mixer``: flash attention or the convolution over the bucket;
    ``_step_mixer``: the paged kernel, the gather or the taps over the kept
    rows): the slots a call fills and the slots that decode are disjoint, so
    are their pages, rings and conv rows, and a slot that is not active
    writes nothing beside a prompt (alone it computes into its own rows, or
    the scratch page, and nobody reads them). Returns the logits of each side
    it was given, [R, vocab] and [B, vocab] (a pair where both), and the
    cache; the routing it leaves in ``moe_load`` is that of all its rows.

    A model without ``layer_kinds`` had a loop of its own in each program
    before it took this one. What is marked ``plain`` here (a step's
    positions expanded once, its mask once a layer, a prompt's last position
    found after the layers) keeps those programs' text as it was, reshape
    for reshape, as ``_embed`` keeps their stream: no arithmetic hangs on it,
    and the next change to those programs folds it."""
    kinds, plain = _kinds(cfg), not cfg.layer_kinds
    kept = {"dense": (cache.k, cache.v), "latent": cache.rows,
            "full": cache.pages, "window": cache.rings, "conv": cache.conv,
            "mamba2": (cache.ssm, cache.conv), "kda": (cache.ssm, cache.conv),
            "retention": (cache.ssm, cache.pending, cache.pending_count)}
    xs, positions, valid, mixers = [], [], [], []
    if prompt is not None:
        tokens, lengths, tables, slots = prompt
        index = _prompt_index(cfg, cache, tokens.shape[1], lengths, tables)
        xs.append(_embed(p, cfg, tokens))
        positions.append(index[0])
        valid.append(index[1])
        last = index[4]
        mixers.append(functools.partial(_prompt_mixer, cfg, index, slots,
                                        lengths))
    if step is not None:
        last_tokens, seq_lens, tables, active = step
        index = _decode_index(cfg, cache, seq_lens, tables, active)
        xs.append(_embed(p, cfg, last_tokens))
        _, at, *_, at_tables = index
        positions.append(at_tables if plain else at[:, None])
        valid.append(active if plain else active[:, None])
        # a "retention" layer's slot that does not decode appends a null
        # position, so that a fold finds nothing of it to take
        keep = active if "retention" in kinds else None
        if prompt is not None:
            keep = active
            index = (jnp.where(active, index[0], active.shape[0]), *index[1:])
        mixers.append(functools.partial(
            _step_mixer, cfg, index, _page_size(cache), keep,
            "decode" if prompt is None else "riding"))
    shapes = [x.shape[:2] for x in xs]
    x, positions, valid = map(_end_to_end, (xs, positions, valid))
    name = "moe_gmm_decode" if prompt is None else "moe_gmm_prefill"
    loads, carried = [], None
    for i, kind in enumerate(kinds):
        lp, at = p[f"layer_{i}"], kinds[:i].count(kind)
        if kind == "conv":
            q, (row, gate) = None, _conv_gates(x, lp, cfg)
        elif kind == "mamba2":  # "q": the step sizes, split as queries are
            q, (row, gate) = _mamba2_inputs(x, lp, cfg)
        elif kind == "kda":  # "q": the two gates and beta, split likewise
            q, row = _kda_inputs(x, lp, cfg)
        elif kind == "retention":  # "q": the log-gates; the row: q, k, v
            q, row = _retention_inputs(x, lp, cfg, positions)
        else:
            h, q, row = _attn_inputs(x, lp, cfg, positions, kind)
        outs = []
        for mixer, q_, row_ in zip(mixers, _apart(q, shapes),
                                   _apart(row, shapes)):
            o, kept[kind] = mixer(kind, at, lp, kept[kind], q_, row_)
            outs.append(o)
        o = _end_to_end(outs)
        if kind == "conv":
            o = _conv_out(gate, o, lp, cfg)
        elif kind == "mamba2":
            o = _mamba2_out(gate, o, lp, cfg)
        elif kind == "kda":  # each side's norm and gate are its mixer's
            o = _dense(o, lp["kda"]["o_proj"], cfg.dtype)
        elif kind == "retention":
            o = jnp.einsum("...hk,hkd->...d", o, lp["retention"]["o_proj"][
                "kernel"].astype(cfg.dtype))
        else:
            o = _attn_out(h, o, lp, cfg)
        if cfg.shortcut_moe:
            x, load, carried = _paired_rest(x, o, lp, cfg, valid, name,
                                            carried)
        else:
            x, load = _block_rest(x, o, lp, cfg, valid, name)
        if load is not None:
            loads.append(load)
    sides = _apart(x, shapes)
    if prompt is not None:  # each prompt's last real position
        at = jnp.maximum(lengths - 1, 0)[:, None, None] if plain \
            else last[..., None]
        sides[0] = jnp.take_along_axis(sides[0], at, axis=1)
    logits = _head(jnp.concatenate([t[:, 0] for t in sides]), p, cfg)
    if len(sides) == 2:
        R = shapes[0][0]
        logits = logits[:R], logits[R:]
    k, v = kept["dense"]
    state = next((kind for kind in ("mamba2", "kda") if kind in kinds), "")
    ssm, pending, count = kept["retention"]
    ssm, conv = kept[state] if state else (ssm, kept["conv"])
    if count is not None and step is not None:
        # a step that rode a prefill call folded, whatever it found
        from ray_tpu.ops.retention import advance

        count = advance(count) if prompt is None else jnp.zeros_like(count)
    return logits, Cache(
        k=k, v=v, rows=kept["latent"], pages=kept["full"],
        rings=kept["window"], ssm=ssm, conv=conv,
        moe_load=jnp.stack(loads) if loads else None, pending=pending,
        pending_count=count)


def _end_to_end(sides):
    """One or two sides' rows ([R, S, ..] and [B, 1, ..]) as ONE array: a side
    alone as it is, two end to end, [1, R S + B, ..]."""
    if len(sides) == 1:
        return sides[0]
    return jnp.concatenate(
        [t.reshape(1, -1, *t.shape[2:]) for t in sides], axis=1)


def _apart(x, shapes):
    """``_end_to_end``'s inverse: the sides of ``x`` (None: of nothing; a
    list, as the "latent" kind's queries are: of each of its arrays), of
    ``shapes`` (R, S) and (B, 1)."""
    if x is None or len(shapes) == 1:
        return [x] * len(shapes)
    if isinstance(x, (list, tuple)):
        return [list(side) for side in zip(*(_apart(t, shapes) for t in x))]
    (R, S), (B, _) = shapes
    return [x[:, :R * S].reshape(R, S, *x.shape[2:]),
            x[:, R * S:].reshape(B, 1, *x.shape[2:])]


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def prefill(params: Any, cfg: TransformerConfig, cache: Cache,
            tokens: jax.Array, lengths: jax.Array,
            block_tables: jax.Array, slots: Optional[jax.Array] = None,
            riders: Optional[Tuple[jax.Array, ...]] = None
            ) -> Tuple[jax.Array, Cache]:
    """Run the prompt forward, write KV pages, return last-position logits.

    tokens: [B, S] padded with PAD after `lengths`; block_tables: [B, MP].
    Returns logits [B, vocab] at position lengths-1 and the updated cache.
    ``slots`` [B]: the slot each row fills, for a model that keeps state by
    slot (``layer_kinds``); such a model leaves its state at position
    ``lengths - 1``, not at the padded ``S - 1``, and runs its cross-decoder
    on that position alone. The engine always gives ``slots`` for such a
    model: one row an admitted request, and for a padding row (length 0, a
    block table of zeros) the slot past the last, whose writes the scatters
    drop. Such a row reaches no expert, and what it writes by position lands
    on the scratch page, in every kind of model. Where no ``slots`` is
    given, row ``b`` fills slot ``b``: that is the path of the benchmark's
    check alone (``benchmarks/jobs/serve.py:reference_check`` calls an
    every-slot ``[max_num_seqs, S]`` batch without it), and goes once the
    harness calls ``[1, S]`` with a slot.

    ``riders``: the operands of ``decode_step`` after the cache (last_tokens,
    seq_lens, block_tables, active, all ``[max_num_seqs, ..]``), for a model
    that ``rides``: the call then runs that decode step too, on slots none of
    its rows fills, and returns ``((logits [B, vocab], the step's logits
    [max_num_seqs, vocab]), cache)``: what the prompts and the step would have
    computed one after the other, with every held weight read once.
    """
    if riders is not None and not rides(cfg):
        raise ValueError("no decode rows ride this model's prefill call")
    if slots is None:
        slots = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    rows = (tokens, lengths, block_tables, slots)
    if cfg.sambay:
        return _hybrid_prefill(params["params"], cfg, cache, *rows)
    return _forward(params["params"], cfg, cache, rows, riders)


@functools.partial(jax.jit, donate_argnums=(0,))
def place_rows(buffer: jax.Array, rows: jax.Array, slots: jax.Array
               ) -> jax.Array:
    """buffer [B, V] with ``rows`` [R, V] written at the rows ``slots`` [R],
    traced: one program a number of rows whichever slots, so the engine can
    gather the logits of a phase's prefill calls by slot without a shape that
    depends on how many requests it admitted. A slot past the last (a padding
    row's) is dropped."""
    return buffer.at[slots].set(rows, mode="drop")


@jax.jit
def split_key(rng: jax.Array):
    """``jax.random.split(rng)`` as one program, the two keys apart: the
    engine's stream and the key of one sampler call (eagerly it is a split
    and two slices, each a dispatch of its own, once a step)."""
    stream, key = jax.random.split(rng)
    return stream, key


@jax.jit
def select_rows(mask: jax.Array, new: jax.Array, old: jax.Array) -> jax.Array:
    """[B] ``new`` where ``mask``, else ``old``: how the engine puts the
    tokens a prefill phase sampled (or an imported request's last one) among
    the decode step's, on the device and at one shape however many they are."""
    return jnp.where(mask, new, old)


def _head(last, p, cfg):
    """Final norm and output head on [B, d] -> float32 logits [B, vocab]."""
    last = _rmsnorm(last, p["final_norm"]["scale"], cfg.norm_eps
                    ).astype(cfg.dtype)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bd,vd->bv", last, p["embed"].astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bd,dv->bv", last, p["lm_head"].astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
    logits = logits.astype(jnp.float32)
    return logits if cfg.logit_scale == 1.0 else logits * cfg.logit_scale


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def decode_step(params: Any, cfg: TransformerConfig, cache: Cache,
                last_tokens: jax.Array, seq_lens: jax.Array,
                block_tables: jax.Array, active: jax.Array
                ) -> Tuple[jax.Array, Cache]:
    """One batched decode step over all slots: [B] tokens -> [B, vocab].

    Inactive slots compute garbage into scratch page 0 and reach no expert.
    The new token's KV is written at position seq_lens before attention, so
    the mask is pos <= seq_lens.
    """
    rows = (last_tokens, seq_lens, block_tables, active)
    if cfg.sambay:
        return _hybrid_decode(params["params"], cfg, cache, *rows)
    return _forward(params["params"], cfg, cache, step=rows)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("max_top_k",))
def sample_tokens(logits: jax.Array, rng: jax.Array, temps: jax.Array,
                  top_ks: jax.Array, top_ps: jax.Array,
                  seeds: jax.Array, steps: jax.Array,
                  max_top_k: int = 64) -> jax.Array:
    """Per-slot sampling: greedy when temp==0, else temp/top-k/top-p over a
    static top-``max_top_k`` shortlist (keeps the program shape static).

    One program with two branches, taken on the device from ``temps``
    (``jax.lax.cond``: no host read, no second program):

    - no slot has a positive temperature: ``argmax`` over the vocabulary and
      nothing else. No shortlist, softmax, cumulative sum or key is computed,
      since no row would read them;
    - any slot samples: the shortlist (``jax.lax.top_k`` over the whole
      vocabulary), temperature, top-k and top-p masks over it, one key a
      slot and a categorical draw; the greedy rows of such a batch still take
      the same ``argmax``.

    A row's token is the same whichever branch its batch takes.

    ``seeds[b] >= 0`` gives that slot its own reproducible stream
    (PRNGKey(seed) folded with the slot's step count), independent of batch
    composition; ``seeds[b] < 0`` draws from the engine-global stream."""
    B, V = logits.shape
    K = min(max_top_k, V)

    def greedy_path():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def shortlist_path():
        greedy = jnp.argmax(logits, axis=-1)

        vals, idx = jax.lax.top_k(logits, K)  # [B, K] descending
        safe_t = jnp.maximum(temps, 1e-6)[:, None]
        scaled = vals / safe_t
        ranks = jnp.arange(K, dtype=jnp.int32)[None]
        k_lim = jnp.where(top_ks <= 0, K, jnp.minimum(top_ks, K))[:, None]
        mask = ranks < k_lim
        probs = jax.nn.softmax(jnp.where(mask, scaled, -1e30), axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose cumulative prob before them is < top_p
        mask = mask & ((cum - probs) < top_ps[:, None])
        final = jnp.where(mask, scaled, -1e30)

        global_keys = jax.random.split(rng, B)
        seeded_keys = jax.vmap(
            lambda s, st: jax.random.fold_in(jax.random.PRNGKey(s), st)
        )(jnp.maximum(seeds, 0).astype(jnp.uint32), steps.astype(jnp.uint32))
        keys = jnp.where((seeds >= 0)[:, None], seeded_keys, global_keys)
        sampled_pos = jax.vmap(jax.random.categorical)(keys, final)
        sampled = jnp.take_along_axis(idx, sampled_pos[:, None], axis=1)[:, 0]
        return jnp.where(temps <= 0, greedy, sampled).astype(jnp.int32)

    return jax.lax.cond(jnp.any(temps > 0), shortlist_path, greedy_path)
