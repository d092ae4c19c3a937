"""Inference forward passes with a paged KV cache (TPU-native vLLM core).

Reference capability: ray.llm serves models through vLLM's PagedAttention
engine (llm/_internal/serve/engines/vllm/vllm_engine.py). The TPU redesign
keeps the *cache geometry* idea — KV lives in fixed-shape pages, sequences
own pages through a block table — but implements it as pure-jnp programs so
every prefill bucket and the decode step are each ONE compiled XLA program
with static shapes (no dynamic shapes, no host sync inside the step).

ONE cache type (``Cache``) holds every model's state by KIND of layer, side
by side under the kinds' names; what a kind is, what it keeps and how its
mixer reads it is said ONCE, in the kind's module under ``llm/kinds/``, and
``llm/kinds/__init__.py:KINDS`` is the table everything here asks.
``block_tables`` [max_num_seqs, pages_per_seq] int32 hands out the pages of
whichever kinds are ``paged``, and page 0 is scratch: masked-out writes
(padding, inactive slots) land there. Both programs take the cache donated
and write it in place: per layer one scatter a leaf whose operand is the
whole array and whose indices are (layer, page, offset), B rows in decode,
and in prefill the S positions of each row it is given. Nothing slices a
layer out or writes one back, so a step's cache traffic is the rows it
writes, not the cache.

ONE per-layer composition (``_forward``) is both programs of every model but
the decoder-hybrid-decoder: a prompt side, a step side, or both (``prefill``'s
``riders``; ``rides`` says for which models, ``llm/engine.py`` when). Where
the kinds of a model with ``layer_kinds`` keep their pages and rings has ONE
definition here (``_prompt_index``, ``_write_rings``, ``_decode_index``,
``_ring_blocks``): a layer is found by its rank among the layers of its
kind, and the two work lists of the paged kernels (``ops/mla.py:live_pages``:
the live pages, the rings' filled blocks) are built once a step. Whatever of
a per-head q/k norm, an attention gate, sandwich norms, a scaled embedding, a
multiplier on what a sublayer adds to the stream (``residual_scale``), a
softmax scale of its own (``attn_scale``: the queries are scaled before the
kernels, which divide by sqrt(head_dim)), a multiplier on the logits
(``logit_scale``) and experts (all of them, or the share ``experts_held`` of
an expert-parallel rank, a router whose last outputs are zero-compute
identity experts, ``zero_experts``) the config asks for is a field the block
reads; at 1.0 / 0 the multipliers trace nothing. Under ``shortcut_moe`` the
layers come in pairs (``_paired_rest``), ``moe_load`` has one entry a PAIR,
and where the router has zero experts a last column that counts the valid
assignments that fell on one.

The decoder-hybrid-decoder ("sambay": Mamba layers, window and full
DIFFERENTIAL attention, gated memory units, cross layers; LayerNorm, no
position embedding) is a composition of its own (``_hybrid_prefill``,
``_hybrid_decode``) over the same cache and the same index: the states of its
"full", "window" and "mamba" kinds (``kinds/attention.py``,
``kinds/mamba.py``). Its prefill runs the self-decoder over the prompt (the
scan through ``ops/ssm.py``, padding passed over with ``dt = 0``; the window
through the flash kernel, blocks left of it skipped) and the cross-decoder on
the ONE last position, since those layers write no state and the engine reads
one row of logits.

Weights come from ``ray_tpu.models.transformer.Transformer`` — this module
reads the same param pytree (checkpoint-compatible with training). The dense
layer math (norms, projections, RoPE, SwiGLU) is written out again here and
must stay the arithmetic of ``models/transformer.py``; a layer with experts
(``n_experts > 0``: the tree has ``moe`` where a dense layer has ``mlp``) is
NOT the training module's capacity-bound dispatch but ``ops/moe.py``:
dropless, rows that are padding or belong to an inactive slot reach no
expert. What routing did in a call comes back beside the states, as
``Cache.moe_load`` (per expert layer, how many real rows each expert got).
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import export

from ray_tpu.llm import kinds
from ray_tpu.llm.kinds import KINDS
from ray_tpu.models.transformer import TransformerConfig, _rope


@jax.tree_util.register_pytree_node_class
class Cache:
    """What a program takes donated and hands back: the states of the model's
    kinds of layer side by side, ``cache[kind]`` each as the kind's module
    says (an array or a tuple of them, all of the kind's layers in one), and
    ``moe_load``: for a model with experts, what the call's routing did. A
    kind the model lacks, or one that keeps nothing, is not there and adds
    nothing to its programs. Among a program's arguments and results the
    leaves lie in the order of ``KINDS``, ``moe_load`` last."""

    def __init__(self, states: Dict[str, Any],
                 moe_load: Optional[jax.Array] = None):
        self.states, self.moe_load = states, moe_load

    def __getitem__(self, kind: str) -> Any:
        return self.states[kind]

    def __contains__(self, kind: str) -> bool:
        return kind in self.states

    def replace(self, states: Dict[str, Any]) -> "Cache":
        return Cache({**self.states, **states}, self.moe_load)

    def tree_flatten(self):
        held = tuple(kind for kind in KINDS if kind in self.states)
        return (*(self.states[kind] for kind in held), self.moe_load), held

    @classmethod
    def tree_unflatten(cls, held, children):
        return cls(dict(zip(held, children[:-1])), children[-1])


export.register_pytree_node_serialization(
    Cache, serialized_name="ray_tpu.llm.Cache",
    serialize_auxdata=lambda held: json.dumps(held).encode(),
    deserialize_auxdata=lambda data: tuple(json.loads(data)))


def _page_size(cache: Cache) -> int:
    """The positions of a page; 0 for a model that keeps nothing by position
    (none of its kinds is ``paged``)."""
    return next((leaf.shape[2] for kind, state in cache.states.items()
                 if KINDS[kind].paged for leaf in jax.tree.leaves(state)), 0)


def init_cache(cfg: TransformerConfig, num_pages: int, page_size: int,
               max_num_seqs: int = 0) -> Cache:
    """``max_num_seqs``: the engine's slots, which only a model that keeps
    state by slot (``layer_kinds``) needs."""
    load = None
    layers = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    if layers:  # a model with zero experts counts them in a last column
        load = jnp.zeros((layers, cfg.n_experts_held + bool(cfg.zero_experts)),
                         jnp.int32)
    if cfg.layer_kinds and not max_num_seqs:
        raise ValueError("a model with layer_kinds keeps window rings and "
                         "recurrent rows by slot: init_cache needs "
                         "max_num_seqs")
    of, states = kinds.of(cfg), {}
    for kind in dict.fromkeys(of):
        if kind not in KINDS:
            raise ValueError(
                f"init_cache: layer kind {kind!r} is no entry of "
                f"llm/kinds (it has {', '.join(KINDS)})")
        state = KINDS[kind].alloc(cfg, of.count(kind), max_num_seqs,
                                  num_pages, page_size)
        if state is not None:
            states[kind] = state
    return Cache(states, load)


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
    operands in float32; under ``ssm_inner_norms`` the step size's low-rank
    input, B and C each through an RMSNorm of their own first."""
    N, R = cfg.ssm_state, cfg.ssm_dt_rank
    a = jax.nn.silu(a)
    x = _dense(a, m["x_proj"], jnp.float32)

    def normed(t, name):
        if not cfg.ssm_inner_norms:
            return t
        return _rmsnorm(t, m[name]["scale"], cfg.norm_eps)

    dt = jax.nn.softplus(_dense(normed(x[..., :R], "dt_norm"), m["dt_proj"],
                                jnp.float32))
    return (a, dt, normed(x[..., R:R + N], "b_norm"),
            normed(x[..., R + N:], "c_norm"))


def _mamba_skip(y, a, m):
    """The scan's y [.., I] float32 with the skip ``D a``."""
    return y + m["D"] * a.astype(jnp.float32)


def _mamba_gated(y, z, m, cfg):
    """y [.., I] float32 with its skip, gated by ``z``, through out_proj."""
    return _dense(y.astype(cfg.dtype) * jax.nn.silu(z), m["out_proj"],
                  cfg.dtype)


def _mamba_output(y, a, z, m, cfg):
    """The scan's y [.., I] float32 -> (the mixer's output, the memory a
    gated memory unit reads: y with the D term, before the gate)."""
    y = _mamba_skip(y, a, m)
    return _mamba_gated(y, z, m, cfg), y


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
    if "window" in cache:
        ring_pos = last - (last - jnp.arange(W, dtype=jnp.int32)[None]) % W
    return positions, in_prompt, page, offset, last, ring_pos


def _ring_block(cfg, page_size):
    """The block a ring is read in: a page's positions where the window is
    whole pages (a block of ``paged_gqa_decode`` then has a page's size
    whichever it reads), else the whole ring."""
    return page_size if page_size and cfg.window % page_size == 0 \
        else cfg.window


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
    page = offset = work = None  # nothing is kept by position: none
    if P:
        cur_page = jnp.take_along_axis(block_tables, at // P, axis=1)[:, 0]
        page = jnp.where(active, cur_page, 0)
        offset = jnp.where(active, positions % P, 0)
        if "dense" in cache:
            Lmax = block_tables.shape[1] * P
            work = block_tables, (jnp.arange(Lmax, dtype=jnp.int32)[None]
                                  <= seq_lens[:, None]) & active[:, None]
        else:
            work = live_pages(positions, active, block_tables, P)
    if "window" not in cache:
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

    pages, rings, (ssm, conv) = cache["full"], cache["window"], cache["mamba"]
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
    return _hybrid_head(x[:, 0], p, cfg), Cache({
        "full": pages, "window": rings, "mamba": kinds.Recurrent(ssm, conv)})


def _hybrid_decode(p, cfg, cache, last_tokens, seq_lens, block_tables, active):
    P, W = _page_size(cache), cfg.window
    # one work list for the full layer and every cross layer, one for the rings
    slot, positions, page, offset, work, ring_work, _ = _decode_index(
        cfg, cache, seq_lens, block_tables, active)

    pages, rings, (ssm, conv) = cache["full"], cache["window"], cache["mamba"]
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
    return _hybrid_head(x, p, cfg), Cache({
        "full": pages, "window": rings, "mamba": kinds.Recurrent(ssm, conv)})


# ---------------------------------------------------------------------------
# the RMSNorm block (models/transformer.py:Block), every model's but the
# decoder-hybrid-decoder's: ONE per-layer composition over the layer's KIND
# (llm/kinds/), with the per-head q/k norm, the attention gate, the sandwich
# norms, the multipliers and the experts the config asks for. ``_embed``
# decides the residual stream's type
# ---------------------------------------------------------------------------


def attends(cfg: TransformerConfig) -> bool:
    """Does a prefill call of this model run ``flash_fwd`` over its rows: has
    it a layer of a kind that ``attends`` (the decoder-hybrid-decoder's
    differential attention too)?"""
    return any(KINDS[kind].attends for kind in kinds.of(cfg))


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


def _paired_rest(x, o, lp, cfg, valid, name, carried):
    """``_block_rest`` of a sublayer of a shortcut-connected double layer
    (``shortcut_moe``: ``n_layers`` counts sublayers, each with its own
    mixer, dense MLP and two norms, so a published layer keeps two rows a
    position): every sublayer has a dense MLP; the even one also
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
    own rows and its own part of the cache, the prompts' first (the kind's
    ``prompt``: flash attention, a convolution or a scan over the bucket; its
    ``step``: the paged kernel, the gather, the taps over the kept rows or
    one step of a state): the slots a call fills and the slots that decode are disjoint, so
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
    of, plain = kinds.of(cfg), not cfg.layer_kinds
    states = dict(cache.states)
    xs, positions, valid, sides = [], [], [], []
    if prompt is not None:
        tokens, lengths, tables, slots = prompt
        index = _prompt_index(cfg, cache, tokens.shape[1], lengths, tables)
        xs.append(_embed(p, cfg, tokens))
        positions.append(index[0])
        valid.append(index[1])
        last = index[4]
        sides.append(("prompt", kinds.Prompt(index, slots, lengths)))
    if step is not None:
        last_tokens, seq_lens, tables, active = step
        index = _decode_index(cfg, cache, seq_lens, tables, active)
        xs.append(_embed(p, cfg, last_tokens))
        _, at, *_, at_tables = index
        positions.append(at_tables if plain else at[:, None])
        valid.append(active if plain else active[:, None])
        if prompt is not None:
            index = (jnp.where(active, index[0], active.shape[0]), *index[1:])
        sides.append(("step", kinds.Step(
            index, _page_size(cache), active,
            "decode" if prompt is None else "riding")))
    shapes = [x.shape[:2] for x in xs]
    x, positions, valid = map(_end_to_end, (xs, positions, valid))
    name = "moe_gmm_decode" if prompt is None else "moe_gmm_prefill"
    loads, carried = [], None
    for i, kind in enumerate(of):
        lp, at, k = p[f"layer_{i}"], of[:i].count(kind), KINDS[kind]
        q, row, aux = k.inputs(x, lp, cfg, positions)
        outs = []
        for (mixer, side), q_, row_ in zip(sides, _apart(q, shapes),
                                           _apart(row, shapes)):
            o, states[kind] = getattr(k, mixer)(cfg, side, at, lp,
                                                states[kind], q_, row_)
            outs.append(o)
        o = k.out(aux, _end_to_end(outs), lp, cfg)
        if cfg.shortcut_moe:
            x, load, carried = _paired_rest(x, o, lp, cfg, valid, name,
                                            carried)
        else:
            x, load = _block_rest(x, o, lp, cfg, valid, name)
        if load is not None:
            loads.append(load)
    rows = _apart(x, shapes)
    if prompt is not None:  # each prompt's last real position
        at = jnp.maximum(lengths - 1, 0)[:, None, None] if plain \
            else last[..., None]
        rows[0] = jnp.take_along_axis(rows[0], at, axis=1)
    logits = _head(jnp.concatenate([t[:, 0] for t in rows]), p, cfg)
    if len(rows) == 2:
        R = shapes[0][0]
        logits = logits[:R], logits[R:]
    for kind, state in states.items():
        states[kind] = KINDS[kind].after(state, prompt is not None,
                                         step is not None)
    return logits, Cache(states, jnp.stack(loads) if loads else None)


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
