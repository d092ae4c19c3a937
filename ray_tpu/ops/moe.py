"""The expert layer of a sparse decoder: one definition of its arithmetic.

``expert_layer`` is a pure function of the rows, which of them are real, and
the layer's weights. Dropless: every real row reaches each of its ``top_k``
experts, whatever the load; there is no capacity and nothing falls through.

1. router logits and their softmax in float32, from the rows upcast to
   float32 and at the highest matmul precision (``D x E`` is free, and matmul
   rounding then has no say in which experts a row gets);
2. ``top_k`` of the probabilities, renormalised to sum to one only with
   ``norm_topk_prob`` (``select_experts``; a selection bias, the ``sigmoid``
   kind's always, chooses and does not weigh: the scores alone do);
3. rows that are padding or belong to an inactive slot get no expert: their
   assignments sort behind every real one, lie in no group, cost no expert
   compute and are not counted in the load;
4. the ``T x top_k`` assignments sorted by expert (static shape whatever is
   real), the rows gathered into that order (XLA's gather, at its bytes'
   rate), and a grouped matmul by the per-expert group sizes for each of the
   three products of ``down(silu(gate(x)) * up(x))``;
5. the weighted float32 sum over a row's experts, back in row order: a decode
   step's few hundred assignments by one gather of ``[T, top_k, D]``, a
   prefill call's thousands choice by choice (``_sum_by_choice``).

One rank of an expert-parallel deployment is told which experts it holds
(``held = (first, count)``): it routes over ALL the router's outputs and
computes the part of the result its own ``count`` experts give. An assignment
to an expert held elsewhere sorts behind every group as an invalid row's
does: it costs no row tile and adds nothing; no stand-in for the other ranks,
no exchange. Zero-compute experts, and buffers by what is HELD: ``_by_held``.

``grouped_matmul`` is a Pallas kernel: for each (group, row tile) pair that
holds a real row it multiplies the tile by that group's matrix, so a step
streams the touched experts' weights once and nothing else; rows no group
owns are never written and hold whatever lay there (step 5 masks them).
``name`` names it in a device trace (``moe_gmm_decode``, ``moe_gmm_prefill``);
off the TPU it runs in interpret mode. Training's ``MoEMLP`` dispatches itself.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one [K, tn] block of an expert's matrix the kernel holds (twice: the
# pipeline's two buffers), in elements: 2 MiB of bfloat16
_RHS_BLOCK_ELEMS = 1 << 20


def select_experts(logits: jax.Array, top_k: int, norm_topk_prob: bool,
                   kind: str = "softmax", bias: Optional[jax.Array] = None,
                   scale: float = 1.0, norm_eps: float = 1e-20):
    """Router logits [..., E] float32 -> (weights [..., top_k], experts
    [..., top_k] int32 in descending order of what chose them, scores
    [..., E]). The scores are the softmax or the sigmoids; the experts are
    the top_k of ``scores + bias``, a per-output selection bias that chooses
    and does not weigh (``softmax`` without one: of the scores); the weights
    are the chosen scores without it, renormalised only with
    ``norm_topk_prob`` (``sigmoid``: divided by their sum plus ``norm_eps``,
    published routers differ in it), times ``scale``."""
    if kind == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        weights, experts = _chosen(scores, top_k, bias)
        if norm_topk_prob:
            weights = weights / jnp.maximum(
                weights.sum(-1, keepdims=True), 1e-9)
    elif kind == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(scores + bias, top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk_prob:
            weights = weights / (weights.sum(-1, keepdims=True) + norm_eps)
    else:
        raise ValueError(f"unknown router kind {kind!r}")
    if kind == "sigmoid" or scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32), scores


def route(x: jax.Array, router: jax.Array, top_k: int, norm_topk_prob: bool,
          kind: str = "softmax", bias: Optional[jax.Array] = None,
          scale: float = 1.0, norm_eps: float = 1e-20
          ) -> Tuple[jax.Array, jax.Array]:
    """x [T, D], router [D, E] -> (weights [T, top_k] float32, experts
    [T, top_k] int32): ``select_experts`` of the float32 logits."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return select_experts(logits, top_k, norm_topk_prob, kind, bias, scale,
                          norm_eps)[:2]


def _tiles(m: int, k: int, n: int, itemsize: int) -> Tuple[int, int]:
    """(rows of a tile, columns of a weight block). Rows: a decode step's
    assignments fit one 128-row tile; above that 256, where a block's product
    takes about as long as its 2 MiB take to arrive. Columns: the contraction
    axis whole, no accumulator. (Which way back: ``_BY_CHOICE_MIN``, below.)"""
    sublane = 8 * 4 // itemsize
    tm = 256 if m >= 256 else -(-m // sublane) * sublane
    tn = n
    while tn % 256 == 0 and k * tn > _RHS_BLOCK_ELEMS:
        tn //= 2
    return tm, tn


def _group_tiles(group_sizes: jax.Array, tiles_m: int, tm: int):
    """Which (group, row tile) pairs hold a real row, in order: group ``g``
    owns rows [offsets[g], offsets[g + 1]) and so the tiles from its first
    row's to its last row's; an empty group owns none. Returns ``offsets
    [E + 1]``, ``group_ids [G]``, ``tile_ids [G]`` and how many of the ``G =
    tiles_m + E - 1`` entries are in use (the rest repeat the last one)."""
    E = group_sizes.shape[0]
    G = tiles_m + E - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    count = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    used = count.sum()
    group_ids = jnp.repeat(jnp.arange(E, dtype=jnp.int32), count,
                           total_repeat_length=G)
    before = jnp.cumsum(count) - count        # entries of earlier groups
    tile_ids = first[group_ids] + jnp.arange(G, dtype=jnp.int32) \
        - before[group_ids]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets.astype(jnp.int32), group_ids,
            jnp.clip(tile_ids, 0, tiles_m - 1).astype(jnp.int32), used)


def _gmm_kernel(offsets, group_ids, tile_ids, lhs, rhs, out, *, tm: int):
    i = pl.program_id(1)
    g = group_ids[i]
    rows = tile_ids[i] * tm + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
    mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
    acc = jnp.dot(lhs[...], rhs[...], preferred_element_type=jnp.float32)
    # a tile two groups share is visited by both, one after the other: keep
    # what the earlier one wrote
    out[...] = jnp.where(mine, acc.astype(out.dtype), out[...])


def _gmm(lhs, rhs, group_sizes, *, name: str, interpret: bool):
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tn = _tiles(m, k, n, lhs.dtype.itemsize)
    assert m % tm == 0, (m, tm)
    offsets, group_ids, tile_ids, used = _group_tiles(group_sizes, m // tm, tm)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, off, gid, tid: (tid[i], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, i, off, gid, tid: (gid[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, off, gid, tid: (tid[i], j)),
            grid=(n // tn, used),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(offsets, group_ids, tile_ids, lhs, rhs)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, name: str) -> jax.Array:
    """lhs [M, K] with its rows sorted by group, rhs [E, K, N], group_sizes
    [E] int32 -> [M, N]: row r of group g is ``lhs[r] @ rhs[g]``. Rows past
    ``group_sizes.sum()`` belong to no group and are left unwritten (whatever
    the buffer held): the caller masks them. M is a multiple of the row tile
    (``_tiles``)."""
    return jax.lax.platform_dependent(
        lhs, rhs, group_sizes,
        tpu=functools.partial(_gmm, name=name, interpret=False),
        default=functools.partial(_gmm, name=name, interpret=True))


def expert_layer(x: jax.Array, valid: jax.Array, router: jax.Array,
                 w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, *,
                 top_k: int, norm_topk_prob: bool,
                 name: str = "moe_gmm", router_kind: str = "softmax",
                 router_bias: Optional[jax.Array] = None,
                 router_scale: float = 1.0, router_norm_eps: float = 1e-20,
                 held: Optional[Tuple[int, int]] = None,
                 zero_experts: int = 0) -> Tuple[jax.Array, jax.Array]:
    """x [T, D], valid [T] bool, router [D, R], w_gate / w_up [E, D, F],
    w_down [E, F, D] -> (y [T, D] in x's dtype, load [E] int32). ``load`` is
    the number of real rows each expert got; a row that is not valid gives
    zeros and loads nobody. The ``router_*`` arguments are ``route``'s.
    ``held = (first, E)``: the matrices are those of experts ``first .. first
    + E`` of the router's routed outputs; without it all of them are here.
    ``zero_experts``: the router's LAST that many outputs are no expert but
    the identity (``R`` = routed + zero): a row that chose one gets ``gate *
    x`` for it, on every rank alike, and ``load`` is ``[E + 1]``, its last
    entry the valid assignments that fell on a zero expert."""
    T, D = x.shape
    E = w_gate.shape[0]
    routed = router.shape[1] - zero_experts
    weights, experts = route(x, router, top_k, norm_topk_prob, router_kind,
                             router_bias, router_scale, router_norm_eps)
    if zero_experts:
        # no row is sorted, gathered or multiplied for them: one weighted
        # sum of gates a row, one multiply (added where y is float32, below)
        with jax.named_scope("moe.zero"):
            free = valid[:, None] & (experts >= routed)
            passed = jnp.where(free, weights, 0.0).sum(-1)[:, None] \
                * x.astype(jnp.float32)
    if held is not None:
        assert held[1] == E and held[0] + E <= routed, (held, E, routed)
        experts = experts - held[0]
        valid = valid[:, None] & (experts >= 0) & (experts < E)
    else:
        assert E == routed, (E, router.shape, zero_experts)
        valid = jnp.broadcast_to(valid[:, None], experts.shape)
        if zero_experts:
            valid = valid & (experts < E)
    # an assignment of an invalid row, or to an expert that is not here, goes
    # to "expert E": behind every group
    flat = jnp.where(valid, experts, E).reshape(-1)
    load = (flat[:, None] == jnp.arange(E, dtype=jnp.int32)).sum(
        0, dtype=jnp.int32)
    M = T * top_k
    tm, _ = _tiles(M, D, w_gate.shape[2], x.dtype.itemsize)
    window = held_window(M, E if held else 0, router.shape[1], tm)
    padded = -(-M // (window or tm)) * (window or tm)
    flat = jnp.pad(flat, (0, padded - M), constant_values=E)
    order = jnp.argsort(flat, stable=True)          # sorted row -> assignment
    if window:
        y = _by_held(x, order, load, weights, w_gate, w_up, w_down,
                     window=window, name=name)
    else:
        y = _by_assignment(x, order, load, weights, valid, w_gate, w_up,
                           w_down, name)
    if zero_experts:
        with jax.named_scope("moe.zero"):
            y = y + passed
        load = jnp.concatenate([load, free.sum(dtype=jnp.int32)[None]])
    return y.astype(x.dtype), load


def _by_assignment(x, order, load, weights, valid, w_gate, w_up, w_down,
                   name):
    """Steps 4 and 5 over ALL ``padded`` sorted assignments, whoever holds
    them -> y [T, D] float32 (a decode step's: in x's dtype, as the sum of
    one gather leaves it)."""
    T, top_k = weights.shape
    M, padded = T * top_k, order.shape[0]
    rows = x[jnp.minimum(order // top_k, T - 1)]     # [padded, D]
    gate = grouped_matmul(rows, w_gate.astype(x.dtype), load, name=name)
    up = grouped_matmul(rows, w_up.astype(x.dtype), load, name=name)
    out = grouped_matmul(jax.nn.silu(gate) * up, w_down.astype(x.dtype), load,
                         name=name)
    # assignment -> its sorted row, then the weighted sum over a row's experts
    where = jnp.zeros(padded, jnp.int32).at[order].set(
        jnp.arange(padded, dtype=jnp.int32))[:M].reshape(T, top_k)
    if M >= _BY_CHOICE_MIN:
        return _sum_by_choice(out, where, weights, valid)
    picked = out[where].astype(jnp.float32)           # [T, top_k, D]
    y = jnp.where(valid[..., None], picked * weights[..., None], 0.0)
    return y.sum(1)


def held_window(assignments: int, held: int, outputs: int, tm: int) -> int:
    """How many sorted rows ``_by_held`` gives the three products at a time
    (whole row tiles of ``tm``, ``_tiles``' for the call), or 0 where the
    layer goes by assignment: a decode step (under ``_BY_CHOICE_MIN``
    assignments), a layer that holds all its experts (``held`` 0) or an
    eighth and more of the router's ``outputs``: the scatter-add back costs
    by the row where the other way's gather runs at its bytes' rate, so it
    pays only where few rows are held (PERF.md section 6, PR 53: the layer
    alone at both ways). Else twice what uniform routing sends here: the loop
    runs once unless the call's routing is twice as uneven as that."""
    if assignments < _BY_CHOICE_MIN or not held or held * 8 >= outputs:
        return 0
    expected = assignments * held / outputs
    return min(-(-assignments // tm), max(1, -(-int(2 * expected) // tm))) * tm


def _by_held(x, order, load, weights, w_gate, w_up, w_down, *, window: int,
             name: str):
    """Steps 4 and 5 sized by what is HELD. The sorted assignments that
    belong to an expert held here lie first (``load.sum()`` of them, a
    fiftieth of ``T x top_k`` at 16 experts of 768 outputs); everything
    behind them is another rank's, a zero expert's or padding and is never
    gathered. A ``lax.while_loop`` walks them ``window`` rows at a time:
    gather ``[window, D]``, the three grouped products with the window's
    share of each group, and the way back a weighted scatter-add of
    ``[window, D]`` into ``[T, D]`` float32. Static shapes, no capacity: a
    call that holds more than one window runs the body again, nothing is
    dropped and nothing is ``T x top_k`` rows long. Returns y float32."""
    T, top_k = weights.shape
    ends = jnp.cumsum(load)
    starts, mine = ends - load, ends[-1]
    gates = weights.reshape(-1)
    at = jnp.arange(window, dtype=jnp.int32)

    def one(state):
        w, y = state
        first = w * window
        picked = jax.lax.dynamic_slice(order, (first,), (window,))
        live = first + at < mine
        token = jnp.minimum(picked // top_k, T - 1)
        rows = x[token]                                    # [window, D]
        sizes = jnp.clip(ends, first, first + window) \
            - jnp.clip(starts, first, first + window)
        gate = grouped_matmul(rows, w_gate.astype(x.dtype), sizes, name=name)
        up = grouped_matmul(rows, w_up.astype(x.dtype), sizes, name=name)
        out = grouped_matmul(jax.nn.silu(gate) * up, w_down.astype(x.dtype),
                             sizes, name=name)
        g = gates[jnp.minimum(picked, gates.shape[0] - 1)]
        # a row past the held ones was never written: dropped, not weighed
        part = jnp.where(live[:, None],
                         out.astype(jnp.float32) * g[:, None], 0.0)
        return w + 1, y.at[token].add(part)

    with jax.named_scope("moe.held"):
        return jax.lax.while_loop(
            lambda state: state[0] * window < mine, one,
            (jnp.int32(0), jnp.zeros((T, x.shape[1]), jnp.float32)))[1]


# The least ``T x top_k`` whose way back is ``_sum_by_choice``. Every decode
# step of the sparse serve cells lies under it (128, 192, 128, 512 and 400
# assignments) and keeps the one gather above; every prefill call lies at or
# over it (the least are 1,024: ``[1, 128]`` at top-8 and a ``[1, 128]``
# call of 128 riding slots at top-4). The chip's sweep (TPU v5e, PERF.md
# section 6, PR 46): at 1,024 assignments the layer takes 1.307 ms this way
# and 1.318 the other, at 2,048 1.331 against 1.397, at 40,960 (hidden 4096,
# top-10) 7.44 against 10.64; at 512 the two ways back are 10 and 28 us of a
# layer of 1.1 ms, nothing a step could show, so the decode programs stay
# what they were. (It stands here and not beside ``_tiles`` so that the lines
# above keep their numbers: a kernel's body carries them into a program.)
_BY_CHOICE_MIN = 1024


@jax.jit
def _sum_by_choice(out: jax.Array, where: jax.Array, weights: jax.Array,
                   valid: jax.Array) -> jax.Array:
    """out [padded, D] sorted rows, where / weights / valid [T, top_k] ->
    [T, D] float32: ``weights[t, k] * out[where[t, k]]`` summed over a row's
    valid choices, k ascending. The rows are gathered choice by choice,
    ``[top_k, T, D]`` in out's dtype, which is ``[padded, D]`` as the gather
    wrote it and the sum's operand as it lies: whole ``[T, D]`` tiles, read
    once, upcast, weighted and added in registers. Gathered row by row,
    ``[T, top_k, D]``, the same values are laid out again in float32 around
    a ``top_k`` padded to sixteen sublanes before one sum reads them back: at
    hidden 4096 and top-10 three passes and 6.2 ms where this takes 3.0, of
    which 2.5 are the gather (a row of a tiled ``[padded, D]`` is 32 pieces
    of 512 bytes). A choice nobody here took reads its own sorted row, past
    every group, whatever lies there: pointing all of them at one row made
    the gather slower (2.65 ms), and the select drops what they read.
    Jitted so that a model's layers share one trace and one lowering of a
    shape (as ``ops/attention.py:_flash_bwd_pair`` is): XLA inlines the call."""
    picked = out[where.T]
    y = None
    for k in range(where.shape[1]):
        term = jnp.where(valid[:, k, None], picked[k].astype(jnp.float32)
                         * weights[:, k, None], 0.0)
        y = term if y is None else y + term
    return y


def _chosen(scores: jax.Array, top_k: int, bias: Optional[jax.Array]):
    """``select_experts``' softmax kind: the top_k scores and whose they
    are; with a selection ``bias`` the top_k of ``scores + bias`` choose and
    the scores alone weigh. (Down here for the reason ``_BY_CHOICE_MIN`` is.)"""
    if bias is None:
        return jax.lax.top_k(scores, top_k)
    _, experts = jax.lax.top_k(scores + bias, top_k)
    return jnp.take_along_axis(scores, experts, axis=-1), experts
