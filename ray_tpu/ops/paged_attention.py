"""Decode attention over paged keys and values: one Pallas kernel,
``paged_gqa_decode``, for caches whose row holds a position's keys and values
of every head side by side, ``k[0] .. k[KVH-1] | v[0] .. v[KVH-1]``.

Differential attention pairs heads: the query heads of a GROUP (two key heads
``k1 | k2``, ``2 hd`` lanes of the row's key half, and the ``2 hd`` lanes ``v1 |
v2`` at the same place in its value half) attend over ``k1`` or over ``k2`` and
all read the whole ``v1 | v2``. The caller lays a group's query heads out as
rows ``2 hd`` wide with zeros where the other key lies (``llm/model_runner.py``),
so one product ``rows . (k1 | k2)^T`` gives every head its own ``hd``-wide score
and one product ``p . (v1 | v2)`` its ``2 hd``-wide value: at head_dim 64 both
operands are whole 128-lane tiles read where they lie in the page, for each
group in turn. PLAIN heads of 64 lanes are laid out the same way, for the
same reason: two key heads lie in one 128-lane tile of the row, so a group is
the two of them, the queries of the first key laid at lanes 0-63 and those of
the second at 64-127 with zeros beside them, and each head takes its own key's
half of ``p . (v1 | v2)`` (``llm/model_runner.py:_grouped_query``,
``_paged_attention``); a group a key head would slice half a tile out of the
page (it lowers too; PERF.md section 6, PR 40 has both timed). At head_dim 128
a group is one key head and nothing is laid beside it. Row statistics (max,
sum), scores and accumulators are float32;
``p`` is rounded to the cache's type on its way into ``p . v``, as the flash
kernels round theirs; the softmax scale is the caller's, on the query.

The grid is the work list of ``ops/mla.py:live_pages``, one page a step over a
dynamic bound: only pages that hold live positions are read, a slot's pages
follow each other and its running (max, sum, accumulator) live in scratch from
its first page to its last. The same kernel serves a window layer's rings: a
ring is a slot's own page, its live positions the ring's filled entries, and
since no position is embedded the order in which a ring holds them is nobody's
concern. An inactive slot gets one step over a page with no live position and
writes zeros. Off the TPU the kernel runs in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))   # a @ b.T, both contract their minor dimension
_MASKED = -1e30


def _kernel(slot_of, page_of, starts, lengths, q_ref, kv_ref, o_ref, m_ref,
            l_ref, acc_ref):
    i = pl.program_id(0)
    b = slot_of[i]
    first = starts[b]
    groups, rows, width = q_ref.shape
    page = kv_ref.shape[0]
    values = groups * width                  # where the row's value half begins

    @pl.when(i == first)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    pos = (i - first) * page + jax.lax.broadcasted_iota(
        jnp.int32, (rows, page), 1)
    live = pos < lengths[b]
    for g in range(groups):
        k = kv_ref[:, g * width:(g + 1) * width]                 # (P, 2 hd)
        v = kv_ref[:, values + g * width:values + (g + 1) * width]
        s = jax.lax.dot_general(q_ref[g], k, _NT,
                                preferred_element_type=jnp.float32)
        s = jnp.where(live, s, _MASKED)                          # (rows, P)
        m = m_ref[g]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        # a page with no live position (an inactive slot's) weighs nothing
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_ref[g] = l_ref[g] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[g] = m_new

    @pl.when(i == starts[b + 1] - 1)
    def _():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def _paged_gqa_decode(q, pages, slot_of, page_of, starts, lengths, used, *,
                      layer: int, name: str, interpret: bool):
    B, G, R, W = q.shape
    P, row_width = pages.shape[2:]

    def row(i, slot_of, page_of, starts, lengths):
        return slot_of[i], 0, 0, 0

    def page(i, slot_of, page_of, starts, lengths):
        return layer, page_of[i], 0, 0

    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((None, G, R, W), row),
                pl.BlockSpec((None, None, P, row_width), page),
            ],
            out_specs=pl.BlockSpec((None, G, R, W), row),
            grid=(used,),
            scratch_shapes=[pltpu.VMEM((G, R, 1), jnp.float32),
                            pltpu.VMEM((G, R, 1), jnp.float32),
                            pltpu.VMEM((G, R, W), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(slot_of, page_of, starts, lengths, q, pages)


def paged_gqa_decode(q: jax.Array, pages: jax.Array,
                     work: Tuple[jax.Array, ...], *, layer: int,
                     name: str = "paged_gqa_decode") -> jax.Array:
    """q [B, G, R, W] (group by group, a row a query head, scaled, zeros where
    the group's other keys lie; ``W``: the lanes of a group's keys, 2 hd for a
    differential pair or two plain heads of 64, hd for one plain head of 128),
    the cache ``pages`` [L, NP, P, 2 G W] (keys of every group, then values),
    ``work`` from ``ops/mla.py:live_pages`` -> [B, G, R, W] in q's type: for
    each slot and row the softmax over the
    slot's live positions of ``q . k``, times the group's values. ``layer``
    (static) is the layer of ``pages`` read; none is sliced out. ``name`` is
    the kernel's name in a device trace."""
    call = functools.partial(_paged_gqa_decode, layer=layer, name=name)
    return jax.lax.platform_dependent(
        q, pages, *work,
        tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True))
