"""Decode attention over a paged LATENT cache: one Pallas kernel, ``mla_decode``.

Latent attention (MLA) keeps, per position and layer, one ``R``-wide
normalised latent ``c`` and one ``Dr``-wide rotated key ``k_pe`` that all heads
share: one cache row ``c | k_pe | 0`` of ``W`` lanes, ``W`` the next multiple
of 128 (640 for 512 + 64: a page is then whole 128-lane tiles and the kernel
reads it where it lies; with a 576-wide or a 64-wide array the compiler copies
the whole cache into a padded layout on every call). In decode the per-head
up-projections are absorbed into the query and the output
(``llm/model_runner.py``), so every head attends over the SAME rows:
``score[h] = (q_lat[h] | q_pe[h] | 0) . row`` and ``o_lat[h] = probs[h] .
row[:R]``. The kernel therefore reads each live row once for all heads,
straight from the pages and in the cache's own type (bfloat16 in the cell),
and reads nothing else: the work list is the live pages of every slot, walked
through the block table. Row statistics (max, sum), the scores and the accumulator are
float32; ``p`` is rounded to the cache's type on its way into ``p . c``, as
the flash kernels round theirs.

The grid is that work list, one page a step and as long as the pages in use
(a dynamic bound, as ``ops/moe.py``'s is): ``slot_of[i]`` and ``page_of[i]``
come in as scalar prefetch, the block a step gets is page ``page_of[i]`` of
the layer, and a slot's pages follow each other, so the
running (max, sum, accumulator) live in scratch from a slot's first page to
its last, where the output row is written. An inactive slot gets one step over
the scratch page with no live position and writes zeros. Off the TPU the same
kernel runs in interpret mode.
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


def live_pages(seq_lens: jax.Array, active: jax.Array, block_tables: jax.Array,
               page_size: int) -> Tuple[jax.Array, ...]:
    """The kernel's work list, the same for every layer of a step. A slot
    attends over positions 0..seq_len (its new row is written first), so it
    owns ``seq_len // page_size + 1`` pages; an inactive one owns one step
    and no position. Returns ``slot_of [G]``, ``page_of [G]`` (page ids),
    ``starts [B + 1]`` (a slot's first entry), ``lengths [B]`` (live
    positions) and ``used``, how many of the ``G = B x pages_per_seq``
    entries count."""
    B, MP = block_tables.shape
    G = B * MP
    lengths = jnp.where(active, seq_lens + 1, 0).astype(jnp.int32)
    count = jnp.where(active, seq_lens // page_size + 1, 1).astype(jnp.int32)
    ends = jnp.cumsum(count)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), ends]).astype(jnp.int32)
    slot_of = jnp.repeat(jnp.arange(B, dtype=jnp.int32), count,
                         total_repeat_length=G)
    nth = jnp.arange(G, dtype=jnp.int32) - starts[slot_of]
    page_of = block_tables[slot_of, jnp.clip(nth, 0, MP - 1)]
    return slot_of, page_of.astype(jnp.int32), starts, lengths, ends[-1]


def _kernel(slot_of, page_of, starts, lengths, q_ref, rows_ref, o_ref, m_ref,
            l_ref, acc_ref, *, sm_scale: float):
    i = pl.program_id(0)
    b = slot_of[i]
    first = starts[b]
    page, rank = rows_ref.shape[0], o_ref.shape[1]

    @pl.when(i == first)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    s = jax.lax.dot_general(q_ref[...], rows_ref[...], _NT,
                            preferred_element_type=jnp.float32)
    c = rows_ref[:, :rank]                                  # (P, R)
    pos = (i - first) * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    live = pos < lengths[b]
    s = jnp.where(live, s * sm_scale, _MASKED)              # (H, P) float32
    m = m_ref[...]
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    # a page with no live position (an inactive slot's) must weigh nothing
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(c.dtype), c, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(i == starts[b + 1] - 1)
    def _():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def _mla_decode(q, pages, slot_of, page_of, starts, lengths, used, *,
                rank: int, layer: int, sm_scale: float, interpret: bool):
    B, H, W = q.shape
    P = pages.shape[2]

    def row(i, slot_of, page_of, starts, lengths):
        return slot_of[i], 0, 0

    def page(i, slot_of, page_of, starts, lengths):
        return layer, page_of[i], 0, 0

    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((None, H, W), row),
                pl.BlockSpec((None, None, P, W), page),
            ],
            out_specs=pl.BlockSpec((None, H, rank), row),
            grid=(used,),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, rank), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode",
    )(slot_of, page_of, starts, lengths, q, pages)


def mla_decode(q: jax.Array, pages: jax.Array, work: Tuple[jax.Array, ...], *,
               rank: int, layer: int, sm_scale: float) -> jax.Array:
    """q [B, H, W] (``q_lat | q_pe | 0``: the query with the key
    up-projection absorbed, its rotated part, padding), the cache ``pages``
    [L, NP, P, W] of rows ``c | k_pe | 0`` with ``c`` the first ``rank``
    lanes, ``work`` from ``live_pages`` -> o_lat [B, H, rank] in q's type:
    for each slot and head the softmax over its live positions of ``(q .
    row) * sm_scale``, times ``c``. ``layer`` (static) is the layer of the
    cache read; no layer is sliced out."""
    call = functools.partial(_mla_decode, rank=rank, layer=layer,
                             sm_scale=sm_scale)
    return jax.lax.platform_dependent(
        q, pages, *work,
        tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True))
