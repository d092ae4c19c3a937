"""Attention ops: pallas TPU flash-attention forward and backward, and the
pure-XLA reference path.

The MXU-friendly hot op of the flagship model. Three pallas kernels, named so
that a device trace shows them under one key each, one call a layer and step:
``flash_fwd`` (online softmax: one (head, q-block) program, a loop over the
k-blocks of the whole-sequence K/V held in VMEM; saves the row logsumexp),
``flash_bwd_dq`` and ``flash_bwd_dkv`` (FlashAttention-2 style, softmax
rebuilt from the saved logsumexp; dk/dv on the transposed score tile).
All three share one loop shape: every product takes the operands as they come
(bfloat16 in the cells) and accumulates in float32, with ``p`` and ``ds``
rounded to the operand dtype only on their way into a product, as
``reference_attention`` does; max, sum, ``exp``, logsumexp, delta and the
accumulators stay float32. The causal mask is built only in the blocks the
diagonal crosses (a second loop with the same body), the blocks above it are
never visited, and the row statistics lie along the lanes, a row a head.

Two layouts, chosen by what the call can see. A DIFFERENTIATED call (a train
step: ``_fa_fwd``, ``_flash_bwd_pair``) of an even count of 64-lane heads
(``_heads_a_program``) hands the kernels the operands as the projections
produce them, ``(B, S, H, D)`` seen as ``[B, S, H x D]``: a program finds its
128-lane tile through the ``BlockSpec`` index maps (``_tile_specs``), owns the
two heads of it (``_each_head``), and writes o, dq, dk, dv the same way, so
nothing is transposed before or after any of the three calls. Every other
call, the forward-only ones of the serving engine's prefill among them (a
window, values with a head size of their own) and every call of heads of 128
lanes or more, brings a head's rows together first, ``(B*H, S, D)``, and takes
them apart after.
A forward-only call that knows its rows' lengths (``lens``: the engine's
prefill, whose prompts are padded to a bucket) prefetches them as scalars,
and a program whose query block lies wholly behind its row's end does
nothing: no loop, no fetch, zeros out (``_flash_fwd_lens_kernel``). Under a
causal mask those are the dearest programs, each visits every key block
before it: a 4,200-token prompt in a bucket of 8,192 keeps 45 of 136 visits
(on the v5e ``bf16[48, 8192, 128]`` takes 7.81 ms at a full bucket, 4.97 at
6,144 positions and 3.16 at 4,608, 7.69 without lengths: my chip run, PR 54).
``q_blocks`` is the host's count of both. The differentiated path takes no
lengths.
``_blocks`` sizes the blocks from the shapes; ``_use_pallas_bwd`` picks the
backward from the shapes too: the pallas pair wherever its whole-sequence
blocks fit fast memory (bfloat16: head_dim 128 up to 12,288 positions, 64 up
to 10,240), a rematerialised backward through ``reference_attention`` for a
longer sequence and for values with a head size of their own.

CI runs the kernels in pallas interpret mode on CPU (SURVEY.md §4 implication:
every accelerator feature needs a hardware-free tier).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp


def reference_attention(q, k, v, causal: bool = True,
                        segment_ids: Optional[jax.Array] = None,
                        window: int = 0):
    """Pure-XLA attention: (B, S, H, D) -> (B, S, H, Dv), fp32 softmax. With
    ``window`` a query sees only the ``window`` newest keys up to its own."""
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    S = q.shape[1]
    if causal:
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        if window:
            mask &= ~jnp.tril(jnp.ones((S, S), dtype=bool), -window)
        scores = jnp.where(mask[None, None], scores, -1e30)
    if segment_ids is not None:
        seg_mask = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        scores = jnp.where(seg_mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# the three pallas kernels: one loop shape, three bodies
# ---------------------------------------------------------------------------

# a @ b.T as ONE product: both tiles contract their minor dimension, so no
# tile is transposed on its way to the MXU
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))
_MASKED = -1e30


def _blocks(seq_len: int) -> tuple:
    """(block_q, block_k) of all three kernels, from the shapes alone: the
    largest of 512, 256, 128 that divides the sequence (one block below 128).

    A larger block pays a loop iteration's fixed cost (the per-row statistics,
    the rescaled accumulator) less often and computes more of the masked half
    of a diagonal block. Chosen by device time on the v5e at the cells' shapes
    (PERF.md section 6, PR 30: forward / dq / dkv ms a call at
    ``bf16[4,2048,32,64]``, 128 x 128 6.84 / 6.21 / 5.88, 256 x 256 2.96 / 2.63
    / 3.50, 512 x 512 1.88 / 1.81 / 2.40, 1024 x 1024 2.10 / 1.99 / 2.63, no
    unequal pair ahead); head_dim 128 orders the same way (PR 34, the same
    twelve at ``bf16[2,2048,16,128]``: 1.73 / 1.53 / 1.43, 0.74 / 0.64 / 0.85,
    0.47 / 0.45 / 0.60, 0.53 / 0.50 / 0.66; 256 x 512 is 0.6% ahead in the
    forward alone and 16% behind over the three), so neither head_dim nor
    the dtype enters the rule. Swept again for the program of two 64-lane
    heads that reads the projections' layout (PERF.md section 6, PR 47;
    ``bf16[4,2048,32,64]``: 4.14 / 5.13 / 4.63, 1.99 / 2.01 / 2.19, **1.84 /
    1.69 / 2.13**, 1,024 x 1,024 past fast memory with two heads' score tiles;
    of six unequal pairs 256 x 512 is 1.7% ahead in the forward alone and 3.8%
    behind over the three): the order stands, and so does the rule."""
    if seq_len <= 128:
        return seq_len, seq_len
    for block in (512, 256, 128):
        if seq_len % block == 0:
            return block, block
    raise ValueError("flash attention takes at most 128 positions or a "
                     f"multiple of 128, got {seq_len}")


def q_blocks(seq_len: int, lens) -> tuple:
    """On the host: the query blocks ``flash_fwd``'s grid has a head for rows
    of ``lens`` real positions in a bucket of ``seq_len``, and those of them
    a call that knows its rows' lengths passes over (a block wholly behind
    its row's end; every block of a padding row). A bucket the kernel does
    not take (``attention``'s rule: over 128 positions and no multiple of
    128) has no grid."""
    if seq_len > 128 and seq_len % 128:
        return 0, 0
    block_q, _ = _blocks(seq_len)
    blocks = len(lens) * (seq_len // block_q)
    return blocks, blocks - sum(-(-int(n) // block_q) for n in lens)


def _lanes(d: int) -> int:
    """A row of ``d`` elements as fast memory holds it: whole 128-lane tiles."""
    return -(-d // 128) * 128


def _dot(a, b, dims=_NN):
    """Operands as they come (bfloat16 in the cells), float32 out."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _scores(a, sm_scale):
    """A program's score tiles: ``b -> (a @ b.T) * sm_scale`` in float32, for
    its own block ``a`` and each block ``b`` of its loop. A scale that is a power
    of two (head_dim 64, 256) goes on ``a`` once, before the loop, exactly
    whatever ``a``'s dtype: (rows, head_dim) multiplications in place of every
    tile's. Any other (head_dim 128) would cost ``a`` a rounding there, and
    multiplies the scores."""
    if math.frexp(sm_scale)[0] == 0.5:
        a = (a.astype(jnp.float32) * sm_scale).astype(a.dtype)
        return lambda b: _dot(a, b, _NT)
    return lambda b: _dot(a, b, _NT) * sm_scale


def _heads_a_program(n_heads: int, head_dim: int) -> int:
    """2 where a differentiated call reads the projections' own layout ``[B,
    S, H x D]``: a block's minor dimension is whole 128-lane tiles, so one
    program owns the TWO 64-lane heads of a tile, and an even count of them
    tiles a row. 1 everywhere else: a head's rows are brought together first
    (``_to_bh``). A head of 128 lanes could be read where it lies too, and is
    not: in cell 4 (16 heads of 128) that step read 171.36 ms against 167.41
    (PERF.md section 6, PR 47: blocks strided across the row cost the kernels
    0.70 ms a step, and a head of whole tiles transposes cheaply as it is)."""
    return 2 if head_dim == 64 and n_heads % 2 == 0 else 1


def _first_head(width):
    """The lanes of a tile's first head, ``(1, width)``."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) < width // 2


def _each_head(x, heads):
    """A block ``(rows, heads x D)`` as one operand a head. Two 64-lane heads
    lie side by side in a tile: each gets the block with zeros in the other's
    lanes, so a product that contracts all 128 lanes is that head's own and no
    lane is sliced (``ops/paged_attention.py`` pairs its key heads the same
    way). On a 128-wide MXU the padded product costs the passes the 64-wide
    one costs."""
    if heads == 1:
        return [x]
    first, zero = _first_head(x.shape[1]), jnp.zeros_like(x)
    return [jnp.where(first, x, zero), jnp.where(first, zero, x)]


def _side_by_side(parts, width):
    """The heads' results back in one tile ``(rows, width)``: of each head's
    product the lanes that are its own (the others hold its product with the
    other head's operand, which nobody reads), of its ``(rows, 1)`` statistic
    a copy in each of them."""
    if len(parts) == 1:
        return parts[0]
    return jnp.where(_first_head(width), *parts)


def _block(i, size, whole):
    """The i-th block of ``size`` positions of ``whole``, as an index; static
    when the one block is everything, which is also all Mosaic takes along the
    lanes below 128 positions."""
    import jax.experimental.pallas as pl

    if size == whole:
        return slice(None)
    return pl.ds(pl.multiple_of(i * size, size), size)


def _hide_future(s, q_start, k_start, q_axis):
    """The causal mask over one score tile whose queries run along
    ``q_axis``: only the tiles the diagonal crosses come here."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q_pos >= k_pos, s, _MASKED)


def _hide_outside(s, q_start, k_start, window):
    """The causal mask and the window's left edge over one score tile
    (queries down): a query sees the ``window`` newest keys up to its own."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where((q_pos >= k_pos) & (k_pos > q_pos - window), s, _MASKED)


def _window_blocks(q_start, block_q, block_k, window):
    """For the queries [q_start, q_start + block_q) under a window: key blocks
    before ``first`` lie wholly left of every query's window and are never
    visited, [first, edge) may hold a pair the window hides, and the blocks
    from ``edge`` on lie right of the last query's left edge."""
    first = jnp.maximum(q_start - window + 1, 0) // block_k
    edge = jnp.maximum(q_start + block_q - window + block_k - 1, 0) // block_k
    return first, edge


def _key_blocks(q_start, block_q, block_k, seq_len, causal):
    """For the queries [q_start, q_start + block_q): key blocks [0, clear) lie
    wholly below the diagonal and need no mask, [clear, end) cross it, and
    the blocks from ``end`` on hold no visible pair."""
    if not causal:
        return seq_len // block_k, seq_len // block_k
    return (q_start + 1) // block_k, (q_start + block_q + block_k - 1) // block_k


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, causal,
                      sm_scale, window=0, heads=1, q_block=None):
    """``heads`` 2: the program's blocks are one 128-lane tile of two 64-lane
    heads (``_each_head``), each with a softmax of its own: the statistics
    are lists, one entry a head, the accumulator is the one tile of ``o``
    (a whole tile a head read 4 ms a step slower in cell 1: PERF.md section
    6, PR 47), and ``lse_ref`` holds a row a head. ``q_block``: the program's
    query block, from a caller that has asked already (interpret mode finds
    no ``program_id`` under a ``pl.when``)."""
    import jax.experimental.pallas as pl

    block_q, d = q_ref.shape[1], v_ref.shape[2]
    seq_len = k_ref.shape[1]
    scores = [_scores(q, sm_scale) for q in _each_head(q_ref[0], heads)]
    q_start = (pl.program_id(1) if q_block is None else q_block) * block_q

    def step(masked):
        def body(i, carry):
            acc, m, l = carry
            keys = _block(i, block_k, seq_len)
            k, v = k_ref[0, keys, :], v_ref[0, keys, :]
            m_new, p, alpha, l_new = [], [], [], []
            for h in range(heads):
                s = scores[h](k)                          # (bq, bk) float32
                if masked and window:
                    s = _hide_outside(s, q_start, i * block_k, window)
                elif masked:
                    s = _hide_future(s, q_start, i * block_k, 0)
                m_new.append(jnp.maximum(m[h], s.max(axis=-1, keepdims=True)))
                p.append(jnp.exp(s - m_new[h]))
                alpha.append(jnp.exp(m[h] - m_new[h]))
                l_new.append(l[h] * alpha[h] + p[h].sum(axis=-1, keepdims=True))
            acc_new = acc * _side_by_side(alpha, d) + _side_by_side(
                [_dot(p_h.astype(v.dtype), v) for p_h in p], d)
            return acc_new, m_new, l_new
        return body

    carry = (jnp.zeros((block_q, d), jnp.float32),
             [jnp.full((block_q, 1), _MASKED, jnp.float32)] * heads,
             [jnp.zeros((block_q, 1), jnp.float32)] * heads)
    clear, end = _key_blocks(q_start, block_q, block_k, seq_len, causal)
    first = 0
    if window:
        # the blocks the window's left edge crosses, masked; every row of a
        # visited block sees a key of it or of an earlier one, so no row's
        # running max is still the mask's when a later block rescales it
        first, edge = _window_blocks(q_start, block_q, block_k, window)
        edge = jnp.minimum(edge, end)
        carry = jax.lax.fori_loop(first, edge, step(True), carry)
        first, clear = edge, jnp.maximum(clear, edge)
    carry = jax.lax.fori_loop(first, clear, step(False), carry)
    acc, m, l = jax.lax.fori_loop(clear, end, step(True), carry)
    l_safe = [jnp.maximum(l_h, 1e-30) for l_h in l]
    o_ref[0] = (acc / _side_by_side(l_safe, d)).astype(o_ref.dtype)
    # logsumexp per row, the backward's softmax reconstruction key, laid
    # along the lanes: a (S, 1) array pads every row to 128 lanes, in fast
    # memory and in HBM (134 MB for 1 MB of statistics at cell 1's shape)
    for h in range(heads):
        lse_ref[0, h:h + 1] = (m[h] + jnp.log(l_safe[h])).T


def _flash_fwd_lens_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                           heads_a_row, **kernel):
    """``_flash_fwd_kernel`` for a call that knows its rows' lengths (scalar
    prefetch): a program whose query block lies WHOLLY behind its row's end
    runs none of the loops. It writes zeros: what lies behind a prompt's end
    is nobody's, but it goes on through the output projection and the MLP or
    the experts into the next layer's norms, so it has to be finite. The
    block that holds the end is computed whole: a real position gets what a
    call without lengths gives it, from the same blocks in the same order."""
    import jax.experimental.pallas as pl

    q_block = pl.program_id(1)
    real = (q_block * q_ref.shape[1]
            < lens_ref[pl.program_id(0) // heads_a_row])

    @pl.when(real)
    def _():
        _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                          q_block=q_block, **kernel)

    @pl.when(jnp.logical_not(real))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        lse_ref[...] = jnp.zeros_like(lse_ref)


def _to_bh(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _from_bh(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _whole_seq_params(S, D, Dv, itemsize, room=10 << 20):
    """``pallas_call`` arguments for the forward's whole-sequence K and V,
    twice each (the pipeline's two buffers), as fast memory holds them (the
    minor dimension in whole 128-lane tiles): 12.6 MiB at bf16[.., 8192, 192 /
    128], which with the blocks and the score tile is 0.4 MiB past the
    compiler's own 16 MiB. Only such a shape gets a limit of its own (more
    than ``room`` held); every other call is compiled as it was."""
    held = 2 * S * (_lanes(D) + _lanes(Dv)) * itemsize
    if held <= room:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=held + (16 << 20))}


def _flash_fwd_impl(q, k, v, causal: bool, interpret: bool, window: int = 0,
                    lens=None):
    """Returns (o, lse) with o in (B, S, H, Dv) and lse in (B*H, 1, S). The
    values may have a head size of their own (latent attention: q . k over
    192, p . v over 128); the softmax scale is the key head size's. With
    ``window`` (causal only) a query sees the ``window`` newest keys up to its
    own, and key blocks left of the window are skipped as the ones above the
    diagonal are. With ``lens`` (causal only; ``int32 [B]``, the real
    positions of each row, 0 for a padding row) the query blocks wholly
    behind a row's end are passed over (``_flash_fwd_lens_kernel``: zeros)
    and fetch nothing: their index maps name the blocks the program before
    them held (the row's last real query block; for a row with no real
    position, K and V of the call's first head). The keys need no mask of
    their own: padding lies behind every real query, where the causal mask
    hides it."""
    import jax.experimental.pallas as pl

    B, S, H, D = q.shape
    Dv = v.shape[-1]
    block_q, block_k = _blocks(S)
    qt, kt, vt = _to_bh(q), _to_bh(k), _to_bh(v)
    kernel = functools.partial(_flash_fwd_kernel, block_k=block_k,
                               causal=causal, sm_scale=1.0 / (D ** 0.5))
    if window:
        assert causal, "a window is the causal mask's left edge"
        kernel = functools.partial(kernel, window=window)

    def q_at(bh, qi, *lens):   # behind a row's end: its last real block
        if lens:
            last = jnp.maximum(lens[0][bh // H] - 1, 0) // block_q
            qi = jnp.minimum(qi, last)
        return bh, qi, 0

    def kv_at(bh, qi, *lens):   # of a row with no real position: nobody's
        if lens:
            bh = jnp.where(lens[0][bh // H] > 0, bh, 0)
        return bh, 0, 0

    how = dict(
        grid=(B * H, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_at),
            pl.BlockSpec((1, S, D), kv_at),
            pl.BlockSpec((1, S, Dv), kv_at),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda bh, qi, *_: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, *_: (bh, 0, qi)),
        ])
    operands = (qt, kt, vt)
    if lens is not None:
        assert causal, "the causal mask is what hides a row's padding"
        from jax.experimental.pallas import tpu as pltpu

        kernel = functools.partial(
            _flash_fwd_lens_kernel, heads_a_row=H, **kernel.keywords)
        how = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **how))
        operands = (lens.astype(jnp.int32), *operands)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        **how,
        **_whole_seq_params(S, D, Dv, k.dtype.itemsize),
    )(*operands)
    return _from_bh(out, B, H), lse


def flash_attention_fwd(q, k, v, causal: bool = True,
                        interpret: bool = False, window: int = 0, lens=None):
    """(B, S, H, D) flash forward via pallas (TPU) / interpret mode (CI)."""
    return _flash_fwd_impl(q, k, v, causal, interpret, window, lens)[0]


# ---------------------------------------------------------------------------
# pallas flash backward (FlashAttention-2 style: dQ kernel over k-blocks,
# dK/dV kernel over q-blocks, softmax reconstructed from the saved LSE)
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k, causal, sm_scale, heads):
    import jax.experimental.pallas as pl

    block_q, seq_len = q_ref.shape[1], k_ref.shape[1]
    scores = [_scores(q, sm_scale) for q in _each_head(q_ref[0], heads)]
    do = _each_head(do_ref[0], heads)         # (bq, heads x d) each
    lse = [lse_ref[0, h:h + 1].T for h in range(heads)]   # (1, bq) -> (bq, 1)
    delta = [delta_ref[0, h:h + 1].T for h in range(heads)]
    q_start = pl.program_id(1) * block_q

    def step(masked):
        def body(i, dq_acc):
            keys = _block(i, block_k, seq_len)
            k, v = k_ref[0, keys, :], v_ref[0, keys, :]
            dq = []
            for h in range(heads):
                s = scores[h](k)                              # (bq, bk)
                if masked:
                    s = _hide_future(s, q_start, i * block_k, 0)
                p = jnp.exp(s - lse[h])
                ds = p * (_dot(do[h], v, _NT) - delta[h])
                dq.append(_dot(ds.astype(k.dtype), k))
            return dq_acc + _side_by_side(dq, k.shape[1])
        return body

    clear, end = _key_blocks(q_start, block_q, block_k, seq_len, causal)
    dq = jax.lax.fori_loop(0, clear, step(False),
                           jnp.zeros(q_ref.shape[1:], jnp.float32))
    dq = jax.lax.fori_loop(clear, end, step(True), dq)
    # ds went into its product without the softmax scale: once here
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q, causal, sm_scale, heads):
    """Works on the TRANSPOSED score tile (keys down, queries across), so
    that ``p^T do`` and ``ds^T q`` are plain products and the statistics are
    read as they lie, along the lanes."""
    import jax.experimental.pallas as pl

    block_k, seq_len = k_ref.shape[1], q_ref.shape[1]
    scores = [_scores(k, sm_scale) for k in _each_head(k_ref[0], heads)]
    v = _each_head(v_ref[0], heads)           # (bk, heads x d) each
    k_start = pl.program_id(1) * block_k

    def step(masked):
        def body(i, carry):
            dk_acc, dv_acc = carry
            queries = _block(i, block_q, seq_len)
            q, do = q_ref[0, queries, :], do_ref[0, queries, :]
            delta, p = [], []
            for h in range(heads):
                lse = lse_ref[0, h:h + 1, queries]                # (1, bq)
                delta.append(delta_ref[0, h:h + 1, queries])
                s = scores[h](q)                                  # (bk, bq)
                if masked:
                    s = _hide_future(s, i * block_q, k_start, 1)
                p.append(jnp.exp(s - lse))
            dv_acc = dv_acc + _side_by_side(
                [_dot(p_h.astype(do.dtype), do) for p_h in p], q.shape[1])
            ds = [p[h] * (_dot(v[h], do, _NT) - delta[h])
                  for h in range(heads)]
            dk_acc = dk_acc + _side_by_side(
                [_dot(ds_h.astype(q.dtype), q) for ds_h in ds], q.shape[1])
            return dk_acc, dv_acc
        return body

    # query blocks [first, clear) cross the diagonal, [clear, nq) lie wholly
    # below it, the ones before ``first`` hold no visible pair
    nq = seq_len // block_q
    if causal:
        first = k_start // block_q
        clear = (k_start + block_k - 1 + block_q - 1) // block_q
    else:
        first = clear = 0
    carry = (jnp.zeros(k_ref.shape[1:], jnp.float32),) * 2
    carry = jax.lax.fori_loop(first, clear, step(True), carry)
    dk, dv = jax.lax.fori_loop(clear, nq, step(False), carry)
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# a differentiated call: the three kernels on the projections' own layout
# ---------------------------------------------------------------------------


def _tile_specs(tiles, width, heads, seq_len):
    """The ``BlockSpec``s of a program (p, i) over operands ``[N, S, tiles x
    width]`` and statistics ``(N x tiles, heads, S)``: program p owns lane
    block ``p % tiles`` (a tile of two heads) of row ``p // tiles``, or, with
    one lane block a row, row p (one head's rows, brought together);
    ``rows(n)`` is its i-th block of n positions, ``rows()`` the whole
    sequence (fetched once a p: the index does not move with i), ``stats``
    the same for the statistics. In ``[B, S, H x D]`` a head is addressed
    where the projections left it."""
    import jax.experimental.pallas as pl

    def at(n):   # a block of n positions moves with i, the whole sequence not
        return (lambda i: 0) if n is None else (lambda i: i)

    def rows(n=None):
        shape, j = (1, n or seq_len, width), at(n)
        if tiles == 1:
            return pl.BlockSpec(shape, lambda p, i: (p, j(i), 0))
        return pl.BlockSpec(shape, lambda p, i: (p // tiles, j(i), p % tiles))

    def stats(n=None):
        j = at(n)
        return pl.BlockSpec((1, heads, n or seq_len),
                            lambda p, i: (p, 0, j(i)))

    return rows, stats


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _flash_fwd_two_heads(q, k, v, causal, interpret, block_q, block_k):
    """The forward of a differentiated call of two heads a program
    (``_heads_a_program``), on ``[B, S, H x D]`` as it lies: (o, lse), o in
    (B, S, H, D) and lse in ``(B*H/2, 2, S)``, a row a head of each program.
    Jitted for the reason ``_flash_bwd_pair`` is."""
    import jax.experimental.pallas as pl

    B, S, H, D = q.shape
    heads, tiles = 2, H // 2
    rows, stats = _tile_specs(tiles, heads * D, heads, S)
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, block_k=block_k, causal=causal,
                          sm_scale=1.0 / (D ** 0.5), heads=heads),
        grid=(B * tiles, S // block_q),
        in_specs=[rows(block_q), rows(), rows()],
        out_specs=[rows(block_q), stats(block_q)],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
            jax.ShapeDtypeStruct((B * tiles, heads, S), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        # two heads' score tiles lie beside K and V: a limit of its own from
        # 8 MiB held (AOT, PR 47: 10,240 positions, 10 MiB, do not compile
        # under the compiler's 16)
        **_whole_seq_params(S, heads * D, heads * D, k.dtype.itemsize,
                            room=8 << 20),
    )(*(x.reshape(B, S, H * D) for x in (q, k, v)))
    return out.reshape(q.shape), lse


def flash_attention_bwd(q, k, v, o, lse, g, causal: bool,
                        interpret: bool = False):
    """(dq, dk, dv) by the pallas pair, at the blocks ``_blocks`` gives the
    sequence; ``lse`` as the forward of the same shapes left it
    (``_fa_fwd``)."""
    return _flash_bwd_pair(q, k, v, o, lse, g, causal, interpret,
                           *_blocks(q.shape[1]))


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _flash_bwd_pair(q, k, v, o, lse, g, causal, interpret, block_q, block_k):
    """Jitted so that the layers of a model share one trace and one lowering
    of it: a ``pallas_call`` is traced and lowered to Mosaic wherever it is
    bound, about 0.2 s of host time a kernel on the chip's machine, compile
    cache or not (the 16 calls of cell 4's step: 3.3 s of every start, PERF.md
    section 6, PR 34); XLA inlines the call, and the compiled step is the
    same. Everything the trace reads besides the operands is a static
    argument, the blocks too: the cache holds one trace for each.

    With two 64-lane heads to a program (``_heads_a_program``) q, k, v, dO
    are read and dq, dk, dv written as ``[B, S, H x D]``, the layout the
    projections produce and their gradients consume: no operand and no
    result is transposed here (PR 47; until then eleven whole-array
    transposes a layer stood around the three calls, 17.8 ms of cell 1's 281
    ms step with their converts; what is left is XLA's: it keeps q, k, v and
    their cotangents around RoPE with the positions along the lanes and
    copies each once, a dense ``[B, S, H x D]`` array, 5.1 ms a step).
    Elsewhere a head's rows are brought together first, ``(B*H, S, D)``, and
    the same calls see one head a program, one lane block a row."""
    import jax.experimental.pallas as pl

    B, S, H, D = q.shape
    heads = _heads_a_program(H, D)
    if heads == 2:
        tiles = H // 2
        lay, back = (lambda x: x.reshape(B, S, H * D),
                     lambda x: x.reshape(B, S, H, D))
    else:
        tiles = 1
        lay, back = _to_bh, lambda x: _from_bh(x, B, H)
    qt, kt, vt, dot = (lay(x) for x in (q, k, v, g))
    # delta = rowsum(dO * O): cheap elementwise — plain XLA, not a kernel;
    # reduced where the operands lie, so that only the (B, S, H) sums are
    # transposed, into lse's layout (B*H/heads, heads, S). O comes rounded to
    # the operands' dtype: where attention is nearly uniform and dP - delta
    # cancels, that rounding is what puts a model's q and k gradients 2.5e-2
    # from the float32 reference where ``reference_attention``'s backward
    # reads 1.4e-2 (PERF.md section 6, PR 34)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.transpose(0, 2, 1).reshape(lse.shape)
    rows, stats = _tile_specs(tiles, heads * D, heads, S)
    common = dict(causal=causal, sm_scale=1.0 / (D ** 0.5), heads=heads)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k, **common),
        grid=(qt.shape[0] * tiles, S // block_q),
        in_specs=[rows(block_q), rows(), rows(), rows(block_q),
                  stats(block_q), stats(block_q)],
        out_specs=rows(block_q),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q, **common),
        grid=(qt.shape[0] * tiles, S // block_k),
        in_specs=[rows(), rows(block_k), rows(block_k), rows(),
                  stats(), stats()],
        out_specs=[rows(block_k), rows(block_k)],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, v.dtype)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, dot, lse, delta)
    return back(dq), back(dk), back(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, interpret: bool = False):
    return flash_attention_fwd(q, k, v, causal=causal, interpret=interpret)


def _fa_fwd(q, k, v, causal, interpret):
    # the shapes are static at trace time; the pallas pair has one head size
    # (latent attention's 192 / 128 forward keeps the reference backward)
    B, S, H, D = q.shape
    if D == v.shape[-1] and _use_pallas_bwd(D, S, q.dtype.itemsize):
        if _heads_a_program(H, D) == 2:
            o, lse = _flash_fwd_two_heads(q, k, v, causal, interpret,
                                          *_blocks(S))
        else:
            o, lse = _flash_fwd_impl(q, k, v, causal, interpret)
        return o, (q, k, v, o, lse)
    # reference backward never reads o/lse: don't hold them across bwd
    return flash_attention_fwd(q, k, v, causal=causal,
                               interpret=interpret), (q, k, v, None, None)


# What one whole-sequence operand of the backward pair may take of fast
# memory, as it lies there (the minor dimension in whole 128-lane tiles), by
# the bytes of a row: (rows up to, bytes). ``flash_bwd_dq`` holds K and V
# whole, ``flash_bwd_dkv`` Q and dO, each twice (the pipeline's two buffers):
# four times this of the compiler's 16 MiB; the blocks, accumulators and
# score tiles beside them grow with the row, so a wider row leaves less.
# Found by compiling for the v5e each kernel alone, the pair together and a
# train step, at 16 to 128 heads (tests/test_chip_compile.py; PERF.md
# section 6, PR 34); the longest sequence at which all of them compiled / the
# shortest at which one did not: bfloat16 at head_dim 128 12,800 / 13,312, at
# 64 12,288 / 13,312, at 256 4,608 / 5,120, at 192 (256 lanes) 4,096 / 5,120;
# float32 at 128 5,632 / 6,144, at 256 1,536 / 2,048. (Where it stops
# depends on what surrounds the call: at 2 to 4 heads 16,384 positions of
# head_dim 128 compile.) So bfloat16 takes the pair to 12,288 positions at
# head_dim 128 and to 4,096 at 192 and 256, float32 to 4,096 at 128 and to
# 1,536 at 256. A program of two 64-lane heads (``_heads_a_program``, PR 47)
# holds two heads' score tiles beside the same blocks, 2 MiB more of the 16,
# half a MiB off each operand: bfloat16 at head_dim 64 10,240 / 10,752 (12,288
# / 13,312 while a program held one head), float32 4,608 and more.
_BWD_WHOLE_SEQ_BYTES = ((256, 3 << 20), (512, 2 << 20), (1024, 3 << 19))
_TWO_HEADS_BYTES = 1 << 19


def _use_pallas_bwd(head_dim: int, seq_len: int = 0, itemsize: int = 2) -> bool:
    """Whether the backward of ``flash_attention`` is the pallas pair
    (``flash_bwd_dq``, ``flash_bwd_dkv``) or ``reference_attention``
    rematerialised: the pair wherever its whole-sequence blocks fit the
    compiler's scoped fast memory, a function of the shapes alone. The pair
    is the faster wherever it compiles (bare on the v5e at
    ``bf16[2,2048,16,128]``: 0.45 + 0.60 ms a call against 7.4, PR 30; at
    head_dim 64 it has been the rule since before that), so the reference
    backward is the fallback for a sequence the pair cannot hold. It costs
    S x S float32 arrays there (19 GB each at 32 heads of 12,288 positions):
    past the bound a gradient compiles, and fits the chip only at a few heads.

    With the head size alone (``benchmarks/jobs/train.py`` prints that
    answer) the answer for a sequence short enough: every head size the
    kernels are given has one."""
    row = _lanes(head_dim) * itemsize
    spare = _TWO_HEADS_BYTES if head_dim == 64 else 0
    return any(row <= widest and seq_len * row <= room - spare
               for widest, room in _BWD_WHOLE_SEQ_BYTES)


def _fa_bwd(causal, interpret, res, g):
    q, k, v, o, lse = res
    if o is not None:
        return flash_attention_bwd(q, k, v, o, lse, g, causal, interpret)
    # rematerialized backward through the reference path (correct, HBM-flat)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: reference_attention(q_, k_, v_, causal), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# (mesh, PartitionSpec) of the jit-partitioned program being traced, or None
# — see ``partitioned_over``
_PARTITION = contextvars.ContextVar("attention_partition", default=None)


@contextlib.contextmanager
def partitioned_over(mesh, batch_axes, head_axes):
    """Enter while TRACING a program that jit partitions over ``mesh``.

    The partitioner cannot split a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so inside this context ``attention`` runs
    the flash kernel under a ``shard_map``: each device runs it on its own
    batch rows (``batch_axes``) and heads (``head_axes``) of the
    (B, S, H, D) operands, always over the whole sequence. The spec is over
    (B, S, H, D) whatever layout the kernels read: a differentiated call
    sees its device's ``(B', S, H', D)`` as ``[B', S, H' x D]`` INSIDE the
    mapped function (``_flash_fwd_two_heads``, ``_flash_bwd_pair``), by the
    device's own head count."""
    from jax.sharding import PartitionSpec as P

    token = _PARTITION.set((mesh, P(batch_axes, None, head_axes, None)))
    try:
        yield
    finally:
        _PARTITION.reset(token)


def _flash(q, k, v, causal: bool, interpret: bool):
    part = _PARTITION.get()
    if part is None:
        return flash_attention(q, k, v, causal, interpret)
    mesh, spec = part
    return jax.shard_map(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, causal, interpret),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


def attention(q, k, v, causal: bool = True, impl: str = "auto",
              segment_ids: Optional[jax.Array] = None, window: int = 0,
              lens: Optional[jax.Array] = None):
    """Dispatching attention op used by the flagship model. ``window``, and
    ``lens`` (each row's real positions, padding behind them: the flash
    kernel passes over the query blocks wholly behind a row's end and leaves
    zeros there; the reference computes them, and nobody reads them): the
    forward alone (serving's prefill); the flash kernels' backward has
    neither."""
    if impl == "auto":
        from ray_tpu.utils import is_tpu

        use_flash = (
            is_tpu()
            and segment_ids is None
            and q.shape[1] % 128 == 0
            and q.shape[-1] in (64, 128, 192, 256)
        )
        impl = "flash" if use_flash else "xla"
    if (window or lens is not None) and impl in ("flash", "flash_interpret"):
        return flash_attention_fwd(q, k, v, causal, impl != "flash", window,
                                   lens)
    if impl == "flash":
        return _flash(q, k, v, causal, False)
    if impl == "flash_interpret":
        return _flash(q, k, v, causal, True)
    return reference_attention(q, k, v, causal, segment_ids, window)
