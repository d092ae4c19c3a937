"""A power-retention layer's recurrence (gated degree-2 power attention:
Manifest AI, *Scaling Context Requires Rethinking Attention*, arXiv
2507.04239): linear attention whose feature map is the symmetric square of the
key. Per key/value head a matrix state ``S`` [D feature lanes, V value
lanes] and a normaliser ``z`` [D], float32, ``D = n (n + 1) / 2`` for keys of
``n`` lanes (8,256 at 128); several query heads (``H / KVH``, query head
``h`` reads the state of ``h // (H / KVH)``) read ONE state. A position ``t``
with ``q_t`` [n] a query head, ``k_t`` [n], ``v_t`` [V] and the log-gate
``log g_t`` (<= 0, a scalar) a key/value head::

    phi(x)_(i,j) = x_i x_j (i = j),  sqrt(2) x_i x_j (i < j)     phi(x).phi(y) = (x.y)^2
    S_t = g_t S_{t-1} + phi(k_t) v_t^T
    z_t = g_t z_{t-1} + phi(k_t)
    o_t = phi(s q_t)^T S_t / phi(s q_t)^T z_t                    s = n^-0.5

which is attention with the weights ``a[t, j] = (s q_t . k_j)^2 prod_{j < r <=
t} g_r`` divided by their sum: no softmax, no maximum, no window. ``s``
cancels in the quotient and is kept for the range of the float32 sums.

``retention_reference`` is the recurrence as it reads, one position a
``lax.scan`` step, float32, with the explicit ``phi`` (a gather of the D
pairs): what both kernels are held to.

**The state's layout.** The kernels do not keep ``S`` by the pair ``(i, j)``
but by the ROTATION that makes the pair: slab ``r`` (``r = 0 .. n / 2``) holds
``sum_t decay k_t[i] k_t[(i - r) mod n] v_t`` at ``[value lane, i]``, so a
slab is one ``[n, n]`` tile, its features are a 128-lane operand times the
same operand rotated by ``r`` lanes (``pltpu.roll``: no gather, no unaligned
slice), and every unordered pair lies in exactly one slab (those at distance
``n / 2`` twice: that slab is read with weight 1, the slabs ``0 < r < n / 2``
with weight 2, slab 0 with 1, so that the sum over slabs is ``(q . k)^2``
again). The values lie along the SUBLANES and the features along the lanes:
the prefill kernel's products are then ``phi(Q) S^T`` (transposed right-hand
side, the MXU's own) and ``V^T phi(K)`` (ONE transpose of a chunk's values for
all slabs), and no slab is ever transposed. The normaliser is kept as the
symmetric matrix ``M = sum_t decay k_t k_t^T`` [n, n] (``z`` holds the same
numbers once each: ``phi(q)^T z = q^T M q``), one more slab behind the
``n / 2 + 1`` of ``S``. A head's state is ``[n / 2 + 2, n, n]`` float32: 66 x
128 x 128 x 4 B = 4.33 MB, 34.6 MB a slot and layer at 8 heads, where the
exact ``8 x 8256 x 129`` floats are 34.08 MB: 1.5% more bytes moved a decode
step, for whole tiles everywhere. ``rolled_state`` lays a reference's ``(S,
z)`` out this way.

``retention_scan`` (Pallas, name ``retention_scan``; ``retention_prefill``
below is how the engine calls it) is the chunked evaluation of the SAME
recurrence over a prefill call's rows, from a zero state: the grid is (row,
key/value head, chunk of ``CHUNK`` positions), a head's chunks follow each
other and its state stays in fast memory (the output block itself, written
back once). Over a chunk with ``G_t = sum_{u <= t} log g_u``::

    A  = (s Q K^T)^2 * exp(G_t - G_j)   j <= t         inside the chunk
    O  = A V + e^G phi(s Q) S           n = A 1 + e^G (s Q) M (s Q)^T
    S' = e^{G_C} S + (e^{G_C - G} V)^T phi(K)          M' likewise, o = O / n

The decay is a scalar a head, so every factor is formed as a difference of
cumulative sums and is at most one. The ``H / KVH`` query heads of a state
are stacked along the rows. ``phi``'s rows are built a slab at a time in fast
memory (an operand times its rotation) and contracted at once: no ``[S, D]``
array is ever written. The first chunk of a row reads no state (it is
zero): a prompt of at most ``CHUNK`` positions costs the quadratic form
alone. Products take their operands in the type q, k and v arrive in
(bfloat16 in a bfloat16 model, float32 accumulation; float32 at the highest
precision in a float32 one, which is how the tests hold the kernel to the
reference at 1e-5); ``G``, every ``exp``, ``n``, the carried state and the
quotient are float32. **Lengths are prefetched** as ``ops/kda.py``'s: from
``lengths[r]`` on ``log g = 0`` and ``k = 0``, a chunk wholly behind its row's
end does none of the body, reads nothing (its blocks' index maps are clamped
to the row's last real chunk) and writes zeros to ``o``, so the state that
comes back is the one after ``lengths - 1``; a row of length 0 leaves a zero
state. ``o`` behind a prompt's end is zeros. The state's output block IS the
row's slot of the cache's leaf (the slots prefetched, the leaf aliased): a
head's state goes from fast memory to where decode reads it, once, and a
call of 32 rows holds no 1.1 GB array of their states. A padding row's lands
in the slot past the engine's last, which the leaf has for that and nothing
reads (34.6 MB a layer).

**A decode step reads a slot's state every position and writes it back
every fourth** (``FOLD``). Between two write-backs a slot holds, beside its
state as of the last one ``S_f``, the ``FOLD - 1`` positions since (their
``k``, ``v`` and log-gates, float32, 36 KB a slot and layer at 8 heads; a
null position ``k = 0``, ``log g = 0`` where it has fewer: one that adds
nothing and decays nothing, whenever it is taken). It is the same function,
re-associated: with ``Gamma`` the decay since the write-back and ``gamma_j``
the decay from pending position ``j`` to now,

    phi(s q)^T S_t = Gamma phi(s q)^T S_f + sum_j gamma_j (s q . k_j)^2 v_j
    S_t            = Gamma S_f + sum_j gamma_j phi(k_j) v_j^T

over the pending positions and the step's own (the normaliser likewise from
``M_f``), float32 throughout, no position dropped and nothing rounded that
was not. ``retention_decode`` is a layer's step: ONE count of the pending
positions for all slots (``Cache.pending_count``, advanced by the program,
``advance``) decides on the device (``lax.cond``) which of two passes runs:

- ``retention_read`` (Pallas, name ``retention_read``), three steps of four:
  the grid is (slot, key/value head), a head's ``[n / 2 + 2, n, n]`` state is
  read ONCE and not written; the query heads' ``phi(s q)^T S_f`` and ``(s
  q)^T M_f (s q)`` come out of the pass, accumulated slab by slab on the
  vector unit; the pending positions' and the step's own terms are a few
  hundred thousand multiply-adds around it (elementwise, so that they stay
  float32), then the quotient. The step's ``k``, ``v``, ``log g`` join the
  pending rows. Bound by bytes: 34.6 MB a slot, once;
- ``retention_step`` (Pallas, name ``retention_step``; ``retention_riding``
  where a prefill call carries the step, which ALWAYS folds), THE FOLD, every
  fourth step: the state is read, takes the pending positions and the
  step's own, and is written IN PLACE (``input_output_aliases`` on the whole
  ``[layers, B + 1, KVH, n / 2 + 2, n, n]`` leaf: the other layers' bytes are
  never touched and nothing is copied), the queries read from the same pass;
  the caller empties the pending rows. With nothing pending it is one
  position of the recurrence. A slot with ``g = 1`` and ``k = 0`` and
  nothing pending keeps its state to the bit: that is how ``keep`` leaves the
  slots a prompt has just written untouched. Bound by bytes: 2 x 34.6 MB a
  slot.

The read pass takes the leaf aliased too and writes one tile of the slot
past the last (``retention_prefill``'s scratch, which nobody reads): both
branches of the ``cond`` then hand back the leaf they were given, and the
compiler has no reason to copy 6.6 GB. ``retention_prefill`` empties the
pending rows of the slots it fills (``llm/model_runner.py``): a slot
admitted between two folds has fewer positions pending than its neighbours,
and its last tenant's are not its own.

Off the TPU both kernels run in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# positions a grid step of retention_scan takes
CHUNK = 512
# a slot's state is written back every FOLD-th decode step: between two
# write-backs it keeps the FOLD - 1 positions since (their k, v, log g)
FOLD = 4
# fast memory a kernel may use: a head's state twice (a block in, a block
# out, each double-buffered in the fold's kernel) and a chunk's temporaries
_VMEM_LIMIT = 64 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_HIGHEST = jax.lax.Precision.HIGHEST


def check_degree(degree: int) -> None:
    if degree != 2:
        raise ValueError(f"power retention of degree {degree}: only the "
                         "symmetric square (degree 2) is implemented")


def phi(x, degree: int = 2):
    """The symmetric power of x [.., n] -> [.., n (n + 1) / 2]: ``x_i x_j``
    for ``i = j`` and ``sqrt(2) x_i x_j`` for ``i < j``, so that ``phi(x) .
    phi(y) = (x . y)^2`` exactly."""
    check_degree(degree)
    i, j = np.triu_indices(x.shape[-1])
    return x[..., i] * x[..., j] * np.where(i == j, 1.0, np.sqrt(2.0)).astype(
        np.float32)


def retention_reference(q, k, v, log_g, degree: int = 2):
    """q [R, S, H, n], k [R, S, KVH, n], v [R, S, KVH, V], log_g [R, S, KVH]
    (<= 0; 0 with ``k = 0`` on padding) -> (o [R, S, H, V], the state after
    the last position S [R, KVH, D, V], z [R, KVH, D]), float32, from a zero
    state. Query head ``h`` reads the state of ``h // (H / KVH)``."""
    R, _, H, n = q.shape
    KVH, V = v.shape[-2:]
    D = n * (n + 1) // 2

    def step(carry, t):
        S, z = carry
        q_t, k_t, v_t, g_t = t        # [R, H, n], [R, KVH, n], .., [R, KVH]
        g_t, f_k = jnp.exp(g_t), phi(k_t, degree)
        S = g_t[..., None, None] * S + f_k[..., None] * v_t[..., None, :]
        z = g_t[..., None] * z + f_k
        f_q = phi(q_t * n ** -0.5, degree).reshape(R, KVH, H // KVH, D)
        num = jnp.einsum("rgjd,rgdv->rgjv", f_q, S)
        den = jnp.einsum("rgjd,rgd->rgj", f_q, z)
        return (S, z), (num / den[..., None]).reshape(R, H, V)

    f32 = lambda t: jnp.moveaxis(t.astype(jnp.float32), 1, 0)   # noqa: E731
    (S, z), o = jax.lax.scan(
        step, (jnp.zeros((R, KVH, D, V), jnp.float32),
               jnp.zeros((R, KVH, D), jnp.float32)),
        (f32(q), f32(k), f32(v), f32(log_g)))
    return jnp.moveaxis(o, 0, 1), S, z


def state_shape(n: int):
    """A head's state as the kernels keep it: ``n / 2 + 1`` slabs of ``S``
    and the normaliser's matrix, each [n, n]."""
    if n % 2:
        raise ValueError(f"a power-retention head of {n} lanes: the rotated "
                         "layout pairs lanes at distance n / 2")
    return n // 2 + 2, n, n


def _slab_weight(r, n):
    """What a query's slab ``r`` is read with: 2 where a pair lies once in
    the slab as ``x_i x_j`` without its ``sqrt(2)`` on either side, 1 for the
    squares (slab 0) and for the slab of distance ``n / 2``, which holds each
    of its pairs twice."""
    return 1.0 if r in (0, n // 2) else 2.0


def rolled_state(S, z):
    """A reference's state (S [.., D, V], z [.., D]) as the kernels keep it,
    [.., n / 2 + 2, V, n]: slab ``r`` at ``[v, i]`` the pair ``(i, (i - r)
    mod n)`` of ``S`` without its ``sqrt(2)``, the last slab the normaliser as
    the symmetric matrix ``M[a, b]``. ``V = n``."""
    n = S.shape[-1]
    a, b = np.triu_indices(n)
    pair = np.zeros((n, n), np.int32)
    pair[a, b] = pair[b, a] = np.arange(len(a))
    plain = np.where(np.eye(n, dtype=bool), 1.0, np.sqrt(0.5)).astype(
        np.float32)                              # [n, n]: 1 / phi's weight
    lane = np.arange(n)
    slabs = []
    for r in range(n // 2 + 1):
        other = (lane - r) % n
        slab = S[..., pair[lane, other], :] * plain[lane, other][:, None]
        slabs.append(jnp.swapaxes(slab, -1, -2))                 # [.., V, i]
    slabs.append(z[..., pair] * plain)
    return jnp.stack(slabs, axis=-3)


# -- the chunked form over a prefill call's rows ---------------------------------


def _own(t, h):
    """Column ``h`` of t [C, KVH] as [C, 1]: mask and reduce."""
    lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    return jnp.sum(jnp.where(lane == h, t, 0.0), axis=-1, keepdims=True)


def _scan_kernel(len_ref, slot_ref, layer_ref, q_ref, k_ref, v_ref, g_ref,
                 ssm_ref, o_ref, s_ref, *, rep):
    """A grid step: one chunk of one key/value head and the ``rep`` query
    heads that read it. ``s_ref`` is the state's output block, the row's slot
    of the whole leaf (``ssm_ref``: the same bytes, never read), resident
    over the head's chunks."""
    del slot_ref, layer_ref, ssm_ref
    r, h, chunk = (pl.program_id(axis) for axis in range(3))
    first, length = chunk * k_ref.shape[0], len_ref[r]

    @pl.when(chunk == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(first < length)
    def _():
        _chunk_step(h, chunk, first, length, q_ref, k_ref, v_ref, g_ref,
                    o_ref, s_ref, rep)

    # wholly behind the prompt's end: zeros, the state as it is, and nothing
    # read (the blocks are the last real chunk's, ``_real_chunk``)
    @pl.when(first >= length)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _chunk_step(h, chunk, first, length, q_ref, k_ref, v_ref, g_ref, o_ref,
                s_ref, rep):
    f32 = jnp.float32
    C, n = k_ref.shape
    slabs = s_ref.shape[0] - 1
    kind = v_ref.dtype                    # what the large products multiply in
    one = functools.partial(jax.lax.dot_general, preferred_element_type=f32)
    exact = functools.partial(one, precision=_HIGHEST)
    mxu = exact if kind == f32 else one
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    in_prompt = first + jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) < length

    # no decay and no key from the prompt's end on
    g = jnp.where(in_prompt, _own(g_ref[...].astype(f32), h), 0.0)   # [C, 1]
    k = jnp.where(in_prompt, k_ref[...].astype(f32), 0.0)            # [C, n]
    v = v_ref[...]
    # the log-gate summed from the chunk's first position on, down the rows
    # and (the same numbers) along the lanes
    g_wide = jnp.broadcast_to(g, (C, n))
    G_wide = exact((row >= col).astype(f32), g_wide, _NN)            # [C, n]
    G = G_wide[:, :1]                                                # [C, 1]
    G_row = exact(g_wide, (row <= col).astype(f32), _TN)[:1]         # [1, C]
    decay = jnp.where(row >= col, jnp.exp(jnp.minimum(G - G_row, 0.0)), 0.0)
    scale = n ** -0.5
    qs = [q_ref[:, j * n:(j + 1) * n].astype(f32) * scale for j in range(rep)]
    k_kind = k.astype(kind)

    # inside the chunk: the squared scores under the decay and the mask
    num, den = [], []
    for q in qs:
        a = mxu(q.astype(kind), k_kind, _NT)
        a = a * a * decay                                            # [C, C]
        num.append(mxu(a.astype(kind), v, _NN))
        den.append(jnp.sum(a, axis=-1, keepdims=True))
    num, den = jnp.concatenate(num), jnp.concatenate(den)       # [rep C, ..]

    # what the chunks before this one left: the queries' slabs against the
    # state's, a slab at a time; the first chunk's state is zero
    stacked = jnp.concatenate(qs)                                # [rep C, n]

    def earlier():
        def slab(r, carry):
            acc, turned = carry
            weight = jnp.where((r == 0) | (r == slabs - 1), 1.0, 2.0)
            feature = (stacked * turned * weight).astype(kind)
            acc = acc + mxu(feature, s_ref[r].astype(kind), _NT)
            return acc, pltpu.roll(turned, 1, 1)

        acc, _ = jax.lax.fori_loop(
            0, slabs, slab, (jnp.zeros((rep * C, n), f32), stacked))
        norm = mxu(stacked.astype(kind), s_ref[slabs].astype(kind), _NN)
        return acc, jnp.sum(norm * stacked, axis=-1, keepdims=True)

    def nothing():
        return jnp.zeros((rep * C, n), f32), jnp.zeros((rep * C, 1), f32)

    acc, norm = jax.lax.cond(chunk > 0, earlier, nothing)
    reach = jnp.concatenate([jnp.exp(G)] * rep)                  # [rep C, 1]
    num, den = num + reach * acc, den + reach * norm
    o = jnp.where(jnp.concatenate([in_prompt] * rep), num / den, 0.0)
    for j in range(rep):
        o_ref[:, j * n:(j + 1) * n] = o[j * C:(j + 1) * C].astype(o_ref.dtype)

    # the state over the chunk: what it held decays by the whole chunk, a
    # position's update by what follows it in the chunk
    left = jnp.exp(G[C - 1:C] - G)                                   # [C, 1]
    whole = jnp.exp(G_wide[C - 1:C])         # [1, n]: a row, the same n times
    eye = (row == col).astype(kind)
    # [n, C]: the ONE transpose all slabs share (a product with the identity)
    v_t = mxu((v.astype(f32) * left).astype(kind), eye, _TN).astype(kind)
    k_t = mxu((k * left).astype(kind), eye, _TN).astype(kind)

    def update(r, turned):
        s_ref[r] = whole * s_ref[r] + mxu(v_t, (k * turned).astype(kind), _NN)
        return pltpu.roll(turned, 1, 1)

    jax.lax.fori_loop(0, slabs, update, k)
    s_ref[slabs] = whole * s_ref[slabs] + mxu(k_t, k_kind, _NN)


def _tile(S, chunk):
    """The positions a grid step takes of a bucket of ``S``."""
    return chunk if S % chunk == 0 else S


def scan_chunks(S, lengths):
    """On the host: the chunks ``retention_prefill``'s grid has a head for
    rows of ``lengths`` in a bucket of ``S``, and those of them it passes
    over (a chunk wholly behind its row's end)."""
    T = _tile(S, CHUNK)
    chunks = len(lengths) * (S // T)
    return chunks, chunks - sum(-(-int(n) // T) for n in lengths)


def _real_chunk(r, t, T, len_ref):
    """Chunk ``t`` of row ``r`` or, where ``t`` lies wholly behind the row's
    end, the last chunk that holds a real position (the first of an empty
    row): the block a step that is passed over already holds."""
    return jnp.minimum(t, jnp.maximum(len_ref[r] - 1, 0) // T)


@functools.partial(jax.jit, static_argnames=("heads", "chunk"))
def retention_prefill(q, k, v, log_g, lengths, ssm, layer, slots, *, heads,
                      chunk: int = CHUNK):
    """A layer's recurrence over a prefill call's rows from a zero state,
    each row's final state written straight INTO its slot of ``ssm`` [layers,
    B + 1, KVH, n / 2 + 2, n, n] float32, which the caller hands over donated
    (``input_output_aliases``: no array of the rows' states is made, and no
    scatter): q [R, S, H n] (after the head norm and the rotation), k, v [R,
    S, KVH n] in the stored type, log_g [R, S, KVH] float32 (<= 0), the
    prompts' lengths [R], the ``layer`` (traced) and the rows' ``slots`` [R];
    ``heads`` = (H, KVH). A padding row names the slot past the engine's
    last, ``B``: the leaf's scratch slot, which nothing reads. Returns (o [R,
    S, H n] in the stored type, as ``o_proj`` reads it, zeros behind a
    prompt's end; ``ssm`` with the slots' states after ``lengths - 1``, zeros
    for a row of length 0). ``S`` is a multiple of ``chunk`` or one chunk.
    Jitted: a program of several such layers traces and lowers the kernel
    once."""
    H, KVH = heads
    R, S, _ = k.shape
    n, rep = k.shape[-1] // KVH, H // KVH
    T = _tile(S, chunk)
    state = state_shape(n)
    last = ssm.shape[1] - 1

    def lanes(width):
        return pl.BlockSpec((None, T, width), lambda r, h, t, lengths, *_: (
            r, _real_chunk(r, t, T, lengths), h))

    def call(*operands, interpret):
        return pl.pallas_call(
            functools.partial(_scan_kernel, rep=rep),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(R, KVH, S // T),
                in_specs=[lanes(rep * n), lanes(n), lanes(n),
                          pl.BlockSpec(
                              (None, T, KVH), lambda r, h, t, lengths, *_: (
                                  r, _real_chunk(r, t, T, lengths), 0)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=[
                    pl.BlockSpec((None, T, rep * n),
                                 lambda r, h, t, *_: (r, t, h)),
                    pl.BlockSpec(
                        (None, None, None, *state),
                        lambda r, h, t, lengths, slots, layer: (
                            layer[0], jnp.minimum(slots[r], last), h, 0, 0,
                            0))]),
            out_shape=[jax.ShapeDtypeStruct((R, S, H * n), q.dtype),
                       jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
            input_output_aliases={7: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            name="retention_scan",
        )(*operands)

    operands = (lengths.astype(jnp.int32), slots.astype(jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1), q, k, v,
                log_g.astype(jnp.float32), ssm)
    from ray_tpu.utils import is_tpu

    if is_tpu():    # known while tracing: the interpreted call is not traced
        return call(*operands, interpret=False)
    return jax.lax.platform_dependent(
        *operands, tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True))


def retention_scan(q, k, v, log_g, lengths=None, *, chunk: int = CHUNK):
    """``retention_reference`` through the chunked kernel: q [R, S, H, n], k,
    v [R, S, KVH, n], log_g [R, S, KVH], lengths [R] (None: every row is
    whole) -> (o [R, S, H, n] float32 where q is, the state [R, KVH, n / 2 +
    2, n, n])."""
    R, S, H, n = q.shape
    if lengths is None:
        lengths = jnp.full((R,), S, jnp.int32)
    flat = lambda t: t.reshape(R, S, -1)   # noqa: E731
    KVH = k.shape[2]
    o, ssm = retention_prefill(
        flat(q), flat(k), flat(v), log_g, lengths,
        jnp.zeros((1, R + 1, KVH, *state_shape(n)), jnp.float32), 0,
        jnp.arange(R), heads=(H, KVH), chunk=chunk)
    return o.reshape(R, S, H, n), ssm[0, :R]


# -- one decode step of one layer, every slot: the read pass and the fold --------


def _pass_kernel(layer_ref, s_ref, rows_ref, cols_ref, o_ref, out_ref, *, rep,
                 fold):
    """One slot's head, the state read once. ``rows`` [.., n] holds the
    ``rep`` scaled queries as rows (features along the lanes), ``cols`` [n,
    ..] the same as columns; ``o`` takes each query's ``phi(s q)^T S`` in
    lane ``j`` and, in lane ``rep + j``, the terms of ``(s q)^T M (s q)`` by
    sublane (the caller sums them). Where the pass ``fold``s, ``rows`` also
    holds the ``FOLD`` positions' keys and the decay since the last fold as
    rows, ``cols`` each position's value and key as columns, times its decay
    from there to now: the state takes them, is written back to ``out_ref``,
    and the queries read what is written. Where it does not, ``out_ref`` is
    one tile of the leaf's scratch slot, which nobody reads."""
    del layer_ref
    slabs, n = s_ref.shape[0] - 1, s_ref.shape[-1]
    rows, cols = rows_ref[...], cols_ref[...]
    keys = range(rep, rep + FOLD)
    if fold:
        gate = rows[rep + FOLD:rep + FOLD + 1]                       # [1, n]
    else:
        out_ref[...] = jnp.zeros_like(out_ref)
    acc = [jnp.zeros((n, n), jnp.float32)] * rep
    for r in range(slabs):
        # every row times itself turned by r lanes: the queries' and the
        # keys' features of this slab
        feature = rows * (pltpu.roll(rows, r, 1) if r else rows)
        slab = s_ref[r]
        if fold:
            slab = gate * slab
            for p in keys:
                slab = slab + cols[:, p:p + 1] * feature[p:p + 1]
            out_ref[r] = slab
        for j in range(rep):
            acc[j] = acc[j] + slab * (feature[j:j + 1] * _slab_weight(r, n))
    norm = s_ref[slabs]
    if fold:
        norm = gate * norm
        for p in keys:
            norm = norm + cols[:, FOLD + p:FOLD + p + 1] * rows[p:p + 1]
        out_ref[slabs] = norm
    lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    o = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(rep):
        num = jnp.sum(acc[j], axis=1, keepdims=True)                 # [n, 1]
        den = jnp.sum(norm * rows[j:j + 1], axis=1, keepdims=True) \
            * cols[:, j:j + 1]                                       # [n, 1]
        o = jnp.where(lane == j, num, jnp.where(lane == rep + j, den, o))
    o_ref[...] = o


def _state_pass(layer, ssm, rows, cols, *, rep, fold, name, interpret):
    (B, KVH), state = rows.shape[:2], ssm.shape[3:]
    n = state[-1]
    state_spec = pl.BlockSpec(
        (None, None, None, *state),
        lambda s, h, layer: (layer[0], s, h, 0, 0, 0))
    row_spec = pl.BlockSpec((None, None, rows.shape[2], n),
                            lambda s, h, _: (s, h, 0, 0))
    col_spec = pl.BlockSpec((None, None, n, cols.shape[3]),
                            lambda s, h, _: (s, h, 0, 0))
    width = -(-2 * rep // 8) * 8
    out = pl.BlockSpec((None, None, n, width), lambda s, h, _: (s, h, 0, 0))
    # the read pass hands the leaf back as the fold does, aliased, so that
    # either branch of the caller's ``cond`` owns it and neither copies it;
    # what it writes is one tile of the slot past the last
    parked = pl.BlockSpec(
        (None, None, None, None, 8, n),
        lambda s, h, layer: (layer[0], ssm.shape[1] - 1, 0, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_pass_kernel, rep=rep, fold=fold),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, KVH),
            in_specs=[state_spec, row_spec, col_spec],
            out_specs=[out, state_spec if fold else parked]),
        out_shape=[jax.ShapeDtypeStruct((B, KVH, n, width), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(layer, ssm, rows, cols)


def _stacked(*parts):
    """``parts`` [B, KVH, .., n] one under the other, zero rows up to whole
    tiles of 8."""
    rows = jnp.concatenate(parts, axis=2)
    return jnp.pad(rows, [(0, 0), (0, 0), (0, -rows.shape[2] % 8), (0, 0)])


def _by_state(q, KVH):
    """q [B, H, n] scaled, float32, by the state its head reads: [B, KVH, H /
    KVH, n]."""
    B, H, n = q.shape
    return q.astype(jnp.float32).reshape(B, KVH, H // KVH, n) * n ** -0.5


def _positions(k, v, log_g, keep, pending):
    """The ``FOLD`` positions a pass takes beside the state, oldest first, the
    step's own last: their keys and values [B, KVH, FOLD, n], the decay from
    each to now [B, KVH, FOLD] (1 for the step's own) and the decay since the
    last fold [B, KVH], float32, every factor a sum of log-gates and at most
    one; with them the step's own position as a pending row [3, B, KVH, n].
    A slot ``keep`` does not mark gets a null position (no key, no decay)."""
    f32 = lambda t: t.astype(jnp.float32)   # noqa: E731
    k, v, log_g = f32(k), f32(v), f32(log_g)
    if keep is not None:
        k, v = (jnp.where(keep[:, None, None], t, 0.0) for t in (k, v))
        log_g = jnp.where(keep[:, None], log_g, 0.0)
    own = jnp.stack([k, v, jnp.broadcast_to(log_g[..., None], k.shape)])
    if pending is None:
        pending = jnp.zeros((FOLD - 1, *own.shape), jnp.float32)
    held = jnp.moveaxis(jnp.concatenate([pending, own[None]]), 0, 3)
    keys, values, gates = held[0], held[1], held[2, ..., 0]
    after = [jnp.zeros_like(log_g)]      # the log-gates behind a position
    for p in range(FOLD - 1, 0, -1):
        after.insert(0, after[0] + gates[..., p])
    return (keys, values, jnp.exp(jnp.stack(after, axis=-1)),
            jnp.exp(after[0] + gates[..., 0]), own)


def _call(kernel, layer, ssm, rows, cols):
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    return jax.lax.platform_dependent(
        layer, ssm, rows, cols,
        tpu=functools.partial(kernel, interpret=False),
        default=functools.partial(kernel, interpret=True))


def _read_out(o, rep):
    """A pass's ``o`` [B, KVH, n, ..] -> each query's sum over the state [B,
    KVH, rep, n] and its normaliser [B, KVH, rep]."""
    return (jnp.swapaxes(o[..., :rep], 2, 3),
            jnp.sum(o[..., rep:2 * rep], axis=2))


@functools.partial(jax.jit, static_argnames=("name",))
def retention_step(ssm, layer, q, k, v, log_g, keep=None, pending=None, *,
                   name: str = "retention_step"):
    """THE FOLD: the positions since a slot's last write-back (``pending``
    [FOLD - 1, 3, B, KVH, n] float32: each one's key, value and log-gate, the
    last along the lanes, a null one ``k = 0``, ``log g = 0``; None: nothing
    pending, and this is one position of ``retention_reference``) and the
    step's own position into every slot's state, on layer ``layer`` (traced)
    of ``ssm`` [layers, B + 1, KVH, n / 2 + 2, n, n] float32 (the slot past
    the last is ``retention_prefill``'s scratch: no step touches it), which
    the caller hands over donated, and the queries' outputs read from the same
    pass: q [B, H, n], k, v [B, KVH, n], log_g [B, KVH] (<= 0), keep [B] (a
    slot it does not mark takes a null position: with nothing pending it
    keeps its state to the bit; None: all step) -> (o [B, H, n] float32,
    ``ssm`` with the layer's state as of this position). The caller empties
    the pending rows. Jitted: a program of several such layers traces and
    lowers the kernel once."""
    (B, H, n), KVH = q.shape, k.shape[1]
    rep, q = H // KVH, _by_state(q, KVH)
    keys, values, since, whole, _ = _positions(k, v, log_g, keep, pending)
    rows = _stacked(q, keys, jnp.broadcast_to(whole[..., None, None],
                                              (B, KVH, 1, n)))
    cols = jnp.swapaxes(_stacked(q, values * since[..., None],
                                 keys * since[..., None]), 2, 3)
    o, ssm = _call(functools.partial(_state_pass, rep=rep, fold=True,
                                     name=name), layer, ssm, rows, cols)
    num, den = _read_out(o, rep)
    return (num / den[..., None]).reshape(B, H, n), ssm


@jax.jit
def retention_read(ssm, layer, q, k, v, log_g, keep=None, pending=None):
    """THE READ PASS (Pallas, name ``retention_read``): ``retention_step``'s
    ``o`` for the same operands with the state read once and NOT written: the
    queries against the state as of the last fold, decayed to now, plus the
    pending positions and the step's own by ``phi(x) . phi(y) = (x . y)^2``.
    Returns (o [B, H, n] float32, ``ssm``, the same bytes and donated as
    the fold's, the step's own position as a pending row [3, B, KVH, n]:
    what the caller keeps in place of a written state)."""
    (B, H, n), KVH = q.shape, k.shape[1]
    rep, q = H // KVH, _by_state(q, KVH)
    keys, values, since, whole, own = _positions(k, v, log_g, keep, pending)
    rows = _stacked(q)
    o, ssm = _call(functools.partial(_state_pass, rep=rep, fold=False,
                                     name="retention_read"),
                   layer, ssm, rows, jnp.swapaxes(rows, 2, 3))
    num, den = _read_out(o, rep)
    # elementwise, then summed: float32 as it stands (a product on the TPU
    # would round its operands)
    weight = jnp.sum(q[:, :, :, None] * keys[:, :, None], axis=-1) ** 2 \
        * since[:, :, None]                                # [B, KVH, rep, FOLD]
    num = whole[..., None, None] * num + jnp.sum(
        weight[..., None] * values[:, :, None], axis=3)
    den = whole[..., None] * den + jnp.sum(weight, axis=-1)
    return (num / den[..., None]).reshape(B, H, n), ssm, own


@functools.partial(jax.jit, static_argnames=("riding",))
def retention_decode(ssm, pending, count, layer, q, k, v, log_g, keep=None, *,
                     riding: bool = False):
    """One decode step of one layer for every slot, as the cache's own count
    of the pending positions says: the read pass, whose position joins the
    pending rows, or (``count`` = FOLD - 1; ``riding``, a step that a prefill
    call carries: always, under the name ``retention_riding``) the fold, which
    empties them. ``ssm`` and ``pending`` [layers, FOLD - 1, 3, B, KVH, n] are
    the whole leaves, handed over donated -> (o [B, H, n] float32, ``ssm``,
    ``pending``). The caller advances the count (``advance``), once a step."""
    def fold(ssm, pending):
        o, ssm = retention_step(
            ssm, layer, q, k, v, log_g, keep, pending[layer],
            name="retention_riding" if riding else "retention_step")
        return o, ssm, pending.at[layer].set(0.0)

    def read(ssm, pending):
        o, ssm, own = retention_read(ssm, layer, q, k, v, log_g, keep,
                                     pending[layer])
        return o, ssm, pending.at[layer, count].set(own)

    if riding:
        return fold(ssm, pending)
    return jax.lax.cond(count >= FOLD - 1, fold, read, ssm, pending)


def advance(count):
    """The count of pending positions after a decode step that found
    ``count``: a fold leaves none."""
    return jnp.where(count >= FOLD - 1, 0, count + 1)
