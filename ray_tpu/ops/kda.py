"""A delta-rule linear-attention layer's recurrence with a decay per key lane
(KDA, Kimi Linear, arXiv 2510.26692): per head a matrix state ``S`` [K key
lanes, V value lanes], float32, and a position ``t`` with ``q_t, k_t`` [K],
``v_t`` [V], the log-decay ``g_t`` [K] (<= 0) and ``beta_t`` in (0, 1)::

    S~  = diag(exp(g_t)) S_{t-1}
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t

The update READS the state (it takes out what the state already holds along
``k_t`` before it writes ``v_t`` there) and the decay is a vector, so neither
``ops/ssd.py``'s chunked form (a scalar decay a head, an update that does not
read the state) nor ``ops/ssm.py`` serves it. At 32 heads of 128 x 128 the
state is 2.1 MB a slot and layer.

``kda_reference`` is the recurrence as it reads, one position a ``lax.scan``
step, float32: what both kernels are held to.

``kda_scan`` (Pallas, name ``kda_scan``; ``kda_prefill`` below is how the
engine calls it) is the chunked evaluation of the SAME
recurrence over a prefill call's rows, from a zero state: the grid is (row,
heads, chunk of ``CHUNK`` positions), a grid step takes four heads
(``_heads_a_step``: two or one where four do not divide the layer's), a
head's chunks follow each other and its state stays in fast memory. Over a
chunk with ``G_t = sum_{u <= t} g_u`` and
``Kb = diag(beta) K``::

    N  = tril_strict((Kb e^G) (K e^-G)^T)          what position t reads of s < t
    A  = (I + N)^-1                                 the triangular solve
    W  = A (Kb e^G)        U = A (diag(beta) V) - W S
    O  = (Q e^G) S + tril((Q e^G) (K e^-G)^T) U
    S' = diag(e^{G_C}) S + (K e^{G_C - G})^T U

``e^-G`` leaves float32's range within a few positions of a fast lane (a
seeded ``g`` reaches -4 a position, a trained one more), so no product is ever
formed from it: ``N`` and the query's matrix are built in sub-chunks of ``SUB``
positions. A block LEFT of the diagonal takes its exponents from the last
position before its rows' sub-chunk, ``e^{G_t - a} . e^{a - G_s}``, both
factors at most one; a block ON the diagonal is computed pair by pair,
``sum_d x_t[d] k_s[d] e^{G_t[d] - G_s[d]}``, a column at a time. Whatever
underflows there is a contribution that is zero in float32 anyway. The solve is
float32 throughout: the ``SUB``-wide diagonal blocks by forward substitution
(all of a chunk's at once, laid side by side ``SUB`` rows deep), the blocks
below them by the finite series of the block-nilpotent rest, whose powers'
empty rows are not computed. ``G`` (a product with a triangle of ones), every ``exp``
and the carried state are float32; the products with ``A``, with the state and
between sub-chunks take their operands in the type q, k and v arrive in
(bfloat16 in a bfloat16 model, float32 accumulation; float32 at the highest
precision in a float32 one, which is how the tests hold the kernel to the
reference at 1e-5). A position with ``g = 0`` and ``beta = 0`` leaves the
state as it was: that is how padding behind a prompt is passed over inside
the chunk that holds the prompt's end, so the state that comes back is the
one after ``lengths - 1``.

A grid step's heads are independent chains of dependent products, and what
the step costs is the chain, not the MXU's passes: the heads' stages are
stated in turn (``_in_turn``) so that the compiler lays one head's products
over another's vector work. At ``[1, 4096]``, 32 heads of 128, bfloat16, a
head and chunk take 1.79 us where one head a step took 3.87 (my chip runs,
PR 52; PERF.md section 6 has what each part was worth).

``kda_prefill`` is the SAME ``pallas_call`` (one kernel body, one name) handed
a layer's arrays as its products left them, and is what a prefill call runs:
everything between the convolutions and ``o_proj`` that is local to a position
and a head happens in the kernel's [chunk, 128 lanes] tile, so XLA makes no
pass over ``[S, 4096..12288]`` there and no float32 ``[S, 4096]`` is ever
written. It takes ``q | k | v`` after the convolutions and the silu as ONE
array [R, S, 3 H K] through three block specs (a head's lanes at ``h``, ``H +
h``, ``2 H + h``: no split), the decay gate's ``f`` and the output gate [R, S,
H K] in the stored type, beta [R, S, H] float32 (the head's column by mask and
reduce), ``dt_bias``, ``A_log``, the output norm's scale and the prompts'
lengths (scalar prefetch). Prologue, float32: ``q / |q| K^-0.5``, ``k / |k|``,
``g = -exp(A_log[h]) softplus(f + dt_bias)``, ``g = 0`` and ``beta = 0`` from
``lengths[r]`` on, ``beta k``, ``beta v``; q, k and ``beta k`` stay float32
where the recurrence uses them so and are cast where it casts them (they are
no longer rounded to the stored type on the way in), ``beta v`` enters its
product in the stored type as before. Epilogue: the head's RMSNorm of the
float32 ``o``, the sigmoid of the gate, the cast to the stored type, written
[R, S, H V] lane-dense as ``o_proj`` reads it. ``kda_scan`` on prepared
operands is the same body with neither (decided by what it is handed, at
trace time) and writes ``o`` float32: what the tests hold to
``kda_reference``.

``kda_prefill`` knows its rows' lengths, and a grid step whose chunk lies
WHOLLY behind its row's end (``chunk * T >= lengths[r]``; every chunk of a
padding row) does none of the body. It writes zeros to its tile of ``o``:
what lies behind a prompt's end is nobody's, but it goes on through
``o_proj``, the router and the expert kernels into the next layer's norms,
so it has to be finite. It leaves the carried states as they are, and it
reads nothing: the input blocks' index maps are clamped to the row's last
real chunk (``_real_chunk``: the first of an empty row), so the step asks
for the blocks it already holds and no copy is started. The first chunk's
zeroing of the states and the last chunk's copy to the result are not
conditional: an empty row hands back a zero state, and a prompt that ends in
chunk 3 of 128 the state after ``lengths - 1``. Inside the chunk that holds
the end, ``o`` behind the end is nobody's as before (finite: q and k are
normalised with an epsilon, ``g`` and ``beta`` are forced to zero there).
Such a step takes 0.33 us for its four heads against 7.1 us for a real one:
a ``[1, 16384]`` layer takes 7.30 ms at a full bucket, 5.57 ms at a prompt
of 12,288 and 0.41 ms at one of 100 (15.84 ms at any length before: my chip
runs, PR 52). ``kda_scan`` on prepared operands has no lengths and passes
over nothing.

``CHUNK`` is 128 where the family's own kernels take 64: the solve's cost a
position grows with the chunk, the state's products shrink with it, and on
the v5e a chunk's matrices are then whole 128-lane registers and whole MXU
tiles: 4.51 ms against 5.55 for a layer of ``[1, 4096]`` at 32 heads of 128
(my chip run, PR 49), and still 2.05 against 2.08 with four heads a step;
``SUB`` 8 and 32 both lose to 16 (2.23 and 2.21 ms against 2.05: my chip
run, PR 52).

``kda_step`` (Pallas, name ``kda_step``; ``kda_riding`` where a prefill call
carries the step) is one decode step of one layer for every slot: the grid is
(slot,), a slot's ``[H, K, V]`` state is read once, updated and written once
IN PLACE (``input_output_aliases`` on the whole ``[layers, B, H, K, V]`` leaf:
the other layers' bytes are never touched and nothing is copied), and ``o``
comes out of the same pass. What goes by key lane (the decay, ``k``, ``beta
k``, ``q``) arrives ``[B, K, H]``, the key lanes along the sublanes as the
state has them, so a head's is a column broadcast along the lanes; ``beta v``
is a row. A slot with ``g = 0`` and ``beta = 0`` keeps its state to the bit
(``S * 1 + k * 0``): that is how ``keep`` leaves the slots a prompt has just
written untouched. It is bound by bytes: 2 x 2.1 MB a slot.

Off the TPU both kernels run in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# positions a grid step of kda_scan takes
CHUNK = 128
# positions of a sub-chunk: the diagonal blocks that are computed pair by pair
# and solved by substitution
SUB = 16

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_HIGHEST = jax.lax.Precision.HIGHEST


def kda_reference(q, k, v, g, beta, s0=None):
    """q, k [R, S, H, K], v [R, S, H, V], g [R, S, H, K] (the log-decay, <= 0;
    0 on padding), beta [R, S, H] (0 on padding), s0 [R, H, K, V] (zeros where
    none is given) -> (o [R, S, H, V] float32, the state after the last
    position [R, H, K, V] float32)."""
    R, _, H, K = q.shape
    if s0 is None:
        s0 = jnp.zeros((R, H, K, v.shape[-1]), jnp.float32)

    def step(s, t):
        q_t, k_t, v_t, g_t, b_t = t       # [R, H, K] x 2, [R, H, V], .., [R, H]
        s = jnp.exp(g_t)[..., None] * s
        d = v_t - jnp.sum(s * k_t[..., None], axis=-2)
        s = s + (b_t[..., None] * k_t)[..., None] * d[..., None, :]
        return s, jnp.sum(s * q_t[..., None], axis=-2)

    f32 = lambda t: jnp.moveaxis(t.astype(jnp.float32), 1, 0)   # noqa: E731
    s, o = jax.lax.scan(step, s0.astype(jnp.float32),
                        (f32(q), f32(k), f32(v), f32(g), f32(beta)))
    return jnp.moveaxis(o, 0, 1), s


# -- the chunked form over a prefill call's rows ---------------------------------


def _raw_operands(h, first, length, q_ref, k_ref, v_ref, f_ref, beta_ref,
                  bias_ref, alog_ref):
    """The prologue of a grid step that is handed a layer's arrays as the
    convolution and the gates' products left them: head ``h``'s [C, K] lanes
    of ``q | k | v`` (after the silu) and of the decay gate's ``f``, every
    head's beta [C, H], the head's ``dt_bias`` [1, K], ``A_log`` [1, H], the
    chunk's ``first`` position and the row's ``length``. Returns what the
    recurrence takes, float32: q and k of unit length (q times ``K^-0.5``),
    ``beta k``, ``beta v`` (in the stored type, as the products take it) and
    the log-decay; no decay and no update behind the prompt's end."""
    f32 = jnp.float32
    C, K = q_ref.shape

    def unit(ref):
        t = ref[...].astype(f32)
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    def own(t):          # the head's column of t [.., H]: mask and reduce
        lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
        return jnp.sum(jnp.where(lane == h, t, 0.0), axis=-1, keepdims=True)

    def in_prompt(shape):
        return first + jax.lax.broadcasted_iota(jnp.int32, shape, 0) < length

    q, k = unit(q_ref) * K ** -0.5, unit(k_ref)
    g = own(-jnp.exp(alog_ref[...].astype(f32))) * jax.nn.softplus(
        f_ref[...].astype(f32) + bias_ref[...].astype(f32))
    g = jnp.where(in_prompt((C, K)), g, 0.0)
    beta = jnp.where(in_prompt((C, 1)), own(beta_ref[...].astype(f32)), 0.0)
    vb = (v_ref[...].astype(f32) * beta).astype(v_ref.dtype)
    return q, k, k * beta, vb, g


def _scan_kernel(*refs, heads, sub, eps):
    """A grid step: ``heads`` heads of one chunk. ``refs``: the prepared
    operands (q, k, beta k, beta v, g) or the rows' lengths and a layer's own
    arrays with the output gate and the output norm's scale behind them
    (``_raw_operands``; then ``o`` leaves normalised, gated and in the stored
    type, and a chunk wholly behind its row's end is passed over), the two
    results, the carried states."""
    *operands, o_ref, sT_ref, s_ref = refs
    r, h, chunk = (pl.program_id(axis) for axis in range(3))

    @pl.when(chunk == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def own(ref, j):       # head j's lanes of a block of ``heads`` heads
        lanes = ref.shape[-1] // heads
        return ref.at[:, pl.ds(j * lanes, lanes)]

    if len(operands) == 5:
        _in_turn(_chunk_step([own(ref, j) for ref in operands], None, None,
                             own(o_ref, j), s_ref.at[j], sub, eps)
                 for j in range(heads))
    else:
        len_ref, q, k, v, f, beta, bias, alog, gate, scale = operands
        first, length = chunk * o_ref.shape[0], len_ref[r]

        @pl.when(first < length)
        def _():
            _in_turn(_chunk_step(
                _raw_operands(h * heads + j, first, length, own(q, j),
                              own(k, j), own(v, j), own(f, j), beta,
                              own(bias, j), alog),
                own(gate, j), scale, own(o_ref, j), s_ref.at[j], sub, eps)
                for j in range(heads))

        # wholly behind the prompt's end: zeros, the states as they are, and
        # nothing read (the blocks are the last real chunk's, ``_real_chunk``)
        @pl.when(first >= length)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _():
        sT_ref[...] = s_ref[...]


def _in_turn(steps):
    """Runs generators a statement each in turn, to their ends: the heads of
    a grid step are independent chains of dependent products, and the
    compiler lays over each other what the program states side by side (four
    heads a step 2.74 us a head and chunk one after the other, 2.00 in turn:
    my chip run, PR 52)."""
    steps, done = list(steps), object()
    while steps:
        steps = [step for step in steps if next(step, done) is not done]


def _split3(x):
    """A float32 array as three bfloat16 terms that add up to it exactly: a
    product with a matrix of zeros and ones (exact in bfloat16) in three
    passes, float32 accumulation, where the highest precision spends six."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _chunk_step(operands, gate_ref, scale_ref, o_ref, s_ref, sub, eps):
    """One chunk of one head: ``o_ref``'s tile written and the state in
    ``s_ref`` moved over the chunk's positions; with ``gate_ref`` and
    ``scale_ref`` the output's norm and gate too. A generator: it yields
    between its stages (``_in_turn``)."""
    f32 = jnp.float32
    if gate_ref is None:
        q, k, kb = (ref[...].astype(f32) for ref in operands[:3])
        vb, g = operands[3][...], operands[4][...]
    else:
        q, k, kb, vb, g = operands
    C, K = q.shape
    kind = vb.dtype                       # what the large products multiply in
    one = functools.partial(jax.lax.dot_general, preferred_element_type=f32)
    exact = functools.partial(one, precision=_HIGHEST)
    mxu = exact if kind == f32 else one
    blocks = C // sub
    shift = sub.bit_length() - 1          # sub is a power of two
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = (row == col).astype(f32)
    same = (row >> shift) == (col >> shift)      # within a diagonal block

    # the log-decay summed from the chunk's first position on
    G = exact((row >= col).astype(f32), g, _NN)                  # [C, K]
    yield

    # what position t reads of position s, rows of ``sub`` positions at a time:
    # n for the update (beta k), p for the output (q)
    group = min(sub, 8)                   # a float32 register's rows
    cols = jax.lax.broadcasted_iota(jnp.int32, (group, C), 1)
    n_rows, p_rows = [], []
    for block in range(blocks):
        first = block * sub
        at = slice(first, first + sub)
        n_b = p_b = jnp.zeros((sub, C), f32)
        if block:       # left of the diagonal: both exponents from ``anchor``
            anchor = G[first - 1:first]                          # [1, K]
            left = jnp.exp(G[at] - anchor)
            # the positions before the block alone: zeros in the others' place
            earlier = (k[:first] * jnp.exp(anchor - G[:first])).astype(kind)
            both = mxu(
                jnp.concatenate([kb[at] * left, q[at] * left]).astype(kind),
                jnp.concatenate([earlier, jnp.zeros((C - first, K), kind)]),
                _NT)
            n_b, p_b = both[:sub], both[sub:]
        # on the diagonal: pair by pair, column first + i, over the rows a
        # register at a time: the rows above a column's own read nothing
        n_d, p_d = [], []
        for top in range(first, first + sub, group):
            rows = slice(top, top + group)
            G_g, kb_g, q_g = G[rows], kb[rows], q[rows]
            n_g = p_g = jnp.zeros((group, C), f32)
            for s in range(first, top + group):
                e = jnp.exp(jnp.minimum(G_g - G[s:s + 1], 0.0)) * k[s:s + 1]
                here = cols == s
                n_g = n_g + jnp.where(
                    here, jnp.sum(kb_g * e, axis=-1, keepdims=True), 0.0)
                p_g = p_g + jnp.where(
                    here, jnp.sum(q_g * e, axis=-1, keepdims=True), 0.0)
            n_d.append(n_g)
            p_d.append(p_g)
        n_rows.append(n_b + jnp.concatenate(n_d))
        p_rows.append(p_b + jnp.concatenate(p_d))
        yield
    N = jnp.where(row > col, jnp.concatenate(n_rows), 0.0)
    P = jnp.where(row >= col, jnp.concatenate(p_rows), 0.0)

    # A = (I + N)^-1. The diagonal blocks X = (I + Nd)^-1 by forward
    # substitution, all of them at once and side by side, ``sub`` rows deep:
    # Xr[m, l] = X[row m of l's block, l]. Row i is e_i less the rows above it
    # weighted by Nd[row i, column m], which step i needs along the whole
    # block of lanes: coef_i[m, l] = Nd[row i of l's block, column m of it], the
    # blocks' rows laid side by side, each entry kept in the row of its lane's
    # place and spread over its block by a product with a matrix of ones
    Nd = jnp.where(same, N, 0.0)
    place = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 1) & (sub - 1)
    depth = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 0)
    beside = functools.reduce(
        jnp.add, (Nd[b * sub:(b + 1) * sub] for b in range(blocks)))
    ones = same.astype(jnp.bfloat16)
    hi, mid, lo = (one(part, ones, _NN) for part in _split3(jnp.concatenate(
        [jnp.where(place == depth, beside[i:i + 1], 0.0)
         for i in range(sub)])))
    coef = hi + mid + lo                                         # [sub sub, C]
    yield
    Xr = (place == depth).astype(f32)
    for i in range(1, sub):
        new = jnp.sum(coef[i * sub:(i + 1) * sub] * Xr, axis=0, keepdims=True)
        Xr = jnp.where(depth == i, (place == i).astype(f32) - new, Xr)
    X = jnp.where(same, jnp.concatenate([Xr] * blocks), 0.0)
    yield
    # I + N = (I + Nd)(I + Z), Z = X (N - Nd) strictly below the blocks:
    # (I + Z)^-1 = (I - Z)(I + Z^2)(I + Z^4) .. , finite. Z^m is empty in its
    # first m rows of blocks, and so is whatever it multiplies from the right
    # of a lower triangle: those rows are not computed
    Z = exact(X, N - Nd, _NN)
    yield
    series, power, reach = eye - Z, Z, 2
    while reach < blocks:
        empty = jnp.zeros((reach * sub, C), f32)
        power = jnp.concatenate(
            [empty, exact(power[reach * sub:], power, _NN)])
        yield
        series = series + jnp.concatenate(
            [empty, exact(series[reach * sub:], power, _NN)])
        yield
        reach *= 2
    A = exact(series, X, _NN).astype(kind)
    yield

    eG = jnp.exp(G)
    s0 = s_ref[...]
    state = s0.astype(kind)
    w = mxu(A, (kb * eG).astype(kind), _NN)                      # [C, K]
    yield
    u = mxu(A, vb, _NN) - mxu(w.astype(kind), state, _NN)
    u = u.astype(kind)                                           # [C, V]
    yield
    o = mxu((q * eG).astype(kind), state, _NN) + mxu(P.astype(kind), u, _NN)
    if gate_ref is not None:   # the head's norm, the gate, o_proj's type
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        o = o * scale_ref[...].astype(f32) * jax.nn.sigmoid(
            gate_ref[...].astype(f32))
    o_ref[...] = o.astype(o_ref.dtype)
    yield
    last = G[C - 1:C]                                            # [1, K]
    # diag(e^last) S: the row as a column, through the diagonal's mask
    lane = jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    decay = jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (K, K), 0) == lane,
        jnp.exp(last), 0.0), axis=-1, keepdims=True)             # [K, 1]
    s_ref[...] = decay * s0 + mxu(
        (k * jnp.exp(last - G)).astype(kind), u, _TN)


def _tile(S, chunk):
    """The positions a grid step takes of a bucket of ``S``."""
    T = chunk if S % chunk == 0 else S
    sub = min(SUB, T)
    if T % sub or sub & (sub - 1):
        raise ValueError(f"kda_scan takes a power of two up to {chunk} "
                         f"positions or a multiple of {chunk}, got {S}")
    return T


def _heads_a_step(H):
    """The heads a grid step takes: four where they divide the layer's (two,
    or one, where they do not): a step's fixed cost once for all of them, and
    their chains laid over each other (``_in_turn``)."""
    return 4 if H % 4 == 0 else 2 if H % 2 == 0 else 1


def scan_chunks(S, lengths):
    """On the host: the chunks ``kda_prefill``'s grid has a head for rows of
    ``lengths`` in a bucket of ``S``, and those of them it passes over (a
    chunk wholly behind its row's end)."""
    T = _tile(S, CHUNK)
    chunks = len(lengths) * (S // T)
    return chunks, chunks - sum(-(-int(n) // T) for n in lengths)


def _real_chunk(r, t, T, len_ref):
    """Chunk ``t`` of row ``r`` or, where ``t`` lies wholly behind the row's
    end, the last chunk that holds a real position (the first of an empty
    row): the block a step that is passed over already holds, so nothing is
    fetched for it."""
    return jnp.minimum(t, jnp.maximum(len_ref[r] - 1, 0) // T)


def _head_lanes(T, lanes, first=0, real=False):
    """``lanes`` lanes (a step's heads') of ``T`` positions of an array [R, S,
    n H K], from block ``first`` of such blocks on; ``real``: an input of a
    call that knows its rows' lengths (``_real_chunk``)."""
    return pl.BlockSpec((None, T, lanes), lambda r, h, t, *lengths: (
        r, _real_chunk(r, t, T, *lengths) if real else t, first + h))


def _kda_scan(*operands, specs, prefetch, dims, heads, T, eps, o_dtype):
    """The one ``pallas_call``: ``operands`` under ``specs`` (the first
    ``prefetch`` of them scalars), ``dims`` = (R, S, H, K, V), ``heads`` of
    the ``H`` a grid step. A process that knows it traces for the TPU
    (``utils.is_tpu``: the serving process, and the one that exports a
    bucket's programs of several rows, ``llm/prefill_shapes.py``) does not
    trace the interpreted twin: a step of four heads is 3 s of tracing, and
    those programs have to be ready before a window opens."""
    R, S, H, K, V = dims

    def call(*operands, interpret):
        return pl.pallas_call(
            functools.partial(_scan_kernel, heads=heads, sub=min(SUB, T),
                              eps=eps),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=prefetch,
                grid=(R, H // heads, S // T),
                in_specs=specs,
                out_specs=[_head_lanes(T, heads * V),
                           pl.BlockSpec((None, heads, K, V),
                                        lambda r, h, t, *_: (r, h, 0, 0))],
                scratch_shapes=[pltpu.VMEM((heads, K, V), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct((R, S, H * V), o_dtype),
                       jax.ShapeDtypeStruct((R, H, K, V), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="kda_scan",
        )(*operands)

    from ray_tpu.utils import is_tpu

    if is_tpu():    # known while tracing: the interpreted call is not traced
        return call(*operands, interpret=False)
    return jax.lax.platform_dependent(
        *operands, tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True))


def kda_scan(q, k, v, g, beta, *, chunk: int = CHUNK):
    """``kda_reference`` from a zero state through the chunked kernel, on
    prepared operands: q, k [R, S, H, K], v [R, S, H, V], g [R, S, H, K] (<=
    0; 0 on padding), beta [R, S, H] (0 on padding) -> (o [R, S, H, V]
    float32, the state after the last position [R, H, K, V] float32). ``S``
    is a power of two up to ``chunk`` or a multiple of it. No chunk is passed
    over: the call has no lengths."""
    R, S, H, K = q.shape
    V = v.shape[-1]
    T, heads = _tile(S, chunk), _heads_a_step(H)
    b = beta.astype(jnp.float32)[..., None]
    scaled = lambda t: (t.astype(jnp.float32) * b).astype(t.dtype)  # noqa: E731
    flat = lambda t: t.reshape(R, S, -1)   # noqa: E731
    keys, values = _head_lanes(T, heads * K), _head_lanes(T, heads * V)
    o, state = _kda_scan(
        flat(q), flat(k), flat(scaled(k)), flat(scaled(v)),
        flat(g.astype(jnp.float32)),
        specs=[keys, keys, keys, values, keys], prefetch=0,
        dims=(R, S, H, K, V), heads=heads, T=T, eps=None, o_dtype=jnp.float32)
    return o.reshape(R, S, H, V), state


@functools.partial(jax.jit, static_argnames=("eps", "chunk"))
def kda_prefill(qkv, f, beta, gate, dt_bias, A_log, o_scale, lengths, *,
                eps: float, chunk: int = CHUNK):
    """A layer's recurrence over a prefill call's rows from a zero state with
    everything that is local to a position and a head done in the kernel's
    tile, on the arrays as the layer's products left them: ``q | k | v`` after
    the convolutions and the silu qkv [R, S, 3 H K] (one array, read through
    three views), the decay gate's f [R, S, H K] and the output gate [R, S, H
    K] in the stored type, beta [R, S, H] float32, ``dt_bias`` [H K], ``A_log``
    [H], the output norm's scale ``o_scale`` [K], the prompts' lengths [R].
    Returns (o [R, S, H K] in the stored type: under the head's RMSNorm
    (``eps``) and the gate's sigmoid, what ``o_proj`` reads; behind a prompt's
    end zeros in the chunks that lie wholly behind it and nobody's, but
    finite, in the chunk that holds the end; the state after ``lengths - 1``
    [R, H, K, K] float32). ``S`` as ``kda_scan`` takes it. Jitted: a program
    of several such layers traces and lowers the kernel once."""
    R, S, _ = qkv.shape
    H = A_log.shape[0]
    K = f.shape[-1] // H
    T, heads = _tile(S, chunk), _heads_a_step(H)
    per = H // heads                       # blocks of a step's heads in q's H K
    head = _head_lanes(T, heads * K, real=True)
    whole = lambda n: pl.BlockSpec(   # noqa: E731
        (1, n), lambda r, h, t, *_: (0, 0))
    return _kda_scan(
        lengths.astype(jnp.int32), qkv, qkv, qkv, f, beta,
        dt_bias.reshape(1, -1), A_log.reshape(1, -1), gate,
        o_scale.reshape(1, -1),
        specs=[head, _head_lanes(T, heads * K, per, True),
               _head_lanes(T, heads * K, 2 * per, True), head,
               pl.BlockSpec((None, T, H), lambda r, h, t, lengths: (
                   r, _real_chunk(r, t, T, lengths), 0)),
               pl.BlockSpec((1, heads * K), lambda r, h, t, *_: (0, h)),
               whole(H), head, whole(K)],
        prefetch=1, dims=(R, S, H, K, K), heads=heads, T=T, eps=eps,
        o_dtype=qkv.dtype)


# -- one decode step of one layer, every slot, in place --------------------------


def _step_kernel(s_ref, a_ref, k_ref, kb_ref, q_ref, vb_ref, o_ref, out_ref):
    for h in range(s_ref.shape[0]):
        lane = slice(h, h + 1)               # a head's column [K, 1]
        s = s_ref[h] * a_ref[:, lane]
        d = vb_ref[lane, :] - jnp.sum(s * kb_ref[:, lane], axis=0,
                                      keepdims=True)             # [1, V]
        s = s + k_ref[:, lane] * d
        out_ref[h] = s
        o_ref[lane, :] = jnp.sum(s * q_ref[:, lane], axis=0, keepdims=True)


def _kda_step(ssm, a, k, kb, q, vb, *, layer, name, interpret):
    _, B, H, K, V = ssm.shape
    column = pl.BlockSpec((None, K, H), lambda s: (s, 0, 0))
    row = pl.BlockSpec((None, H, V), lambda s: (s, 0, 0))
    state = pl.BlockSpec((None, None, H, K, V), lambda s: (layer, s, 0, 0, 0))
    return pl.pallas_call(
        _step_kernel,
        grid=(B,),
        in_specs=[state, column, column, column, column, row],
        out_specs=[row, state],
        out_shape=[jax.ShapeDtypeStruct((B, H, V), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=name,
    )(ssm, a, k, kb, q, vb)


def kda_step(ssm, layer: int, q, k, v, g, beta, keep=None, *,
             name: str = "kda_step"):
    """One position of ``kda_reference`` for every slot, on layer ``layer``
    of ``ssm`` [layers, B, H, K, V] float32, which the caller hands over
    donated: q, k [B, H, K], v [B, H, V], g [B, H, K] (<= 0), beta [B, H],
    keep [B] (a slot it does not mark keeps its state to the bit; None: all
    step) -> (o [B, H, V] float32, ``ssm`` with the layer's state stepped)."""
    f32 = lambda t: t.astype(jnp.float32)   # noqa: E731
    g, beta = f32(g), f32(beta)
    if keep is not None:
        g = jnp.where(keep[:, None, None], g, 0.0)
        beta = jnp.where(keep[:, None], beta, 0.0)
    column = lambda t: jnp.swapaxes(t, 1, 2)   # noqa: E731  [B, K, H]
    b = beta[..., None]
    return jax.lax.platform_dependent(
        ssm, column(jnp.exp(g)), column(f32(k)), column(f32(k) * b),
        column(f32(q)), f32(v) * b,
        tpu=functools.partial(_kda_step, layer=layer, name=name,
                              interpret=False),
        default=functools.partial(_kda_step, layer=layer, name=name,
                                  interpret=True))
