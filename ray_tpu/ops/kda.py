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
head, chunk of ``CHUNK`` positions), a head's chunks follow each other and its
state stays in fast memory. Over a chunk with ``G_t = sum_{u <= t} g_u`` and
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
(all of a chunk's at once), the blocks below them by the finite series of the
block-nilpotent rest. ``G`` (a product with a triangle of ones), every ``exp``
and the carried state are float32; the products with ``A``, with the state and
between sub-chunks take their operands in the type q, k and v arrive in
(bfloat16 in a bfloat16 model, float32 accumulation; float32 at the highest
precision in a float32 one, which is how the tests hold the kernel to the
reference at 1e-5). A position with ``g = 0`` and ``beta = 0`` leaves the
state as it was: that is how padding behind a prompt is passed over, so the
state that comes back is the one after ``lengths - 1``.

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
``kda_reference``. The step grows by about a tenth for it
(PERF.md section 6, PR 50).

``CHUNK`` is 128 where the family's own kernels take 64: the solve's cost a
position grows with the chunk, the state's products shrink with it, and on
the v5e a chunk's matrices are then whole 128-lane registers and whole MXU
tiles: 4.51 ms against 5.55 for a layer of ``[1, 4096]`` at 32 heads of 128
(my chip run, PR 49).

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


def _raw_operands(len_ref, q_ref, k_ref, v_ref, f_ref, beta_ref, bias_ref,
                  alog_ref):
    """The prologue of a grid step that is handed a layer's arrays as the
    convolution and the gates' products left them: a head's [C, K] lanes of
    ``q | k | v`` (after the silu) and of the decay gate's ``f``, every head's
    beta [C, H], the head's ``dt_bias`` [1, K], ``A_log`` [1, H] and the row's
    length. Returns what the recurrence takes, float32: q and k of unit
    length (q times ``K^-0.5``), ``beta k``, ``beta v`` (in the stored type,
    as the products take it) and the log-decay; no decay and no update behind
    the prompt's end."""
    f32 = jnp.float32
    C, K = q_ref.shape
    r, h, chunk = (pl.program_id(axis) for axis in range(3))

    def unit(ref):
        t = ref[...].astype(f32)
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    def own(t):          # the head's column of t [.., H]: mask and reduce
        lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
        return jnp.sum(jnp.where(lane == h, t, 0.0), axis=-1, keepdims=True)

    def in_prompt(shape):
        at = chunk * C + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        return at < len_ref[r]

    q, k = unit(q_ref) * K ** -0.5, unit(k_ref)
    g = own(-jnp.exp(alog_ref[...].astype(f32))) * jax.nn.softplus(
        f_ref[...].astype(f32) + bias_ref[...].astype(f32))
    g = jnp.where(in_prompt((C, K)), g, 0.0)
    beta = jnp.where(in_prompt((C, 1)), own(beta_ref[...].astype(f32)), 0.0)
    vb = (v_ref[...].astype(f32) * beta).astype(v_ref.dtype)
    return q, k, k * beta, vb, g


def _scan_kernel(*refs, sub, eps):
    """``refs``: the prepared operands (q, k, beta k, beta v, g) or a layer's
    own arrays with the output gate and the output norm's scale behind them
    (``_raw_operands``; then ``o`` leaves normalised, gated and in the stored
    type), the two results, the carried state."""
    *operands, o_ref, sT_ref, s_ref = refs
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    f32 = jnp.float32
    raw = len(operands) != 5
    if raw:
        *operands, gate_ref, scale_ref = operands
        q, k, kb, vb, g = _raw_operands(*operands)
    else:
        q, k, kb = (ref[...].astype(f32) for ref in operands[:3])
        vb, g = operands[3][...], operands[4][...]
    C, K = q.shape
    kind = vb.dtype                       # what the large products multiply in
    exact = functools.partial(jax.lax.dot_general, precision=_HIGHEST,
                              preferred_element_type=f32)
    mxu = exact if kind == f32 else functools.partial(
        jax.lax.dot_general, preferred_element_type=f32)
    shift = sub.bit_length() - 1          # sub is a power of two
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = (row == col).astype(f32)
    same = (row >> shift) == (col >> shift)      # within a diagonal block

    # the log-decay summed from the chunk's first position on
    G = exact((row >= col).astype(f32), g, _NN)                  # [C, K]

    # what position t reads of position s, rows of ``sub`` positions at a time:
    # n for the update (beta k), p for the output (q)
    cols = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 1)
    n_rows, p_rows = [], []
    for block in range(C // sub):
        first = block * sub
        at = slice(first, first + sub)
        G_b, kb_b, q_b = G[at], kb[at], q[at]
        n_b = p_b = jnp.zeros((sub, C), f32)
        if block:       # left of the diagonal: both exponents from ``anchor``
            anchor = G[first - 1:first]                          # [1, K]
            left = jnp.exp(G_b - anchor)
            both = mxu(
                jnp.concatenate([kb_b * left, q_b * left]).astype(kind),
                (k * jnp.exp(jnp.minimum(anchor - G, 0.0))).astype(kind), _NT)
            n_b = jnp.where(cols < first, both[:sub], 0.0)
            p_b = jnp.where(cols < first, both[sub:], 0.0)
        for i in range(sub):  # on the diagonal: pair by pair, column first + i
            s = first + i
            e = jnp.exp(jnp.minimum(G_b - G[s:s + 1], 0.0)) * k[s:s + 1]
            here = cols == s
            n_b = n_b + jnp.where(
                here, jnp.sum(kb_b * e, axis=-1, keepdims=True), 0.0)
            p_b = p_b + jnp.where(
                here, jnp.sum(q_b * e, axis=-1, keepdims=True), 0.0)
        n_rows.append(n_b)
        p_rows.append(p_b)
    N = jnp.where(row > col, jnp.concatenate(n_rows), 0.0)
    P = jnp.where(row >= col, jnp.concatenate(p_rows), 0.0)

    # A = (I + N)^-1. The diagonal blocks by forward substitution, all of them
    # at once: X stays block diagonal, so one sum over its rows holds every
    # block's new row, each in its own lanes
    Nd = jnp.where(same, N, 0.0)
    NdT = exact(eye, Nd, _NT)
    X = eye
    for i in range(1, sub):
        # c[j] = Nd[row i of j's block, j]
        c = jnp.sum(jnp.where((col & (sub - 1)) == i, NdT, 0.0), axis=-1,
                    keepdims=True)
        new = jnp.sum(c * X, axis=0, keepdims=True)              # [1, C]
        X = jnp.where((row & (sub - 1)) == i,
                      jnp.where(same, eye - new, 0.0), X)
    # I + N = (I + Nd)(I + Z), Z = X (N - Nd) strictly below the blocks:
    # (I + Z)^-1 = (I - Z)(I + Z^2)(I + Z^4) .. , finite
    Z = exact(X, N - Nd, _NN)
    series, power, reach = eye - Z, Z, 2
    while reach < C // sub:
        power = exact(power, power, _NN)
        series = exact(series, eye + power, _NN)
        reach *= 2
    A = exact(series, X, _NN).astype(kind)

    eG = jnp.exp(G)
    s0 = s_ref[...]
    state = s0.astype(kind)
    w = mxu(A, (kb * eG).astype(kind), _NN)                      # [C, K]
    u = mxu(A, vb, _NN) - mxu(w.astype(kind), state, _NN)
    u = u.astype(kind)                                           # [C, V]
    o = mxu((q * eG).astype(kind), state, _NN) + mxu(P.astype(kind), u, _NN)
    if raw:     # the head's norm, the output gate, the type o_proj takes
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        o = o * scale_ref[...].astype(f32) * jax.nn.sigmoid(
            gate_ref[...].astype(f32))
    o_ref[...] = o.astype(o_ref.dtype)
    last = G[C - 1:C]                                            # [1, K]
    # diag(e^last) S: the row as a column, through the diagonal's mask
    lane = jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    decay = jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (K, K), 0) == lane,
        jnp.exp(last), 0.0), axis=-1, keepdims=True)             # [K, 1]
    s_ref[...] = decay * s0 + mxu(
        (k * jnp.exp(last - G)).astype(kind), u, _TN)

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _():
        sT_ref[...] = s_ref[...]


def _tile(S, chunk):
    """The positions a grid step takes of a bucket of ``S``."""
    T = chunk if S % chunk == 0 else S
    sub = min(SUB, T)
    if T % sub or sub & (sub - 1):
        raise ValueError(f"kda_scan takes a power of two up to {chunk} "
                         f"positions or a multiple of {chunk}, got {S}")
    return T


def _head_lanes(T, K, first=0):
    """A head's ``K`` lanes of ``T`` positions of an array [R, S, n H K], the
    heads' lanes from head ``first`` on."""
    return pl.BlockSpec((None, T, K), lambda r, h, t, *_: (r, t, first + h))


def _kda_scan(*operands, specs, prefetch, dims, T, eps, o_dtype):
    """The one ``pallas_call``: ``operands`` under ``specs`` (the first
    ``prefetch`` of them scalars), ``dims`` = (R, S, H, K, V)."""
    R, S, H, K, V = dims

    def call(*operands, interpret):
        return pl.pallas_call(
            functools.partial(_scan_kernel, sub=min(SUB, T), eps=eps),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=prefetch,
                grid=(R, H, S // T),
                in_specs=specs,
                out_specs=[_head_lanes(T, V),
                           pl.BlockSpec((None, None, K, V),
                                        lambda r, h, t, *_: (r, h, 0, 0))],
                scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct((R, S, H * V), o_dtype),
                       jax.ShapeDtypeStruct((R, H, K, V), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="kda_scan",
        )(*operands)

    return jax.lax.platform_dependent(
        *operands, tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True))


def kda_scan(q, k, v, g, beta, *, chunk: int = CHUNK):
    """``kda_reference`` from a zero state through the chunked kernel, on
    prepared operands: q, k [R, S, H, K], v [R, S, H, V], g [R, S, H, K] (<=
    0; 0 on padding), beta [R, S, H] (0 on padding) -> (o [R, S, H, V]
    float32, the state after the last position [R, H, K, V] float32). ``S``
    is a power of two up to ``chunk`` or a multiple of it."""
    R, S, H, K = q.shape
    V = v.shape[-1]
    T = _tile(S, chunk)
    b = beta.astype(jnp.float32)[..., None]
    scaled = lambda t: (t.astype(jnp.float32) * b).astype(t.dtype)  # noqa: E731
    flat = lambda t: t.reshape(R, S, -1)   # noqa: E731
    o, state = _kda_scan(
        flat(q), flat(k), flat(scaled(k)), flat(scaled(v)),
        flat(g.astype(jnp.float32)),
        specs=[_head_lanes(T, K)] * 3 + [_head_lanes(T, V), _head_lanes(T, K)],
        prefetch=0, dims=(R, S, H, K, V), T=T, eps=None, o_dtype=jnp.float32)
    return o.reshape(R, S, H, V), state


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
    (``eps``) and the gate's sigmoid, what ``o_proj`` reads, and whatever lies
    behind a prompt's end is nobody's; the state after ``lengths - 1`` [R, H,
    K, K] float32). ``S`` as ``kda_scan`` takes it."""
    R, S, _ = qkv.shape
    H = A_log.shape[0]
    K = f.shape[-1] // H
    T = _tile(S, chunk)
    head = _head_lanes(T, K)
    whole = lambda n: pl.BlockSpec(   # noqa: E731
        (1, n), lambda r, h, t, *_: (0, 0))
    return _kda_scan(
        lengths.astype(jnp.int32), qkv, qkv, qkv, f, beta,
        dt_bias.reshape(1, -1), A_log.reshape(1, -1), gate,
        o_scale.reshape(1, -1),
        specs=[head, _head_lanes(T, K, H), _head_lanes(T, K, 2 * H), head,
               pl.BlockSpec((None, T, H), lambda r, h, t, *_: (r, t, 0)),
               pl.BlockSpec((1, K), lambda r, h, t, *_: (0, h)), whole(H),
               head, whole(K)],
        prefetch=1, dims=(R, S, H, K, K), T=T, eps=eps, o_dtype=qkv.dtype)


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
