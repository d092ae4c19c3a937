"""A Mamba-2 layer's recurrence (SSD, "state-space duality"): per head ``h`` of
``P`` channels, ``S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x)
B_t``, ``y_t[h] = S_t[h] C_t``; ``B_t``, ``C_t`` [N] are shared by all heads
(one group), the decay is ONE number a head and position, and the state ``S``
is a matrix [P, N] a head, float32: at 128 heads of 64 and N = 128 it is 4.19
MB a slot and layer, the largest thing a slot holds.

THE LAYOUT. Everywhere here the heads' channels are laid side by side, ``I = H
x P``, and the state is ``[.., N, I]``: ``I`` along the lanes, ``N`` along the
sublanes (as ``ops/ssm.py`` keeps a Mamba-1 state). Whatever goes by channel
(the decay, ``dt x``, ``y``) is then a row broadcast over the sublanes,
``B_t`` and ``C_t`` are columns broadcast along the lanes, ``y = sum_n S[n, :]
C[n]`` reduces over sublanes (adds of whole registers), and in the chunked
form both products with the state are ONE matrix product for all heads of a
block: ``C [T, N] @ S [N, I]`` and ``B^T [N, T] @ (w dt x) [T, I]``.

``ssd_reference`` is the recurrence as it reads, one position a ``lax.scan``
step: what training differentiates and what both kernels are held to.

``ssd_scan`` (Pallas, name ``ssd_scan``) is the chunked evaluation of the SAME
recurrence over a prefill call's rows, from a zero state: the grid is (row,
block of ``BLOCK`` lanes = 16 heads, chunk of ``CHUNK`` positions), a block's
chunks follow each other and its state stays in fast memory. Inside a chunk,
with ``cs_t = sum_{u <= t} dt_u A`` (taken outside, float32), ``y = (L o (C
B^T)) (dt x) + exp(cs) (C S_prev)`` with ``L[t, s] = exp(cs_t - cs_s)`` for
``s <= t``, and ``S_new = exp(cs_T) S_prev + B^T (exp(cs_T - cs) dt x)``. The
products run on the MXU with bfloat16 operands (``x``, ``B``, ``C`` arrive in
bfloat16 as the layer's activations are) and float32 accumulation; ``cs``,
every ``exp`` and the carried state are float32. Heads are 64 lanes wide and
a register 128: the kernel walks PAIRS of heads, multiplies each head's ``L o
G`` with the pair's 128 lanes and keeps each head's own half, which costs the
MXU what two 64-wide products would (it is 128 columns wide either way) and
needs no slice inside a register. A position with ``dt = 0`` leaves the state
as it was and adds nothing: that is how padding behind a prompt is passed
over, so the state that comes back is the one after ``lengths - 1``.

``ssd_step`` (Pallas, name ``ssd_step``; ``ssd_riding`` where a prefill call
carries the step) is one decode step of one layer for every slot: the grid is
(slot, block of ``STEP_BLOCK`` lanes), a block's state is read once, updated
and written once IN PLACE (``input_output_aliases`` on the whole
``[layers, B, N, I]`` leaf: the other layers' bytes are never touched and
nothing is copied), and ``y`` comes out of the same pass. A slot with ``dt =
0`` keeps its state to the bit (``S * 1 + 0``): that is how ``keep`` leaves the
slots a prompt has just written untouched. It is bound by bytes: 2 x 4.19 MB
a slot.

Off the TPU both kernels run in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# positions a grid step of ssd_scan takes: the published mamba_chunk_size
CHUNK = 256
# lanes (channels) a grid step of ssd_scan takes: 16 heads of 64
BLOCK = 1024
# lanes a grid step of ssd_step takes: [N, STEP_BLOCK] float32 is 2 MB at N =
# 128, in and out and double-buffered 8 MB of the 16 a kernel may hold
STEP_BLOCK = 4096


def ssd_reference(dt, x, Bm, Cm, A, s0=None):
    """dt [R, S, H] float32 (after softplus; 0 on padding), x [R, S, I] with
    ``I = H x P``, Bm, Cm [R, S, N], A [H] (negative), s0 [R, N, I] (zeros
    where none is given) -> (y [R, S, I] float32, the state after the last
    position [R, N, I] float32)."""
    R, S, H = dt.shape
    I, N = x.shape[-1], Bm.shape[-1]
    P = I // H
    if s0 is None:
        s0 = jnp.zeros((R, N, I), jnp.float32)

    def step(s, t):
        dt_t, x_t, b_t, c_t = t            # [R, H], [R, I], [R, N], [R, N]
        decay = jnp.repeat(jnp.exp(dt_t * A), P, axis=-1)          # [R, I]
        dtx = jnp.repeat(dt_t, P, axis=-1) * x_t
        s = decay[:, None, :] * s + b_t[:, :, None] * dtx[:, None, :]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    f32 = lambda t: jnp.moveaxis(t.astype(jnp.float32), 1, 0)   # noqa: E731
    s, y = jax.lax.scan(step, s0.astype(jnp.float32),
                        (f32(dt), f32(x), f32(Bm), f32(Cm)))
    return jnp.moveaxis(y, 0, 1), s


# -- the chunked form over a prefill call's rows ---------------------------------


def _scan_kernel(x_ref, dt_ref, cs_ref, csT_ref, b_ref, c_ref, y_ref, sT_ref,
                 s_ref, *, head):
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    T, W = x_ref.shape
    pair = 2 * head
    dt, cs, csT = dt_ref[...], cs_ref[...], csT_ref[...]   # [T, hb] x 2, [hb, T]
    c = c_ref[...]                                          # [T, N] bfloat16
    # what position t reads of position s before the heads' decays: shared
    G = jnp.dot(c, b_ref[...], preferred_element_type=jnp.float32)   # [T, T]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (T, T), 1))
    first = jax.lax.broadcasted_iota(jnp.int32, (1, pair), 1) < head
    last = cs[T - 1:T, :]                                   # [1, hb]

    for j in range(W // pair):
        lanes = slice(j * pair, (j + 1) * pair)
        h0, h1 = 2 * j, 2 * j + 1
        both = lambda m: jnp.where(first, m[:, h0:h0 + 1],   # noqa: E731
                                   m[:, h1:h1 + 1])          # -> [.., pair]
        dtx = x_ref[:, lanes].astype(jnp.float32) * both(dt)
        dtx16 = dtx.astype(jnp.bfloat16)
        halves = []
        for h in (h0, h1):
            seg = cs[:, h:h + 1] - csT[h:h + 1, :]           # cs_t - cs_s
            L = jnp.exp(jnp.where(causal, seg, -1e30))
            halves.append(jnp.dot((L * G).astype(jnp.bfloat16), dtx16,
                                  preferred_element_type=jnp.float32))
        s = s_ref[:, lanes]                                  # [N, pair]
        carried = jnp.dot(c, s.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        y_ref[:, lanes] = (jnp.where(first, halves[0], halves[1])
                           + jnp.exp(both(cs)) * carried)
        w = jnp.exp(both(last) - both(cs))                   # [T, pair]
        s_ref[:, lanes] = jnp.exp(both(last)) * s + jnp.dot(
            b_ref[...], (dtx * w).astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _():
        sT_ref[...] = s_ref[...]


def _ssd_scan(x, dt, cs, Bm, Cm, *, T, interpret):
    R, S, I = x.shape
    H, N = dt.shape[-1], Bm.shape[-1]
    head = I // H
    W = BLOCK if I % BLOCK == 0 and BLOCK % (2 * head) == 0 else I
    nb, hb = I // W, W // head
    # a block's heads side by side in the LAST axis, whole: [R, nb, S, hb]
    by_block = lambda t: jnp.moveaxis(   # noqa: E731
        t.reshape(R, S, nb, hb), 2, 1)
    dt, cs = by_block(dt), by_block(cs)
    rows = pl.BlockSpec((None, None, T, hb), lambda r, i, t: (r, i, t, 0))
    return pl.pallas_call(
        functools.partial(_scan_kernel, head=head),
        grid=(R, nb, S // T),
        in_specs=[
            pl.BlockSpec((None, T, W), lambda r, i, t: (r, t, i)),
            rows, rows,
            pl.BlockSpec((None, None, hb, T), lambda r, i, t: (r, i, 0, t)),
            pl.BlockSpec((None, N, T), lambda r, i, t: (r, 0, t)),
            pl.BlockSpec((None, T, N), lambda r, i, t: (r, t, 0))],
        out_specs=[pl.BlockSpec((None, T, W), lambda r, i, t: (r, t, i)),
                   pl.BlockSpec((None, N, W), lambda r, i, t: (r, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((R, S, I), jnp.float32),
                   jax.ShapeDtypeStruct((R, N, I), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(x, dt, cs, jnp.swapaxes(cs, 2, 3), jnp.swapaxes(Bm, 1, 2), Cm)


def ssd_scan(dt, x, Bm, Cm, A, *, chunk: int = CHUNK):
    """``ssd_reference`` from a zero state through the chunked kernel: dt [R,
    S, H] float32 (0 on padding), x [R, S, I], Bm, Cm [R, S, N], A [H] -> (y
    [R, S, I] float32, the state after the last position [R, N, I] float32).
    ``S`` is at most ``chunk`` positions or a multiple of it; ``H`` is even."""
    R, S, H = dt.shape
    T = chunk if S % chunk == 0 else S
    dt = dt.astype(jnp.float32)
    # the decay's exponent summed from each chunk's first position on
    cs = jnp.cumsum((dt * A.astype(jnp.float32)).reshape(R, S // T, T, H),
                    axis=2).reshape(R, S, H)
    bf16 = lambda t: t.astype(jnp.bfloat16)   # noqa: E731
    return jax.lax.platform_dependent(
        bf16(x), dt, cs, bf16(Bm), bf16(Cm),
        tpu=functools.partial(_ssd_scan, T=T, interpret=False),
        default=functools.partial(_ssd_scan, T=T, interpret=True))


# -- one decode step of one layer, every slot, in place --------------------------


def _step_kernel(s_ref, decay_ref, dtx_ref, b_ref, c_ref, y_ref, out_ref):
    W, lanes = s_ref.shape[1], b_ref.shape[1]
    b, c = b_ref[...], c_ref[...]                # [N, lanes], every lane alike

    def body(j, _):
        at = pl.ds(pl.multiple_of(j * lanes, lanes), lanes)
        s = decay_ref[:, at] * s_ref[:, at] + b * dtx_ref[:, at]
        out_ref[:, at] = s
        y_ref[:, at] = jnp.sum(s * c, axis=0, keepdims=True)
        return _

    jax.lax.fori_loop(0, W // lanes, body, 0)


def _ssd_step(ssm, decay, dtx, b, c, *, layer, name, interpret):
    _, B, N, I = ssm.shape
    W = STEP_BLOCK if I % STEP_BLOCK == 0 else I
    lanes = b.shape[-1]
    row = pl.BlockSpec((None, 1, W), lambda s, i: (s, 0, i))
    col = pl.BlockSpec((None, N, lanes), lambda s, i: (s, 0, 0))
    state = pl.BlockSpec((None, None, N, W), lambda s, i: (layer, s, 0, i))
    y, ssm = pl.pallas_call(
        _step_kernel,
        grid=(B, I // W),
        in_specs=[state, row, row, col, col],
        out_specs=[row, state],
        out_shape=[jax.ShapeDtypeStruct((B, 1, I), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=name,
    )(ssm, decay, dtx, b, c)
    return y[:, 0], ssm


def ssd_step(ssm, layer: int, dt, x, Bm, Cm, A, keep=None, *,
             name: str = "ssd_step"):
    """One position of ``ssd_reference`` for every slot, on layer ``layer``
    of ``ssm`` [layers, B, N, I] float32, which the caller hands over donated:
    dt [B, H] float32 (after softplus), x [B, I], Bm, Cm [B, N], A [H], keep
    [B] (a slot it does not mark keeps its state to the bit; None: all step)
    -> (y [B, I] float32, ``ssm`` with the layer's state stepped)."""
    B, H = dt.shape
    I = x.shape[-1]
    P = I // H
    dt = dt.astype(jnp.float32)
    if keep is not None:
        dt = jnp.where(keep[:, None], dt, 0.0)
    wide = lambda t: jnp.repeat(t, P, axis=-1)[:, None]   # noqa: E731
    lanes = min(128, I)
    col = lambda t: jnp.broadcast_to(   # noqa: E731
        t.astype(jnp.float32)[..., None], (*t.shape, lanes))
    return jax.lax.platform_dependent(
        ssm, wide(jnp.exp(dt * A.astype(jnp.float32))),
        wide(dt) * x.astype(jnp.float32)[:, None], col(Bm), col(Cm),
        tpu=functools.partial(_ssd_step, layer=layer, name=name,
                              interpret=False),
        default=functools.partial(_ssd_step, layer=layer, name=name,
                                  interpret=True))
