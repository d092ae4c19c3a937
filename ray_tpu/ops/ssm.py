"""The selective scan of a Mamba-1 layer: ``s_t = exp(dt_t A) * s_{t-1} + (dt_t
a_t) B_t^T``, ``y_t = s_t C_t``, the state ``s`` [inner, N] float32.

``selective_scan_reference`` is the recurrence as it reads, one position a
``lax.scan`` step: what training differentiates and what the kernel is held
to. On the chip a step of such a loop costs microseconds whatever it computes,
thousands of positions a prompt and nine layers of them, so the serving
prefill runs the Pallas kernel ``ssm_scan``: the grid is (row, block of
``inner``, chunk of positions), the chunks of a block follow each other and the
block's state stays in fast memory from the first to the last; inside a chunk
a loop walks the positions. The state lies [N, block] (``inner`` along the
lanes), ``dt_t`` and ``a_t`` are rows broadcast over the N sublanes, and
``B_t``, ``C_t`` come as COLUMNS [N, 1] broadcast along the lanes: the caller
hands them over as [B, S, N, 1], which pads each to a tile (67 MB a layer at
8,192 positions, read once) and spares the kernel any transpose. Nothing of
[S, inner, N] ever exists outside the state of the position at hand.

A position with ``dt = 0`` leaves the state as it was and adds nothing: that
is how padding behind a prompt is passed over, and how a slot that may not
move sits out a decode step.

One decode step is the recurrence's one position for every slot, and what it
costs is the state: 327,680 bytes a slot and layer at 5,120 channels, read and
written, against a few KB of operands. ``ssm_step`` is that pass as a kernel
of its own: the grid is (block of slots, block of ``inner``), the state is the
cache's whole leaf ``[layers, slots, N, inner]`` aliased in and out with the
layer in the index map, so a step moves the one layer's states and nothing
else of the leaf, and ``y`` comes out of the same pass. It writes the state at
EVERY step. (The decoder-hybrid-decoder's own decode loop,
``llm/model_runner.py:_hybrid_decode``, still computes its step in place with
XLA's fusions.) Off the TPU both kernels run in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# positions a grid step walks, and the width of its block of ``inner``. The
# recurrence is a chain a position long whatever the block: a wider block
# gives each link more independent lanes. Alone on the v5e at [1, 8192, 5120]
# (host clock over four calls in one program, PR 33) a call took 17.39 ms at
# 256 channels, 9.17 at 512, 5.11 at 1024, 3.45 at 2560 (the state then spills
# the vector registers and is still ahead)
CHUNK = 128
BLOCK = 2560
# slots a grid step of ``ssm_step`` walks (the sublanes of a float32 tile of
# ``dt``, ``a`` and ``y`` rows) and the lanes it takes at a time: a block of
# 8 slots x 16 x 2,560 float32 is 1.3 MB each way
STEP_SLOTS = 8
STEP_LANES = 512


def selective_scan_reference(dt, a, Bm, Cm, A, s0):
    """dt, a [B, S, I] float32, Bm, Cm [B, S, N], A [I, N] (negative), s0
    [B, I, N] -> (y [B, S, I] float32, the state after the last position)."""
    def step(s, t):
        dt_t, a_t, b_t, c_t = t
        s = jnp.exp(dt_t[..., None] * A) * s \
            + (dt_t * a_t)[..., None] * b_t[:, None, :]
        return s, jnp.einsum("bin,bn->bi", s, c_t)

    f32 = lambda t: jnp.moveaxis(t.astype(jnp.float32), 1, 0)   # noqa: E731
    s, y = jax.lax.scan(step, s0, (f32(dt), f32(a), f32(Bm), f32(Cm)))
    return jnp.moveaxis(y, 0, 1), s


def _kernel(dt_ref, a_ref, b_ref, c_ref, A_ref, s0_ref, y_ref, sT_ref, s_ref):
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        s_ref[...] = s0_ref[...]

    A = A_ref[...]                                          # (N, block)

    def body(t, s):
        dt = dt_ref[pl.ds(t, 1), :]                         # (1, block)
        u = dt * a_ref[pl.ds(t, 1), :]
        s = jnp.exp(A * dt) * s + b_ref[t] * u              # (N, 1) x (1, block)
        y_ref[pl.ds(t, 1), :] = jnp.sum(s * c_ref[t], axis=0, keepdims=True)
        return s

    s = jax.lax.fori_loop(0, dt_ref.shape[0], body, s_ref[...])
    s_ref[...] = s

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _():
        sT_ref[...] = s


def _ssm_scan(dt, a, b, c, A, s0, *, interpret: bool):
    B, S, inner = dt.shape
    N = A.shape[0]
    T = CHUNK if S % CHUNK == 0 else S
    W = BLOCK if inner % BLOCK == 0 else inner
    row = pl.BlockSpec((None, T, W), lambda r, i, t: (r, t, i))
    col = pl.BlockSpec((None, T, N, 1), lambda r, i, t: (r, t, 0, 0))
    state = pl.BlockSpec((None, N, W), lambda r, i, t: (r, 0, i))
    return pl.pallas_call(
        _kernel,
        grid=(B, inner // W, S // T),
        in_specs=[row, row, col, col,
                  pl.BlockSpec((N, W), lambda r, i, t: (0, i)), state],
        out_specs=[row, state],
        out_shape=[jax.ShapeDtypeStruct((B, S, inner), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, inner), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(dt, a, b, c, A, s0)


def selective_scan(dt, a, Bm, Cm, A, s0=None):
    """The same recurrence as ``selective_scan_reference`` through the kernel,
    the state in the KERNEL's layout: dt, a [B, S, I], Bm, Cm [B, S, N], A [I,
    N], s0 [B, N, I] (zeros where none is given) -> (y [B, S, I] float32, the
    state after the last position [B, N, I] float32). ``S`` is at most
    ``CHUNK`` positions or a multiple of it."""
    f32 = lambda t: t.astype(jnp.float32)   # noqa: E731
    if s0 is None:
        s0 = jnp.zeros((dt.shape[0], A.shape[1], A.shape[0]), jnp.float32)
    return jax.lax.platform_dependent(
        f32(dt), f32(a), f32(Bm)[..., None], f32(Cm)[..., None], f32(A).T,
        f32(s0),
        tpu=functools.partial(_ssm_scan, interpret=False),
        default=functools.partial(_ssm_scan, interpret=True))


def _step_kernel(s_ref, dt_ref, a_ref, b_ref, c_ref, A_ref, y_ref, out_ref):
    """A block of slots' block of lanes, each state read once and written
    once: ``dt``, ``a`` [slots, W] rows, ``b``, ``c`` [slots, N, 1] columns."""
    slots, _, W = s_ref.shape
    lanes = STEP_LANES if W % STEP_LANES == 0 else W

    def body(k, _):
        at = pl.ds(pl.multiple_of(k * lanes, lanes), lanes)
        A = A_ref[:, at]                                    # (N, lanes)
        for j in range(slots):
            dt = dt_ref[pl.ds(j, 1), at]                    # (1, lanes)
            u = dt * a_ref[pl.ds(j, 1), at]
            s = jnp.exp(A * dt) * s_ref[j, :, at] + b_ref[j] * u
            out_ref[j, :, at] = s
            y_ref[pl.ds(j, 1), at] = jnp.sum(s * c_ref[j], axis=0,
                                             keepdims=True)
        return _

    jax.lax.fori_loop(0, W // lanes, body, 0)


def _ssm_step(ssm, dt, a, b, c, A, *, layer: int, name: str,
              interpret: bool):
    _, B, N, inner = ssm.shape
    G = STEP_SLOTS if B % STEP_SLOTS == 0 else B
    W = BLOCK if inner % BLOCK == 0 else inner
    row = pl.BlockSpec((G, W), lambda s, i: (s, i))
    col = pl.BlockSpec((G, N, 1), lambda s, i: (s, 0, 0))
    state = pl.BlockSpec((None, G, N, W), lambda s, i: (layer, s, 0, i))
    return pl.pallas_call(
        _step_kernel,
        grid=(B // G, inner // W),
        in_specs=[state, row, row, col, col,
                  pl.BlockSpec((N, W), lambda s, i: (0, i))],
        out_specs=[row, state],
        out_shape=[jax.ShapeDtypeStruct((B, inner), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=name,
    )(ssm, dt, a, b, c, A)


def ssm_step(ssm, layer: int, dt, a, Bm, Cm, A, keep=None, *,
             name: str = "ssm_step"):
    """One position of ``selective_scan_reference`` for every slot, on layer
    ``layer`` of ``ssm`` [layers, B, N, I] float32 (the KERNEL's layout),
    which the caller hands over donated: dt (after softplus), a [B, I], Bm, Cm
    [B, N], A [I, N], keep [B] (a slot it does not mark takes ``dt = 0`` and
    keeps its state to the bit; None: all step) -> (y [B, I] float32, ``ssm``
    with the layer's states as of this position). ``name``: the kernel's in
    a device trace ("ssm_riding" where the step rides a prefill call)."""
    f32 = lambda t: t.astype(jnp.float32)   # noqa: E731
    dt = f32(dt)
    if keep is not None:
        dt = jnp.where(keep[:, None], dt, 0.0)
    return jax.lax.platform_dependent(
        ssm, dt, f32(a), f32(Bm)[..., None], f32(Cm)[..., None], f32(A).T,
        tpu=functools.partial(_ssm_step, layer=layer, name=name,
                              interpret=False),
        default=functools.partial(_ssm_step, layer=layer, name=name,
                                  interpret=True))
