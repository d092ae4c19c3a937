"""Attention ops: pallas TPU flash-attention forward and backward, and the
pure-XLA reference path.

The MXU-friendly hot op of the flagship model. Three pallas kernels, named so
that a device trace shows them under one key each: ``flash_fwd`` (the
standard online-softmax flash pattern: one (batch*head, q-block) program,
fori_loop over k-blocks held in VMEM; saves the row logsumexp),
``flash_bwd_dq`` and ``flash_bwd_dkv`` (FlashAttention-2 style, softmax
rebuilt from the saved logsumexp). ``_use_pallas_bwd`` picks the backward:
the pallas pair at head_dim <= 64, a rematerialised backward through
``reference_attention`` at 128 and above.

CI runs the kernels in pallas interpret mode on CPU (SURVEY.md §4 implication:
every accelerator feature needs a hardware-free tier).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional

import jax
import jax.numpy as jnp


def reference_attention(q, k, v, causal: bool = True,
                        segment_ids: Optional[jax.Array] = None):
    """Pure-XLA attention: (B, S, H, D) -> (B, S, H, D), fp32 softmax."""
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    S = q.shape[1]
    if causal:
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    if segment_ids is not None:
        seg_mask = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        scores = jnp.where(seg_mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# pallas flash forward
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q,
                      block_k, causal, sm_scale, seq_len):
    import jax.experimental.pallas as pl

    q = q_ref[0].astype(jnp.float32)  # (block_q, d)
    q_blk = pl.program_id(1)
    d = q.shape[-1]

    nk = seq_len // block_k
    if causal:
        # only k-blocks up to (and including) the diagonal block
        upper = jnp.minimum(((q_blk + 1) * block_q + block_k - 1) // block_k, nk)
    else:
        upper = nk

    def body(i, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = q_blk * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc = jnp.zeros((block_q, d), jnp.float32)
    m = jnp.full((block_q, 1), -1e30, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc, m, l))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # logsumexp per row: the backward's softmax reconstruction key
    # (kept (S, 1)-shaped: TPU blocks need last-two dims 8/128-divisible
    # or full-size, which a trailing singleton satisfies)
    lse_ref[0] = m + jnp.log(l_safe)


def _to_bh(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _from_bh(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _flash_fwd_impl(q, k, v, causal: bool, interpret: bool,
                    block_q: int = 128, block_k: int = 128):
    """Returns (o, lse) with o in (B, S, H, D) and lse in (B*H, S)."""
    import jax.experimental.pallas as pl

    B, S, H, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, "seq must divide block sizes"
    sm_scale = 1.0 / (D ** 0.5)
    qt, kt, vt = _to_bh(q), _to_bh(k), _to_bh(v)
    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k, causal=causal,
        sm_scale=sm_scale, seq_len=S)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, S, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, S, D), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    return _from_bh(out, B, H), lse


def flash_attention_fwd(q, k, v, causal: bool = True, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """(B, S, H, D) flash forward via pallas (TPU) / interpret mode (CI)."""
    return _flash_fwd_impl(q, k, v, causal, interpret, block_q, block_k)[0]


# ---------------------------------------------------------------------------
# pallas flash backward (FlashAttention-2 style: dQ kernel over k-blocks,
# dK/dV kernel over q-blocks, softmax reconstructed from the saved LSE)
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_q, block_k, causal, sm_scale,
                         seq_len):
    import jax.experimental.pallas as pl

    q = q_ref[0].astype(jnp.float32)          # (bq, d)
    do = do_ref[0].astype(jnp.float32)        # (bq, d)
    lse = lse_ref[0]                          # (bq, 1)
    delta = delta_ref[0]                      # (bq, 1)
    q_blk = pl.program_id(1)
    nk = seq_len // block_k
    if causal:
        upper = jnp.minimum(((q_blk + 1) * block_q + block_k - 1) // block_k,
                            nk)
    else:
        upper = nk

    def body(i, dq_acc):
        k = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = q_blk * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        p = jnp.exp(s - lse)                              # (bq, bk)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        return dq_acc + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, upper, body,
                           jnp.zeros_like(q, dtype=jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q, block_k, causal,
                          sm_scale, seq_len):
    import jax.experimental.pallas as pl

    k = k_ref[0].astype(jnp.float32)          # (bk, d)
    v = v_ref[0].astype(jnp.float32)          # (bk, d)
    k_blk = pl.program_id(1)
    nq = seq_len // block_q
    lower = (k_blk * block_k) // block_q if causal else 0

    def body(i, carry):
        dk_acc, dv_acc = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_blk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        p = jnp.exp(s - lse)                              # (bq, bk)
        dv_acc = dv_acc + jnp.dot(p.T, do,
                                  preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_acc = dk_acc + jnp.dot(ds.T, q,
                                  preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    dk, dv = jax.lax.fori_loop(
        lower, nq, body,
        (jnp.zeros_like(k, dtype=jnp.float32),
         jnp.zeros_like(v, dtype=jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, g, causal: bool,
                        interpret: bool = False, block_q: int = 128,
                        block_k: int = 128):
    import jax.experimental.pallas as pl

    B, S, H, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    sm_scale = 1.0 / (D ** 0.5)
    qt, kt, vt = _to_bh(q), _to_bh(k), _to_bh(v)
    dot = _to_bh(g)
    # delta = rowsum(dO * O): cheap elementwise — plain XLA, not a kernel
    delta = jnp.sum(dot.astype(jnp.float32)
                    * _to_bh(o).astype(jnp.float32), axis=-1,
                    keepdims=True)  # (B*H, S, 1)
    common = dict(block_q=block_q, block_k=block_k, causal=causal,
                  sm_scale=sm_scale, seq_len=S)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(B * H, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, S, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, S, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(B * H, S // block_k),
        in_specs=[
            pl.BlockSpec((1, S, D), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, S, D), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, S, 1), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, S, 1), lambda bh, ki: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, S, D), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, dot, lse, delta)
    return (_from_bh(dq, B, H), _from_bh(dk, B, H), _from_bh(dv, B, H))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, interpret: bool = False):
    return flash_attention_fwd(q, k, v, causal=causal, interpret=interpret)


def _fa_fwd(q, k, v, causal, interpret):
    if _use_pallas_bwd(q.shape[-1]):  # head_dim is static at trace time
        o, lse = _flash_fwd_impl(q, k, v, causal, interpret)
        return o, (q, k, v, o, lse)
    # reference backward never reads o/lse: don't hold them across bwd
    return flash_attention_fwd(q, k, v, causal=causal,
                               interpret=interpret), (q, k, v, None, None)


def _use_pallas_bwd(head_dim: int) -> bool:
    """The pallas backward pair is used for head_dim <= 64 by default; at
    128 the backward rematerializes through ``reference_attention``. Both
    backwards compile for the v5e at the ``1b`` shapes
    (tests/test_chip_compile.py); which is faster at head_dim 128 is not
    measured, so the rule stands until a chip trace decides it (ROADMAP
    S1b). Override with RAY_TPU_FLASH_BWD=pallas|reference."""
    import os

    mode = os.environ.get("RAY_TPU_FLASH_BWD", "auto")
    if mode == "pallas":
        return True
    if mode == "reference":
        return False
    return head_dim <= 64


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
    (B, S, H, D) operands, always over the whole sequence."""
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
              segment_ids: Optional[jax.Array] = None):
    """Dispatching attention op used by the flagship model."""
    if impl == "auto":
        from ray_tpu.utils import is_tpu

        use_flash = (
            is_tpu()
            and segment_ids is None
            and q.shape[1] % 128 == 0
            and q.shape[-1] in (64, 128, 256)
        )
        impl = "flash" if use_flash else "xla"
    if impl == "flash":
        return _flash(q, k, v, causal, False)
    if impl == "flash_interpret":
        return _flash(q, k, v, causal, True)
    return reference_attention(q, k, v, causal, segment_ids)
