"""The "mamba2" kind: a Mamba-2 mixer (``models/transformer.py:Mamba2``).

Its state, ``Recurrent(state, tail)``: per layer and slot the matrix state of
every head, float32, [layers, slots, N, heads x head size] as ``ops/ssd.py``
keeps it (the channels along the lanes; 4.19 MB a slot and layer at 128 heads
of 64 and a state of 128: the largest thing a slot holds), and the last
``ssm_conv - 1`` rows of the convolution's input ``x | B | C``, [layers,
ssm_conv - 1, slots, ssm_inner + 2 ssm_state]. Prefill runs the chunked scan
over the bucket (``ssd_scan``, padding passed over with ``dt = 0``) and WRITES
the slot's state and tail from the prompt alone, which is how a slot is reset
at admission, reused, or given back to a preempted request; a decode step
convolves the tail with the new input, steps every slot's state once, in
place (``ssd_step``; beside a prompt ``ssd_riding`` with ``keep``), and shifts
the tail. One product (``in_proj``) makes ``z | xBC | dt`` for all rows, the
gated norm and ``out_proj`` run once over all rows."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.llm.kinds import Host as _Host
from ray_tpu.llm.kinds import Recurrent
from ray_tpu.llm.model_runner import _dense, _normed, _rmsnorm, _rows_at
from ray_tpu.models.transformer import causal_conv
from ray_tpu.ops.ssd import ssd_scan, ssd_step


def alloc(cfg, layers, slots, num_pages, page_size):
    return Recurrent(
        jnp.zeros((layers, slots, cfg.ssm_state, cfg.ssm_inner), jnp.float32),
        jnp.zeros((layers, cfg.ssm_conv - 1, slots,
                   cfg.ssm_inner + 2 * cfg.ssm_state), cfg.dtype))


class Host(_Host):
    """Per decode step (riding ones too) and layer: ``ssd_step_slots`` (the
    slots whose state the step reads and writes: all of them,
    ``ops/ssd.py:ssd_step`` walks every slot) and ``ssd_step_live_slots``
    (those of them that decode)."""

    def count_step(self, metrics, slots, lens, riding):
        metrics["ssd_step_slots"] += self.layers * slots
        metrics["ssd_step_live_slots"] += self.layers * len(lens)


def inputs(x, lp, cfg, positions):
    """-> the step sizes before their bias ``dt`` [.., H], the convolution's
    input ``x | B | C``, the gate ``z`` on the recurrence's output."""
    h = _normed(x, lp["attn_norm"], cfg)
    z, xbc, dt = jnp.split(
        _dense(h, lp["mamba"]["in_proj"], cfg.dtype),
        [cfg.ssm_inner, 2 * cfg.ssm_inner + 2 * cfg.ssm_state], axis=-1)
    return dt, xbc, z


def _operands(a, dt, m, cfg):
    """The convolved input a [.., I + 2N] and the raw step sizes dt [.., H]
    -> what the recurrence takes: (dt after bias and softplus in float32, x,
    B, C, A [H])."""
    a = jax.nn.silu(a)
    x, Bm, Cm = jnp.split(a, [cfg.ssm_inner, cfg.ssm_inner + cfg.ssm_state],
                          axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + m["dt_bias"])
    return dt, x, Bm, Cm, -jnp.exp(m["A_log"])


def _skip(y, x, m, cfg):
    """y [.., I] float32 with the skip ``D[h] x`` of each head."""
    return y + jnp.repeat(m["D"], cfg.ssm_inner // cfg.ssm_heads) \
        * x.astype(jnp.float32)


def prompt(cfg, side, at, lp, kept, dt, xbc):
    """xbc [R, S, I + 2N], dt [R, S, H], from a zero state -> y and ``kept``
    with the rows of the call's slots left at the prompts' last position
    (zeros in the tail where a prompt has none). Padding behind a prompt
    neither advances the state (``dt = 0``) nor enters the tail."""
    ssm, conv = kept
    in_prompt, slots = side.index[1], side.slots
    m, tail = lp["mamba"], cfg.ssm_conv - 1
    tail_pos = side.lengths[:, None] - tail + jnp.arange(tail)[None]
    with jax.named_scope("ssd.prefill"):
        dt, x, Bm, Cm, A = _operands(causal_conv(
            xbc, m["conv_kernel"].astype(cfg.dtype),
            m["conv_bias"].astype(cfg.dtype)), dt, m, cfg)
        y, state = ssd_scan(jnp.where(in_prompt[..., None], dt, 0.0), x, Bm,
                            Cm, A)
        # [layer, tap, slot]: the indexed axes come first, [R, K-1, I + 2N]
        return _skip(y, x, m, cfg), Recurrent(
            ssm.at[at, slots].set(state),
            conv.at[at, :, slots].set(_rows_at(xbc, tail_pos)))


def step(cfg, side, at, lp, kept, dt, xbc):
    """xbc [B, 1, I + 2N], dt [B, 1, H]; beside a prompt only the slots that
    decode move."""
    ssm, conv = kept
    m, keep = lp["mamba"], side.keep
    with jax.named_scope("ssd.step"):
        taps = jnp.concatenate([conv[at], xbc[:, 0][None]], axis=0)
        dt, x, Bm, Cm, A = _operands(
            jnp.einsum("kbc,kc->bc", taps, m["conv_kernel"].astype(cfg.dtype))
            + m["conv_bias"].astype(cfg.dtype), dt[:, 0], m, cfg)
        y, ssm = ssd_step(ssm, at, dt, x, Bm, Cm, A, keep,
                          name="ssd_step" if side.op == "decode"
                          else "ssd_" + side.op)
        rows = taps[1:]
        if keep is not None:
            rows = jnp.where(keep[None, :, None], rows, conv[at])
        return _skip(y, x, m, cfg)[:, None], Recurrent(
            ssm, conv.at[at].set(rows))


def out(z, y, lp, cfg):
    """The gated norm over all ``ssm_inner`` channels of the recurrence's y
    [.., I] float32, then ``out_proj``."""
    m = lp["mamba"]
    with jax.named_scope("ssd.gate_norm"):
        y = _rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)),
                     m["norm"]["scale"], cfg.norm_eps).astype(cfg.dtype)
    return _dense(y, m["out_proj"], cfg.dtype)
