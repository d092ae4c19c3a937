"""The "mamba" kind: a Mamba-1 mixer, a selective scan
(``models/transformer.py:Mamba`` under an "rms" block; the
decoder-hybrid-decoder's "mamba" layers keep the same state and have their
arithmetic in ``model_runner._hybrid_prefill`` / ``_hybrid_decode``, which ask
this module for ``alloc`` alone).

Its state, ``Recurrent(state, tail)``: per layer and slot the scan's state,
float32, [layers, slots, N, inner] (``inner`` along the lanes as ``ops/ssm.py``
keeps it: [.., N, inner] is whole tiles where [.., inner, N] would pad 16
lanes to 128; 327,680 bytes a slot and layer at 5,120 channels), and the
convolution's last ``ssm_conv - 1`` inputs, [layers, ssm_conv - 1, slots,
inner], in the products' type.

Prefill convolves the bucket, runs the scan from a zero state
(``ops/ssm.py:selective_scan``, padding passed over with ``dt = 0``) and
WRITES the slot's state and tail from the prompt alone, which is how a slot
is reset at admission, reused, or given back to a preempted request; a decode
step convolves the tail with the new input, steps every slot's state in place
(``ops/ssm.py:ssm_step``: read and written at EVERY step; beside a prompt
under the name ``ssm_riding``) and shifts the tail. Beside a prompt a slot
that does not decode keeps both to the bit. One product (``in_proj``) makes
``u | z`` for all rows; the gate and ``out_proj`` run once over all rows. With
``cfg.ssm_inner_norms`` the step size's low-rank input, ``B`` and ``C`` each
pass an RMSNorm of their own (``model_runner._mamba_inputs``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.llm import kinds
from ray_tpu.llm.kinds import Recurrent
from ray_tpu.llm.model_runner import (_dense, _mamba_gated, _mamba_inputs,
                                      _mamba_skip, _normed, _rows_at)
from ray_tpu.models.transformer import causal_conv
from ray_tpu.ops.ssm import selective_scan, ssm_step


def alloc(cfg, layers, slots, num_pages, page_size):
    return Recurrent(
        jnp.zeros((layers, slots, cfg.ssm_state, cfg.ssm_inner), jnp.float32),
        jnp.zeros((layers, cfg.ssm_conv - 1, slots, cfg.ssm_inner), cfg.dtype))


class Host(kinds.Host):
    """Per decode step (riding ones too) and layer: ``ssm_step_slots`` (the
    slots whose state the step moves: all of them, ``ssm_step`` walks every
    slot) and ``ssm_step_live_slots`` (those of them that decode); per decode
    step ``ssm_steps``."""

    def count_step(self, metrics, slots, lens, riding):
        metrics["ssm_step_slots"] += self.layers * slots
        metrics["ssm_step_live_slots"] += self.layers * len(lens)
        metrics["ssm_steps"] += 1


def inputs(x, lp, cfg, positions):
    """-> no query, the convolution's input ``u`` (what the tail keeps), the
    gate ``z`` on the recurrence's output."""
    h = _normed(x, lp["attn_norm"], cfg)
    uz = _dense(h, lp["mamba"]["in_proj"], cfg.dtype)
    return None, uz[..., :cfg.ssm_inner], uz[..., cfg.ssm_inner:]


def _taps(m, cfg):
    return (m["conv_kernel"].astype(cfg.dtype),
            m["conv_bias"].astype(cfg.dtype))


def prompt(cfg, side, at, lp, kept, q, u):
    """u [R, S, I], from a zero state -> y and ``kept`` with the rows of the
    call's slots left at the prompts' last position (zeros in the tail where
    a prompt has none). Padding behind a prompt neither advances the state
    (``dt = 0``) nor enters the tail."""
    ssm, conv = kept
    in_prompt, slots = side.index[1], side.slots
    m, tail = lp["mamba"], cfg.ssm_conv - 1
    tail_pos = side.lengths[:, None] - tail + jnp.arange(tail)[None]
    with jax.named_scope("ssm.prefill"):
        a, dt, Bm, Cm = _mamba_inputs(causal_conv(u, *_taps(m, cfg)), m, cfg)
        y, state = selective_scan(jnp.where(in_prompt[..., None], dt, 0.0),
                                  a, Bm, Cm, -jnp.exp(m["A_log"]))
        # [layer, tap, slot]: the indexed axes come first, [R, K-1, I]
        return _mamba_skip(y, a, m), Recurrent(
            ssm.at[at, slots].set(state),
            conv.at[at, :, slots].set(_rows_at(u, tail_pos)))


def step(cfg, side, at, lp, kept, q, u):
    """u [B, 1, I]: one position of every slot's recurrence, in place. Beside
    a prompt a slot that does not decode takes ``dt = 0`` and its tail stays."""
    ssm, conv = kept
    m, keep = lp["mamba"], side.keep
    with jax.named_scope("ssm.step"):
        taps = jnp.concatenate([conv[at], u[:, 0][None]], axis=0)
        w, b = _taps(m, cfg)
        a, dt, Bm, Cm = _mamba_inputs(
            jnp.einsum("kbi,ki->bi", taps, w) + b, m, cfg)
        y, ssm = ssm_step(
            ssm, at, dt, a, Bm, Cm, -jnp.exp(m["A_log"]), keep,
            name="ssm_step" if side.op == "decode" else "ssm_riding")
        rows = taps[1:]
        if keep is not None:
            rows = jnp.where(keep[None, :, None], rows, conv[at])
        return _mamba_skip(y, a, m)[:, None], Recurrent(
            ssm, conv.at[at].set(rows))


def out(z, y, lp, cfg):
    """The recurrence's y [.., I] float32 with its skip, gated, then
    ``out_proj``."""
    return _mamba_gated(y, z, lp["mamba"], cfg)
