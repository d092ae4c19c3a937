"""The "conv" kind: a gated short convolution
(``models/transformer.py:ShortConv``), no attention at all.

Its state: [layers, conv_taps - 1, slots, d_model], per slot the last
``conv_taps - 1`` gated inputs ``B * z``, oldest first. Prefill convolves the
bucket and leaves the rows of positions ``lengths - conv_taps + 1 .. lengths
- 1`` (zeros where the prompt is shorter than that; padding behind the prompt
never enters them), which is how a slot is reset at admission; a decode step
convolves the rows with the new input and shifts them by one."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.llm.model_runner import _dense, _normed, _rows_at
from ray_tpu.models.transformer import causal_conv


def alloc(cfg, layers, slots, num_pages, page_size):
    return jnp.zeros((layers, cfg.conv_taps - 1, slots, cfg.d_model),
                     cfg.dtype)


def inputs(x, lp, cfg, positions):
    """-> no query, the gated input ``s = B * z`` that is convolved (and
    kept), the gate ``C`` on the convolution's output."""
    h = _normed(x, lp["attn_norm"], cfg)
    b, c, z = jnp.split(_dense(h, lp["conv"]["in_proj"], cfg.dtype), 3, axis=-1)
    return None, b * z, c


def prompt(cfg, side, at, lp, state, q, s):
    """s [R, S, D] -> y and the state with the rows of the call's slots."""
    tail = cfg.conv_taps - 1
    tail_pos = side.lengths[:, None] - tail + jnp.arange(tail)[None]
    with jax.named_scope("conv.prefill"):
        y = causal_conv(s, lp["conv"]["conv_kernel"].astype(cfg.dtype), 0)
        # [layer, tap, slot]: the indexed axes come first, [R, K-1, D]
        return y, state.at[at, :, side.slots].set(_rows_at(s, tail_pos))


def step(cfg, side, at, lp, state, q, s):
    """s [B, 1, D]; beside a prompt only the slots that decode shift."""
    keep = side.keep
    with jax.named_scope("conv.step"):
        taps = jnp.concatenate([state[at], s[:, 0][None]], axis=0)
        y = jnp.einsum("kbd,kd->bd", taps,
                       lp["conv"]["conv_kernel"].astype(cfg.dtype))
        rows = taps[1:]
        if keep is not None:
            rows = jnp.where(keep[None, :, None], rows, state[at])
        return y[:, None], state.at[at].set(rows)


def out(c, y, lp, cfg):
    return _dense(c * y, lp["conv"]["out_proj"], cfg.dtype)
