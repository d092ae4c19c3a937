"""The "kda" kind: delta-rule linear attention with a decay per key lane
(``models/transformer.py:KDA``).

Its state, ``Recurrent(state, tail)``: per layer and slot the matrix state of
every head, float32, [layers, slots, heads, key lanes, value lanes] as
``ops/kda.py`` keeps it (2.1 MB a slot and layer at 32 heads of 128 x 128),
and the last ``kda_conv - 1`` rows of the three convolutions' input ``q | k |
v``, [layers, kda_conv - 1, slots, 3 x heads x head dim]. Prefill runs the
chunked delta rule over the bucket (``kda_scan`` through
``ops/kda.py:kda_prefill``, padding passed over from ``lengths`` on, by the
whole chunk where a chunk holds nothing else) and WRITES the slot's state and
tail from the prompt alone, which is how a slot is reset at admission,
reused, or given back to a preempted request; a decode step convolves the
tail with the new input, steps every slot's state once, in place
(``kda_step``; beside a prompt ``kda_riding`` with ``keep``), and shifts the
tail. One product (``qkv_proj``) makes ``q | k | v`` and one the low-rank
gates' inner halves and ``beta`` for all rows, and ``o_proj`` runs once over
all rows; what lies between is each side's own. The prompt side: the
convolutions with the silu behind them stay XLA's (one fusion over ``[R, S, 3
H K]``), then ONE kernel takes that array, ``f``, beta and the output gate as
their products left them and does the l2 norms, the log-decay, beta's folds,
the recurrence, the head's output norm and the gate in its tile, and writes
``o`` in the products' type as ``o_proj`` reads it. The step side (``[B, 1]``
rows) does the same arithmetic in XLA around ``kda_step`` (``_operands``
before it, the norm and gate after)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.llm.kinds import Host as _Host
from ray_tpu.llm.kinds import Recurrent
from ray_tpu.llm.model_runner import _dense, _normed, _rmsnorm, _rows_at
from ray_tpu.models.transformer import (causal_conv, kda_log_decay,
                                        kda_qk_norm)
from ray_tpu.ops.kda import kda_prefill, kda_step, scan_chunks


def alloc(cfg, layers, slots, num_pages, page_size):
    H, K = cfg.kda_heads, cfg.kda_head_dim
    return Recurrent(
        jnp.zeros((layers, slots, H, K, K), jnp.float32),
        jnp.zeros((layers, cfg.kda_conv - 1, slots, 3 * H * K), cfg.dtype))


class Host(_Host):
    """``kda_step_slots`` / ``kda_step_live_slots`` as a Mamba-2 layer's
    (``kda_step`` walks every slot too) and, per prefill call,
    ``kda_scan_chunks`` (the chunks of ``CHUNK`` positions ``kda_scan``'s
    grid has a head and layer: the call's ``R x S / CHUNK``) and
    ``kda_scan_chunks_skipped`` (those wholly behind their row's length, a
    padding row's all: the kernel passes over them)."""

    def count_prompt(self, metrics, S, lens, carried):
        chunks, skipped = scan_chunks(S, lens)
        metrics["kda_scan_chunks"] += chunks
        metrics["kda_scan_chunks_skipped"] += skipped

    def count_step(self, metrics, slots, lens, riding):
        metrics["kda_step_slots"] += self.layers * slots
        metrics["kda_step_live_slots"] += self.layers * len(lens)


def inputs(x, lp, cfg, positions):
    """-> (the decay gate's ``f`` [.., H K], ``beta`` [.., H] float32, the
    output gate [.., H K]: each as its product left it), ``q | k | v`` before
    the convolutions, and nothing for ``out``: each side's norm and gate are
    its mixer's."""
    m, r = lp["kda"], cfg.kda_gate_rank
    h = _normed(x, lp["attn_norm"], cfg)
    with jax.named_scope("kda.in_proj"):
        qkv = _dense(h, m["qkv_proj"], cfg.dtype)
    with jax.named_scope("kda.gates"):
        inner = jnp.einsum("...d,df->...f", h, jnp.concatenate(
            [m[n]["kernel"] for n in ("f_a", "g_a", "b_proj")],
            axis=-1).astype(cfg.dtype))
        f = _dense(inner[..., :r], m["f_b"], cfg.dtype)
        gate = _dense(inner[..., r:2 * r], m["g_b"], cfg.dtype)
        beta = jax.nn.sigmoid(inner[..., 2 * r:].astype(jnp.float32))
    return (f, beta, gate), qkv, None


def _operands(a, f, m, cfg):
    """The convolved ``q | k | v`` a [.., 3 H K] and ``f`` [.., H K] -> q, k
    (unit length a head, q times ``K^-0.5``), v [.., H, K] and the log-decay
    g [.., H, K] float32, in XLA: a decode step's rows and the tests'."""
    H, K = cfg.kda_heads, cfg.kda_head_dim
    q, k, v = (t.reshape(*t.shape[:-1], H, K)
               for t in jnp.split(jax.nn.silu(a), 3, axis=-1))
    q, k = kda_qk_norm(q, k)
    return q, k, v, kda_log_decay(f, m["dt_bias"], m["A_log"])


def prompt(cfg, side, at, lp, kept, gates, qkv):
    """qkv [R, S, 3 H K] and the gates of those rows, from a zero state ->
    ``o`` and ``kept`` with the rows of the call's slots left at the prompts'
    last position (zeros in the tail where a prompt has none). Padding
    behind a prompt neither moves the state nor enters the tail: the kernel
    does nothing for a chunk that lies wholly behind ``lengths`` (it reads
    none of these arrays there and writes zeros to ``o``) and forces no decay
    and no update from ``lengths`` on inside the chunk that holds the end,
    where ``o`` is nobody's but finite; the padded rows of ``o`` go on
    through ``o_proj`` and the experts like any row."""
    ssm, conv = kept
    lengths, slots = side.lengths, side.slots
    m, tail = lp["kda"], cfg.kda_conv - 1
    tail_pos = lengths[:, None] - tail + jnp.arange(tail)[None]
    with jax.named_scope("kda.conv"):
        a = jax.nn.silu(causal_conv(qkv, m["conv_kernel"].astype(cfg.dtype), 0))
    o, state = kda_prefill(a, *gates, m["dt_bias"], m["A_log"],
                           m["o_norm"]["scale"], lengths, eps=cfg.norm_eps)
    # [layer, tap, slot]: the indexed axes come first, [R, K-1, 3 H K]
    return o, Recurrent(ssm.at[at, slots].set(state),
                        conv.at[at, :, slots].set(_rows_at(qkv, tail_pos)))


def step(cfg, side, at, lp, kept, gates, qkv):
    """qkv [B, 1, 3 H K] and the gates of those rows; beside a prompt only
    the slots that decode move."""
    ssm, conv = kept
    m, keep = lp["kda"], side.keep
    f, beta, gate = gates
    with jax.named_scope("kda.conv"):
        taps = jnp.concatenate([conv[at], qkv[:, 0][None]], axis=0)
        q, k, v, g = _operands(
            jnp.einsum("kbc,kc->bc", taps, m["conv_kernel"].astype(cfg.dtype)),
            f[:, 0], m, cfg)
    o, ssm = kda_step(ssm, at, q, k, v, g, beta[:, 0], keep,
                      name="kda_step" if side.op == "decode"
                      else "kda_" + side.op)
    with jax.named_scope("kda.out"):  # [B, 1, H K], what o_proj reads
        o = _rmsnorm(o, m["o_norm"]["scale"], cfg.norm_eps) * jax.nn.sigmoid(
            gate.astype(jnp.float32).reshape(o.shape))
        o = o.astype(cfg.dtype).reshape(gate.shape)
    rows = taps[1:]
    if keep is not None:
        rows = jnp.where(keep[None, :, None], rows, conv[at])
    return o, Recurrent(ssm, conv.at[at].set(rows))


def out(aux, o, lp, cfg):
    return _dense(o, lp["kda"]["o_proj"], cfg.dtype)
