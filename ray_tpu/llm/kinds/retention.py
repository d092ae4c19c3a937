"""The "retention" kind: power retention, gated power attention of degree 2
(``models/transformer.py:Retention``).

Its state, ``State(state, pending, count)``: per layer and slot (and one slot
past the last, where a prefill call's padding rows land) every key/value
head's state with its normaliser, float32, [layers, slots + 1, KVH, head_dim
/ 2 + 2, head_dim, head_dim] (``ops/retention.py``'s layout: the symmetric
square of the keys against their values laid by rotation, the normaliser the
last of those slabs, one leaf, so that one alias moves both in place and
every tile is whole; 34.6 MB a slot and layer at 8 heads of 128: most of what
the chip holds), ``n_heads / n_kv_heads`` query heads reading ONE state. That
is the state as of the slot's last WRITE-BACK: a decode step reads it every
position and writes it every ``FOLD``-th. Beside it ``pending``, per layer
the ``FOLD - 1`` positions since (each one's key, value and log-gate,
float32, [layers, FOLD - 1, 3, slots, KVH, head_dim]; a null one, ``k = 0``
and ``log g = 0``, where a slot has fewer), and ``count``, ONE int32 for all
slots and layers: how many decode steps' positions lie there, which is what
decides on the device whether a step reads or folds. No convolution, and
nothing by position: a model of such layers alone has no paged state, its
block tables stay arguments of both programs and address nothing.

q, k and v are the attention kinds' three products (``model_runner._qkv``:
the per-head norms, then the rotation by the rows' positions, on both sides
alike) and one float32 product makes the gates. Prefill runs the chunked
recurrence over the bucket (``retention_scan`` through
``ops/retention.py:retention_prefill``, padding passed over from ``lengths``
on, by the whole chunk where a chunk holds nothing else) and WRITES the
slot's state from the prompt alone and empties its pending positions, which
is how a slot is reset at admission, reused, or given back to a preempted
request; a decode step READS every slot's state once and keeps its position
beside it (``retention_read``: half the bytes of a step that writes), and
every ``FOLD``-th step folds the pending positions and its own into the
state, in place (``retention_step``; beside a prompt always, as
``retention_riding`` with ``keep``): the same function, re-associated;
``o_proj`` runs once over all rows."""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import export

from ray_tpu.llm.kinds import Host as _Host
from ray_tpu.llm.model_runner import _dense, _normed, _qkv
from ray_tpu.ops.retention import (FOLD, advance, check_degree,
                                   retention_decode, retention_prefill,
                                   scan_chunks, state_shape)


class State(NamedTuple):
    state: jax.Array
    pending: jax.Array
    count: jax.Array


export.register_namedtuple_serialization(
    State, serialized_name="ray_tpu.llm.kinds.retention.State")


def alloc(cfg, layers, slots, num_pages, page_size):
    check_degree(cfg.retention_degree)
    return State(
        # a slot past the last: where a padding row's state lands
        jnp.zeros((layers, slots + 1, cfg.n_kv_heads,
                   *state_shape(cfg.head_dim)), jnp.float32),
        jnp.zeros((layers, FOLD - 1, 3, slots, cfg.n_kv_heads, cfg.head_dim),
                  jnp.float32),
        jnp.zeros((), jnp.int32))


class Host(_Host):
    """Per decode step (riding ones too) and layer ``retention_state_slots``
    (the slots whose state the step reads: all of them) and
    ``retention_live_slots`` (those that decode); per decode step
    ``retention_steps`` and ``retention_fold_steps``, those that also WROTE
    every slot's state back (the step that found ``FOLD - 1`` positions
    pending, and every riding step), by the program's own rule on
    ``pending``, the host's copy of ``State.count``: a quarter of the steps
    where few ride; per prefill call ``retention_scan_chunks`` /
    ``_skipped`` as a "kda" layer's."""
    pending = 0

    def count_prompt(self, metrics, S, lens, carried):
        chunks, skipped = scan_chunks(S, lens)
        metrics["retention_scan_chunks"] += chunks
        metrics["retention_scan_chunks_skipped"] += skipped
        if carried:  # its step side folded, whether or not a slot rode it
            self.pending = 0

    def count_step(self, metrics, slots, lens, riding):
        fold = riding or self.pending >= FOLD - 1
        metrics["retention_state_slots"] += self.layers * slots
        metrics["retention_live_slots"] += self.layers * len(lens)
        metrics["retention_steps"] += 1
        metrics["retention_fold_steps"] += fold
        self.pending = 0 if fold else self.pending + 1


def inputs(x, lp, cfg, positions):
    """-> the log-gates [.., KVH] (float32 from the product on: one gate a
    key/value head, kernel and bias), [q [.., H, hd], k, v [.., KVH, hd]]
    (rotated where ``rope_kinds`` names the kind), nothing."""
    m = lp["retention"]
    h = _normed(x, lp["attn_norm"], cfg)
    with jax.named_scope("retention.inputs"):
        q, k, v = _qkv(h, m, cfg, positions, "retention" in cfg.rope_kinds)
        log_g = jax.nn.log_sigmoid(_dense(h, m["g_proj"], jnp.float32))
    return log_g, [q, k, v], None


def prompt(cfg, side, at, lp, kept, log_g, qkv):
    """log_g [R, S, KVH] and q, k, v of those rows, from a zero state -> ``o``
    and ``kept`` with the states of the call's slots left at the prompts'
    last position (the kernel writes each row's state into its slot of the
    leaf itself; a padding row's is the one past the last) and their pending
    positions EMPTIED: the slot's last tenant's are not this request's, and a
    null position adds nothing whenever it is folded. ``o`` behind a
    prompt's end is zeros, which go on through ``o_proj`` like any row."""
    ssm, pending, count = kept
    q, k, v = (t.reshape(*t.shape[:2], -1) for t in qkv)
    with jax.named_scope("retention.scan"):
        o, ssm = retention_prefill(q, k, v, log_g, side.lengths, ssm, at,
                                   side.slots,
                                   heads=(cfg.n_heads, cfg.n_kv_heads))
        # [layer, :, :, slot]: a padding row's slot is past the last, dropped
        pending = pending.at[at, :, :, side.slots].set(0.0, mode="drop")
    return o.reshape(qkv[0].shape), State(ssm, pending, count)


def step(cfg, side, at, lp, kept, log_g, qkv):
    """log_g [B, 1, KVH] and q, k, v of those rows: a read or a fold, as the
    count says (``ops/retention.py:retention_decode``). A slot that does not
    decode takes a null position, alone or beside a prompt, so that a fold
    finds nothing of it to take."""
    ssm, pending, count = kept
    q, k, v = (t[:, 0] for t in qkv)
    with jax.named_scope("retention.step"):
        o, ssm, pending = retention_decode(
            ssm, pending, count, at, q, k, v, log_g[:, 0], side.active,
            riding=side.op == "riding")
    return o.astype(cfg.dtype)[:, None], State(ssm, pending, count)


def out(aux, o, lp, cfg):
    return jnp.einsum("...hk,hkd->...d", o, lp["retention"]["o_proj"][
        "kernel"].astype(cfg.dtype))


def after(kept, prompt, step):
    """The count as the call leaves it: a step alone advances it; a step that
    rode a prefill call folded, whatever it found."""
    if not step:
        return kept
    count = jnp.zeros_like(kept.count) if prompt else advance(kept.count)
    return kept._replace(count=count)
