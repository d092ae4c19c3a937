"""The four kinds that attend: "dense", "latent", "full", "window".

They share the projections (``model_runner._qkv``), the output (the gate,
then ``o_proj``) and, on the prompt side, the flash kernel over the call's own
keys and values, which is handed ``lengths``: the query blocks wholly behind a
row's end are passed over and come back zeros (``ops/attention.py``). What
each keeps, and how a decode step reads it:

- "dense", every layer of a model with neither ``layer_kinds`` nor a latent
  rank: ``KV(k, v)``, each [layers, num_pages, page_size, n_kv_heads, hd],
  the keys and values by head, a layer found by its number. A decode step
  gathers each slot's pages by (layer, block_tables) into a [B, Lmax] view
  and runs grouped-query attention against it under a mask;
- "latent", every layer of a model with ``kv_latent_rank`` and no
  ``layer_kinds``, or one kind among others of a model with them (the
  rotated part is rotated only where ``rope_kinds`` names "latent", else its
  lanes are plain ones): [layers, num_pages, page_size, W], per position ONE
  row ``c | k_pe | 0`` for all heads: the normalised latent, the rotated key
  and padding to whole 128-lane tiles (512 + 64 -> 640; ``ops/mla.py`` says
  why), in place of 2 x heads x head_dim. Prefill attends over keys and
  values expanded to heads (192-wide q . k and 128-wide values); decode
  attends over the rows themselves with the up-projection absorbed, through
  ``ops/mla.py:mla_decode``, which reads only the pages that hold live
  positions. With ``q_latent_rank`` the queries are low-rank (down, a norm,
  up to the heads: ``mla.q_lora``), and with ``latent_lora_scale`` the
  queries and the normalised latent are scaled by their widths' ratios; the
  SCALED latent is what a row holds, so both paths read it;
- "full" of a model with ``layer_kinds``: [layers, num_pages, page_size, 2
  KVH hd], a row ``k | v`` of all heads a position, handed out by the block
  tables as any page is (a decoder-hybrid-decoder has one such layer, which
  its "cross" layers read too). Decode attends through
  ``ops/paged_attention.py`` over the live pages (a work list built once a
  step, ``ops/mla.py:live_pages``); nothing is gathered over a slot's whole
  length;
- "window" of such a model: [layers, slots, window, 2 KVH hd], per slot a
  ring of the ``window`` newest positions' rows, position ``t`` at entry ``t
  mod window``, masked by how many entries are filled; decode attends over
  the rings' filled blocks through the same kernel.

Keys are rotated before they are written where the kind rotates
(``TransformerConfig.rope_kinds``). Pages belong to a request, rings to a
SLOT: prefill overwrites a slot's from the prompt alone, which is also how a
slot is reset at admission."""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import export

from ray_tpu.llm.kinds import Host
from ray_tpu.llm.model_runner import (_normed, _paged_attention, _qkv,
                                      _ring_blocks, _rmsnorm, _write_rings)
from ray_tpu.models.transformer import _rope
from ray_tpu.ops.attention import attention as attention_op
from ray_tpu.ops.mla import mla_decode


class KV(NamedTuple):
    k: jax.Array  # [layers, num_pages, page_size, KVH, HD]
    v: jax.Array


export.register_namedtuple_serialization(
    KV, serialized_name="ray_tpu.llm.kinds.attention.KV")


class _PagedReads(Host):
    """What a decode step attends over (``live``: positions 0..seq_len of
    every active slot) and what it reads for that (``read``: the positions of
    the pages ``ops/mla.py:live_pages`` lists, an inactive slot's one step
    over the scratch page included), for ONE layer that reads them, into
    ``<prefix>_live_tokens`` / ``_read_tokens``: counted once a decode step,
    not once a layer that reads pages."""
    prefix = ""

    def count_step(self, metrics, slots, lens, riding):
        P, lens = self.page_size, lens.astype(np.int64)
        metrics[self.prefix + "_live_tokens"] += int((lens + 1).sum())
        metrics[self.prefix + "_read_tokens"] += int(
            ((lens // P + 1) * P).sum()) + P * (slots - len(lens))


class _LatentReads(_PagedReads):
    prefix = "mla_decode"


class _FullReads(_PagedReads):
    prefix = "shared_kv"


class _WindowReads(Host):
    """``window_live_tokens``: filled ring entries the active slots attend
    over, a step and window layer."""

    def count_step(self, metrics, slots, lens, riding):
        metrics["window_live_tokens"] += int(
            np.minimum(lens + 1, self.cfg.window).sum())


class _Attention:
    def out(self, h, o, lp, cfg):
        """The attention ``o`` [B, S, H, hd] of a layer with input ``h`` ->
        what the mixer adds to the stream: the gate, then o_proj."""
        a = lp["attn"]
        if cfg.attn_gate:
            with jax.named_scope("attn.gate"):
                o = o * jax.nn.sigmoid(jnp.einsum(
                    "...d,dhk->...hk", h,
                    a["gate_proj"]["kernel"].astype(cfg.dtype)))
        return jnp.einsum("...hk,hkd->...d", o,
                          a["o_proj"]["kernel"].astype(cfg.dtype))


class _Dense(_Attention):
    def alloc(self, cfg, layers, slots, num_pages, page_size):
        shape = (layers, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
        return KV(jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))

    def inputs(self, x, lp, cfg, positions):
        """-> q [.., H, hd], (k, v) by head, the normalised input."""
        h = _normed(x, lp["attn_norm"], cfg)
        q, k, v = _qkv(h, lp["attn"], cfg, positions)
        return q, (k, v), h

    def prompt(self, cfg, side, at, lp, state, q, row):
        _, _, page, offset, _, _ = side.index
        k, v = row
        state = KV(state.k.at[at, page, offset].set(k, mode="drop"),
                   state.v.at[at, page, offset].set(v, mode="drop"))
        rep = cfg.n_heads // cfg.n_kv_heads
        if rep != 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return attention_op(q, k, v, causal=True, impl=cfg.attention_impl,
                            lens=side.lengths), state

    def step(self, cfg, side, at, lp, state, q, row):
        """The new keys and values k, v [B, 1, KVH, HD] into the state, every
        slot's pages gathered straight from it, [B, Lmax, KVH, HD], and
        grouped-query attention over that under the mask, without
        materializing repeated heads."""
        _, _, page, offset, (block_tables, kv_mask), _, _ = side.index
        k, v = row
        B, Lmax = kv_mask.shape
        KVH, HD = state.k.shape[3:]
        new_k = state.k.at[at, page, offset].set(k[:, 0], mode="drop")
        new_v = state.v.at[at, page, offset].set(v[:, 0], mode="drop")
        k_all = new_k[at, block_tables].reshape(B, Lmax, KVH, HD)
        v_all = new_v[at, block_tables].reshape(B, Lmax, KVH, HD)
        qg = q[:, 0].reshape(B, KVH, cfg.n_heads // cfg.n_kv_heads, HD)
        scores = jnp.einsum("bkgd,blkd->bkgl", qg, k_all,
                            preferred_element_type=jnp.float32) * (
                                1.0 / (HD ** 0.5))
        scores = jnp.where(kv_mask[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        attn = jnp.einsum("bkgl,blkd->bkgd", probs, v_all)
        return attn.reshape(B, 1, cfg.n_heads, HD), KV(new_k, new_v)


def _latent_width(cfg) -> int:
    return -(-(cfg.kv_latent_rank + cfg.qk_rope_head_dim) // 128) * 128


def _latent_row(parts, width):
    """``parts`` side by side along the last axis, zeros up to ``width``."""
    row = jnp.concatenate(parts, axis=-1)
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1)
                   + [(0, width - row.shape[-1])])


def _latent_qkv(x, p, cfg, positions):
    """Latent attention's projections of x [B, S, D]: q_nope [B, S, H, nope],
    q_pe [B, S, H, rope] rotated, the normalised latent c [B, S, R], the
    rotated key k_pe [B, S, rope] (one for all heads), and the cache row
    ``c | k_pe | 0`` [B, S, W]. As a kind of a model with ``layer_kinds``
    that does not name "latent" in ``rope_kinds`` nothing is rotated: q_pe and
    k_pe are the same lanes, plain."""
    dtype = cfg.dtype
    r, nope = cfg.kv_latent_rank, cfg.qk_nope_head_dim
    if cfg.q_latent_rank:  # low-rank queries: down, a norm, up to the heads
        with jax.named_scope("mla.q_lora"):
            cq = _rmsnorm(jnp.einsum(
                "...d,dr->...r", x, p["q_a_proj"]["kernel"].astype(dtype)),
                p["q_a_norm"]["scale"], cfg.norm_eps)
            q = jnp.einsum("...r,rhk->...hk", cq,
                           p["q_b_proj"]["kernel"].astype(dtype))
    else:
        q = jnp.einsum("...d,dhk->...hk", x,
                       p["q_proj"]["kernel"].astype(dtype))
    a = jnp.einsum("...d,dr->...r", x, p["kv_a_proj"]["kernel"].astype(dtype))
    c_scale = p["kv_a_norm"]["scale"]
    if cfg.latent_lora_scale:
        # the queries times s_q; the normalised latent times s_kv, in the
        # norm's own float32 (sqrt(12) is no bfloat16 number). The SCALED
        # latent is what the cache row holds: both halves of kv_b_proj read
        # it, expanded in prefill and absorbed in decode alike
        from ray_tpu.models.transformer import latent_scales

        s_q, s_kv = latent_scales(cfg)
        q, c_scale = q * jnp.asarray(s_q, q.dtype), c_scale * s_kv
    c = _rmsnorm(a[..., :r], c_scale, cfg.norm_eps)
    if cfg.layer_kinds and "latent" not in cfg.rope_kinds:
        q_pe, k_pe = q[..., nope:], a[..., r:]
    else:
        q_pe = _rope(q[..., nope:], positions, cfg.rope_theta)
        k_pe = _rope(a[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    return (q[..., :nope], q_pe, c, k_pe,
            _latent_row([c, k_pe], _latent_width(cfg)))


def _latent_attention_expanded(q_nope, q_pe, c, k_pe, p, cfg, lengths=None):
    """Prefill's path: keys and values up-projected from the latent to heads,
    then plain causal attention over 192-wide q . k and 128-wide values,
    which passes over what lies behind the rows' ``lengths``."""
    nope = cfg.qk_nope_head_dim
    kv = jnp.einsum("...r,rhk->...hk", c,
                    p["kv_b_proj"]["kernel"].astype(cfg.dtype))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[..., None, :], q_pe.shape)],
        axis=-1)
    return attention_op(q, k, kv[..., nope:], causal=True,
                        impl=cfg.attention_impl, lens=lengths)


def _latent_attention_absorbed(q_nope, q_pe, rows, work, layer, p, cfg):
    """Decode's path, the same mathematics with ``kv_b_proj`` absorbed: the
    query goes up to the latent (``q_lat[h] = q_nope[h] W_k[h]^T``), all heads
    attend over the cache rows themselves (``ops/mla.py:mla_decode``), and the
    result comes down through the value half (``out[h] = o_lat[h] W_v[h]``).
    q_nope [B, H, nope], q_pe [B, H, rope] -> [B, H, v_head_dim]."""
    r, nope = cfg.kv_latent_rank, cfg.qk_nope_head_dim
    w = p["kv_b_proj"]["kernel"].astype(cfg.dtype)        # [R, H, nope + v]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w[..., :nope])
    o_lat = mla_decode(
        _latent_row([q_lat, q_pe], rows.shape[-1]), rows, work, rank=r,
        layer=layer, sm_scale=1.0 / ((nope + q_pe.shape[-1]) ** 0.5))
    return jnp.einsum("bhr,rhv->bhv", o_lat, w[..., nope:])


class _Latent(_Attention):
    Host = _LatentReads

    def alloc(self, cfg, layers, slots, num_pages, page_size):
        return jnp.zeros((layers, num_pages, page_size, _latent_width(cfg)),
                         cfg.dtype)

    def inputs(self, x, lp, cfg, positions):
        """-> [q_nope, q_pe, c, k_pe], the row ``c | k_pe | 0``, the
        normalised input."""
        h = _normed(x, lp["attn_norm"], cfg)
        *q, row = _latent_qkv(h, lp["attn"], cfg, positions)
        return q, row, h

    def prompt(self, cfg, side, at, lp, state, q, row):
        _, _, page, offset, _, _ = side.index
        state = state.at[at, page, offset].set(row, mode="drop")
        return _latent_attention_expanded(*q, lp["attn"], cfg,
                                          side.lengths), state

    def step(self, cfg, side, at, lp, state, q, row):
        _, _, page, offset, work, _, _ = side.index
        state = state.at[at, page, offset].set(row[:, 0], mode="drop")
        o = _latent_attention_absorbed(q[0][:, 0], q[1][:, 0], state, work,
                                       at, lp["attn"], cfg)
        return o[:, None], state


class _Full(_Attention):
    name, window = "full", False

    Host = _FullReads

    def alloc(self, cfg, layers, slots, num_pages, page_size):
        return jnp.zeros((layers, num_pages, page_size,
                          2 * cfg.n_kv_heads * cfg.head_dim), cfg.dtype)

    def inputs(self, x, lp, cfg, positions):
        """-> q [.., H, hd], the row ``k | v`` [.., 2 KVH hd], the
        normalised input."""
        h = _normed(x, lp["attn_norm"], cfg)
        q, k, v = _qkv(h, lp["attn"], cfg, positions,
                       self.name in cfg.rope_kinds)
        flat = lambda t: t.reshape(*t.shape[:-2], -1)   # noqa: E731
        return q, jnp.concatenate([flat(k), flat(v)], axis=-1), h

    def _write(self, side, at, state, row):
        _, _, page, offset, _, _ = side.index
        return state.at[at, page, offset].set(row, mode="drop")

    def prompt(self, cfg, side, at, lp, state, q, row):
        rep = cfg.n_heads // cfg.n_kv_heads
        R, S = row.shape[:2]
        state = self._write(side, at, state, row)
        k, v = (t.reshape(R, S, cfg.n_kv_heads, -1)
                for t in jnp.split(row, 2, axis=-1))
        return attention_op(
            q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
            causal=True, impl=cfg.attention_impl,
            window=cfg.window if self.window else 0, lens=side.lengths), state

    def step(self, cfg, side, at, lp, state, q, row):
        _, _, page, offset, work, _, _ = side.index
        state = state.at[at, page, offset].set(row[:, 0], mode="drop")
        o = _paged_attention(q[:, 0], state, work, at, "paged_gqa_" + side.op,
                             cfg)
        return o[:, None], state


class _Window(_Full):
    name, window = "window", True

    Host = _WindowReads

    def alloc(self, cfg, layers, slots, num_pages, page_size):
        return jnp.zeros((layers, slots, cfg.window,
                          2 * cfg.n_kv_heads * cfg.head_dim), cfg.dtype)

    def _write(self, side, at, state, row):
        return _write_rings(state, at, side.slots, row, side.index[5])

    def step(self, cfg, side, at, lp, state, q, row):
        slot, positions, _, _, _, ring_work, _ = side.index
        # a slot past the last (beside a prompt: one that is not active) is
        # dropped, as _write_rings drops a padding row's
        state = state.at[at, slot, positions % cfg.window].set(row[:, 0])
        o = _paged_attention(q[:, 0], _ring_blocks(state, cfg, side.page_size),
                             ring_work, at, "window_gqa_" + side.op, cfg)
        return o[:, None], state


dense, latent, full, window = _Dense(), _Latent(), _Full(), _Window()
