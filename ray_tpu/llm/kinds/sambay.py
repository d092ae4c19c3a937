"""The decoder-hybrid-decoder's own kinds: "mamba", "gmu", "cross".

That model ("sambay") is a composition of its own
(``model_runner._hybrid_prefill``, ``_hybrid_decode``), not ``_forward``: its
kinds have their facts and their state here and their arithmetic there. Its
"full" and "window" layers keep what ``kinds/attention.py`` says (one paged
layer, which the full layer writes and every "cross" layer reads, and rings).

- "mamba" (a Mamba-1 selective scan): ``Recurrent(state, tail)``, per layer
  and slot the scan's state, float32, [layers, slots, N, inner] (``inner``
  along the lanes as ``ops/ssm.py`` keeps it: [.., N, inner] is whole tiles
  where [.., inner, N] would pad 16 lanes to 128), and the convolution's last
  ``ssm_conv - 1`` inputs, [layers, ssm_conv - 1, slots, inner];
- "gmu" (a gated memory unit) and "cross" (attention over the full layer's
  keys and values) keep nothing; a prefill call runs the cross-decoder on ONE
  position a row, which ``prefill_cross_rows`` counts."""

from __future__ import annotations

import types

import jax.numpy as jnp

from ray_tpu.llm.kinds import Host, Recurrent


def _mamba(cfg, layers, slots, num_pages, page_size):
    return Recurrent(
        jnp.zeros((layers, slots, cfg.ssm_state, cfg.ssm_inner), jnp.float32),
        jnp.zeros((layers, cfg.ssm_conv - 1, slots, cfg.ssm_inner), cfg.dtype))


def _nothing(cfg, layers, slots, num_pages, page_size):
    return None


class _CrossRows(Host):
    """``prefill_cross_rows``: one a row of a call, against
    ``prefill_batch_tokens`` for the self-decoder."""

    def count_prompt(self, metrics, S, lens, carried):
        metrics["prefill_cross_rows"] += len(lens)


mamba = types.SimpleNamespace(alloc=_mamba)
gmu = types.SimpleNamespace(alloc=_nothing)
cross = types.SimpleNamespace(alloc=_nothing, Host=_CrossRows)
