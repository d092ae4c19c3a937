"""The decoder-hybrid-decoder's own kinds: "gmu", "cross".

That model ("sambay") is a composition of its own
(``model_runner._hybrid_prefill``, ``_hybrid_decode``), not ``_forward``: its
kinds have their facts and their state here and their arithmetic there. Its
"full" and "window" layers keep what ``kinds/attention.py`` says (one paged
layer, which the full layer writes and every "cross" layer reads, and rings),
its "mamba" layers what ``kinds/mamba.py`` says (``Recurrent(state, tail)``:
that kind is a whole record of ``_forward``; the two loops here read its
state and do the step's arithmetic themselves).

"gmu" (a gated memory unit) and "cross" (attention over the full layer's keys
and values) keep nothing; a prefill call runs the cross-decoder on ONE
position a row, which ``prefill_cross_rows`` counts."""

from __future__ import annotations

import types

from ray_tpu.llm.kinds import Host


def _nothing(cfg, layers, slots, num_pages, page_size):
    return None


class _CrossRows(Host):
    """``prefill_cross_rows``: one a row of a call, against
    ``prefill_batch_tokens`` for the self-decoder."""

    def count_prompt(self, metrics, S, lens, carried):
        metrics["prefill_cross_rows"] += len(lens)


gmu = types.SimpleNamespace(alloc=_nothing)
cross = types.SimpleNamespace(alloc=_nothing, Host=_CrossRows)
