"""The kinds of layer the serving programs run: ONE table, ``KINDS``.

A layer's kind decides what the layer keeps of the positions it has seen and
how its mixer reads it. Everything that asks "which kind?" asks this table:
``model_runner.init_cache`` (what to allocate), ``_forward`` (whose code to
run), ``llm/engine.py`` (what to count, which state moves with a request's
pages). A record is two things.

**Static facts**, on its line of the table, known without importing the
kind's code or kernels: ``paged`` (its state is addressed through the block
tables, every leaf ``[layers, num_pages, page_size, ..]``), ``attends`` (a
prefill call runs ``flash_fwd`` over its rows), ``recurrent`` (a state a slot
and layer that no block table addresses and no ring holds: what
``EngineConfig.expect_state_layers`` counts) and ``counters`` (the names it
adds to ``engine.metrics``).

**Code**, in the module the line names (``attention.full``: the attribute
``full`` of ``kinds/attention.py``; ``kda``: the module itself), imported at
the record's first use and never by a model without the kind, so a dense
replica loads no delta-rule code. The contract, over the stream ``x`` [rows,
positions, D] of one or both sides of a call:

- ``alloc(cfg, layers, slots, num_pages, page_size)`` -> the state of the
  kind's ``layers`` layers, any pytree (None: it keeps nothing);
- ``inputs(x, lp, cfg, positions)`` -> ``(q, row, aux)``, ONE product for all
  rows. ``q`` and ``row`` (arrays, or tuples and lists of them) are split by
  side, the prompts' ``[R, S, ..]`` and the step's ``[B, 1, ..]``; ``aux``
  is not: it is what ``out`` needs of every row (a gate, the input);
- ``prompt(cfg, side, at, lp, state, q, row)``, ``step(...)`` -> ``(o,
  state)``: the mixer proper over one side's rows (``Prompt``, ``Step``
  below) for the ``at``-th layer of the kind, that side's state written;
- ``out(aux, o, lp, cfg)`` -> what the mixer adds to the stream, from both
  sides' ``o`` laid end to end;
- ``after(state, prompt, step)`` -> the state as the call leaves it, given
  which sides it had (default: as it is);
- ``Host(cfg, layers, page_size)``: the kind's side on the host, one an
  engine (default: counts nothing; ``FoldingHost`` for a kind that writes
  its state back every few steps).

The order of the table is the order of the cache's leaves among a program's
arguments (``model_runner.Cache``): a new kind goes behind "window", where it
moves no leaf of a model on record. Adding a kind is one module here, one
line below, its kernel in ``ops/`` and its flax class.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, NamedTuple, Optional, Tuple

import jax
import numpy as np


class Prompt(NamedTuple):
    """A prefill call's side: ``model_runner._prompt_index``'s answer, the
    slot each row fills and each row's length."""
    index: tuple
    slots: jax.Array
    lengths: jax.Array


class Step(NamedTuple):
    """A decode step's side: ``model_runner._decode_index``'s answer, the
    positions of a page (0: nothing is paged), the slots that decode and
    ``op``: "decode", or "riding" where the step's rows ride a prefill call,
    whose prompts have just written other slots."""
    index: tuple
    page_size: int
    active: jax.Array
    op: str

    @property
    def keep(self) -> Optional[jax.Array]:
        """The slots whose state may move: beside a prompt the active ones;
        alone every slot's (one that is not active computes into its own
        rows and nobody reads them)."""
        return self.active if self.op == "riding" else None


class Host:
    """A kind's side on the host, one an engine, for its ``layers`` layers."""

    def __init__(self, cfg, layers: int, page_size: int):
        self.cfg, self.layers, self.page_size = cfg, layers, page_size

    def count_prompt(self, metrics: dict, S: int, lens: np.ndarray,
                     carried: bool) -> None:
        """A prefill call of rows ``lens`` at bucket ``S`` has run;
        ``carried``: its program carried a decode step (riders or none)."""

    def count_step(self, metrics: dict, slots: int, lens: np.ndarray,
                   riding: bool) -> None:
        """A decode step over ``slots`` slots is dispatched, alone or
        ``riding`` a prefill call; ``lens``: the live slots' lengths."""


class FoldingHost(Host):
    """The host's side of a kind whose decode step READS a slot's state
    every position and WRITES it back every ``FOLD``-th (the kind's own
    constant), the positions between kept beside it: ``pending`` mirrors the
    count the kind's state keeps on the device, by the program's own rule (a
    step that finds ``FOLD - 1`` positions pending folds, and so does every
    step a prefill call carries, riders or none)."""
    FOLD = 0
    pending = 0

    def count_prompt(self, metrics, S, lens, carried):
        if carried:  # its step side folded, whether or not a slot rode it
            self.pending = 0

    def folds(self, riding: bool) -> bool:
        """Whether the decode step being dispatched writes the states back;
        the mirror moves with it."""
        fold = riding or self.pending >= self.FOLD - 1
        self.pending = 0 if fold else self.pending + 1
        return fold


def _after(state: Any, prompt: bool, step: bool) -> Any:
    return state


_DEFAULTS = {"after": _after, "Host": Host}


@dataclasses.dataclass(frozen=True)
class Kind:
    name: str
    code: str  # module[.attribute] under this package
    paged: bool = False
    attends: bool = False
    recurrent: bool = False
    counters: Tuple[str, ...] = ()

    def __getattr__(self, what: str):
        # what the record's own fields lack: the kind's code, imported here
        if what.startswith("__"):
            raise AttributeError(what)
        module, _, attribute = self.code.partition(".")
        code = importlib.import_module(f"{__name__}.{module}")
        if attribute:
            code = getattr(code, attribute)
        try:
            return getattr(code, what)
        except AttributeError:
            if what in _DEFAULTS:
                return _DEFAULTS[what]
            raise


KINDS = {kind.name: kind for kind in (
    Kind("dense", "attention.dense", paged=True, attends=True),
    Kind("latent", "attention.latent", paged=True, attends=True, counters=(
        "mla_decode_live_tokens", "mla_decode_read_tokens")),
    Kind("full", "attention.full", paged=True, attends=True, counters=(
        "shared_kv_live_tokens", "shared_kv_read_tokens")),
    Kind("window", "attention.window", attends=True, counters=(
        "window_live_tokens",)),
    Kind("cross", "sambay.cross", counters=("prefill_cross_rows",)),
    Kind("gmu", "sambay.gmu"),
    Kind("conv", "conv", recurrent=True),
    Kind("mamba", "mamba", recurrent=True, counters=(
        "ssm_step_slots", "ssm_step_live_slots", "ssm_steps")),
    Kind("mamba2", "mamba2", recurrent=True, counters=(
        "ssd_step_slots", "ssd_step_live_slots", "ssd_steps",
        "ssd_fold_steps")),
    Kind("kda", "kda", recurrent=True, counters=(
        "kda_step_slots", "kda_step_live_slots", "kda_scan_chunks",
        "kda_scan_chunks_skipped")),
    Kind("retention", "retention", recurrent=True, counters=(
        "retention_state_slots", "retention_live_slots", "retention_steps",
        "retention_fold_steps", "retention_scan_chunks",
        "retention_scan_chunks_skipped")),
)}


def of(cfg) -> Tuple[str, ...]:
    """Each layer's kind. A model without ``layer_kinds`` is ``n_layers``
    layers of ONE kind; not "full": that is ``k | v`` rows under the paged
    kernel."""
    return cfg.layer_kinds or (
        ("latent" if cfg.kv_latent_rank else "dense",) * cfg.n_layers)


class Recurrent(NamedTuple):
    """What a recurrent kind with a convolution before its recurrence keeps a
    slot and layer: the recurrence's ``state`` (float32) and the ``tail``,
    the convolution's last inputs ``[layers, taps - 1, slots, width]``."""
    state: jax.Array
    tail: jax.Array


jax.export.register_namedtuple_serialization(
    Recurrent, serialized_name="ray_tpu.llm.kinds.Recurrent")
