"""Continuous-batching JAX LLM engine (TPU-native vLLM-engine equivalent).

Reference capability: ray.llm wraps vLLM's AsyncLLMEngine
(llm/_internal/serve/engines/vllm/vllm_engine.py) — request queue, paged KV
cache, continuous batching. Here the engine is a host-side scheduler over
two compiled XLA programs (prefill per length bucket, one decode step):

- slots: ``max_num_seqs`` concurrent sequences, fixed batch shape so decode
  is a single cached compilation;
- pages: a free list of KV pages; sequences allocate pages on demand as they
  cross page boundaries (admission blocks when no pages are free);
- scheduling per ``step()``: admit waiting requests into free slots and
  prefill them, one row a request: a request admitted alone is one program
  call on a ``[1, S]`` batch with ``S`` its own length bucket, and the
  requests of a step that admitted several share calls of two or four rows at
  the longest member's bucket (``prefill_groups``: every held weight is then
  read once a call and not once a request, which in a sparse model is most of
  a short prompt's call); the calls run back to back and one sampler call
  samples their first tokens. Then one decode step for all active slots:
  a program of its own (``jit_decode_step``) in a step that admitted nobody,
  and, where the model's prefill program can carry one
  (``model_runner.rides``: the models of ``layer_kinds`` under the "rms"
  block), the decode rows of a step that admitted somebody RIDE the phase's
  first prefill call. That call runs each side's mixer on its own rows and
  everything else (experts, head) once over both, so a sparse model streams
  its experts once in such a step and not twice; the slots that were active
  before the admission get their token from it, one sampler call samples
  theirs and the admitted requests' first, no ``decode_step`` is dispatched,
  and the requests admitted in the step take their second token in the next.
  A shape has one prefill program, of one row or of several: the carrying one
  where the call's own rows do not dwarf the step's (``_RIDE_ROWS``), called
  with nobody marked active where nothing rides, and the plain one elsewhere.
  A phase's carrying calls go first, so its first call takes the step; a
  ``[1, S]`` whose carrying program the compiler finds no room for is the
  plain program from then on (``plain_buckets`` says why), and a phase with no
  carrying call runs ``decode_step`` after it.
  Nothing waits for a partner: WHEN a request is admitted is as it was. A
  shape of several rows is made off the serving path from its bucket's first
  use on (``llm/prefill_shapes.py``: traced in a process of its own, compiled
  in threads), and a group forms only at a shape that is ready: until then
  its requests run as one-row calls.

The engine is single-threaded by design (actor wrappers, serve_llm.LLMServer,
give it an async front end) and reads a step's tokens ONE STEP LATE. A call of
``step()`` dispatches its programs from what the host knows, starts the copy
of their tokens to the host, and only then blocks on the tokens of the call
before it, emits those and returns THAT step's outputs: the device always has
a step queued behind the one it runs, and the host's admit, emit and the
caller's way back to ``step()`` cost it nothing. What makes that possible:

- every slot's newest token stays on the device (``_tokens``): the decode
  step reads the sampler's output of the step before, merged there with a
  prefill phase's first tokens by one ``[B]`` select;
- ``seq_lens``, block tables and the active mask do not depend on a token's
  value: the host advances them at dispatch, and counts a token in flight as
  generated. A request that ends by ``max_tokens`` or ``max_model_len`` ends
  at a step the host can count: its slot and pages are free at that dispatch
  (the device runs programs in order, so whatever is queued later may have
  them) and the next call admits into the slot;
- a request that EOS or a stop token ends is seen one step late: its slot has
  run one more decode step by then, whose token is dropped, never emitted
  (``dropped_tokens``), and is free for the call after;
- whoever reads or changes slot state out of that order first reads
  everything in flight (``_drain``): ``abort_request``, ``export_kv``,
  ``add_request_with_kv``, ``step(decode=False)`` (which also reads its own
  tokens before it returns) and a preemption. Outputs read outside a
  ``step()`` are handed over by the next one; ``has_unfinished()`` is true
  until they are. A caller whose ``step()`` raised drops what is in flight
  unread (``discard_in_flight``) before it aborts its requests. ``params``
  may be swapped between calls: a step already dispatched keeps the tree it
  was given.

What the engine says about itself (``LLMServer.engine_metrics()`` returns a
copy of ``engine.metrics``: flat, numeric, only ever growing, every key there
from ``__init__``, so two snapshots subtract; ``ray_tpu/serve/README.md`` is
the operator's guide to reading them):

- counts: ``steps`` (calls of ``step()`` that ran a program),
  ``overlapped_steps`` (those that did so while an earlier step's tokens were
  unread), ``dropped_tokens``, ``sample_calls`` (calls of
  ``jit_sample_tokens``: one a prefill phase and one a decode step that rode
  none) and ``sample_greedy_calls`` (those with no positive temperature in
  any slot, by the rule the program applies on the device: it then takes its
  ``argmax`` branch), ``prefill_steps`` (steps that ran a prefill phase),
  ``decode_steps`` (a dropped token's step is one; so is a step whose decode
  rows rode a prefill call) and, of those, ``riding_steps`` (no
  ``jit_decode_step``, no sampler call and no read of their own, so
  ``sample_calls == prefill_steps + decode_steps - riding_steps``; every
  counter of decode rows counts a riding step's as any decode step's),
  ``admitted`` (requests, each one row of a prefill call) against
  ``prefill_calls`` (program calls: fewer, where rows shared one),
  ``prefill_tokens`` (real prompt positions) against
  ``prefill_batch_tokens`` (the ``R x S`` of every call, padding rows
  included: what the device computes), ``generated_tokens``, ``preempted``,
  ``compiles`` (first use of a prefill bucket or of decode on the serving
  path), ``prefill_shapes_wanted`` / ``prefill_shapes_ready`` (shapes of
  several rows asked for off it, and compiled: fewer for good where one could
  not be made, which ``prefill_shapes.RowShapes.failed`` and the log
  explain); program counts move at dispatch, ``generated_tokens`` at emit;
- host milliseconds (``perf_counter_ns``): ``step_ms`` = ``host_ms`` +
  ``readback_ms`` (blocked on the device in ``np.asarray(tokens)``); the
  phases ``admit_ms``, ``prefill_dispatch_ms``, ``decode_dispatch_ms``,
  ``sample_dispatch_ms``, ``readback_ms``, ``emit_ms`` add up to ``step_ms``;
  ``between_steps_ms`` is the caller's time from one ``step()`` to the next
  while work was left;
- what each blocking read waited for, over a whole window and with no
  profiler: ``prefill_phase_ms`` and ``decode_phase_ms``, ``phase_ms`` their
  sum. A read of a prefill phase's first tokens waits behind that phase's
  ``jit_prefill`` calls and their one sampler call, a read of a decode
  step's behind ``jit_decode_step`` and its sampler call; a phase that
  carried a decode step is read once, as a prefill phase (so
  ``prefill_phase_ms / phase_ms`` is then no longer "the share lost to
  prefill" and ``decode_phase_calls`` is ``decode_steps - riding_steps``);
  each adds the time from the end of the read before it (or from its own
  dispatch, if later: the device had run dry) to the moment its
  ``np.asarray(tokens)`` returned. While the reads truly block
  (``readback_ms / phase_ms`` near 1 less the host's share) that is the
  device's time for the phase to a copy's latency; near 0 (the device waits
  for the host; on the CPU backend always) the split by kind says nothing
  and ``phase_ms`` stays the busy time. ``prefill_phase_calls``,
  ``decode_phase_calls``, ``prefill_phase_positions`` and
  ``prefill_phase_real_positions`` are ``prefill_calls``, ``decode_steps``,
  ``prefill_batch_tokens`` and ``prefill_tokens`` again, moving with the read
  and not at dispatch, so a window's deltas cover the same calls: a call's
  and a step's time with their share of the sampler, what a padded position
  of prefill costs whatever buckets a window drew, and ``generated_tokens /
  phase_ms``, the tokens a busy millisecond brings, with no request's end
  and no window's edge in it;
- reads that stalled: a read that waited over ``_STALL_MS`` (1,000 ms: nearly
  three times the longest program of any benchmark cell) for EACH call it
  stood behind adds one to ``stalled_reads`` and its wait to
  ``stalled_read_ms``, and the engine logs one line (``stalled read: kind=
  calls= bucket= rows=``, the wait, the time since its dispatch, and whether
  ``compiles`` moved since). ``stalled_read_ms / phase_ms`` is 0 in a sound
  run;
- every slot's row of every decode step (riding ones too), by what filled or
  emptied it, at the step's dispatch: ``slot_steps`` (``max_num_seqs`` a
  step) = ``slot_steps_live`` (rows that decode; once everything is read
  ``slot_steps_live == generated_tokens + dropped_tokens - admitted``) +
  ``slot_steps_prefilling`` (in a step whose decode rows ride a prefill
  call, the slots that went to a request in that very step) +
  ``slot_steps_starved`` (empty, and the admission before the step left
  nobody waiting: whoever offers the load set this step's batch) +
  ``slot_steps_page_blocked`` (empty though somebody waited: the queue's
  head lacked pages, or ``_grow_pages`` sent a decoding request back to the
  queue). Nothing else empties a row;
- per request, summed: ``queue_wait_ms`` (``add_request`` to first
  admission) and ``ttft_ms`` (``add_request`` to first token);
- the gaps a request sees between its tokens, each token dated by the moment
  the read that brought it returned: ``itl_ms`` (their sum), ``itl_tokens``
  (their number: every emitted token but a request's first) and the ladder
  ``itl_over_25ms``, ``_50ms``, ``_100ms``, ``_200ms``, ``_400ms``,
  ``_800ms`` (gaps strictly longer, so the p-quantile lies at or under the
  first rung whose count is at most ``(1 - p) x itl_tokens``). A decode step
  alone is 14-21 ms, so a gap over 50 ms was spent behind somebody's prefill
  phase; a preempted request's gap across its second prefill counts;
- what routing did in the ``jit_decode_step`` calls, for a model with experts
  (a carrying prefill call's ``moe_load`` mixes prompt and decode rows and is
  not read): ``moe_decode_layer_steps`` (those calls x expert layers) and,
  summed over those, ``moe_decode_routed_assignments`` (active slots x
  top_k), ``moe_decode_assignments`` (those that fell on an expert HELD here,
  ``TransformerConfig.experts_held``), ``moe_decode_zero_assignments`` (on a
  zero-compute expert, ``zero_experts``), ``moe_decode_experts_touched``
  (held experts that got a row) and ``moe_decode_max_load`` (rows of the
  fullest held expert). They come from ``Cache.moe_load``, a few KB copied
  out of the cache at dispatch (the next dispatch donates the cache) and read
  in ``emit`` with that step's tokens, a step later;
- what the model's kinds of layer count (``llm/kinds/``: a record's
  ``counters``, counted and defined by its ``Host``; every kind's keys are
  here at 0 whatever the model): what a decode step attends over and reads
  through the block tables and the rings (``mla_decode_*``, ``shared_kv_*``,
  ``window_live_tokens``), the slots whose recurrent state a step moves
  (``ssm_step_*`` with ``ssm_steps``, ``ssd_step_*``, ``kda_step_*``,
  ``retention_*_slots``, with
  ``retention_steps`` and ``retention_fold_steps``), the chunks a prefill
  call's scan has and passes over (``kda_scan_chunks*``,
  ``retention_scan_chunks*``) and ``prefill_cross_rows``. A model of
  recurrent layers alone keeps nothing by position: its requests are still
  handed pages and give them back (the accounting is every model's and costs
  nothing), and the pages address nothing;
- for every model that has an attention layer, per prefill call:
  ``flash_q_blocks`` (the query blocks ``flash_fwd``'s grid has a head for
  the call's ``[R, S]``, ``ops/attention.py:q_blocks``: ``R x S / 512`` from
  512 positions on, one a row under that) and ``flash_q_blocks_skipped``
  (those of them that lie wholly behind their row's length, a padding row's
  all: the kernel passes over them, in every attention layer of the prompt
  side alike).

Such a model's rings and rows need no allocator: a slot owns its own, a
prefill call overwrites all of them from the prompt (the engine tells it the
slot), so admission resets them and a preempted request, prefilled again from
its tokens, gets all three kinds back. Pages are handed out as for any model,
for one layer. ``export_kv`` / ``add_request_with_kv`` refuse such a model
by name: a request's state is not a gather of its pages.

The same boundaries are spans on the profiler's clock
(``util.tracing.annotate``): a ``jax.profiler`` trace taken in the process
that owns the engine shows ``ray_tpu/engine.step`` on the host plane and,
inside it, in this order, ``engine.admit`` (arguments, all as the admission
left them: ``waiting``, the queue's length; ``free_slots``; ``free_pages``;
``stopped``: why it admitted no more, ``queue``: nobody waited, ``slots``:
none was free, ``pages``: the queue's head lacked pages, or a slot that
decoded was sent back to the queue for one: why a row of this step's batch
runs empty, step by step), ``.prefill_dispatch`` (arguments
``bucket``, the largest of the phase, ``admitted``, ``calls``, ``rows``:
the calls' rows, padding included, and ``riding``: the decoding slots whose
step the phase is to carry, 0 where none), ``.sample_dispatch``
(argument ``greedy``: no slot samples, the program takes its ``argmax``
branch), ``.decode_dispatch`` (arguments ``overlapped``: 1 if an earlier step
is unread; ``experts``: experts touched
per layer in the newest decode step the host has read, and ``held``: that
step's assignments per layer to experts held here, models with experts only; ``live_tokens``: positions the step attends over through the block
tables, models with a latent cache or with ``layer_kinds`` only;
``state_slots``: the decoding slots, whose recurrent states the step moves, models
with a recurrent kind that counts them only), ``.sample_dispatch``, then ``.readback`` and ``.emit`` once for
every sampler call of the step before. ``.readback`` names what it waited
for (arguments ``kind``: ``prefill`` or ``decode``; ``calls``: the prefill
calls behind it, 1 for a decode step; ``bucket``: the largest of those
calls' buckets, 0 for a decode step; ``rows``: the requests whose token it
brings, a carried step's among a phase's), and its end is the moment
the phase counters and the token gaps are dated by: in a trace it lies a
copy's latency after the end of the last ``jit_sample_tokens`` before it on
the device plane. Around a shape's first use on the serving path,
``.compile``. With
``RAY_TPU_ENABLE_TRACING`` a finished request also leaves ``engine.queued``,
``engine.prefill`` and ``engine.decode`` spans (``request_id``; the last
also ``tokens`` and ``max_gap_ms``, the longest gap between two of its
tokens: WHICH request stalled) under the span that called ``add_request``
(``/api/timeline``). An operator's guide is in ``ray_tpu/serve/README.md``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import logging
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu.llm import kinds, prefill_shapes
from ray_tpu.llm.config import EngineConfig, LLMConfig, SamplingParams
from ray_tpu.llm.kinds import KINDS
from ray_tpu.llm.tokenizer import get_tokenizer
from ray_tpu.util import goodput, tracing

logger = logging.getLogger(__name__)

# the rungs of the ladder ``itl_over_<n>ms``: a gap between two tokens of a
# request is counted on every rung it is strictly longer than
_ITL_RUNGS_MS = (25, 50, 100, 200, 400, 800)
_ITL_RUNGS_NS = np.array(_ITL_RUNGS_MS, np.int64) * 1_000_000
_ITL_KEYS = tuple(f"itl_over_{n}ms" for n in _ITL_RUNGS_MS)
# a read stalled if it waited longer than this for EACH model call it stood
# behind: the longest program of any benchmark cell, a ``[1, 16384]`` prefill
# call, takes 359 ms on the chip (PERF.md section 6, PR 50)
_STALL_MS = 1000

# rows of a prefill call that holds more than one request: a group of three
# takes the four with one row of padding
_ROW_BUCKETS = (2, 4)
# the positions a call may pad beyond its requests' own length buckets for
# every call it saves. A call costs a fixed part (every held weight read
# once) and a part that goes with its padded positions; sharing saves the
# first and pays the second for what it pads. On the chip a one-row call of
# cell 9's model takes 12.8 / 15.3 / 18.5 / 26.7 / 39.6 ms at 128 / 256 / 512
# / 1,024 / 2,048 positions (tests/test_chip_lfm2.py, PR 41): 10.5 ms fixed,
# 0.013-0.016 ms a position, so 700-800 padded positions cost what one shared
# call saves. The same division of the ledger's call times (PR 40) by the
# weights' bytes gives about 600 in cell 3, 800-850 in cells 6 and 8 and 270
# in cell 7: a dense model stands at the chip's 240 operations a byte, a
# sparse one above it by its total over its active parameters. 512 keeps a
# margin in every cell but 7, whose traffic forms no groups. A fraction of S
# instead would let a 4,096 bucket take a 2,048 along: 2,048 positions padded
# for 800 saved.
_PAD_TOKENS = 512

# A prefill call carries the decode step of its phase (``model_runner.prefill``'s
# ``riders``) where its own rows, ``R x S``, are at most this many a slot. What
# a carried step saves is fixed (its read of the experts, the dense weights and
# the head); what it adds grows with the call (both sides' rows are laid end to
# end and taken apart around every mixer) and every carrying program traces a
# step side of its own at its first use, on the serving path. On the chip (PR
# 42, PERF.md section 6): cell 9 (128 slots, calls of 128-2,048 positions in
# 14-44 ms, a step of 16.4) gains 11-12 ms a carried step at every shape; cell
# 8 (32 slots, calls of 256-16,384 positions, 75 ms in the mean, a step of 7.7)
# paid 6 ms a carried step for the 7.7 saved and 2 s of start-up a bucket, seven
# buckets. 16 is every shape of cell 9's (2,048 positions at 128 slots) and the
# two smallest buckets of cell 8's (512 at 32).
_RIDE_ROWS = 16


def row_buckets(S: int, cap: int) -> List[int]:
    """The rows a call at length bucket ``S`` may have beside one: two while
    ``2 x S`` padded tokens fit ``cap``, four while ``4 x S`` do and three
    requests with a row of padding keep within ``_PAD_TOKENS`` a call saved
    (four requests of one bucket over that are two calls of two)."""
    # R rows hold at least R // 2 + 1 requests (fewer take the row bucket
    # below), which saves R // 2 calls and leaves R // 2 - 1 rows of padding
    return [R for R in _ROW_BUCKETS
            if R * S <= cap and (R // 2 - 1) * S <= _PAD_TOKENS * (R // 2)]


def prefill_groups(buckets: Sequence[int], cap: int,
                   ready: Callable[[int, int], bool] = lambda R, S: True
                   ) -> List[Tuple[int, int, List[int]]]:
    """Which prefill calls the requests admitted in one step form. ``buckets``:
    each request's own length bucket, in admission order. Returns ``(R, S,
    members)`` a call: its rows, its length bucket, and which requests (as
    indices into ``buckets``) fill its first ``len(members)`` rows; every
    request is in exactly one. The longest request left leads a call at its
    own bucket ``S`` and takes the next longest with it, as many as (1) ``R``,
    their number rounded up to a row bucket of 2 or 4, is one of
    ``row_buckets(S, cap)``: ``R x S`` padded tokens do not pass ``cap``, the
    largest one-row call the deployment runs anyway (what must fit the chip);
    (2) the call pads no more than ``_PAD_TOKENS`` positions beyond its
    members' own buckets for every call it saves (a padded position computes
    for nothing, and enough of them cost what the shared read of the weights
    saves); (3) ``[R, S]`` is a shape that is ``ready``. A request with no
    such partner is the ``[1, S]`` call it always was."""
    left = sorted(range(len(buckets)), key=lambda i: -buckets[i])
    calls = []
    while left:
        S = buckets[left[0]]
        rows, n = 1, 1
        allowed = row_buckets(S, cap)
        for take in range(min(_ROW_BUCKETS[-1], len(left)), 1, -1):
            R = min(R for R in _ROW_BUCKETS if R >= take)
            padded = R * S - sum(buckets[i] for i in left[:take])
            if (R in allowed and padded <= _PAD_TOKENS * (take - 1)
                    and ready(R, S)):
                rows, n = R, take
                break
        calls.append((rows, S, left[:n]))
        left = left[n:]
    return calls


@dataclasses.dataclass
class _Request:
    request_id: str
    prompt_tokens: List[int]  # original prompt (never mutated)
    params: SamplingParams
    generated: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    pages: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    finish_reason: Optional[str] = None
    # tokens the device was asked for and the host has not read yet
    in_flight: int = 0
    # perf_counter seconds; 0.0 = not yet
    t_added: float = dataclasses.field(default_factory=time.perf_counter)
    t_admitted: float = 0.0
    t_first_token: float = 0.0
    # perf_counter_ns at which its newest token reached the host (0 = none
    # yet), and the longest gap between two of its tokens so far
    t_last_token_ns: int = 0
    max_gap_ns: int = 0
    # tracing.current_context() of add_request's caller
    trace_ctx: Optional[Tuple[str, str]] = None

    @property
    def cache_tokens(self) -> List[int]:
        """Tokens re-prefilled on (re)admission: prompt + anything already
        generated before a preemption (vLLM's recompute preemption, without
        dropping emitted tokens from the output)."""
        return self.prompt_tokens + self.generated


@dataclasses.dataclass
class _Unread:
    """One sampler call whose tokens the host has not read."""
    tokens: Any  # [B] int32 on the device, its copy to the host started
    rows: List[Tuple[_Request, int]]  # whose token sits at which slot
    kind: str  # "prefill" (a phase's first tokens) or "decode" (a step's)
    # model calls it waits behind: prefill calls (of one or more rows each),
    # or 1 decode step
    calls: int
    bucket: int  # the largest of the prefill calls' length buckets; 0 = decode
    sent_ns: int  # perf_counter_ns at its dispatch
    moe_load: Any = None  # a decode step's routing, outside the donated cache
    # a prefill phase's positions, as the device computes them (the ``R x S``
    # of its calls) and as the prompts hold them; 0 for a decode step
    positions: int = 0
    real_positions: int = 0
    compiles: int = 0  # ``metrics["compiles"]`` at its dispatch


@dataclasses.dataclass
class RequestOutput:
    request_id: str
    token_ids: List[int]
    finished: bool
    finish_reason: Optional[str]
    text: Optional[str] = None


class JaxLLMEngine:
    """Continuous-batching engine over the paged-KV model runner: one
    ``step()`` dispatches a step and returns the outputs of the one before
    (module docstring)."""

    def __init__(self, config: LLMConfig, params: Any = None, seed: int = 0):
        import jax

        from ray_tpu.llm import model_runner

        self.config = config
        self.ecfg: EngineConfig = config.engine_config
        self.mcfg = config.transformer_config()
        expect = (self.ecfg.expect_experts, self.ecfg.expect_routed_experts
                  or self.ecfg.expect_experts)
        if (self.mcfg.n_experts_held, self.mcfg.n_experts) != expect:
            raise ValueError(
                f"the deployment expects {expect[0]} experts a layer of "
                f"{expect[1]} routed, the model holds "
                f"{self.mcfg.n_experts_held} of {self.mcfg.n_experts}")
        if self.mcfg.zero_experts != self.ecfg.expect_zero_experts:
            raise ValueError(
                f"the deployment expects {self.ecfg.expect_zero_experts} "
                f"zero-compute experts among the router's outputs, the model "
                f"has {self.mcfg.zero_experts}")
        if self.mcfg.kv_latent_rank != self.ecfg.expect_latent_rank:
            raise ValueError(
                f"the deployment expects a latent cache of rank "
                f"{self.ecfg.expect_latent_rank}, the model has "
                f"{self.mcfg.kv_latent_rank}")
        # the host's side of the model's kinds of layer, what each counts
        of = kinds.of(self.mcfg)
        self._kinds: Dict[str, kinds.Host] = {
            kind: KINDS[kind].Host(self.mcfg, of.count(kind),
                                   self.ecfg.page_size)
            for kind in dict.fromkeys(of)}
        # has it a recurrent kind that counts the slots a step moves
        self._counts_slots = any(
            KINDS[kind].recurrent and KINDS[kind].counters
            for kind in self._kinds)
        state_layers = sum(host.layers for kind, host in self._kinds.items()
                           if KINDS[kind].recurrent)
        if state_layers != self.ecfg.expect_state_layers:
            raise ValueError(
                f"the deployment expects {self.ecfg.expect_state_layers} "
                f"layers with recurrent state, the model has {state_layers}")
        if self.mcfg.conv_taps != self.ecfg.expect_conv_taps:
            raise ValueError(
                f"the deployment expects short convolutions of "
                f"{self.ecfg.expect_conv_taps} taps, the model's have "
                f"{self.mcfg.conv_taps}")
        if self.mcfg.ssm_heads != self.ecfg.expect_ssm_heads:
            raise ValueError(
                f"the deployment expects Mamba-2 layers of "
                f"{self.ecfg.expect_ssm_heads} heads, the model's have "
                f"{self.mcfg.ssm_heads}")
        if self.mcfg.kda_heads != self.ecfg.expect_kda_heads:
            raise ValueError(
                f"the deployment expects delta-rule layers of "
                f"{self.ecfg.expect_kda_heads} heads, the model's have "
                f"{self.mcfg.kda_heads}")
        retention_heads = self.mcfg.n_kv_heads * bool(
            self.mcfg.retention_degree)
        if retention_heads != self.ecfg.expect_retention_heads:
            raise ValueError(
                f"the deployment expects power-retention layers of "
                f"{self.ecfg.expect_retention_heads} key/value heads, the "
                f"model's have {retention_heads}")
        if self.mcfg.ssm_inner_norms != self.ecfg.expect_ssm_inner_norms:
            raise ValueError(
                f"the deployment expects Mamba-1 layers with inner norms: "
                f"{self.ecfg.expect_ssm_inner_norms}, the model's have them: "
                f"{self.mcfg.ssm_inner_norms}")
        self.tokenizer = get_tokenizer(config.tokenizer)
        self._mr = model_runner
        self._jax = jax

        if params is not None:
            self.params = params
        elif config.checkpoint_path:
            self.params = _load_params(config.checkpoint_path)
        else:
            self.params = self._init_random_params(seed)

        e = self.ecfg
        # what the programs hand round (the cache, the logits buffer, the
        # newest tokens) is committed to its device from the start: a program
        # of several rows hands its outputs back committed
        # (llm/prefill_shapes.py), jit keys its programs by that, and none
        # may meet this state both ways (the second would be a compile in the
        # middle of serving)
        def pinned(tree):
            return jax.tree.map(lambda x: jax.device_put(x, x.sharding), tree)

        self.cache = pinned(model_runner.init_cache(
            self.mcfg, e.num_pages, e.page_size, e.max_num_seqs))
        B, MP = e.max_num_seqs, e.pages_per_seq
        # the most padded tokens a call of several rows may hold
        self._group_cap = self._prefill_bucket(e.max_model_len)
        self._block_tables = np.zeros((B, MP), np.int32)
        self._seq_lens = np.zeros(B, np.int32)
        self._active = np.zeros(B, bool)
        # every slot's newest token, on the device: what the next decode step
        # reads, whether or not the host has seen it yet
        self._tokens = pinned(jax.numpy.zeros(B, jax.numpy.int32))
        # sampler calls dispatched and not yet read, oldest first
        self._unread: collections.deque[_Unread] = collections.deque()
        # what was emitted since step() last returned
        self._outputs: List[RequestOutput] = []
        # how many of the oldest unread sampler calls an EARLIER step()
        # dispatched: what the call under way hides behind, and reads last
        self._earlier = 0
        self._temps = np.zeros(B, np.float32)
        self._top_ks = np.zeros(B, np.int32)
        self._top_ps = np.ones(B, np.float32)
        self._seeds = np.full(B, -1, np.int32)  # -1 = engine-global stream
        # _up's kept copies: name -> (the host's array as sent, the device's)
        self._kept: Dict[str, tuple] = {}
        # where a prefill phase gathers its calls' logits, by slot, for the
        # one sampler call; rows of slots not admitted in a phase are stale
        # and their samples unread
        self._prefill_logits = pinned(jax.numpy.asarray(
            np.zeros((B, self.mcfg.vocab_size), np.float32)))
        # can a prefill call of this model carry a decode step's rows, and
        # the [1, S] buckets where the compiler refused that program (why)
        self._rides = model_runner.rides(self.mcfg)
        self.plain_buckets: Dict[int, str] = {}
        # calls of several rows: their programs, made off the serving path
        self._row_shapes = prefill_shapes.RowShapes(
            self.mcfg, self.params, self.cache, self._prefill_logits, MP,
            self._row_shape_ready, self._carries)
        self._slots: List[Optional[_Request]] = [None] * B
        self._free_pages = collections.deque(range(1, e.num_pages))
        self._waiting: collections.deque[_Request] = collections.deque()
        # why the newest admission stopped: "queue", "slots" or "pages"
        # (_try_admit; a preemption afterwards makes it "pages")
        self._stopped = "queue"
        self._requests: Dict[str, _Request] = {}
        self._rng = jax.random.PRNGKey(seed)
        self._compile_watch = goodput.CompileWatch()
        # perf_counter_ns at the end of the last step() that left work
        self._step_ended_ns: Optional[int] = None
        # perf_counter_ns at the end of the newest phase (_phase), and of the
        # newest blocking read: where the next read's wait starts
        self._phase_ended_ns = 0
        self._read_ended_ns = 0
        # see the module docstring; snapshots are subtracted key by key, so
        # every key is here from the start and none ever decreases
        self.metrics = {
            "prefill_tokens": 0, "decode_steps": 0, "riding_steps": 0,
            "generated_tokens": 0,
            "preempted": 0, "steps": 0, "prefill_steps": 0, "admitted": 0,
            "prefill_calls": 0, "prefill_batch_tokens": 0, "compiles": 0,
            "prefill_shapes_wanted": 0, "prefill_shapes_ready": 0,
            "step_ms": 0.0, "host_ms": 0.0, "readback_ms": 0.0,
            "admit_ms": 0.0, "prefill_dispatch_ms": 0.0,
            "decode_dispatch_ms": 0.0, "sample_dispatch_ms": 0.0,
            "emit_ms": 0.0, "between_steps_ms": 0.0,
            "queue_wait_ms": 0.0, "ttft_ms": 0.0,
            "overlapped_steps": 0, "dropped_tokens": 0,
            "sample_calls": 0, "sample_greedy_calls": 0,
            "prefill_phase_ms": 0.0, "decode_phase_ms": 0.0, "phase_ms": 0.0,
            "prefill_phase_calls": 0, "decode_phase_calls": 0,
            "prefill_phase_positions": 0, "prefill_phase_real_positions": 0,
            "stalled_reads": 0, "stalled_read_ms": 0.0,
            "slot_steps": 0, "slot_steps_live": 0, "slot_steps_starved": 0,
            "slot_steps_page_blocked": 0, "slot_steps_prefilling": 0,
            "itl_ms": 0.0, "itl_tokens": 0, **dict.fromkeys(_ITL_KEYS, 0),
            "moe_decode_layer_steps": 0, "moe_decode_assignments": 0,
            "moe_decode_experts_touched": 0, "moe_decode_max_load": 0,
            "moe_decode_routed_assignments": 0,
            "moe_decode_zero_assignments": 0,
            # what the kinds of layer count, every kind's whatever the model
            **dict.fromkeys((name for kind in KINDS.values()
                             for name in kind.counters), 0),
            "flash_q_blocks": 0, "flash_q_blocks_skipped": 0}
        # span attribute of decode_dispatch; none for a dense model
        self._experts_attr: Dict[str, float] = {}

    # -- params ------------------------------------------------------------

    def _init_random_params(self, seed: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.transformer import Transformer

        import flax.linen as nn

        model = Transformer(self.mcfg)
        toks = jnp.zeros((1, min(8, self.mcfg.max_seq_len)), jnp.int32)
        return nn.meta.unbox(model.init(jax.random.PRNGKey(seed), toks))

    # -- request lifecycle -------------------------------------------------

    def add_request(self, request_id: str, prompt: Any,
                    params: Optional[SamplingParams] = None) -> None:
        params = params or SamplingParams()
        if isinstance(prompt, str):
            tokens = self.tokenizer.encode(prompt)
        else:
            tokens = list(prompt)
        limit = self.ecfg.max_model_len - 1
        if len(tokens) > limit:
            tokens = tokens[-limit:]
        # reject requests the page pool can never satisfy (even alone) —
        # otherwise admission would livelock retrying forever
        final_len = min(self.ecfg.max_model_len,
                        len(tokens) + params.max_tokens)
        need_total = math.ceil(final_len / self.ecfg.page_size)
        if need_total > self.ecfg.num_pages - 1:
            raise ValueError(
                f"request needs {need_total} KV pages but the engine has "
                f"{self.ecfg.num_pages - 1}; raise num_pages or lower "
                f"max_tokens/prompt length")
        req = _Request(request_id, tokens, params,
                       trace_ctx=tracing.current_context())
        self._requests[request_id] = req
        self._waiting.append(req)

    def abort_request(self, request_id: str) -> None:
        if request_id not in self._requests:
            return
        self._drain()  # its last token may be the one in flight
        req = self._requests.pop(request_id, None)
        if req is None:
            return
        if req.slot >= 0:
            self._release(req)
        else:
            try:
                self._waiting.remove(req)
            except ValueError:
                pass

    def has_unfinished(self) -> bool:
        """Is there anything a further ``step()`` would run, read or hand
        over? True while a dispatched step's tokens are unread."""
        return bool(self._waiting or self._unread or self._outputs) \
            or bool(self._active.any())

    def num_waiting(self) -> int:
        return len(self._waiting)

    def num_active(self) -> int:
        return int(self._active.sum())

    # -- scheduling internals ----------------------------------------------

    def _release(self, req: _Request) -> None:
        self._free_pages.extend(req.pages)
        req.pages = []
        if req.slot >= 0:
            self._active[req.slot] = False
            self._slots[req.slot] = None
            self._seq_lens[req.slot] = 0
            self._block_tables[req.slot, :] = 0
            # an empty slot samples nothing: left at a positive temperature
            # it would hold every later batch on the sampler's shortlist
            self._temps[req.slot] = 0.0
            req.slot = -1

    def _try_admit(self) -> Tuple[List[_Request], int]:
        """Waiting requests into free slots, oldest first, until nobody
        waits (``_stopped`` = "queue"), no slot is free ("slots") or the
        queue's head lacks pages ("pages"). Returns the admitted requests
        and how many slots are still free."""
        admitted = []
        free_slots = [i for i, s in enumerate(self._slots) if s is None]
        while self._waiting and free_slots:
            req = self._waiting[0]
            need = max(1, math.ceil(len(req.cache_tokens)
                                    / self.ecfg.page_size))
            if len(self._free_pages) < need:
                self._stopped = "pages"
                break
            self._waiting.popleft()
            req.slot = free_slots.pop(0)
            req.pages = [self._free_pages.popleft() for _ in range(need)]
            self._slots[req.slot] = req
            row = self._block_tables[req.slot]
            row[:] = 0
            row[:need] = req.pages
            self._seq_lens[req.slot] = len(req.cache_tokens)
            self._set_sampling(req)
            admitted.append(req)
        else:
            self._stopped = "slots" if self._waiting else "queue"
        return admitted, len(free_slots)

    def _set_sampling(self, req: _Request) -> None:
        p = req.params
        self._temps[req.slot] = p.temperature
        self._top_ks[req.slot] = p.top_k
        self._top_ps[req.slot] = p.top_p
        self._seeds[req.slot] = -1 if p.seed is None else p.seed

    def _prefill_bucket(self, n: int) -> int:
        b = self.ecfg.prefill_bucket_min
        while b < n:
            b *= 2
        return min(b, self.ecfg.max_model_len)

    def _ensure_page(self, req: _Request) -> bool:
        """Allocate the page for the next token position if needed."""
        pos = int(self._seq_lens[req.slot])
        need = pos // self.ecfg.page_size + 1
        if need <= len(req.pages):
            return True
        if not self._free_pages:
            return False
        page = self._free_pages.popleft()
        req.pages.append(page)
        self._block_tables[req.slot, need - 1] = page
        return True

    def _grow_pages(self) -> None:
        """Every active slot gets the page of its next position; where the
        pool is empty the request goes back to waiting. (A slot admitted in
        this step is active once its prefill call is dispatched: before a
        decode step of its own, not before the call its first token rides
        with the others' next.)"""
        for slot in np.flatnonzero(self._active):
            req = self._slots[slot]
            if req is None or self._ensure_page(req):
                continue
            if self._unread:
                # a preempted request is prefilled again from ALL its tokens;
                # and what is unread may end a request (this one too) and
                # bring its pages back
                self._drain()
                if self._slots[slot] is not req or self._ensure_page(req):
                    continue
            self.metrics["preempted"] += 1
            self._requeue(req)
            self._stopped = "pages"  # the pool sets this step's batch now

    def _next_rng(self):
        self._rng, sub = self._mr.split_key(self._rng)
        return sub

    def _up(self, a: np.ndarray, keep: str = ""):
        """One of the host's own arrays on the device as it reads NOW. The
        host goes on writing them while the programs that take them are still
        queued, and a transfer may read the buffer it was given after it
        returns (the CPU backend aliases it): it gets a copy nobody writes.
        Under ``keep`` the device's copy is kept by that name and handed out
        again while the host's array reads the same: the block tables, the
        active mask and the sampling parameters change with an admission, a
        release or a new page, not with a step, and a transfer costs the
        host more than the comparison."""
        if keep:
            kept = self._kept.get(keep)
            if kept is not None and np.array_equal(kept[0], a):
                return kept[1]
        host = a.copy()
        dev = self._jax.numpy.asarray(host)
        if keep:
            self._kept[keep] = (host, dev)
        return dev

    def _sample(self, logits):
        """One sampler call over every slot's row of ``logits``. The tokens
        stay on the device; their copy to the host starts and nothing waits
        for it (``_read`` does, a step later)."""
        # the rule ``sample_tokens`` applies on the device to the same array:
        # with no positive temperature it takes its argmax branch
        greedy = not (self._temps > 0).any()
        self.metrics["sample_calls"] += 1
        self.metrics["sample_greedy_calls"] += greedy
        with self._phase("sample_dispatch", greedy=greedy):
            # a seeded request's position in its stream: the tokens it was
            # given, read or not (the engine's own stream takes no position)
            steps = np.zeros(len(self._slots), np.int32)
            for i in np.flatnonzero(self._seeds >= 0):
                s = self._slots[i]
                if s is not None:
                    steps[i] = len(s.generated) + s.in_flight
            toks = self._mr.sample_tokens(
                logits, self._next_rng(), self._up(self._temps, "temps"),
                self._up(self._top_ks, "top_ks"),
                self._up(self._top_ps, "top_ps"),
                self._up(self._seeds, "seeds"), self._up(steps, "steps"),
                max_top_k=self.ecfg.max_top_k)
            toks.copy_to_host_async()
        return toks

    # -- the step ----------------------------------------------------------

    @contextlib.contextmanager
    def _phase(self, name: str, **attrs):
        """One phase of ``step()``: the span ``engine.<name>`` on the
        profiler's clock (yielded: what a phase learns on its way it adds
        with ``set_metadata``), its host time in ``metrics[<name>_ms]`` and
        its end in ``_phase_ended_ns``."""
        t0 = time.perf_counter_ns()
        try:
            with tracing.annotate("engine." + name, **attrs) as span:
                yield span
        finally:
            self._phase_ended_ns = t1 = time.perf_counter_ns()
            self.metrics[name + "_ms"] += (t1 - t0) / 1e6

    @contextlib.contextmanager
    def _first_use(self, program: str, bucket: int = 0):
        """Around a model program's call: the span ``engine.compile`` when
        this engine has not yet called it at this shape (jit traces and
        compiles, or reads its cache, before it returns). A prefill bucket's
        first use also asks for the bucket's calls of several rows, which
        are made off the serving path (``prefill_shapes.RowShapes``)."""
        if self._compile_watch.observe(program, (bucket,)) is None:
            yield False
            return
        self.metrics["compiles"] += 1
        with tracing.annotate("engine.compile", program=program,
                              bucket=bucket):
            yield True
        if program == "prefill":
            shapes = [(R, bucket)
                      for R in row_buckets(bucket, self._group_cap)]
            self.metrics["prefill_shapes_wanted"] += len(shapes)
            self._row_shapes.want(shapes)

    def _row_shape_ready(self) -> None:
        # from RowShapes' threads, one at a time; they write this key alone
        self.metrics["prefill_shapes_ready"] += 1

    def step(self, decode: bool = True) -> List[RequestOutput]:
        """One scheduling step: dispatch this step's programs from what the
        host knows, THEN block on the tokens of the step before, emit them
        and return that step's outputs (a request's output arrives one call
        after the call that computed it; ``has_unfinished()`` stays true
        until it has). ``decode=False`` runs only the admit+prefill phase and
        reads its own tokens before it returns: the prefill side of PD
        disaggregation (reference serving pattern:
        serving_patterns/prefill_decode/pd_server.py:31)."""
        m = self.metrics
        t0 = time.perf_counter_ns()
        if self._step_ended_ns is not None:
            m["between_steps_ms"] += (t0 - self._step_ended_ns) / 1e6
        readback0 = m["readback_ms"]
        programs0 = m["prefill_steps"] + m["decode_steps"]
        self._earlier = len(self._unread)
        with tracing.annotate("engine.step"):
            overlapped = self._step(decode)
        outputs, self._outputs = self._outputs, []
        t1 = time.perf_counter_ns()
        step_ms = (t1 - t0) / 1e6
        if m["prefill_steps"] + m["decode_steps"] > programs0:
            m["steps"] += 1
            m["overlapped_steps"] += overlapped
        m["step_ms"] += step_ms
        m["host_ms"] += step_ms - (m["readback_ms"] - readback0)
        self._step_ended_ns = t1 if self.has_unfinished() else None
        return outputs

    def _step(self, decode: bool) -> bool:
        """Returns whether a program was dispatched behind an unread step."""
        import jax.numpy as jnp

        mr, m = self._mr, self.metrics
        overlapped = False
        if not decode:
            self._drain()

        # 1) admit + prefill: the admitted requests one row each, alone at
        # their own length bucket or several to a call at the longest's
        # (prefill_groups), the calls back to back (the donated cache chains
        # them; nothing is read in between). Each call's logits land in the
        # [B, vocab] buffer at its requests' slots, so one sampler call serves
        # the phase however many were admitted. Its tokens join the others on
        # the device. Where the phase's first call carries a decode step's
        # rows (_carries: such calls are put first), the slots that were
        # decoding before this admission ride it: its [B, vocab] of decode
        # logits IS the buffer the phase's prompts' rows are placed in, the
        # one sampler call samples both, and 2) is not run in this step (the
        # requests admitted here take their second token in the next).
        calls, ride = [], False
        with self._phase("admit") as span:
            admitted, free_slots = self._try_admit()
            now = time.perf_counter()
            for r in admitted:
                if not r.t_admitted:  # not a re-admission after preemption
                    r.t_admitted = now
                    m["queue_wait_ms"] += (now - r.t_added) * 1e3
            if admitted:
                slots = [r.slot for r in admitted]
                calls = prefill_groups(
                    [self._prefill_bucket(n) for n in self._seq_lens[slots]],
                    self._group_cap,
                    lambda R, S: (R, S) in self._row_shapes.ready)
                # a call that carries goes first: it takes the step
                calls.sort(key=lambda c: not self._carries(c[0], c[1]))
                if (decode and self._active.any()
                        and self._carries(*calls[0][:2])):
                    # the decoding slots' next pages, before their step is
                    # dispatched: may read what is in flight, may preempt
                    self._grow_pages()
                    ride = bool(self._active.any())
            # why a row of this step's batch runs empty, on the device's clock
            span.set_metadata(
                waiting=len(self._waiting), free_slots=free_slots,
                free_pages=len(self._free_pages), stopped=self._stopped)
        riders: List[_Request] = []
        if admitted:
            overlapped = self._earlier > 0
            rows = sum(R for R, _, _ in calls)
            bucket = max(S for _, S, _ in calls)
            with self._phase("prefill_dispatch", bucket=bucket,
                             admitted=len(admitted), calls=len(calls),
                             rows=rows,
                             riding=int(self._active.sum()) if ride else 0):
                for n, (R, S, members) in enumerate(calls):
                    if self._prefill_call(R, S, [slots[i] for i in members],
                                          carry=ride and n == 0):
                        riders = [self._slots[i]
                                  for i in np.flatnonzero(self._active)]
            firsts = self._sample(self._prefill_logits)
            if riders:
                # every row a slot will read is new: a decode step's, or an
                # admitted request's first
                self._tokens = firsts
                self._count_decode_reads(riding=True)
                self._count_slot_steps(prefilling=len(admitted))
                m["decode_steps"] += 1
                m["riding_steps"] += 1
                self._seq_lens[self._active] += 1
            else:
                self._tokens = mr.select_rows(
                    jnp.asarray(np.isin(np.arange(len(self._slots)), slots)),
                    firsts, self._tokens)
            real = int(self._seq_lens[slots].sum())
            positions = sum(R * S for R, S, _ in calls)
            m["prefill_steps"] += 1
            m["admitted"] += len(admitted)
            m["prefill_calls"] += len(calls)
            m["prefill_tokens"] += real
            m["prefill_batch_tokens"] += positions
            self._active[slots] = True
            self._sent(firsts, admitted + riders, "prefill", len(calls),
                       bucket, positions=positions, real_positions=real)

        # 2) one decode step for all active slots, on the tokens the device
        # holds: the host advances what does not depend on a token's value
        if decode and not riders and self._active.any():
            attrs = dict(self._experts_attr, overlapped=int(self._earlier > 0))
            if self.mcfg.kv_latent_rank or self.mcfg.layer_kinds:
                # positions the step attends over, through the block tables
                attrs["live_tokens"] = int(
                    (self._seq_lens[self._active] + 1).sum())
            if self._counts_slots:
                # live slots whose state the step moves
                attrs["state_slots"] = int(self._active.sum())
            load = None
            with self._phase("decode_dispatch", **attrs):
                self._grow_pages()
                decoding = bool(self._active.any())
                if decoding:
                    overlapped = overlapped or self._earlier > 0
                    self._count_decode_reads()
                    self._count_slot_steps()
                    with self._first_use("decode"):
                        logits, self.cache = mr.decode_step(
                            self.params, self.mcfg, self.cache,
                            *self._decode_rows(self._active))
                    if self.cache.moe_load is not None:
                        # a copy outside the cache: the next call donates
                        # the cache before the host reads this one's routing
                        load = jnp.copy(self.cache.moe_load)
                        load.copy_to_host_async()
            if decoding:
                self._tokens = self._sample(logits)
                m["decode_steps"] += 1
                self._seq_lens[self._active] += 1
                self._sent(self._tokens, [
                    self._slots[i] for i in np.flatnonzero(self._active)],
                    "decode", 1, moe_load=load)

        # 3) the steps before this one: the device has this call's programs
        # queued behind them, so the read, the emit and the caller's way back
        # here cost it nothing
        while self._unread and (self._earlier or not decode):
            self._read()
        return overlapped

    def _carries(self, R: int, S: int) -> bool:
        """Is the prefill program at ``[R, S]`` the one that carries a decode
        step? Where the model's can (``model_runner.rides``), the call's own
        rows do not dwarf the step's (``_RIDE_ROWS``) and the compiler took
        the program (``plain_buckets``; a shape of several rows it refused is
        never ready, so never asked about)."""
        return (self._rides and R * S <= _RIDE_ROWS * len(self._slots)
                and (R > 1 or S not in self.plain_buckets))

    def _prefill_call(self, R: int, S: int, slots: List[int],
                      carry: bool = False) -> bool:
        """One prefill program call: the requests in ``slots`` in its first
        rows, padding rows behind them (length 0, a block table of zeros and
        the slot past the last, so that whatever they write is dropped or
        lands on the scratch page), the logits of the real rows into
        ``_prefill_logits`` at their slots. ``carry``: the decoding slots'
        step rides this call, the first of its phase, if its program carries
        one: then (True is returned and) the step's ``[B, vocab]`` logits
        become the buffer. A program that carries a step is called so whether
        or not one rides (with no slot marked active its decode side attends
        over the scratch page and reaches no expert): a shape has ONE prefill
        program. Which shapes carry, ``_carries`` says; a ``[1, S]`` whose
        carrying program the compiler finds no room for at its first use is
        the plain one from then on (``plain_buckets``), and its phase runs
        ``decode_step`` after it."""
        import jax
        import jax.numpy as jnp

        mr, n = self._mr, len(slots)
        toks = np.zeros((R, S), np.int32)
        lens = np.zeros(R, np.int32)
        tables = np.zeros((R, self._block_tables.shape[1]), np.int32)
        where = np.full(R, len(self._slots), np.int32)
        lens[:n], tables[:n], where[:n] = (
            self._seq_lens[slots], self._block_tables[slots], slots)
        for i, slot in enumerate(slots):
            toks[i, :lens[i]] = self._slots[slot].cache_tokens
        where = jnp.asarray(where)
        rows = [jnp.asarray(toks), jnp.asarray(lens), jnp.asarray(tables)]
        if mr.attends(self.mcfg):
            # the grid steps of flash_fwd a head, and those it passes over (a
            # model none of whose layers attends runs no flash_fwd)
            from ray_tpu.ops.attention import q_blocks

            blocks, skipped = q_blocks(S, lens)
            self.metrics["flash_q_blocks"] += blocks
            self.metrics["flash_q_blocks_skipped"] += skipped
        if self.mcfg.layer_kinds:  # a model that keeps state by slot is told
            rows.append(where)
        carries = self._carries(R, S)
        carry = carry and carries
        if carries:
            rows.append(self._decode_rows(
                self._active if carry else np.zeros_like(self._active)))
        if R == 1:  # jit's own, compiled at the bucket's first use
            prefill = functools.partial(mr.prefill, self.params, self.mcfg)
            place, first_use = mr.place_rows, self._first_use("prefill", S)
        else:  # made off the serving path, the model compiled in
            prefill = functools.partial(self._row_shapes.ready[(R, S)],
                                        self.params)
            place = self._row_shapes.place[R]
            first_use = contextlib.nullcontext(False)
        with first_use as first:
            try:
                logits, cache = prefill(self.cache, *rows)
            except jax.errors.JaxRuntimeError as e:
                # the compiler's own refusal, before anything was donated
                if not (first and carries and "RESOURCE_EXHAUSTED" in str(e)
                        ) or jax.tree.leaves(self.cache)[0].is_deleted():
                    raise
                self.plain_buckets[S] = str(e)
                logger.warning(
                    "prefill [1, %d] carries no decode step: %s", S,
                    self.plain_buckets[S][:300])
                carries = carry = False
                logits, cache = prefill(self.cache, *rows[:-1])
            self.cache, buffer = cache, self._prefill_logits
            for host in self._kinds.values():
                host.count_prompt(self.metrics, S, lens, carries)
            if carries:
                logits, step_logits = logits
                if carry:
                    buffer = step_logits
            self._prefill_logits = place(buffer, logits, where)
        return carry

    def _decode_rows(self, active: np.ndarray) -> tuple:
        """The operands of a decode step after the cache, for the slots
        ``active`` marks: ``decode_step``'s own, or a prefill call's
        ``riders``."""
        return (self._tokens, self._up(self._seq_lens),
                self._up(self._block_tables, "tables"),
                self._up(active, "active" if active.any() else "idle"))

    def _count_decode_reads(self, riding: bool = False) -> None:
        """What the decode step being dispatched (alone, or ``riding`` a
        prefill call) reads and moves, into the counters of the model's
        kinds of layer."""
        lens = self._seq_lens[self._active]
        for host in self._kinds.values():
            host.count_step(self.metrics, len(self._slots), lens, riding)

    def _count_slot_steps(self, prefilling: int = 0) -> None:
        """Every slot's row of the decode step being dispatched (alone, or
        riding a prefill call) by its state: it decodes (``live``: a token
        that a stop token, read a step late, has already ended is among them,
        ``dropped_tokens`` says how many), or its slot went in this very step
        to one of ``prefilling`` requests whose prompt the carrying call runs
        (their first decode row is the next step's), or it is empty: because
        the admission before it left nobody waiting (``starved``: whoever
        offers the load set this step's batch), else because the page pool
        did (``page_blocked``: the queue's head lacked pages, or every slot
        was held and one was emptied after the admission, which only
        ``_grow_pages`` does, on an empty pool)."""
        m = self.metrics
        slots, live = len(self._slots), int(self._active.sum())
        empty = ("slot_steps_starved" if self._stopped == "queue"
                 else "slot_steps_page_blocked")
        m["slot_steps"] += slots
        m["slot_steps_live"] += live
        m["slot_steps_prefilling"] += prefilling
        m[empty] += slots - live - prefilling

    def _sent(self, tokens, reqs: List[_Request], kind: str, calls: int,
              bucket: int = 0, moe_load=None, positions: int = 0,
              real_positions: int = 0) -> None:
        """A sampler call is on its way with a token for each of ``reqs``,
        behind ``calls`` prefill calls (the largest at ``bucket``, over
        ``positions`` padded and ``real_positions`` real positions) or one
        decode step. A request that ends by length with it ends at a step the
        host can count: its slot and pages are free at once (the device runs
        its programs in order, so whatever is queued behind this call may
        have them)."""
        self._unread.append(_Unread(
            tokens, [(r, r.slot) for r in reqs], kind, calls, bucket,
            time.perf_counter_ns(), moe_load, positions=positions,
            real_positions=real_positions, compiles=self.metrics["compiles"]))
        for r in reqs:
            r.in_flight += 1
            if self._ends_by_length(r, len(r.generated) + r.in_flight):
                self._release(r)

    def _ends_by_length(self, req: _Request, generated: int) -> bool:
        return (generated >= req.params.max_tokens
                or len(req.prompt_tokens) + generated
                >= self.ecfg.max_model_len)

    def _read(self) -> None:
        """Block on the oldest unread sampler call, lay the time since the
        read before it (or since its own dispatch, if that came later) to its
        kind, and emit its tokens as of the moment the read returned."""
        m = self.metrics
        u = self._unread.popleft()
        self._earlier = max(self._earlier - 1, 0)
        with self._phase("readback", kind=u.kind, calls=u.calls,
                         bucket=u.bucket, rows=len(u.rows)):
            toks = np.asarray(u.tokens)  # blocks until the device is done
        now = self._phase_ended_ns
        waited = (now - max(self._read_ended_ns, u.sent_ns)) / 1e6
        self._read_ended_ns = now
        m[u.kind + "_phase_ms"] += waited
        m[u.kind + "_phase_calls"] += u.calls
        m["prefill_phase_positions"] += u.positions  # 0 behind a decode step
        m["prefill_phase_real_positions"] += u.real_positions
        m["phase_ms"] += waited
        if waited > _STALL_MS * u.calls:
            m["stalled_reads"] += 1
            m["stalled_read_ms"] += waited
            logger.warning(
                "stalled read: kind=%s calls=%d bucket=%d rows=%d waited "
                "%.0f ms, %.0f ms after its dispatch; compiled since: %s",
                u.kind, u.calls, u.bucket, len(u.rows), waited,
                (now - u.sent_ns) / 1e6, m["compiles"] > u.compiles)
        with self._phase("emit"):
            if u.moe_load is not None:
                self._count_routing(np.asarray(u.moe_load), len(u.rows))
            # a stop token read since the dispatch ended a request: its slot
            # ran on, for nobody
            live = [req for req, _ in u.rows if not req.finished]
            m["dropped_tokens"] += len(u.rows) - len(live)
            self._count_gaps(live, now)
            for req, slot in u.rows:
                req.in_flight -= 1
                if not req.finished:
                    self._emit(req, int(toks[slot]), now)

    def _count_gaps(self, reqs: List[_Request], now: int) -> None:
        """The gap each of ``reqs`` saw before the token that reached the
        host at ``now`` (perf_counter_ns), into ``itl_ms``, ``itl_tokens``
        and the ladder. A request's first token has no gap; a preempted
        request's gap spans its second prefill."""
        m = self.metrics
        last = np.array([r.t_last_token_ns for r in reqs], np.int64)
        gaps = now - last[last > 0]
        m["itl_tokens"] += len(gaps)
        m["itl_ms"] += float(gaps.sum()) / 1e6
        over = (gaps[:, None] > _ITL_RUNGS_NS).sum(axis=0)
        for key, n in zip(_ITL_KEYS, over.tolist()):
            m[key] += n

    def _drain(self) -> None:
        """Read everything in flight: whoever reads or changes slot state
        from outside a step's own order does this first."""
        while self._unread:
            self._read()

    def discard_in_flight(self) -> None:
        """Forget every dispatched step without reading it: for a caller
        whose ``step()`` raised, since what is unread may be what failed and a
        read of it raises again. The tokens are lost; ``abort_request`` then
        has nothing left to read and cannot raise."""
        for u in self._unread:
            for req, _ in u.rows:
                req.in_flight -= 1
        self._unread.clear()
        self._earlier = 0

    def _count_routing(self, load: np.ndarray, rows: int) -> None:
        """``load`` [expert layers, E]: real rows per expert HELD here in one
        decode step of ``rows`` real rows."""
        m = self.metrics
        if self.mcfg.zero_experts:  # their count rides in the last column
            m["moe_decode_zero_assignments"] += int(load[:, -1].sum())
            load = load[:, :-1]
        touched = int((load > 0).sum())
        held = int(load.sum())
        m["moe_decode_layer_steps"] += load.shape[0]
        m["moe_decode_assignments"] += held
        m["moe_decode_routed_assignments"] += (
            rows * self.mcfg.experts_per_token * load.shape[0])
        m["moe_decode_experts_touched"] += touched
        m["moe_decode_max_load"] += int(load.max(axis=1).sum())
        self._experts_attr = {"experts": touched / load.shape[0],
                              "held": held / load.shape[0]}

    def _requeue(self, req: _Request) -> None:
        """Preempt a running request back to the waiting queue; its KV is
        recomputed from prompt+generated on re-admission (vLLM's recompute
        preemption). ``generated`` is kept so emitted tokens and the
        max_tokens budget survive preemption."""
        self._release(req)
        self._waiting.appendleft(req)

    def _emit(self, req: _Request, token: int, now: int) -> None:
        """``token`` reached the host at ``now`` (perf_counter_ns)."""
        req.generated.append(token)
        self.metrics["generated_tokens"] += 1
        if not req.t_first_token:
            req.t_first_token = now / 1e9
            self.metrics["ttft_ms"] += (req.t_first_token - req.t_added) * 1e3
        if req.t_last_token_ns:
            req.max_gap_ns = max(req.max_gap_ns, now - req.t_last_token_ns)
        req.t_last_token_ns = now
        if (token == self.tokenizer.eos_token_id
                or token in req.params.stop_token_ids):
            req.finished, req.finish_reason = True, "stop"
        elif self._ends_by_length(req, len(req.generated)):
            req.finished, req.finish_reason = True, "length"
        if req.finished:
            self._release(req)  # nothing left to free if it ended by length
            self._requests.pop(req.request_id, None)
            if tracing.enabled():
                self._record_request_spans(req, now / 1e9)
        self._outputs.append(RequestOutput(
            req.request_id, list(req.generated), req.finished,
            req.finish_reason))

    def _record_request_spans(self, req: _Request, now: float) -> None:
        """A request that finished at ``now`` (perf_counter seconds) as three
        spans in the GCS trace table, children of the span that called
        ``add_request``; ``engine.decode`` says how many tokens it got and
        the longest gap between two of them."""
        wall = time.time() - time.perf_counter()  # -> the spans' wall clock
        ids = {}
        if req.trace_ctx is not None:
            ids = {"trace_id": req.trace_ctx[0], "parent_id": req.trace_ctx[1]}
        for name, start, end, attrs in (
                ("engine.queued", req.t_added, req.t_admitted, {}),
                ("engine.prefill", req.t_admitted, req.t_first_token, {}),
                ("engine.decode", req.t_first_token, now,
                 {"tokens": len(req.generated),
                  "max_gap_ms": req.max_gap_ns / 1e6})):
            tracing.record_span(name, start + wall, end + wall,
                                category="llm", request_id=req.request_id,
                                **ids, **attrs)

    # -- PD disaggregation (KV page export / import) -----------------------
    # Reference: serving_patterns/prefill_decode/pd_server.py + the vLLM
    # KV-transfer connectors (engines/vllm/kv_transfer/). The paged layout
    # makes a sequence's KV state a gather of its pages.

    def prefill_only(self, request_id: str, prompt: Any,
                     params: Optional[SamplingParams] = None,
                     max_steps: int = 1000) -> dict:
        """Prefill one request (emitting its first token) and export its KV
        state; the request is then released here — a decode engine imports
        the state and continues without re-prefilling."""
        self.add_request(request_id, prompt, params)
        req = self._requests[request_id]
        for _ in range(max_steps):
            # what the call read of other requests waits for the next step()
            outs = self.step(decode=False)
            self._outputs += [o for o in outs if o.request_id != request_id]
            if req.finished or req.generated:
                break
        else:
            self.abort_request(request_id)
            raise RuntimeError(f"prefill of {request_id} did not get admitted")
        if req.finished:
            # done at prefill (e.g. max_tokens=1): no KV to hand off
            return {"request_id": request_id,
                    "prompt_tokens": list(req.prompt_tokens),
                    "generated": list(req.generated), "seq_len": 0,
                    "finished": True, "finish_reason": req.finish_reason,
                    "params": req.params}
        return self.export_kv(request_id)

    def export_kv(self, request_id: str) -> dict:
        """Gather a live request's KV pages + scheduling state, releasing
        the request locally. The blob is plain numpy: it ships over the
        object plane (or the device-object plane when replicas colocate)."""
        self._refuse_state_by_slot("export_kv")
        self._drain()
        req = self._requests.get(request_id)
        if req is None or req.slot < 0:
            raise KeyError(f"no live request {request_id}")
        pages = np.asarray(req.pages, np.int32)
        state = {
            "request_id": req.request_id,
            "prompt_tokens": list(req.prompt_tokens),
            "generated": list(req.generated),
            "seq_len": int(self._seq_lens[req.slot]),
            "finished": req.finished,
            "finish_reason": req.finish_reason,
            "params": req.params,
        }
        # the paged kinds' states under the kinds' names, each as the cache
        # holds it with the request's pages alone
        for kind in self._page_leaves():
            state[kind] = self._jax.tree.map(
                lambda leaf: np.asarray(leaf[:, pages]), self.cache[kind])
        self.abort_request(request_id)
        return state

    def _page_leaves(self) -> List[str]:
        """The kinds whose state this model's block tables address."""
        return [kind for kind in self.cache.states if KINDS[kind].paged]

    def _refuse_state_by_slot(self, what: str) -> None:
        """A request's state is a gather of its pages only where pages are
        all of it: a model with window rings and recurrent rows is prefilled
        where it decodes (its pages could move, its rings and rows have no
        hand-over yet)."""
        if self.mcfg.layer_kinds:
            raise ValueError(
                f"{what}: model {self.config.model_id!r} keeps window rings "
                f"and recurrent state by slot beside its pages; prefill / "
                f"decode disaggregation does not carry them")

    def add_request_with_kv(self, state: dict) -> None:
        """Admit a prefilled request directly into a decode slot: allocate
        fresh pages, scatter the imported KV into them, and resume decoding
        at the imported position (no re-prefill)."""
        import jax.numpy as jnp

        if state.get("finished"):
            # finished during prefill (e.g. max_tokens=1): nothing to decode
            raise ValueError("request already finished at prefill")
        self._refuse_state_by_slot("add_request_with_kv")
        self._drain()
        free_slots = [i for i, s in enumerate(self._slots) if s is None]
        leaves = self._page_leaves()
        n_pages = self._jax.tree.leaves(state[leaves[0]])[0].shape[1]
        if not free_slots or len(self._free_pages) < n_pages:
            raise RuntimeError("decode engine has no capacity; retry")
        req = _Request(state["request_id"], list(state["prompt_tokens"]),
                       state["params"], trace_ctx=tracing.current_context())
        # queued and prefilled elsewhere: neither wait is this engine's
        req.t_admitted = req.t_first_token = req.t_added
        # its client has the first token: the next one's gap starts here
        req.t_last_token_ns = time.perf_counter_ns()
        req.generated = list(state["generated"])
        req.slot = free_slots[0]
        req.pages = [self._free_pages.popleft() for _ in range(n_pages)]
        pages = jnp.asarray(np.asarray(req.pages, np.int32))
        self.cache = self.cache.replace({
            kind: self._jax.tree.map(
                lambda leaf, new: leaf.at[:, pages].set(jnp.asarray(new)),
                self.cache[kind], state[kind]) for kind in leaves})
        row = self._block_tables[req.slot]
        row[:] = 0
        row[:n_pages] = req.pages
        self._seq_lens[req.slot] = state["seq_len"]
        here = np.arange(len(self._slots)) == req.slot
        self._tokens = self._mr.select_rows(
            jnp.asarray(here), jnp.asarray(here * np.int32(req.generated[-1])),
            self._tokens)
        self._set_sampling(req)
        self._slots[req.slot] = req
        self._active[req.slot] = True
        self._requests[req.request_id] = req

    # -- convenience -------------------------------------------------------

    def generate(self, prompts: List[Any],
                 params: Optional[SamplingParams] = None,
                 decode_text: bool = True) -> List[RequestOutput]:
        """Blocking batch generation; preserves input order."""
        ids = [f"gen-{i}-{time.monotonic_ns()}" for i in range(len(prompts))]
        for rid, prompt in zip(ids, prompts):
            self.add_request(rid, prompt, params)
        done: Dict[str, RequestOutput] = {}
        while self.has_unfinished():
            for out in self.step():
                if out.finished:
                    done[out.request_id] = out
        results = [done[rid] for rid in ids]
        if decode_text:
            for r in results:
                toks = [t for t in r.token_ids
                        if t != self.tokenizer.eos_token_id]
                r.text = self.tokenizer.decode(toks)
        return results


def _load_params(path: str):
    """Engine params from ``path``: a checkpoint-plane store (manifest +
    content-addressed chunks — the format ``save_params`` writes), or the
    legacy single-file ``params.msgpack`` layout."""
    import os

    from ray_tpu.ckpt import CheckpointStore, restore_tree

    if os.path.isdir(path):
        store = CheckpointStore(path, name="llm")
        if store.latest_id() is not None:
            return restore_tree(store)

    import flax.serialization

    fn = path if os.path.isfile(path) else os.path.join(path, "params.msgpack")
    with open(fn, "rb") as f:
        blob = f.read()
    return flax.serialization.msgpack_restore(blob)


def save_params(params: Any, path: str, *, step: int = 0) -> str:
    """Commit engine params through the checkpoint plane: ``path`` becomes
    a checkpoint store (manifest + chunks). Repeated saves of mostly-
    unchanged params (a LoRA refresh, an embedding-only update) dedup to
    the shared chunk pool; a torn save never becomes ``latest``."""
    import os

    import flax.serialization

    from ray_tpu.ckpt import CheckpointStore, save_checkpoint

    state = flax.serialization.to_state_dict(params)
    store = CheckpointStore(path, name="llm")
    manifest = save_checkpoint(store, state, step=step)
    return os.path.join(path, "manifests", f"{manifest.ckpt_id}.json")
