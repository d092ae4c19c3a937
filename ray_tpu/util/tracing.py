"""Causal task tracing + profile events with chrome://tracing export.

Reference: the reference captures per-task profile events in C++
(``core_worker/profile_event.cc``) into a ``TaskEventBuffer``
(``task_event_buffer.cc``) that flushes to the GCS ``GcsTaskManager`` and
feeds the dashboard timeline; opt-in OpenTelemetry spans wrap remote calls
and propagate trace context through the TaskSpec
(``util/tracing/tracing_helper.py:326``). Here every worker buffers span
records and flushes them to the GCS KV (``trace`` namespace); the driver
gathers them with :func:`get_spans` and writes a chrome://tracing JSON
timeline with :func:`export_chrome_trace` (also ``ray-tpu timeline``).

Causality: every span carries ``trace_id``/``span_id``/``parent_id``.
The active span rides a :mod:`contextvars` context var; ``submit_task`` /
actor submission stamp the caller's active span into the ``TaskSpec``
(``trace_id``/``parent_span_id``), and task execution installs the task's
span as current, so nested ``.remote()`` calls and :func:`profile` blocks
form a tree that spans processes. :func:`export_chrome_trace` emits
chrome-trace flow events (``ph: "s"/"f"``) for every cross-thread edge, so
driver→actor→nested-task causality renders as arrows in Perfetto.

Enable with ``RAY_TPU_ENABLE_TRACING=1`` (on the driver: before init — the
flag propagates to workers through the runtime env) or per-session via
``ray_tpu.util.tracing.enable()``. User code can add custom spans::

    with ray_tpu.util.tracing.profile("tokenize"):
        ...

Beside the device: :func:`annotate` is the one span primitive on the
PROFILER's clock. It buffers and ships nothing; the span exists only in a
``jax.profiler`` session taken by this process, where it lies on the host
plane as ``ray_tpu/<name>`` beside the device's own lines. :func:`profile`
and ``goodput.region`` enter it, so every block the runtime already marks
shows in such a trace, whether or not ``RAY_TPU_ENABLE_TRACING`` is set.
"""

from __future__ import annotations

import atexit
import contextlib
import contextvars
import json
import os
import sys
from ray_tpu._private import wire
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

_lock = threading.Lock()
_buffer: List[dict] = []
_flush_counter = 0
_enabled: Optional[bool] = None

_FLUSH_EVERY = 32
_FLUSH_INTERVAL_S = 1.0
_MAX_BUFFER = 10_000  # drop-oldest beyond this: tracing never leaks unbounded
_last_flush = time.time()
_timer: Optional[threading.Timer] = None
# cluster-unique flush-key tag (pids collide across nodes/restarts)
_proc_tag = uuid.uuid4().hex[:10]

# ---------------------------------------------------------------------------
# trace context (reference: tracing_helper.py's _opentelemetry context
# propagation — here a plain (trace_id, span_id) pair on a ContextVar, so it
# follows asyncio tasks automatically and can be installed on pool threads)
# ---------------------------------------------------------------------------

_ctx: "contextvars.ContextVar[Optional[Tuple[str, str]]]" = \
    contextvars.ContextVar("ray_tpu_trace_ctx", default=None)


def new_trace_id() -> str:
    return uuid.uuid4().hex


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def current_context() -> Optional[Tuple[str, str]]:
    """The active (trace_id, span_id), or None outside any span."""
    return _ctx.get()


def set_context(trace_id: str, span_id: str):
    """Install (trace_id, span_id) as the active span; returns a token for
    :func:`reset_context`. Used by task execution so nested ``.remote()``
    calls and :func:`profile` blocks parent onto the running task's span."""
    return _ctx.set((trace_id, span_id))


def reset_context(token) -> None:
    try:
        _ctx.reset(token)
    except ValueError:
        # token from another context (e.g. exec-pool thread reuse): clearing
        # is the right fallback — never let a stale span leak across tasks
        _ctx.set(None)


def enabled() -> bool:
    global _enabled
    if _enabled is None:
        _enabled = os.environ.get("RAY_TPU_ENABLE_TRACING", "") in ("1", "true")
    return _enabled


def enable():
    global _enabled
    os.environ["RAY_TPU_ENABLE_TRACING"] = "1"
    _enabled = True


def reset_after_fork():
    """Drop every piece of state a forked child inherits from its parent's
    span pipeline. A zygote-forked worker shares the parent's buffer, flush
    counter, proc tag, AND a dangling ``_timer`` reference (the timer
    thread does not survive the fork, so the child would believe a flush
    is armed and never arm one again) — without this reset the child
    re-ships the zygote's buffered spans and clobbers the parent's GCS
    flush keys (same class of bug as core_worker's ``_obs_proc_tag``)."""
    global _timer, _flush_counter, _last_flush, _proc_tag
    with _lock:
        _buffer.clear()
        _timer = None  # parent's timer thread is gone in the child
        _flush_counter = 0
        _last_flush = time.time()
        _proc_tag = uuid.uuid4().hex[:10]
    del _local_spans[:]
    _ctx.set(None)


# -- tail-span protection: without this, spans recorded in the last
# _FLUSH_INTERVAL_S before process exit die with the pending _timer --
_atexit_registered = False


def _flush_at_exit():
    try:
        flush()
    except Exception:
        pass
    try:
        from ray_tpu._private import task_events

        task_events.flush()
    except Exception:
        pass


def _ensure_atexit():
    global _atexit_registered
    if not _atexit_registered:
        _atexit_registered = True
        atexit.register(_flush_at_exit)


def record_span(name: str, start_s: float, end_s: float,
                category: str = "task", **extra):
    """Buffer one span; flushes to the GCS every _FLUSH_EVERY spans.

    Span causality fields (``trace_id``/``span_id``/``parent_id``) are
    filled from the active context when not passed explicitly."""
    if not enabled():
        return
    if "trace_id" not in extra:
        ctx = _ctx.get()
        if ctx is not None:
            extra["trace_id"] = ctx[0]
            extra.setdefault("parent_id", ctx[1])
    span = {
        "name": name,
        "cat": category,
        "ts": start_s,
        "dur": end_s - start_s,
        "pid": os.getpid(),
        "tid": threading.get_ident() % 100_000,
        **extra,
    }
    global _timer
    flush_now = False
    with _lock:
        _ensure_atexit()
        _buffer.append(span)
        if len(_buffer) > _MAX_BUFFER:
            del _buffer[: len(_buffer) - _MAX_BUFFER]
        if len(_buffer) >= _FLUSH_EVERY:
            # size-triggered flushes hand off without waiting for the GCS
            # round trip (a traced submit loop must not stall every 32
            # spans); boundedness comes from _MAX_BUFFER drop-oldest
            flush_now = True
        elif _timer is None:
            _timer = threading.Timer(_FLUSH_INTERVAL_S, _timer_flush)
            _timer.daemon = True
            _timer.start()
    if flush_now:
        flush(block=False)


def _timer_flush():
    global _timer
    with _lock:
        _timer = None
    flush()


ANNOTATION_PREFIX = "ray_tpu/"


def annotate(name: str, **attrs):
    """A host span named ``ray_tpu/<name>`` on the profiler's clock: a
    ``jax.profiler.TraceAnnotation`` (``attrs`` become its arguments) when
    this process has imported JAX, else a null context. The driver, the GCS
    and the raylets never import JAX for this. Outside a profiler session
    the annotation is inert, and it never selects what the device runs."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name, **attrs)


@contextlib.contextmanager
def profile(name: str, category: str = "user", **extra):
    """Custom user span (reference: ray.util.tracing via profile events).

    Runs as a child of the active span (the executing task, or an enclosing
    profile block) and installs itself as current for the duration, so
    nested profile blocks and nested ``.remote()`` submissions tree up.
    Always an :func:`annotate` span too; the GCS record is what
    ``RAY_TPU_ENABLE_TRACING`` gates."""
    with annotate(name):
        if not enabled():
            yield
            return
        parent = _ctx.get()
        span_id = new_span_id()
        trace_id = parent[0] if parent is not None else new_trace_id()
        token = _ctx.set((trace_id, span_id))
        t0 = time.time()
        try:
            yield
        finally:
            reset_context(token)
            record_span(name, t0, time.time(), category=category,
                        trace_id=trace_id, span_id=span_id,
                        parent_id=parent[1] if parent is not None else None,
                        **extra)


def flush(block: bool = True):
    """Push buffered spans to the GCS KV; safe to call anywhere.

    ``block=False`` (the size-triggered path in :func:`record_span`) ships
    without waiting for the round trip; explicit callers (get_spans,
    shutdown, atexit) keep the blocking read-your-writes semantics."""
    global _flush_counter, _last_flush
    with _lock:
        _last_flush = time.time()
        if not _buffer:
            return
        spans, _buffer[:] = list(_buffer), []
        _flush_counter += 1
        counter = _flush_counter
    def _rebuffer():
        with _lock:
            _buffer[:0] = spans
            if len(_buffer) > _MAX_BUFFER:
                del _buffer[: len(_buffer) - _MAX_BUFFER]

    try:
        from ray_tpu._private.worker import global_worker, is_initialized

        if not is_initialized():
            _rebuffer()  # pre-init spans surface after init
            return
        core = global_worker()
        if getattr(core, "mode", "") == "local":
            # local mode: keep spans in-process (get_spans reads them back)
            _local_spans.extend(spans)
            return
        req = {"ns": "trace", "key": f"spans_{_proc_tag}_{counter}",
               "value": wire.dumps(spans)}

        async def _put_guarded():
            try:
                await core._gcs_call("KVPut", req)
            except Exception:
                _rebuffer()

        try:
            import asyncio

            running = asyncio.get_running_loop()
        except RuntimeError:
            running = None
        if running is not None and running is core.loop:
            # called from the worker's event loop (task-execution path):
            # blocking would deadlock — fire and forget, re-buffer on error
            from ray_tpu._private.async_util import spawn

            spawn(_put_guarded(), what="trace-span flush")
        elif not block:
            # async hand-off: ship on the io loop, don't await the ack
            # (_put_guarded re-buffers on failure)
            import asyncio as _asyncio

            _asyncio.run_coroutine_threadsafe(_put_guarded(), core.loop)
        else:
            core._run(_put_guarded())
    except Exception:
        # tracing must never take down the workload
        _rebuffer()


_local_spans: List[dict] = []


def get_spans() -> List[dict]:
    """Gather all spans recorded so far, cluster-wide."""
    flush()
    from ray_tpu._private.worker import global_worker

    core = global_worker()
    if getattr(core, "mode", "") == "local":
        return list(_local_spans)
    keys = core._run(core._gcs_call(
        "KVKeys", {"ns": "trace", "prefix": "spans_"}))["keys"]
    out: List[dict] = []
    for key in keys:
        blob = core._run(core._gcs_call(
            "KVGet", {"ns": "trace", "key": key}))["value"]
        if blob:
            out.extend(wire.loads(blob))
    return sorted(out, key=lambda s: s["ts"])


def clear():
    """Delete all collected spans (GCS trace table + local buffers)."""
    global _local_spans
    with _lock:
        _buffer.clear()
    _local_spans = []
    from ray_tpu._private.worker import global_worker, is_initialized

    if not is_initialized():
        return
    core = global_worker()
    if getattr(core, "mode", "") == "local":
        return
    core._run(core._gcs_call("KVDel", {"ns": "trace", "key": "spans_",
                                       "prefix": True}))


_SPAN_META = ("name", "cat", "ts", "dur", "pid", "tid")


def spans_to_chrome_events(spans: List[dict],
                           flow_id_base: int = 0) -> List[dict]:
    """Convert span records to chrome-trace events (``ph: "X"`` slices +
    flow-event pairs for cross-track parent→child edges). Shared by the
    driver-side :func:`export_chrome_trace` and the GCS timeline endpoint
    (``GET /api/timeline``), which merges these with task-event slices."""
    events = [
        {
            "name": s["name"],
            "cat": s.get("cat", "task"),
            "ph": "X",
            "ts": s["ts"] * 1e6,  # microseconds
            "dur": max(s["dur"], 0.0) * 1e6,
            "pid": s.get("pid", 0),
            "tid": s.get("tid", 0),
            "args": {k: v for k, v in s.items() if k not in _SPAN_META},
        }
        for s in spans
    ]
    by_id = {s["span_id"]: s for s in spans if s.get("span_id")}
    flow_n = flow_id_base
    for s in spans:
        parent = by_id.get(s.get("parent_id") or "")
        if parent is None:
            continue
        same_track = (parent.get("pid"), parent.get("tid")) == \
            (s.get("pid"), s.get("tid"))
        if same_track:
            continue  # same-thread nesting already renders as stacked slices
        flow_n += 1
        # the flow-start ts must land inside the parent slice for Perfetto
        # to bind the arrow to it
        start_ts = min(max(s["ts"], parent["ts"]),
                       parent["ts"] + max(parent["dur"], 0.0))
        events.append({
            "name": "task_flow", "cat": "flow", "ph": "s", "id": flow_n,
            "ts": start_ts * 1e6, "pid": parent.get("pid", 0),
            "tid": parent.get("tid", 0),
        })
        events.append({
            "name": "task_flow", "cat": "flow", "ph": "f", "bp": "e",
            "id": flow_n, "ts": s["ts"] * 1e6, "pid": s.get("pid", 0),
            "tid": s.get("tid", 0),
        })
    return events


def export_chrome_trace(path: str) -> int:
    """Write a chrome://tracing (about://tracing, Perfetto) JSON file.

    Besides the ``ph: "X"`` duration slices, every parent→child span edge
    that crosses a thread or process emits a flow-event pair (``ph: "s"`` on
    the parent slice, ``ph: "f"`` on the child slice) so cross-process
    causality — driver submit → actor task → nested task — renders as
    arrows. Returns the number of events written."""
    events = spans_to_chrome_events(get_spans())
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return len(events)
