"""Goodput ledger: exclusive, exhaustive wall-clock attribution.

Every process classifies its wall time into the buckets below via the
:func:`region` context manager (nested regions are EXCLUSIVE: a child
region's time is subtracted from its parent, so each second lands in
exactly one bucket) plus :func:`add` for externally-measured windows
(e.g. the train controller's re-form downtime). Whatever is not claimed
by any bucket is derived as ``idle`` in :func:`snapshot`, making the
decomposition exhaustive by construction: ``sum(buckets) + idle ==
wall``.

The ledger is per-process and per-job (:func:`set_job` re-anchors when
the job tag changes). The core worker's observability flush ships
:func:`flush_payload` into the GCS KV ``goodput`` namespace on the same
cadence as the metrics registry; the GCS aggregates the per-process
payloads into a per-job ``GoodputLedger`` surfaced as ``/api/goodput``,
``util.state.goodput()`` and ``ray-tpu goodput``, and mirrors
``goodput_fraction`` / MFU into the metrics registry so they ride
``MetricsHistory`` like any other gauge.

Signal sources wired into the region API:

- ``step_compute`` / ``compile``: ``parallel/train.py`` wraps the train
  step dispatch; a :class:`CompileWatch` keyed on batch shapes/dtypes
  detects jit cache misses and routes the blocking first call into the
  ``compile`` bucket (counting *re*-compiles — same program, new key —
  separately as the storm signal);
- ``input_stall``: ``data/dataset.py:iter_device_batches`` wraps the
  consumer-side queue wait;
- ``ckpt_pause``: ``ckpt/saver.py`` wraps the caller-thread
  drain+snapshot window of ``CheckpointSaver.save``;
- ``reform_downtime``: the elastic train controller's RESTARTING window
  and pipeline gang recovery report via :func:`add`;
- ``bubble`` / ``collective_wait``: pipeline stages report schedule
  recv waits and send/reduce waits via :func:`add`;
- ``overhead``: the core worker's observability flush itself.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Optional, Tuple

from ray_tpu.util.tracing import annotate

__all__ = [
    "BUCKETS", "CompileWatch", "add", "batch_key", "count", "enabled",
    "flush_payload", "note_mfu", "region", "reset", "reset_after_fork",
    "set_job", "snapshot",
]

#: Exclusive attribution buckets; ``idle`` is derived (wall minus the
#: sum of these) so the decomposition is exhaustive by construction.
BUCKETS = (
    "step_compute", "collective_wait", "input_stall", "ckpt_pause",
    "compile", "reform_downtime", "bubble", "overhead",
)

_lock = threading.Lock()
_tls = threading.local()

_job: str = ""
_anchor: Optional[float] = None  # perf_counter at ledger start
_anchor_ts: float = 0.0          # time.time() at ledger start
_buckets: Dict[str, float] = {}
_counters: Dict[str, float] = {}
_mfu: Optional[float] = None

_metrics_lock = threading.Lock()
_metrics: Optional[dict] = None


def enabled() -> bool:
    from ray_tpu._private.config import RAY_CONFIG

    return bool(RAY_CONFIG.goodput_enabled)


def _obs() -> dict:
    """Lazily-created goodput instruments on the shared metrics registry
    (set on every ledger flush, so ``goodput_fraction`` and MFU ride
    ``MetricsHistory`` like any other gauge)."""
    global _metrics
    with _metrics_lock:
        if _metrics is None:
            from ray_tpu.util.metrics import Gauge

            _metrics = {
                "fraction": Gauge(
                    "ray_tpu.goodput.fraction",
                    "step_compute share of ledger wall time for this "
                    "process's active job"),
                "mfu": Gauge(
                    "ray_tpu.goodput.mfu",
                    "model FLOPs utilization last reported by the train "
                    "loop on this process"),
                "compiles": Gauge(
                    "ray_tpu.goodput.compiles",
                    "cumulative jit compiles observed by the compile "
                    "watch (first-key compiles plus recompiles)"),
                "recompiles": Gauge(
                    "ray_tpu.goodput.recompiles",
                    "cumulative shape/dtype-keyed jit RE-compiles (same "
                    "program, new key) — the recompile-storm signal"),
                "bucket_seconds": Gauge(
                    "ray_tpu.goodput.bucket_seconds",
                    "cumulative attributed wall seconds per goodput "
                    "bucket", tag_keys=("bucket",)),
            }
        return _metrics


def _anchor_locked() -> None:
    global _anchor, _anchor_ts
    if _anchor is None:
        _anchor = time.perf_counter()
        _anchor_ts = time.time()


def set_job(name: str) -> None:
    """Tag this process's ledger with its job (run) name. A *different*
    job name resets the accumulators and re-anchors wall time, so a
    reused worker never leaks a previous job's seconds into the next."""
    global _job, _anchor, _anchor_ts, _mfu
    if not enabled():
        return
    with _lock:
        if name != _job:
            _buckets.clear()
            _counters.clear()
            _mfu = None
            _anchor = None
        _job = name
        _anchor_locked()


def _add_locked(bucket: str, seconds: float) -> None:
    _anchor_locked()
    _buckets[bucket] = _buckets.get(bucket, 0.0) + seconds


def add(bucket: str, seconds: float) -> None:
    """Attribute an externally-measured window (controller re-form
    downtime, pipeline bubble/reduce waits) directly to a bucket."""
    if not enabled() or seconds <= 0.0:
        return
    with _lock:
        _add_locked(bucket, float(seconds))


def count(name: str, n: float = 1) -> None:
    """Bump a ledger counter (steps, compiles, recompiles, input_waits,
    ckpt_saves, reforms)."""
    if not enabled():
        return
    with _lock:
        _anchor_locked()
        _counters[name] = _counters.get(name, 0) + n


def note_mfu(value: float) -> None:
    """Record the train loop's latest MFU so it rides the ledger flush
    (and the ``ray_tpu.goodput.mfu`` gauge) without a separate path."""
    global _mfu
    if not enabled():
        return
    with _lock:
        _anchor_locked()
        _mfu = float(value)


@contextmanager
def region(bucket: str):
    """Attribute the enclosed wall time to ``bucket``. Nesting is
    exclusive: a nested region's full duration (its own time plus its
    children's) is subtracted from the parent frame, so concurrent-with
    -nothing code attributes each second to exactly one bucket. The
    region is also a ``ray_tpu/goodput.<bucket>`` span on the profiler's
    clock (``tracing.annotate``), ledger enabled or not."""
    with annotate("goodput." + bucket):
        if not enabled():
            yield
            return
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        frame = [bucket, time.perf_counter(), 0.0]  # bucket, t0, child_s
        stack.append(frame)
        try:
            yield
        finally:
            stack.pop()
            dt = time.perf_counter() - frame[1]
            own = max(0.0, dt - frame[2])
            with _lock:
                _add_locked(bucket, own)
            if stack:
                stack[-1][2] += dt


def snapshot() -> Dict[str, Any]:
    """Current ledger state. ``buckets`` carries every attribution
    bucket plus derived ``idle`` (wall minus accounted), so the values
    always sum to ``wall_s`` (modulo concurrent-thread overlap)."""
    with _lock:
        wall = 0.0 if _anchor is None else time.perf_counter() - _anchor
        buckets = {b: _buckets.get(b, 0.0) for b in BUCKETS}
        accounted = sum(buckets.values())
        buckets["idle"] = max(0.0, wall - accounted)
        snap: Dict[str, Any] = {
            "job": _job,
            "wall_s": wall,
            "started": _anchor_ts,
            "buckets": buckets,
            "counters": dict(_counters),
        }
        if _mfu is not None:
            snap["mfu"] = _mfu
        return snap


def flush_payload(node: str = "") -> Optional[Dict[str, Any]]:
    """Build the per-process KV payload for the observability flush, or
    ``None`` when this process has nothing to report (keeps idle
    utility processes out of the ``goodput`` namespace). Also mirrors
    the derived gauges onto the shared metrics registry."""
    if not enabled():
        return None
    snap = snapshot()
    if not snap["job"] and not _counters and not any(
            v > 0.0 for b, v in snap["buckets"].items() if b != "idle"):
        return None
    import os

    snap["pid"] = os.getpid()
    snap["time"] = time.time()
    snap["node"] = node
    try:
        obs = _obs()
        wall = snap["wall_s"]
        if wall > 0:
            obs["fraction"].set(snap["buckets"]["step_compute"] / wall)
        if snap.get("mfu") is not None:
            obs["mfu"].set(snap["mfu"])
        counters = snap["counters"]
        obs["compiles"].set(counters.get("compiles", 0))
        obs["recompiles"].set(counters.get("recompiles", 0))
        for b, v in snap["buckets"].items():
            obs["bucket_seconds"].set(v, tags={"bucket": b})
    except Exception:
        pass  # instrument mirroring must never block the flush
    return snap


class CompileWatch:
    """Shape/dtype-keyed jit compile detector.

    ``observe(fn, key)`` returns ``"compile"`` for the first key a
    program ever sees, ``"recompile"`` for a *new* key on an
    already-seen program (same fn, new shapes/dtypes — the storm
    signal), and ``None`` for a warm cache hit."""

    def __init__(self):
        self._seen: Dict[str, set] = {}
        self._lock = threading.Lock()

    def observe(self, fn: str, key: Tuple) -> Optional[str]:
        with self._lock:
            seen = self._seen.setdefault(fn, set())
            if key in seen:
                return None
            seen.add(key)
            return "compile" if len(seen) == 1 else "recompile"


def batch_key(batch: Dict[str, Any]) -> Tuple:
    """A jit-cache-shaped key for a train batch: sorted (name, shape,
    dtype) triples. Deliberately ignores values and the param tree —
    cheap enough for the hot path, and shape/dtype changes are what
    trigger retraces."""
    out = []
    for k in sorted(batch):
        v = batch[k]
        shape = tuple(getattr(v, "shape", ()))
        dtype = str(getattr(v, "dtype", type(v).__name__))
        out.append((k, shape, dtype))
    return tuple(out)


def reset() -> None:
    """Zero the ledger (tests; also the fork path below)."""
    global _job, _anchor, _anchor_ts, _mfu
    with _lock:
        _job = ""
        _anchor = None
        _anchor_ts = 0.0
        _mfu = None
        _buckets.clear()
        _counters.clear()
    _tls.stack = []


def reset_after_fork() -> None:
    """Drop ledger state inherited through a zygote fork: a child that
    keeps the parent image's accumulators re-reports the zygote's
    seconds under a fresh proc key, double-counting them per job (the
    ``_obs_proc_tag`` class of fork bug)."""
    reset()
