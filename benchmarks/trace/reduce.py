"""From a profiler trace (``.xplane.pb``) and the benchmark's spans and
counters to per-layer metrics.

Two stages, so that the process holding the chip does the heavy reading once
and the parent (which never touches JAX) only does arithmetic on plain dicts:

1. ``summarize(path)`` reads the trace with ``jax.profiler.ProfileData`` and
   returns a small dict: busy/idle union per device, device time per XLA
   module, exposed collective time, seconds and events of every kind of
   device operation, the top ten of them and the idle gaps attributed to the
   benchmark's host annotations.
2. ``REDUCTIONS`` are the reductions a file under ``layer_metrics/`` may
   name. Each takes the run's context ``{"trace": summary-or-None, "spans":
   {...}, "counters": {...}, "facts": {...}, "config": the configuration
   file's dict, "architecture": a call that returns its module}`` and returns
   a number, or ``None`` when there is nothing to read (the harness then
   leaves the metric out of the line). A device operation is read by a
   regular expression over its kind, and what a count needs comes from the
   configuration's architecture: a metric over a new kernel or a new module
   is a data file, and one over a new architecture adds that module.

Interval arithmetic is in integer nanoseconds on the trace's own clock.
"""

from __future__ import annotations

import math
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all)")
ANNOTATION_PREFIX = "bench/"


# -- interval arithmetic -------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(merged: Sequence[Interval]) -> int:
    return sum(e - s for s, e in merged)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves(events: Sequence[Tuple[str, int, int]]) -> List[Tuple[str, int, int]]:
    """Events (name, start, end) of one line that contain no other event: a
    ``while`` or ``conditional`` spans its body's operations, and counting both
    would count the body twice."""
    # an event of no length (sub-nanosecond, truncated) is nobody's child
    ordered = sorted((ev for ev in events if ev[2] > ev[1]),
                     key=lambda ev: (ev[1], -(ev[2] - ev[1])))
    out = []
    for i, ev in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[1] < ev[2] and nxt[2] <= ev[2] \
                and (nxt[1], nxt[2]) != (ev[1], ev[2]):
            continue  # ev is a parent of the next event
        out.append(ev)
    return out


def innermost_segments(spans: Sequence[Tuple[str, int, int]]
                       ) -> List[Tuple[str, int, int]]:
    """Flatten possibly nested named spans into non-overlapping segments,
    each labelled by the innermost span covering it."""
    points = sorted(((s, e, name) for name, s, e in spans if e > s),
                    key=lambda p: (p[0], -(p[1] - p[0])))
    out: List[Tuple[str, int, int]] = []
    stack: List[Tuple[int, str]] = []  # (end, name)
    cur = None

    def emit(upto):
        nonlocal cur
        if stack and cur is not None and upto > cur:
            out.append((stack[-1][1], cur, upto))
        cur = upto

    for s, e, name in points:
        while stack and stack[-1][0] <= s:
            end = stack[-1][0]
            emit(end)
            stack.pop()
        emit(s)
        stack.append((e, name))
        cur = s
    while stack:
        end = stack[-1][0]
        emit(end)
        stack.pop()
    return out


def attribute(gaps: Sequence[Interval], segments: Sequence[Tuple[str, int, int]]
              ) -> Dict[str, int]:
    """Nanoseconds of ``gaps`` under each segment's label; what no segment
    covers goes to ``(no span)``."""
    acc: Dict[str, int] = {}
    j = 0
    for s, e in gaps:
        covered = 0
        while j < len(segments) and segments[j][2] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][1] < e:
            name, ss, se = segments[k]
            ov = min(e, se) - max(s, ss)
            if ov > 0:
                acc[name] = acc.get(name, 0) + ov
                covered += ov
            k += 1
        if e - s > covered:
            acc["(no span)"] = acc.get("(no span)", 0) + (e - s - covered)
    return acc


# -- reading the trace ----------------------------------------------------------


def module_name(name: str) -> str:
    """``jit_train_step(123456)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_kind(name: str) -> str:
    """The trace names a device operation by its whole HLO line,
    ``%fusion.42 = bf16[4,2048,8192]{...} fusion(...)``. Its kind here is the
    name without the instance number plus the result's type without layouts:
    the same operation in every layer then falls under one key."""
    head, sep, rest = name.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.lstrip("%"))
    if not sep:
        return base[:100]
    rest = re.sub(r"\{[^}]*\}", "", rest)
    result = rest[:rest.find(")") + 1] if rest.startswith("(") \
        else rest.split(" ", 1)[0]
    return (base + " " + result)[:100]


def read_planes(path: str) -> dict:
    """{"devices": {plane: {"ops": [(name, s, e)], "modules": [...]}},
    "annotations": [(name, s, e)]} from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    annotations: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    d[key].append((ev.name, s, s + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        s = int(ev.start_ns)
                        annotations.append((ev.name[len(ANNOTATION_PREFIX):],
                                            s, s + int(ev.duration_ns)))
    return {"devices": devices, "annotations": annotations}


def inventory(path: str) -> dict:
    """{plane: {line: events}}: what a trace holds, for the message of a run
    whose trace cannot be reduced."""
    from jax.profiler import ProfileData

    return {plane.name: {line.name: sum(1 for _ in line.events)
                         for line in plane.lines}
            for plane in ProfileData.from_file(path).planes}


def summarize_planes(planes: dict, top: int = 10) -> Optional[dict]:
    devices = {k: v for k, v in planes["devices"].items()
               if v["ops"] or v["modules"]}
    if not devices:
        return None
    n = len(devices)
    lo = min(ev[1] for d in devices.values() for ev in d["ops"] + d["modules"])
    hi = max(ev[2] for d in devices.values() for ev in d["ops"] + d["modules"])
    segments = innermost_segments(planes["annotations"])
    busy_ns = 0
    exposed_ns = 0
    collective_ns = 0
    modules: Dict[str, dict] = {}
    op_ns: Dict[str, int] = {}
    op_events: Dict[str, int] = {}
    gap_ns: Dict[str, int] = {}
    for d in devices.values():
        ops = leaves(d["ops"]) if d["ops"] else d["modules"]
        busy = union((s, e) for _, s, e in ops)
        busy_ns += length(busy)
        coll = union((s, e) for nm, s, e in ops if COLLECTIVE.match(nm))
        rest = union((s, e) for nm, s, e in ops if not COLLECTIVE.match(nm))
        collective_ns += length(coll)
        exposed_ns += length(subtract(coll, rest))
        for nm, s, e in ops:
            k = op_kind(nm)
            op_ns[k] = op_ns.get(k, 0) + (e - s)
            op_events[k] = op_events.get(k, 0) + 1
        for nm, s, e in d["modules"]:
            m = modules.setdefault(module_name(nm), {"count": 0, "total_ns": 0})
            m["count"] += 1
            m["total_ns"] += e - s
        # a gap while a module executes is the device stalling between its own
        # operations (a wait on a copy, a semaphore); only a gap between
        # modules is the host's doing, and goes to the host span covering it
        gaps = subtract([(lo, hi)], busy)
        in_module = union((s, e) for _, s, e in d["modules"])
        between = subtract(gaps, in_module)
        stalled = length(gaps) - length(between)
        if stalled:
            k = "(inside a module: stalls between its operations)"
            gap_ns[k] = gap_ns.get(k, 0) + stalled
        for k, v in attribute(between, segments).items():
            gap_ns[k] = gap_ns.get(k, 0) + v

    def ranked(acc):
        return [[k, v / n / 1e9] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "collective_s": collective_ns / n / 1e9,
        "exposed_collective_s": exposed_ns / n / 1e9,
        # per device: executions and seconds of each XLA module
        "modules": {k: {"count": v["count"] / n, "total_s": v["total_ns"] / n / 1e9}
                    for k, v in modules.items()},
        "device_ops": ranked(op_ns),
        # per device: [seconds, events] of every kind of operation
        "op_kinds": {k: [v / n / 1e9, op_events[k] / n] for k, v in op_ns.items()},
        "idle_gaps": ranked(gap_ns),
        "annotations": len(planes["annotations"]),
    }


def summarize(path: str) -> Optional[dict]:
    return summarize_planes(read_planes(path))


# -- the reductions -----------------------------------------------------------------


def _module(ctx, module):
    t = ctx.get("trace")
    if not t:
        return None
    m = t["modules"].get(module)
    return m if m and m["count"] else None


def span_value(ctx, span, scale=1.0):
    """A scalar the benchmark's own wrappers timed, times ``scale``."""
    v = ctx["spans"].get(span)
    return None if v is None or isinstance(v, list) else v * scale


def span_quantile(ctx, span, q, scale=1.0, min_samples=2):
    """The q-quantile (0 < q < 1; 0.5 = median) of a list of timed values."""
    vals = ctx["spans"].get(span)
    if not isinstance(vals, list) or len(vals) < min_samples:
        return None
    return quantile(vals, q) * scale


def counter_ratio(ctx, num, den):
    c = ctx["counters"]
    if not c.get(den):
        return None
    return c.get(num, 0) / c[den]


def module_ms_per_exec(ctx, module):
    m = _module(ctx, module)
    return None if m is None else m["total_s"] / m["count"] * 1e3


def exposed_collective_ms_per_exec(ctx, module):
    m = _module(ctx, module)
    if m is None:
        return None
    return ctx["trace"]["exposed_collective_s"] / m["count"] * 1e3


def idle_share_percent(ctx):
    t = ctx.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def mfu_percent(ctx, module):
    """Required operations per token (the architecture's count) x tokens/s
    over chips x peak, with the tokens/s of the traced window: the executions
    of the step's module the trace holds, times the tokens of a step, over the
    trace's length (idle gaps included). The run's own tokens/s would carry
    the cost of starting and stopping the profiler."""
    m, f = _module(ctx, module), ctx["facts"]
    need = ("seq_len", "tokens_per_step", "chips", "peak_flops_per_s")
    if m is None or any(f.get(k) is None for k in need):
        return None
    flops_per_token = ctx["architecture"]().train_flops_per_token(
        ctx["config"], f["seq_len"])
    tokens_per_s = m["count"] * f["tokens_per_step"] / ctx["trace"]["window_s"]
    return 100.0 * flops_per_token * tokens_per_s / (
        f["chips"] * f["peak_flops_per_s"])


def _op_kinds(ctx, op):
    """(seconds, events) per device of the operation kinds whose name the
    regular expression ``op`` finds (``re.search``: anchor it), or ``None``."""
    t = ctx.get("trace")
    found = [v for k, v in (t or {}).get("op_kinds", {}).items()
             if re.search(op, k)]
    if not found:
        return None
    return sum(s for s, _ in found), sum(n for _, n in found)


def device_op_ms_per_exec(ctx, op, module):
    """Device time of the operation kinds matching ``op``, per execution of
    ``module``: what a kernel, or a family of them, costs a step."""
    m, found = _module(ctx, module), _op_kinds(ctx, op)
    if m is None or found is None:
        return None
    return found[0] / m["count"] * 1e3


def roofline_share_percent(ctx, op, kernel):
    """The least time the chip could take for the calls of a kernel the trace
    holds, over the time they took: calls x max(operations / peak operations/s,
    bytes / peak bytes/s) over the seconds of the kinds matching ``op``, with
    (operations, bytes) of one call from the architecture's
    ``kernel_cost(kernel, config, facts)``."""
    found, f = _op_kinds(ctx, op), ctx["facts"]
    if found is None or not found[0] or any(
            f.get(k) is None for k in ("peak_flops_per_s", "peak_hbm_bytes_per_s")):
        return None
    seconds, events = found
    operations, nbytes = ctx["architecture"]().kernel_cost(
        kernel, ctx["config"], f)
    least = max(operations / f["peak_flops_per_s"],
                nbytes / f["peak_hbm_bytes_per_s"])
    return 100.0 * events * least / seconds


REDUCTIONS = {
    "span_value": span_value,
    "span_quantile": span_quantile,
    "counter_ratio": counter_ratio,
    "module_ms_per_exec": module_ms_per_exec,
    "exposed_collective_ms_per_exec": exposed_collective_ms_per_exec,
    "idle_share_percent": idle_share_percent,
    "mfu_percent": mfu_percent,
    "device_op_ms_per_exec": device_op_ms_per_exec,
    "roofline_share_percent": roofline_share_percent,
}


def quantile(values: Sequence[float], q: float) -> float:
    """Nearest-rank quantile: the smallest value with at least q of the
    sample at or below it (so the 0.9-quantile of 70 values is the 63rd)."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("quantile of an empty sample")
    if q == 0.5:
        return statistics.median(ordered)
    rank = max(1, math.ceil(q * len(ordered) - 1e-9))
    return ordered[min(rank, len(ordered)) - 1]
