"""The serving engine's own measurement (CPU tier, tiny model): the counters
of ``engine.metrics``, the ``ray_tpu/`` spans on the profiler's clock and
the per-request spans of the GCS trace table. Counts and order only: a time
read here is a host time of the CPU backend and is compared with nothing."""

import contextlib
import os
import sys
import types

import pytest

from ray_tpu.llm.config import EngineConfig, LLMConfig, SamplingParams
from ray_tpu.util import goodput, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KEYS = {
    "prefill_tokens", "decode_steps", "generated_tokens", "preempted",
    "steps", "prefill_steps", "admitted", "prefill_batch_tokens", "compiles",
    "step_ms", "host_ms", "readback_ms", "admit_ms", "prefill_dispatch_ms",
    "decode_dispatch_ms", "sample_dispatch_ms", "emit_ms",
    "between_steps_ms", "queue_wait_ms", "ttft_ms",
    # what routing did; a dense model's (this one's) stay 0
    "moe_decode_layer_steps", "moe_decode_assignments",
    "moe_decode_experts_touched", "moe_decode_max_load"}
PHASES = ("admit_ms", "prefill_dispatch_ms", "decode_dispatch_ms",
          "sample_dispatch_ms", "readback_ms", "emit_ms")
SLOTS, BUCKET = 8, 16


def _config():
    eng = EngineConfig(max_num_seqs=SLOTS, max_model_len=128, page_size=16,
                       prefill_bucket_min=BUCKET)
    return LLMConfig(model_id="tiny", engine_config=eng,
                     model_overrides={"attention_impl": "xla"})


@pytest.fixture(scope="module")
def params():
    from ray_tpu.llm.engine import JaxLLMEngine

    return JaxLLMEngine(_config(), seed=0).params


@pytest.fixture
def engine(params):
    """A fresh engine on shared weights: the compiled programs are jit's, so
    only the first test of the module pays for them."""
    from ray_tpu.llm.engine import JaxLLMEngine

    return JaxLLMEngine(_config(), params=params, seed=0)


def _prompt(n):
    return list(range(3, 3 + n))


@pytest.fixture
def recorder(monkeypatch):
    """``jax.profiler.TraceAnnotation`` replaced by a recorder: one
    ``(depth, name, attrs)`` per span entered, in order."""
    import jax

    entered, depth = [], [0]

    class Recorder:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, attrs

        def __enter__(self):
            entered.append((depth[0], self.name, self.attrs))
            depth[0] += 1
            return self

        def __exit__(self, *exc):
            depth[0] -= 1
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return entered


def test_metrics_complete_numeric_monotone(engine):
    m = engine.metrics
    assert set(m) == KEYS
    assert all(type(v) in (int, float) and v == 0 for v in m.values())
    for i in range(SLOTS + 2):  # more than the slots: some wait
        engine.add_request(f"r{i}", _prompt(5 + i),
                           SamplingParams(max_tokens=4 + i))
    prev = dict(m)
    busy = 0
    for _ in range(30):
        busy += engine.has_unfinished()
        engine.step()
        assert set(m) == KEYS
        for k, v in m.items():
            assert type(v) in (int, float)
            assert v >= prev[k], k
        prev = dict(m)
    assert not engine.has_unfinished()
    assert m["host_ms"] + m["readback_ms"] == pytest.approx(m["step_ms"])
    phases = sum(m[k] for k in PHASES)
    # everything step() does lies inside one phase or another
    assert 0.8 * m["step_ms"] <= phases <= m["step_ms"]
    assert all(m[k] > 0 for k in PHASES)
    # a call that finds nothing to run is no step
    assert 0 < m["steps"] == busy < 30
    assert m["steps"] <= m["prefill_steps"] + m["decode_steps"]
    assert m["between_steps_ms"] > 0
    assert m["compiles"] == 2  # one prefill bucket, decode


def test_prefill_counters_equal_the_hand_count(engine):
    """Eight prompts fill the eight slots in one prefill of 8 x 16; the
    ninth waits for a slot and takes a prefill of 8 x 32 alone."""
    m = engine.metrics
    lens = [4, 5, 6, 7, 8, 9, 10, 11]
    for i, n in enumerate(lens):
        engine.add_request(f"r{i}", _prompt(n),
                           SamplingParams(max_tokens=2 if i == 0 else 12))
    engine.add_request("ninth", _prompt(20), SamplingParams(max_tokens=3))
    ninth = engine._requests["ninth"]
    engine.step()
    assert (m["prefill_steps"], m["admitted"]) == (1, 8)
    assert m["prefill_tokens"] == sum(lens)
    assert m["prefill_batch_tokens"] == SLOTS * BUCKET
    assert ninth.t_admitted == 0.0
    waited = m["queue_wait_ms"]
    while engine.has_unfinished():
        engine.step()
    assert (m["prefill_steps"], m["admitted"]) == (2, 9)
    assert m["prefill_tokens"] == sum(lens) + 20
    assert m["prefill_batch_tokens"] == SLOTS * BUCKET + SLOTS * 2 * BUCKET
    # the ninth waited at least one whole step for its slot
    assert ninth.t_admitted - ninth.t_added > 0
    assert m["queue_wait_ms"] - waited == pytest.approx(
        (ninth.t_admitted - ninth.t_added) * 1e3)
    assert m["ttft_ms"] > m["queue_wait_ms"] > 0
    assert m["generated_tokens"] >= 9


@pytest.mark.parametrize("enter,name,attrs", [
    (lambda: tracing.annotate("x", bucket=256), "ray_tpu/x", {"bucket": 256}),
    (lambda: tracing.profile("x", detail="kept off the trace"), "ray_tpu/x",
     {}),
    (lambda: goodput.region("compile"), "ray_tpu/goodput.compile", {}),
], ids=["annotate", "profile", "goodput.region"])
def test_spans_ride_the_profiler_annotation(recorder, monkeypatch, enter,
                                            name, attrs):
    assert not tracing.enabled()  # the GCS record is gated, this is not
    with enter():
        with tracing.annotate("inner"):
            pass
    assert recorder == [(0, name, attrs), (1, "ray_tpu/inner", {})]
    # a process that never imported JAX pays a null context and records nothing
    del recorder[:]
    monkeypatch.delitem(sys.modules, "jax")
    assert isinstance(tracing.annotate("x"), contextlib.nullcontext)
    with enter():
        pass
    assert recorder == []


def test_engine_spans_nest_in_step_order(engine, recorder):
    engine.add_request("a", _prompt(5), SamplingParams(max_tokens=3))
    engine.step()
    names = [(d, n.removeprefix("ray_tpu/engine.")) for d, n, _ in recorder]
    assert all(n.startswith("ray_tpu/") for _, n, _ in recorder)
    after_dispatch = [(1, "sample_dispatch"), (1, "readback"), (1, "emit")]
    assert names == (
        [(0, "step"), (1, "admit"), (1, "prefill_dispatch"), (2, "compile")]
        + after_dispatch + [(1, "decode_dispatch"), (2, "compile")]
        + after_dispatch)
    attrs = {n: a for _, n, a in recorder}
    assert attrs["ray_tpu/engine.prefill_dispatch"] == {
        "bucket": BUCKET, "admitted": 1}
    assert attrs["ray_tpu/engine.compile"]["program"] == "decode"
    # a shape this engine has used is not a compile again
    del recorder[:]
    engine.step()
    assert [n for _, n, _ in recorder] == [
        "ray_tpu/engine." + p for p in (
            "step", "admit", "decode_dispatch", "sample_dispatch", "readback",
            "emit")]


def test_benchmark_wrappers_still_see_step_and_sample(engine, recorder,
                                                      tmp_path, monkeypatch):
    """The benchmark's ``_annotate_engine`` replaces ``eng.step`` and
    ``eng._sample`` from outside: both stay methods under those names, and
    its two spans still enclose the engine's own."""
    monkeypatch.syspath_prepend(REPO)
    from benchmarks.jobs import common
    from benchmarks.jobs.serve import BenchLLMServer

    BenchLLMServer._annotate_engine(types.SimpleNamespace(
        engine=engine, _tracer=common.Tracer(True, str(tmp_path))))
    out = engine.generate([_prompt(6)], SamplingParams(max_tokens=3))[0]
    assert len(out.token_ids) == 3 or out.finish_reason == "stop"
    names = [(d, n) for d, n, _ in recorder]
    assert names[:2] == [(0, "bench/engine.step"), (1, "ray_tpu/engine.step")]
    i = names.index((2, "bench/sample_readback"))
    assert names[i + 1:i + 3] == [(3, "ray_tpu/engine.sample_dispatch"),
                                  (3, "ray_tpu/engine.readback")]
    steps = engine.metrics["prefill_steps"] + engine.metrics["decode_steps"]
    assert names.count((2, "bench/sample_readback")) == steps


def test_finished_request_leaves_three_spans_under_its_parent(engine,
                                                              monkeypatch):
    monkeypatch.setattr(tracing, "_enabled", None)   # restored afterwards,
    monkeypatch.setenv("RAY_TPU_ENABLE_TRACING", "0")  # with the flag
    tracing.enable()
    trace_id, parent = tracing.new_trace_id(), tracing.new_span_id()
    token = tracing.set_context(trace_id, parent)
    try:
        engine.add_request("traced", _prompt(7), SamplingParams(max_tokens=4))
    finally:
        tracing.reset_context(token)
    engine.add_request("bare", _prompt(7), SamplingParams(max_tokens=4))
    try:
        while engine.has_unfinished():
            engine.step()
        with tracing._lock:
            spans = [s for s in tracing._buffer if s["cat"] == "llm"]
    finally:
        with tracing._lock:
            tracing._buffer.clear()
    mine = [s for s in spans if s["request_id"] == "traced"]
    assert [s["name"] for s in mine] == [
        "engine.queued", "engine.prefill", "engine.decode"]
    assert {(s["trace_id"], s["parent_id"]) for s in mine} == {
        (trace_id, parent)}
    # end to end without a hole, on the spans' wall clock
    for a, b in zip(mine, mine[1:]):
        assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-6)
    assert all(s["dur"] >= 0 for s in mine)
    bare = [s for s in spans if s["request_id"] == "bare"]
    assert len(bare) == 3 and all("parent_id" not in s for s in bare)
