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
    "moe_decode_experts_touched", "moe_decode_max_load",
    # what a latent cache's decode read; 0 without one
    "mla_decode_live_tokens", "mla_decode_read_tokens"}
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
    """Eight prompts of 4 to 11 tokens fill the eight slots in one prefill
    phase of eight one-row calls of 16 positions; the ninth, 20 tokens, waits
    for a slot and takes one call of 32 alone."""
    m = engine.metrics
    lens = [4, 5, 6, 7, 8, 9, 10, 11]
    for i, n in enumerate(lens):
        engine.add_request(f"r{i}", _prompt(n),
                           SamplingParams(max_tokens=2 if i == 0 else 12))
    engine.add_request("ninth", _prompt(20), SamplingParams(max_tokens=3))
    ninth = engine._requests["ninth"]
    engine.step()
    assert (m["prefill_steps"], m["admitted"]) == (1, 8)
    assert m["prefill_tokens"] == sum(lens) == 60
    assert m["prefill_batch_tokens"] == 8 * BUCKET == 128
    assert ninth.t_admitted == 0.0
    waited = m["queue_wait_ms"]
    while engine.has_unfinished():
        engine.step()
    assert (m["prefill_steps"], m["admitted"]) == (2, 9)
    assert m["prefill_tokens"] == 60 + 20
    assert m["prefill_batch_tokens"] == 128 + 2 * BUCKET == 160
    # the ninth waited at least one whole step for its slot
    assert ninth.t_admitted - ninth.t_added > 0
    assert m["queue_wait_ms"] - waited == pytest.approx(
        (ninth.t_admitted - ninth.t_added) * 1e3)
    assert m["ttft_ms"] > m["queue_wait_ms"] > 0
    assert m["generated_tokens"] >= 9


def test_one_row_calls_leave_what_the_padded_batch_leaves(engine):
    """A prefill phase of one-row calls, each at its own length bucket (16,
    32, 16, 64 here), against ONE ``mr.prefill`` on the padded [8, 64] batch
    of the same admitted set and block tables: the same K and V in every
    page a prompt owns, the same first-token logits at every admitted slot
    (bf16 activations: a 1-row and an 8-row product may round apart)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import model_runner as mr

    lens = [5, 20, 11, 40]
    for i, n in enumerate(lens):
        engine.add_request(f"r{i}", _prompt(n), SamplingParams(max_tokens=4))
    engine.step(decode=False)
    m = engine.metrics
    assert (m["prefill_steps"], m["admitted"]) == (1, 4)
    assert m["prefill_batch_tokens"] == 16 + 32 + 16 + 64
    slots = [engine._requests[f"r{i}"].slot for i in range(4)]
    tables = engine._block_tables.copy()
    owned = np.unique(tables[slots])
    owned = owned[owned > 0]
    assert len(owned) == 1 + 2 + 1 + 3  # pages of 16 positions

    toks = np.zeros((SLOTS, 64), np.int32)
    full = np.zeros(SLOTS, np.int32)
    for slot, n in zip(slots, lens):
        toks[slot, :n] = _prompt(n)
        full[slot] = n
    e = engine.ecfg
    want, cache = mr.prefill(
        engine.params, engine.mcfg,
        mr.init_cache(engine.mcfg, e.num_pages, e.page_size),
        jnp.asarray(toks), jnp.asarray(full), jnp.asarray(tables))
    for got_pages, want_pages in ((engine.cache.k, cache.k),
                                  (engine.cache.v, cache.v)):
        got_pages = np.asarray(got_pages[:, owned], np.float32)
        want_pages = np.asarray(want_pages[:, owned], np.float32)
        assert np.abs(want_pages).max() > 0.1
        np.testing.assert_allclose(got_pages, want_pages, rtol=2e-2, atol=2e-2)
    got = np.asarray(engine._prefill_logits)[slots]
    want = np.asarray(want)[slots]
    assert np.linalg.norm(got - want) <= 2e-2 * np.linalg.norm(want)
    # and the tokens the engine went on with are those logits' own
    firsts = [engine._requests[f"r{i}"].generated[0] for i in range(4)]
    assert firsts == list(got.argmax(-1))


def test_no_shape_depends_on_how_many_were_admitted(engine, monkeypatch):
    """The benchmark warms one request per reachable length bucket, one at a
    time, and nothing may compile in its window: after that warm-up, steps
    that admit 1, 3 and all eight requests at once, of mixed buckets, compile
    nothing, by the engine's own count and by the benchmark's (JAX's compile
    events: any program or eager operation at a shape not yet run)."""
    monkeypatch.syspath_prepend(REPO)
    from benchmarks.jobs.common import CompileCounter

    m = engine.metrics
    for n in (10, 20, 40, 100):  # the four buckets of 16..128
        engine.generate([_prompt(n)], SamplingParams(max_tokens=3))
    assert m["compiles"] == 5  # four prefill buckets, decode
    events = CompileCounter()
    for i, burst in enumerate([(7,), (12, 33, 90), (5, 17, 70, 9, 30, 101, 16,
                                                   64)]):
        admitted, calls = m["admitted"], m["prefill_steps"]
        for j, n in enumerate(burst):
            engine.add_request(f"b{i}-{j}", _prompt(n),
                               SamplingParams(max_tokens=2))
        engine.step()
        assert m["admitted"] - admitted == len(burst)
        assert m["prefill_steps"] - calls == 1
        while engine.has_unfinished():
            engine.step()
    assert m["compiles"] == 5
    assert events.count == 0


@pytest.mark.parametrize("others", [(), (9,), (40, 6, 100)],
                         ids=["alone", "one-short", "three-mixed"])
def test_seeded_first_token_is_independent_of_the_admitted_set(engine,
                                                                others):
    """A seeded request's tokens, the one sampled from prefill's logits
    first, do not depend on what else the step admitted or on its slot: its
    call is its own row at its own bucket, its stream its own seed's."""
    sp = SamplingParams(max_tokens=6, temperature=1.0, seed=1234)
    alone = engine.generate([_prompt(12)], sp)[0].token_ids
    for i, n in enumerate(others):  # admitted first: the seeded one's slot moves
        engine.add_request(f"o{i}", _prompt(n),
                           SamplingParams(max_tokens=6, temperature=0.7))
    engine.add_request("seeded", _prompt(12), sp)
    seeded = engine._requests["seeded"]
    engine.step()
    assert seeded.slot == len(others)
    assert engine.metrics["admitted"] == 1 + len(others) + 1
    while engine.has_unfinished():
        engine.step()
    assert seeded.generated == alone


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
