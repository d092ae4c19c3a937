"""The serving engine's own measurement (CPU tier, tiny model): the counters
of ``engine.metrics``, the ``ray_tpu/`` spans on the profiler's clock and
the per-request spans of the GCS trace table. Counts and order only: a time
read here is a host time of the CPU backend and is compared with nothing."""

import contextlib
import os
import sys
import threading
import types

import numpy as np
import pytest

from ray_tpu.llm.config import EngineConfig, LLMConfig, SamplingParams
from ray_tpu.util import goodput, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KEYS = {
    "prefill_tokens", "decode_steps", "generated_tokens", "preempted",
    # decode steps whose rows rode a prefill call; a dense model's stay 0
    "riding_steps",
    "steps", "prefill_steps", "admitted", "prefill_batch_tokens", "compiles",
    # prefill program calls (of one row or several), and the shapes of
    # several rows compiled off the serving path
    "prefill_calls", "prefill_shapes_wanted", "prefill_shapes_ready",
    "step_ms", "host_ms", "readback_ms", "admit_ms", "prefill_dispatch_ms",
    "decode_dispatch_ms", "sample_dispatch_ms", "emit_ms",
    "between_steps_ms", "queue_wait_ms", "ttft_ms",
    # steps dispatched behind an unread one; tokens computed for nobody
    "overlapped_steps", "dropped_tokens",
    # sampler calls, and those in which no slot had a positive temperature
    "sample_calls", "sample_greedy_calls",
    # what each blocking read waited for, by kind, and both
    "prefill_phase_ms", "decode_phase_ms", "phase_ms",
    "prefill_phase_calls", "decode_phase_calls",
    # the positions of the prefill calls those reads waited behind, padded and
    # real: ``prefill_batch_tokens`` and ``prefill_tokens`` again, but moving
    # with the read as ``prefill_phase_ms`` does
    "prefill_phase_positions", "prefill_phase_real_positions",
    # reads that waited over a second for each call they stood behind
    "stalled_reads", "stalled_read_ms",
    # every slot's row of every decode step, by what filled or emptied it
    "slot_steps", "slot_steps_live", "slot_steps_starved",
    "slot_steps_page_blocked", "slot_steps_prefilling",
    # the gaps between a request's tokens: sum, number, and how many were
    # strictly longer than each rung
    "itl_ms", "itl_tokens", "itl_over_25ms", "itl_over_50ms",
    "itl_over_100ms", "itl_over_200ms", "itl_over_400ms", "itl_over_800ms",
    # what routing did; a dense model's (this one's) stay 0
    "moe_decode_layer_steps", "moe_decode_assignments",
    "moe_decode_experts_touched", "moe_decode_max_load",
    "moe_decode_routed_assignments",
    # those of them that fell on a zero-compute expert
    "moe_decode_zero_assignments",
    # what a latent cache's decode read; 0 without one
    "mla_decode_live_tokens", "mla_decode_read_tokens",
    # a model with layer_kinds: its shared layer's pages, rings, recurrent
    # rows and the cross-decoder's prefill rows; 0 without them
    "shared_kv_live_tokens", "shared_kv_read_tokens", "window_live_tokens",
    "prefill_cross_rows",
    # the slots whose Mamba-2 state a decode step moves, and those of them
    # that decode; 0 without such layers
    "ssd_step_slots", "ssd_step_live_slots",
    # such a model's decode steps, and those of them that wrote the states
    # back (a step in FOLD, and every riding one); 0 without such layers
    "ssd_steps", "ssd_fold_steps",
    # the slots whose Mamba-1 state a decode step moves, those of them that
    # decode, and such a model's decode steps; 0 without such layers
    "ssm_step_slots", "ssm_step_live_slots", "ssm_steps",
    # the same two for delta-rule layers, and the chunks their prefill
    # kernel's grid has and passes over; 0 without such layers
    "kda_step_slots", "kda_step_live_slots",
    "kda_scan_chunks", "kda_scan_chunks_skipped",
    # the same four for power-retention layers; 0 without such layers
    "retention_state_slots", "retention_live_slots",
    "retention_scan_chunks", "retention_scan_chunks_skipped",
    # such a model's decode steps, and those of them that wrote the states
    # back (a step in FOLD, and every riding one); 0 without such layers
    "retention_steps", "retention_fold_steps",
    # the query blocks a prefill call's flash kernel has a head, and those
    # behind their row's end that it passes over
    "flash_q_blocks", "flash_q_blocks_skipped"}
LADDER = tuple(f"itl_over_{n}ms" for n in (25, 50, 100, 200, 400, 800))
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

        def set_metadata(self, **attrs):  # what a span learns on its way
            self.attrs.update(attrs)

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
    # a call that runs no program is no step: the last one of a run only
    # reads what the one before it dispatched, and an idle one does nothing
    assert 0 < m["steps"] == busy - 1 < 29
    assert m["steps"] <= m["prefill_steps"] + m["decode_steps"]
    # every step but the first was dispatched before the one before was read
    assert m["overlapped_steps"] == m["steps"] - 1
    # one sampler call a prefill phase and one a decode step; nobody sampled
    assert m["sample_calls"] == m["prefill_steps"] + m["decode_steps"]
    assert m["riding_steps"] == 0  # this model's prefill call carries none
    assert m["sample_greedy_calls"] == m["sample_calls"]
    # no delta-rule layer: nothing for a kda_scan to walk or pass over
    assert m["kda_scan_chunks"] == m["kda_scan_chunks_skipped"] == 0
    # no power-retention layer: no state of one for a step to move or a
    # scan to build
    assert m["retention_state_slots"] == m["retention_live_slots"] == 0
    assert m["retention_steps"] == m["retention_fold_steps"] == 0
    # no Mamba-2 layer either (with such layers ``ssd_steps`` is
    # ``decode_steps`` and ``riding_steps <= ssd_fold_steps``:
    # ``tests/test_granite_hybrid.py``)
    assert m["ssd_steps"] == m["ssd_fold_steps"] == m["ssd_step_slots"] == 0
    # nor a Mamba-1 layer (``tests/test_jamba.py``)
    assert m["ssm_steps"] == m["ssm_step_slots"] == m["ssm_step_live_slots"] == 0
    assert m["retention_scan_chunks"] == m["retention_scan_chunks_skipped"] == 0
    # a prompt here fills its row's one query block, a padding row none
    assert (m["flash_q_blocks"] - m["flash_q_blocks_skipped"]
            == m["admitted"] > 0)
    # no expert, least of all a zero-compute one
    assert m["moe_decode_zero_assignments"] == 0
    # a token computed for a request that EOS had ended is not a generated one
    assert m["generated_tokens"] <= sum(4 + i for i in range(SLOTS + 2))
    assert m["between_steps_ms"] > 0
    assert m["compiles"] == 2  # one prefill bucket, decode
    _phases_and_gaps_add_up(m, first_tokens=SLOTS + 2)


def _phases_and_gaps_add_up(m, first_tokens, slots=SLOTS):
    """Every read's wait went to one kind; every emitted token but a
    request's first saw one gap, counted on the rungs it is longer than."""
    assert m["prefill_phase_ms"] > 0 and m["decode_phase_ms"] > 0
    assert m["prefill_phase_ms"] + m["decode_phase_ms"] == pytest.approx(
        m["phase_ms"])
    # nothing is left unread: every call dispatched was waited for
    assert m["prefill_phase_calls"] == m["prefill_calls"] <= m["admitted"]
    assert m["decode_phase_calls"] == m["decode_steps"]
    # so the positions dated by the read are those counted at dispatch
    assert m["prefill_phase_positions"] == m["prefill_batch_tokens"]
    assert m["prefill_phase_real_positions"] == m["prefill_tokens"]
    _slot_account_closes(m, slots)
    assert m["itl_tokens"] == m["generated_tokens"] - first_tokens > 0
    rungs = [m["itl_tokens"]] + [m[k] for k in LADDER]
    assert all(a >= b >= 0 for a, b in zip(rungs, rungs[1:]))
    assert m["itl_ms"] > 0


def _slot_account_closes(m, slots=SLOTS):
    """Every row of every decode step is in exactly one state."""
    assert m["slot_steps"] == slots * m["decode_steps"]
    assert m["slot_steps"] == (
        m["slot_steps_live"] + m["slot_steps_starved"]
        + m["slot_steps_page_blocked"] + m["slot_steps_prefilling"])
    # a row that decodes brings a token, emitted or dropped
    assert m["slot_steps_live"] == (
        m["generated_tokens"] + m["dropped_tokens"] - m["admitted"])


def _small(params, model_overrides=None, **engine):
    """Four slots on pages of 16 positions."""
    from ray_tpu.llm.engine import JaxLLMEngine

    geometry = dict(max_num_seqs=4, max_model_len=64, page_size=16,
                    prefill_bucket_min=BUCKET)
    return JaxLLMEngine(LLMConfig(
        model_id="tiny", engine_config=EngineConfig(**geometry, **engine),
        model_overrides=model_overrides or {"attention_impl": "xla"}),
        params=params, seed=0)


def _starved(params):
    """Three requests of four tokens on four slots: every step decodes three
    rows and the fourth is empty because nobody was offered."""
    eng = _small(params)
    for i in range(3):
        eng.add_request(f"r{i}", _prompt(8 + i), SamplingParams(max_tokens=4))
    return eng, [], dict(decode_steps=3, live=9, starved=3), [
        dict(waiting=0, free_slots=1, free_pages=16 - 3, stopped="queue")]


def _page_blocked(params):
    """Four usable pages: ``a`` and ``b``, two pages each, take them all, and
    ``c`` waits at the head of the queue beside two free slots until both have
    ended; it then decodes alone, and nobody waits."""
    eng = _small(params, num_pages=5)
    for rid in "ab":
        eng.add_request(rid, _prompt(20), SamplingParams(max_tokens=4))
    eng.add_request("c", _prompt(20), SamplingParams(max_tokens=3))
    return eng, [], dict(decode_steps=5, live=3 * 2 + 2 * 1,
                         page_blocked=3 * 2, starved=2 * 3), [
        dict(waiting=1, free_slots=2, free_pages=0, stopped="pages")] * 3 + [
        dict(waiting=0, free_slots=3, free_pages=2, stopped="queue")]


def _riding(params):
    """A model whose prefill call carries a decode step. ``a`` decodes alone
    for two steps; the step that admits ``b`` rides ``b``'s prefill call: of
    its four rows ``a``'s decodes, ``b``'s slot is being prefilled (its first
    decode row is the next step's) and two are empty."""
    import jax.numpy as jnp

    eng = _small(None, expect_state_layers=1, expect_conv_taps=3,
                 model_overrides=dict(
                     n_layers=2, layer_kinds=("conv", "full"), block="rms",
                     rope_kinds=("full",), conv_taps=3, dtype=jnp.float32))
    eng.add_request("a", _prompt(5), SamplingParams(max_tokens=6))
    late = [(2, "b", _prompt(9), SamplingParams(max_tokens=3))]
    return eng, late, dict(decode_steps=5, riding_steps=1, live=5 + 2,
                           prefilling=1, starved=3 + 3 + 2 + 2 + 2), [
        dict(waiting=0, free_slots=3, free_pages=16 - 1, stopped="queue")]


@pytest.mark.parametrize("scene", [_starved, _page_blocked, _riding],
                         ids=["starved", "page-blocked", "riding"])
def test_every_row_of_a_decode_step_is_in_one_state(params, recorder, scene):
    """``slot_steps`` by hand: a row decodes, or its slot went in this very
    step to a request whose prompt the carrying call runs, or it is empty,
    because nobody waited or because the queue's head lacked pages; and
    ``engine.admit`` says which, step by step."""
    eng, late, want, admits = scene(params)
    calls, done = 0, {}
    while eng.has_unfinished() or late:
        for _, rid, prompt, sp in [x for x in late if x[0] == calls]:
            eng.add_request(rid, prompt, sp)
        late = [x for x in late if x[0] != calls]
        done.update((o.request_id, o) for o in eng.step() if o.finished)
        calls += 1
    # none met a stop token: the hand count holds
    assert {o.finish_reason for o in done.values()} == {"length"}
    m = eng.metrics
    got = {k: m[k] for k in ("decode_steps", "riding_steps")}
    got.update((k, m["slot_steps_" + k])
               for k in ("live", "starved", "page_blocked", "prefilling"))
    assert got == dict(dict.fromkeys(got, 0), **want)
    assert m["dropped_tokens"] == 0
    _slot_account_closes(m, slots=4)
    seen = [a for _, n, a in recorder if n == "ray_tpu/engine.admit"]
    assert seen[:len(admits)] == admits


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
    assert engine.step() == []  # dispatched, nothing read yet
    assert (m["prefill_steps"], m["admitted"]) == (1, 8)
    assert m["prefill_tokens"] == sum(lens) == 60
    assert m["prefill_batch_tokens"] == 8 * BUCKET == 128
    assert (m["decode_steps"], m["generated_tokens"]) == (1, 0)
    assert ninth.t_admitted == 0.0
    waited = m["queue_wait_ms"]
    # r0 ends by length with the token of that call's decode step: the host
    # counts that without the token, so the very next call admits the ninth
    # into r0's slot, and only then reads r0's two tokens
    outs = engine.step()
    assert (ninth.slot, m["admitted"], m["prefill_steps"]) == (0, 9, 2)
    assert [(o.request_id, len(o.token_ids), o.finished) for o in outs[:1]
            + outs[8:9]] == [("r0", 1, False), ("r0", 2, True)]
    # two sampler calls of eight rows read: a token is emitted, or dropped
    # because its request had ended by EOS (_prompt(7)'s first token is EOS)
    assert m["generated_tokens"] + m["dropped_tokens"] == 16
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
    for got_pages, want_pages in zip(engine.cache["dense"], cache["dense"]):
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
    time, and nothing may compile in its window: that warm-up's first
    request also asks for every bucket's calls of two and of four rows, off
    the serving path, and once they are made (the benchmark's reference check
    and lead-in give it the time) steps that admit 1, 3 and all eight requests at
    once, of mixed buckets, alone or sharing calls, compile nothing, by the
    engine's own count and by the benchmark's (JAX's compile events: any
    program or eager operation at a shape not yet run)."""
    monkeypatch.syspath_prepend(REPO)
    from benchmarks.jobs.common import CompileCounter

    m = engine.metrics
    for n in (10, 20, 40, 100):  # the four buckets of 16..128
        engine.generate([_prompt(n)], SamplingParams(max_tokens=3))
    assert m["compiles"] == 5  # four prefill buckets, decode
    assert engine._row_shapes.wait(300)
    # [2, 16], [4, 16], [2, 32], [4, 32], [2, 64]: 128 padded tokens at most
    assert m["prefill_shapes_wanted"] == m["prefill_shapes_ready"] == 5
    # the counter hears the whole process: what the engines of the tests
    # before this one asked for off their serving paths is made first
    for maker in threading.enumerate():
        if maker.name == "prefill-shapes":
            maker.join(300)
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
    assert m["compiles"] == 5 and m["prefill_shapes_ready"] == 5
    assert m["prefill_calls"] == 4 + 1 + 2 + 4 < m["admitted"] == 4 + 12
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
    """A call dispatches its programs first and reads afterwards, and what it
    reads is the call BEFORE it: one ``readback`` and ``emit`` a sampler call,
    in the order they were dispatched."""
    engine.add_request("a", _prompt(5), SamplingParams(max_tokens=3))
    assert engine.step() == []
    names = [(d, n.removeprefix("ray_tpu/engine.")) for d, n, _ in recorder]
    assert all(n.startswith("ray_tpu/") for _, n, _ in recorder)
    assert names == [
        (0, "step"), (1, "admit"), (1, "prefill_dispatch"), (2, "compile"),
        (1, "sample_dispatch"), (1, "decode_dispatch"), (2, "compile"),
        (1, "sample_dispatch")]
    attrs = {n: a for _, n, a in recorder}
    # why the step's other seven rows run empty: nobody else was offered
    assert attrs["ray_tpu/engine.admit"] == {
        "waiting": 0, "free_slots": SLOTS - 1, "stopped": "queue",
        "free_pages": engine.ecfg.num_pages - 2}
    assert attrs["ray_tpu/engine.prefill_dispatch"] == {
        "bucket": BUCKET, "admitted": 1, "calls": 1, "rows": 1, "riding": 0}
    assert attrs["ray_tpu/engine.compile"]["program"] == "decode"
    assert attrs["ray_tpu/engine.decode_dispatch"] == {"overlapped": 0}
    assert attrs["ray_tpu/engine.sample_dispatch"] == {"greedy": True}
    # a shape this engine has used is not a compile again; the second call
    # reads the two sampler calls of the first behind its own dispatch
    del recorder[:]
    assert [len(o.token_ids) for o in engine.step()] == [1, 2]
    assert [n for _, n, _ in recorder] == [
        "ray_tpu/engine." + p for p in (
            "step", "admit", "decode_dispatch", "sample_dispatch", "readback",
            "emit", "readback", "emit")]
    assert recorder[1][2]["stopped"] == "queue"
    assert recorder[2][2] == {"overlapped": 1}
    # the third token was the second call's: the third call runs nothing
    del recorder[:]
    out, = engine.step()
    assert (len(out.token_ids), out.finish_reason) == (3, "length")
    assert [n for _, n, _ in recorder] == [
        "ray_tpu/engine." + p for p in ("step", "admit", "readback", "emit")]
    assert engine.metrics["steps"] == 2 and not engine.has_unfinished()


def test_benchmark_wrappers_still_see_step_and_sample(engine, recorder,
                                                      tmp_path, monkeypatch):
    """The benchmark's ``_annotate_engine`` replaces ``eng.step`` and
    ``eng._sample`` from outside: both stay methods under those names, and
    its two spans still enclose the engine's own. ``_sample`` dispatches and
    does not wait, so ``bench/sample_readback`` holds the sampler's dispatch
    alone, once a sampler call; the blocking read is ``engine.readback``,
    later and outside it."""
    monkeypatch.syspath_prepend(REPO)
    from benchmarks.jobs import common
    from benchmarks.jobs.serve import BenchLLMServer

    BenchLLMServer._annotate_engine(types.SimpleNamespace(
        engine=engine, _tracer=common.Tracer(True, str(tmp_path))))
    out = engine.generate([_prompt(6)], SamplingParams(max_tokens=3))[0]
    assert len(out.token_ids) == 3 or out.finish_reason == "stop"
    names = [(d, n) for d, n, _ in recorder]
    assert names[:2] == [(0, "bench/engine.step"), (1, "ray_tpu/engine.step")]
    wraps = [i for i, n in enumerate(names)
             if n == (2, "bench/sample_readback")]
    steps = engine.metrics["prefill_steps"] + engine.metrics["decode_steps"]
    assert len(wraps) == steps
    for i in wraps:
        assert names[i + 1] == (3, "ray_tpu/engine.sample_dispatch")
        assert names[i + 2][0] <= 2
    reads = [d for d, n in names if n == "ray_tpu/engine.readback"]
    assert reads == [2] * steps


@pytest.fixture
def llm_spans(monkeypatch):
    """``RAY_TPU_ENABLE_TRACING`` for one test; call it for the ``llm`` spans
    buffered since."""
    monkeypatch.setattr(tracing, "_enabled", None)   # restored afterwards,
    monkeypatch.setenv("RAY_TPU_ENABLE_TRACING", "0")  # with the flag
    tracing.enable()

    def take():
        with tracing._lock:
            spans = [s for s in tracing._buffer if s["cat"] == "llm"]
            tracing._buffer.clear()
        return spans
    yield take
    take()


def test_finished_request_leaves_three_spans_under_its_parent(engine,
                                                              llm_spans):
    trace_id, parent = tracing.new_trace_id(), tracing.new_span_id()
    token = tracing.set_context(trace_id, parent)
    try:
        engine.add_request("traced", _prompt(7), SamplingParams(max_tokens=4))
    finally:
        tracing.reset_context(token)
    engine.add_request("bare", _prompt(7), SamplingParams(max_tokens=4))
    while engine.has_unfinished():
        engine.step()
    spans = llm_spans()
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


# -- a step's tokens are read one step late ------------------------------------


def _pages_conserved(engine):
    """Every page but the scratch one is free or owned by one live request."""
    owned = [p for r in engine._slots if r is not None for p in r.pages]
    pages = sorted(list(engine._free_pages) + owned)
    return pages == list(range(1, engine.ecfg.num_pages))


def test_overlap_needs_a_step_to_hide_behind(engine):
    """One request of one token: its only step has no predecessor, and the
    call after it runs nothing; ``has_unfinished()`` holds the caller until
    that call has read the token."""
    m = engine.metrics
    engine.add_request("one", _prompt(5), SamplingParams(max_tokens=1))
    assert engine.step() == []
    # the host counted the request out at dispatch: nothing waits, no slot is
    # active, and the token is still to be read
    assert not engine._waiting and engine.num_active() == 0
    assert engine._slots == [None] * SLOTS and _pages_conserved(engine)
    assert engine.has_unfinished()
    out, = engine.step()
    assert (len(out.token_ids), out.finished) == (1, True)
    assert not engine.has_unfinished() and engine.step() == []
    assert (m["steps"], m["overlapped_steps"], m["decode_steps"]) == (1, 0, 0)


@pytest.mark.parametrize("at", [0, 2], ids=["first-token", "third-token"])
def test_a_stop_token_is_seen_one_step_late(engine, at):
    """The slot of a request that a stop token ends has run on when the host
    reads the token: what it computed since is dropped, never emitted, and
    the pages go back to the pool once."""
    sp = SamplingParams(max_tokens=8)
    free = engine.generate([_prompt(9)], sp)[0].token_ids
    stop = free[at]
    assert stop not in free[:at]
    m = engine.metrics
    before = dict(m)
    engine.add_request("s", _prompt(9), SamplingParams(
        max_tokens=8, stop_token_ids=(stop,)))
    seen = []
    while engine.has_unfinished():
        seen += engine.step()
        assert _pages_conserved(engine)
    assert [o.token_ids for o in seen] == [free[:i + 1] for i in range(at + 1)]
    assert [o.finished for o in seen] == [False] * at + [True]
    assert seen[-1].finish_reason == "stop"
    # the token is read behind the dispatch of the next step, and the first
    # one behind its own phase's decode step too
    late = 1 if at else 2
    assert m["dropped_tokens"] - before["dropped_tokens"] == late
    assert m["generated_tokens"] - before["generated_tokens"] == at + 1
    # a dropped token's step IS a decode step
    assert m["decode_steps"] - before["decode_steps"] == at + late
    assert len(engine._free_pages) == engine.ecfg.num_pages - 1


def test_a_full_engine_overlaps_every_step_but_the_first(engine):
    """Twelve requests over eight slots, answers of different lengths: every
    call that runs a program does so with the call before unread, and a slot
    that ends by length is admitted into by the very next call."""
    m = engine.metrics
    for i in range(SLOTS + 4):
        engine.add_request(f"r{i}", _prompt(8 + i),  # none of these meets EOS
                           SamplingParams(max_tokens=2 + i % 5))
    done, held = {}, [None]  # held[c]: who has each slot after call c
    while engine.has_unfinished():
        for o in engine.step():
            if o.finished:
                done[o.request_id] = o
        held.append([r and r.request_id for r in engine._slots])
        assert _pages_conserved(engine)
    assert len(done) == SLOTS + 4
    assert all(len(done[f"r{i}"].token_ids) == 2 + i % 5
               for i in range(SLOTS + 4))
    calls = len(held) - 1
    assert m["steps"] == calls - 1 and m["overlapped_steps"] == m["steps"] - 1
    # r0 and r5 end with the second token, which call 1's decode step is
    # asked for: their slots are free when it returns and r8 and r9 have them
    # after call 2; r1 and r6 end with call 2's token, r10 and r11 follow
    # (r10, two tokens again, comes and goes within call 3)
    assert held[1] == [None, "r1", "r2", "r3", "r4", None, "r6", "r7"]
    assert held[2] == ["r8", None, "r2", "r3", "r4", "r9", None, "r7"]
    assert held[3][:2] + held[3][5:7] == ["r8", None, "r9", "r11"]


# -- what a read waited for, and the gaps a request sees -----------------------


def test_readback_names_what_it_waited_for(engine, recorder):
    """Three requests admitted in one phase, buckets 16, 32 and 16: the read
    of their first tokens waited behind three prefill calls, the largest at
    32; the read of the decode step's tokens behind that one step."""
    for i, n in enumerate((5, 20, 11)):
        engine.add_request(f"r{i}", _prompt(n), SamplingParams(max_tokens=4))
    engine.step()
    assert not [n for _, n, _ in recorder if n.endswith("readback")]
    engine.step()
    reads = [a for _, n, a in recorder if n == "ray_tpu/engine.readback"]
    assert reads == [
        {"kind": "prefill", "calls": 3, "bucket": 32, "rows": 3},
        {"kind": "decode", "calls": 1, "bucket": 0, "rows": 3}]
    m = engine.metrics
    assert (m["prefill_phase_calls"], m["decode_phase_calls"]) == (3, 1)


def test_a_preempted_requests_gap_spans_its_second_prefill(params):
    """Two slots, pages for one and a half long answers: a request sent back
    to the queue is prefilled again from all its tokens, and the token that
    second prefill gives it has a gap like any other, its client waited for
    it. So every emitted token but a REQUEST's first has one, however many
    times the request was admitted."""
    from ray_tpu.llm.engine import JaxLLMEngine

    tight = EngineConfig(max_num_seqs=2, max_model_len=64, page_size=16,
                         num_pages=7, prefill_bucket_min=BUCKET)
    eng = JaxLLMEngine(LLMConfig(
        model_id="tiny", engine_config=tight,
        model_overrides={"attention_impl": "xla"}), params=params, seed=0)
    outs = eng.generate([list(range(3, 33)), list(range(40, 70))],
                        SamplingParams(max_tokens=30))
    m = eng.metrics
    assert m["preempted"] >= 1 and m["admitted"] == 2 + m["preempted"]
    assert m["generated_tokens"] == sum(len(o.token_ids) for o in outs)
    _phases_and_gaps_add_up(m, first_tokens=2, slots=2)
    # a preempted request waits for pages, not for a slot: from its
    # preemption to its second admission the pool set the batch
    assert m["slot_steps_page_blocked"] >= 1
    assert m["slot_steps_prefilling"] == 0  # nothing rides in a dense model


class _Clock:
    """Stands in for the engine module's ``time``: it moves only when the
    device below makes a read wait."""

    def __init__(self):
        self.ns = 5_000_000_000

    def perf_counter_ns(self):
        return self.ns

    def perf_counter(self):
        return self.ns / 1e9

    def time(self):
        return 1.7e9 + self.ns / 1e9

    def monotonic_ns(self):
        return self.ns


class _Late:
    """A sampler call's tokens on a device that may still be busy: reading
    them waits until the call is done."""

    def __init__(self, tokens, clock, ready_ns):
        self.tokens, self.clock, self.ready_ns = tokens, clock, ready_ns

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        self.clock.ns = max(self.clock.ns, self.ready_ns)
        return np.asarray(self.tokens)


class _Device:
    """The engine's ``model_runner`` with the device's clock beside it: the
    real programs compute at once, and each is said to have taken
    ``cost_ms(program, bucket)`` on a device that runs them one after another
    and is never ahead of the host's dispatch."""

    def __init__(self, mr, clock, cost_ms):
        self.mr, self.clock, self.cost_ms = mr, clock, cost_ms
        self.busy_until = 0

    def __getattr__(self, name):
        fn = getattr(self.mr, name)

        def call(*args, **kwargs):
            args = [a.tokens if isinstance(a, _Late) else a for a in args]
            out = fn(*args, **kwargs)
            bucket = args[3].shape[1] if name == "prefill" else 0
            self.busy_until = max(self.busy_until, self.clock.ns) \
                + int(self.cost_ms(name, bucket) * 1e6)
            if name == "sample_tokens":
                return _Late(out, self.clock, self.busy_until)
            return out
        return call


DECODE_MS, SAMPLE_MS, SHORT_MS = 10, 1, 5


@pytest.fixture
def timed(engine, monkeypatch):
    """``timed(long_ms)``: the engine on a device whose decode step takes 10
    ms, a sampler call 1, a prefill call 5 at the smallest bucket and
    ``long_ms`` at any other, with a host that takes no time at all."""
    from ray_tpu.llm import engine as engine_module

    clock = _Clock()
    monkeypatch.setattr(engine_module, "time", clock)

    def build(long_ms):
        def cost_ms(program, bucket):
            return {"decode_step": DECODE_MS, "sample_tokens": SAMPLE_MS,
                    "prefill": SHORT_MS if bucket == BUCKET else long_ms,
                    }.get(program, 0)
        engine._mr = _Device(engine._mr, clock, cost_ms)
        return engine
    return build


def _add(engine, rid, n, max_tokens):
    from ray_tpu.llm import engine as engine_module

    engine.add_request(rid, _prompt(n), SamplingParams(max_tokens=max_tokens))
    # the dataclass took the real clock's function when it was defined
    engine._requests[rid].t_added = engine_module.time.perf_counter()


@pytest.mark.parametrize("long_ms,over", [(40, 2), (200, 4), (800, 6),
                                          (999, 6), (3500, 6)],
                         ids=["40ms", "200ms", "800ms", "999ms", "stalled"])
def test_a_decoder_waits_behind_anothers_prefill(timed, caplog, long_ms, over):
    """``a`` decodes alone, a token every 11 ms (decode step + sampler).
    ``b``'s prompt takes the 64 bucket: its prefill call of ``long_ms`` and
    the phase's sampler call run between two of ``a``'s decode steps, so ONE
    of ``a``'s gaps is ``long_ms`` + 12 and lands on every rung under it, and
    the prefill reads waited ``long_ms`` + 1 more than before. A read that
    waited over a second for its one call stalled: it is counted with its
    wait and the engine says so in one line; 999 + 1 ms behind a call and its
    sampler call is the longest wait that is none."""
    caplog.set_level("WARNING", logger="ray_tpu.llm.engine")
    engine = timed(long_ms)
    m = engine.metrics
    _add(engine, "a", 5, 12)
    for _ in range(4):
        engine.step()
    a = engine._requests["a"]
    assert len(a.generated) == 4  # the fourth call's own token is unread
    before = dict(m)
    assert before["prefill_phase_ms"] == pytest.approx(SHORT_MS + SAMPLE_MS)
    assert before["decode_phase_ms"] == pytest.approx(
        3 * (DECODE_MS + SAMPLE_MS))
    assert (before["itl_tokens"], before["itl_over_25ms"]) == (3, 0)
    assert before["itl_ms"] == pytest.approx(3 * (DECODE_MS + SAMPLE_MS))
    _add(engine, "b", 40, 3)
    while engine.has_unfinished():
        engine.step()
    assert m["prefill_phase_ms"] - before["prefill_phase_ms"] \
        == pytest.approx(long_ms + SAMPLE_MS)
    assert m["prefill_phase_ms"] + m["decode_phase_ms"] == pytest.approx(
        m["phase_ms"])
    # the device was never idle between the first dispatch and the last read
    assert m["phase_ms"] == pytest.approx(
        SHORT_MS + long_ms + (2 + m["decode_steps"]) * SAMPLE_MS
        + m["decode_steps"] * DECODE_MS)
    assert a.max_gap_ns == (long_ms + DECODE_MS + 2 * SAMPLE_MS) * 1_000_000
    assert [m[k] for k in LADDER] == [1] * over + [0] * (6 - over)
    assert m["itl_tokens"] == m["generated_tokens"] - 2 == 11 + 2
    assert m["itl_ms"] == pytest.approx(
        13 * (DECODE_MS + SAMPLE_MS) + long_ms + SAMPLE_MS)
    # what the reads waited for is what the host was blocked for: it took no
    # time of its own
    assert m["readback_ms"] == pytest.approx(m["phase_ms"])
    stalled = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("stalled read")]
    if long_ms + SAMPLE_MS <= 1000:
        assert (m["stalled_reads"], m["stalled_read_ms"], stalled) == (0, 0, [])
    else:
        assert (m["stalled_reads"], m["stalled_read_ms"]) == (
            1, pytest.approx(long_ms + SAMPLE_MS))
        # b's phase was dispatched behind a's fourth decode step
        assert stalled == [
            f"stalled read: kind=prefill calls=1 bucket=64 rows=1 waited "
            f"{long_ms + SAMPLE_MS} ms, {long_ms + DECODE_MS + 2 * SAMPLE_MS} "
            f"ms after its dispatch; compiled since: False"]


def test_decode_span_says_how_many_tokens_and_the_longest_gap(timed,
                                                              llm_spans):
    """With ``RAY_TPU_ENABLE_TRACING`` the finished request's
    ``engine.decode`` span names WHICH request stalled: ``a`` behind ``b``'s
    300 ms prefill, ``b`` behind nobody's."""
    engine = timed(300)
    _add(engine, "a", 5, 8)
    for _ in range(3):
        engine.step()
    _add(engine, "b", 40, 3)
    while engine.has_unfinished():
        engine.step()
    spans = {(s["request_id"], s["name"]): s for s in llm_spans()}
    assert len(spans) == 6
    a, b = spans["a", "engine.decode"], spans["b", "engine.decode"]
    assert (a["tokens"], b["tokens"]) == (8, 3)
    assert a["max_gap_ms"] == 300 + DECODE_MS + 2 * SAMPLE_MS
    assert b["max_gap_ms"] == DECODE_MS + SAMPLE_MS
    # the span ends when the last token reached the host, and lasts as long
    # as its gaps together
    assert b["dur"] == pytest.approx(2 * (DECODE_MS + SAMPLE_MS) / 1e3,
                                     abs=1e-5)  # doubles around 1.7e9 s
    assert "tokens" not in spans["a", "engine.prefill"]
    assert "max_gap_ms" not in spans["a", "engine.queued"]


def test_mamba_kind_runs_under_its_two_scopes_and_kernel_names():
    """The "mamba" kind of ``_forward`` (a two-layer model: Mamba, attention)
    puts its prompt side under ``ssm.prefill`` and its step side under
    ``ssm.step`` (the names the decoder-hybrid-decoder's loops use), around
    kernels a device trace finds by name: ``ssm_scan``; ``ssm_step`` in a
    decode step alone, ``ssm_riding`` where the step rides a prefill call."""
    import dataclasses

    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    import test_jamba
    from ray_tpu.llm import model_runner as mr
    from ray_tpu.models.transformer import CONFIGS, Transformer

    published = dict(test_jamba.PUBLISHED, num_hidden_layers=2,
                     attn_layer_period=2, attn_layer_offset=1)
    cfg = dataclasses.replace(CONFIGS["tiny"], **dict(
        test_jamba.ref.program_overrides(published, 64), dtype=jnp.float32,
        remat=False))
    assert cfg.layer_kinds == ("mamba", "full")
    B, MP, P = 3, 4, 16
    toks = jnp.zeros((1, BUCKET), jnp.int32)
    params = jax.eval_shape(lambda: nn.meta.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), toks)))
    cache = jax.eval_shape(lambda: mr.init_cache(cfg, 1 + B * MP, P, B))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)   # noqa: E731
    step = (i32(B), i32(B), i32(B, MP), jax.ShapeDtypeStruct((B,), jnp.bool_))
    decode = mr.decode_step.lower(params, cfg, cache, *step).as_text(
        debug_info=True)
    assert "ssm.step" in decode and "ssm.prefill" not in decode
    assert "ssm_step" in decode
    assert "ssm_riding" not in decode and "ssm_scan" not in decode
    call = mr.prefill.lower(params, cfg, cache, i32(1, BUCKET), i32(1),
                            i32(1, MP), i32(1), step).as_text(debug_info=True)
    for name in ("ssm.prefill", "ssm.step", "ssm_scan", "ssm_riding"):
        assert name in call, name
