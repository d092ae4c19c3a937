"""What a read waited for and the gaps a request sees reach the benchmark as
data alone: seven files naming ``counter_ratio``, seven entries of
``BENCHMARK.json``'s ``per_layer``, in all four serve cells, and no edit to
any benchmark file that was there."""

import json
import os

import pytest

from benchmarks.registry import HERE, REPO, Cell
from benchmarks.trace import reduce

CELLS = ("internlm2-1.8b.alpaca-saturated",
         "olmoe-1b-7b.alpaca-saturated-b16",
         "moonlight-16b-a3b.longdoc-saturated-b32",
         "phi-4-mini-flash-reasoning.mathturns-saturated-b48")
# metric -> (numerator, denominator) among the keys of ``engine.metrics``,
# unit, and the hand value from WINDOW below
NEW = {
    "engine.prefill_time_share": ("prefill_phase_ms", "phase_ms", "ratio",
                                  0.4),
    "engine.prefill_phase_ms": ("prefill_phase_ms", "prefill_phase_calls",
                                "ms", 120.0),
    "engine.decode_phase_ms": ("decode_phase_ms", "decode_phase_calls", "ms",
                               22.5),
    "engine.read_wait_share": ("readback_ms", "phase_ms", "ratio", 0.8),
    "engine.itl_mean_ms": ("itl_ms", "itl_tokens", "ms", 37.5),
    "engine.itl_over_50ms_share": ("itl_over_50ms", "itl_tokens", "ratio",
                                   0.125),
    "engine.itl_over_200ms_share": ("itl_over_200ms", "itl_tokens", "ratio",
                                    0.03125),
}
# a made-up window: 150 prefill calls took 18 of 45 busy seconds, 1,200 decode
# steps the other 27; 57,600 tokens after a first, 7,200 of them behind a
# prefill phase and 1,800 behind a long one
WINDOW = {"prefill_phase_ms": 18000.0, "decode_phase_ms": 27000.0,
          "phase_ms": 45000.0, "prefill_phase_calls": 150,
          "decode_phase_calls": 1200, "readback_ms": 36000.0, "admitted": 151,
          "decode_steps": 1201, "steps": 1201, "generated_tokens": 57750,
          "itl_ms": 2160000.0, "itl_tokens": 57600, "itl_over_25ms": 9000,
          "itl_over_50ms": 7200, "itl_over_100ms": 5400,
          "itl_over_200ms": 1800, "itl_over_400ms": 40, "itl_over_800ms": 0}
# the parent commit's engine: every key it has that a reader above names
PARENT = {"readback_ms": 36000.0, "admitted": 151, "decode_steps": 1201,
          "steps": 1201, "generated_tokens": 57750}


def _values(cell, counters):
    ctx = {"trace": None, "spans": {}, "counters": counters, "facts": {}}
    return {k: v["value"] for k, v in cell.per_layer_values(ctx).items()}


@pytest.fixture(scope="module")
def cells():
    bench = os.path.join(REPO, "BENCHMARK.json")
    return [Cell(name, bench) for name in CELLS]


@pytest.mark.parametrize("metric", sorted(NEW))
def test_metric_is_a_file_an_entry_and_a_hand_value(cells, metric):
    num, den, unit, want = NEW[metric]
    with open(os.path.join(HERE, "layer_metrics", metric + ".json")) as f:
        raw = json.load(f)
    assert raw == {"reduce": "counter_ratio", "args": {"num": num, "den": den}}
    assert raw["reduce"] in reduce.REDUCTIONS
    for cell in cells:
        assert cell.reader(metric) == raw
        entry = {m["name"]: m for m in cell.per_layer()}[metric]
        assert entry == {
            "name": metric, "unit": unit, "source": "program_counter",
            "better": "higher" if metric == "engine.read_wait_share"
            else "lower",
            "layer": "engine (llm/engine.py)", "moves": "serve_tokens_per_s",
            "workloads": list(CELLS)}
        # a layer the benchmark already named, letter for letter
        assert entry["layer"] in {m["layer"]
                                  for m in cell.benchmark["per_layer"]
                                  if m["name"] not in NEW}
        assert _values(cell, WINDOW)[metric] == pytest.approx(want)


def test_new_entries_are_the_last_seven_and_list_the_serve_cells(cells):
    names = [m["name"] for m in cells[0].benchmark["per_layer"]]
    assert sorted(names[-7:]) == sorted(NEW) and len(set(names)) == len(names)
    # every cell that reports what they move lists them, and no other
    serving = next(m for m in cells[0].benchmark["end_to_end"]
                   if m["name"] == "serve_tokens_per_s")["workloads"]
    assert tuple(serving) == CELLS


def test_the_parents_counters_leave_all_seven_out(cells):
    """The parent's engine has none of the new counters, and every reader's
    DENOMINATOR is one of them (``counter_ratio`` reads a missing numerator
    as 0 and a missing denominator as nothing to read): no value, no error."""
    assert not {den for _, den, _, _ in NEW.values()} & set(PARENT)
    for cell in cells:
        got = _values(cell, PARENT)
        assert not set(NEW) & set(got)
        assert got["engine.tokens_per_step"] == 57750 / 1201   # as before
