"""The engine's whole-window account of its capacity reaches the benchmark as
data alone: a file a metric naming ``counter_ratio`` over two keys of
``engine.metrics``, an entry a metric in ``BENCHMARK.json``'s ``per_layer``,
in cells that report ``serve_tokens_per_s``, and no edit to any benchmark
file that was there."""

import json
import os

import pytest

from benchmarks.registry import HERE, REPO, Cell
from benchmarks.trace import reduce

# metric -> (numerator, denominator, unit, better, the hand value from WINDOW)
NEW = {
    "engine.tokens_per_busy_ms": ("generated_tokens", "phase_ms",
                                  "tokens/ms", "higher", 1.25),
    "engine.slot_live_share": ("slot_steps_live", "slot_steps", "ratio",
                               "higher", 0.875),
    "engine.slot_starved_share": ("slot_steps_starved", "slot_steps",
                                  "ratio", "lower", 0.0625),
    "engine.slot_page_blocked_share": ("slot_steps_page_blocked",
                                       "slot_steps", "ratio", "lower",
                                       0.03125),
    "engine.slot_prefilling_share": ("slot_steps_prefilling", "slot_steps",
                                     "ratio", "lower", 0.03125),
    "engine.prefill_positions_per_ms": ("prefill_phase_positions",
                                        "prefill_phase_ms", "positions/ms",
                                        "higher", 64.0),
    "engine.prefill_real_positions_per_ms": ("prefill_phase_real_positions",
                                             "prefill_phase_ms",
                                             "positions/ms", "higher", 40.0),
    "engine.stalled_time_share": ("stalled_read_ms", "phase_ms", "ratio",
                                  "lower", 0.1),
}
# a made-up window: 1,500 decode steps of 32 slots in 40 busy seconds, 18 of
# them behind prefill calls over 1,152,000 padded positions, and one read
# that stalled for 4 s
WINDOW = {"generated_tokens": 50000, "phase_ms": 40000.0,
          "prefill_phase_ms": 18000.0, "decode_steps": 1500,
          "slot_steps": 48000, "slot_steps_live": 42000,
          "slot_steps_starved": 3000, "slot_steps_page_blocked": 1500,
          "slot_steps_prefilling": 1500,
          "prefill_phase_positions": 1152000,
          "prefill_phase_real_positions": 720000,
          "stalled_reads": 1, "stalled_read_ms": 4000.0}
# the parent commit's engine: the keys it has that a reader above names
PARENT = {"generated_tokens": 50000, "phase_ms": 40000.0,
          "prefill_phase_ms": 18000.0, "decode_steps": 1500}


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _serving(bench):
    return next(m for m in bench["end_to_end"]
                if m["name"] == "serve_tokens_per_s")["workloads"]


def _values(cell, counters):
    ctx = {"trace": None, "spans": {}, "counters": counters, "facts": {}}
    return {k: v["value"] for k, v in cell.per_layer_values(ctx).items()}


@pytest.fixture(scope="module")
def engine_keys():
    """The keys of ``engine.metrics``, from the program itself."""
    from ray_tpu.llm.config import EngineConfig, LLMConfig
    from ray_tpu.llm.engine import JaxLLMEngine

    return set(JaxLLMEngine(LLMConfig(
        model_id="tiny", engine_config=EngineConfig(
            max_num_seqs=2, max_model_len=32, page_size=16))).metrics)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_metric_is_a_file_an_entry_and_a_hand_value(metric, engine_keys):
    num, den, unit, better, want = NEW[metric]
    with open(os.path.join(HERE, "layer_metrics", metric + ".json")) as f:
        raw = json.load(f)
    assert raw == {"reduce": "counter_ratio", "args": {"num": num, "den": den}}
    assert raw["reduce"] in reduce.REDUCTIONS
    assert {num, den} <= engine_keys
    bench = _bench()
    entry, = [m for m in bench["per_layer"] if m["name"] == metric]
    cells = entry.pop("workloads")
    assert entry == {"name": metric, "unit": unit, "better": better,
                     "source": "program_counter",
                     "layer": "engine (llm/engine.py)",
                     "moves": "serve_tokens_per_s"}
    # a layer the benchmark already named, letter for letter
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] not in NEW}
    # every cell that lists it reports what it moves
    assert cells and set(cells) <= set(_serving(bench))
    assert len(set(cells)) == len(cells)
    for name in cells:
        cell = Cell(name, os.path.join(REPO, "BENCHMARK.json"))
        assert cell.reader(metric) == raw
        assert _values(cell, WINDOW)[metric] == pytest.approx(want)


def test_the_slot_shares_of_a_window_add_up_to_one():
    cell = Cell(_serving(_bench())[0], os.path.join(REPO, "BENCHMARK.json"))
    got = _values(cell, WINDOW)
    assert sum(got["engine.slot_%s_share" % s] for s in (
        "live", "starved", "page_blocked", "prefilling")) == pytest.approx(1)
    # a run with no stalled read prints 0.0, it does not leave the metric out
    sound = dict(WINDOW, stalled_reads=0, stalled_read_ms=0.0)
    assert _values(cell, sound)["engine.stalled_time_share"] == 0.0


def test_the_parents_counters_raise_nothing():
    """Laid over the parent's program, the readers whose denominator it lacks
    (``slot_steps``) find nothing; ``phase_ms`` and ``prefill_phase_ms`` it
    has: the busy-time rate reads as on the change, and what divides a
    counter it lacks by them reads 0.0."""
    bench = _bench()
    for name in _serving(bench):
        got = _values(Cell(name, os.path.join(REPO, "BENCHMARK.json")), PARENT)
        assert not [m for m in got if m.startswith("engine.slot_")]
        assert got["engine.tokens_per_busy_ms"] == 1.25
        assert got["engine.stalled_time_share"] == 0.0
        assert got["engine.prefill_positions_per_ms"] == 0.0
        assert got["engine.tokens_per_step"] == 50000 / 1500  # as before
