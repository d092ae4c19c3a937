"""The engine's own counters reach the benchmark as data alone: each metric
this adds is one file naming a reduction that is there, and resolves through
``registry.Cell`` for the serve cell with no edit to any benchmark file."""

import json
import os

import pytest

from benchmarks.registry import HERE, REPO, Cell
from benchmarks.trace import reduce

CELL = "internlm2-1.8b.alpaca-saturated"
# metric -> (numerator, denominator) among the keys of ``engine.metrics``
NEW = {
    "engine.host_ms_per_step": ("host_ms", "steps"),
    "engine.prefill_real_share": ("prefill_tokens", "prefill_batch_tokens"),
    "engine.admitted_per_prefill": ("admitted", "prefill_steps"),
    "serve.pump_gap_ms": ("between_steps_ms", "steps"),
}


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL, os.path.join(REPO, "BENCHMARK.json"))


@pytest.mark.parametrize("metric", sorted(NEW))
def test_metric_file_names_an_existing_reduction(cell, metric):
    with open(os.path.join(HERE, "layer_metrics", metric + ".json")) as f:
        raw = json.load(f)
    assert set(raw) == {"reduce", "args"}
    assert raw["reduce"] in reduce.REDUCTIONS
    assert (raw["args"]["num"], raw["args"]["den"]) == NEW[metric]
    assert cell.reader(metric) == raw
    entry = {m["name"]: m for m in cell.per_layer()}[metric]
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["workloads"] == [CELL]
    # a layer the benchmark already names, letter for letter
    assert entry["layer"] in {m["layer"] for m in cell.benchmark["per_layer"]
                              if m["name"] not in NEW}


def test_counters_of_a_window_give_the_four_values(cell):
    window = {"host_ms": 300.0, "steps": 100, "prefill_tokens": 57,
              "prefill_batch_tokens": 3 * 8 * 256, "admitted": 3,
              "prefill_steps": 3, "between_steps_ms": 25.0,
              "generated_tokens": 800, "decode_steps": 100}
    ctx = {"trace": None, "spans": {}, "counters": window, "facts": {}}
    got = {k: v["value"] for k, v in cell.per_layer_values(ctx).items()}
    assert got["engine.host_ms_per_step"] == 3.0
    assert got["engine.prefill_real_share"] == 57 / 6144
    assert got["engine.admitted_per_prefill"] == 1.0
    assert got["serve.pump_gap_ms"] == 0.25
    assert got["engine.tokens_per_step"] == 8.0   # as before


def test_an_engine_without_the_counters_leaves_the_metrics_out(cell):
    """The parent commit's engine has four counters: the readers then find
    nothing, return nothing and raise nothing."""
    old = {"prefill_tokens": 57, "decode_steps": 100, "generated_tokens": 800,
           "preempted": 0}
    ctx = {"trace": None, "spans": {}, "counters": old, "facts": {}}
    assert set(cell.per_layer_values(ctx)) == {"engine.tokens_per_step"}
