"""Finds everything that belongs to a cell by the names in ``BENCHMARK.json``.

A cell is ``{"name", "config", "traffic", "chips"}``. Its configuration is
the file the ``configs`` entry names, its traffic mix is
``<root>/traffic/<traffic>.json`` and each of its per-layer metrics is
``<root>/layer_metrics/<metric>.json``. Adding a cell, a configuration, a mix
or a metric therefore adds entries and files and edits none. ``root`` is the
benchmark's directory; the self-test points it at a temporary one.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List

from benchmarks import traffic
from benchmarks.trace import reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    def __init__(self, workload: str, benchmark_file: str, root: str = HERE):
        self.root = root
        self.benchmark = bench = _load(benchmark_file)
        base = os.path.dirname(os.path.abspath(benchmark_file))
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in {benchmark_file}; "
                             f"known: {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = _load(os.path.join(base, cfg_entry["file"]))
        self.mix = traffic.load_mix(self.entry["traffic"], root)
        if self.mix["kind"] != self.config["job"]["kind"]:
            raise SystemExit(
                f"cell {workload}: configuration job kind "
                f"{self.config['job']['kind']!r} cannot take traffic of kind "
                f"{self.mix['kind']!r}")

    def _metrics(self, group: str) -> List[dict]:
        return [m for m in self.benchmark[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def end_to_end(self) -> List[dict]:
        return self._metrics("end_to_end")

    def per_layer(self) -> List[dict]:
        return self._metrics("per_layer")

    def reader(self, metric: str) -> dict:
        """The metric's own file: which reduction reads it, from what."""
        r = _load(os.path.join(self.root, "layer_metrics", metric + ".json"))
        if r["reduce"] not in reduce.REDUCTIONS:
            raise SystemExit(f"metric {metric}: unknown reduction {r['reduce']!r}")
        return r

    def per_layer_values(self, ctx: dict) -> Dict[str, dict]:
        """Every per-layer metric of this cell that has something to read."""
        out = {}
        for m in self.per_layer():
            r = self.reader(m["name"])
            value = reduce.REDUCTIONS[r["reduce"]](ctx, **r.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out


def peak_for(device_kind: str, root: str = HERE) -> dict:
    peaks = _load(os.path.join(root, "peaks.json"))
    if device_kind not in peaks or device_kind.startswith("_"):
        raise SystemExit(f"device kind {device_kind!r} is not in peaks.json: "
                         f"no peak is assumed for an unknown device")
    return peaks[device_kind]


def program_overrides(cfg: dict, max_seq_len: int) -> dict:
    """Published keys -> the program's ``TransformerConfig`` fields: public
    widths go in as data (``dataclasses.replace`` / ``LLMConfig
    .model_overrides``), no preset's sizes are used."""
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                d_ff=cfg["intermediate_size"], max_seq_len=max_seq_len,
                rope_theta=float(cfg["rope_theta"]),
                tie_embeddings=bool(cfg["tie_word_embeddings"]), remat=True)
