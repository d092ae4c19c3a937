"""Finds everything that belongs to a cell by the names in ``BENCHMARK.json``.

A cell is ``{"name", "config", "traffic", "chips"}``. Its configuration is
the file the ``configs`` entry names, its traffic mix is
``<root>/traffic/<traffic>.json`` and each of its per-layer metrics is
``<root>/layer_metrics/<metric>.json``. What the benchmark knows about the
configuration's architecture (the way from its published keys to the program,
its plain reference, its operation counts, the cost of its kernels) is the one
module ``<root>/architectures/<adapter>.py`` that the configuration file names
with ``"adapter"``. Adding a cell, a configuration, a mix, a metric or an
architecture therefore adds entries and files and edits none: the harness
itself reads only ``job`` and ``vocab_size`` from a configuration file.
``root`` is the benchmark's directory; the self-test points it at a temporary
one.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Dict, List

from benchmarks import traffic
from benchmarks.trace import reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


# what the harness asks of an architecture's module, and nothing else
MEMBERS = ("program_overrides", "reference_cfg", "to_reference_params",
           "forward", "loss", "train_flops_per_token", "total_params",
           "kernel_cost")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def architecture(config: dict, root: str = HERE):
    """The module of a configuration's architecture: the file
    ``<root>/architectures/<adapter>.py``, loaded by its path (a temporary
    root has no package) once a process. A configuration file without
    ``"adapter"`` is a ``dense_decoder``. The module imports JAX, so the
    parent of a run asks for it only after the window."""
    name = config.get("adapter", "dense_decoder")
    folder = os.path.join(root, "architectures")
    path = os.path.join(folder, name + ".py")
    key = "benchmarks_architecture:" + path
    if key in sys.modules:
        return sys.modules[key]
    known = sorted(f[:-3] for f in os.listdir(folder)
                   if f.endswith(".py") and not f.startswith("_"))
    if name not in known:
        raise SystemExit(f"configuration {config.get('name')!r}: no architecture "
                         f"{name!r} under {folder}; known: {known}")
    spec = importlib.util.spec_from_file_location(key, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    missing = [m for m in MEMBERS if not callable(getattr(module, m, None))]
    if missing:
        raise SystemExit(f"architecture {name!r} ({path}) lacks {missing}")
    sys.modules[key] = module
    return module


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

    def architecture(self):
        return architecture(self.config, self.root)

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
        """Every per-layer metric of this cell that has something to read. A
        reduction that needs a count asks ``ctx["architecture"]()`` for the
        module and gives it ``ctx["config"]``."""
        ctx = dict(ctx, config=self.config, architecture=self.architecture)
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
