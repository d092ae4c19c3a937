#!/usr/bin/env python3
"""Finds a serve configuration's knee: one sweep of fixed arrival rates
inside ONE replica's life.

    python3 benchmarks/sweep.py --workload <serve cell> --rates 1,1.5,2,2.5,3,4 --seconds 20

For each rate the cell's own mix (lengths as in the mix file, the rate
replaced) is offered for ``--seconds``; what is still unanswered at the end
is counted (the backlog) and then given time to drain before the next rate.
The knee is the highest rate whose backlog at the end is no larger than the
engine's slots. No committed cell runs below the knee yet: the sweep is here
for the PR that adds one (PERF.md, Open questions); no cell runs it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def main() -> int:
    from benchmarks import run as R

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="a toy cell of rehearsal/cells.json on the CPU")
    a = ap.parse_args()
    a.trace, a.keep_trace = 0, None

    from benchmarks import traffic
    from benchmarks.jobs import serve as sjob
    from benchmarks.registry import Cell
    from benchmarks.trace.reduce import quantile

    cell = Cell(a.workload, os.path.join(HERE, "rehearsal", "cells.json")
                if a.rehearse else os.path.join(REPO, "BENCHMARK.json"))
    R.set_environment(a.rehearse)
    out_dir = os.path.join(REPO, ".bench_out", "sweep")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    cluster = R.Cluster(cell.chips, a.rehearse)
    try:
        d = R.deploy_serve(cell, a, cluster, out_dir)
        slots = cell.config["job"]["engine"]["max_num_seqs"]
        for i, rate in enumerate(float(x) for x in a.rates.split(",")):
            mix = json.loads(json.dumps(cell.mix))
            mix["arrival"]["rate_per_s"] = rate
            mix["lead_s"] = 0.0
            mix["end"] = "abandon"      # no tail: each rate is drained alone
            reqs = traffic.serve_schedule(mix, a.seed + i, a.seconds,
                                          cell.config["vocab_size"])
            before = d["call"]("engine_metrics")
            t0 = time.time() + 0.5
            recs = sjob.offer_load(d["url"], reqs, t0, a.seconds,
                                   mix["temperature"], "drain", 120.0)
            after = d["call"]("engine_metrics")
            wall = time.time() - t0
            ok = [r for r in recs if r["status"] == 200]
            lat = [r["done_s"] - r["due_s"] for r in ok]
            backlog = sum(1 for r in recs
                          if r["status"] != 200 or r["done_s"] > a.seconds)
            half = [r["done_s"] - r["due_s"] for r in ok
                    if r["due_s"] >= a.seconds / 2]
            first = [r["done_s"] - r["due_s"] for r in ok
                     if r["due_s"] < a.seconds / 2]
            R.emit(rate=rate, offered=len(recs), answered=len(ok),
                   unanswered_at_window_end=backlog, slots=slots,
                   p50_ms=statistics.median(lat) * 1e3 if lat else None,
                   p90_ms=quantile(lat, 0.9) * 1e3 if lat else None,
                   p50_first_half_ms=statistics.median(first) * 1e3 if first else None,
                   p50_second_half_ms=statistics.median(half) * 1e3 if half else None,
                   drained_after_s=wall,
                   out_tokens_per_s_in_window=sum(
                       len(r["token_ids"]) for r in ok
                       if r["done_s"] <= a.seconds) / a.seconds,
                   requests_per_s_completed_until_drained=len(ok) / wall,
                   counters={k: after[k] - before[k] for k in after})
        d["shutdown"]()
        R.emit(note="chip_handoff", returned_after_s=cluster.wait_chip_returned())
    except BaseException:
        cluster.dump_logs()
        raise
    finally:
        cluster.ray.shutdown()
        shutil.rmtree(out_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
