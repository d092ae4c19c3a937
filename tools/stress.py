"""Control-plane scale/stress harness (reference: release/benchmarks/ —
many_nodes/many_tasks/many_actors/many_pgs + the object-broadcast shape in
release/benchmarks/object_store.py).

Runs the whole envelope on ONE machine: N virtual raylet processes under a
single GCS, then drives tasks / actors / placement groups / a wide object
broadcast through the real two-level scheduler and object plane. Numbers are
committed as STRESS_r{N}.json so every round has envelope evidence, and
`tests/test_stress.py` pins a scaled-down version so regressions fail CI.

Usage: python tools/stress.py [--nodes 16] [--tasks 20000] [--actors 512]
                              [--pgs 100] [--broadcast-mb 100] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# runnable as `python tools/stress.py` from the repo root: sys.path[0] is
# tools/, so put the repo root (where ray_tpu/ lives) in front
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The stress tier never runs device compute: keep every spawned process
# off the chip. Must happen before the cluster boots; children inherit.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import ray_tpu  # noqa: E402
from ray_tpu.cluster_utils import Cluster  # noqa: E402
from ray_tpu.util.placement_group import (placement_group,  # noqa: E402
                                          remove_placement_group)
from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy  # noqa: E402


@ray_tpu.remote(num_cpus=1)
def _noop(i):
    return i


@ray_tpu.remote(num_cpus=0.1)
class _StressActor:
    def __init__(self, rank):
        self.rank = rank

    def ping(self):
        return self.rank


@ray_tpu.remote(num_cpus=0.5)
def _consume(blob, rank):
    return (rank, len(blob))


def phase_tasks(total: int, window: int = 2000) -> dict:
    """Submit `total` no-op tasks keeping ~`window` in flight (the reference
    many_tasks shape: sustained pipeline, not one barrier)."""
    t0 = time.perf_counter()
    in_flight = [_noop.remote(i) for i in range(min(window, total))]
    submitted = len(in_flight)
    completed = 0
    while in_flight:
        ready, in_flight = ray_tpu.wait(
            in_flight, num_returns=min(len(in_flight), 100), timeout=300.0)
        completed += len(ready)
        while submitted < total and len(in_flight) < window:
            in_flight.append(_noop.remote(submitted))
            submitted += 1
    dt = time.perf_counter() - t0
    assert completed == total, (completed, total)
    return {"tasks": total, "tasks_wall_s": round(dt, 2),
            "tasks_per_s": round(total / dt, 1)}


def phase_actors(total: int) -> dict:
    t0 = time.perf_counter()
    actors = [_StressActor.remote(i) for i in range(total)]
    ranks = ray_tpu.get([a.ping.remote() for a in actors], timeout=1200.0)
    assert sorted(ranks) == list(range(total))
    created = time.perf_counter() - t0
    # one sync call round per actor, all pipelined
    t1 = time.perf_counter()
    ray_tpu.get([a.ping.remote() for a in actors], timeout=600.0)
    call_round = time.perf_counter() - t1
    for a in actors:
        ray_tpu.kill(a)
    return {"actors": total,
            "actor_create_wall_s": round(created, 2),
            "actor_creates_per_s": round(total / created, 1),
            "actor_call_round_s": round(call_round, 2)}


def phase_pgs(total: int) -> dict:
    t0 = time.perf_counter()
    pgs = [placement_group([{"pg_slot": 1.0}, {"pg_slot": 1.0}],
                           strategy="PACK") for _ in range(total)]
    for pg in pgs:
        assert pg.ready(timeout=600.0)
    created = time.perf_counter() - t0
    t1 = time.perf_counter()
    for pg in pgs:
        remove_placement_group(pg)
    removed = time.perf_counter() - t1
    return {"pgs": total, "pg_create_wall_s": round(created, 2),
            "pgs_per_s": round(total / created, 1),
            "pg_remove_wall_s": round(removed, 2)}


def phase_broadcast(mb: int, node_ids: list) -> dict:
    import numpy as np

    blob = np.random.default_rng(0).integers(
        0, 255, size=mb * 1024 * 1024, dtype=np.uint8)
    ref = ray_tpu.put(blob)
    t0 = time.perf_counter()
    refs = [_consume.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(node_id=nid))
        .remote(ref, i) for i, nid in enumerate(node_ids)]
    out = ray_tpu.get(refs, timeout=600.0)
    dt = time.perf_counter() - t0
    assert all(n == mb * 1024 * 1024 for _, n in out)
    agg = mb * len(node_ids) / dt
    return {"broadcast_mb": mb, "broadcast_nodes": len(node_ids),
            "broadcast_wall_s": round(dt, 2),
            "broadcast_agg_MB_per_s": round(agg, 1)}


def run(nodes: int, tasks: int, actors: int, pgs: int, broadcast_mb: int,
        cpus_per_node: float = 4.0) -> dict:
    wall0 = time.perf_counter()
    cluster = Cluster(initialize_head=True, head_node_args={
        "resources": {"CPU": cpus_per_node, "pg_slot": float(pgs)}})
    for _ in range(nodes - 1):
        cluster.add_node(resources={"CPU": cpus_per_node,
                                    "pg_slot": float(pgs)})
    ray_tpu.init(address=cluster.address)
    try:
        from ray_tpu.util.state import list_nodes

        deadline = time.time() + 120
        while time.time() < deadline:
            alive = [n for n in list_nodes() if n["alive"]]
            if len(alive) >= nodes:
                break
            time.sleep(0.5)
        assert len(alive) >= nodes, f"only {len(alive)}/{nodes} nodes alive"
        result = {"nodes": nodes, "cpus_per_node": cpus_per_node}
        print(f"[stress] {nodes} nodes up", flush=True)
        result.update(phase_tasks(tasks))
        print(f"[stress] tasks: {result['tasks_per_s']}/s", flush=True)
        result.update(phase_actors(actors))
        print(f"[stress] actors: {result['actor_creates_per_s']}/s creates",
              flush=True)
        result.update(phase_pgs(pgs))
        print(f"[stress] pgs: {result['pgs_per_s']}/s", flush=True)
        result.update(phase_broadcast(
            broadcast_mb, [n["node_id"] for n in alive]))
        print(f"[stress] broadcast: {result['broadcast_agg_MB_per_s']} MB/s "
              f"aggregate", flush=True)
        result["total_wall_s"] = round(time.perf_counter() - wall0, 2)
        return result
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--tasks", type=int, default=20000)
    ap.add_argument("--actors", type=int, default=512)
    ap.add_argument("--pgs", type=int, default=100)
    ap.add_argument("--broadcast-mb", type=int, default=100)
    ap.add_argument("--cpus-per-node", type=float, default=4.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    result = run(args.nodes, args.tasks, args.actors, args.pgs,
                 args.broadcast_mb, args.cpus_per_node)
    result["argv"] = sys.argv[1:]
    print(json.dumps(result, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
