"""Control-plane bench: actor creates/s, tasks/s, lease-grant latency.

The companion to tools/stress.py for the provisioning plane (ISSUE 8 /
ROADMAP "control-plane throughput"): measures the paths the zygote prefork
pool + batched lease grants attack, and can run the same envelope with the
pool DISABLED (cold subprocess spawns, the STRESS_r05 configuration) to
show the ratio on one host.

Usage:
  python tools/bench_control_plane.py [--nodes 2] [--actors 40]
      [--tasks 4000] [--lease-samples 50] [--drivers 4] [--out FILE]
  python tools/bench_control_plane.py --compare --out STRESS_r06.json
      # runs warm then cold in fresh interpreters, emits both + speedups

``--drivers K`` adds a multi-driver task phase: K driver PROCESSES submit
against the same cluster concurrently (the reference runtime's shape —
ownership is per-driver by design, PAPER.md L2), reporting per-driver and
aggregate tasks/s. This is the number that proves the cluster side scales
past the single-owner submission ceiling.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

COLD_ENV = {
    # the STRESS_r05 configuration: every lease miss pays a cold
    # interpreter+import spawn, no zygote, no warm pool, no prestart
    "RAY_TPU_WORKER_ZYGOTE_ENABLED": "0",
    "RAY_TPU_WORKER_POOL_WARM_TARGET": "0",
    "RAY_TPU_PRESTART_WORKERS": "0",
}


def phase_actors(total: int) -> dict:
    import ray_tpu

    @ray_tpu.remote(num_cpus=0.1)
    class _A:
        def ping(self):
            return 1

    t0 = time.perf_counter()
    actors = [_A.remote() for _ in range(total)]
    ray_tpu.get([a.ping.remote() for a in actors], timeout=3600.0)
    created = time.perf_counter() - t0
    t1 = time.perf_counter()
    ray_tpu.get([a.ping.remote() for a in actors], timeout=600.0)
    call_round = time.perf_counter() - t1
    for a in actors:
        ray_tpu.kill(a)
    return {"actors": total,
            "actor_create_wall_s": round(created, 2),
            "actor_creates_per_s": round(total / created, 2),
            "actor_call_round_s": round(call_round, 3)}


def phase_tasks(total: int, window: int = 1000) -> dict:
    import ray_tpu

    @ray_tpu.remote(num_cpus=0.1)
    def _noop(i):
        return i

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
    out = {"tasks": total, "tasks_wall_s": round(dt, 2),
           "tasks_per_s": round(total / dt, 1)}
    try:
        from ray_tpu._private.worker import _global_worker

        stats = _global_worker.submit_stats()
        out["submit_per_task_us"] = stats["per_submit_us"]
        out["submit_fast_path_frac"] = round(
            stats["fast_path"] / max(1, stats["count"]), 3)
        out["submit_kickoff_wakeups"] = stats["kickoff_wakeups"]
        out["submit_spec_frames"] = stats["spec_frames"]
    except Exception:
        pass  # client/local modes have no core-worker submit stats
    return out


def phase_tasks_multidriver(drivers: int, total: int, address: str) -> dict:
    """Fork `drivers` driver processes against the running cluster, each
    submitting total/drivers no-op tasks. Aggregate tasks/s is measured
    over the union window (first start to last finish), so driver skew
    counts against it."""
    per = max(1, total // drivers)
    procs = []
    for i in range(drivers):
        out_path = f"/tmp/_bench_cp_driver{i}_{os.getpid()}.json"
        cmd = [sys.executable, os.path.abspath(__file__), "--child-driver",
               "--address", address, "--tasks", str(per), "--out", out_path]
        procs.append((subprocess.Popen(cmd), out_path))
    results = []
    for proc, out_path in procs:
        rc = proc.wait(timeout=1800)
        assert rc == 0, f"driver subprocess failed (rc={rc})"
        with open(out_path) as f:
            results.append(json.load(f))
        os.unlink(out_path)
    window = max(r["t1"] for r in results) - min(r["t0"] for r in results)
    agg = round(per * drivers / window, 1)
    return {
        "drivers": drivers,
        "multidriver_tasks": per * drivers,
        "multidriver_window_s": round(window, 2),
        "per_driver_tasks_per_s": [r["tasks_per_s"] for r in results],
        "aggregate_tasks_per_s": agg,
        "driver_submit_per_task_us": results[0].get("submit_per_task_us"),
    }


def child_driver(address: str, tasks: int, out_path: str):
    """One forked driver of the multi-driver phase: connect, submit, report
    wall-clock endpoints (time.time() — comparable across processes)."""
    import ray_tpu

    ray_tpu.init(address=address)
    try:
        t0 = time.time()
        result = phase_tasks(tasks)
        result["t0"], result["t1"] = t0, time.time()
        with open(out_path, "w") as f:
            json.dump(result, f)
    finally:
        ray_tpu.shutdown()


def phase_lease_latency(samples: int) -> dict:
    """Direct RequestWorkerLease/Return round trips against the local
    raylet: grant latency with a warm pool is adoption cost; cold it is a
    full worker spawn. Also measures the multi-grant form (count=8)."""
    from ray_tpu._private import wire
    from ray_tpu._private.rpc import RetryingRpcClient
    from ray_tpu._private.worker import _global_worker as core

    client = RetryingRpcClient(core.raylet_address)

    async def one(count=1):
        t0 = time.perf_counter()
        reply = wire.loads(await client.call("RequestWorkerLease", wire.dumps(
            {"resources": {"CPU": 0.1}, "job_id": None, "count": count}),
            timeout=120.0))
        dt = time.perf_counter() - t0
        assert reply["status"] == "granted", reply
        grants = [reply] + (reply.get("extra_grants") or [])
        for g in grants:
            await client.call("ReturnWorkerLease", wire.dumps(
                {"lease_id": g["lease_id"]}))
        return dt, len(grants)

    lat = []
    for _ in range(samples):
        dt, _n = core._run(one(), 180.0)
        lat.append(dt)
    lat.sort()
    _, batch = core._run(one(count=8), 180.0)
    core._run(client.close(), 30.0)
    return {
        "lease_samples": samples,
        "lease_grant_p50_ms": round(lat[len(lat) // 2] * 1000, 2),
        "lease_grant_p95_ms": round(lat[int(len(lat) * 0.95)] * 1000, 2),
        "lease_multigrant_count8": batch,
    }


def pool_stats() -> dict:
    from ray_tpu.util.state import get_node_stats, list_nodes

    out = {}
    for n in list_nodes():
        if not n["alive"]:
            continue
        stats = get_node_stats(n["address"])
        out[n["node_id"][:10]] = stats.get("worker_pool", {})
    return out


def run(nodes: int, actors: int, tasks: int, lease_samples: int,
        drivers: int = 1) -> dict:
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    wall0 = time.perf_counter()
    cluster = Cluster(initialize_head=True,
                      head_node_args={"resources": {"CPU": 8.0}})
    for _ in range(nodes - 1):
        cluster.add_node(resources={"CPU": 8.0})
    ray_tpu.init(address=cluster.address)
    try:
        from ray_tpu.util.state import list_nodes

        deadline = time.time() + 120
        while time.time() < deadline:
            if len([n for n in list_nodes() if n["alive"]]) >= nodes:
                break
            time.sleep(0.2)
        result = {"nodes": nodes,
                  "mode": "cold" if os.environ.get(
                      "RAY_TPU_WORKER_ZYGOTE_ENABLED") == "0" else "warm"}
        result.update(phase_lease_latency(lease_samples))
        print(f"[bench] lease p50 {result['lease_grant_p50_ms']}ms",
              flush=True)
        result.update(phase_actors(actors))
        print(f"[bench] actors: {result['actor_creates_per_s']}/s", flush=True)
        result.update(phase_tasks(tasks))
        print(f"[bench] tasks: {result['tasks_per_s']}/s", flush=True)
        if drivers > 1:
            result.update(phase_tasks_multidriver(
                drivers, tasks, cluster.address))
            print(f"[bench] multidriver x{drivers}: "
                  f"{result['aggregate_tasks_per_s']}/s aggregate", flush=True)
        result["worker_pools"] = pool_stats()
        result["total_wall_s"] = round(time.perf_counter() - wall0, 2)
        return result
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def compare(args) -> dict:
    """Run warm and cold in fresh interpreters (env must be set before the
    cluster boots; children inherit)."""
    out = {}
    for mode in ("warm", "cold"):
        env = dict(os.environ)
        if mode == "cold":
            env.update(COLD_ENV)
        tmp = f"/tmp/_bench_cp_{mode}.json"
        cmd = [sys.executable, os.path.abspath(__file__),
               "--nodes", str(args.nodes), "--actors", str(args.actors),
               "--tasks", str(args.tasks),
               "--lease-samples", str(args.lease_samples), "--out", tmp]
        print(f"[bench] === {mode} run ===", flush=True)
        subprocess.run(cmd, env=env, check=True, timeout=3600)
        with open(tmp) as f:
            out[mode] = json.load(f)
    out["speedup_actor_creates"] = round(
        out["warm"]["actor_creates_per_s"]
        / max(out["cold"]["actor_creates_per_s"], 1e-9), 1)
    out["speedup_tasks"] = round(
        out["warm"]["tasks_per_s"] / max(out["cold"]["tasks_per_s"], 1e-9), 2)
    out["speedup_lease_p50"] = round(
        out["cold"]["lease_grant_p50_ms"]
        / max(out["warm"]["lease_grant_p50_ms"], 1e-9), 1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--actors", type=int, default=40)
    ap.add_argument("--tasks", type=int, default=4000)
    ap.add_argument("--lease-samples", type=int, default=50)
    ap.add_argument("--drivers", type=int, default=1,
                    help="run a K-driver-process task phase against the "
                         "same cluster and report aggregate tasks/s")
    ap.add_argument("--compare", action="store_true",
                    help="run warm AND cold (fresh interpreters), emit both")
    ap.add_argument("--child-driver", action="store_true",
                    help=argparse.SUPPRESS)  # internal: multidriver child
    ap.add_argument("--address", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.child_driver:
        child_driver(args.address, args.tasks, args.out)
        return
    if args.compare:
        result = compare(args)
    else:
        result = run(args.nodes, args.actors, args.tasks, args.lease_samples,
                     args.drivers)
    result["argv"] = sys.argv[1:]
    print(json.dumps(result, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
