"""Fast control-plane smoke (tier-1, not slow): the provisioning plane's
bench tool runs end-to-end at a tiny scale and its envelope completes —
leases grant, actors create at warm-pool (not cold-spawn) rates, the
multi-driver phase aggregates, pool stats surface. Throughput numbers come
from the full tools/bench_control_plane.py run (STRESS_r*.json).

Also the submit fast-path regression guards (ISSUE 13): a warm submit must
not re-frame the TaskSpec through wire.dumps, and a burst of `.remote()`
calls must wake the io loop at most once."""

import json
import os
import subprocess
import sys
import time


def test_control_plane_bench_smoke(tmp_path):
    out = tmp_path / "cp.json"
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "bench_control_plane.py"),
         "--nodes", "2", "--actors", "10", "--tasks", "400",
         "--lease-samples", "6", "--drivers", "2", "--out", str(out)],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=dict(os.environ), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (
        f"bench failed\n--- stdout ---\n{proc.stdout[-4000:]}"
        f"\n--- stderr ---\n{proc.stderr[-3000:]}")
    result = json.loads(out.read_text())
    assert result["mode"] == "warm"
    assert result["actors"] == 10 and result["tasks"] == 400
    # the rates are measured and printed, never floored: a timing on this
    # host beside other test workers is a check that the phase ran
    for rate in ("actor_creates_per_s", "tasks_per_s", "lease_grant_p50_ms",
                 "aggregate_tasks_per_s"):
        assert result[rate] > 0, result
        print(f"{rate}: {result[rate]}")
    # spawn-backed multi-grant top-up: a count=8 lease grants ~8 (forking
    # the remainder), not the 1-2 the warm pool happened to hold (the old
    # cap). >= 6 because top-up is best-effort by design — a refused fork
    # or one slow registration on a loaded host legally drops a grant
    assert result["lease_multigrant_count8"] >= 6, result
    # the spec was framed exactly once. Which share of the 400 submits
    # took the fast path is a race, not a count: the driver thread issues
    # all of them in one burst while the loop thread is still caching the
    # template on the first task's drive (function push, renv prep), so
    # the share is the host's speed (0.0 on this box, ~0.9 on the one it
    # was written on). Printed; the deterministic guard (>= 100 of 100
    # warm submits) is test_submit_fast_path_regression_guards.
    assert result["submit_spec_frames"] == 1, result
    assert 0.0 <= result["submit_fast_path_frac"] <= 1.0, result
    print(f"submit_fast_path_frac: {result['submit_fast_path_frac']}")
    # multi-driver phase: 2 forked drivers, aggregate over the union window
    assert result["drivers"] == 2
    assert result["multidriver_tasks"] == 400, result
    assert len(result["per_driver_tasks_per_s"]) == 2
    # pool stats surfaced from every node, and the zygote actually served
    pools = result["worker_pools"]
    assert len(pools) == 2
    assert any(p.get("zygote_alive") for p in pools.values()), pools
    assert sum(p.get("hits", 0) + p.get("misses", 0)
               for p in pools.values()) > 0, pools


def test_submit_fast_path_regression_guards():
    """Per-submit cost guards: (1) the TaskSpec template is wire-framed
    once per (function, options) — the second and later submits of the
    same function reuse the cached blob; (2) a burst of `.remote()` calls
    while the io loop is busy pays at most ONE call_soon_threadsafe."""
    import ray_tpu

    ray_tpu.init()
    try:
        from ray_tpu._private.worker import _global_worker as core

        @ray_tpu.remote(num_cpus=0.1)
        def f(i):
            return i + 1

        # first submit frames + caches the template (slow path)
        assert ray_tpu.get(f.remote(0), timeout=120) == 1
        frames0 = core._submit_stats["spec_frames"]

        # occupy the io loop so the burst below cannot be drained mid-way:
        # every submit lands while the loop is provably busy
        import asyncio

        async def _block():
            time.sleep(0.3)  # blocking ON the loop, intentionally

        blocker = asyncio.run_coroutine_threadsafe(_block(), core.loop)
        time.sleep(0.05)  # let the loop enter the blocker
        wake0 = core._submit_stats["kickoff_wakeups"]
        refs = [f.remote(i) for i in range(100)]
        wake1 = core._submit_stats["kickoff_wakeups"]
        blocker.result(timeout=10)
        assert wake1 - wake0 <= 1, (wake0, wake1)
        assert ray_tpu.get(refs, timeout=120) == list(range(1, 101))
        # no re-framing of the spec template on warm submits
        assert core._submit_stats["spec_frames"] == frames0, (
            frames0, core._submit_stats)
        assert core._submit_stats["fast_path"] >= 100
        # (3) the serialization scratch pool absorbs warm submits: after
        # the first submit sized the per-thread buffer, a same-shape burst
        # re-packs into it instead of allocating per call
        stats = core.submit_stats()
        assert stats["pack_pool_hits"] >= 95, stats
        assert stats["pack_pool_hits"] > 10 * stats["pack_pool_misses"]
        # semantics preserved through the fast path: dependency chains,
        # multiple returns, and errors still behave
        @ray_tpu.remote(num_cpus=0.1, num_returns=2)
        def two(x):
            return x, x * 10

        a, b = two.remote(3)
        chained = f.remote(b)
        assert ray_tpu.get([a, chained], timeout=120) == [3, 31]

        # (4) wait() partitions readiness via the per-poll set
        # intersection (not per-ref store probes) — the counter proves the
        # vectorized path actually engaged, and semantics hold
        polls0 = core._submit_stats["wait_vector_polls"]
        more = [f.remote(i) for i in range(20)]
        done, not_done = ray_tpu.wait(more, num_returns=20, timeout=120)
        assert len(done) == 20 and not not_done
        assert core._submit_stats["wait_vector_polls"] > polls0, (
            polls0, core._submit_stats)

        @ray_tpu.remote(num_cpus=0.1)
        def boom():
            raise ValueError("intentional")

        import pytest

        with pytest.raises(Exception, match="intentional"):
            ray_tpu.get(boom.remote(), timeout=120)
    finally:
        ray_tpu.shutdown()


def test_resource_view_delta_coalescing():
    """N availability updates inside one GCS tick -> ONE batched
    resource_view publish carrying only the latest view; values flapping
    back to the published view are suppressed entirely."""
    import asyncio

    from ray_tpu._private import wire
    from ray_tpu._private.common import NodeInfo
    from ray_tpu._private.gcs import GcsServer
    from ray_tpu._private.ids import NodeID

    async def _run():
        gcs = GcsServer()
        pushes = []

        class FakeConn:
            conn_id = 1

            async def push(self, channel, payload):
                pushes.append((channel, wire.loads(payload)))

        gcs.subs[1] = (FakeConn(), {"resource_view"})
        info = NodeInfo(node_id=NodeID.from_random(), address="host:1",
                        object_store_address="",
                        total_resources={"CPU": 8.0})
        await gcs._rpc_RegisterNode({"info": info}, None)
        await asyncio.sleep(0)  # let the registration publish land
        assert len(pushes) == 1, pushes
        assert pushes[0][1]["views"][0]["available"] == {"CPU": 8.0}

        # a burst of heartbeat availability changes within one tick...
        for i in range(10):
            await gcs._rpc_Heartbeat(
                {"node_id": info.node_id,
                 "available": {"CPU": float(i)}}, None)
        assert len(pushes) == 1  # nothing published before the tick
        gcs._flush_resource_views()
        await asyncio.sleep(0)
        # ...coalesces to ONE publish carrying the LATEST view
        assert len(pushes) == 2, pushes
        views = pushes[1][1]["views"]
        assert len(views) == 1
        assert views[0]["available"] == {"CPU": 9.0}

        # delta suppression: flapping back to the published value inside
        # the tick publishes nothing at all
        await gcs._rpc_Heartbeat(
            {"node_id": info.node_id, "available": {"CPU": 3.0}}, None)
        await gcs._rpc_Heartbeat(
            {"node_id": info.node_id, "available": {"CPU": 9.0}}, None)
        gcs._flush_resource_views()
        await asyncio.sleep(0)
        assert len(pushes) == 2, pushes

        # node death flushes immediately with alive=False
        await gcs._mark_node_dead(info.node_id, "test")
        await asyncio.sleep(0)
        dead = [m for _, m in pushes[2:]
                for v in m["views"] if not v["alive"]]
        assert dead, pushes
        gcs.store.close()

    asyncio.run(_run())


def test_renv_keyed_warm_pool_replenish():
    """A hot non-default runtime env gets warm workers too: after leases
    for an env_vars renv, the replenish loop keys on its hash and tops up
    warm workers of that exact shape (STRESS_r06's 113-miss pattern)."""
    import ray_tpu

    ray_tpu.init()
    try:
        from ray_tpu.util.state import get_node_stats, list_nodes

        @ray_tpu.remote(num_cpus=0.1, runtime_env={
            "env_vars": {"RTPU_HOT_RENV_TEST": "1"}})
        def hot():
            return os.environ.get("RTPU_HOT_RENV_TEST")

        assert ray_tpu.get(hot.remote(), timeout=180) == "1"
        deadline = time.time() + 60
        warm = {}
        while time.time() < deadline:
            node = [n for n in list_nodes() if n["alive"]][0]
            warm = get_node_stats(node["address"]).get("worker_pool", {})
            if warm.get("warm_hot_renv", 0) >= 1:
                break
            time.sleep(0.5)
        assert warm.get("hot_renv_hash"), warm
        assert warm.get("warm_hot_renv", 0) >= 1, warm
    finally:
        ray_tpu.shutdown()
