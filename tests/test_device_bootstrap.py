"""How the program finds the chip and its native libraries, without a chip:
the raylet's chip count comes from device nodes (never from JAX, which would
take the chip in a daemon that never gives it back), and a native library is
reused only when it was built from exactly the source at hand."""

import os
import subprocess
import sys
import time

import pytest

from ray_tpu.util import accelerators

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_dev(tmp_path, names):
    for name in names:
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text("")
    return str(tmp_path)


def test_chip_count_from_device_nodes_without_jax(tmp_path, monkeypatch):
    for var in ("RAY_TPU_CHIPS", "TPU_VISIBLE_CHIPS"):
        monkeypatch.delenv(var, raising=False)
    # what the one-chip v5e machine shows: the image says v5litepod-4, one
    # vfio group is attached (plus the container node, which is no chip)
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_TOPOLOGY", "2x2")
    one = _fake_dev(tmp_path / "one", ["vfio/1", "vfio/vfio"])
    chips, labels = accelerators.detect_tpu(one)
    assert chips == 1
    assert labels[accelerators.LABEL_TPU_POD_TYPE] == "v5litepod-4"
    four = _fake_dev(tmp_path / "four", [f"accel{i}" for i in range(4)])
    assert accelerators.detect_tpu(four)[0] == 4
    # no device node at all: the slice metadata is the last resort
    none = _fake_dev(tmp_path / "none", ["null"])
    assert accelerators.detect_tpu(none)[0] == 4
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE")
    assert accelerators.detect_tpu(none) == (0, {})
    monkeypatch.setenv("RAY_TPU_CHIPS", "2")
    assert accelerators.detect_tpu(none)[0] == 2
    monkeypatch.setenv("RAY_TPU_CHIPS", "many")
    with pytest.raises(ValueError):  # a bad override is not "no TPU"
        accelerators.detect_tpu(none)

    # the raylet's route, in a fresh interpreter: jax is never imported
    probe = (
        "import sys\n"
        "from ray_tpu.util.accelerators import detect_tpu\n"
        f"chips, _ = detect_tpu({one!r})\n"
        "assert chips == 1, chips\n"
        "assert 'jax' not in sys.modules and 'jaxlib' not in sys.modules\n")
    env = {k: v for k, v in os.environ.items() if k != "RAY_TPU_CHIPS"}
    out = subprocess.run([sys.executable, "-c", probe], cwd=REPO_ROOT,
                         env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr


def test_native_build_is_keyed_by_content_not_mtime(tmp_path):
    from ray_tpu._private import native_build

    src = tmp_path / "answer.cc"
    lib = str(tmp_path / "build" / "libanswer.so")

    def answer(value):
        src.write_text(f'extern "C" int answer() {{ return {value}; }}\n')
        # the source looks OLDER than any library already built
        old = time.time() - 3600
        os.utime(src, (old, old))
        loaded = native_build.build_and_load(str(src), lib)
        assert loaded is not None
        return loaded.answer()

    assert answer(41) == 41
    first = set(os.listdir(tmp_path / "build"))
    assert answer(42) == 42  # rebuilt: the content changed, mtime did not
    second = set(os.listdir(tmp_path / "build"))
    assert len(first) == len(second) == 1 and first != second
    assert answer(42) == 42 and set(os.listdir(tmp_path / "build")) == second

    # a failed build is loud and returns None (callers fall back in the open)
    src.write_text("this is not c++\n")
    assert native_build.build_and_load(str(src), lib) is None


def test_a_frozen_host_is_not_a_dead_node(monkeypatch):
    """Opening a TPU freezes a v5e host for 5-6 s, every process at once
    (PERF.md, PR 21) — longer than the node-death timeout. Time during which
    the GCS itself could not run says nothing about a node; a raylet that
    really stops heartbeating is still declared dead."""
    import asyncio

    from ray_tpu._private.gcs import GcsServer
    from ray_tpu._private.raylet import Raylet

    for var, val in (("HEALTH_CHECK_PERIOD_MS", "100"),
                     ("HEALTH_CHECK_TIMEOUT_MS", "600"),
                     ("PRESTART_WORKERS", "0"),
                     ("WORKER_POOL_WARM_TARGET", "0"),
                     ("WORKER_ZYGOTE_ENABLED", "0")):
        monkeypatch.setenv(f"RAY_TPU_{var}", val)

    async def body():
        gcs = GcsServer()
        raylet = Raylet(gcs_address=await gcs.start(),
                        resources={"CPU": 1.0})
        await raylet.start()
        node = gcs.nodes[raylet.node_id]
        try:
            for _ in range(3):
                await asyncio.sleep(0.3)
                time.sleep(1.5)  # one loop hosts both: GCS and raylet freeze
            await asyncio.sleep(0.3)
            assert node.alive
            for task in raylet._background:  # now only the raylet goes quiet
                task.cancel()
            await asyncio.sleep(1.5)
            assert not node.alive
        finally:
            await raylet.stop()
            await gcs.stop()

    asyncio.run(asyncio.wait_for(body(), 60))


def test_a_chip_is_leasable_again_only_when_its_holder_is_gone(monkeypatch):
    """One process for each chip, at the raylet: a worker whose lease
    carried TPU is not pooled when the lease comes back — it is killed, and
    the TPU returns to the pool only once the process is gone. Removing a
    placement group under a live TPU worker does not hand the chip back
    early either."""
    import asyncio

    from ray_tpu._private import wire
    from ray_tpu._private.gcs import GcsServer
    from ray_tpu._private.raylet import Raylet
    from ray_tpu._private.rpc import RetryingRpcClient

    monkeypatch.setenv("RAY_TPU_PRESTART_WORKERS", "0")
    monkeypatch.setenv("RAY_TPU_WORKER_POOL_WARM_TARGET", "0")

    async def body():
        gcs = GcsServer()
        raylet = Raylet(gcs_address=await gcs.start(),
                        resources={"CPU": 4.0, "TPU": 1.0})
        await raylet.start()
        client = RetryingRpcClient(raylet.server.address)

        async def call(method, **req):
            return wire.loads(await client.call(method, wire.dumps(req),
                                                timeout=90.0))

        async def lease(**extra):
            reply = await call("RequestWorkerLease", job_id=None, count=1,
                               resources={"CPU": 1.0, "TPU": 1.0},
                               runtime_env=None, **extra)
            assert reply["status"] == "granted", reply
            return reply["lease_id"], raylet.workers[reply["worker_pid"]]

        async def gone(worker):
            for _ in range(200):
                if worker.pid not in raylet.workers:
                    return
                # the chip is never leasable while its holder is tracked
                assert raylet.available["TPU"] == 0.0
                await asyncio.sleep(0.05)
            raise AssertionError("TPU worker outlived its lease")

        try:
            # a TASK lease: returned while the worker is alive
            lease_id, worker = await lease()
            assert raylet.available["TPU"] == 0.0
            await call("ReturnWorkerLease", lease_id=lease_id)
            assert worker not in raylet.idle_workers
            await gone(worker)
            assert raylet.available["TPU"] == 1.0

            # a placement group removed under a live TPU worker
            pg = b"p" * 16
            bundles = {0: {"CPU": 1.0, "TPU": 1.0}}
            assert (await call("PreparePGBundles", pg_id=pg,
                               bundles=bundles))["status"] == "ok"
            await call("CommitPGBundles", pg_id=pg)
            lease_id, worker = await lease(pg=pg, bundle_index=0)
            await call("ReleasePGBundles", pg_id=pg)
            assert raylet.available == {"CPU": 4.0, "TPU": 0.0}
            worker.proc.kill()
            await gone(worker)
            assert raylet.available == {"CPU": 4.0, "TPU": 1.0}
        finally:
            await client.close()
            await raylet.stop()
            await gcs.stop()

    asyncio.run(asyncio.wait_for(body(), 120))
