"""Weight-plane microbenchmark: plan stats + transfer throughput on the
8-device virtual CPU mesh (one JSON line).

Measures the three flows the weight plane exists for:

- ``plan``: planner stats for a 4-host train mesh -> 2-host serve mesh
  reshard of the payload tree (edges, bytes moved, unique chunk bytes).
- ``broadcast``: one publisher -> N subscriber actors pulling the same
  version through the store (fan-out throughput, aggregate MB/s).
- ``reshard``: 4 source actors publish planned chunks, 2 destination actors
  pull their resharded shards (end-to-end MB/s for the cross-mesh path).
- ``compression`` (``--compression int8``): quantized publish/allreduce wire
  bytes vs fp32 (the EQuARX tier — codec bytes ratio must clear ~4x).
- ``delta`` (``--delta``): small-update delta publish bytes vs a full
  publish, with a byte-exact pull check.

Usage::

    python tools/bench_weights.py [--payload-mb 8] [--runners 8]
                                  [--compression int8] [--delta]

Prints one JSON list of ``{"name": ..., "value": ..., "unit": ...}`` rows
(the microbenchmark idiom of ``_private/microbenchmark.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _payload_tree(payload_mb: float):
    n = int(payload_mb * 1024 * 1024 // 4 // 8) * 8  # float32, 8-divisible
    return {"w": np.arange(n, dtype=np.float32).reshape(8, n // 8)}


def bench_compression(payload_mb: float, compression: str) -> list:
    """Quantized-tier pricing: (a) bucket-allreduce wire bytes through the
    2-rank quantized collective vs fp32 at equal tree size, (b) quantized
    store publish bytes + pull error."""
    import time as _time

    import ray_tpu
    from ray_tpu.collective import quant
    from ray_tpu.weights import WeightStore

    codec = quant.resolve_codec(compression)
    if codec is None:  # --compression none/off: nothing to price
        return []
    tree = _payload_tree(payload_mb)
    raw = tree["w"].nbytes
    rows = []

    @ray_tpu.remote(num_cpus=0.5)
    class Rank:
        def __init__(self, rank, world, comp):
            from ray_tpu import collective as col

            col.init_collective_group(world, rank, backend="cpu",
                                      group_name="bench_w.quant")
            self.rank, self.world, self.comp = rank, world, comp

        def reduce(self, payload_mb):
            from ray_tpu.collective.bucketed import (AsyncBucketReducer,
                                                     leaf_meta,
                                                     plan_buckets)

            tree = _payload_tree(payload_mb)
            plan = plan_buckets(leaf_meta(tree), bucket_bytes=4 << 20,
                                world_size=self.world)
            red = AsyncBucketReducer("bench_w.quant", plan,
                                     compression=self.comp)
            try:
                t0 = _time.perf_counter()
                red.reduce_tree(tree)
                dt = _time.perf_counter() - t0
                return red.wire_stats(), dt
            finally:
                red.shutdown()

    ranks = [Rank.remote(r, 2, compression) for r in range(2)]
    (stats, dt), _ = ray_tpu.get(
        [a.reduce.remote(payload_mb) for a in ranks], timeout=600)
    rows += [
        {"name": "quant_allreduce_fp32_bytes",
         "value": stats["bytes_fp32_equiv"], "unit": "bytes"},
        {"name": "quant_allreduce_wire_bytes",
         "value": stats["bytes_wire"], "unit": "bytes"},
        {"name": "quant_allreduce_reduction",
         "value": stats.get("wire_reduction_x", 0.0), "unit": "x"},
        {"name": "quant_allreduce_s", "value": round(dt, 4), "unit": "s"},
    ]
    for a in ranks:
        ray_tpu.kill(a)

    store = WeightStore(f"bench_quant_{compression}")
    v = store.publish(tree, durable=True, compression=compression)
    pulled = store.pull(v)
    import numpy as _np

    err = float(_np.abs(pulled["w"] - tree["w"]).max()
                / _np.abs(tree["w"]).max())
    pub = store.stats()["versions"][str(v)]["bytes_published"]
    rows += [
        {"name": "quant_publish_bytes", "value": pub, "unit": "bytes"},
        {"name": "quant_publish_raw_bytes", "value": raw, "unit": "bytes"},
        {"name": "quant_publish_reduction", "value": round(raw / pub, 2),
         "unit": "x"},
        {"name": "quant_pull_rel_err", "value": round(err, 5), "unit": "x"},
        {"name": "quant_codec_bytes_per_el",
         "value": round(codec.bytes_per_element, 4), "unit": "B"},
    ]
    return rows


def bench_delta(payload_mb: float, leaves: int = 16,
                changed: int = 2) -> list:
    """Delta-publish pricing: change ``changed`` of ``leaves`` leaves and
    compare published bytes vs the full publish; pulls must be byte-exact."""
    import numpy as _np

    from ray_tpu.weights import WeightStore

    n = max(int(payload_mb * 1024 * 1024 // 4 // leaves), 64)
    rng = _np.random.default_rng(0)
    tree = {f"l{i}": rng.normal(size=n).astype(_np.float32)
            for i in range(leaves)}
    store = WeightStore("bench_delta")
    v1 = store.publish(tree, durable=True)
    tree2 = dict(tree)
    for i in range(changed):
        tree2[f"l{i}"] = tree[f"l{i}"] + 1.0
    v2 = store.publish(tree2, durable=True, delta_from=v1)
    pulled = store.pull(v2)
    exact = all(_np.array_equal(pulled[k], tree2[k]) for k in tree2)
    vs = store.stats()["versions"]
    full = vs[str(v1)]["bytes_published"]
    delta = vs[str(v2)]["bytes_published"]
    return [
        {"name": "delta_full_publish_bytes", "value": full, "unit": "bytes"},
        {"name": "delta_publish_bytes", "value": delta, "unit": "bytes"},
        {"name": "delta_fraction", "value": round(delta / full, 4),
         "unit": "x"},
        {"name": "delta_bytes_reused", "value": vs[str(v2)]["bytes_reused"],
         "unit": "bytes"},
        {"name": "delta_pull_byte_exact", "value": int(exact), "unit": "bool"},
    ]


def main(payload_mb: float = 8.0, runners: int = 8,
         compression: str = "", delta: bool = False) -> list:
    import ray_tpu
    from ray_tpu.weights import (MeshSpec, ShardedTreeSpec, WeightStore,
                                 local_shards_of, plan_reshard,
                                 publish_host_shards)

    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=max(8, runners))
    tree = _payload_tree(payload_mb)
    nbytes = tree["w"].nbytes
    rows = []

    # -- plan stats: 4-host train mesh -> 2-host serve mesh ---------------
    src_mesh = MeshSpec((4,), ("data",), tuple(f"t{i}" for i in range(4)))
    dst_mesh = MeshSpec((2,), ("model",), ("s0", "s1"))
    src = ShardedTreeSpec.from_tree(tree, src_mesh, default_part=("data",))
    dst = ShardedTreeSpec.from_tree(tree, dst_mesh,
                                    parts={"w": (None, "model")})
    plan = plan_reshard(src, dst)
    st = plan.stats()
    rows += [
        {"name": "plan_edges", "value": st["num_edges"], "unit": "edges"},
        {"name": "plan_bytes_moved", "value": st["bytes_moved"],
         "unit": "bytes"},
        {"name": "plan_unique_chunk_bytes", "value": st["unique_chunk_bytes"],
         "unit": "bytes"},
        {"name": "plan_no_gather", "value": int(plan.no_gather()),
         "unit": "bool"},
    ]

    # -- broadcast fan-out throughput -------------------------------------
    @ray_tpu.remote(num_cpus=0.1)
    class Subscriber:
        def __init__(self, store_name):
            self.store = WeightStore(store_name)

        def pull(self, version):
            tree = self.store.pull(version)
            return int(tree["w"].nbytes)

    store = WeightStore("bench_broadcast")
    subs = [Subscriber.remote("bench_broadcast") for _ in range(runners)]
    version = store.publish(tree)
    ray_tpu.get([s.pull.remote(version) for s in subs], timeout=300)  # warm
    t0 = time.perf_counter()
    moved = sum(ray_tpu.get([s.pull.remote(version) for s in subs],
                            timeout=300))
    dt = time.perf_counter() - t0
    rows += [
        {"name": "broadcast_fanout", "value": runners, "unit": "consumers"},
        {"name": "broadcast_MB_s", "value": round(moved / dt / 1e6, 1),
         "unit": "MB/s"},
    ]
    for s in subs:
        ray_tpu.kill(s)

    # -- cross-mesh reshard throughput ------------------------------------
    @ray_tpu.remote(num_cpus=0.1)
    class SrcHost:
        def __init__(self, store_name, host, src, dst, tree_blob):
            from ray_tpu._private.serialization import loads_trusted

            self.store = WeightStore(store_name)
            self.host, self.src, self.dst = host, src, dst
            self.shards = local_shards_of(loads_trusted(tree_blob),
                                          src, host)

        def publish(self, version):
            return publish_host_shards(self.store, version, self.src,
                                       self.host, self.shards,
                                       dst_spec=self.dst)

    @ray_tpu.remote(num_cpus=0.1)
    class DstHost:
        def __init__(self, store_name, host, dst):
            self.store = WeightStore(store_name)
            self.host, self.dst = host, dst

        def pull(self, version):
            shards = self.store.pull_shards(self.dst, self.host, version)
            return sum(a.nbytes for boxes in shards.values()
                       for a in boxes.values())

    import cloudpickle

    blob = cloudpickle.dumps(tree)
    srcs = [SrcHost.remote("bench_reshard", h, src, dst, blob)
            for h in src_mesh.hosts]
    dsts = [DstHost.remote("bench_reshard", h, dst) for h in dst_mesh.hosts]
    t0 = time.perf_counter()
    ray_tpu.get([s.publish.remote(1) for s in srcs], timeout=300)
    moved = sum(ray_tpu.get([d.pull.remote(1) for d in dsts], timeout=300))
    dt = time.perf_counter() - t0
    rows += [
        {"name": "reshard_bytes", "value": moved, "unit": "bytes"},
        {"name": "reshard_MB_s", "value": round(moved / dt / 1e6, 1),
         "unit": "MB/s"},
        {"name": "payload_MB", "value": round(nbytes / 1e6, 1),
         "unit": "MB"},
    ]
    for a in srcs + dsts:
        ray_tpu.kill(a)

    if compression:
        rows += bench_compression(payload_mb, compression)
    if delta:
        rows += bench_delta(payload_mb)
    return rows


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--payload-mb", type=float, default=8.0)
    parser.add_argument("--runners", type=int, default=8)
    parser.add_argument("--compression", default="",
                        help="price the quantized tier (int8/fp8/bf16)")
    parser.add_argument("--delta", action="store_true",
                        help="price the delta-publish tier")
    args = parser.parse_args()
    import ray_tpu

    rows = main(args.payload_mb, args.runners, args.compression, args.delta)
    print(json.dumps(rows))
    ray_tpu.shutdown()
    sys.exit(0)
