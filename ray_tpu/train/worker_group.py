"""Training worker group: N actors gang-scheduled on a PG / TPU slice.

Reference: train/v2/_internal/execution/worker_group/worker_group.py:104 —
actors created in a placement group (SPREAD across hosts), each running the
user's train loop; the TPU path reserves an ICI slice first
(callbacks/tpu_reservation_callback.py:9 -> util/tpu.py slice PG).

TPU runtime ownership note (SURVEY.md §7 hard part (c)): exactly one process
per host may own the TPU, and a process that initialized jax.distributed
cannot re-form a smaller mesh — so the group always kills its workers on
shutdown/restart and re-creates fresh actor processes.
"""

from __future__ import annotations

import os
import socket
import threading
import time
from typing import Any, Dict, List, Optional

import cloudpickle

import ray_tpu
from ray_tpu.train.config import ScalingConfig
from ray_tpu.train.context import TrainContext, set_context
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.util.placement_group import PlacementGroup, placement_group, remove_placement_group
from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy


@ray_tpu.remote
class TrainWorker:
    def __init__(self, rank: int, world_size: int):
        self.rank = rank
        self.world_size = world_size
        self._distributed = False
        self._grad_sync: Optional[Dict[str, Any]] = None

    def setup_grad_sync(self, group_name: str, backend: str,
                        bucket_bytes: int,
                        compression: Optional[str] = None) -> bool:
        """Join the group's bucketed grad-sync collective (and its
        ``.norm`` sibling for the sharded update's clip allgather + param
        broadcasts). The train loop reaches it through
        ``train.get_context().make_bucket_reducer`` /
        ``make_sharded_optimizer`` (collective/bucketed.py).
        ``compression`` (None/int8/fp8/bf16) is the default codec those
        helpers hand to the reducer/optimizer (collective/quant.py)."""
        from ray_tpu import collective as col
        from ray_tpu.collective.bucketed import init_sharded_optimizer_groups
        from ray_tpu.collective.quant import resolve_codec

        # fail at setup, not mid-train: only the CPU store-actor backend
        # implements the explicit quantized exchange (XlaGroup raises at
        # the first bucket otherwise)
        if resolve_codec(compression) is not None and backend != "cpu":
            raise ValueError(
                f"grad_sync_compression={compression!r} requires "
                f"grad_sync_backend='cpu' (got {backend!r}): the XLA "
                f"backend has no quantized exchange")
        init_sharded_optimizer_groups(self.world_size, self.rank,
                                      backend=backend, base_name=group_name)
        # a group is dedicated to ONE reducer (ops match by sequence
        # number): user-level bucket reducers get their own sibling so
        # they can't interleave with a sharded optimizer's internal one
        col.init_collective_group(self.world_size, self.rank,
                                  backend=backend,
                                  group_name=f"{group_name}.user")
        self._grad_sync = {"group": group_name, "backend": backend,
                           "bucket_bytes": int(bucket_bytes),
                           "world_size": self.world_size,
                           "compression": compression}
        return True

    def get_host_info(self) -> Dict[str, Any]:
        return {
            "hostname": socket.gethostname(),
            "ip": "127.0.0.1",
            "node_id": ray_tpu.get_runtime_context().get_node_id(),
            "pid": os.getpid(),
        }

    def find_free_port(self) -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def setup_distributed(self, coordinator: str, num_processes: int,
                          process_id: int) -> bool:
        """jax.distributed bootstrap (reference: train/v2/jax/config.py:41
        _setup_jax_tpu_environment -> jax.distributed.initialize)."""
        from ray_tpu.utils import import_jax

        jax = import_jax()
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
        self._distributed = True
        return True

    def run(self, fn_blob: bytes, config: Optional[dict], controller,
            latest_checkpoint_path: Optional[str], run_dir: str,
            dataset_shard_blob: Optional[bytes]) -> Dict[str, Any]:
        # driver-authored blobs: decode only through the audited
        # serialization boundary (raylint SER001)
        from ray_tpu._private.serialization import loads_trusted
        from ray_tpu.util import goodput

        # tag this process's goodput ledger with the run so its bucket
        # seconds aggregate under the right job GCS-side (a reused worker
        # switching runs resets its accumulators in set_job)
        goodput.set_job(run_dir.rsplit("/", 1)[-1])
        fn = loads_trusted(fn_blob)
        shards = loads_trusted(dataset_shard_blob) if dataset_shard_blob else {}
        ctx = TrainContext(
            rank=self.rank,
            world_size=self.world_size,
            local_rank=0,
            node_rank=self.rank,
            controller=controller,
            latest_checkpoint=(Checkpoint(latest_checkpoint_path)
                               if latest_checkpoint_path else None),
            config=config,
            dataset_shards=shards,
            grad_sync=self._grad_sync,
        )
        ctx.run_dir = run_dir
        set_context(ctx)
        try:
            if config is not None:
                result = fn(config)
            else:
                result = fn()
            return {"rank": self.rank, "result": result}
        finally:
            set_context(None)

    # -- weight plane (ray_tpu/weights/): elastic state hand-off ---------

    def publish_weight_shards(self, store_name: str, version: int,
                              shard_tree: Any, durable: bool = True) -> int:
        """Publish this rank's shard of the training state (every leaf
        sharded equally along dim 0 across the group). ``durable=True``
        routes the bytes through the store actor so the published version
        outlives this worker — the elastic re-form path: a killed group's
        surviving state is pulled back by the NEXT incarnation, resharded
        onto its (smaller) mesh, via ``pull_weight_shards``."""
        from ray_tpu.train.scaling_policy import mesh_spec_for
        from ray_tpu.util import tracing
        from ray_tpu.weights import (ShardedTreeSpec, WeightStore,
                                     publish_host_shards)
        from ray_tpu.weights.spec import flatten_tree, host_boxes
        import numpy as np

        with tracing.profile("train.publish", category="train",
                             store=store_name, version=version):
            mesh = mesh_spec_for(self.world_size)
            skeleton, leaves = flatten_tree(shard_tree)
            parts, meta, shards = {}, {}, {}
            host = mesh.hosts[self.rank]
            for path, leaf in leaves.items():
                arr = np.asarray(leaf)
                parts[path] = ("data",) + (None,) * (arr.ndim - 1)
                meta[path] = ((arr.shape[0] * self.world_size,)
                              + arr.shape[1:], arr.dtype.str)
            spec = ShardedTreeSpec(mesh=mesh, parts=parts, meta=meta)
            for path, leaf in leaves.items():
                box = host_boxes(spec.mesh, parts[path], meta[path][0],
                                 host)[0]
                shards[path] = {box: np.asarray(leaf)}
            publish_host_shards(WeightStore(store_name), version, spec, host,
                                shards, skeleton=skeleton, durable=durable)
        return version

    def pull_weight_shards(self, store_name: str,
                           version: Optional[int] = None) -> Dict[str, Any]:
        """Pull this rank's shard of the newest published state, resharded
        onto THIS group's mesh (the publisher's world size may differ —
        that is the point). Returns ``{"version": v, "tree": shard_tree}``
        with each leaf's dim 0 sized for this world."""
        from ray_tpu.train.scaling_policy import mesh_spec_for
        from ray_tpu.weights import ShardedTreeSpec, WeightStore
        from ray_tpu.weights.spec import unflatten_tree
        from ray_tpu.weights.store import _spec_from_payload

        store = WeightStore(store_name)
        man = store.manifest(version)
        src = _spec_from_payload(man["spec"])
        mesh = mesh_spec_for(self.world_size)
        dst = ShardedTreeSpec(
            mesh=mesh,
            parts={p: ("data",) + (None,) * (len(shape) - 1)
                   for p, (shape, _) in src.meta.items()},
            meta=dict(src.meta))
        shards, ver = store.pull_shards(dst, mesh.hosts[self.rank],
                                        man["version"], return_version=True)
        leaves = {p: next(iter(boxes.values())) for p, boxes in shards.items()}
        return {"version": ver, "tree": unflatten_tree(man["skeleton"], leaves)}

    def shutdown(self):
        return True


class WorkerGroup:
    def __init__(self, scaling: ScalingConfig, name_prefix: str = "train",
                 ready_timeout: float = 600.0):
        self.scaling = scaling
        self.ready_timeout = ready_timeout
        self.workers: List[Any] = []
        self.pg: Optional[PlacementGroup] = None
        self.slice_pg = None
        self._create()

    def _create(self):
        n = self.scaling.num_workers
        timeout = self.ready_timeout
        if self.scaling.use_tpu:
            from ray_tpu.util.tpu import slice_placement_group

            try:
                self.slice_pg = slice_placement_group(
                    num_hosts=n, pod_type=self.scaling.topology,
                    chips_per_host=self.scaling.chips_per_worker or None)
                if self.slice_pg.ready(timeout=timeout):
                    self.pg = self.slice_pg.placement_group
                else:
                    # unready slice reservation must be released, not
                    # silently scheduled against (leaks across retries)
                    try:
                        remove_placement_group(self.slice_pg.placement_group)
                    except Exception:
                        pass
                    self.slice_pg = None
            except Exception:
                self.pg = None  # fall through to plain PG
        if self.pg is None:
            self.pg = placement_group(
                [self.scaling.bundle() for _ in range(n)],
                strategy=self.scaling.placement_strategy
                if self.scaling.placement_strategy in
                ("PACK", "SPREAD", "STRICT_PACK", "STRICT_SPREAD") else "SPREAD")
            if not self.pg.ready(timeout=timeout):
                from ray_tpu.exceptions import PlacementGroupError

                pg, self.pg = self.pg, None
                try:
                    remove_placement_group(pg)  # don't leak the reservation
                except Exception:
                    pass
                raise PlacementGroupError(
                    f"worker-group placement group ({n} x "
                    f"{self.scaling.bundle()}) not ready within {timeout}s")
        res = self.scaling.bundle()
        self.workers = [
            TrainWorker.options(
                num_cpus=res.get("CPU", 1.0),
                num_tpus=res.get("TPU", 0.0),
                resources={k: v for k, v in res.items() if k not in ("CPU", "TPU")},
                scheduling_strategy=PlacementGroupSchedulingStrategy(self.pg, i),
                max_restarts=0,
            ).remote(i, n)
            for i in range(n)
        ]
        # make sure every worker is alive before proceeding
        ray_tpu.get([w.get_host_info.remote() for w in self.workers],
                    timeout=self.ready_timeout)

    def setup_grad_sync(self, group_name: str, backend: str = "cpu",
                        bucket_bytes: int = 32 << 20,
                        compression: Optional[str] = None):
        """Initialize bucketed grad sync on every worker (driver side)."""
        ray_tpu.get([
            w.setup_grad_sync.remote(group_name, backend, bucket_bytes,
                                     compression)
            for w in self.workers
        ], timeout=300)

    def bootstrap_distributed(self):
        """Form the jax.distributed mesh across all workers (rank 0 hosts the
        coordinator)."""
        infos = ray_tpu.get([w.get_host_info.remote() for w in self.workers],
                            timeout=300)
        port = ray_tpu.get(self.workers[0].find_free_port.remote(), timeout=60)
        coordinator = f"{infos[0]['ip']}:{port}"
        refs = [
            w.setup_distributed.remote(coordinator, len(self.workers), i)
            for i, w in enumerate(self.workers)
        ]
        ray_tpu.get(refs, timeout=600)

    def run(self, fn_blob, config, controller, latest_ckpt, run_dir, shards_per_rank):
        return [
            w.run.remote(fn_blob, config, controller,
                         latest_ckpt.path if latest_ckpt else None, run_dir,
                         shards_per_rank[i] if shards_per_rank else None)
            for i, w in enumerate(self.workers)
        ]

    def shutdown(self):
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []
        if self.pg is not None:
            try:
                remove_placement_group(self.pg)
            except Exception:
                pass
            self.pg = None
