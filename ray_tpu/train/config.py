"""Train configuration dataclasses.

Reference: python/ray/air/config.py (ScalingConfig/RunConfig/CheckpointConfig)
+ train/v2 failure policy config (v2/_internal/execution/failure_handling/).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional


@dataclass
class ScalingConfig:
    num_workers: int = 1
    use_tpu: bool = False
    # resources per training worker actor
    resources_per_worker: Dict[str, float] = field(default_factory=lambda: {"CPU": 1.0})
    chips_per_worker: int = 0  # TPU chips each worker owns (0 = all on its host)
    topology: Optional[str] = None  # e.g. "v5e-16" — selects a slice pod type
    placement_strategy: str = "SPREAD"
    # bootstrap jax.distributed across workers (multi-host SPMD). Defaults on
    # for multi-worker TPU groups.
    bootstrap_distributed: Optional[bool] = None
    # elasticity (reference: scaling_policy.py:32): on worker loss, re-form
    # the group at the largest mesh-shaped size the cluster can host
    # instead of insisting on num_workers
    elastic: bool = False
    min_workers: int = 1
    # mesh-shaped sizes only: "pow2" (powers of two) or an int slice size
    # (group size must be a whole multiple — TPU slice granularity)
    elastic_granularity: Any = "pow2"
    # bucketed grad synchronization across the group (the explicit-
    # collective tier of the overlapped train step): backend "cpu" (CI) or
    # "xla" (device collectives); None = off. Train loops reach it via
    # train.get_context().grad_sync / make_bucket_reducer /
    # make_sharded_optimizer (cross-replica sharded update: opt state
    # 1/N per worker).
    grad_sync_backend: Optional[str] = None
    grad_sync_bucket_bytes: int = 32 << 20
    # wire compression for the bucketed grad sync (collective/quant.py):
    # None (fp32, bit-identical to the uncompressed tier), "int8" / "fp8"
    # (block-quantized with error feedback; ~4x fewer wire bytes) or
    # "bf16" (plain narrowing, 2x). Strictly opt-in; CPU backend only —
    # the device step (TrainStepBundle) syncs gradients inside its one
    # GSPMD program, in their own dtype.
    grad_sync_compression: Optional[str] = None

    def bundle(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker)
        if self.use_tpu and self.chips_per_worker:
            res["TPU"] = float(self.chips_per_worker)
        return res


@dataclass
class FailureConfig:
    max_failures: int = 0  # worker-group restarts allowed


@dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"


@dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None  # local path or fsspec-style URI
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(default_factory=CheckpointConfig)


@dataclass
class Result:
    metrics: Dict[str, Any]
    checkpoint: Optional["Checkpoint"]
    error: Optional[str] = None
    path: str = ""


from ray_tpu.train.checkpoint import Checkpoint  # noqa: E402  (re-export cycle)
