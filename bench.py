"""Flagship benchmark: transformer LM train-step MFU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} and
exits 0 — or exits non-zero when anything fails, and when JAX finds no TPU:
a number from a CPU run is never written under the name of a device metric.
The north-star target (BASELINE.md) is >=35% MFU on the fine-tune path;
``vs_baseline`` is measured MFU / 0.35 (so 1.0 == target met). The reference
publishes no tokens/sec constants (BASELINE.json `published` is empty), so
the MFU target is the comparison axis.

All device work happens in THIS process (a chip belongs to one process at a
time; the script starts no children). On one chip the line is the fused
step; on several local chips the primary metric is the **overlapped +
cross-replica-sharded** data-parallel step across every chip (per-chip
MFU): optimizer state sharded over the data axis (1/N per replica), grads
reduce-scattered out of the backward, updated params all-gathered — all
inside one XLA program whose async collectives hide the comms under
compute (see ray_tpu/parallel/OVERLAP.md). The emitted line carries a
per-phase breakdown (`fwd_bwd_s`, `optimizer_s`, `allreduce_s`,
`overlap_fraction`, `opt_state_bytes_per_replica`) so MFU movement is
attributable to a phase, and the single-chip fused step stays on the line
as `mfu_1chip`. The sharded phase has not run on a chip through this
script yet (`chip_smoke.py --chips 4` runs the same step through
JaxTrainer); the workloads matrix and the peaks table are ROADMAP S0.
"""

from __future__ import annotations

import json
import os
import sys
import time


# bf16 peak FLOP/s per chip by generation (v5e default; override via env).
# ORDER MATTERS: more specific substrings first ("v5 lite" must not match
# the v5p entry).
PEAK_FLOPS = [
    ("v5e", 197e12),
    ("v5lite", 197e12),
    ("v5p", 459e12),
    ("v6e", 918e12),
    ("v6", 918e12),
    ("v4", 275e12),
    ("v5", 459e12),
]


def _peak_for(kind: str) -> float:
    env = os.environ.get("RAY_TPU_PEAK_FLOPS")
    if env:
        return float(env)
    norm = (kind or "").lower().replace(" ", "").replace("-", "")
    for key, val in PEAK_FLOPS:
        if key in norm:
            return val
    raise ValueError(f"no peak FLOP/s known for device_kind {kind!r}: add it "
                     f"to PEAK_FLOPS (or set RAY_TPU_PEAK_FLOPS)")


def main() -> int:
    print(json.dumps(_run()))
    return 0


def _measure(cfg, mesh_devices, batch, seq, steps, warmup, peak):
    """One config's (mfu, tokens/s) on the given devices (fused step)."""
    import dataclasses

    import jax
    import numpy as np

    from ray_tpu.parallel import TrainStepBundle, create_mesh, make_optimizer

    cfg = dataclasses.replace(cfg, max_seq_len=seq)
    mesh = create_mesh({"data": 1, "fsdp": 1, "seq": 1, "tensor": 1,
                        "expert": 1}, devices=mesh_devices)
    bundle = TrainStepBundle(cfg, mesh, optimizer=make_optimizer(
        learning_rate=1e-4, warmup_steps=10, total_steps=1000))
    params, opt_state = bundle.init(jax.random.PRNGKey(0))
    batch_data = bundle.make_batch(np.random.default_rng(0), batch, seq)
    for _ in range(warmup):
        params, opt_state, loss = bundle.step(params, opt_state, batch_data)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = bundle.step(params, opt_state, batch_data)
    float(loss)
    dt = (time.perf_counter() - t0) / steps
    tps = batch * seq / dt
    return tps * cfg.flops_per_token() / peak, tps


def _phase_breakdown(bundle, params, opt_state, batch_data, step_time_s,
                     iters=3):
    """Price the split phase programs + the bare collectives so the fused
    sharded step's time decomposes attributably.

    - ``fwd_bwd_s``: split backward WITH the grad reduce-scatter on its
      output (the overlappable phase);
    - ``optimizer_s``: sharded update + param all-gather;
    - ``allreduce_s``: the bare collective cost (flat reduce-scatter over
      the grad bytes + flat all-gather over the param bytes);
    - ``overlap_fraction``: the share of ``allreduce_s`` the ONE-program
      step hides: (fwd_bwd_s + optimizer_s - step_time_s) / allreduce_s,
      clamped to [0, 1] (phase-split runs expose the collectives at
      program boundaries; the fused program overlaps them with compute).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    out = {}
    p, s = params, opt_state

    def _barrier(state):
        # tiny scalar readback as the completion barrier
        for leaf in jax.tree_util.tree_leaves(state):
            if getattr(leaf, "shape", None) == ():
                return float(jax.device_get(leaf))
        return None

    # compile both split programs before any timed loop
    loss_w, grads_w = bundle._fwd_bwd_rs(p, batch_data)
    float(loss_w)
    p, s = bundle._opt_apply_sharded(grads_w, s, p)
    _barrier(s)
    # phase 1: split backward w/ reduce-scattered grads (loss readback =
    # program completion)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, grads = bundle._fwd_bwd_rs(p, batch_data)
        float(loss)
    out["fwd_bwd_s"] = (time.perf_counter() - t0) / iters
    # phase 1+2 threaded (opt donates state+params, so each iteration
    # consumes and re-emits them)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss2, grads2 = bundle._fwd_bwd_rs(p, batch_data)
        float(loss2)
        p, s = bundle._opt_apply_sharded(grads2, s, p)
        _barrier(s)
    both = (time.perf_counter() - t0) / iters
    out["optimizer_s"] = max(both - out["fwd_bwd_s"], 0.0)

    # bare collectives at the real byte volumes (flat proxies: collective
    # cost is volume-bound, not tree-shape-bound)
    mesh = bundle.mesh
    n = bundle.dp_size
    gelems = sum(int(np.prod(a.shape)) for a in
                 jax.tree_util.tree_leaves(bundle._abstract_params))
    gelems = max((gelems // (n * n)) * (n * n), n * n)

    def rs(x):
        return jax.lax.psum_scatter(x, "data", scatter_dimension=0,
                                    tiled=True)

    def ag(x):
        return jax.lax.all_gather(x, "data", axis=0, tiled=True)

    rs_fn = jax.jit(shard_map(rs, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False))
    ag_fn = jax.jit(shard_map(ag, mesh=mesh, in_specs=P("data"),
                              out_specs=P(), check_vma=False))
    flat = jnp.zeros((gelems,), jnp.float32)
    jax.block_until_ready(rs_fn(flat))  # compile
    jax.block_until_ready(ag_fn(flat))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(rs_fn(flat))
        jax.block_until_ready(ag_fn(flat))
    out["allreduce_s"] = (time.perf_counter() - t0) / iters
    exposed_saved = out["fwd_bwd_s"] + out["optimizer_s"] - step_time_s
    out["overlap_fraction"] = round(
        max(0.0, min(1.0, exposed_saved / out["allreduce_s"]))
        if out["allreduce_s"] > 0 else 0.0, 4)
    out["fwd_bwd_s"] = round(out["fwd_bwd_s"], 4)
    out["optimizer_s"] = round(out["optimizer_s"], 4)
    out["allreduce_s"] = round(out["allreduce_s"], 4)
    return out


def _measure_sharded(cfg, devices, per_chip_batch, seq, steps, warmup, peak):
    """The primary path: DP across all local chips with the overlapped +
    sharded optimizer update (ONE program; opt state 1/N per replica)."""
    import dataclasses

    import jax
    import numpy as np

    from ray_tpu.parallel import TrainStepBundle, create_mesh, make_optimizer

    n = len(devices)
    cfg = dataclasses.replace(cfg, max_seq_len=seq)
    mesh = create_mesh({"data": n, "fsdp": 1, "seq": 1, "tensor": 1,
                        "expert": 1}, devices=devices)
    bundle = TrainStepBundle(
        cfg, mesh, shard_update=True,
        optimizer_factory=lambda spec_fn: make_optimizer(
            learning_rate=1e-4, warmup_steps=10, total_steps=1000,
            clip_spec_fn=spec_fn))
    params, opt_state = bundle.init_sharded(jax.random.PRNGKey(0))
    batch = per_chip_batch * n
    batch_data = bundle.make_batch(np.random.default_rng(0), batch, seq)
    for _ in range(warmup):
        params, opt_state, loss = bundle.step(params, opt_state, batch_data)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = bundle.step(params, opt_state, batch_data)
    float(loss)
    dt = (time.perf_counter() - t0) / steps
    tokens_per_sec = batch * seq / dt
    mfu = tokens_per_sec * cfg.flops_per_token() / (peak * n)
    stats = {
        "mfu": mfu,
        "tokens_per_sec": tokens_per_sec,
        "step_time_s": dt,
        "loss": float(loss),
        "n_chips": n,
        "batch_global": batch,
        "opt_state_bytes_per_replica":
            bundle.opt_state_bytes_per_replica(opt_state),
        "bucket_count": bundle.bucket_plan.num_buckets,
        "bucket_bytes": bundle.bucket_bytes,
    }
    stats["opt_state_bytes_total"] = bundle.opt_state_bytes_total()
    if not os.environ.get("RAY_TPU_BENCH_SKIP_PHASES"):
        stats.update(_phase_breakdown(bundle, params, opt_state,
                                      batch_data, dt))
    return stats


def _run() -> dict:
    t_start = time.time()
    import dataclasses

    import jax
    import numpy as np

    from ray_tpu.models import CONFIGS
    from ray_tpu.parallel import TrainStepBundle, create_mesh, make_optimizer
    from ray_tpu.utils import is_tpu

    devices = jax.devices()
    if not is_tpu():
        raise SystemExit(
            f"bench.py measures the train step on a TPU; JAX found "
            f"{devices[0].platform!r} devices and a CPU timing is never "
            f"written under the name of a device metric")
    peak = _peak_for(devices[0].device_kind)

    config_name = os.environ.get("RAY_TPU_BENCH_CONFIG", "") or "1b"
    # 1b at batch 4 is what fits one 16 GB chip with fp32 params + AdamW
    batch, seq = int(os.environ.get("RAY_TPU_BENCH_BATCH", "4")), 2048
    steps, warmup = 10, 3

    cfg = dataclasses.replace(CONFIGS[config_name], max_seq_len=seq)
    mesh = create_mesh({"data": 1, "fsdp": 1, "seq": 1, "tensor": 1,
                        "expert": 1}, devices=devices[:1])
    bundle = TrainStepBundle(cfg, mesh, optimizer=make_optimizer(
        learning_rate=1e-4, warmup_steps=10, total_steps=1000))
    params, opt_state = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch_data = bundle.make_batch(rng, batch, seq)

    for _ in range(warmup):
        params, opt_state, loss = bundle.step(params, opt_state, batch_data)
    float(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = bundle.step(params, opt_state, batch_data)
    float(loss)  # steps serialize through the params dependency chain
    dt = (time.perf_counter() - t0) / steps

    tokens_per_sec = batch * seq / dt
    flops_per_token = cfg.flops_per_token()  # 6*N_active + attention
    mfu_1chip = tokens_per_sec * flops_per_token / peak

    result = {
        "metric": f"train_mfu_{config_name}",
        "value": round(mfu_1chip, 4),
        "unit": "mfu_fraction",
        "vs_baseline": round(mfu_1chip / 0.35, 4),
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "step_time_s": round(dt, 4),
        "loss": round(float(loss), 4),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "config": config_name,
        "batch": batch,
        "seq": seq,
        "mfu_1chip": round(mfu_1chip, 4),
        "step_time_1chip_s": round(dt, 4),
        # breakdown defaults for the 1-chip line (the sharded phase below
        # overwrites them when it runs)
        "fwd_bwd_s": 0.0,
        "optimizer_s": 0.0,
        "allreduce_s": 0.0,
        "overlap_fraction": 0.0,
        "opt_state_bytes_per_replica":
            bundle.opt_state_bytes_per_replica(opt_state),
    }
    # release the primary config's HBM before the sharded phase
    del params, opt_state, bundle, batch_data

    if len(devices) > 1 and not os.environ.get("RAY_TPU_BENCH_SKIP_SHARDED"):
        # PRIMARY on several chips: overlapped bucketed allreduce +
        # cross-replica sharded optimizer update across every chip;
        # `value` is the per-chip MFU of that step. The 1-chip fused
        # number above stays on the line as mfu_1chip.
        sh = _measure_sharded(CONFIGS[config_name], devices,
                              per_chip_batch=batch, seq=seq,
                              steps=8, warmup=2, peak=peak)
        result["value"] = round(sh["mfu"], 4)
        result["vs_baseline"] = round(sh["mfu"] / 0.35, 4)
        result["tokens_per_sec_per_chip"] = round(
            sh["tokens_per_sec"] / sh["n_chips"], 1)
        result["step_time_s"] = round(sh["step_time_s"], 4)
        result["loss"] = round(sh["loss"], 4)
        result["batch"] = sh["batch_global"]
        for k in ("n_chips", "fwd_bwd_s", "optimizer_s", "allreduce_s",
                  "overlap_fraction", "opt_state_bytes_per_replica",
                  "opt_state_bytes_total", "bucket_count", "bucket_bytes"):
            if k in sh:
                result[k] = sh[k]
        result["sharded_update"] = True

    if config_name == "1b" and not os.environ.get(
            "RAY_TPU_BENCH_SKIP_SECONDARY"):
        # secondary config: 350m at b8/s1024, the head_dim-64 shape whose
        # backward runs the pallas kernels
        mfu2, tps2 = _measure(CONFIGS["350m"], mesh_devices=devices[:1],
                              batch=8, seq=1024, steps=6, warmup=2,
                              peak=peak)
        result["mfu_350m"] = round(mfu2, 4)
        result["tokens_per_sec_350m"] = round(tps2, 1)
        result["vs_target_350m"] = round(mfu2 / 0.35, 4)
    result["wall_s"] = round(time.time() - t_start, 1)
    return result


if __name__ == "__main__":
    sys.exit(main())
