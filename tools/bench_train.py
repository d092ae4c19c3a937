"""Overlapped-train-step microbenchmark (``python -m tools.bench_train``).

Prices the PR 12 train-step flavors against each other on whatever
devices are present (the 8-device CPU mesh in CI; the TPU slice on
hardware), so BENCH rounds can attribute MFU movement to a phase:

* ``fused_step_us``           — the single fused program, unsharded
  (the 1-replica fallback / pre-PR-12 path)
* ``fused_sharded_step_us``   — ONE program with the cross-replica
  sharded optimizer update (reduce-scatter grads, 1/N opt state,
  all-gather params; XLA async collectives overlap them with compute)
* ``split_sharded_step_us``   — the phase-split flavor (fwd_bwd with
  reduce-scattered grads + sharded opt program): the difference against
  ``fused_sharded_step_us`` is the comm time a program boundary exposes
* ``traced_sharded_step_us``  — the explicit bucketed pipeline the traced
  tier runs (per-bucket reduce programs + spans)
* ``bucket_plan``             — the layer-order bucket plan stats
* ``opt_state_bytes_per_replica`` / ``opt_state_bytes_total``
* ``reducer_allreduce_mb_s``  — AsyncBucketReducer throughput through the
  CPU collective tier (single-process rank-0 loopback)

Emits one JSON object on stdout (plus ``--out FILE``).
"""

from __future__ import annotations

import argparse
import json
import os
import time


def _mesh_and_bundle(bucket_bytes: int):
    import jax

    from ray_tpu.models import CONFIGS
    from ray_tpu.parallel import TrainStepBundle, create_mesh, make_optimizer

    devs = jax.devices()
    n = len(devs)
    mesh = create_mesh({"data": n, "fsdp": 1, "seq": 1, "tensor": 1,
                        "expert": 1}, devices=devs)
    factory = lambda spec_fn: make_optimizer(  # noqa: E731
        learning_rate=1e-3, warmup_steps=5, total_steps=1000,
        clip_spec_fn=spec_fn)
    bundle = TrainStepBundle(CONFIGS["tiny"], mesh,
                             optimizer_factory=factory,
                             shard_update=n > 1, bucket_bytes=bucket_bytes)
    return bundle, n


def _time_steps(fn, init, batch, steps, warmup):
    import jax

    params, opt_state = init()
    for _ in range(warmup):
        params, opt_state, loss = fn(params, opt_state, batch)
        jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = fn(params, opt_state, batch)
    jax.block_until_ready(loss)
    return (time.perf_counter() - t0) / steps * 1e6, (params, opt_state)


def bench_step_flavors(bucket_bytes: int, steps: int = 10,
                       warmup: int = 3) -> dict:
    """One bucketed+sharded step of every flavor under JAX_PLATFORMS=cpu
    is ALSO the tier-1 smoke path (tests/test_train_smoke.py) — keep this
    callable cheap and hardware-free."""
    import jax
    import numpy as np

    from ray_tpu.util import tracing

    out = {}
    bundle, n = _mesh_and_bundle(bucket_bytes)
    out["n_devices"] = n
    batch = bundle.make_batch(np.random.default_rng(0), 2 * n, 64)

    out["fused_step_us"], _ = _time_steps(
        lambda p, s, b: bundle._fused_step(p, s, b),
        lambda: bundle.init(jax.random.PRNGKey(0)), batch, steps, warmup)
    if bundle.shard_update:
        out["fused_sharded_step_us"], (ps, ss) = _time_steps(
            lambda p, s, b: bundle._fused_step_sharded(p, s, b),
            lambda: bundle.init_sharded(jax.random.PRNGKey(0)),
            batch, steps, warmup)

        def split(p, s, b):
            loss, g = bundle._fwd_bwd_rs(p, b)
            p, s = bundle._opt_apply_sharded(g, s, p)
            return p, s, loss

        out["split_sharded_step_us"], _ = _time_steps(
            split, lambda: bundle.init_sharded(jax.random.PRNGKey(0)),
            batch, steps, warmup)
        was_enabled = tracing.enabled()
        tracing.enable()
        try:
            out["traced_sharded_step_us"], _ = _time_steps(
                lambda p, s, b: bundle.step(p, s, b),
                lambda: bundle.init_sharded(jax.random.PRNGKey(0)),
                batch, max(steps // 2, 1), warmup)
        finally:
            if not was_enabled:
                tracing._enabled = False
                os.environ.pop("RAY_TPU_ENABLE_TRACING", None)
        out["opt_state_bytes_per_replica"] = \
            bundle.opt_state_bytes_per_replica(ss)
        out["opt_state_bytes_total"] = bundle.opt_state_bytes_total()
        out["bucket_plan"] = bundle.bucket_plan.stats()
    return out


def bench_reducer(mb: int = 8, compression=None) -> dict:
    """AsyncBucketReducer throughput on a world-size-1 loopback group
    (prices the pack/unpack + thread handoff floor, no network). With
    ``compression`` the same tree rides the quantized path — the wire
    accounting (``*_wire_reduction_x``) is the fp32-vs-quantized byte
    ratio the ISSUE acceptance bar reads."""
    import numpy as np

    from ray_tpu import collective as col
    from ray_tpu.collective.bucketed import (AsyncBucketReducer, leaf_meta,
                                             plan_buckets)

    tag = compression or "fp32"
    group = f"bench_train.reducer.{tag}"
    tree = {f"leaf{i}": np.random.default_rng(i).normal(
        size=(mb * 1024, 128)).astype(np.float32) for i in range(2)}
    col.init_collective_group(1, 0, backend="cpu", group_name=group)
    plan = plan_buckets(leaf_meta(tree), bucket_bytes=4 << 20, world_size=1)
    red = AsyncBucketReducer(group, plan, compression=compression)
    prefix = "reducer" if compression is None else f"reducer_{compression}"
    try:
        red.reduce_tree(tree)  # warm
        nbytes = sum(a.nbytes for a in tree.values())
        t0 = time.perf_counter()
        iters = 3
        for _ in range(iters):
            red.reduce_tree(tree)
        dt = (time.perf_counter() - t0) / iters
    finally:
        red.shutdown()
        col.destroy_collective_group(group)
    out = {f"{prefix}_allreduce_mb_s": nbytes / dt / 1e6,
           f"{prefix}_buckets": plan.num_buckets}
    if compression is not None:
        ws = red.wire_stats()
        out[f"{prefix}_wire_bytes"] = ws["bytes_wire"]
        out[f"{prefix}_fp32_bytes"] = ws["bytes_fp32_equiv"]
        out[f"{prefix}_wire_reduction_x"] = ws.get("wire_reduction_x", 0.0)
        out[f"{prefix}_encode_s_per_iter"] = round(
            ws["encode_s"] / (iters + 1), 5)
    return out


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="")
    parser.add_argument("--bucket-bytes", type=int, default=1 << 20)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--skip-reducer", action="store_true")
    parser.add_argument("--compression", default="int8",
                        help="codec for the quantized-reducer pricing "
                             "(int8/fp8/bf16; 'none' skips it)")
    args = parser.parse_args()

    t0 = time.time()
    result = bench_step_flavors(args.bucket_bytes, steps=args.steps)
    if not args.skip_reducer:
        import ray_tpu

        started = not ray_tpu.is_initialized()
        if started:
            # this process has touched JAX and holds whatever chips there
            # are; the reducer's workers run the CPU collective tier, so
            # pin every child to the CPU backend (children inherit)
            os.environ["JAX_PLATFORMS"] = "cpu"
            ray_tpu.init(num_cpus=2)
        try:
            result.update(bench_reducer())
            if args.compression and args.compression != "none":
                result.update(bench_reducer(compression=args.compression))
        finally:
            if started:
                ray_tpu.shutdown()
    result["wall_s"] = round(time.time() - t0, 1)
    blob = json.dumps(result, indent=2, default=str)
    print(blob)
    if args.out:
        with open(args.out, "w") as f:
            f.write(blob)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
