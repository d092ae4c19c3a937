"""Multi-learner RL throughput bench (``python -m tools.bench_rl_learners``).

Produces the RL_MULTILEARNER_r* artifact: PPO CartPole steps/sec at N
learners with the gradient allreduce on the fp32 collective path vs the
quantized (int8 + error-feedback) path — the end-to-end number for the
EQuARX-style compression tier. Also reports final mean episode return per
flavor so a throughput win cannot silently ship a quality regression.

Usage::

    python tools/bench_rl_learners.py [--learners 4] [--iters 8]
        [--compression int8] [--out RL_MULTILEARNER_r06.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_flavor(num_learners: int, iters: int, compression, seed: int = 1,
               num_cpus: int = 8) -> dict:
    import numpy as np

    import ray_tpu
    from ray_tpu.rl import PPOConfig

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=num_cpus)
    algo = PPOConfig(
        env="CartPole-v1",
        num_env_runners=2,
        num_envs_per_runner=4,
        rollout_length=128,
        epochs=8,
        num_learners=num_learners,
        grad_compression=compression,
        seed=seed,
    ).build()
    sps, returns = [], []
    try:
        algo.train()  # warm: compile + actor spin-up out of the window
        for _ in range(iters):
            m = algo.train()
            sps.append(m["steps_per_sec"])
            returns.append(m["episode_return_mean"])
    finally:
        algo.stop()
        ray_tpu.shutdown()
    return {
        "steps_per_sec": round(float(np.median(sps)), 1),
        "steps_per_sec_mean": round(float(np.mean(sps)), 1),
        "episode_return_final": round(float(returns[-1]), 1),
        "loss_metric_iters": iters,
    }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--learners", type=int, default=4)
    parser.add_argument("--iters", type=int, default=8)
    parser.add_argument("--compression", default="int8")
    parser.add_argument("--num-cpus", type=int, default=8)
    parser.add_argument("--out", default="")
    args = parser.parse_args()

    t0 = time.time()
    n = args.learners
    from ray_tpu.collective.quant import resolve_codec

    codec = resolve_codec(args.compression)
    # analytic per-element both-legs ratio (fp32 = 4 B/el on each leg);
    # matches the reducer's measured wire_stats() at real tree sizes —
    # int8:256 -> 3.94x, fp8 -> 3.94x, bf16 -> 2.0x
    wire_x = round(4.0 / codec.bytes_per_element, 2) if codec else 1.0
    fp32 = run_flavor(n, args.iters, None, num_cpus=args.num_cpus)
    quant = run_flavor(n, args.iters, args.compression,
                       num_cpus=args.num_cpus)
    result = {
        f"sps_num_learners_{n}_fp32": fp32["steps_per_sec"],
        f"sps_num_learners_{n}_{args.compression}": quant["steps_per_sec"],
        "ratio_quant_vs_fp32": round(
            quant["steps_per_sec"] / max(fp32["steps_per_sec"], 1e-9), 3),
        "return_final_fp32": fp32["episode_return_final"],
        f"return_final_{args.compression}": quant["episode_return_final"],
        "detail": {"fp32": fp32, args.compression: quant},
        "wire_reduction_x": wire_x,
        "note": (
            f"PPO CartPole steps/sec, 2 env-runners, {n} learners, CPU CI "
            f"tier: gradient allreduce on the fp32 collective path vs the "
            f"{args.compression} block-quantized path (error-feedback, "
            f"contribute + broadcast legs quantized — {wire_x}x fewer "
            f"wire bytes; see collective/QUANT.md). CPU-tier caveat: the "
            f"'wire' here is same-host shared memory (free), so the SPS "
            f"ratio prices the ENCODE overhead only — the byte reduction "
            f"would have to pay on DCN-bound multi-host learner groups, "
            f"which no run has measured."),
        "wall_s": round(time.time() - t0, 1),
    }
    blob = json.dumps(result, indent=1)
    print(blob)
    if args.out:
        with open(args.out, "w") as f:
            f.write(blob + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
