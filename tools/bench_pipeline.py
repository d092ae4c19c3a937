"""Pipeline-parallel microbenchmark: 1F1B bubble + throughput vs single
mesh (one JSON line; writes PIPE_r*.json at the repo root).

Measures, per stage count S (default 2 and 4, M microbatches each) and
optionally per interleave factor V (``--interleave``):

- ``tokens_per_s``: end-to-end pipeline training throughput over real
  stage actors + channels, vs the single-mesh fused ``TrainStepBundle``
  step at the same total batch (the equal-chip-count baseline on the CPU
  tier: both sides own the same 8 virtual devices).
- ``bubble_fraction``: the (interleaved) 1F1B schedule's analytic bubble
  from the event simulator — exactly (S-1)/(S-1+V*M) at equal per-chunk
  costs, carried as ``meta.floor`` on the row so benchtrack can hold the
  measurement to the analytic bound — plus the *measured* per-stage idle
  fraction (wall - compute)/wall, which on the CPU tier also carries
  serialization + channel costs.
- ``activation_bytes_per_microbatch``: what one microbatch hand-off
  puts on the wire between adjacent stages.
- per-hop channel breakdown (``hop_*_ms`` rows): where one training
  step's channel time goes on the zero-copy fast path — array extract
  (encode), skeleton pickle, slot memcpy (copy), downstream ack wait,
  and reader-side decode. ``hop_pickle_ms`` prices ONLY the tree
  skeleton: a fat pickle row here means arrays fell off the zero-copy
  path.

``--activation-compression int8`` streams forward activations quantized
(block-scaled int8 codes on the wire, exact gradients); rows gain a
``_q8`` tag so they never alias the exact-path trajectory.

Usage::

    python tools/bench_pipeline.py [--stages 2,4] [--microbatches 8]
        [--interleave 2] [--activation-compression int8]
        [--steps 3] [--out PIPE_r02.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# A CPU-tier tool: this process AND every stage actor run JAX, and a chip
# belongs to one process at a time — so the whole gang is pinned to the
# (virtual-device) CPU backend before anything imports jax.
os.environ["JAX_PLATFORMS"] = "cpu"


def _bench_cfg(n_layers: int):
    from ray_tpu.models.transformer import CONFIGS

    # n_kv_heads=4 so the single-mesh baseline shards over the default
    # 8-device mesh's tensor=4 axis (tiny's GQA kv=2 does not divide it)
    return dataclasses.replace(CONFIGS["tiny"], n_layers=n_layers,
                               n_kv_heads=4, remat=False)


_HOP_KEYS = ("send_encode_s", "send_pickle_s", "send_copy_s",
             "send_ack_wait_s", "recv_copy_s", "recv_decode_s")


def _hop_rows(prefix: str, hops: list) -> list:
    """Aggregate one step's per-rank hop stats into ``hop_*_ms`` rows
    (summed across ranks: total channel time spent per step)."""
    rows = []
    for key in _HOP_KEYS:
        total = sum(h.get(key, 0.0) for h in hops)
        name = key[:-2].replace("send_", "hop_").replace("recv_", "hop_rx_")
        rows.append({"name": f"{prefix}_{name}_ms", "value": total * 1e3,
                     "unit": "ms"})
    wire = sum(h.get("send_wire_bytes", 0) for h in hops)
    rows.append({"name": f"{prefix}_hop_wire_bytes", "value": wire,
                 "unit": "bytes"})
    # bytes that still pass through pickle: the tree skeleton only.
    # wire - pickled = bytes that rode the zero-copy array path
    pickled = sum(h.get("send_skel_bytes", 0) for h in hops)
    rows.append({"name": f"{prefix}_hop_pickled_bytes", "value": pickled,
                 "unit": "bytes"})
    return rows


def main(stages=(2, 4), microbatches: int = 8, microbatch_size: int = 2,
         seq_len: int = 64, steps: int = 3, n_layers: int = 4,
         interleave: int = 1, activation_compression: str = None,
         out: str = None) -> list:
    import numpy as np

    import ray_tpu
    from ray_tpu.parallel.mesh import create_mesh, default_mesh_axes
    from ray_tpu.parallel.train import TrainStepBundle
    from ray_tpu.train.pipeline import (
        PipelineConfig,
        PipelineTrainer,
        bubble_upper_bound,
        make_microbatches,
        simulate,
    )

    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=max(8, max(stages) + 1))
    cfg = _bench_cfg(n_layers)
    batch_tokens = microbatches * microbatch_size * seq_len
    rows = []

    # -- single-mesh baseline (fused step, same total batch) --------------
    mesh = create_mesh(default_mesh_axes(8))
    bundle = TrainStepBundle(cfg, mesh, donate=False)
    pipe0 = PipelineConfig(num_stages=1, num_microbatches=microbatches,
                           microbatch_size=microbatch_size, seq_len=seq_len)
    import jax

    params, opt_state = bundle.init(jax.random.PRNGKey(0))
    mbs = make_microbatches(cfg, pipe0, 0, 0)
    batch = {k: np.concatenate([m[k] for m in mbs]) for k in mbs[0]}
    params, opt_state, _ = bundle.step(params, opt_state, batch)  # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, _ = bundle.step(params, opt_state, batch)
    single_tps = steps * batch_tokens / (time.perf_counter() - t0)
    rows.append({"name": "single_mesh_tokens_per_s", "value": single_tps,
                 "unit": "tokens/s"})

    # -- pipeline at each (stage count, interleave) -----------------------
    variants = [(S, 1) for S in stages]
    if interleave > 1:
        # each of the S*V virtual stages needs at least one layer
        variants += [(S, interleave) for S in stages
                     if S * interleave <= n_layers]
    for S, V in variants:
        tag = f"pipeline_s{S}" + (f"v{V}" if V > 1 else "") \
            + ("_q8" if activation_compression else "")
        pipe = PipelineConfig(num_stages=S, num_microbatches=microbatches,
                              microbatch_size=microbatch_size,
                              seq_len=seq_len, virtual_stages=V,
                              activation_compression=activation_compression)
        trainer = PipelineTrainer(cfg, pipe,
                                  run_name=f"bench_pipe_s{S}v{V}")
        try:
            trainer.train(1)  # compile + warm the channels
            t0 = time.perf_counter()
            stats = trainer.train(1 + steps)
            elapsed = time.perf_counter() - t0
            tps = steps * batch_tokens / elapsed
            bound = bubble_upper_bound(S, microbatches, V)
            sim = simulate(S, microbatches, num_chunks=V,
                           channel_depth=pipe.channel_depth)
            measured_idle = float(np.mean(
                [1.0 - c / w for c, w in
                 zip(stats[-1]["compute_s"],
                     [stats[-1]["wall_s"]] * S)]))
            rows += [
                {"name": f"{tag}_tokens_per_s", "value": tps,
                 "unit": "tokens/s"},
                {"name": f"{tag}_vs_single_mesh", "value":
                 tps / single_tps, "unit": "x"},
                # the simulator's bubble can never undercut the analytic
                # bound; benchtrack enforces the floor on this row
                {"name": f"{tag}_bubble_fraction",
                 "value": sim["bubble_fraction"], "unit": "fraction",
                 "meta": {"floor": bound}},
                {"name": f"{tag}_bubble_bound", "value": bound,
                 "unit": "fraction"},
                {"name": f"{tag}_idle_fraction_measured",
                 "value": measured_idle, "unit": "fraction"},
                {"name": f"{tag}_activation_bytes_per_microbatch",
                 "value": stats[-1]["activation_bytes_per_mb"],
                 "unit": "bytes"},
            ]
            rows += _hop_rows(tag, stats[-1].get("hop", []))
        finally:
            trainer.shutdown()

    rows.append({"name": "config", "value": 0, "unit": "meta",
                 "meta": {"n_layers": n_layers, "d_model": cfg.d_model,
                          "microbatches": microbatches,
                          "microbatch_size": microbatch_size,
                          "seq_len": seq_len, "steps": steps,
                          "interleave": interleave,
                          "activation_compression":
                          activation_compression,
                          # the host envelope: benchtrack only prices
                          # round-over-round moves between rounds from
                          # comparable machines
                          "host_cpus": os.cpu_count()}})
    if out:
        with open(out, "w") as f:
            json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", default="2,4")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--microbatch-size", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--n-layers", type=int, default=4)
    ap.add_argument("--interleave", type=int, default=1,
                    help="also bench V model chunks per rank (V>1)")
    ap.add_argument("--activation-compression", default=None,
                    help="stream fwd activations quantized (e.g. int8)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    rows = main(stages=tuple(int(s) for s in args.stages.split(",")),
                microbatches=args.microbatches,
                microbatch_size=args.microbatch_size,
                seq_len=args.seq_len, steps=args.steps,
                n_layers=args.n_layers, interleave=args.interleave,
                activation_compression=args.activation_compression,
                out=args.out)
    print(json.dumps(rows, indent=1))
