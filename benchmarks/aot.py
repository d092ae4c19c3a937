#!/usr/bin/env python3
"""Compiles a cell's programs for a DESCRIBED v5e (``v5e:2x2``), no chip
attached, and prints the compiler's memory analysis per device.

    JAX_PLATFORMS=cpu python3 benchmarks/aot.py --workload <name> [--set KEY=VALUE ...] [--per-chip-batch B]

A compile that passes is not a chip run: nothing executes, so this says what
fits and which kernels and collectives the program holds, never a time. It is
how the depth and batch of the train configurations were settled (PERF.md):
``--set`` replaces a number of the configuration file (its depth, say) for
this compile.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    d = {k: getattr(m, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}
    d["live_bytes"] = (d["argument_size_in_bytes"] + d["output_size_in_bytes"]
                       - d["alias_size_in_bytes"] + d["temp_size_in_bytes"])
    return d


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="a whole number in place of the configuration file's")
    ap.add_argument("--per-chip-batch", type=int, default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import traffic
    from benchmarks.jobs import common
    from benchmarks.registry import Cell

    jax.config.update("jax_enable_compilation_cache", False)
    cell = Cell(args.workload, os.path.join(REPO, "BENCHMARK.json"))
    conf, job = dict(cell.config), cell.config["job"]
    changed = {k: int(v) for k, v in (kv.split("=", 1) for kv in args.set)}
    unknown = sorted(set(changed) - set(conf))
    if unknown:
        raise SystemExit(f"--set: {cell.entry['config']} has no key {unknown}")
    conf.update(changed)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")

    def out(**kw):
        print(json.dumps({"workload": cell.name, "set": changed, **kw}),
              flush=True)

    if job["kind"] == "train":
        shape = traffic.train_shape(cell.mix, job)
        if args.per_chip_batch:
            shape["per_chip_batch"] = args.per_chip_batch
        # "auto" asks the attached backend (the CPU here): steer the dispatch
        # the way a TPU backend would
        mcfg = dataclasses.replace(
            common.transformer_config(conf, shape["seq_len"]),
            attention_impl="flash")
        bundle = common.build_bundle(mcfg, job, topo.devices[:job["chips"]])
        if bundle.shard_update:
            step, opt_sh = bundle._fused_step_sharded, bundle.opt_shard_shardings
        else:
            step, opt_sh = bundle._fused_step, bundle.opt_shardings

        def sds(tree, shardings):
            return jax.tree_util.tree_map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                tree, shardings)

        rows = shape["per_chip_batch"] * bundle.dp_size
        batch = {k: jax.ShapeDtypeStruct((rows, shape["seq_len"]), dt,
                                         sharding=bundle.batch_sharding)
                 for k, dt in (("tokens", jnp.int32), ("targets", jnp.int32),
                               ("mask", jnp.float32))}
        compiled = step.lower(sds(bundle._abstract_params, bundle.param_shardings),
                              sds(bundle._abstract_opt, opt_sh), batch).compile()
        text = compiled.as_text()
        out(program="train_step", **shape, **_mem(compiled),
            tpu_custom_calls=text.count("tpu_custom_call"),
            reduce_scatter=text.count("reduce-scatter"),
            all_gather=text.count("all-gather"))
        # the gradient check's two programs (jobs/train.py), which run beside
        # the parameters and the optimizer state before the first step
        from benchmarks.jobs.train import CHECK_SEQ

        p_abs = sds(bundle._abstract_params, bundle.param_shardings)
        check = {k: jax.ShapeDtypeStruct((bundle.dp_size, CHECK_SEQ), dt,
                                         sharding=bundle.batch_sharding)
                 for k, dt in (("tokens", jnp.int32), ("targets", jnp.int32),
                               ("mask", jnp.float32))}
        fb = bundle._fwd_bwd.lower(p_abs, check).compile()
        out(program="check_fwd_bwd", **_mem(fb),
            tpu_custom_calls=fb.as_text().count("tpu_custom_call"))
        toks = jax.ShapeDtypeStruct((bundle.dp_size, CHECK_SEQ + 1), jnp.int32,
                                    sharding=bundle.batch_sharding)
        ref = common.gradient_check(conf).lower(
            p_abs, p_abs, toks).compile()
        out(program="check_reference_gradient", **_mem(ref))
        return 0

    import flax.linen as nn

    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm.config import EngineConfig
    from ray_tpu.models.transformer import Transformer

    one = SingleDeviceSharding(topo.devices[0])
    e = EngineConfig(**job["engine"])
    mcfg = dataclasses.replace(common.transformer_config(conf, e.max_model_len),
                               attention_impl="flash")

    def on(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    params = on(jax.eval_shape(lambda: nn.meta.unbox(Transformer(mcfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))))
    cache = on(jax.eval_shape(lambda: mr.init_cache(mcfg, e.num_pages, e.page_size)))
    B, MP = e.max_num_seqs, e.pages_per_seq

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    active = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one)
    c = mr.decode_step.lower(params, mcfg, cache, i32(B), i32(B), i32(B, MP),
                             active).compile()
    out(program="decode_step", **_mem(c))
    for S in traffic.serve_prefill_buckets(cell.mix, e.prefill_bucket_min,
                                           e.max_model_len):
        c = mr.prefill.lower(params, mcfg, cache, i32(B, S), i32(B),
                             i32(B, MP)).compile()
        out(program=f"prefill_{S}", **_mem(c),
            tpu_custom_calls=c.as_text().count("tpu_custom_call"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
