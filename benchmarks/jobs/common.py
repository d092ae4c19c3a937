"""Helpers that run INSIDE the process holding the chip (train worker, serve
replica). Importing this module imports JAX; the parent never imports it."""

from __future__ import annotations

import contextlib
import glob
import os
import threading
from typing import Optional

import jax
import numpy as np

from benchmarks.registry import architecture
from benchmarks.trace import reduce

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA compile requests of this process (a persistent-cache read
    counts too: either means a program was not ready when it was needed)."""

    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == COMPILE_EVENT:
            with self._lock:
                self.count += 1


def transformer_config(cfg: dict, max_seq_len: int):
    """The program's ``TransformerConfig`` at the published widths of a
    configuration file, as its architecture maps them: public widths go in as
    data, no preset is used."""
    import dataclasses

    from ray_tpu.models.transformer import CONFIGS

    return dataclasses.replace(
        CONFIGS["tiny"],
        **architecture(cfg).program_overrides(cfg, max_seq_len))


def build_bundle(mcfg, job: dict, devices):
    """The program's ``TrainStepBundle`` on the mesh a configuration's job
    block names: the fused step, or the sharded-update step when the job
    shards the optimizer state across ``data`` (as ``chip_smoke.py`` builds
    them)."""
    from ray_tpu.parallel import TrainStepBundle, create_mesh, make_optimizer

    axes = {"data": 1, "fsdp": 1, "seq": 1, "tensor": 1, "expert": 1}
    axes.update(job["mesh"])
    mesh = create_mesh(axes, devices=devices)
    opt_kw = job["optimizer"]
    if job["shard_update"]:
        return TrainStepBundle(
            mcfg, mesh, shard_update=True,
            optimizer_factory=lambda spec_fn: make_optimizer(
                clip_spec_fn=spec_fn, **opt_kw))
    return TrainStepBundle(mcfg, mesh, optimizer=make_optimizer(**opt_kw))


def reference_loss(conf: dict):
    """``(params, tokens [rows, n+1]) -> loss`` of the architecture's plain
    float32 reference on the program's own parameter tree."""
    arch = architecture(conf)
    rcfg = arch.reference_cfg(conf)

    def ref_loss(params, toks):
        with jax.default_matmul_precision("highest"):
            return arch.loss(arch.to_reference_params(params, conf),
                             toks[:, :-1], toks[:, 1:], rcfg)

    return ref_loss


def gradient_check(conf: dict):
    """A jitted ``(params, program_grads, tokens) -> (reference loss, per-leaf
    [|g - g_ref|^2, |g_ref|^2])``: the plain float32 reference's gradient on
    the same parameters and tokens, differentiated through the renaming so it
    comes out in the program's own tree, and reduced to two sums a leaf inside
    the one program so that no second full gradient outlives it."""
    import jax.numpy as jnp

    ref_loss = reference_loss(conf)

    def distance(params, grads, toks):
        loss, ref = jax.value_and_grad(ref_loss)(params, toks)

        def sums(g, r):
            g, r = g.astype(jnp.float32), r.astype(jnp.float32)
            return jnp.stack([jnp.sum(jnp.square(g - r)), jnp.sum(jnp.square(r))])

        return loss, jax.tree_util.tree_map(sums, grads, ref)

    return jax.jit(distance)


def gradient_distances(sums) -> dict:
    """Per-leaf sums of ``gradient_check`` -> the relative distances judged."""
    leaves = [(jax.tree_util.keystr(k), float(v[0]), float(v[1]))
              for k, v in jax.tree_util.tree_leaves_with_path(sums)]
    per = {k: (d / n) ** 0.5 if n > 0 else float("inf") for k, d, n in leaves}
    worst = max(per, key=per.get)
    return {"grad_rel_err": (sum(d for _, d, _ in leaves)
                             / sum(n for _, _, n in leaves)) ** 0.5,
            "grad_leaf_rel_err_max": per[worst], "grad_worst_leaf": worst,
            "grad_leaves": len(per)}


def executable_live_bytes(jitted, *args) -> int:
    """What the compiler says one execution of ``jitted`` on ``args`` holds:
    arguments + outputs - aliased + temporaries. The allocator's
    ``peak_bytes_in_use`` leaves an executable's temporaries out on this
    backend (PERF.md), so the step's own figure is read beside it. Lowering
    again reads the executable back from the compile cache."""
    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        args)
    m = jitted.lower(*abstract).compile().memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def device_report(executable_bytes: int = 0) -> dict:
    """The device as JAX reports it here, and the peak on the fullest chip:
    the allocator's peak, or what the largest executable holds on a chip while
    it runs (``executable_live_bytes``) when that is more."""
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    alloc = max([p for p in peaks if p is not None], default=None)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(alloc or 0, executable_bytes) or None,
            "allocator_peak_bytes": alloc}


class Tracer:
    """A profiler trace of a few seconds, taken by the process that holds the
    chip, and its reduction. ``annotate(name)`` puts a host span on the
    profiler's clock (a no-op context when tracing is off)."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled = enabled
        self.dir = out_dir
        self.active = False
        self.summary = None
        self.error = None

    def annotate(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(reduce.ANNOTATION_PREFIX + name)

    def start(self):
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        # the benchmark's spans are TraceAnnotations (the host tracer's);
        # tracing every Python call as well is read by nothing and cost the
        # traced serve run a fifth of its window
        opts.python_tracer_level = 0
        opts.raise_error_on_start_failure = True    # the default is silence
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True

    def stop(self):
        jax.profiler.stop_trace()
        self.active = False

    def reduce(self, keep_as: Optional[str] = None):
        """Read the newest ``.xplane.pb`` under the trace directory."""
        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if not files:
            self.error = "the profiler wrote no .xplane.pb"
            return None
        self.summary = reduce.summarize(files[-1])
        if self.summary is None:
            self.error = ("no device plane with events in the trace; it holds "
                          + str(reduce.inventory(files[-1]))[:1500])
        if keep_as:
            os.makedirs(os.path.dirname(keep_as), exist_ok=True)
            os.replace(files[-1], keep_as)
        return self.summary


def token_batch(rng: np.random.Generator, rows: int, seq_len: int, vocab: int):
    """[rows, seq_len+1] ids uniform over the vocabulary, made on the host."""
    return rng.integers(0, vocab, (rows, seq_len + 1), dtype=np.int32)
