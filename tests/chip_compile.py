"""What the files ``test_chip_compile*.py`` share: the described chip, and a
configuration file's programs lowered for it at the published widths.

Each of those files compiles in its own process (an xdist worker under
``--dist loadfile``): the process that describes the topology loads the TPU's
library and keeps it until it exits, which several may do at once under
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` (tier-1's command sets it; set here for a run
by hand). The topology is described inside a fixture, never at import. One
file a configuration, so that the workers share what one ran while all of it
was ``test_chip_compile.py``'s (973 s of a 1,007 s suite, PR 56)."""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep these compiles out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _live(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes), m.temp_size_in_bytes


def _held(cache):
    """The bytes of every leaf of every kind's state: what a program that
    writes its cache in place must alias."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(cache.states))


def _float32_rows_a_choice(text, top_k, hidden):
    """The float32 ``[T, top_k, hidden]`` values of a compiled program: what
    the expert layer's way back laid out before ``ops/moe.py`` summed a prefill
    call's rows choice by choice (PR 46); a prefill program holds none."""
    return re.findall(rf"= f32\[\d+,{top_k},{hidden}\]", text)


def _lower_hybrid(one_chip, n_layers=8):
    """The engine's programs at the published widths of
    ``benchmarks/configs/phi-4-mini-flash-reasoning.json`` and its job block's
    geometry (48 slots x 10240, pages of 512), depth cut to ``n_layers`` with
    the pattern kept (8: Mamba, window, Mamba, window, the Mamba layer that
    hands its memory on, full, a gated memory unit, cross)."""
    import json

    import flax.linen as nn

    from benchmarks.jobs import common
    from benchmarks.registry import REPO, architecture
    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm.config import EngineConfig
    from ray_tpu.models.transformer import Transformer

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        conf = json.load(f)
    e = EngineConfig(**conf["job"]["engine"])
    cfg = dataclasses.replace(
        common.transformer_config(conf, e.max_model_len), n_layers=n_layers,
        layer_kinds=architecture(conf).layer_kinds(n_layers),
        attention_impl="flash")
    params = _on(jax.eval_shape(lambda: nn.meta.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))), one_chip)
    cache = _on(jax.eval_shape(lambda: mr.init_cache(
        cfg, e.num_pages, e.page_size, e.max_num_seqs)), one_chip)
    B, MP = e.max_num_seqs, e.pages_per_seq

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    active = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)

    def prefill(bucket):
        return mr.prefill.lower(params, cfg, cache, i32(1, bucket), i32(1),
                                i32(1, MP), i32(1))

    return cache, prefill, lambda: mr.decode_step.lower(
        params, cfg, cache, i32(B), i32(B), i32(B, MP), active)


def _lower_rms_kinds(one_chip, name="trinity-large-preview"):
    """The engine's programs at the published widths and the whole cut of a
    configuration file with an ``"rms"`` block by kind: ``benchmarks/configs/
    trinity-large-preview.json`` (5 layers, 32 of 256 experts held, 32 slots x
    16896, pages of 512) unless another is named."""
    e, cfg, params, cache = _rms_kinds(one_chip, name, "flash")
    B, MP = e.max_num_seqs, e.pages_per_seq

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    active = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)

    step = (i32(B), i32(B), i32(B, MP), active)

    def prefill(rows, bucket):
        # the engine's call: told its slot and, where the call's rows do not
        # dwarf the step's, carrying a decode step's rows (PR 42:
        # ``engine._carries``); the benchmark's check gives neither
        from ray_tpu.llm.engine import _RIDE_ROWS

        told = (i32(1),) if rows == 1 else ()
        if told and bucket <= _RIDE_ROWS * B:
            told += (step,)
        return mr.prefill.lower(params, cfg, cache, i32(rows, bucket),
                                i32(rows), i32(rows, MP), *told)

    from ray_tpu.llm import model_runner as mr
    return cache, prefill, lambda: mr.decode_step.lower(
        params, cfg, cache, *step)


def _rms_kinds(one_chip, name, attention_impl):
    """``benchmarks/configs/<name>.json`` as the engine holds it: its
    geometry, the model, and the shapes of its parameters and cache on
    ``one_chip``. ``attention_impl``: ``"auto"`` is what the cell runs (the
    flash kernel where the program is traced for the chip), ``"flash"`` what a
    lowering in this process, which has no chip, must be told."""
    import json

    import flax.linen as nn

    from benchmarks.jobs import common
    from benchmarks.registry import REPO
    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm.config import EngineConfig
    from ray_tpu.models.transformer import Transformer

    with open(os.path.join(REPO, "benchmarks", "configs",
                           name + ".json")) as f:
        conf = json.load(f)
    e = EngineConfig(**conf["job"]["engine"])
    cfg = dataclasses.replace(
        common.transformer_config(conf, e.max_model_len),
        attention_impl=attention_impl)
    params = _on(jax.eval_shape(lambda: nn.meta.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))), one_chip)
    cache = _on(jax.eval_shape(lambda: mr.init_cache(
        cfg, e.num_pages, e.page_size, e.max_num_seqs)), one_chip)
    return e, cfg, params, cache


