"""Which programs does a serve configuration run, and did a change move one?

    python tools/program_digest.py <benchmarks/configs/x.json | preset> ...

lowers the programs ``llm/engine.py`` runs for each configuration
(``decode_step``, and ``prefill`` at every length bucket: the ``[1, S]`` call
as the engine makes it, told its slot and carrying a decode step where
``engine._carries`` says so, the plain ``[1, S]`` beside every carrying one,
the calls of two and four rows at the smallest bucket, and the every-slot call
of the benchmark's check) and prints one line a program: a digest of its
StableHLO with the source locations stripped, those inside a Mosaic kernel's
serialized body too (the body is parsed and printed again without them), and
the results' names (``jax.result_info``: a leaf's path in its container), so
that moving code between files and lines changes no digest and changing what
is computed does. A configuration file is lowered for a described TPU v5e at
its published widths (nothing compiles, nothing runs; about a minute a
configuration), a preset of ``models/transformer.py:CONFIGS`` for the CPU.

Run it from the root of the tree it is to read (``ray_tpu`` and
``benchmarks`` are imported from the working directory), once on the parent
(``git archive <commit> | tar -x -C <dir>``) and once on the change, and
``diff`` the outputs; ``--text <dir>`` keeps each program's stripped text, for
``diff`` to say WHAT moved (two programs that differ only in the order of
their arguments differ in the numbering of their values throughout).
"""

from __future__ import annotations

import argparse
import base64
import dataclasses
import hashlib
import json
import os
import re
import sys

_CONFIG = re.compile(r'backend_config = "((?:[^"\\]|\\.)*)"')
_RESULT = re.compile(r' \{jax\.result_info = "[^"]*"\}')


def stripped(text: str) -> str:
    """A lowered program's text with every Mosaic kernel's serialized body
    (bytecode with its locations: file, line and the callers') replaced by
    the body's own text without them."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def body(match):
        raw = re.sub(r"\\([0-9A-Fa-f]{2})",
                     lambda m: chr(int(m.group(1), 16)), match.group(1))
        try:
            config = json.loads(raw)
            code = base64.b64decode(config["custom_call_config"]["body"])
        except (ValueError, KeyError, TypeError):
            return match.group(0)  # not a Mosaic kernel's
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            kernel = ir.Module.parse(code).operation.get_asm(
                enable_debug_info=False)
        config["custom_call_config"]["body"] = hashlib.sha256(
            kernel.encode()).hexdigest()
        return "backend_config = " + json.dumps(config, sort_keys=True)

    # a result's name is its path in the container that handed it back
    return _RESULT.sub("", _CONFIG.sub(body, text))


def digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _buckets(e) -> list:
    out, b = [], e.prefill_bucket_min
    while b < e.max_model_len:
        out.append(b)
        b *= 2
    return out + [min(b, e.max_model_len)]


def programs(cfg, e, params, cache, on):
    """(name, lowered) of every program an engine of ``cfg`` and ``e`` runs,
    its arguments' shapes placed by ``on``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm.engine import _RIDE_ROWS

    B, MP = e.max_num_seqs, e.pages_per_seq

    def i32(*shape):
        return on(jax.ShapeDtypeStruct(shape, jnp.int32))

    step = (i32(B), i32(B), i32(B, MP), on(jax.ShapeDtypeStruct((B,), bool)))
    yield "decode_step", mr.decode_step.lower(params, cfg, cache, *step)

    def prefill(R, S, told=True, carry=False):
        rows = (i32(R, S), i32(R), i32(R, MP))
        if told and cfg.layer_kinds:
            rows += (i32(R),)
        if carry:
            rows += (step,)
        return mr.prefill.lower(params, cfg, cache, *rows)

    def carries(R, S):
        return mr.rides(cfg) and R * S <= _RIDE_ROWS * B

    buckets = _buckets(e)
    for S in buckets:
        if carries(1, S):
            yield f"prefill_1x{S}_carrying", prefill(1, S, carry=True)
        yield f"prefill_1x{S}", prefill(1, S)
    for R in (2, 4):
        S = buckets[0]
        name = f"prefill_{R}x{S}" + "_carrying" * carries(R, S)
        yield name, prefill(R, S, carry=carries(R, S))
    # benchmarks/jobs/serve.py:reference_check: every slot a row, no slots
    yield f"prefill_{B}x{buckets[0]}_check", prefill(B, buckets[0], told=False)


def lowered(name: str):
    """(label, programs) of a configuration file or a preset."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm.config import EngineConfig, LLMConfig
    from ray_tpu.models.transformer import Transformer

    if name.endswith(".json"):
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        from benchmarks.jobs import common

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        with open(name) as f:
            conf = json.load(f)
        e = EngineConfig(**conf["job"]["engine"])
        # "auto" asks the attached backend, the CPU here: say what a TPU takes
        cfg = dataclasses.replace(
            common.transformer_config(conf, e.max_model_len),
            attention_impl="flash")
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])

        def on(s):
            return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
        label = os.path.basename(name)[:-len(".json")]
    else:
        e = EngineConfig(max_num_seqs=4, max_model_len=64, page_size=8,
                         prefill_bucket_min=16)
        cfg = LLMConfig(model_id=name, engine_config=e).transformer_config()

        def on(s):
            return s
        label = name
    params = jax.tree.map(on, jax.eval_shape(lambda: nn.meta.unbox(
        Transformer(cfg).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32)))))
    cache = jax.tree.map(on, jax.eval_shape(lambda: mr.init_cache(
        cfg, e.num_pages, e.page_size, e.max_num_seqs)))
    return label, programs(cfg, e, params, cache, on)


def digests(name: str, text_dir: str = "") -> list:
    """One line a program of ``name``: ``<label>.<program> <digest>``."""
    label, progs = lowered(name)
    lines = []
    for program, low in progs:
        text = stripped(low.as_text())
        if text_dir:
            os.makedirs(text_dir, exist_ok=True)
            with open(os.path.join(
                    text_dir, f"{label}.{program}.mlir"), "w") as f:
                f.write(text)
        lines.append(f"{label}.{program} {digest(text)}")
    return lines


def main(argv=None) -> int:
    import jax

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("configs", nargs="+")
    parser.add_argument("--text", default="", metavar="DIR")
    args = parser.parse_args(argv)
    sys.path.insert(0, os.getcwd())
    # an executable for a described chip cannot be read back without it
    jax.config.update("jax_enable_compilation_cache", False)
    for name in args.configs:
        for line in digests(name, args.text):
            print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
