"""A chip test: Brumby-14B-Base (brumby) at the published widths and the
cell's sizes against the plain reference, through the programs the engine
times.

The benchmark cell's own ``correct`` (``benchmarks/jobs/serve.py:
reference_check``) runs 200 + 4 positions through an every-slot ``[32, 256]``
batch: less than one chunk of the recurrence (so never the state's carry
from chunk to chunk, and never a chunk that is passed over), never the ``[1,
S]`` call with a slot that the engine times, no decode step beside a prompt.
This does, on ``benchmarks/configs/brumby-14b-base.json``:

- 3,000 + 512 positions and 8 more through the engine's ``[1, 4096]`` call
  into a slot that is not the first: seven chunks of 512 whose state is
  carried six times, the eighth passed over, the state written from fast
  memory into slot 5 of the cache's leaf; then a 300-token prompt through
  ``[1, 512]`` and a 2-token one through ``[1, 256]``, calls that CARRY the
  decoding slots' step (``retention_riding`` with ``keep`` beside
  ``retention_scan``), and 32-slot decode steps between and after
  (``tests/prefill_rows.py:teacher_forced_riding``). A slot's state is
  written back every fourth decode step and whenever a call carries the
  step (``ops/retention.py:FOLD``): the eight steps behind the last
  admission cross two folds with three read passes before each, the count
  the cache keeps is read before every step (``counts_before_steps``), and
  the decode program, compiled again here for the chip it runs on, holds no
  second copy of the 6.85 GB of state (``decode_temp_bytes``,
  ``state_copies``).

Every position's logits against ``benchmarks/architectures/brumby.py:
forward`` (the ATTENTION form, blocked over query positions) in float32 at
the highest matmul precision. ``TOL`` 3e-2 is the cell's: bfloat16 weights,
activations and products against float32 through six layers. Each spoiled
reference (``SPOILED``: a part of the mathematics left out or done wrong)
has to FAIL ``TOL``; what those under ``PRINTED`` read is printed beside them
and held by ``tests/test_brumby.py`` at float32's tolerance instead (the
configuration file's ``assumed.initializer`` says why). Two faults of the
SYSTEM are run too and have to fail: the long request again with its call
told that the row fills the bucket (the state carried from the padded end),
and its decode steps from another request's slot.

The control: the reference again from weights rounded to the three mantissa
bits of float8's e4m3, the nearest precision below the configuration's
bfloat16, against the reference itself by the same comparison: it has to
FAIL ``TOL`` too, or the limit would admit a model a whole precision coarser.

``--initializer '{"mlp": 0.02}'`` runs at other seeded deviations than the
file's (how they were set: PERF.md section 6, PR 55); ``--table 0`` leaves the
spoiled references out, ``--only a,b`` all but those, ``--control 0`` the
control.

It needs the chip (7.1 GB of weights and 6.9 GB of state), so under
``tests/conftest.py`` (which holds JAX to the CPU) the test only starts this
file as a process of its own where the machine has a chip, and is skipped
elsewhere:

    chiprun -- python3 tests/test_chip_brumby.py
"""
import argparse
import json
import os
import re
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 3e-2                     # the cell's: benchmarks/jobs/serve.py
STEPS, SEED = 8, 2718281828
CONFIG = os.path.join(REPO, "benchmarks", "configs", "brumby-14b-base.json")
# (prompt, slot, first page: the pages address nothing)
LONG, MIDDLE, SHORT = (3512, 5, 7), (300, 20, 100), (2, 31, 30)
SPOILED = ("degree", "sqrt2", "gate", "gate_twice", "normaliser",
           "normaliser_decay", "grouped", "rotation", "norm_order",
           "head_norms")
# a gate of 0.993 over the new term too reweighs a key by 0.7%: under the
# engine's own error at any deviation that keeps a state for hundreds of
# positions; held at float32's 1e-4 by tests/test_brumby.py
PRINTED = ("gate_on_new",)


def main(argv=()) -> dict:
    global SEED
    ap = argparse.ArgumentParser()
    ap.add_argument("--initializer", default="{}")
    ap.add_argument("--table", type=int, default=1)
    ap.add_argument("--only", default="", help="of the table, these alone")
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--seed", type=int, default=SEED)
    a = ap.parse_args(argv)
    SEED = a.seed
    sys.path[:0] = [REPO, os.path.join(REPO, "tests")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.registry import architecture
    from prefill_rows import teacher_forced_riding
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.config import EngineConfig
    from ray_tpu.llm.engine import JaxLLMEngine
    from ray_tpu.ops.retention import FOLD, scan_chunks

    t_start = time.time()

    def note(*a):
        print(f"[+{time.time() - t_start:6.1f}s]", *a, file=sys.stderr,
              flush=True)

    with open(CONFIG) as f:
        conf = json.load(f)
    conf["initializer"].update(json.loads(a.initializer))
    arch = architecture(conf)
    e = EngineConfig(**conf["job"]["engine"])
    eng = JaxLLMEngine(LLMConfig(
        model_id="tiny", seed=SEED % 2 ** 32, engine_config=e,
        model_overrides=arch.program_overrides(conf, e.max_model_len)),
        seed=SEED % 2 ** 32)
    mcfg, mr = eng.mcfg, eng._mr
    note("engine up on", jax.devices()[0].device_kind, conf["initializer"])
    requests = (LONG, MIDDLE, SHORT)
    carrying = [eng._carries(1, eng._prefill_bucket(r[0])) for r in requests]

    rng = np.random.default_rng(SEED)

    def draw(prompt):
        return rng.integers(0, mcfg.vocab_size, prompt + STEPS, dtype=np.int32)

    def reference(*without):
        rcfg = dict(arch.reference_cfg(conf), without=without)

        @jax.jit
        def run(p, t):
            with jax.default_matmul_precision("highest"):
                return arch.forward(arch.to_reference_params(p, conf),
                                    t[None], rcfg, last=STEPS + 1)[0]
        return lambda toks: np.asarray(run(eng.params["params"],
                                           jnp.asarray(toks)))

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    out = {"device": jax.devices()[0].device_kind, "seed": SEED, "tol": TOL,
           "initializer": conf["initializer"], "calls_carrying": carrying,
           "state_shape": list(eng.cache["retention"].state.shape),
           "state_dtype": str(eng.cache["retention"].state.dtype),
           "paged_leaves": eng._page_leaves(),
           "long_call_chunks": scan_chunks(eng._prefill_bucket(LONG[0]),
                                           [LONG[0]])}
    want = reference()
    seqs = {slot: (draw(prompt), prompt, page)
            for prompt, slot, page in requests}

    class Counting:
        """``eng._mr`` with the cache's count of pending positions read
        before every decode step."""
        seen = []

        def __getattr__(self, name):
            return getattr(mr, name)

        def decode_step(self, params, cfg, cache, *rows):
            self.seen.append(int(cache["retention"].count))
            return mr.decode_step(params, cfg, cache, *rows)

    eng._mr = Counting()
    got = teacher_forced_riding(eng, seqs, gap=1)
    eng._mr = mr
    out["counts_before_steps"] = Counting.seen
    note("three requests through [1, 4096], [1, 512] carrying, [1, 256] "
         "carrying, decode steps between and after")
    long_toks = seqs[LONG[1]][0]

    # the system's own faults: the state from the padded end; another slot's
    B, MP = e.max_num_seqs, e.pages_per_seq
    tables = jnp.zeros((B, MP), jnp.int32)

    def decode_from(slot):
        steps = []
        for i in range(STEPS):
            last = np.zeros(B, np.int32)
            lens = np.zeros(B, np.int32)
            active = np.zeros(B, bool)
            last[slot], lens[slot], active[slot] = (
                long_toks[LONG[0] + i], LONG[0] + i, True)
            logits, eng.cache = mr.decode_step(
                eng.params, mcfg, eng.cache, jnp.asarray(last),
                jnp.asarray(lens), tables, jnp.asarray(active))
            steps.append(np.asarray(logits[slot]))
        return np.stack(steps)

    batch = np.zeros((1, eng._prefill_bucket(LONG[0])), np.int32)
    batch[0, :LONG[0]] = long_toks[:LONG[0]]
    _, eng.cache = mr.prefill(
        eng.params, mcfg, eng.cache, jnp.asarray(batch),
        jnp.asarray([batch.shape[1]], jnp.int32), tables[:1],
        jnp.asarray([9], jnp.int32))
    from_padded_end = decode_from(9)
    from_other_slot = decode_from(MIDDLE[1])
    note("the long request's steps from the padded end and from slot",
         MIDDLE[1])
    idle = (jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32), tables,
            jnp.zeros(B, bool))
    compiled = mr.decode_step.lower(eng.params, mcfg, eng.cache,
                                    *idle).compile()
    out["decode_temp_bytes"] = compiled.memory_analysis().temp_size_in_bytes
    out["state_copies"] = len(re.findall(
        r"= f32\[6,33,8,66,128,128\]\S* copy\(", compiled.as_text()))
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_gb"] = round(stats.get("peak_bytes_in_use", 0) / 1e9, 3)
    # the references want the room the state holds
    jax.tree.map(lambda x: x.delete(), eng.cache)

    finite, wants = True, {}
    for name, req in (("long", LONG), ("middle", MIDDLE), ("short", SHORT)):
        g, toks = got[req[1]], seqs[req[1]][0]
        finite = finite and bool(np.isfinite(g).all())
        w = wants[name] = want(toks)
        out[f"rel_err_{name}"] = rel(g, w)
        top = np.sort(w, axis=-1)
        out[f"logit_std_{name}"] = float(w.std())
        out[f"top_gap_{name}"] = float((top[:, -1] - top[:, -2]).mean())
        note(name, "reference", out[f"rel_err_{name}"])
    out["padded_end"] = rel(from_padded_end, wants["long"][1:])
    out["other_slot"] = rel(from_other_slot, wants["long"][1:])
    note("padded_end", out["padded_end"], "other_slot", out["other_slot"])
    if a.table:
        long_got = got[LONG[1]]
        for what in SPOILED + PRINTED:
            if a.only and what not in a.only.split(","):
                continue
            out[what] = rel(long_got, reference(what)(long_toks))
            note("long", what, out[what])
    if a.control:
        # in place, leaf by leaf: the chip has no room for a second copy.
        # (A cast to float8 and back is one the TPU compiler takes out.)
        coarse = jax.jit(lambda w: jax.lax.reduce_precision(w, 8, 3),
                         donate_argnums=0)
        eng.params = jax.tree.map(
            lambda w: coarse(w) if w.ndim >= 2 else w, eng.params)
        for name, req in (("long", LONG), ("middle", MIDDLE), ("short", SHORT)):
            out[f"control_float8_{name}"] = rel(want(seqs[req[1]][0]),
                                               wants[name])
            note(name, "float8 weights", out[f"control_float8_{name}"])
    out["finite"] = finite
    errs = [v for k, v in out.items() if k.startswith("rel_err_")]
    out["ok"] = bool(
        finite and max(errs) < TOL
        and all(not out[k] <= TOL for k in SPOILED if k in out)
        and out["padded_end"] > TOL and out["other_slot"] > TOL
        and all(v > TOL for k, v in out.items() if k.startswith("control_"))
        and carrying == [False, True, True]
        and out["state_shape"] == [6, 33, 8, 66, 128, 128]
        and out["state_dtype"] == "float32" and not out["paged_leaves"]
        and out["long_call_chunks"] == (8, 1)
        # the last request's eight steps: read, read, read, fold, twice
        and out["counts_before_steps"][-8:] == list(range(FOLD)) * 2
        and out["decode_temp_bytes"] < 128 << 20 and not out["state_copies"])
    print(json.dumps(out), flush=True)
    return out


def test_engine_programs_match_the_reference_over_chunks_and_slots():
    from ray_tpu.util.accelerators import _count_device_nodes

    if not _count_device_nodes():
        pytest.skip("needs a TPU chip: chiprun -- python3 "
                    "tests/test_chip_brumby.py")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=2400)
    print(proc.stderr[-4000:], proc.stdout[-4000:])
    assert proc.returncode == 0, proc.stdout[-2000:]


if __name__ == "__main__":
    sys.exit(0 if main(sys.argv[1:])["ok"] else 1)
