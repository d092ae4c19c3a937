"""A chip test: one rank of Trinity-Large-Preview (afmoe) at the published
widths against the plain reference, past the window, a ring's wrap and the
first pages.

The benchmark cell's own ``correct`` (``benchmarks/jobs/serve.py:
reference_check``) runs 200 + 4 positions through an every-slot ``[32, 256]``
batch: with a window of 4,096 and pages of 512 it never masks by the window,
never wraps a ring, never reads a second page and never runs the ``[1, S]``
call with a slot that the engine times. This does: 4,096 + 512 + 8 positions
of ``benchmarks/configs/trinity-large-preview.json`` through the engine's
``[1, 8192]`` prefill with a slot that is not the first and pages that are not
the first (the rings wrap in prefill: 512 positions overwrite the oldest), then
300 + 8 through ``[1, 512]`` into another slot, each call the program the
engine calls since PR 42: the plain one at 8,192 positions, whose rows dwarf
the 32 slots' step, and at 512 the one that CARRIES the decode step of
whoever decodes by then (``prefill``'s ``riders``: the first request's third
step; ``tests/prefill_rows.py:teacher_forced_riding``), 32-slot decode steps
before and after through rings and ten live pages, logits against
``benchmarks/architectures/afmoe.py:forward`` in float32; and the same against
references that lack the attention gate, the rotary embedding of the sliding
layers, or the window, each of which has to FAIL the cell's tolerance (a
reference that rotates the ONE full layer too read 3.04e-2, on the tolerance
itself: PERF.md section 6).

It needs the chip (8.6 GB of weights; the reference at 4,616 positions), so
under ``tests/conftest.py`` (which holds JAX to the CPU) the test only starts
this file as a process of its own where the machine has a chip, and is skipped
elsewhere:

    chiprun -- python3 tests/test_chip_afmoe.py
    chiprun -- python3 -m pytest tests/test_chip_afmoe.py -q -s
"""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 3e-2                     # the cell's: benchmarks/jobs/serve.py
PROMPT, STEPS, SEED, SLOT, FIRST_PAGE = 4096 + 512, 8, 3141592653, 5, 7
SECOND = (300, 9, 40)          # prompt, slot, first page


def main() -> dict:
    sys.path.insert(0, REPO)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.registry import architecture
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.config import EngineConfig
    from ray_tpu.llm.engine import JaxLLMEngine

    t_start = time.time()

    def note(*a):
        print(f"[+{time.time() - t_start:6.1f}s]", *a, file=sys.stderr,
              flush=True)

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "trinity-large-preview.json")) as f:
        conf = json.load(f)
    arch = architecture(conf)
    e = EngineConfig(**conf["job"]["engine"])
    eng = JaxLLMEngine(LLMConfig(
        model_id="tiny", seed=SEED % 2 ** 32, engine_config=e,
        model_overrides=arch.program_overrides(conf, e.max_model_len)),
        seed=SEED % 2 ** 32)
    mcfg = eng.mcfg
    note("engine up on", jax.devices()[0].device_kind)
    from prefill_rows import teacher_forced_riding

    total = PROMPT + STEPS
    rng = np.random.default_rng(SEED)
    toks = rng.integers(0, mcfg.vocab_size, total, dtype=np.int32)
    S = eng._prefill_bucket(PROMPT)
    need = -(-total // e.page_size)
    toks2 = rng.integers(0, mcfg.vocab_size, SECOND[0] + STEPS, dtype=np.int32)
    # the engine's own calls: [1, S] told its slot; the second, at 512,
    # carries the first request's third step
    both = teacher_forced_riding(eng, {
        SLOT: (toks, PROMPT, FIRST_PAGE),
        SECOND[1]: (toks2, SECOND[0], SECOND[2])}, gap=2)
    got, got2 = both[SLOT], both[SECOND[1]]
    note(f"prefill [1, {S}], [1, 512] carrying a step, and the decode steps "
         f"of both done")

    def reference(**change):
        rcfg = dict(arch.reference_cfg(conf), **change)

        @jax.jit
        def run(p, t):
            rp = arch.to_reference_params(p, conf)
            with jax.default_matmul_precision("highest"):
                return arch.forward(rp, t[None], rcfg, last=STEPS + 1)[0]
        return run

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    tj, p = jnp.asarray(toks), eng.params["params"]
    out = {"device": jax.devices()[0].device_kind, "bucket": S,
           "positions": total, "pages": need, "tol": TOL,
           "finite": bool(np.isfinite(got).all()),
           "rel_err": rel(got, np.asarray(reference()(p, tj))),
           "rel_err_second": rel(got2, np.asarray(
               reference()(p, jnp.asarray(toks2))))}
    note("reference", out["rel_err"], "second request", out["rel_err_second"])
    spoiled = {"no_gate": {"attention_gate": False},
               "no_rope_in_sliding": {"rotated": ()},
               "no_window": {"sliding_window": 0}}
    for name, change in spoiled.items():
        out[name] = rel(got, np.asarray(reference(**change)(p, tj)))
        note(name, out[name])
    out["ok"] = bool(out["finite"] and np.isfinite(got2).all()
                     and out["rel_err"] < TOL and out["rel_err_second"] < TOL
                     and all(out[k] > TOL for k in spoiled))
    print(json.dumps(out), flush=True)
    return out


def test_engine_programs_match_the_reference_past_window_and_page():
    from ray_tpu.util.accelerators import _count_device_nodes

    if not _count_device_nodes():
        pytest.skip("needs a TPU chip: chiprun -- python3 "
                    "tests/test_chip_afmoe.py")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=1500)
    print(proc.stderr[-4000:], proc.stdout[-4000:])
    assert proc.returncode == 0, proc.stdout[-2000:]


if __name__ == "__main__":
    sys.exit(0 if main()["ok"] else 1)
