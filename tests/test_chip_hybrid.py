"""A chip test: the hybrid model's two timed programs at the published widths
against the plain reference, past the window, the ring's wrap and the first
page.

The benchmark cell's own ``correct`` (``benchmarks/jobs/serve.py:
reference_check``) runs 200 + 4 positions through an every-slot ``[48, 256]``
batch: with a window of 512 and pages of 512 it never masks by the window,
never wraps a ring, never reads a second page and never runs the ``[1, S]``
call with a slot that the engine times. This does: 4,000 + 8 positions of
``benchmarks/configs/phi-4-mini-flash-reasoning.json`` through the engine's
``[1, 4096]`` prefill with a slot that is not the first and pages that are not
the first, then eight 48-slot decode steps, logits against
``benchmarks/architectures/phi4flash.py:forward`` in float32; and the same
against references that lack the learned lambda, the recurrent state or the
window, each of which has to FAIL the cell's tolerance.

It needs the chip (7.7 GB of weights; the reference at 4,008 positions), so
under ``tests/conftest.py`` (which holds JAX to the CPU) the test only starts
this file as a process of its own where the machine has a chip, and is skipped
elsewhere:

    chiprun -- python3 tests/test_chip_hybrid.py
    chiprun -- python3 -m pytest tests/test_chip_hybrid.py -q -s
"""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 3e-2                     # the cell's: benchmarks/jobs/serve.py
PROMPT, STEPS, SEED, SLOT, FIRST_PAGE = 4000, 8, 2718281828, 5, 7


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
                           "phi-4-mini-flash-reasoning.json")) as f:
        conf = json.load(f)
    arch = architecture(conf)
    e = EngineConfig(**conf["job"]["engine"])
    eng = JaxLLMEngine(LLMConfig(
        model_id="tiny", seed=SEED % 2 ** 32, engine_config=e,
        model_overrides=arch.program_overrides(conf, e.max_model_len)))
    mcfg, mr = eng.mcfg, eng._mr
    note("engine up on", jax.devices()[0].device_kind)
    B, MP, total = e.max_num_seqs, e.pages_per_seq, PROMPT + STEPS
    toks = np.random.default_rng(SEED).integers(
        0, mcfg.vocab_size, total, dtype=np.int32)
    S = eng._prefill_bucket(PROMPT)
    need = -(-total // e.page_size)
    tables = np.zeros((B, MP), np.int32)
    tables[SLOT, :need] = np.arange(FIRST_PAGE, FIRST_PAGE + need)
    batch = np.zeros((1, S), np.int32)
    batch[0, :PROMPT] = toks[:PROMPT]
    # the engine's own call: one admitted request, [1, S], told its slot
    logits, eng.cache = mr.prefill(
        eng.params, mcfg, eng.cache, jnp.asarray(batch),
        jnp.asarray([PROMPT], jnp.int32), jnp.asarray(tables[SLOT:SLOT + 1]),
        jnp.asarray([SLOT], jnp.int32))
    got = [np.asarray(logits[0])]
    active = np.zeros(B, bool)
    active[SLOT] = True
    last, seq_lens = np.zeros(B, np.int32), np.zeros(B, np.int32)
    for i in range(STEPS):
        last[SLOT], seq_lens[SLOT] = toks[PROMPT + i], PROMPT + i
        logits, eng.cache = mr.decode_step(
            eng.params, mcfg, eng.cache, jnp.asarray(last),
            jnp.asarray(seq_lens), jnp.asarray(tables), jnp.asarray(active))
        got.append(np.asarray(logits[SLOT]))
    got = np.stack(got)
    note(f"prefill [1, {S}] and {STEPS} decode steps done")

    def reference(window=None):
        rcfg = dict(arch.reference_cfg(conf))
        if window is not None:
            rcfg["sliding_window"] = window

        @jax.jit
        def run(p, t):
            rp = arch.to_reference_params(p, conf)
            with jax.default_matmul_precision("highest"):
                return arch.forward(rp, t[None], rcfg, last=STEPS + 1)[0]
        return run

    def without(edit):
        p = dict(eng.params["params"])
        for i in range(mcfg.n_layers):
            lp = dict(p[f"layer_{i}"])
            lp["mixer"] = edit(dict(lp["mixer"]))
            p[f"layer_{i}"] = lp
        return p

    def no_lambda(m):
        for k in ("lambda_q1", "lambda_q2"):
            if k in m:
                m[k] = m[k] * 0
        return m

    def no_state(m):            # B = 0: the recurrence carries nothing
        if "x_proj" in m:
            R, N = mcfg.ssm_dt_rank, mcfg.ssm_state
            m["x_proj"] = {"kernel": m["x_proj"]["kernel"].at[:, R:R + N].set(0)}
        return m

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    tj, plain = jnp.asarray(toks), reference()
    out = {"device": jax.devices()[0].device_kind, "bucket": S,
           "positions": total, "pages": need, "tol": TOL,
           "finite": bool(np.isfinite(got).all()),
           "rel_err": rel(got, np.asarray(plain(eng.params["params"], tj)))}
    note("reference", out["rel_err"])
    for name, edit in (("no_lambda", no_lambda), ("no_state", no_state)):
        out[name] = rel(got, np.asarray(plain(without(edit), tj)))
        note(name, out[name])
    del plain
    out["no_window"] = rel(got, np.asarray(
        reference(window=0)(eng.params["params"], tj)))
    out["ok"] = bool(out["finite"] and out["rel_err"] < TOL and all(
        out[k] > TOL for k in ("no_lambda", "no_state", "no_window")))
    print(json.dumps(out), flush=True)
    return out


def test_engine_programs_match_the_reference_past_window_and_page():
    from ray_tpu.util.accelerators import _count_device_nodes

    if not _count_device_nodes():
        pytest.skip("needs a TPU chip: chiprun -- python3 "
                    "tests/test_chip_hybrid.py")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=1500)
    print(proc.stderr[-4000:], proc.stdout[-4000:])
    assert proc.returncode == 0, proc.stdout[-2000:]


if __name__ == "__main__":
    sys.exit(0 if main()["ok"] else 1)
