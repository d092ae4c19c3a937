"""A chip test: LFM2-8B-A1B (lfm2_moe) at the published widths against the
plain reference, through the programs the engine times.

The benchmark cell's own ``correct`` (``benchmarks/jobs/serve.py:
reference_check``) runs 200 + 4 positions through an every-slot ``[128, 256]``
batch: with pages of 256 it never reads a second page, never runs the ``[1,
S]`` call with a slot that the engine times, never gives a slot a prompt
shorter than the convolution's taps and never decodes two requests beside each
other. This does: 600 + 8 positions of ``benchmarks/configs/lfm2-8b-a1b.json``
through the engine's ``[1, 1024]`` prefill into a slot that is not the first,
on pages that are not the first (three of them), and a 2-token prompt through
``[1, 128]`` into another slot (one row of its state is the prompt's first
position, the other its second; nothing before them), then eight 128-slot
decode steps for both at once through the convolutions' rows and the live
pages, logits against ``benchmarks/architectures/lfm2_moe.py:forward`` in
float32; and the same against references with the taps reversed, without the
output gate, with the kept rows a position late or with another
``rope_theta``, each of which has to FAIL the cell's tolerance; what a
reference without ``expert_bias`` reads is printed beside them and not held
to it (what a changed routing moves goes with what bfloat16 rounding's own
flipped choices move: PERF.md section 6, PR 40). (A head norm after RoPE
cannot show here: the seeded scales are ones, and such a norm commutes with
the rotation. ``tests/test_lfm2.py`` draws the scales.)

It needs the chip (9.3 GB of weights), so under ``tests/conftest.py`` (which
holds JAX to the CPU) the test only starts this file as a process of its own
where the machine has a chip, and is skipped elsewhere:

    chiprun -- python3 tests/test_chip_lfm2.py
    chiprun -- python3 -m pytest tests/test_chip_lfm2.py -q -s
"""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 3e-2                     # the cell's: benchmarks/jobs/serve.py
STEPS, SEED = 8, 2718281828
# (prompt, slot, first page)
LONG, SHORT = (600, 5, 7), (2, 77, 40)


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

    seed = SEED
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "lfm2-8b-a1b.json")) as f:
        conf = json.load(f)
    arch = architecture(conf)
    e = EngineConfig(**conf["job"]["engine"])
    eng = JaxLLMEngine(LLMConfig(
        model_id="tiny", seed=seed % 2 ** 32, engine_config=e,
        model_overrides=arch.program_overrides(conf, e.max_model_len)),
        seed=seed % 2 ** 32)
    mcfg, mr = eng.mcfg, eng._mr
    note("engine up on", jax.devices()[0].device_kind, conf["initializer"])
    B, MP = e.max_num_seqs, e.pages_per_seq
    rng = np.random.default_rng(seed)
    tables = np.zeros((B, MP), np.int32)
    active = np.zeros(B, bool)
    seqs, got = {}, {}
    for prompt, slot, first in (LONG, SHORT):
        toks = rng.integers(0, mcfg.vocab_size, prompt + STEPS, dtype=np.int32)
        S = eng._prefill_bucket(prompt)
        need = -(-len(toks) // e.page_size)
        tables[slot, :need] = np.arange(first, first + need)
        batch = np.zeros((1, S), np.int32)
        batch[0, :prompt] = toks[:prompt]
        # the engine's own call: one admitted request, [1, S], told its slot
        logits, eng.cache = mr.prefill(
            eng.params, mcfg, eng.cache, jnp.asarray(batch),
            jnp.asarray([prompt], jnp.int32),
            jnp.asarray(tables[slot:slot + 1]), jnp.asarray([slot], jnp.int32))
        seqs[slot], got[slot] = (toks, prompt), [np.asarray(logits[0])]
        active[slot] = True
        note(f"prefill [1, {S}] of {prompt} tokens into slot {slot}")
    for i in range(STEPS):
        last, seq_lens = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for slot, (toks, prompt) in seqs.items():
            last[slot], seq_lens[slot] = toks[prompt + i], prompt + i
        logits, eng.cache = mr.decode_step(
            eng.params, mcfg, eng.cache, jnp.asarray(last),
            jnp.asarray(seq_lens), jnp.asarray(tables), jnp.asarray(active))
        for slot in seqs:
            got[slot].append(np.asarray(logits[slot]))
    got = {slot: np.stack(v) for slot, v in got.items()}
    note(f"{STEPS} decode steps of both requests done")

    def reference(drop_bias=False, **change):
        rcfg = dict(arch.reference_cfg(conf), **change)

        @jax.jit
        def run(p, t):
            rp = arch.to_reference_params(p, conf)
            if drop_bias:
                rp = dict(rp, layers=[
                    dict(lp, expert_bias=lp["expert_bias"] * 0.0)
                    if "expert_bias" in lp else lp for lp in rp["layers"]])
            with jax.default_matmul_precision("highest"):
                return arch.forward(rp, t[None], rcfg, last=STEPS + 1)[0]
        return run

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    p = eng.params["params"]
    out = {"device": jax.devices()[0].device_kind, "seed": seed, "tol": TOL,
           "initializer": conf["initializer"],
           "finite": all(bool(np.isfinite(g).all()) for g in got.values())}
    spoiled = {"taps_reversed": {"taps_reversed": True},
               "no_output_gate": {"output_gate": False},
               "state_a_row_late": {"state_lag": 1},
               "rope_theta_1e4": {"rope_theta": 10000},
               "no_expert_bias": {"drop_bias": True}}       # printed only
    for name, (prompt, slot, _) in (("long", LONG), ("short", SHORT)):
        tj = jnp.asarray(seqs[slot][0])
        out[f"rel_err_{name}"] = rel(got[slot], np.asarray(reference()(p, tj)))
        note(name, "reference", out[f"rel_err_{name}"])
        for what, change in spoiled.items():
            if name == "short" and what == "rope_theta_1e4":
                continue     # ten positions: the two thetas hardly differ
            key = f"{what}_{name}"
            out[key] = rel(got[slot], np.asarray(reference(**change)(p, tj)))
            note(name, what, out[key])
    out["ok"] = bool(
        out["finite"] and out["rel_err_long"] < TOL
        and out["rel_err_short"] < TOL
        and all(v > TOL for k, v in out.items()
                if k.endswith(("_long", "_short"))
                and not k.startswith(("rel_", "no_expert_bias"))))
    print(json.dumps(out), flush=True)
    return out


def test_engine_programs_match_the_reference_over_pages_and_short_prompts():
    from ray_tpu.util.accelerators import _count_device_nodes

    if not _count_device_nodes():
        pytest.skip("needs a TPU chip: chiprun -- python3 "
                    "tests/test_chip_lfm2.py")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=1500)
    print(proc.stderr[-4000:], proc.stdout[-4000:])
    assert proc.returncode == 0, proc.stdout[-2000:]


if __name__ == "__main__":
    sys.exit(0 if main()["ok"] else 1)
