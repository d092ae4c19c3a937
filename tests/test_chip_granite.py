"""A chip test: Granite-4.0-H-Small (granitemoehybrid) at the published widths
against the plain reference, through the programs the engine times.

The benchmark cell's own ``correct`` (``benchmarks/jobs/serve.py:
reference_check``) runs 200 + 4 positions through an every-slot ``[40, 256]``
batch: one chunk of the scan, one page, never the ``[1, S]`` call with a slot
that the engine times, no decode step beside a prompt, no slot used twice.
This does, on ``benchmarks/configs/granite-4.0-h-small.json``:

- 1,000 + 8 positions through the engine's ``[1, 1024]`` call into a slot
  that is not the first, on pages that are not the first: four chunks of
  ``ssd_scan`` with the state carried over three edges, a second page of the
  attention layer (at 40 slots a call of more than 640 positions carries
  nobody's step: ``engine._carries``); then a 2-token prompt (shorter than
  the convolution's taps) through ``[1, 256]``, a call that CARRIES the first
  request's decode step (``ssd_riding``, ``paged_gqa_riding``), and 40-slot
  decode steps between and after
  (``tests/prefill_rows.py:teacher_forced_riding``);
- the first slot used AGAIN by a shorter prompt (300 + 8 through a carrying
  ``[1, 512]``, its state and tail overwritten from the prompt alone) beside
  a 130-token request in another;
- two prompts (400 and 270) through ONE ``[2, 512]`` call told its slots,
  then four decode steps of both.

Every position's logits against ``benchmarks/architectures/
granitemoehybrid.py:forward`` in float32 at the highest matmul precision.
Tolerances. ``TOL`` 3e-2 is the cell's: bfloat16 weights, activations and
products against float32 through ten layers read 4e-3..1.2e-2 of the logits'
norm (PERF.md section 6, PR 45), and a reference without D, dt_bias, the
convolution's bias or the gate, or with the residual or attention multiplier
of a plain decoder, moves the logits by 0.19-0.65 of their norm on the chip
(PR 45): each has to FAIL ``TOL``. What a reference that rounds the Mamba-2
state to bfloat16 after every position reads is PRINTED beside them and not
held to anything: over 1,008 positions it moves the logits by 2e-3 of their
norm (8.35e-3 against the engine where the float32 reference reads 8.11e-3,
my chip run, PR 45), under the rounding of the bfloat16 activations
themselves, because the seeded decays (A in [1, 16], dt up to 0.1 and more)
forget within tens of positions and a rounding cannot pile up; that the
state IS float32 is held by its dtype (``tests/test_granite_hybrid.py``,
``tests/test_chip_compile.py``).

It needs the chip (9.5 GB of weights), so under ``tests/conftest.py`` (which
holds JAX to the CPU) the test only starts this file as a process of its own
where the machine has a chip, and is skipped elsewhere:

    chiprun -- python3 tests/test_chip_granite.py
"""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 3e-2                     # the cell's: benchmarks/jobs/serve.py
STEPS, SEED = 8, 3141592653
CONFIG = os.path.join(REPO, "benchmarks", "configs", "granite-4.0-h-small.json")
# (prompt, slot, first page)
LONG, SHORT = (1000, 5, 7), (2, 33, 20)
AGAIN, FOURTH = (300, 5, 40), (130, 12, 50)
PAIR = ((400, 20, 60), (270, 21, 70))
SPOILED = {"no_D": {"without": ("D",)}, "no_dt_bias": {"without": ("dt_bias",)},
           "no_conv_bias": {"without": ("conv_bias",)},
           "no_gate": {"without": ("gate",)},
           "residual_multiplier_1": {"residual_multiplier": 1.0},
           "attention_multiplier_rsqrt": {"attention_multiplier": 128 ** -0.5}}


def main() -> dict:
    sys.path[:0] = [REPO, os.path.join(REPO, "tests")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.registry import architecture
    from prefill_rows import kernels, teacher_forced_riding
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.config import EngineConfig
    from ray_tpu.llm.engine import JaxLLMEngine

    t_start = time.time()

    def note(*a):
        print(f"[+{time.time() - t_start:6.1f}s]", *a, file=sys.stderr,
              flush=True)

    with open(CONFIG) as f:
        conf = json.load(f)
    arch = architecture(conf)
    e = EngineConfig(**conf["job"]["engine"])
    eng = JaxLLMEngine(LLMConfig(
        model_id="tiny", seed=SEED % 2 ** 32, engine_config=e,
        model_overrides=arch.program_overrides(conf, e.max_model_len)),
        seed=SEED % 2 ** 32)
    mcfg, mr = eng.mcfg, eng._mr
    B, MP = e.max_num_seqs, e.pages_per_seq
    note("engine up on", jax.devices()[0].device_kind, conf["initializer"])
    carrying = [eng._carries(1, eng._prefill_bucket(r[0]))
                for r in (LONG, SHORT, AGAIN, FOURTH)]

    rng = np.random.default_rng(SEED)

    def draw(prompt):
        return rng.integers(0, mcfg.vocab_size, prompt + STEPS, dtype=np.int32)

    def reference(**change):
        rcfg = dict(arch.reference_cfg(conf), **change)

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
           "initializer": conf["initializer"],
           "calls_carrying": carrying}
    want = reference()
    # (1) four chunks and a second page; a 2-token prompt whose call carries
    # the first request's step; 40-slot decode steps
    first = {slot: (draw(prompt), prompt, page)
             for prompt, slot, page in (LONG, SHORT)}
    got = teacher_forced_riding(eng, first, gap=1)
    # (2) the first slot again, shorter, beside another request
    second = {slot: (draw(prompt), prompt, page)
              for prompt, slot, page in (AGAIN, FOURTH)}
    got2 = teacher_forced_riding(eng, second, gap=1)
    note("four requests through [1, 1024], [1, 256] carrying, [1, 512], "
         "[1, 256] carrying, decode steps between and after")
    finite = True
    for name, seqs, g in (("long", first, got), ("short", first, got),
                          ("again", second, got2), ("fourth", second, got2)):
        slot = {"long": LONG, "short": SHORT, "again": AGAIN,
                "fourth": FOURTH}[name][1]
        finite = finite and bool(np.isfinite(g[slot]).all())
        out[f"rel_err_{name}"] = rel(g[slot], want(seqs[slot][0]))
        note(name, "reference", out[f"rel_err_{name}"])
    long_got, long_toks = got[LONG[1]], first[LONG[1]][0]
    for what, change in SPOILED.items():
        out[what] = rel(long_got, reference(**change)(long_toks))
        note("long", what, out[what])
    out["bfloat16_state"] = rel(long_got, reference(
        without=("float32_state",))(long_toks))
    note("long, a reference with a bfloat16 state", out["bfloat16_state"])

    # (3) two prompts through ONE [2, 512] call told its slots, four steps
    S = eng._prefill_bucket(max(prompt for prompt, _, _ in PAIR))
    toks2 = {slot: draw(prompt) for prompt, slot, _ in PAIR}
    batch, lens = np.zeros((2, S), np.int32), np.zeros(2, np.int32)
    tables = np.zeros((B, MP), np.int32)
    for i, (prompt, slot, page) in enumerate(PAIR):
        batch[i, :prompt], lens[i] = toks2[slot][:prompt], prompt
        need = -(-(prompt + STEPS) // e.page_size)
        tables[slot, :need] = np.arange(page, page + need)
    slots = np.asarray([slot for _, slot, _ in PAIR], np.int32)
    call = mr.prefill.lower(eng.params, mcfg, eng.cache, jnp.asarray(batch),
                            jnp.asarray(lens), jnp.asarray(tables[slots]),
                            jnp.asarray(slots)).compile()
    out["kernels_2x512"] = dict(kernels(call))
    logits, eng.cache = mr.prefill(
        eng.params, mcfg, eng.cache, jnp.asarray(batch), jnp.asarray(lens),
        jnp.asarray(tables[slots]), jnp.asarray(slots))
    pair = {slot: [np.asarray(logits[i])] for i, slot in enumerate(slots)}
    last, seq_lens = np.zeros(B, np.int32), np.zeros(B, np.int32)
    active = np.zeros(B, bool)
    active[slots] = True
    for step in range(4):
        for prompt, slot, _ in PAIR:
            last[slot], seq_lens[slot] = toks2[slot][prompt + step], prompt + step
        logits, eng.cache = mr.decode_step(
            eng.params, mcfg, eng.cache, jnp.asarray(last),
            jnp.asarray(seq_lens), jnp.asarray(tables), jnp.asarray(active))
        for slot in slots:
            pair[slot].append(np.asarray(logits[slot]))
    for i, (prompt, slot, _) in enumerate(PAIR):
        # the reference's last STEPS + 1 rows are positions prompt - 1 ..
        # prompt + STEPS - 1: the first five are the call's and the steps'
        g, w = np.stack(pair[slot]), want(toks2[slot])[:5]
        out[f"rel_err_pair_{i}"] = rel(g, w)
        finite = finite and bool(np.isfinite(g).all())
        note(f"[2, {S}] row", i, out[f"rel_err_pair_{i}"])
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_gb"] = round(stats.get("peak_bytes_in_use", 0) / 1e9, 3)
    out["finite"] = finite
    errs = [v for k, v in out.items() if k.startswith("rel_err_")]
    out["ok"] = bool(
        finite and max(errs) < TOL
        and all(out[k] > TOL for k in SPOILED)
        and carrying == [False, True, True, True]
        and out["kernels_2x512"].get("ssd_scan") == 9)
    print(json.dumps(out), flush=True)
    return out


def test_engine_programs_match_the_reference_over_chunks_and_slots():
    from ray_tpu.util.accelerators import _count_device_nodes

    if not _count_device_nodes():
        pytest.skip("needs a TPU chip: chiprun -- python3 "
                    "tests/test_chip_granite.py")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=1500)
    print(proc.stderr[-4000:], proc.stdout[-4000:])
    assert proc.returncode == 0, proc.stdout[-2000:]


if __name__ == "__main__":
    sys.exit(0 if main()["ok"] else 1)
