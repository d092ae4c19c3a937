"""A chip test: Kimi-Linear-48B-A3B-Instruct (kimi_linear) at the published
widths and the cell's sizes against the plain reference, through the programs
the engine times.

The benchmark cell's own ``correct`` (``benchmarks/jobs/serve.py:
reference_check``) runs 200 + 4 positions through an every-slot ``[64, 256]``
batch: three chunks and one chunk edge of ``kda_scan``, one page of latent
rows, never the ``[1, S]`` call with a slot that the engine times, no decode
step beside a prompt, no slot used twice. This does, on
``benchmarks/configs/kimi-linear-48b-a3b.json``:

- 6,000 + 512 positions and 8 more through the engine's ``[1, 8192]`` call
  into a slot that is not the first, on pages that do not start at 1: 102
  chunks of ``kda_scan`` with the state carried over every edge and padding
  behind the prompt, thirteen pages of latent rows in each of the two latent
  layers; then a 700-token prompt through ``[1, 1024]`` and a 2-token one
  (shorter than the convolutions' taps) through ``[1, 256]``, calls that
  CARRY the decoding slots' step (``kda_riding``; the latent mixer with a
  prompt's rows and a step's in one program), and 64-slot decode steps
  between and after (``tests/prefill_rows.py:teacher_forced_riding``);
- the first slot used AGAIN by a shorter prompt (300 + 8 through a carrying
  ``[1, 512]``, its state and tail overwritten from the prompt alone) beside
  a 130-token request in another;
- two prompts (400 and 270) through ONE ``[2, 512]`` call told its slots,
  then four decode steps of both;
- a 100-token prompt through a ``[1, 16384]`` call, the cell's longest
  bucket: it ends in the first of 128 chunks, ``kda_scan`` passes over the
  other 127 in every head and layer, and the zeros it leaves behind the
  prompt's end go through ``o_proj``, the experts and the latent layers like
  any row; then four decode steps from the state it handed back.

Every position's logits against ``benchmarks/architectures/
kimi_linear.py:forward`` in float32 at the highest matmul precision.
Tolerances. ``TOL`` 3e-2 is the cell's: bfloat16 weights, activations and
products against float32 through eight layers (PERF.md section 6 has the
readings). A reference without beta, the decay, the convolutions, the output
gate or the l2 norm on k, with rotated latent layers, with 1 / sqrt(128) for
the latent scale or given another rank's experts has to FAIL ``TOL``; what the
references with a bias that enters the gates and with a state rounded to
bfloat16 after every position read is PRINTED beside them (``printed``): the
seeded selection bias is 4% of a score and the seeded decays forget within
tens of positions, so neither is held to a limit here; that the state IS
float32 is held by its dtype, that the bias only chooses by
``tests/test_kimi_linear.py`` at float32's tolerance.

It needs the chip (7.5 GB of weights), so under ``tests/conftest.py`` (which
holds JAX to the CPU) the test only starts this file as a process of its own
where the machine has a chip, and is skipped elsewhere:

    chiprun -- python3 tests/test_chip_kimi_linear.py
"""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 3e-2                     # the cell's: benchmarks/jobs/serve.py
STEPS, SEED = 8, 2718281829
CONFIG = os.path.join(REPO, "benchmarks", "configs", "kimi-linear-48b-a3b.json")
# (prompt, slot, first page)
LONG, MIDDLE, SHORT = (6512, 5, 7), (700, 40, 100), (2, 33, 30)
AGAIN, FOURTH = (300, 5, 140), (130, 12, 150)
PAIR = ((400, 20, 160), (270, 21, 170))
FIRST_CHUNK, LONGEST = ((100, 50, 190),), 16384
SPOILED = {"no_beta": {"without": ("beta",)},
           "no_decay": {"without": ("decay",)},
           "no_conv": {"without": ("conv",)},
           "no_out_gate": {"without": ("out_gate",)},
           "no_k_norm": {"without": ("k_norm",)},
           "rotated_latent": {"rotate_latent": True},
           "latent_scale_rsqrt_128": {"latent_scale": 128 ** -0.5},
           "other_ranks_experts": {"first_expert": 64}}
PRINTED = {"bias_in_gates": {"bias_in_gates": True},
           "bfloat16_state": {"without": ("float32_state",)}}


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
                for r in (LONG, MIDDLE, SHORT, AGAIN, FOURTH)]

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
           "initializer": conf["initializer"], "calls_carrying": carrying,
           "state_dtype": str(eng.cache["kda"].state.dtype)}
    want = reference()
    # (1) 102 chunks and thirteen pages through [1, 8192]; two prompts whose
    # calls carry the decoding slots' step; 64-slot decode steps
    first = {slot: (draw(prompt), prompt, page)
             for prompt, slot, page in (LONG, MIDDLE, SHORT)}
    got = teacher_forced_riding(eng, first, gap=1)
    note("three requests through [1, 8192], [1, 1024] carrying, [1, 256] "
         "carrying, decode steps between and after")
    # (2) the first slot again, shorter, beside another request
    second = {slot: (draw(prompt), prompt, page)
              for prompt, slot, page in (AGAIN, FOURTH)}
    got2 = teacher_forced_riding(eng, second, gap=1)
    note("two more through [1, 512] and [1, 256], both carrying")
    finite = True
    for name, req, seqs, g in (
            ("long", LONG, first, got), ("middle", MIDDLE, first, got),
            ("short", SHORT, first, got), ("again", AGAIN, second, got2),
            ("fourth", FOURTH, second, got2)):
        slot = req[1]
        finite = finite and bool(np.isfinite(g[slot]).all())
        out[f"rel_err_{name}"] = rel(g[slot], want(seqs[slot][0]))
        note(name, "reference", out[f"rel_err_{name}"])
    long_got, long_toks = got[LONG[1]], first[LONG[1]][0]
    for what, change in {**SPOILED, **PRINTED}.items():
        out[what] = rel(long_got, reference(**change)(long_toks))
        note("long", what, out[what])

    def one_call(rows, S, name):
        """The prompts of ``rows`` through ONE ``[len(rows), S]`` call told
        its slots, then four decode steps of them: rel_err_<name>_<row>."""
        nonlocal finite
        toks2 = {slot: draw(prompt) for prompt, slot, _ in rows}
        R = len(rows)
        batch, lens = np.zeros((R, S), np.int32), np.zeros(R, np.int32)
        tables = np.zeros((B, MP), np.int32)
        for i, (prompt, slot, page) in enumerate(rows):
            batch[i, :prompt], lens[i] = toks2[slot][:prompt], prompt
            need = -(-(prompt + STEPS) // e.page_size)
            tables[slot, :need] = np.arange(page, page + need)
        slots = np.asarray([slot for _, slot, _ in rows], np.int32)
        args = (jnp.asarray(batch), jnp.asarray(lens),
                jnp.asarray(tables[slots]), jnp.asarray(slots))
        call = mr.prefill.lower(eng.params, mcfg, eng.cache, *args).compile()
        out[f"kernels_{R}x{S}"] = dict(kernels(call))
        logits, eng.cache = mr.prefill(eng.params, mcfg, eng.cache, *args)
        got = {slot: [np.asarray(logits[i])] for i, slot in enumerate(slots)}
        last, seq_lens = np.zeros(B, np.int32), np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        active[slots] = True
        for step in range(4):
            for prompt, slot, _ in rows:
                last[slot] = toks2[slot][prompt + step]
                seq_lens[slot] = prompt + step
            logits, eng.cache = mr.decode_step(
                eng.params, mcfg, eng.cache, jnp.asarray(last),
                jnp.asarray(seq_lens), jnp.asarray(tables),
                jnp.asarray(active))
            for slot in slots:
                got[slot].append(np.asarray(logits[slot]))
        for i, (prompt, slot, _) in enumerate(rows):
            # the reference's last STEPS + 1 rows are positions prompt - 1 ..
            # prompt + STEPS - 1: the first five are the call's and the steps'
            g, w = np.stack(got[slot]), want(toks2[slot])[:5]
            out[f"rel_err_{name}_{i}"] = rel(g, w)
            finite = finite and bool(np.isfinite(g).all())
            note(f"[{R}, {S}] row", i, out[f"rel_err_{name}_{i}"])

    # (3) two prompts through ONE [2, 512] call told its slots, four steps
    one_call(PAIR, eng._prefill_bucket(max(p for p, _, _ in PAIR)), "pair")
    # (4) a prompt that ends in the first chunk of the longest bucket
    one_call(FIRST_CHUNK, LONGEST, "first_chunk")
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_gb"] = round(stats.get("peak_bytes_in_use", 0) / 1e9, 3)
    out["finite"] = finite
    errs = [v for k, v in out.items() if k.startswith("rel_err_")]
    out["ok"] = bool(
        finite and max(errs) < TOL
        and all(not out[k] <= TOL for k in SPOILED)    # NaN: it diverged
        and out["state_dtype"] == "float32"
        and carrying == [False, True, True, True, True]
        and out["kernels_2x512"].get("kda_scan") == 6)
    print(json.dumps(out), flush=True)
    return out


def test_engine_programs_match_the_reference_over_chunks_and_slots():
    from ray_tpu.util.accelerators import _count_device_nodes

    if not _count_device_nodes():
        pytest.skip("needs a TPU chip: chiprun -- python3 "
                    "tests/test_chip_kimi_linear.py")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=2400)
    print(proc.stderr[-4000:], proc.stdout[-4000:])
    assert proc.returncode == 0, proc.stdout[-2000:]


if __name__ == "__main__":
    sys.exit(0 if main()["ok"] else 1)
