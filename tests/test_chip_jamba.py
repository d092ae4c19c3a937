"""A chip test: AI21-Jamba2-3B (jamba) at the published widths, all 28 layers,
against the plain reference, through the programs the engine times.

The benchmark cell's own ``correct`` (``benchmarks/jobs/serve.py:
reference_check``) runs 200 + 4 positions through an every-slot ``[192, 256]``
batch: two chunks of the scan, one page, never the ``[1, S]`` call with a slot
that the engine times, no decode step beside a prompt, no slot used twice.
This does, on ``benchmarks/configs/ai21-jamba2-3b.json``:

- 1,000 + 8 positions through the engine's ``[1, 1024]`` call into a slot
  that is not the first, on pages that are not the first: eight chunks of
  ``ssm_scan`` with the state carried over seven edges, a second page of both
  attention layers; then a 2-token prompt (shorter than the convolution's
  taps) through ``[1, 128]``, a call that CARRIES the first request's decode
  step (``ssm_riding``, ``paged_gqa_riding``; at 192 slots every ``[1, S]``
  of this cell carries), and 192-slot decode steps between and after
  (``tests/prefill_rows.py:teacher_forced_riding``);
- the first slot used AGAIN by a shorter prompt (300 + 8 through a carrying
  ``[1, 512]``, its state and tail overwritten from the prompt alone) beside
  a 100-token request in another;
- two prompts (400 and 270) through ONE ``[2, 512]`` call told its slots,
  then four decode steps of both;
- the cell's own check: 200 + 4 positions through the every-slot ``[192,
  256]`` call, the largest program of the cell, which has to FIT beside the
  weights and every slot's state.

Every position's logits against ``benchmarks/architectures/jamba.py:forward``
in float32 at the highest matmul precision. Tolerances. ``TOL`` 3e-2 is the
cell's: bfloat16 weights, activations and products against float32 through 28
layers; a reference without the state (``B = 0``), without ``D``, without any
one of the three inner norms, without layer 7's attention, or computed from
weights rounded to float8, has to FAIL ``TOL``: what each reads is printed
and goes into the configuration file's ``initializer``.

It needs the chip (6.06 GB of weights), so under ``tests/conftest.py`` (which
holds JAX to the CPU) the test only starts this file as a process of its own
where the machine has a chip, and is skipped elsewhere:

    chiprun -- python3 tests/test_chip_jamba.py
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
CONFIG = os.path.join(REPO, "benchmarks", "configs", "ai21-jamba2-3b.json")
# (prompt, slot, first page)
LONG, SHORT = (1000, 5, 7), (2, 133, 20)
AGAIN, FOURTH = (300, 5, 40), (100, 12, 50)
PAIR = ((400, 20, 60), (270, 21, 70))
CHECK = (200, 4)               # the cell's own: prompt, decode steps
SPOILED = ("state", "D", "dt_layernorm", "b_layernorm", "c_layernorm",
           "attention", "float8")


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

    def draw(prompt, steps=STEPS):
        return rng.integers(0, mcfg.vocab_size, prompt + steps, dtype=np.int32)

    def reference(spoiled=None, last=STEPS + 1):
        without = () if spoiled in (None, "float8") else (spoiled,)
        rcfg = dict(arch.reference_cfg(conf), without=without)

        @jax.jit
        def run(p, t):
            with jax.default_matmul_precision("highest"):
                params = arch.to_reference_params(p, conf)
                if spoiled == "float8":
                    params = jax.tree.map(lambda x: x.astype(
                        jnp.float8_e4m3fn).astype(x.dtype), params)
                return arch.forward(params, t[None], rcfg, last=last)[0]
        return lambda toks: np.asarray(run(eng.params["params"],
                                           jnp.asarray(toks)))

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    out = {"device": jax.devices()[0].device_kind, "seed": SEED, "tol": TOL,
           "initializer": conf["initializer"], "calls_carrying": carrying}

    # (0) the cell's own check first: the every-slot call has to fit
    prompt, steps = CHECK
    toks = draw(prompt, steps)
    S = eng._prefill_bucket(prompt)
    tables = np.zeros((B, MP), np.int32)
    tables[0, :1] = 1
    batch, lens = np.zeros((B, S), np.int32), np.zeros(B, np.int32)
    batch[0, :prompt], lens[0] = toks[:prompt], prompt
    active = np.zeros(B, bool)
    active[0] = True
    logits, eng.cache = mr.prefill(eng.params, mcfg, eng.cache,
                                   jnp.asarray(batch), jnp.asarray(lens),
                                   jnp.asarray(tables))
    check = [np.asarray(logits[0])]
    last, seq_lens = np.zeros(B, np.int32), np.zeros(B, np.int32)
    for i in range(steps):
        last[0], seq_lens[0] = toks[prompt + i], prompt + i
        logits, eng.cache = mr.decode_step(
            eng.params, mcfg, eng.cache, jnp.asarray(last),
            jnp.asarray(seq_lens), jnp.asarray(tables), jnp.asarray(active))
        check.append(np.asarray(logits[0]))
    check = np.stack(check)
    out["rel_err_check"] = rel(check, reference(last=steps + 1)(toks))
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_gb_after_check"] = round(
        stats.get("peak_bytes_in_use", 0) / 1e9, 3)
    note(f"every-slot [{B}, {S}] + {steps} steps", out["rel_err_check"],
         "peak GB", out["peak_gb_after_check"])

    want = reference()
    # (1) eight chunks and a second page; a 2-token prompt whose call carries
    # the first request's step; 192-slot decode steps
    first = {slot: (draw(prompt), prompt, page)
             for prompt, slot, page in (LONG, SHORT)}
    got = teacher_forced_riding(eng, first, gap=1)
    # (2) the first slot again, shorter, beside another request
    second = {slot: (draw(prompt), prompt, page)
              for prompt, slot, page in (AGAIN, FOURTH)}
    got2 = teacher_forced_riding(eng, second, gap=1)
    note("four requests through [1, 1024], [1, 128], [1, 512], [1, 128], all "
         "carrying, decode steps between and after")
    finite = bool(np.isfinite(check).all())
    for name, seqs, g in (("long", first, got), ("short", first, got),
                          ("again", second, got2), ("fourth", second, got2)):
        slot = {"long": LONG, "short": SHORT, "again": AGAIN,
                "fourth": FOURTH}[name][1]
        finite = finite and bool(np.isfinite(g[slot]).all())
        out[f"rel_err_{name}"] = rel(g[slot], want(seqs[slot][0]))
        note(name, "reference", out[f"rel_err_{name}"])
    long_got, long_toks = got[LONG[1]], first[LONG[1]][0]
    for what in SPOILED:
        out["without_" + what] = rel(long_got, reference(what)(long_toks))
        note("long,", what, "spoiled:", out["without_" + what])

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
    out["kernels_decode"] = dict(kernels(mr.decode_step.lower(
        eng.params, mcfg, eng.cache, jnp.asarray(last), jnp.asarray(seq_lens),
        jnp.asarray(tables), jnp.asarray(active)).compile()))
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_gb"] = round(stats.get("peak_bytes_in_use", 0) / 1e9, 3)
    out["finite"] = finite
    errs = [v for k, v in out.items() if k.startswith("rel_err_")]
    out["ok"] = bool(
        finite and max(errs) < TOL
        and all(out["without_" + k] > TOL for k in SPOILED)
        and carrying == [True] * 4
        and out["kernels_2x512"] == {"ssm_scan": 26, "flash_fwd": 2}
        and out["kernels_decode"] == {"ssm_step": 26, "paged_gqa_decode": 2})
    print(json.dumps(out), flush=True)
    return out


def test_engine_programs_match_the_reference_over_chunks_and_slots():
    from ray_tpu.util.accelerators import _count_device_nodes

    if not _count_device_nodes():
        pytest.skip("needs a TPU chip: chiprun -- python3 "
                    "tests/test_chip_jamba.py")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=2400)
    print(proc.stderr[-4000:], proc.stdout[-4000:])
    assert proc.returncode == 0, proc.stdout[-2000:]


if __name__ == "__main__":
    sys.exit(0 if main()["ok"] else 1)
