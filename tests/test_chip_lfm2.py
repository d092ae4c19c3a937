"""A chip test: LFM2-8B-A1B (lfm2_moe) at the published widths against the
plain reference, through the programs the engine times.

The benchmark cell's own ``correct`` (``benchmarks/jobs/serve.py:
reference_check``) runs 200 + 4 positions through an every-slot ``[128, 256]``
batch: with pages of 256 it never reads a second page, never runs the ``[1,
S]`` call with a slot that the engine times, never gives a slot a prompt
shorter than the convolution's taps and never decodes two requests beside each
other. This does: 600 + 8 positions of ``benchmarks/configs/lfm2-8b-a1b.json``
through the engine's ``[1, 1024]`` prefill into a slot that is not the first,
on pages that are not the first (three of them), a 2-token prompt through
``[1, 128]`` into another slot (one row of its state is the prompt's first
position, the other its second; nothing before them) and 300 + 8 through ``[1,
512]`` into a third, each call the program the engine calls since PR 42: the
one that CARRIES the decode step of whoever decodes by then (``prefill``'s
``riders``: nobody for the first call, the first request's second step in the
second call, both others' steps in the third;
``tests/prefill_rows.py:teacher_forced_riding``), 128-slot decode steps in
between and after through the convolutions' rows and the live pages, every
request's eight tokens fed; logits against ``benchmarks/architectures/lfm2_moe.py:forward`` in
float32; and the same against references with the taps reversed, without the
output gate, with the kept rows a position late or with another
``rope_theta``, each of which has to FAIL the cell's tolerance; what a
reference without ``expert_bias`` reads is printed beside them and not held
to it (what a changed routing moves goes with what bfloat16 rounding's own
flipped choices move: PERF.md section 6, PR 40). (A head norm after RoPE
cannot show here: the seeded scales are ones, and such a norm commutes with
the rotation. ``tests/test_lfm2.py`` draws the scales.)

Since PR 41 the engine also puts the rows of several admitted requests into
one call (``llm/engine.py:prefill_groups``), and ``grouped`` holds that call to
the one-row calls it replaces: two prompts (750 and 600 tokens) through ``[2,
1024]`` and three (300, 400, 270) with one padding row through ``[4, 512]``,
each row told its slot, against the same prompts one a ``[1, S]`` call into
the same slots and pages: logits, the pages and the convolutions' two rows by
slot agree to the cell's tolerance, the padding row (length 0, the slot past the last) writes nothing
but the scratch page. It also prints what the issues rest on: a call's time
by shape (``[1, S]``, ``[2, S]``, ``[4, S]``: the fixed part every held weight
costs once a call, and the part that goes with the padded tokens), how
long a shape takes to be ready off the serving path, and (PR 42) what a
decode step of 127 slots costs alone and riding a ``[1, 1024]`` call.
``--grouped`` runs that part alone.

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
LONG, SHORT, THIRD = (600, 5, 7), (2, 77, 40), (300, 30, 90)


def grouped(eng, note) -> dict:
    """The ``[R, S]`` call with ``slots`` against the same requests' ``[1, S]``
    calls, and a call's time by shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from prefill_rows import kernels

    e, mcfg, mr = eng.ecfg, eng.mcfg, eng._mr
    B, MP = e.max_num_seqs, e.pages_per_seq
    rng = np.random.default_rng(SEED + 1)
    out = {}
    # this model's prefill programs carry a decode step at every shape of
    # the engine's (``eng._carries``): nobody's here
    idle = (jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
            jnp.zeros((B, MP), jnp.int32), jnp.zeros(B, bool))

    # the programs as the engine gets them: all traced in one process of
    # their own, compiled in threads (``llm/prefill_shapes.py``); the engine's
    # own seven and the two past its cap that the times below want
    made = eng._row_shapes
    wanted = [(R, S) for S in (128, 256, 512, 1024, 2048) for R in (2, 4)
              if R * S <= 4096]
    t0 = time.time()
    made.want(wanted)
    assert made.wait(1500)
    out["ready_s"] = round(time.time() - t0, 2)
    out["shapes_ready"] = sorted(made.ready)
    out["shapes_failed"] = {str(k): v[:300] for k, v in made.failed.items()}
    note(f"{len(made.ready)} of {len(wanted)} shapes ready in "
         f"{out['ready_s']}s", out["shapes_failed"])
    assert sorted(made.ready) == sorted(wanted), made.failed

    def compiled(R, S):
        program = made.ready[(R, S)]
        return lambda params, cfg, cache, *rows: program(params, cache, *rows)

    def same_kernels(R, S):
        """The exported program against jit's own lowering at the same shape,
        in this process, which has the chip: the same Pallas calls, the flash
        forward among them (the exporting process has no chip and must choose
        as if it had: PR 41's review)."""
        own = mr.prefill.lower(
            eng.params, mcfg, eng.cache, *made._rows(R, S)).compile()
        got, want = kernels(made.ready[(R, S)]), kernels(own)
        out[f"kernels_{R}x{S}"] = dict(got)
        note(f"[{R}, {S}] kernels", dict(got), "jit's own", dict(want))
        return got == want and got["flash_fwd"] == 3

    def call(fn, rows, S, R=None, riders=idle):
        """``rows``: (tokens, slot, first page) each; padding up to ``R``."""
        R = R or len(rows)
        toks = np.zeros((R, S), np.int32)
        lens = np.zeros(R, np.int32)
        tables = np.zeros((R, MP), np.int32)
        slots = np.full(R, B, np.int32)          # padding: the slot past the last
        for i, (t, slot, first) in enumerate(rows):
            toks[i, :len(t)], lens[i], slots[i] = t, len(t), slot
            need = -(-len(t) // e.page_size)
            tables[i, :need] = np.arange(first, first + need)
        args = (jnp.asarray(toks), jnp.asarray(lens), jnp.asarray(tables),
                jnp.asarray(slots))
        if eng._carries(R, S):  # the shape's program carries a step's rows
            (logits, _), eng.cache = fn(eng.params, mcfg, eng.cache, *args,
                                        riders)
        else:  # past the engine's own shapes: the plain program
            logits, eng.cache = fn(eng.params, mcfg, eng.cache, *args)
        return logits

    def state(rows):
        """What the rows' requests left: their pages and their two rows."""
        pages = np.concatenate([
            np.asarray(eng.cache["full"][:, first:first - (-len(t) // e.page_size)],
                       np.float32).reshape(-1)
            for t, _, first in rows])
        conv = np.asarray(eng.cache["conv"][:, :, [s for _, s, _ in rows]],
                          np.float32)
        return pages, conv

    def timed(fn, rows, S, R=None, n=5, riders=idle):
        call(fn, rows, S, R, riders).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(n):
            logits = call(fn, rows, S, R, riders)
        logits.block_until_ready()
        return (time.perf_counter() - t0) / n * 1e3

    def rel(a, b):
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

    page = 60
    ok = True
    for name, R, S, prompts in (("2x1024", 2, 1024, (750, 600)),
                                ("4x512", 4, 512, (300, 400, 270))):
        rows = []
        for j, n in enumerate(prompts):
            rows.append((rng.integers(0, mcfg.vocab_size, n, dtype=np.int32),
                         9 + 3 * j, page))
            page += -(-n // e.page_size)
        alone = [np.asarray(call(mr.prefill, [r], S)[0]) for r in rows]
        want_pages, want_conv = state(rows)
        # spoil what the one-row calls left, so the group's writes are seen
        eng.cache = eng.cache.replace({
            "full": eng.cache["full"].at[:, rows[0][2]:page].set(0),
            "conv": eng.cache["conv"].at[
                :, :, [s for _, s, _ in rows]].set(0)})
        untouched = (
            np.asarray(eng.cache["full"][:, page:page + 4], np.float32),
            np.asarray(eng.cache["conv"][:, :, B - 1], np.float32))
        fn = compiled(R, S)
        got = np.asarray(call(fn, rows, S, R))
        got_pages, got_conv = state(rows)
        errs = {"logits": max(rel(got[i], a) for i, a in enumerate(alone)),
                "pages": rel(got_pages, want_pages),
                "conv": rel(got_conv, want_conv)}
        same = (np.array_equal(untouched[0], np.asarray(
            eng.cache["full"][:, page:page + 4], np.float32))
            and np.array_equal(untouched[1], np.asarray(
                eng.cache["conv"][:, :, B - 1], np.float32)))
        out[f"group_{name}"] = dict(errs, others_untouched=same)
        note(f"[{R}, {S}] against {len(rows)} one-row calls:", errs, same)
        # two programs of different shapes round apart in bfloat16, and a
        # rounded router score flips a token's fourth expert here and there
        # (PERF.md section 6, PR 40): the cell's own tolerance, as against
        # the reference
        ok = ok and same and all(v < TOL for v in errs.values())
        ok = same_kernels(R, S) and ok
        one = sum(timed(mr.prefill, [r], S) for r in rows)
        both = timed(fn, rows, S, R)
        out[f"ms_{name}"] = {"one_row_calls": round(one, 2),
                             "grouped": round(both, 2)}
        note(f"[{R}, {S}]: {one:.2f} ms as {len(rows)} calls, {both:.2f} as one")
    # a call's time by shape: every row a full bucket less 8
    for S in (128, 256, 512, 1024, 2048):
        for R in (1, 2, 4):
            if R * S > 4096 or (R, S) in ((2, 1024), (4, 512)):
                continue
            fn = mr.prefill if R == 1 else compiled(R, S)
            rows = [(rng.integers(0, mcfg.vocab_size, S - 8, dtype=np.int32),
                     20 + j, 100 + 8 * j) for j in range(R)]
            out[f"ms_full_{R}x{S}"] = round(timed(fn, rows, S), 2)
            note(f"[{R}, {S}] full rows: {out[f'ms_full_{R}x{S}']} ms a call")
    # a decode step alone and riding a call: every slot but the one the call
    # fills at 700 positions (three live pages of its own ten)
    own = 1 + np.arange(B * MP, dtype=np.int32).reshape(B, MP)
    full = (jnp.zeros(B, jnp.int32), jnp.full(B, 700, jnp.int32),
            jnp.asarray(own), jnp.asarray(np.arange(B) > 0))
    row = [(rng.integers(0, mcfg.vocab_size, 1000, dtype=np.int32), 0, 1)]

    def decode_ms(n=10):
        def run():
            logits, eng.cache = mr.decode_step(eng.params, mcfg, eng.cache,
                                               *full)
            return logits
        run().block_until_ready()
        t0 = time.perf_counter()
        for _ in range(n):
            logits = run()
        logits.block_until_ready()
        return (time.perf_counter() - t0) / n * 1e3

    out["ms_riding"] = {
        "call_1x1024_nobody_riding": round(timed(mr.prefill, row, 1024), 2),
        "call_1x1024_127_riding": round(
            timed(mr.prefill, row, 1024, riders=full), 2),
        "decode_step_127_alone": round(decode_ms(), 2)}
    note("a [1, 1024] call and a 127-slot decode step:", out["ms_riding"])
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_gb"] = round(stats.get("peak_bytes_in_use", 0) / 1e9, 3)
    out["limit_gb"] = round(stats.get("bytes_limit", 0) / 1e9, 3)
    out["ok"] = ok
    return out


def main(only_grouped: bool = False) -> dict:
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
    if only_grouped:
        out = grouped(eng, note)
        print(json.dumps(out), flush=True)
        return out
    from prefill_rows import teacher_forced_riding

    rng = np.random.default_rng(seed)
    seqs = {slot: (rng.integers(0, mcfg.vocab_size, prompt + STEPS,
                                dtype=np.int32), prompt, first)
            for prompt, slot, first in (LONG, SHORT, THIRD)}
    # the engine's own calls: [1, S] told its slot, carrying the others' step
    got = teacher_forced_riding(eng, seqs, gap=1)
    note(f"three requests, {STEPS} tokens each: [1, 1024] carrying nobody, "
         f"[1, 128] one step, [1, 512] two; decode steps between and after")

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
    for name, (prompt, slot, _) in (("long", LONG), ("short", SHORT),
                                    ("third", THIRD)):
        tj = jnp.asarray(seqs[slot][0])
        out[f"rel_err_{name}"] = rel(got[slot], np.asarray(reference()(p, tj)))
        note(name, "reference", out[f"rel_err_{name}"])
        for what, change in spoiled.items():
            if name == "third":
                break        # held to the reference; the others show the rest
            if name == "short" and what == "rope_theta_1e4":
                continue     # ten positions: the two thetas hardly differ
            key = f"{what}_{name}"
            out[key] = rel(got[slot], np.asarray(reference(**change)(p, tj)))
            note(name, what, out[key])
    out["ok"] = bool(
        out["finite"] and out["rel_err_long"] < TOL
        and out["rel_err_short"] < TOL and out["rel_err_third"] < TOL
        and all(v > TOL for k, v in out.items()
                if k.endswith(("_long", "_short"))
                and not k.startswith(("rel_", "no_expert_bias"))))
    out["grouped"] = grouped(eng, note)
    out["ok"] = out["ok"] and out["grouped"]["ok"]
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
    sys.exit(0 if main("--grouped" in sys.argv)["ok"] else 1)
