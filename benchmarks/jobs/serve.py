"""The serve cells: the deployment (replica side) and the open-loop load
(driver side). The replica is the only process that touches the chip.

``BenchLLMServer`` is ``LLMServer`` at the configuration's published widths
plus what a measurement needs from inside the replica: the reference check, a
watcher thread that snapshots the engine's counters at the window's edges and
takes the profiler trace, and, under ``--trace 1`` alone, two host spans on
the profiler's clock. With ``--trace 0`` the request path is ``LLMServer``'s
own, unwrapped.

The replica hands its results over in a file: above the knee its actor queue
is full of requests by design, and a control call would wait behind them.
"""

from __future__ import annotations

import asyncio
import json
import os
import threading
import time
from typing import List

from ray_tpu.llm.serve_llm import LLMServer


class BenchLLMServer(LLMServer):
    def __init__(self, config, params_blob=None, bench: dict = None):
        import dataclasses

        from benchmarks.jobs import common
        from benchmarks.registry import architecture

        self._compiles = common.CompileCounter()
        # the published widths go in here and not in the driver: the
        # architecture's module imports JAX, which the driver stays off
        conf = bench["config"]
        config = dataclasses.replace(
            config, model_overrides=architecture(conf).program_overrides(
                conf, config.engine_config.max_model_len))
        super().__init__(config, params_blob)
        self._bench = bench
        self._tracer = common.Tracer(bench["trace"], bench["out_dir"] + "/trace")
        if bench["trace"]:
            self._annotate_engine()

    # -- host spans on the profiler's clock (traced runs only) ---------------

    def _annotate_engine(self):
        """``engine.step`` and the sampler's blocking read-back become host
        spans, so the trace's idle gaps can be laid to them."""
        eng, tracer = self.engine, self._tracer
        step, sample = eng.step, eng._sample

        def annotated_step(*a, **kw):
            with tracer.annotate("engine.step"):
                return step(*a, **kw)

        def annotated_sample(logits):
            with tracer.annotate("sample_readback"):
                return sample(logits)

        eng.step, eng._sample = annotated_step, annotated_sample

    # -- correct ---------------------------------------------------------------

    def reference_check(self, seed: int, prompt_len: int, decode_steps: int) -> dict:
        """Prefill then ``decode_steps`` decode steps through the paged cache,
        with the engine's own compiled programs and shapes, against the plain
        float32 reference's full forward on the same tokens: logits, not
        tokens. Runs while the engine is idle; uses slot 0 and its pages."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.registry import architecture

        eng, e, mcfg = self.engine, self.engine.ecfg, self.engine.mcfg
        if eng.has_unfinished():
            raise RuntimeError("reference check needs an idle engine")
        conf = self._bench["config"]
        rng = np.random.default_rng(seed)
        total = prompt_len + decode_steps
        toks = rng.integers(0, mcfg.vocab_size, total, dtype=np.int32)
        B, MP = e.max_num_seqs, e.pages_per_seq
        S = eng._prefill_bucket(prompt_len)
        need = -(-total // e.page_size)
        tables = np.zeros((B, MP), np.int32)
        tables[0, :need] = np.arange(1, need + 1)
        batch = np.zeros((B, S), np.int32)
        batch[0, :prompt_len] = toks[:prompt_len]
        lens = np.zeros(B, np.int32)
        lens[0] = prompt_len
        active = np.zeros(B, bool)
        active[0] = True
        mr = eng._mr
        got = []
        logits, eng.cache = mr.prefill(
            eng.params, mcfg, eng.cache, jnp.asarray(batch), jnp.asarray(lens),
            jnp.asarray(tables))
        got.append(np.asarray(logits[0]))
        last = np.zeros(B, np.int32)
        seq_lens = np.zeros(B, np.int32)
        for i in range(decode_steps):
            last[0] = toks[prompt_len + i]       # teacher-forced, not sampled
            seq_lens[0] = prompt_len + i
            logits, eng.cache = mr.decode_step(
                eng.params, mcfg, eng.cache, jnp.asarray(last),
                jnp.asarray(seq_lens), jnp.asarray(tables), jnp.asarray(active))
            got.append(np.asarray(logits[0]))
        got = np.stack(got)                       # [1 + decode_steps, vocab]

        arch = architecture(conf)
        rcfg = arch.reference_cfg(conf)

        @jax.jit
        def ref_logits(p, t):
            with jax.default_matmul_precision("highest"):
                full = arch.forward(
                    arch.to_reference_params(p["params"], conf),
                    t[None], rcfg)[0]
            return full[prompt_len - 1:]
        want = np.asarray(ref_logits(eng.params, jnp.asarray(toks)))
        err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        worst = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        # Tolerance: the engine casts its float32 weights to bfloat16 at each
        # use and keeps bfloat16 activations and KV pages (TransformerConfig
        # .dtype), so it differs from the float32 reference by bf16 rounding
        # through 24 layers: 5e-3..1.5e-2 of the logits' norm on the chip
        # (PERF.md). 3e-2 admits that; a wrong page, position, mask or a
        # missing layer moves the logits by their own norm (~1).
        tol = 3e-2
        return {"ok": bool(err <= tol and np.isfinite(got).all()),
                "rel_err": err, "worst_rel": worst, "tol": tol,
                "positions": int(got.shape[0])}

    # -- the window --------------------------------------------------------------

    def arm(self, t0_wall: float, t1_wall: float, trace_at: float,
            trace_len: float) -> None:
        """Before load starts: at which wall-clock times the window opens and
        closes. A watcher thread snapshots the counters at both edges, traces
        ``trace_len`` seconds from ``trace_at`` into the window, and writes
        ``replica.json`` once the driver drops a ``finish`` file."""
        out_dir = self._bench["out_dir"]
        os.makedirs(out_dir, exist_ok=True)
        th = threading.Thread(
            target=self._watch, args=(t0_wall, t1_wall, trace_at, trace_len),
            name="bench-watcher", daemon=True)
        th.start()

    def _watch(self, t0_wall, t1_wall, trace_at, trace_len):
        from benchmarks.jobs import common

        out_dir = self._bench["out_dir"]
        result = {}
        try:
            def sleep_until(t):
                while time.time() < t:
                    time.sleep(min(0.01, max(0.0, t - time.time())))

            sleep_until(t0_wall)
            before = dict(self.engine.metrics)
            compiles0 = self._compiles.count
            if self._tracer.enabled:
                sleep_until(t0_wall + trace_at)
                self._tracer.start()
                sleep_until(t0_wall + trace_at + trace_len)
                self._tracer.stop()
            sleep_until(t1_wall)
            after = dict(self.engine.metrics)
            result = {
                "counters": {k: after[k] - before[k] for k in after},
                "compiles_in_window": self._compiles.count - compiles0,
                "waiting_at_end": self.engine.num_waiting(),
                "active_at_end": self.engine.num_active(),
            }
            finish = os.path.join(out_dir, "finish")
            while not os.path.exists(finish):
                time.sleep(0.05)
            if self._tracer.enabled:
                result["trace"] = self._tracer.reduce(
                    keep_as=self._bench.get("keep_trace_as"))
                result["trace_error"] = self._tracer.error
            result["device"] = common.device_report()
            result["compiles_total"] = self._compiles.count
        except Exception as e:  # the driver must see why, not a missing file
            import traceback

            result["error"] = f"{type(e).__name__}: {e}\n{traceback.format_exc()}"
        tmp = os.path.join(out_dir, "replica.json.tmp")
        with open(tmp, "w") as f:
            json.dump(result, f)
        os.replace(tmp, os.path.join(out_dir, "replica.json"))


# ---------------------------------------------------------------------------
# driver side: the open-loop load over HTTP (one thread, asyncio)
# ---------------------------------------------------------------------------


async def _post(session, url, body, timeout_s):
    import aiohttp

    try:
        async with session.post(
                url, json=body,
                timeout=aiohttp.ClientTimeout(total=timeout_s)) as resp:
            return resp.status, await resp.json(content_type=None)
    except asyncio.CancelledError:
        raise
    except Exception as e:
        return 0, {"error": f"{type(e).__name__}: {e}"}


def post_once(url: str, body: dict, timeout_s: float = 600.0):
    """One blocking POST (warm-up requests, before any load)."""
    async def go():
        import aiohttp

        async with aiohttp.ClientSession() as s:
            return await _post(s, url, body, timeout_s)

    return asyncio.run(go())


def offer_load(url: str, requests, t0_wall: float, seconds: float,
               temperature: float, end: str, drain_s: float) -> List[dict]:
    """Send each request when it is due (``t0_wall + due_s``) whether or not
    earlier ones were answered. ``end == "drain"``: after the window, go on
    offering the schedule's tail until everything that was due inside the
    window is answered, for at most ``drain_s``. ``end == "abandon"``: cancel
    what is unanswered at the window's end. One record per request."""
    records = [{"due_s": r.due_s, "prompt_tokens": len(r.prompt),
                "max_tokens": r.max_tokens, "status": None} for r in requests]

    async def one(session, i, r):
        rec = records[i]
        delay = t0_wall + r.due_s - time.time()
        if delay > 0:
            await asyncio.sleep(delay)
        rec["sent_s"] = time.time() - t0_wall
        status, payload = await _post(
            session, url, {"prompt": r.prompt, "max_tokens": r.max_tokens,
                           "temperature": temperature}, 300.0)
        rec["done_s"] = time.time() - t0_wall
        rec["status"] = status
        try:
            choice = payload["result"]["choices"][0]
            rec["token_ids"] = choice["token_ids"]
            rec["finish_reason"] = choice["finish_reason"]
        except (KeyError, TypeError, IndexError):
            rec["error"] = str(payload)[:500]

    async def go():
        import aiohttp

        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:
            tasks = [asyncio.ensure_future(one(session, i, r))
                     for i, r in enumerate(requests)]
            await asyncio.sleep(max(0.0, t0_wall + seconds - time.time()))
            if end == "drain":
                judged = [t for t, r in zip(tasks, requests)
                          if 0 <= r.due_s < seconds]
                if judged:
                    await asyncio.wait(judged, timeout=drain_s)
            for t in tasks:
                if not t.done():
                    t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)

    asyncio.run(go())
    return records
