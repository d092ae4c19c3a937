"""Observability overhead microbenchmark (``python -m tools.bench_obs``).

Measures what the task-event pipeline and tracing layer cost, so future
rounds can hold the line on "observability is pay-for-what-you-use":

* ``span_record_per_s``       — tracing.record_span throughput (enabled)
* ``event_record_us``         — one task_events.record() call (enabled)
* ``event_flush_us_per_task`` — amortized per-task cost of the 4-transition
                                record + batched AddTaskEvents flush
* ``submit_us_*``             — end-to-end no-op task latency with
                                observability fully off (baseline), task
                                events on (default config, goodput ledger
                                included), events with only the goodput
                                ledger off, and tracing on
* ``*_delta_pct``             — overhead relative to the disabled baseline
* ``train_step_us_*``         — one TrainStepBundle step (tiny config) with
                                built-in spans on vs everything disabled
* ``serve_request_us_*``      — one serve request through a handle (built-in
                                route/queue/execute spans) on vs disabled
* ``history_scrape_ms_*``     — GetMetricsHistory RPC cost (names + one
                                full series) against a live GCS ring

The acceptance bar rides ``traced_delta_pct`` (the microbench
task-throughput path): end-to-end hot-path span overhead must stay <= 5%
vs events-disabled; ``goodput_delta_pct`` / ``train_step_goodput_delta_pct``
hold the same bar for the default-on goodput ledger. Emits one JSON
object on stdout (plus --out FILE) that ``tools/benchtrack.py --check``
tracks for regressions.
"""

from __future__ import annotations

import argparse
import json
import os
import time

# A CPU-tier tool (it prices host-side instrumentation on a toy step): this
# process runs JAX next to a cluster of workers, and a chip belongs to one
# process at a time — pin every process to the CPU backend before jax loads.
os.environ["JAX_PLATFORMS"] = "cpu"


def _bench_span_record(n: int = 20_000) -> float:
    from ray_tpu.util import tracing

    t0 = time.perf_counter()
    now = time.time()
    for i in range(n):
        tracing.record_span("bench_span", now, now + 1e-6,
                            category="bench", idx=i)
    dt = time.perf_counter() - t0
    tracing.flush()
    return n / dt


def _bench_event_record(n: int = 20_000) -> float:
    from ray_tpu._private import task_events

    t0 = time.perf_counter()
    for i in range(n):
        task_events.record(f"bench{i:08x}", task_events.SUBMITTED,
                           name="bench", job_id="bench")
    dt = time.perf_counter() - t0
    task_events.drain()  # don't ship 20k synthetic events to the GCS
    return dt / n * 1e6


def _bench_event_flush(n_tasks: int = 2_000) -> float:
    """4 transitions per task + a real AddTaskEvents flush, amortized."""
    from ray_tpu._private import task_events

    t0 = time.perf_counter()
    for i in range(n_tasks):
        tid = f"flush{i:08x}"
        for st in (task_events.SUBMITTED, task_events.SCHEDULED,
                   task_events.RUNNING, task_events.FINISHED):
            task_events.record(tid, st, name="bench_flush", job_id="bench")
    task_events.flush()
    return (time.perf_counter() - t0) / n_tasks * 1e6


def _bench_submission_configs(ray_tpu, configs, rounds: int = 4,
                              n: int = 200):
    """Measure no-op task submit+complete latency under each observability
    config. Rounds are INTERLEAVED across configs (a-b-c, a-b-c, ...) so
    cluster warmup/noise drift hits every config equally; reports the
    per-config minimum."""
    @ray_tpu.remote
    def _noop(i):
        return i

    # warmup: function push + worker lease
    ray_tpu.get([_noop.remote(i) for i in range(20)], timeout=120)
    best = {name: float("inf") for name, _ in configs}
    for _ in range(rounds):
        for name, apply in configs:
            apply()
            t0 = time.perf_counter()
            ray_tpu.get([_noop.remote(i) for i in range(n)], timeout=300)
            best[name] = min(best[name],
                             (time.perf_counter() - t0) / n * 1e6)
    return best


def _bench_train_step(configs, steps: int = 12, warmup: int = 3):
    """Per-step latency of the tiny-config TrainStepBundle under each
    observability config (the built-in span path vs fully disabled)."""
    import jax
    import numpy as np

    from ray_tpu.models import CONFIGS
    from ray_tpu.parallel import TrainStepBundle, create_mesh

    mesh = create_mesh({"data": 1, "fsdp": 1, "seq": 1, "tensor": 1,
                        "expert": 1}, devices=jax.devices()[:1])
    bundle = TrainStepBundle(CONFIGS["tiny"], mesh, donate=False)
    batch = bundle.make_batch(np.random.default_rng(0), 2, 64)
    # per-config live state; warm every config up front so compiles and
    # first-touch costs never land inside a timed window
    state = {}
    for name, apply in configs:
        apply()
        params, opt_state = bundle.init(jax.random.PRNGKey(0))
        for _ in range(warmup):
            params, opt_state, loss = bundle.step(params, opt_state, batch)
        jax.block_until_ready(loss)
        state[name] = (params, opt_state)
    # rounds INTERLEAVED across configs (like the submit bench) so CPU
    # frequency/cache drift hits every config equally; per-config minimum
    best = {name: float("inf") for name, _ in configs}
    for _ in range(4):
        for name, apply in configs:
            apply()
            params, opt_state = state[name]
            t0 = time.perf_counter()
            for _ in range(steps):
                params, opt_state, loss = bundle.step(params, opt_state,
                                                      batch)
            jax.block_until_ready(loss)
            best[name] = min(best[name],
                             (time.perf_counter() - t0) / steps * 1e6)
            state[name] = (params, opt_state)
    return best


def _bench_serve_request(ray_tpu, configs, n: int = 100):
    """Per-request latency of one serve request through a handle (the
    built-in route/queue/execute span path) under each config."""
    from ray_tpu import serve

    @serve.deployment(name="bench_obs_echo", num_replicas=1)
    class _Echo:
        def __call__(self, x):
            return x

    handle = serve.run(_Echo.bind(), name="bench_obs_echo")
    ray_tpu.get([handle.remote(i) for i in range(20)], timeout=120)  # warm
    best = {name: float("inf") for name, _ in configs}
    for _ in range(5):
        for name, apply in configs:
            apply()
            t0 = time.perf_counter()
            ray_tpu.get([handle.remote(i) for i in range(n)], timeout=300)
            best[name] = min(best[name],
                             (time.perf_counter() - t0) / n * 1e6)
    serve.delete("bench_obs_echo")
    return best


def _bench_history_scrape(n: int = 50):
    """GetMetricsHistory cost against the live GCS ring: the names index
    and one full raw series, in milliseconds per call."""
    from ray_tpu._private import worker as worker_mod

    core = worker_mod.global_worker()
    t0 = time.perf_counter()
    names = []
    for _ in range(n):
        names = core._run(core._gcs_call("GetMetricsHistory", {}))["names"]
    names_ms = (time.perf_counter() - t0) / n * 1e3
    series_ms = 0.0
    if names:
        target = next((x for x in names if "lease_queue" in x), names[0])
        t0 = time.perf_counter()
        for _ in range(n):
            core._run(core._gcs_call(
                "GetMetricsHistory", {"name": target, "tier": "raw"}))
        series_ms = (time.perf_counter() - t0) / n * 1e3
    return {"history_scrape_ms_names": names_ms,
            "history_scrape_ms_series": series_ms,
            "history_names_recorded": len(names)}


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="")
    parser.add_argument("--tasks", type=int, default=200)
    parser.add_argument("--rounds", type=int, default=3)
    args = parser.parse_args(argv)

    # fast history sampling so the scrape bench has points to serve
    os.environ.setdefault("RAY_TPU_METRICS_HISTORY_INTERVAL_S", "0.5")
    os.environ.setdefault("RAY_TPU_METRICS_FLUSH_INTERVAL_S", "2.0")

    import ray_tpu
    from ray_tpu._private import task_events
    from ray_tpu.util import tracing

    ray_tpu.init(num_cpus=4)
    out = {}

    def _goodput(on: bool):
        # config env is read per-access, so this flips the ledger hooks
        # (region timers, compile watch, flush payload) live in-process
        os.environ["RAY_TPU_GOODPUT_ENABLED"] = "1" if on else "0"

    def _off():
        task_events.set_enabled(False)
        tracing._enabled = False
        _goodput(False)

    def _events():
        # the DEFAULT production config: task events + goodput ledger on
        task_events.set_enabled(True)
        tracing._enabled = False
        _goodput(True)

    def _events_nogoodput():
        task_events.set_enabled(True)
        tracing._enabled = False
        _goodput(False)

    def _traced():
        task_events.set_enabled(True)
        tracing._enabled = True
        _goodput(True)

    best = _bench_submission_configs(
        ray_tpu,
        [("disabled", _off), ("events", _events),
         ("events_nogoodput", _events_nogoodput), ("traced", _traced)],
        args.rounds, args.tasks)
    out["submit_us_disabled"] = best["disabled"]
    out["submit_us_events"] = best["events"]
    out["submit_us_events_nogoodput"] = best["events_nogoodput"]
    out["submit_us_traced"] = best["traced"]

    out["events_delta_pct"] = 100.0 * (
        out["submit_us_events"] / out["submit_us_disabled"] - 1.0)
    out["traced_delta_pct"] = 100.0 * (
        out["submit_us_traced"] / out["submit_us_disabled"] - 1.0)
    # goodput-ledger cost on the no-op task path: default config (ledger
    # on) vs the same config with only the ledger off
    out["goodput_delta_pct"] = 100.0 * (
        out["submit_us_events"] / out["submit_us_events_nogoodput"] - 1.0)

    out["span_record_per_s"] = _bench_span_record()
    out["event_record_us"] = _bench_event_record()
    out["event_flush_us_per_task"] = _bench_event_flush()

    # hot-path built-in spans, three configs per path:
    #   disabled — RAY_TPU_TASK_EVENTS=0, tracing off (nothing recorded)
    #   events   — the DEFAULT production config: task events + always-on
    #              histograms + built-in span instrumentation present
    #              (profile() short-circuits; this is what every user pays)
    #   traced   — full span COLLECTION on (diagnostic mode: every span
    #              recorded + shipped to the GCS trace table)
    hot_configs = [("disabled", _off), ("events", _events),
                   ("events_nogoodput", _events_nogoodput),
                   ("traced", _traced)]
    try:
        train = _bench_train_step(hot_configs)
        for name, us in train.items():
            out[f"train_step_us_{name}"] = us
        out["train_step_delta_pct"] = 100.0 * (
            train["events"] / train["disabled"] - 1.0)
        out["train_step_traced_delta_pct"] = 100.0 * (
            train["traced"] / train["disabled"] - 1.0)
        # goodput-ledger cost on the warm train step (region timers + a
        # compile-watch key per step; ledger on vs only the ledger off)
        out["train_step_goodput_delta_pct"] = 100.0 * (
            train["events"] / train["events_nogoodput"] - 1.0)
    except Exception as e:  # no jax/flax in this env: skip, don't sink
        out["train_step_error"] = f"{type(e).__name__}: {e}"
    serve_lat = _bench_serve_request(ray_tpu, hot_configs)
    for name, us in serve_lat.items():
        out[f"serve_request_us_{name}"] = us
    out["serve_request_delta_pct"] = 100.0 * (
        serve_lat["events"] / serve_lat["disabled"] - 1.0)
    out["serve_request_traced_delta_pct"] = 100.0 * (
        serve_lat["traced"] / serve_lat["disabled"] - 1.0)

    # THE acceptance bar: end-to-end overhead of the default always-on
    # config on the microbench task-throughput path vs events-disabled
    out["hot_path_span_overhead_pct"] = out["events_delta_pct"]

    # metrics-history scrape cost (the ring has been sampling all along)
    _events()
    out.update(_bench_history_scrape())

    tracing._enabled = None
    task_events.set_enabled(None)
    os.environ.pop("RAY_TPU_GOODPUT_ENABLED", None)
    ray_tpu.shutdown()

    print(json.dumps(out, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    return out


if __name__ == "__main__":
    main()
