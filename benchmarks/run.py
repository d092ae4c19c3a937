#!/usr/bin/env python3
"""Runs one cell of ``BENCHMARK.json`` once, in a fresh one-node cluster.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's job goes through the runtime's own entry point: ``JaxTrainer.fit``
with the loop function of ``benchmarks/jobs/train.py``, or ``serve.run`` of
the deployment in ``benchmarks/jobs/serve.py`` asked over the HTTP proxy. This
parent process never initialises a JAX backend (a chip belongs to one process):
every device fact it prints was read inside the worker or the replica. It
waits until the chip's holder is gone and the raylet has its chips back, then
prints one JSON object as the LAST line of stdout:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}

``--trace 0``: the cell's end-to-end metrics. ``--trace 1``: its per-layer
metrics, from a profiler trace of a few seconds taken inside the window by the
process that holds the chip. Earlier stdout lines are JSON notes (sample
counts, generator lateness, the checks); progress goes to stderr.

``--rehearse`` runs the same control flow on the CPU with the toy cells of
``benchmarks/rehearsal/cells.json``; it prints no ``metrics`` and exits 1.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import threading
import time

T_PROCESS_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DEADLINE_S = 1150.0     # a run with no verdict by then stops itself (exit 124)


def note(msg: str) -> None:
    print(f"[bench +{time.time() - T_PROCESS_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


class Failed(Exception):
    """The run cannot give a result; exit non-zero and print none."""


# ---------------------------------------------------------------------------
# the two kinds of job
# ---------------------------------------------------------------------------


def run_train(cell, args, cluster, out_dir):
    from benchmarks import traffic
    from benchmarks.jobs.train import train_loop
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    conf = cell.config
    shape = traffic.train_shape(cell.mix, conf["job"])
    loop_cfg = {"config": conf, "shape": shape, "mix": cell.mix,
                "seed": args.seed, "seconds": args.seconds,
                "trace": bool(args.trace), "rehearse": args.rehearse,
                "out_dir": out_dir, "keep_trace_as": args.keep_trace}
    note(f"train: JaxTrainer.fit(), {shape} on {cell.chips} chip(s)")
    fit_called = time.time()
    result = JaxTrainer(
        train_loop, train_loop_config=loop_cfg,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     chips_per_worker=cell.chips),
        run_config=RunConfig(name="bench", storage_path=out_dir + "/run"),
    ).fit()
    m = dict(result.metrics)
    cluster.holder_pid = m["pid"]
    emit(note="train", **{k: m[k] for k in (
        "steps", "tokens_per_step", "window_s", "loss_first", "loss_last",
        "loss_reference", "loss_rel_err", "loss_tol", "gradient",
        "compiles_in_window", "compiles_total", "init_s", "gradient_s",
        "reference_s", "first_step_s", "attention_backward",
        "compile_cache_entries", "traced_steps", "step_executable_bytes",
        "memory_analysis_s", "memory_error")})
    if m["stall"]:
        # the window lost time to something that is not the step: say where,
        # and keep what the raylet, the GCS and the worker logged around it
        emit(note="stall", **m["stall"])
        cluster.dump_logs(tail=200)
    correct = bool(m["loss_ok"] and m["gradient"]["ok"] and m["losses_finite"]
                   and m["compiles_in_window"] == 0 and m["steps"] > 0)
    setup_s = m["window_start_wall"] - T_PROCESS_START
    ctx = {"trace": m["trace"],
           "spans": {"worker_start_s": m["worker_entered_at"] - fit_called},
           "counters": {},
           "facts": {"tokens_per_step": m["tokens_per_step"], "chips": cell.chips,
                     **shape}}
    e2e = {"train_tokens_per_s": m["tokens_per_s"], "setup_s": setup_s}
    return {"correct": correct, "attempted": m["steps"],
            "failed": 0 if m["losses_finite"] else m["steps"],
            "device": m["device"], "e2e": e2e, "ctx": ctx,
            "trace_error": m["trace_error"]}


def deploy_serve(cell, args, cluster, out_dir):
    """``serve.run`` of the cell's deployment, the HTTP proxy, one warm-up
    request per reachable prefill bucket, the reference check."""
    import ray_tpu
    from benchmarks import traffic
    from benchmarks.jobs import serve as sjob
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.config import EngineConfig
    from ray_tpu.serve import api as serve_api

    conf, mix = cell.config, cell.mix
    job = conf["job"]
    eng = EngineConfig(**job["engine"])
    name = "llm"
    # the replica puts the configuration's widths in (``BenchLLMServer``)
    llm = LLMConfig(model_id="tiny", seed=args.seed % (2 ** 32),
                    engine_config=eng, num_replicas=job["num_replicas"],
                    ray_actor_options={"num_cpus": 1.0, "num_tpus": cell.chips})
    bench = {"trace": bool(args.trace), "out_dir": out_dir, "config": conf,
             "keep_trace_as": args.keep_trace}
    # the deployment options of build_llm_deployment (which hard-codes the
    # class), with the benchmark's subclass in LLMServer's place
    app = serve_api.deployment(
        sjob.BenchLLMServer, name=name, num_replicas=llm.num_replicas,
        max_ongoing_requests=eng.max_num_seqs * 2,
        ray_actor_options=dict(llm.ray_actor_options)).bind(llm, None, bench)
    note("serve: serve.run(...)")
    t_run = time.time()
    handle = serve_api.run(app)
    port = serve_api.start_http_proxy()
    url = f"http://127.0.0.1:{port}/{name}"

    def call(method, *a, timeout=900):
        return ray_tpu.get(
            handle.options(method_name=method).remote(*a), timeout=timeout)

    # warm-up over HTTP, one request per prefill bucket this mix reaches; a
    # cold first request can outlast the proxy's 120 s: ask again
    warm = traffic.serve_warmup_lengths(mix, eng.prefill_bucket_min,
                                        eng.max_model_len)
    import random
    wrng = random.Random(args.seed)
    replica_up_s = None
    warm_s = []
    for n in warm:
        body = {"prompt": [wrng.randrange(conf["vocab_size"]) for _ in range(n)],
                "max_tokens": 4, "temperature": 0.0}
        t0 = time.time()
        for attempt in range(8):
            status, payload = sjob.post_once(url, body)
            if status == 200:
                break
            note(f"serve: warm-up {n} tokens: HTTP {status}, again "
                 f"({str(payload)[:200]})")
        else:
            raise Failed(f"warm-up request of {n} tokens never answered")
        if replica_up_s is None:
            replica_up_s = time.time() - t_run
        warm_s.append(time.time() - t0)
        note(f"serve: first {n}-token prompt took {warm_s[-1]:.1f}s")
    facts = call("device_info")
    cluster.holder_pid = facts["pid"]
    if not args.rehearse and facts["device"]["platform"] != "tpu":
        raise Failed(f"the replica sees {facts['device']['platform']!r}, not a TPU")
    check = call("reference_check", args.seed, 200, 4)
    emit(note="reference_check", **check)
    return {"url": url, "call": call, "replica_up_s": replica_up_s,
            "warm": warm, "warm_s": warm_s, "check": check,
            "shapes": {"max_num_seqs": eng.max_num_seqs,
                       "prefill_buckets": traffic.serve_prefill_buckets(
                           mix, eng.prefill_bucket_min, eng.max_model_len)},
            "shutdown": serve_api.shutdown}


def run_serve(cell, args, cluster, out_dir):
    from benchmarks import traffic
    from benchmarks.jobs import serve as sjob
    from benchmarks.trace.reduce import quantile

    conf, mix = cell.config, cell.mix
    d = deploy_serve(cell, args, cluster, out_dir)
    url, call, check = d["url"], d["call"], d["check"]
    replica_up_s, warm, warm_s = d["replica_up_s"], d["warm"], d["warm_s"]

    lead = float(mix.get("lead_s", 0.0))
    t0_wall = time.time() + lead + 1.0
    seconds = float(args.seconds)
    call("arm", t0_wall, t0_wall + seconds, 0.35 * seconds,
         min(4.0, 0.3 * seconds))
    reqs = traffic.serve_schedule(mix, args.seed, seconds, conf["vocab_size"])
    setup_s = t0_wall - T_PROCESS_START
    note(f"serve: offering {len(reqs)} requests at "
         f"{mix['arrival']['rate_per_s']}/s, window opens in "
         f"{t0_wall - time.time():.1f}s")
    end = mix.get("end", "drain")
    recs = sjob.offer_load(url, reqs, t0_wall, seconds, mix["temperature"],
                           end, float(mix.get("drain_s", 30.0)))
    open(os.path.join(out_dir, "finish"), "w").close()
    rep_path = os.path.join(out_dir, "replica.json")
    deadline = time.time() + 120
    while not os.path.exists(rep_path):
        if time.time() > deadline:
            raise Failed("the replica wrote no result")
        time.sleep(0.05)
    with open(rep_path) as f:
        rep = json.load(f)
    if "error" in rep:
        raise Failed("replica watcher failed: " + rep["error"])

    # -- the requests -----------------------------------------------------------
    vocab = conf["vocab_size"]

    def good(r):
        ids = r.get("token_ids")
        return (r["status"] == 200 and ids is not None
                and all(0 <= t < vocab for t in ids)
                and (len(ids) == r["max_tokens"]
                     or (r.get("finish_reason") == "stop"
                         and 0 < len(ids) < r["max_tokens"])))

    for r in recs:
        r["good"] = good(r)
    in_window = [r for r in recs if 0 <= r["due_s"] < seconds]
    if end == "drain":
        judged = in_window                       # unanswered = failed
    else:
        # the backlog at the window's end is by design: only what was answered
        # (rightly or wrongly) inside the window is judged
        judged = [r for r in recs
                  if r["status"] is not None and 0 <= r["done_s"] <= seconds]
    failed = [r for r in judged if not r["good"]]
    late = [r["sent_s"] - r["due_s"] for r in recs if "sent_s" in r]
    lat = [(r["done_s"] - r["due_s"]) if r["good"] else seconds
           for r in in_window]
    done_in = [r for r in recs if r["good"] and 0 <= r["done_s"] <= seconds]
    out_tokens = sum(len(r["token_ids"]) for r in done_in)
    e2e = {"setup_s": setup_s,
           "serve_tokens_per_s": out_tokens / seconds}
    if lat:
        e2e["request_p90_ms"] = quantile(lat, 0.9) * 1e3
    emit(note="requests", offered=len(recs), due_in_window=len(in_window),
         judged=len(judged), failed=len(failed),
         abandoned=sum(1 for r in recs if r["status"] is None),
         completed_in_window=len(done_in), output_tokens_in_window=out_tokens,
         request_p50_ms=(statistics.median(lat) * 1e3 if lat else None),
         request_p90_samples=len(lat),
         generator_late_ms_p50=statistics.median(late) * 1e3 if late else None,
         generator_late_ms_max=max(late) * 1e3 if late else None,
         first_errors=[r.get("error") for r in failed[:3]],
         warmup_lengths=warm, warmup_s=warm_s,
         replica={k: rep[k] for k in ("counters", "compiles_in_window",
                                      "compiles_total", "waiting_at_end",
                                      "active_at_end")})
    ctx = {"trace": rep.get("trace"), "spans": {"replica_up_s": replica_up_s},
           "counters": rep["counters"],
           "facts": {"chips": cell.chips, **d["shapes"]}}
    correct = bool(check["ok"] and not failed and judged
                   and rep["compiles_in_window"] == 0)
    d["shutdown"]()
    return {"correct": correct, "attempted": len(judged), "failed": len(failed),
            "device": rep["device"], "e2e": e2e, "ctx": ctx,
            "trace_error": rep.get("trace_error")}


# ---------------------------------------------------------------------------
# the cluster (driver pattern of chip_smoke.py: the parent stays off JAX and
# leaves only when the chip's holder is gone)
# ---------------------------------------------------------------------------


class Cluster:
    def __init__(self, chips: int, rehearse: bool):
        import ray_tpu

        if rehearse:
            os.environ.setdefault("RAY_TPU_CHIPS", str(chips))
            os.environ.setdefault(
                "XLA_FLAGS", f"--xla_force_host_platform_device_count={chips}")
        self.ray = ray_tpu
        self.worker = ray_tpu.init(num_cpus=max(4, os.cpu_count() or 1))
        self.log_dir = self.worker.node_supervisor.log_dir
        self.holder_pid = None
        nodes = [n for n in ray_tpu.nodes() if n.get("alive", True)]
        if len(nodes) != 1:
            raise Failed(f"one node expected, got {len(nodes)}")
        self.node = nodes[0]
        self.total_tpu = self.node["total_resources"].get("TPU", 0.0)
        if self.total_tpu < chips:
            raise Failed(f"the raylet found {self.total_tpu} TPU chips, the "
                         f"cell needs {chips}: {self.node['total_resources']}")

    def _available_tpu(self) -> float:
        from ray_tpu.util.state import get_node_stats

        return get_node_stats(self.node["address"])["available"].get("TPU", 0.0)

    def wait_chip_returned(self, deadline_s: float = 90.0) -> float:
        """Until the holder's process is gone and the raylet can lease every
        chip again: whoever runs next must find the chips free."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            gone = self.holder_pid is None or _pid_gone(self.holder_pid)
            if gone and self._available_tpu() >= self.total_tpu:
                return time.monotonic() - t0
            time.sleep(0.05)
        raise Failed(f"chips not back {deadline_s}s after the job ended")

    def dump_logs(self, tail: int = 40) -> None:
        for name in sorted(os.listdir(self.log_dir)):
            with open(os.path.join(self.log_dir, name), errors="replace") as f:
                lines = f.readlines()[-tail:]
            if lines:
                print(f"---- {name} (last {len(lines)} lines)\n"
                      + "".join(lines), file=sys.stderr, flush=True)


def _pid_gone(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except FileNotFoundError:
        return True


# ---------------------------------------------------------------------------


def set_environment(rehearse: bool) -> None:
    """Before ``ray_tpu`` is imported, so that the raylet hands it to every
    worker: the program's compile cache at a fixed path inside this checkout
    (the path is part of the cache's key), a size limit from outside lifted
    (an LRU smaller than a cell's programs evicts them between runs), and
    this checkout on the workers' import path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["PYTHONPATH"] = REPO + os.pathsep + os.environ.get("PYTHONPATH", "")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy cells on the CPU; prints no metrics, exits 1")
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="with --trace 1: move the .xplane.pb here")
    args = ap.parse_args()

    bench_file = (os.path.join(HERE, "rehearsal", "cells.json") if args.rehearse
                  else os.path.join(REPO, "BENCHMARK.json"))
    from benchmarks.registry import Cell, peak_for

    cell = Cell(args.workload, bench_file)
    if args.seconds is None:
        args.seconds = float(cell.benchmark["run_seconds"])
    if args.keep_trace:
        args.keep_trace = os.path.abspath(args.keep_trace)

    set_environment(args.rehearse)
    out_dir = os.path.join(REPO, ".bench_out", cell.name)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    cluster = Cluster(cell.chips, args.rehearse)

    def out_of_time():
        note(f"no verdict after {DEADLINE_S}s: giving up")
        cluster.dump_logs()
        cluster.worker.node_supervisor.stop()
        os._exit(124)

    watchdog = threading.Timer(DEADLINE_S, out_of_time)
    watchdog.daemon = True
    watchdog.start()
    try:
        kind = cell.config["job"]["kind"]
        r = (run_train if kind == "train" else run_serve)(
            cell, args, cluster, out_dir)
        handoff_s = cluster.wait_chip_returned()
        emit(note="chip_handoff", holder_pid=cluster.holder_pid,
             returned_after_s=handoff_s)
    except BaseException:
        cluster.dump_logs()
        raise
    finally:
        note("shutting the cluster down")
        cluster.ray.shutdown()
        watchdog.cancel()
        shutil.rmtree(out_dir, ignore_errors=True)

    jax_mod = sys.modules.get("jax")
    if jax_mod is not None:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise Failed("the parent process initialised a JAX backend")

    device = r["device"]
    if args.rehearse:
        emit(rehearsal=True, correct=r["correct"], attempted=r["attempted"],
             failed=r["failed"], device=device,
             counts={"e2e_names": sorted(r["e2e"]),
                     "trace_read": r["ctx"]["trace"] is not None})
        return 1
    if device["platform"] != "tpu" or device["count"] != cell.chips:
        raise Failed(f"the job ran on {device}, the cell needs "
                     f"{cell.chips} TPU chip(s)")
    peak = peak_for(device["kind"])
    r["ctx"]["facts"].update(peak_flops_per_s=peak["bf16_flops_per_s"],
                             peak_hbm_bytes_per_s=peak["hbm_bytes_per_s"])

    line = {"correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"]}
    if args.trace:
        t = r["ctx"]["trace"]
        if t is None:
            raise Failed(f"traced run without a trace: {r['trace_error']}")
        line["metrics"] = cell.per_layer_values(r["ctx"])
        device = dict(device, busy_s=t["busy_s"], window_s=t["window_s"])
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
        emit(note="trace", **{k: t[k] for k in (
            "devices", "window_s", "busy_s", "collective_s",
            "exposed_collective_s", "modules", "annotations")},
             tokens_per_s=r["e2e"].get("train_tokens_per_s"))
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        missing = [n for n in units if n not in r["e2e"]]
        if missing:
            raise Failed(f"no value for end-to-end metric(s) {missing}")
        line["metrics"] = {n: {"value": r["e2e"][n], "unit": u}
                           for n, u in units.items()}
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Failed as e:
        note(f"FAILED: {e}")
        sys.exit(1)
