#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives both of the program's device paths once, at the full width of
``CONFIGS["1b"]``, each through the entry point users call:

  train:  ray_tpu.init() -> JaxTrainer -> controller actor -> worker group ->
          ONE worker process that owns the chip -> TrainStepBundle
  serve:  serve.run(build_llm_deployment(...)) -> replica actor holding
          ``num_tpus=1`` -> JaxLLMEngine, asked over the HTTP proxy

This driver process never initialises a JAX backend: a chip belongs to one
process at a time, so the only processes that touch it are the train worker
and then the serve replica, one after the other. Every device fact printed
here was read inside those processes.

Output: one JSON object per line; the LAST line is the verdict
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
``"ok": true`` is printed only when every phase ran on a TPU and passed; any
failure raises, so the script exits non-zero without a verdict.

    python chip_smoke.py              # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4    # four chips: the sharded-update DP step
                                      # and its one-chip comparison, no other
                                      # phase (run by hand; needs 4 chips)
    python chip_smoke.py --rehearse   # CPU rehearsal of the control flow at
                                      # toy size; never prints "ok": true
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request


T_START = time.monotonic()
# a run with no verdict by then is stopped (exit 124): the whole script has
# 1200 s, compilation included
DEADLINE_S = 1100.0


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def note(msg: str) -> None:
    """Progress, on stderr: stdout carries JSON lines only."""
    print(f"[chip_smoke +{time.monotonic() - T_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


# ---------------------------------------------------------------------------
# code that runs INSIDE the train worker (the process that owns the chips)
# ---------------------------------------------------------------------------


def _timed_steps(bundle, params, opt_state, batch, n, barrier):
    """n steps on one fixed batch; the clock stops at ``barrier(out)``."""
    losses = []
    t0 = time.perf_counter()
    for _ in range(n):
        params, opt_state, loss = bundle.step(params, opt_state, batch)
        losses.append(loss)
    barrier((params, opt_state, loss))
    dt = (time.perf_counter() - t0) / n
    return params, opt_state, [float(x) for x in losses], dt


def train_loop(cfg: dict) -> None:
    """One chip: the fused 1b train step, 2 warm-up + 8 timed steps."""
    import dataclasses

    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models import CONFIGS
    from ray_tpu.ops.attention import _use_pallas_bwd
    from ray_tpu.parallel import TrainStepBundle, create_mesh, make_optimizer
    from ray_tpu.utils import device_facts

    facts = device_facts()
    mcfg = dataclasses.replace(CONFIGS[cfg["model"]], max_seq_len=cfg["seq"])
    mesh = create_mesh({"data": 1, "fsdp": 1, "seq": 1, "tensor": 1,
                        "expert": 1}, devices=jax.devices()[:1])
    bundle = TrainStepBundle(mcfg, mesh, optimizer=make_optimizer(
        learning_rate=3e-4, warmup_steps=2, total_steps=1000))
    t0 = time.perf_counter()
    params, opt_state = jax.block_until_ready(
        bundle.init(jax.random.PRNGKey(cfg["seed"])))
    init_s = time.perf_counter() - t0
    batch = bundle.make_batch(np.random.default_rng(cfg["seed"]),
                              cfg["batch"], cfg["seq"])

    # first step = trace + compile (or a compile-cache read) + one step
    t0 = time.perf_counter()
    params, opt_state, loss = jax.block_until_ready(
        bundle.step(params, opt_state, batch))
    first_step_s = time.perf_counter() - t0
    params, opt_state, loss = jax.block_until_ready(
        bundle.step(params, opt_state, batch))
    # the same program again, ahead of time, to read what the compiler
    # built: a persistent-cache hit when the first step just wrote it
    hlo = bundle._fused_step.lower(params, opt_state, batch).compile().as_text()

    params, opt_state, losses, step_s = _timed_steps(
        bundle, params, opt_state, batch, cfg["steps"], jax.block_until_ready)
    # the old claim: is block_until_ready a completion barrier here? the
    # same steps again, the clock stopped by a host readback of the loss
    params, opt_state, losses_rb, step_readback_s = _timed_steps(
        bundle, params, opt_state, batch, cfg["steps"],
        lambda out: float(out[2]))
    after = device_facts()
    train.report({
        **facts,
        "compile_cache_entries_after": after["compile_cache_entries"],
        "init_s": init_s, "first_step_s": first_step_s,
        "step_s_block_until_ready": step_s,
        "step_s_loss_readback": step_readback_s,
        "tokens_per_step": cfg["batch"] * cfg["seq"],
        "losses": losses, "losses_readback_pass": losses_rb,
        "tpu_custom_calls": hlo.count("tpu_custom_call"),
        "attention_backward": (
            "pallas" if _use_pallas_bwd(mcfg.head_dim, cfg["seq"])
            else "reference_attention"),
        "peak_bytes_in_use": after["peak_bytes_in_use"],
    })


def train_loop_dp4(cfg: dict) -> None:
    """Four chips in ONE worker: the fused step on chip 0 against the
    sharded-update step on a data=N mesh (same global batch, same initial
    parameters), then the sharded step at the shape a user would run."""
    import dataclasses
    import gc

    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models import CONFIGS
    from ray_tpu.parallel import TrainStepBundle, create_mesh, make_optimizer
    from ray_tpu.utils import compile_cache_entries, device_facts

    facts = device_facts()
    devs = jax.devices()
    n = len(devs)
    mcfg = dataclasses.replace(CONFIGS[cfg["model"]], max_seq_len=cfg["seq"])
    opt_kw = dict(learning_rate=3e-4, warmup_steps=2, total_steps=1000)
    ones = {"fsdp": 1, "seq": 1, "tensor": 1, "expert": 1}
    key = jax.random.PRNGKey(cfg["seed"])
    rng = np.random.default_rng(cfg["seed"])
    tokens = rng.integers(0, mcfg.vocab_size, (cfg["batch"], cfg["seq"] + 1),
                          dtype=np.int32)

    def batch_on(bundle, toks):
        b = {"tokens": toks[:, :-1], "targets": toks[:, 1:],
             "mask": np.ones((toks.shape[0], toks.shape[1] - 1), np.float32)}
        return {k: jax.device_put(v, bundle.batch_sharding)
                for k, v in b.items()}

    # reference: the fused step on a one-device mesh (chip 0)
    ref = TrainStepBundle(
        mcfg, create_mesh({"data": 1, **ones}, devices=devs[:1]),
        optimizer=make_optimizer(**opt_kw))
    params, opt_state = ref.init(key)
    batch = batch_on(ref, tokens)
    ref_losses = []
    for _ in range(cfg["parity_steps"]):
        params, opt_state, loss = ref.step(params, opt_state, batch)
        ref_losses.append(float(loss))
    del params, opt_state, batch, ref, loss
    gc.collect()

    # under test: sharded update on data=N, per-chip batch = global / N
    bundle = TrainStepBundle(
        mcfg, create_mesh({"data": n, **ones}, devices=devs),
        shard_update=True,
        optimizer_factory=lambda spec_fn: make_optimizer(
            clip_spec_fn=spec_fn, **opt_kw))
    t0 = time.perf_counter()
    params, opt_state = jax.block_until_ready(bundle.init_sharded(key))
    init_s = time.perf_counter() - t0
    bytes_after_init = [(d.memory_stats() or {}).get("bytes_in_use")
                        for d in devs]
    opt_bytes_per_replica = bundle.opt_state_bytes_per_replica(opt_state)
    opt_bytes_total = bundle.opt_state_bytes_total()
    batch = batch_on(bundle, tokens)
    t0 = time.perf_counter()
    dp_losses = []
    for _ in range(cfg["parity_steps"]):
        params, opt_state, loss = bundle.step(params, opt_state, batch)
        dp_losses.append(float(loss))
    parity_s = time.perf_counter() - t0
    hlo = bundle._fused_step_sharded.lower(
        params, opt_state, batch).compile().as_text()

    # the shape a user would run: per-chip batch = cfg["batch"]
    big = rng.integers(0, mcfg.vocab_size,
                       (cfg["batch"] * n, cfg["seq"] + 1), dtype=np.int32)
    batch = batch_on(bundle, big)
    t0 = time.perf_counter()
    params, opt_state, loss = jax.block_until_ready(
        bundle.step(params, opt_state, batch))
    first_step_s = time.perf_counter() - t0
    params, opt_state, losses, step_s = _timed_steps(
        bundle, params, opt_state, batch, cfg["steps"], jax.block_until_ready)
    train.report({
        **facts,
        "compile_cache_entries_after": compile_cache_entries(),
        "ref_losses_1chip": ref_losses, "dp_losses": dp_losses,
        "init_s": init_s, "parity_s": parity_s,
        "first_step_s": first_step_s, "step_s_block_until_ready": step_s,
        "tokens_per_step": cfg["batch"] * n * cfg["seq"], "losses": losses,
        "opt_state_bytes_per_replica": opt_bytes_per_replica,
        "opt_state_bytes_total": opt_bytes_total,
        "bytes_in_use_after_sharded_init": bytes_after_init,
        "bytes_in_use_end": [(d.memory_stats() or {}).get("bytes_in_use")
                             for d in devs],
        "peak_bytes_in_use": [(d.memory_stats() or {}).get("peak_bytes_in_use")
                              for d in devs],
        "hlo_reduce_scatter": hlo.count("reduce-scatter"),
        "hlo_all_gather": hlo.count("all-gather"),
        "tpu_custom_calls": hlo.count("tpu_custom_call"),
    })


# ---------------------------------------------------------------------------
# driver side
# ---------------------------------------------------------------------------


def _node():
    import ray_tpu

    nodes = [n for n in ray_tpu.nodes() if n.get("alive", True)]
    check(len(nodes) == 1, f"one node expected, got {len(nodes)}")
    return nodes[0]


def _node_stats() -> dict:
    from ray_tpu.util.state import get_node_stats

    return get_node_stats(_node()["address"])


def _obtained(before: dict, after: dict) -> dict:
    """How the phase's workers were obtained, from the pool's counters."""
    d = {k: after["worker_pool"][k] - before["worker_pool"][k]
         for k in ("hits", "misses", "forks", "cold_spawns")}
    if d["cold_spawns"]:
        d["how"] = "cold spawn"
    elif d["misses"]:
        d["how"] = "zygote fork on a pool miss"
    else:
        d["how"] = "warm-pool hit"
    return d


def _pid_gone(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except FileNotFoundError:
        return True


def wait_chip_returned(holder_pid: int, total: float, deadline_s=60.0) -> dict:
    """The raylet must hand the TPU back only once its holder is gone.
    Polls both facts; a TPU that is leasable while the holder still lives
    is a runtime fault, and so is one that never comes back."""
    t0 = time.monotonic()
    gone_at = back_at = None
    while time.monotonic() - t0 < deadline_s:
        gone = _pid_gone(holder_pid)
        back = _node_stats()["available"].get("TPU", 0.0) >= total
        now = time.monotonic() - t0
        check(gone or not back,
              f"raylet leases the TPU again while its holder pid "
              f"{holder_pid} is still alive")
        if gone and gone_at is None:
            gone_at = now
        if back:
            back_at = now
            break
        time.sleep(0.05)
    check(back_at is not None,
          f"TPU not back in the raylet's pool {deadline_s}s after its holder "
          f"was stopped (pid {holder_pid} gone: {gone_at is not None})")
    return {"holder_pid": holder_pid, "holder_gone_after_s": gone_at,
            "tpu_available_after_s": back_at}


def run_train(args, sizes, loop, run_root) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    before = _node_stats()
    note(f"train: JaxTrainer.fit() with {loop.__name__}")
    t0 = time.perf_counter()
    result = JaxTrainer(
        loop, train_loop_config=dict(sizes, seed=args.seed),
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     chips_per_worker=args.chips),
        run_config=RunConfig(name="chip_smoke", storage_path=run_root),
    ).fit()
    m = dict(result.metrics)
    m["fit_wall_s"] = time.perf_counter() - t0
    m["worker_obtained"] = _obtained(before, _node_stats())
    return m


def check_train_common(m: dict, want_chips: int) -> None:
    import math

    check(m["pid"] != os.getpid(), "train ran in the driver process")
    check(m["device"]["count"] == want_chips,
          f"worker saw {m['device']['count']} devices, wanted {want_chips}")
    check(all(math.isfinite(x) for x in m["losses"]), "non-finite loss")
    check(m["losses"][-1] < m["losses"][0],
          f"loss did not fall: {m['losses'][0]} -> {m['losses'][-1]}")


def phase_train(args, sizes, run_root) -> dict:
    m = run_train(args, sizes, train_loop, run_root)
    emit(phase="train", model=sizes["model"], batch=sizes["batch"],
         seq=sizes["seq"], **m)
    check_train_common(m, 1)
    check(len(m["losses"]) == sizes["steps"], "wrong number of timed steps")
    if m["device"]["platform"] == "tpu":
        check(m["tpu_custom_calls"] > 0,
              "no tpu_custom_call in the compiled train step: attention "
              "'auto' did not pick the flash kernel")
    return m


def phase_train_dp4(args, sizes, run_root) -> dict:
    m = run_train(args, sizes, train_loop_dp4, run_root)
    emit(phase="train_dp_sharded_update", model=sizes["model"],
         per_chip_batch=sizes["batch"], seq=sizes["seq"], **m)
    check_train_common(m, args.chips)
    for a, b in zip(m["ref_losses_1chip"], m["dp_losses"]):
        check(abs(a - b) <= 1e-2 * abs(a),
              f"fused 1-chip vs sharded dp losses disagree: "
              f"{m['ref_losses_1chip']} vs {m['dp_losses']}")
    share = m["opt_state_bytes_per_replica"] / m["opt_state_bytes_total"]
    check(abs(share - 1.0 / args.chips) < 0.05,
          f"optimizer state share per replica is {share:.3f}, not "
          f"1/{args.chips}")
    used = m["bytes_in_use_after_sharded_init"]
    if all(u is not None for u in used):
        check(max(used) < 1.25 * min(used),
              f"state is not spread evenly over the chips: {used}")
    if m["device"]["platform"] == "tpu":  # the CPU backend spells them otherwise
        check(m["hlo_reduce_scatter"] > 0 and m["hlo_all_gather"] > 0,
              "compiled sharded step lacks reduce-scatter / all-gather")
        check(m["tpu_custom_calls"] > 0,
              "no tpu_custom_call in the compiled sharded step")
    return m


def _post(port: int, name: str, body: dict):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/{name}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(req, timeout=600) as resp:
            status, payload = resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        status, payload = e.code, json.loads(e.read())
    return status, payload, time.perf_counter() - t0


def phase_serve(args, sizes) -> dict:
    import random

    import ray_tpu
    from ray_tpu.llm import LLMConfig, build_llm_deployment
    from ray_tpu.llm.config import EngineConfig
    from ray_tpu.models.transformer import CONFIGS
    from ray_tpu.serve import api as serve_api

    vocab = CONFIGS[sizes["model"]].vocab_size
    name = "llm"
    before = _node_stats()
    note("serve: serve.run(build_llm_deployment(...))")
    t0 = time.perf_counter()
    handle = serve_api.run(build_llm_deployment(
        LLMConfig(model_id=sizes["model"], seed=args.seed,
                  engine_config=EngineConfig(
                      max_num_seqs=sizes["max_num_seqs"],
                      max_model_len=sizes["max_model_len"]),
                  ray_actor_options={"num_cpus": 1.0, "num_tpus": 1}),
        name=name))
    info = ray_tpu.get(
        handle.options(method_name="device_info").remote(), timeout=600)
    replica_up_s = time.perf_counter() - t0
    note(f"serve: replica pid {info['pid']} up on "
         f"{info['device']['platform']}")
    obtained = _obtained(before, _node_stats())
    port = serve_api.start_http_proxy()

    rnd = random.Random(args.seed)
    alphabet = "abcdefghijklmnopqrstuvwxyz ,."

    def prompt(n_tokens: int) -> str:  # byte tokenizer: BOS + one per char
        return "".join(rnd.choice(alphabet) for _ in range(n_tokens - 1))

    def body(p: str) -> dict:
        return {"prompt": p, "max_tokens": sizes["max_tokens"],
                "temperature": 0.0}

    # one request per prefill bucket the prompts below will hit, through the
    # deployment handle (no proxy timeout in the way): first-use compiles
    warmup_s = []
    for n in sizes["warmup_lens"]:
        t0 = time.perf_counter()
        ray_tpu.get(handle.remote(body(prompt(n))), timeout=900)
        warmup_s.append(time.perf_counter() - t0)
        note(f"serve: first {n}-token prompt took {warmup_s[-1]:.1f}s")

    # 4 HTTP completions in flight together, then one prompt twice in a row
    concurrent_prompts = [prompt(n) for n in sizes["concurrent_lens"]]
    with concurrent.futures.ThreadPoolExecutor(len(concurrent_prompts)) as ex:
        answers = list(ex.map(lambda p: _post(port, name, body(p)),
                              concurrent_prompts))
    repeat = prompt(sizes["repeat_len"])
    answers += [_post(port, name, body(repeat)) for _ in range(2)]

    token_ids = []
    for status, payload, _ in answers:
        check(status == 200, f"HTTP {status}: {str(payload)[:2000]}")
        ids = payload["result"]["choices"][0]["token_ids"]
        check(len(ids) == sizes["max_tokens"],
              f"asked for {sizes['max_tokens']} tokens, got {len(ids)}")
        check(all(0 <= t < vocab for t in ids), "token id outside the vocab")
        token_ids.append(ids)
    check(token_ids[-1] == token_ids[-2],
          "the same prompt sent twice gave different tokens")
    check(info["pid"] != os.getpid(), "engine ran in the driver process")
    after = ray_tpu.get(
        handle.options(method_name="device_info").remote(), timeout=60)
    m = {**info,
         "compile_cache_entries_after": after["compile_cache_entries"],
         "peak_bytes_in_use": after["peak_bytes_in_use"],
         "replica_up_s": replica_up_s, "worker_obtained": obtained,
         "warmup_prompt_tokens": sizes["warmup_lens"],
         "warmup_request_s": warmup_s,
         "concurrent_prompt_tokens": sizes["concurrent_lens"],
         "concurrent_request_s": [a[2] for a in answers[:-2]],
         "repeat_prompt_tokens": sizes["repeat_len"],
         "repeat_request_s": [a[2] for a in answers[-2:]],
         "http_200": len(answers), "max_tokens": sizes["max_tokens"],
         "repeat_tokens_equal": True}
    emit(phase="serve", model=sizes["model"],
         max_num_seqs=sizes["max_num_seqs"],
         max_model_len=sizes["max_model_len"], **m)
    serve_api.shutdown()
    return m


def _dump_logs(log_dir: str, tail: int = 40) -> None:
    """On failure: the end of every worker/raylet log, to stderr. The chip
    machine is thrown away after the run; this is all that is left of it."""
    for name in sorted(os.listdir(log_dir)):
        with open(os.path.join(log_dir, name), errors="replace") as f:
            lines = f.readlines()[-tail:]
        if lines:
            print(f"---- {name} (last {len(lines)} lines)\n"
                  + "".join(lines), file=sys.stderr, flush=True)


FULL = {
    "train": {"model": "1b", "batch": 4, "seq": 2048, "steps": 8,
              "parity_steps": 3},
    "serve": {"model": "1b", "max_num_seqs": 8, "max_model_len": 2048,
              "max_tokens": 32, "warmup_lens": [200, 400, 600],
              "concurrent_lens": [300, 420, 560, 600], "repeat_len": 240},
}
# --rehearse: same control flow at toy size ("tiny": flash-shaped head_dim,
# seq a multiple of 128), for a CPU sandbox
TOY = {
    "train": {"model": "tiny", "batch": 4, "seq": 128, "steps": 8,
              "parity_steps": 3},
    "serve": {"model": "tiny", "max_num_seqs": 4, "max_model_len": 256,
              "max_tokens": 8, "warmup_lens": [20, 40, 100],
              "concurrent_lens": [30, 42, 56, 100], "repeat_len": 24},
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes, CPU allowed; never prints \"ok\": true")
    args = ap.parse_args()
    sizes = TOY if args.rehearse else FULL

    cache_dir_from_outside = "JAX_COMPILATION_CACHE_DIR" in os.environ
    import ray_tpu
    from ray_tpu.utils import compile_cache_dir

    if args.rehearse:
        # no chip here: tell the raylet to advertise what the run asks for
        os.environ.setdefault("RAY_TPU_CHIPS", str(args.chips))
        if args.chips > 1:
            os.environ.setdefault(
                "XLA_FLAGS",
                f"--xla_force_host_platform_device_count={args.chips}")
    run_root = tempfile.mkdtemp(prefix="chip_smoke_")
    worker = ray_tpu.init(num_cpus=max(4, os.cpu_count() or 1))
    log_dir = worker.node_supervisor.log_dir

    def out_of_time():
        # a hung phase must not outlive the run's limit or leave the
        # cluster's processes behind: say where it hung, stop them, leave
        note(f"no verdict after {DEADLINE_S}s: giving up")
        _dump_logs(log_dir)
        worker.node_supervisor.stop()
        os._exit(124)

    watchdog = threading.Timer(DEADLINE_S, out_of_time)
    watchdog.daemon = True
    watchdog.start()
    try:
        node = _node()
        total_tpu = node["total_resources"].get("TPU", 0.0)
        stats = _node_stats()
        emit(phase="cluster", driver_pid=os.getpid(),
             total_resources=node["total_resources"],
             labels=node.get("labels", {}),
             object_store_backend=stats["store"]["backend"],
             compile_cache_dir=compile_cache_dir(),
             JAX_COMPILATION_CACHE_DIR_was_set=cache_dir_from_outside,
             seed=args.seed, chips=args.chips, rehearse=args.rehearse)
        check(total_tpu >= args.chips,
              f"the raylet found {total_tpu} TPU chips on this host, the "
              f"run needs {args.chips}: {node['total_resources']}")
        # after each phase: its process gone and the chips back with the
        # raylet — before the next phase asks for them, and before this
        # script ends (whoever runs next must find the chips free)
        if args.chips == 1:
            train = phase_train(args, sizes["train"], run_root)
            emit(phase="chip_handoff", after="train",
                 **wait_chip_returned(train["pid"], total_tpu))
            serve = phase_serve(args, sizes["serve"])
            emit(phase="chip_handoff", after="serve",
                 **wait_chip_returned(serve["pid"], total_tpu))
            check(serve["compile_cache_dir"] == train["compile_cache_dir"],
                  "train worker and serve replica cache compiles in "
                  "different directories")
            check(serve["device"] == train["device"],
                  "train worker and serve replica saw different devices")
        else:
            train = phase_train_dp4(args, sizes["train"], run_root)
            emit(phase="chip_handoff", after="train",
                 **wait_chip_returned(train["pid"], total_tpu))
    except BaseException:
        _dump_logs(log_dir)  # the failure still propagates
        raise
    finally:
        note("shutting the cluster down")
        ray_tpu.shutdown()
        watchdog.cancel()
        shutil.rmtree(run_root, ignore_errors=True)

    jax_mod = sys.modules.get("jax")
    if jax_mod is not None:
        from jax._src import xla_bridge

        check(not xla_bridge.backends_are_initialized(),
              "the driver process initialised a JAX backend")
    device = train["device"]
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}),
              flush=True)
        return 1
    check(device["platform"] == "tpu",
          f"the phases ran on {device['platform']!r}, not on a TPU")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
