"""Serve autoscale plane tests (ray_tpu/serve/autoscale/).

Unit tier: the rate window (burst-blindness regression), the demand
policy (hysteresis / cooldown / SLO pressure), DRR fair-queue ordering
and bounds, consistent-hash ring stability, prefix-router accounting.

Integration tier (cluster fixture): sustained load bursts scale a
deployment up, the drain scales it down, nothing drops, scale events
land in the task plane; ingress admission sheds on a full tenant queue;
the prefix routing policy keeps a prompt prefix on one replica; the
bench_serve harness runs end to end in --smoke mode.
"""

import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.autoscale import (
    ConsistentHashRing,
    DeploymentMetricsWindow,
    FairQueue,
    LoadShedError,
    PolicyState,
    PrefixRouter,
    SLOConfig,
    decide,
)
from ray_tpu.serve.api import AutoscalingConfig


# ---------------------------------------------------------------------------
# unit: window + policy
# ---------------------------------------------------------------------------


def _stat(arrived=0, completed=0, execute_sum=0.0, execute_count=0,
          ongoing=0, peak=0, queue_samples=()):
    return {"arrived": arrived, "completed": completed,
            "execute_sum": execute_sum, "execute_count": execute_count,
            "ongoing": ongoing, "peak": peak,
            "queue_samples": list(queue_samples)}


def test_window_rates_from_counter_deltas():
    w = DeploymentMetricsWindow(window_s=10.0)
    w.observe([_stat()], now=100.0)
    w.observe([_stat(arrived=40, completed=40, execute_sum=8.0,
                     execute_count=40, queue_samples=[0.01, 0.5])],
              now=102.0)
    assert w.arrival_rate(102.0) == pytest.approx(20.0)
    assert w.completion_rate(102.0) == pytest.approx(20.0)
    assert w.execute_mean_s(102.0) == pytest.approx(0.2)
    assert w.queue_p99_s(102.0) == pytest.approx(0.5)


def test_window_burst_blindness_regression():
    """The PR 8 case, covered structurally: a burst that arrives AND fully
    drains between two polls leaves ongoing=0/peak small at both ticks —
    a point gauge sees nothing, the cumulative arrival counter prices it."""
    w = DeploymentMetricsWindow(window_s=10.0)
    w.observe([_stat()], now=10.0)
    # 100 requests came and went entirely between the two polls
    w.observe([_stat(arrived=100, completed=100, execute_sum=30.0,
                     execute_count=100, ongoing=0, peak=2)], now=11.0)
    assert w.arrival_rate(11.0) == pytest.approx(100.0)
    auto = AutoscalingConfig(min_replicas=1, max_replicas=8,
                             target_ongoing_requests=2.0,
                             upscale_delay_s=0.0, scale_cooldown_s=0.0)
    d = decide(w, current_target=1, config=auto, state=PolicyState(),
               now=11.0)
    # Little's law: 100/s x 0.3s = 30 concurrent -> 15 replicas, clamped
    assert d.direction == "up"
    assert d.want == 8


def test_window_counter_reset_clamped():
    """A replica death steps the cluster-summed cumulative counter DOWN;
    the rate must clamp at zero, not go negative."""
    w = DeploymentMetricsWindow(window_s=10.0)
    w.observe([_stat(arrived=500)], now=50.0)
    w.observe([_stat(arrived=120)], now=51.0)  # membership shrank
    assert w.arrival_rate(51.0) == 0.0


def test_policy_hysteresis_and_cooldown():
    auto = AutoscalingConfig(min_replicas=1, max_replicas=4,
                             target_ongoing_requests=2.0,
                             upscale_delay_s=0.0, downscale_delay_s=0.0,
                             hysteresis=0.1, scale_cooldown_s=5.0)
    st = PolicyState()

    def window_with_demand(concurrency, now):
        w = DeploymentMetricsWindow(window_s=10.0)
        w.observe([_stat()], now=now - 1.0)
        w.observe([_stat(arrived=int(concurrency * 10),
                         completed=int(concurrency * 10),
                         execute_sum=concurrency,
                         execute_count=int(concurrency * 10))], now=now)
        return w

    # demand 6 concurrency / target 2 -> 3 replicas: jump straight there
    d = decide(window_with_demand(6.0, 100.0), current_target=1,
               config=auto, state=st, now=100.0)
    assert (d.direction, d.want) == ("up", 3)
    # cooldown: pressure persists but the next action must wait
    d = decide(window_with_demand(8.0, 101.0), current_target=3,
               config=auto, state=st, now=101.0)
    assert d.direction == "hold"
    # hysteresis: demand 1.9 fits 2 replicas but NOT under the band below
    # (2-1)*(1-0.1)=0.9, so no release even after the cooldown
    d = decide(window_with_demand(1.9 * 2.0, 110.0), current_target=2,
               config=auto, state=st, now=110.0)
    assert d.direction == "hold"
    # true idle clears the band -> step down ONE replica
    d = decide(window_with_demand(0.2, 120.0), current_target=3,
               config=auto, state=st, now=120.0)
    assert (d.direction, d.want) == ("down", 2)


def test_policy_queue_slo_pressure():
    """Queue p99 over the registered target reads as up-pressure even when
    the rate math says capacity is sufficient."""
    auto = AutoscalingConfig(min_replicas=1, max_replicas=4,
                             target_ongoing_requests=2.0,
                             upscale_delay_s=0.0, scale_cooldown_s=0.0)
    w = DeploymentMetricsWindow(window_s=10.0)
    w.observe([_stat()], now=10.0)
    w.observe([_stat(arrived=10, completed=10, execute_sum=0.5,
                     execute_count=10, queue_samples=[2.0] * 8)], now=11.0)
    st = PolicyState()
    assert decide(w, current_target=1, config=auto, state=st, now=11.0
                  ).direction == "hold"  # demand alone is tiny
    d = decide(w, current_target=1, config=auto, state=PolicyState(),
               now=11.0, queue_target_s=0.5)
    assert d.direction == "up"
    assert "SLO" in d.reason


def test_autoscaling_config_backcompat_dict():
    # pre-PR dicts (no window/hysteresis/cooldown keys) must still parse
    cfg = AutoscalingConfig.from_dict({
        "min_replicas": 1, "max_replicas": 3,
        "target_ongoing_requests": 2.0, "upscale_delay_s": 0.5,
        "downscale_delay_s": 1.0})
    assert cfg.window_s == 10.0 and cfg.hysteresis == 0.1
    with pytest.raises(ValueError):
        AutoscalingConfig.from_dict({"max_replicaz": 2})
    with pytest.raises(ValueError):
        SLOConfig.from_dict({"ttft_target_s": 0.5, "bogus": 1})


# ---------------------------------------------------------------------------
# unit: fair queue + routing
# ---------------------------------------------------------------------------


def test_fair_queue_drr_weighted_ordering():
    q = FairQueue(max_depth_per_tenant=16, weights={"a": 2.0, "b": 1.0})
    for i in range(6):
        assert q.push("a", ("a", i))
        assert q.push("b", ("b", i))
    drained = [q.pop() for _ in range(12)]
    assert q.pop() is None
    # per-tenant FIFO preserved
    assert [i for t, i in drained if t == "a"] == list(range(6))
    assert [i for t, i in drained if t == "b"] == list(range(6))
    # weighted share: while both tenants are backlogged (the first 9
    # pops), tenant a (weight 2) drains ~2x tenant b
    first9 = [t for t, _ in drained[:9]]
    assert first9.count("a") == 6 and first9.count("b") == 3


def test_fair_queue_bounded_depth_sheds():
    q = FairQueue(max_depth_per_tenant=4)
    assert all(q.push("flood", i) for i in range(4))
    assert not q.push("flood", 99)  # full -> shed
    assert q.push("other", "x")  # another tenant is unaffected
    assert len(q) == 5


def test_consistent_ring_minimal_remap():
    class R:
        def __init__(self, h):
            self._actor_id = type("A", (), {"hex": lambda s, h=h: h})()

    reps = [R("aa"), R("bb"), R("cc"), R("dd")]
    ring = ConsistentHashRing(reps)
    before = {f"k{i}": ring.lookup(f"k{i}")._actor_id.hex()
              for i in range(400)}
    ring2 = ConsistentHashRing(reps[:3])  # "dd" left
    moved_non_victim = sum(
        1 for k, owner in before.items()
        if owner != "dd" and ring2.lookup(k)._actor_id.hex() != owner)
    assert moved_non_victim == 0  # only the victim's keys remap
    victim_keys = sum(1 for v in before.values() if v == "dd")
    assert 0 < victim_keys < 200  # ~1/4 of the space, not half


def test_prefix_router_key_and_hit_accounting():
    r = PrefixRouter("dep", prefix_len=8)
    assert r.key_of({"prompt": "abcdefghij-tail"}) == "abcdefgh"
    assert r.key_of("raw prompt string")[:3] == "raw"
    assert r.key_of({"messages": [{"role": "user"}]}) is not None
    assert r.key_of(12345) is None

    class R:
        def __init__(self, h):
            self._actor_id = type("A", (), {"hex": lambda s, h=h: h})()

    reps = [R("aa"), R("bb"), R("cc")]
    first = r.pick("warm-key", reps, version=1)
    for _ in range(5):  # repeat hits stay on the same replica
        assert r.pick("warm-key", reps, version=1) is first


# ---------------------------------------------------------------------------
# integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=6)
    yield ray_tpu
    try:
        serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


def test_sustained_load_scale_up_drain_down(cluster):
    """Burst -> rate window prices demand -> scale up; drain -> demand
    decays under the hysteresis band -> scale down; every request
    completes and the scale history + task-plane events record why."""
    from ray_tpu.serve import api as serve_api

    @serve.deployment(
        name="surge", max_ongoing_requests=32,
        autoscaling_config={"min_replicas": 1, "max_replicas": 3,
                            "target_ongoing_requests": 2.0,
                            "upscale_delay_s": 0.3,
                            "downscale_delay_s": 0.8,
                            "window_s": 3.0, "scale_cooldown_s": 0.3},
        ray_actor_options={"num_cpus": 0.25})
    class Surge:
        async def __call__(self, body):
            import asyncio

            await asyncio.sleep(0.15)
            return body["i"]

    handle = serve.run(Surge.bind(), name="surge")
    controller = serve_api._get_controller(create=False)
    # open-loop burst: fire 80 requests over ~2s without waiting
    refs = []
    for i in range(80):
        refs.append(handle.remote({"i": i}))
        time.sleep(0.025)
    out = ray_tpu.get(refs, timeout=120)
    assert sorted(out) == list(range(80))  # zero drops, zero dupes

    state = ray_tpu.get(
        controller.get_autoscale_state.remote("surge"), timeout=30)
    ups = [t for t in state["transitions"] if t["direction"] == "up"]
    assert ups, f"no scale-up recorded: {state}"
    assert ups[0]["to"] > ups[0]["from"]
    assert "demand" in ups[0]["reason"] or "SLO" in ups[0]["reason"]
    assert ups[0]["metrics"]["arrival_rate"] > 0

    # drain: demand decays through the window -> back to min_replicas
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        state = ray_tpu.get(
            controller.get_autoscale_state.remote("surge"), timeout=30)
        if state["target"] == 1 and any(
                t["direction"] == "down" for t in state["transitions"]):
            break
        time.sleep(0.5)
    downs = [t for t in state["transitions"] if t["direction"] == "down"]
    assert downs, f"no scale-down recorded: {state}"
    assert state["target"] == 1

    # monotonic reconciliation: the transition log chains exactly
    # (each action starts from where the previous one landed)
    trs = state["transitions"]
    for prev, nxt in zip(trs, trs[1:]):
        assert nxt["from"] == prev["to"]

    # replicas converge on the target after the drain grace
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        st = serve.status()["surge"]
        if st["num_replicas"] == 1 and st["draining"] == 0:
            break
        time.sleep(0.5)
    assert serve.status()["surge"]["num_replicas"] == 1

    # structured scale events reached the task plane
    from ray_tpu.util import events as events_mod

    evs = [e for e in events_mod.list_events(source="serve")
           if "autoscale surge" in e["message"]]
    assert any(e["metadata"].get("direction") == "up" for e in evs)
    assert any(e["metadata"].get("direction") == "down" for e in evs)
    serve.delete("surge")


def test_ingress_shed_and_fair_admission(cluster):
    """A flooding tenant hits its bounded queue and sheds; admitted work
    all completes; a second tenant is never starved out."""

    @serve.deployment(name="gated", max_ongoing_requests=2,
                      ray_actor_options={"num_cpus": 0.25})
    class Gated:
        async def __call__(self, body):
            import asyncio

            await asyncio.sleep(0.1)
            return body["tenant"]

    serve.run(Gated.bind(), name="gated")
    ingress = serve.build_ingress(
        "gated",
        {"max_queue_depth": 8, "latency_budget_s": 30.0,
         "tenant_weights": {"vip": 2.0}},
        max_inflight_per_replica=2)
    futures, shed_sync = [], 0
    for i in range(40):
        f = ingress.submit({"tenant": "flood"}, tenant="flood")
        # a shed future is resolved synchronously by submit()
        if f.done() and isinstance(f.exception(), LoadShedError):
            shed_sync += 1
        else:
            futures.append(f)
    vip = [ingress.submit({"tenant": "vip"}, tenant="vip")
           for _ in range(4)]
    assert shed_sync > 0, "flood never hit the bounded queue"
    assert len(futures) <= 8 + 4  # bound + inflight window
    for f in futures:
        assert f.result(timeout=60) == "flood"
    for f in vip:
        assert f.result(timeout=60) == "vip"
    st = ingress.stats()
    assert st["shed"] == shed_sync
    assert st["completed"] == len(futures) + len(vip)
    assert st["queued"] == 0 and st["inflight"] == 0
    ingress.close()
    serve.delete("gated")


def test_ingress_deadline_shed(cluster):
    """A request whose latency budget expires while queued is shed at
    dispatch instead of burning replica time."""

    @serve.deployment(name="slowpoke", max_ongoing_requests=1,
                      ray_actor_options={"num_cpus": 0.25})
    class Slowpoke:
        async def __call__(self, body):
            import asyncio

            await asyncio.sleep(0.4)
            return "done"

    serve.run(Slowpoke.bind(), name="slowpoke")
    ingress = serve.build_ingress(
        "slowpoke", {"max_queue_depth": 64, "latency_budget_s": 0.3},
        max_inflight_per_replica=1)
    futs = [ingress.submit({}) for _ in range(6)]
    outcomes = {"ok": 0, "shed": 0}
    for f in futs:
        try:
            f.result(timeout=60)
            outcomes["ok"] += 1
        except LoadShedError:
            outcomes["shed"] += 1
    assert outcomes["ok"] >= 1
    assert outcomes["shed"] >= 1, f"no deadline shed: {outcomes}"
    ingress.close()
    serve.delete("slowpoke")


def test_prefix_routing_policy_sticks_and_survives_scaling(cluster):
    @serve.deployment(name="kv", num_replicas=2,
                      ray_actor_options={"num_cpus": 0.25})
    class KV:
        def __call__(self, body):
            import os

            return os.getpid()

    handle = serve.run(KV.bind(), name="kv").options(
        routing_policy="prefix")
    # the state the spread below means: the handle routes over BOTH replicas
    # (a handle that has seen one yet sends every key there), and the keys
    # are enough that a ring of random replica ids cannot put them all on
    # one (six keys did, one run in 32)
    deadline = time.monotonic() + 120.0
    while time.monotonic() < deadline:
        handle._refresh(force=True)
        if len(handle._replicas) == 2:
            break
        time.sleep(0.2)
    assert len(handle._replicas) == 2
    prompts = [{"prompt": f"conversation-{i}: tell me more"}
               for i in range(24)]
    first = [ray_tpu.get(handle.remote(p), timeout=120) for p in prompts]
    for _ in range(3):  # repeats stay on their replica
        again = [ray_tpu.get(handle.remote(p), timeout=60)
                 for p in prompts]
        assert again == first
    assert len(set(first)) > 1  # keys actually spread across replicas
    # handles survive pickling with the policy intact
    import cloudpickle

    h2 = cloudpickle.loads(cloudpickle.dumps(handle))
    assert h2._routing_policy == "prefix"
    with pytest.raises(ValueError):
        handle.options(routing_policy="bogus")
    serve.delete("kv")


def test_serve_state_and_cli_surface(cluster):
    """The controller mirrors autoscale state into the serve KV namespace:
    util.state.serve_state() and `ray-tpu serve` read it back."""

    @serve.deployment(name="mirrored",
                      autoscaling_config={"min_replicas": 1,
                                          "max_replicas": 2,
                                          "window_s": 2.0},
                      ray_actor_options={"num_cpus": 0.25})
    class Mirrored:
        def __call__(self, body):
            return "ok"

    handle = serve.run(Mirrored.bind(), name="mirrored")
    assert ray_tpu.get(handle.remote({}), timeout=120) == "ok"
    from ray_tpu.util.state import serve_state

    deadline = time.monotonic() + 30.0
    entry = None
    while time.monotonic() < deadline:
        entry = serve_state().get("mirrored")
        if entry and entry.get("rollup", {}).get("samples", 0) > 1:
            break
        time.sleep(0.5)
    assert entry is not None, "serve KV mirror never published"
    assert entry["target"] >= 1
    assert "arrival_rate" in entry["rollup"]
    serve.delete("mirrored")
    # delete cleans the mirror up
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        if "mirrored" not in serve_state():
            break
        time.sleep(0.5)
    assert "mirrored" not in serve_state()


def test_bench_serve_smoke(cluster):
    """tools/bench_serve --smoke end to end in a fresh interpreter: the
    SERVE_r01 acceptance shape (rate-based up AND down, zero drops across
    a rolling update) must reproduce."""
    import json
    import os
    import subprocess
    import sys

    out_path = "/tmp/ray_tpu_serve_smoke.json"
    try:
        os.unlink(out_path)
    except FileNotFoundError:
        pass
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.bench_serve", "--smoke",
         "--out", out_path],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, \
        f"bench_serve failed:\n{proc.stdout[-4000:]}\n{proc.stderr[-2000:]}"
    with open(out_path) as f:
        result = json.load(f)
    assert result["dropped_requests"] == 0
    assert result["requests_completed"] == result["requests_fired"]
    assert result["scaled_up"] and result["scaled_down"]
    assert result["ttft_p99_ms"] > 0
    assert result["rolling_update_weights_version"] == 1


# ---------------------------------------------------------------------------
# server-side TTFT: window differentiation, policy pressure, SLO finding
# ---------------------------------------------------------------------------


def test_window_ttft_p99_from_replica_samples():
    w = DeploymentMetricsWindow(window_s=10.0)
    st = _stat(arrived=20, completed=20, execute_sum=4.0, execute_count=20)
    st["ttft_samples"] = [0.05] * 18 + [0.9, 1.1]
    w.observe([_stat()], now=100.0)
    w.observe([st], now=102.0)
    # p99 sees the slow-first-byte tail, not the happy median
    assert w.ttft_p99_s(102.0) == pytest.approx(1.1)
    assert w.rollup(102.0)["ttft_p99_s"] == pytest.approx(1.1)
    # samples age out with the window
    assert w.ttft_p99_s(102.0 + 11.0) is None


def test_policy_ttft_slo_pressure():
    """TTFT p99 over the registered target reads as up-pressure even when
    the rate math says capacity is sufficient (streams slow to first
    byte are invisible to Little's law)."""
    auto = AutoscalingConfig(min_replicas=1, max_replicas=4,
                             target_ongoing_requests=2.0,
                             upscale_delay_s=0.0, scale_cooldown_s=0.0)
    w = DeploymentMetricsWindow(window_s=10.0)
    st = _stat(arrived=10, completed=10, execute_sum=0.5, execute_count=10)
    st["ttft_samples"] = [2.0] * 8
    w.observe([_stat()], now=10.0)
    w.observe([st], now=11.0)
    assert decide(w, current_target=1, config=auto, state=PolicyState(),
                  now=11.0).direction == "hold"  # demand alone is tiny
    d = decide(w, current_target=1, config=auto, state=PolicyState(),
               now=11.0, ttft_target_s=0.5)
    assert d.direction == "up"
    assert "ttft" in d.reason and "SLO" in d.reason
    assert d.metrics["ttft_p99_s"] == pytest.approx(2.0)


def test_ttft_slo_violation_finding_e2e(cluster):
    """Replica-stamped TTFT flows to the serve rollup, and a registered
    `ttft_target_s` the deployment can't meet becomes a
    `serve_slo_violation` finding on `ttft_p99_s` in the health scan."""
    from ray_tpu.util.state import cluster_health, serve_state

    @serve.deployment(name="slow_first_byte", max_ongoing_requests=4,
                      autoscaling_config={"min_replicas": 1,
                                          "max_replicas": 1,
                                          "window_s": 30.0},
                      ray_actor_options={"num_cpus": 0.25})
    class SlowFirstByte:
        async def __call__(self, body):
            import asyncio

            await asyncio.sleep(0.15)  # every first byte is late
            return "late"

    serve.run(SlowFirstByte.bind(), name="slow_first_byte")
    ingress = serve.build_ingress(
        "slow_first_byte", {"ttft_target_s": 0.01, "max_queue_depth": 64})
    futs = [ingress.submit({}) for _ in range(10)]
    assert all(f.result(timeout=120) == "late" for f in futs)

    # the controller tick drains replica ttft samples into the window and
    # mirrors rollup["ttft_p99_s"] into the serve KV namespace
    deadline = time.monotonic() + 45.0
    entry = None
    while time.monotonic() < deadline:
        entry = serve_state().get("slow_first_byte")
        if entry and entry.get("rollup", {}).get("ttft_p99_s"):
            break
        time.sleep(0.5)
    assert entry and entry["rollup"]["ttft_p99_s"] >= 0.1, entry
    assert entry.get("slo", {}).get("ttft_target_s") == 0.01

    findings = [f for f in cluster_health(scan=True)["findings"]
                if f["kind"] == "serve_slo_violation"
                and f.get("metric") == "ttft_p99_s"]
    assert findings and findings[0]["deployment"] == "slow_first_byte"
    assert findings[0]["value"] > findings[0]["target"]
    ingress.close()
    serve.delete("slow_first_byte")
