"""Serve tests (reference tier: python/ray/serve/tests basics)."""

import time

import pytest

import ray_tpu
from ray_tpu import serve


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


@pytest.fixture(scope="module")
def http_port(cluster):
    """The one HTTP proxy of this module's cluster (a second start of the
    detached proxy actor would bind its port again)."""
    return serve.start_http_proxy()


def test_function_deployment(cluster):
    @serve.deployment
    def doubler(body):
        return body["x"] * 2

    handle = serve.run(doubler.bind())
    assert ray_tpu.get(handle.remote({"x": 21}), timeout=120) == 42
    serve.delete("doubler")


def test_class_deployment_replicas_and_status(cluster):
    @serve.deployment(num_replicas=2)
    class Counter:
        def __init__(self, start):
            self.n = start

        def __call__(self, body):
            self.n += 1
            return {"pid_count": self.n, "base": self.n}

        def peek(self, body=None):
            return self.n

    handle = serve.run(Counter.bind(10))
    outs = ray_tpu.get([handle.remote({}) for _ in range(6)], timeout=120)
    assert all(o["base"] >= 11 for o in outs)
    st = serve.status()
    assert st["Counter"]["num_replicas"] == 2
    # method routing
    peek = handle.options(method_name="peek")
    assert ray_tpu.get(peek.remote(), timeout=60) >= 10
    serve.delete("Counter")


def test_model_composition(cluster):
    @serve.deployment
    class Child:
        def __call__(self, body):
            return body["v"] + 1

    @serve.deployment
    class Parent:
        def __init__(self, child):
            self.child = child

        def __call__(self, body):
            inner = ray_tpu.get(self.child.remote({"v": body["v"]}))
            return inner * 10

    child_app = Child.bind()
    serve.run(child_app)
    handle = serve.run(Parent.bind(child_app))
    assert ray_tpu.get(handle.remote({"v": 4}), timeout=120) == 50
    serve.delete("Parent")
    serve.delete("Child")


def test_batching(cluster):
    @serve.deployment
    class Batched:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
        async def __call__(self, bodies):
            # one invocation sees multiple queued requests
            n = len(bodies)
            return [{"batch_size": n, "x": b["x"]} for b in bodies]

    handle = serve.run(Batched.bind())
    refs = [handle.remote({"x": i}) for i in range(4)]
    outs = ray_tpu.get(refs, timeout=120)
    assert {o["x"] for o in outs} == {0, 1, 2, 3}
    assert max(o["batch_size"] for o in outs) >= 2
    serve.delete("Batched")


def test_replica_restart_on_death(cluster):
    import os

    @serve.deployment
    class Fragile:
        def __call__(self, body):
            if body.get("die"):
                os._exit(1)
            return "alive"

    handle = serve.run(Fragile.bind())
    assert ray_tpu.get(handle.remote({}), timeout=120) == "alive"
    try:
        ray_tpu.get(handle.remote({"die": True}), timeout=60)
    except Exception:
        pass
    # controller reconciles on demand
    controller = ray_tpu.get_actor("serve_controller")
    deadline = time.time() + 60
    ok = False
    while time.time() < deadline:
        ray_tpu.get(controller.check_replicas.remote(), timeout=60)
        handle._refresh(force=True)
        try:
            if ray_tpu.get(handle.remote({}), timeout=30) == "alive":
                ok = True
                break
        except Exception:
            time.sleep(0.5)
    assert ok
    serve.delete("Fragile")


def test_http_proxy(cluster, http_port):
    import json
    import urllib.request

    @serve.deployment
    def echo(body):
        return {"echo": body}

    serve.run(echo.bind())
    port = http_port
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/echo", data=json.dumps({"hi": 1}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        out = json.loads(resp.read())
    assert out["result"]["echo"] == {"hi": 1}
    serve.delete("echo")


def test_http_proxy_keeps_more_requests_in_flight_than_the_loops_pool(
        cluster, http_port):
    """A routed request holds a proxy thread until it is answered; the
    loop's default executor has cpu + 4 of them, and an LLM replica with 32
    decode slots behind it ran half empty. 40 requests whose handler waits
    until all 40 have arrived: every one is answered, so all were in flight
    at once."""
    import asyncio
    import json
    import os
    import threading
    import urllib.request

    N = 40
    assert N > min(32, (os.cpu_count() or 1) + 4)

    @serve.deployment(max_ongoing_requests=N)
    class Gate:
        def __init__(self):
            self.n = 0

        async def __call__(self, body):
            self.n += 1
            for _ in range(600):
                if self.n >= body["n"]:
                    return {"seen": self.n}
                await asyncio.sleep(0.05)
            return {"seen": self.n, "gave_up": True}

    serve.run(Gate.bind())
    port = http_port
    out = [None] * N

    def ask(i):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/Gate", data=json.dumps({"n": N}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=90) as resp:
            out[i] = json.loads(resp.read())["result"]

    threads = [threading.Thread(target=ask, args=(i,)) for i in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert all(o == {"seen": N} for o in out), out
    serve.delete("Gate")


def test_replica_peak_sampling_under_stats_lock():
    """Regression (raylint RCE001): _Replica's ongoing/peak counters are
    mutated on the replica's event loop but take_ongoing_peak() runs on a
    sync actor-pool thread, and its read-reset is a two-step RMW. The
    stats lock keeps a burst that fully drains between two autoscaler
    polls from being silently dropped. No cluster: the replica is driven
    directly on a private event loop."""
    import asyncio
    import threading

    import cloudpickle

    from ray_tpu.serve.api import _Replica

    class SlowTarget:
        def __init__(self):
            self.gate = asyncio.Event()

        async def __call__(self):
            await self.gate.wait()
            return "ok"

    loop = asyncio.new_event_loop()
    runner = threading.Thread(target=loop.run_forever, daemon=True)
    runner.start()
    try:
        replica = _Replica.cls(cloudpickle.dumps(SlowTarget),
                               cloudpickle.dumps(((), {})))
        args_blob = cloudpickle.dumps(((), {}))
        futs = [asyncio.run_coroutine_threadsafe(
            replica.handle_request("__call__", args_blob), loop)
            for _ in range(3)]
        deadline = time.monotonic() + 10
        while replica.num_ongoing() < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert replica.num_ongoing() == 3
        # the burst drains COMPLETELY before the autoscaler's next poll...
        loop.call_soon_threadsafe(replica._callable.gate.set)
        assert [f.result(10) for f in futs] == ["ok"] * 3
        assert replica.num_ongoing() == 0
        # ...yet the poll still sees its high-water mark, exactly once
        assert replica.take_ongoing_peak() == 3
        assert replica.take_ongoing_peak() == 0
    finally:
        loop.call_soon_threadsafe(loop.stop)
        runner.join(5)
        loop.close()
