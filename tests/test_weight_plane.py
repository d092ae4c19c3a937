"""Weight-plane acceptance tests (ray_tpu/weights/): mesh-aware sharded
weight transfer and live resharding on the 8-device virtual CPU mesh.

Covers the four north-star flows:
(a) learner -> N env-runner broadcast via publish/pull with version
    monotonicity,
(b) train-mesh -> differently-sharded serve-replica publish with plan-level
    no-gather and byte-accounting assertions,
(c) elastic re-form: a killed group's durable-published state is pulled
    back resharded onto the shrunken mesh,
(d) rolling serve weight update with zero dropped requests,
plus planner geometry units and the same-mesh collective lowering.
"""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.weights import (
    MeshSpec,
    ShardedTreeSpec,
    WeightStore,
    collective_reshard,
    local_shards_of,
    plan_reshard,
    publish_host_shards,
)


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


def _tree(scale: float = 1.0):
    return {
        "layer0": {"w": (np.arange(64, dtype=np.float32).reshape(8, 8)
                         * scale),
                   "b": np.arange(8, dtype=np.float32) * scale},
        "step": np.asarray([scale], np.float32),
    }


# ---------------------------------------------------------------------------
# planner geometry (pure, no cluster)
# ---------------------------------------------------------------------------


def _replicated_leaves(dst):
    return {leaf for leaf in dst.meta if all(a is None for a in
                                             dst.part_of(leaf))}


def _moved_sharded(plan, dst):
    """Bytes moved for leaves the destination actually shards (replicated
    leaves are broadcasts: each replica receives a copy by declaration)."""
    rep = _replicated_leaves(dst)
    return sum(e.nbytes for e in plan.edges
               if not e.local and e.leaf not in rep)


def _unique_sharded(src, dst):
    import numpy as np

    from ray_tpu.weights.spec import box_nbytes, unique_boxes

    rep = _replicated_leaves(dst)
    total = 0
    for leaf, (shape, dtype) in src.meta.items():
        if leaf in rep:
            continue
        item = np.dtype(dtype).itemsize
        for box in unique_boxes(src.mesh, src.part_of(leaf), shape):
            total += box_nbytes(box, item)
    return total


def test_plan_cross_mesh_bytes_and_no_gather():
    tree = _tree()
    src_mesh = MeshSpec((4,), ("data",), tuple(f"t{i}" for i in range(4)))
    dst_mesh = MeshSpec((2,), ("model",), ("s0", "s1"))
    src = ShardedTreeSpec.from_tree(tree, src_mesh,
                                    default_part=("data",))
    dst = ShardedTreeSpec.from_tree(
        tree, dst_mesh,
        parts={"layer0/w": (None, "model"), "layer0/b": ("model",),
               "step": ()})
    # 'step' (shape (1,)) cannot shard 4-ways; publish it replicated on src
    src.parts["step"] = ()
    plan = plan_reshard(src, dst)
    stats = plan.stats()
    # every dst byte arrives exactly once: for the sharded leaves, moved
    # bytes <= unique shard bytes (the tiny replicated 'step' leaf is a
    # declared broadcast — each replica legitimately receives its copy)
    assert _moved_sharded(plan, dst) <= _unique_sharded(src, dst)
    # published bytes never exceed unique shard bytes, broadcast included
    assert plan.unique_chunk_bytes() <= src.total_unique_bytes()
    # no single host ever holds a full gathered copy of a sharded leaf
    assert plan.no_gather()
    full_w = 64 * 4
    assert plan.max_host_leaf_bytes("layer0/w") < full_w
    assert stats["num_edges"] > 0 and stats["src_hosts"] == 4


def test_plan_broadcast_fanout_and_chunk_dedup():
    tree = _tree()
    src = ShardedTreeSpec.from_tree(tree, MeshSpec.host_mesh(["learner"]))
    dst = ShardedTreeSpec.replicated(tree, [f"r{i}" for i in range(8)])
    plan = plan_reshard(src, dst)
    # replicated destinations share ONE published chunk per leaf
    assert plan.fanout() == 8
    assert plan.unique_chunk_bytes() == src.total_unique_bytes()
    assert plan.bytes_moved() == 8 * src.total_unique_bytes()


def test_plan_rejects_mismatched_trees():
    a = ShardedTreeSpec.from_tree({"w": np.zeros(4)},
                                  MeshSpec.host_mesh(["a"]))
    b = ShardedTreeSpec.from_tree({"v": np.zeros(4)},
                                  MeshSpec.host_mesh(["a"]))
    with pytest.raises(ValueError, match="differ on leaves"):
        plan_reshard(a, b)


# ---------------------------------------------------------------------------
# (a) learner -> 8 env-runner broadcast, version monotonicity
# ---------------------------------------------------------------------------


class _ToyCore:
    def __init__(self, rank, world_size, group_name):
        self.params = {"w": np.zeros(4, np.float32)}

    def update(self, batch):
        self.params["w"] = self.params["w"] + 1.0
        return {"step": float(self.params["w"][0])}

    def get_params(self):
        return self.params

    def get_state(self):
        return self.params

    def set_state(self, state):
        self.params = state


def _toy_factory(rank, world_size, group_name):
    return _ToyCore(rank, world_size, group_name)


@ray_tpu.remote(num_cpus=0.2)
class _Runner:
    def __init__(self, store_name):
        from ray_tpu.rl.env_runner import WeightSync

        self.sync = WeightSync(store_name, start_after=-1)
        self.seen = []

    def poll(self, timeout=0.0):
        v = self.sync.poll(timeout=timeout)
        if v is not None:
            self.seen.append(v)
        return v

    def report(self):
        return {"versions": list(self.seen),
                "w0": float(self.sync.weights["w"][0])
                if self.sync.weights is not None else None}


def test_learner_broadcast_to_runners(cluster):
    from ray_tpu.rl.learner_group import LearnerGroup

    store_name = "bcast_test"
    runners = [_Runner.remote(store_name) for _ in range(8)]
    group = LearnerGroup(_toy_factory, num_learners=1,
                         num_cpus_per_learner=0.5)
    try:
        v1 = group.publish_weights(store_name)
        got = ray_tpu.get([r.poll.remote(timeout=30.0) for r in runners],
                          timeout=120)
        assert got == [v1] * 8
        # nothing new: poll returns None, version does not regress
        assert ray_tpu.get([r.poll.remote(0.0) for r in runners],
                           timeout=60) == [None] * 8
        group.update(np.zeros(1))
        v2 = group.publish_weights(store_name)
        assert v2 > v1
        got = ray_tpu.get([r.poll.remote(timeout=30.0) for r in runners],
                          timeout=120)
        assert got == [v2] * 8
        reports = ray_tpu.get([r.report.remote() for r in runners],
                              timeout=60)
        for rep in reports:
            assert rep["versions"] == sorted(rep["versions"]) == [v1, v2]
            assert rep["w0"] == 1.0  # post-update params reached every runner
        stats = WeightStore(store_name).stats()
        assert stats["latest"] == v2
    finally:
        group.shutdown()
        for r in runners:
            ray_tpu.kill(r)


# ---------------------------------------------------------------------------
# (b) train mesh -> differently-sharded serve replicas through the store
# ---------------------------------------------------------------------------


@ray_tpu.remote(num_cpus=0.2)
class _SrcHost:
    """One host of the train mesh: holds ONLY its shards (cut locally from
    the deterministic test tree — the full tree never crosses a boundary)."""

    def __init__(self, store_name, host, src_spec, dst_spec):
        self.store_name = store_name
        self.host = host
        self.src = src_spec
        self.dst = dst_spec

    def publish(self, version):
        shards = local_shards_of(_tree(), self.src, self.host)
        return publish_host_shards(
            WeightStore(self.store_name), version, self.src, self.host,
            shards, dst_spec=self.dst, durable=False)


@ray_tpu.remote(num_cpus=0.2)
class _DstHost:
    def __init__(self, store_name, host, dst_spec):
        self.store_name = store_name
        self.host = host
        self.dst = dst_spec

    def pull(self, version):
        shards = WeightStore(self.store_name).pull_shards(
            self.dst, self.host, version)
        return {leaf: {str(box): arr for box, arr in boxes.items()}
                for leaf, boxes in shards.items()}


def test_cross_mesh_publish_pull_no_gather(cluster):
    tree = _tree()
    store_name = "reshard_test"
    src_mesh = MeshSpec((4,), ("data",), tuple(f"t{i}" for i in range(4)))
    dst_mesh = MeshSpec((2,), ("model",), ("s0", "s1"))
    src = ShardedTreeSpec.from_tree(tree, src_mesh, default_part=("data",))
    src.parts["step"] = ()
    dst = ShardedTreeSpec.from_tree(
        tree, dst_mesh,
        parts={"layer0/w": (None, "model"), "layer0/b": ("model",),
               "step": ()})
    plan = plan_reshard(src, dst)
    assert plan.no_gather()
    assert _moved_sharded(plan, dst) <= _unique_sharded(src, dst)

    srcs = [_SrcHost.remote(store_name, h, src, dst)
            for h in src_mesh.hosts]
    version = 1
    ray_tpu.get([s.publish.remote(version) for s in srcs], timeout=120)

    dsts = [_DstHost.remote(store_name, h, dst) for h in dst_mesh.hosts]
    out = ray_tpu.get([d.pull.remote(version) for d in dsts], timeout=120)
    # s0 gets columns 0:4, s1 columns 4:8 of w; halves of b; all of step
    for i, host_out in enumerate(out):
        wbox = f"((0, 8), ({i * 4}, {i * 4 + 4}))"
        np.testing.assert_array_equal(
            host_out["layer0/w"][wbox], tree["layer0"]["w"][:, i*4:(i+1)*4])
        bbox = f"(({i * 4}, {i * 4 + 4}),)"
        np.testing.assert_array_equal(
            host_out["layer0/b"][bbox], tree["layer0"]["b"][i*4:(i+1)*4])
        np.testing.assert_array_equal(host_out["step"]["((0, 1),)"],
                                      tree["step"])

    stats = WeightStore(store_name).stats()["versions"][str(version)]
    # published exactly the planned unique chunks; every dst host pulled
    # only its own shard bytes
    assert stats["bytes_published"] == plan.unique_chunk_bytes()
    assert stats["bytes_pulled"] == plan.bytes_moved()
    for a in srcs + dsts:
        ray_tpu.kill(a)


# ---------------------------------------------------------------------------
# (c) elastic re-form: killed group's state reshards onto the smaller mesh
# ---------------------------------------------------------------------------


def test_elastic_reform_reshards_state(cluster):
    from ray_tpu.train.config import ScalingConfig
    from ray_tpu.train.scaling_policy import (ElasticScalingPolicy,
                                              mesh_spec_for)
    from ray_tpu.train.worker_group import TrainWorker

    store_name = "elastic_test"
    old_world = 4
    workers = [TrainWorker.options(num_cpus=0.2).remote(i, old_world)
               for i in range(old_world)]
    # every rank durably publishes ITS shard (dim 0) of the optimizer state
    version = 1
    ray_tpu.get([
        w.publish_weight_shards.remote(
            store_name, version,
            {"opt": {"m": np.full((2, 3), float(i), np.float32)}})
        for i, w in enumerate(workers)], timeout=120)
    # the whole incarnation dies (elastic failure)
    for w in workers:
        ray_tpu.kill(w)

    # scaling policy picks the next mesh-shaped size for what's left
    scaling = ScalingConfig(num_workers=old_world, elastic=True,
                            min_workers=1, elastic_granularity="pow2",
                            resources_per_worker={"CPU": 1.0})
    policy = ElasticScalingPolicy(scaling)
    new_world = policy.size_after_failure(old_world, {"CPU": 2.0})
    assert new_world == 2
    assert mesh_spec_for(new_world).hosts == ("rank0", "rank1")

    new_workers = [TrainWorker.options(num_cpus=0.2).remote(i, new_world)
                   for i in range(new_world)]
    out = ray_tpu.get([
        w.pull_weight_shards.remote(store_name) for w in new_workers],
        timeout=120)
    for rank, res in enumerate(out):
        assert res["version"] == version
        m = res["tree"]["opt"]["m"]
        assert m.shape == (4, 3)  # global dim0=8 resharded 4 -> 2
        expect = np.repeat(np.arange(rank * 2, rank * 2 + 2,
                                     dtype=np.float32), 2)[:, None]
        np.testing.assert_array_equal(m, np.broadcast_to(expect, (4, 3)))
    for w in new_workers:
        ray_tpu.kill(w)


# ---------------------------------------------------------------------------
# (d) rolling serve weight update: zero dropped requests
# ---------------------------------------------------------------------------


class _ServedModel:
    def __init__(self, store_name):
        self.store_name = store_name
        self.version = 0
        self.w = np.zeros(4, np.float32)

    def __call__(self, body):
        time.sleep(0.005)
        return {"version": self.version, "w0": float(self.w[0])}

    def update_weights(self, version=None):
        tree, ver = WeightStore(self.store_name).pull(
            version, return_version=True)
        # attribute swap is atomic under the GIL: in-flight requests keep
        # serving the old tree, the next request sees the new one
        self.w, self.version = tree["w"], ver
        return ver


def test_rolling_serve_weight_update_zero_drops(cluster):
    from ray_tpu.serve import api as serve

    store_name = "serve_weights_test"
    store = WeightStore(store_name)
    app = serve.deployment(
        _ServedModel, name="wmodel", num_replicas=3,
        ray_actor_options={"num_cpus": 0.3}).bind(store_name)
    handle = serve.run(app)
    try:
        failures = []
        responses = []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    responses.append(
                        ray_tpu.get(handle.remote({}), timeout=60))
                except Exception as e:  # any dropped request fails the test
                    failures.append(repr(e))

        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        # traffic must be flowing BEFORE the update: wait for the first
        # answers rather than for a fixed time (beside five other xdist
        # workers the first round trip has taken more than half a second)
        deadline = time.time() + 60
        while len(responses) < 4 and time.time() < deadline:
            time.sleep(0.05)
        v1 = store.publish({"w": np.full(4, 7.0, np.float32)})
        acks = handle.broadcast("update_weights", timeout=120)
        assert acks == [v1] * 3  # every replica applied the update
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not failures, failures[:3]
        assert len(responses) > 20
        # traffic flowed before, during, and after the update; post-update
        # responses carry the new version/weights
        assert responses[0]["version"] == 0
        assert responses[-1]["version"] == v1 and responses[-1]["w0"] == 7.0
    finally:
        serve.delete("wmodel")


# ---------------------------------------------------------------------------
# same-mesh lowering: collective tier (no store involved)
# ---------------------------------------------------------------------------


@ray_tpu.remote(num_cpus=0.3)
class _MeshMember:
    def __init__(self, rank, world, src_spec, dst_spec):
        from ray_tpu import collective as col

        self.rank = rank
        self.src = src_spec
        self.dst = dst_spec
        self.group = col.init_collective_group(world, rank, backend="cpu",
                                               group_name="wp_reshard")

    def reshard(self):
        host = self.src.mesh.hosts[self.rank]
        shards = local_shards_of(_tree(), self.src, host)
        plan = plan_reshard(self.src, self.dst)
        out = collective_reshard(plan, self.group, host, shards)
        return {leaf: {str(b): a for b, a in boxes.items()}
                for leaf, boxes in out.items()}


def test_collective_reshard_same_mesh(cluster):
    tree = _tree()
    mesh = MeshSpec((2,), ("x",), ("m0", "m1"))
    src = ShardedTreeSpec.from_tree(
        tree, mesh, parts={"layer0/w": ("x",), "layer0/b": ("x",),
                           "step": ()})
    dst = ShardedTreeSpec.from_tree(
        tree, mesh, parts={"layer0/w": (None, "x"), "layer0/b": ("x",),
                           "step": ()})
    members = [_MeshMember.remote(i, 2, src, dst) for i in range(2)]
    out = ray_tpu.get([m.reshard.remote() for m in members], timeout=120)
    for i, res in enumerate(out):
        np.testing.assert_array_equal(
            res["layer0/w"][f"((0, 8), ({i * 4}, {i * 4 + 4}))"],
            tree["layer0"]["w"][:, i * 4:(i + 1) * 4])
        # b: same partition on both sides -> pure local edges
        np.testing.assert_array_equal(
            res["layer0/b"][f"(({i * 4}, {i * 4 + 4}),)"],
            tree["layer0"]["b"][i * 4:(i + 1) * 4])
    for m in members:
        ray_tpu.kill(m)


def test_jax_reshard_on_virtual_mesh(cluster):
    """XLA-tier lowering on the 8-device CPU mesh: one device_put per leaf
    re-lays the tree onto a new NamedSharding."""
    from ray_tpu.weights import jax_reshard
    from ray_tpu.utils import import_jax

    jax = import_jax()
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    tree = _tree()
    out = jax_reshard(tree, {"data": 4, "model": 2},
                      {"layer0/w": ("data", "model"),
                       "layer0/b": ("model",)})
    w = out["layer0"]["w"]
    assert len(w.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(w), tree["layer0"]["w"])
    np.testing.assert_array_equal(np.asarray(out["step"]), tree["step"])


# ---------------------------------------------------------------------------
# collective redistribution lowering (portable, no-gather by construction)
# ---------------------------------------------------------------------------


def test_lower_collective_rounds_and_cost_model():
    from ray_tpu.weights import DcnCostModel, lower_collective

    tree = {"w": np.arange(1024, dtype=np.float32).reshape(8, 128)}
    src = ShardedTreeSpec.from_tree(
        tree, MeshSpec((4,), ("data",), tuple(f"t{i}" for i in range(4))),
        default_part=("data",))
    dst = ShardedTreeSpec.from_tree(
        tree, MeshSpec((2,), ("model",), ("s0", "s1")),
        parts={"w": (None, "model")})
    plan = plan_reshard(src, dst)
    cm = DcnCostModel(node_of=lambda h: "A" if h in ("t0", "t1", "s0")
                      else "B")
    prog = lower_collective(plan, cm)
    st = prog.stats()
    # every non-local edge is scheduled exactly once
    assert st["num_edges"] == sum(1 for e in plan.edges if not e.local)
    assert sorted(i for rnd in prog.rounds for i in rnd) == \
        [i for i, e in enumerate(plan.edges) if not e.local]
    # the DCN/ICI split follows the node mapping and prices the estimate
    assert st["dcn_bytes"] + st["ici_bytes"] == plan.bytes_moved()
    assert st["dcn_bytes"] > 0 and st["est_seconds"] > 0
    # a tight in-flight budget forces more rounds, each within budget
    one_edge = max(e.nbytes for e in plan.edges if not e.local)
    tight = lower_collective(plan, cm, inflight_limit_bytes=one_edge)
    assert len(tight.rounds) > len(prog.rounds)
    assert tight.max_round_host_bytes() <= one_edge


def test_lower_collective_refuses_gather_and_logs_fallback():
    from ray_tpu.weights import (ReshardLoweringError, lower_collective,
                                 lowering_fallback_counts,
                                 maybe_lower_collective)

    tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
    src = ShardedTreeSpec.from_tree(
        tree, MeshSpec((4,), ("data",), tuple(f"t{i}" for i in range(4))),
        default_part=("data",))
    # dst axis of size 1: one host would hold the full (non-replicated-
    # by-declaration) leaf — the gather the lowering must refuse
    dst = ShardedTreeSpec.from_tree(
        tree, MeshSpec((1,), ("x",), ("g0",)), parts={"w": ("x",)})
    plan = plan_reshard(src, dst)
    assert not plan.no_gather()
    with pytest.raises(ReshardLoweringError):
        lower_collective(plan)
    before = lowering_fallback_counts().get("plan_not_no_gather", 0)
    assert maybe_lower_collective(plan) is None  # logged, never silent
    assert lowering_fallback_counts()["plan_not_no_gather"] == before + 1


@ray_tpu.remote(num_cpus=0.3)
class _ProgramMember:
    """Collective-group member executing a pre-lowered redistribution
    program (the bounded-in-flight path under collective_reshard)."""

    def __init__(self, rank, world, src_spec, dst_spec, limit):
        from ray_tpu import collective as col
        from ray_tpu.weights import lower_collective

        self.rank = rank
        self.src = src_spec
        self.dst = dst_spec
        plan = plan_reshard(src_spec, dst_spec)
        assert plan.no_gather()
        self.program = lower_collective(plan, inflight_limit_bytes=limit)
        self.group = col.init_collective_group(world, rank, backend="cpu",
                                               group_name="wp_redist")

    def run(self):
        from ray_tpu.weights import redistribute

        host = self.src.mesh.hosts[self.rank]
        shards = local_shards_of(_tree(), self.src, host)
        out = redistribute(self.program, self.group, host, shards)
        return {leaf: {str(b): a for b, a in boxes.items()}
                for leaf, boxes in out.items()}


def test_redistribute_program_multi_round(cluster):
    """A byte-tight in-flight budget splits the exchange into many
    rounds; the round-sequenced execution still lands every byte."""
    tree = _tree()
    mesh = MeshSpec((2,), ("x",), ("m0", "m1"))
    src = ShardedTreeSpec.from_tree(
        tree, mesh, parts={"layer0/w": ("x",), "layer0/b": ("x",),
                           "step": ()})
    dst = ShardedTreeSpec.from_tree(
        tree, mesh, parts={"layer0/w": (None, "x"), "layer0/b": ("x",),
                           "step": ()})
    plan = plan_reshard(src, dst)
    biggest = max(e.nbytes for e in plan.edges if not e.local)
    members = [_ProgramMember.remote(i, 2, src, dst, biggest)
               for i in range(2)]
    out = ray_tpu.get([m.run.remote() for m in members], timeout=120)
    for i, res in enumerate(out):
        np.testing.assert_array_equal(
            res["layer0/w"][f"((0, 8), ({i * 4}, {i * 4 + 4}))"],
            tree["layer0"]["w"][:, i * 4:(i + 1) * 4])
    for m in members:
        ray_tpu.kill(m)


def test_jax_reshard_transition_no_rematerialization(cluster):
    """Regression for the MULTICHIP_r05 warning: a device-tier sharding
    TRANSITION (live jax.Array -> different layout) must take the
    explicit shard-assembly lowering — zero bare cross-sharding
    device_puts, zero XLA "involuntary full rematerialization" output."""
    import logging
    import warnings

    from ray_tpu.utils import import_jax
    from ray_tpu.weights import jax_reshard, reshard_lowering_stats
    from ray_tpu.weights.transport import reset_reshard_lowering_stats

    jax = import_jax()
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    tree = {"w": np.arange(4096, dtype=np.float32).reshape(64, 64),
            "b": np.arange(64, dtype=np.float32)}
    reset_reshard_lowering_stats()
    records = []
    handler = logging.Handler()
    handler.emit = lambda rec: records.append(rec.getMessage())
    root = logging.getLogger()
    root.addHandler(handler)
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            # host upload, then two live transitions across layouts
            t1 = jax_reshard(tree, {"data": 8}, {"w": ("data",),
                                                 "b": ("data",)})
            t2 = jax_reshard(t1, {"data": 4, "model": 2},
                             {"w": ("data", "model"), "b": ("data",)})
            t3 = jax_reshard(t2, {"data": 2, "model": 4},
                             {"w": ("model", "data"), "b": (None,)})
    finally:
        root.removeHandler(handler)
    np.testing.assert_array_equal(np.asarray(t3["w"]), tree["w"])
    np.testing.assert_array_equal(np.asarray(t3["b"]), tree["b"])
    stats = reshard_lowering_stats()
    assert stats["host_put"] == 2           # the initial upload
    assert stats["lowered"] >= 3            # every live transition
    assert stats["fallback"] == 0           # no bare cross-sharding put
    spill = [m for m in records if "rematerialization" in m.lower()]
    spill += [str(w.message) for w in caught
              if "rematerialization" in str(w.message).lower()]
    assert not spill, spill


# ---------------------------------------------------------------------------
# delta + quantized publishes (the compression tier of the weight plane)
# ---------------------------------------------------------------------------


def _delta_tree(rng, n_leaves=8, rows=128):
    return {f"l{i}": rng.normal(size=(rows, 64)).astype(np.float32)
            for i in range(n_leaves)}


def test_delta_publish_byte_exact_and_under_half_bytes(cluster):
    """A small-update delta publish ships only the changed chunks
    (< 50% of full-publish bytes) and pulls stay BYTE-exact against the
    logical tree — unchanged leaves alias the base version's chunks by
    content address."""
    rng = np.random.default_rng(0)
    tree = _delta_tree(rng)
    store = WeightStore("w_delta")
    v1 = store.publish(tree, durable=True)
    tree2 = dict(tree)
    tree2["l3"] = tree["l3"] + 1.0  # 1 of 8 leaves changed
    v2 = store.publish(tree2, durable=True, delta_from=v1)
    pulled = store.pull(v2)
    for k in tree2:
        np.testing.assert_array_equal(pulled[k], tree2[k])
    vs = store.stats()["versions"]
    full, delta = vs[str(v1)], vs[str(v2)]
    assert delta["bytes_published"] < 0.5 * full["bytes_published"], \
        (full, delta)
    assert delta["bytes_reused"] == 7 * tree["l0"].nbytes


def test_chained_deltas_survive_retention(cluster):
    """v3/v4 delta off their predecessors; retention (keep=2) retires the
    intermediate versions, but the aliased chunk entries keep the refs
    alive — the newest delta version still pulls byte-exact."""
    rng = np.random.default_rng(1)
    tree = _delta_tree(rng)
    store = WeightStore("w_chain")
    v = store.publish(tree, durable=True)
    for i in range(3):  # three chained deltas -> the base retires
        tree = dict(tree)
        tree[f"l{i}"] = tree[f"l{i}"] * 2.0 + i
        v = store.publish(tree, durable=True, delta_from=v)
    pulled = store.pull(v)
    for k in tree:
        np.testing.assert_array_equal(pulled[k], tree[k])
    # the earliest version really is retired (not silently kept)
    vs = sorted(int(x) for x in store.stats()["versions"])
    with pytest.raises(Exception):
        store.manifest(vs[0])


def test_delta_base_vanished_falls_back_to_full(cluster):
    rng = np.random.default_rng(2)
    tree = _delta_tree(rng, n_leaves=4)
    store = WeightStore("w_fall")
    for _ in range(4):  # roll versions so v1 retires
        store.publish(tree, durable=True)
    v = store.publish(tree, durable=True, delta_from=1)  # retired base
    vs = store.stats()["versions"][str(v)]
    assert vs["bytes_reused"] == 0  # full publish, no silent aliasing
    pulled = store.pull(v)
    for k in tree:
        np.testing.assert_array_equal(pulled[k], tree[k])


def test_quantized_publish_pull_and_compose_with_delta(cluster):
    """Quantized chunk encoding: int8 publish ships <30% of the raw
    bytes, pulls (full AND sharded) transparently dequantize, and an
    unchanged delta on top of a quantized base reuses every chunk (delta
    hashing keys on RAW bytes, so the tiers compose)."""
    rng = np.random.default_rng(3)
    tree = _delta_tree(rng)
    raw = sum(a.nbytes for a in tree.values())
    store = WeightStore("w_quant")
    v1 = store.publish(tree, durable=True, compression="int8")
    p1 = store.pull(v1)
    for k in tree:
        rel = np.abs(p1[k] - tree[k]).max() / np.abs(tree[k]).max()
        assert rel < 0.02, (k, rel)
    vs = store.stats()["versions"]
    assert vs[str(v1)]["bytes_published"] < 0.3 * raw
    # sharded pull decodes the same bytes
    dst_mesh = MeshSpec((2,), ("data",), ("h0", "h1"))
    dst = ShardedTreeSpec.from_tree(tree, dst_mesh, default_part=("data",))
    shards = store.pull_shards(dst, "h0", v1)
    box = next(iter(shards["l0"]))
    np.testing.assert_array_equal(shards["l0"][box], p1["l0"][:64])
    # delta on an unchanged tree: zero new bytes, pulls match the base
    v2 = store.publish(tree, durable=True, delta_from=v1,
                       compression="int8")
    assert store.stats()["versions"][str(v2)]["bytes_published"] == 0
    p2 = store.pull(v2)
    for k in tree:
        np.testing.assert_array_equal(p2[k], p1[k])


def test_plain_publish_unchanged_by_compression_tier(cluster):
    """Regression guard: the default publish writes NO encodings into the
    manifest and pulls are bitwise-identical — the compression tier is
    strictly opt-in."""
    rng = np.random.default_rng(4)
    tree = _delta_tree(rng, n_leaves=3)
    store = WeightStore("w_plain")
    v = store.publish(tree, durable=True)
    man = store.manifest(v)
    for c in man["chunks"].values():
        assert c["enc"] is None
        assert c["sha"]  # content address recorded for future deltas
    pulled = store.pull(v)
    for k in tree:
        np.testing.assert_array_equal(pulled[k], tree[k])


def test_learner_group_delta_quantized_publish(cluster):
    """The rl publish path: LearnerGroup.publish_weights(delta=True)
    publishes against the learner's previous version; with compression
    the env-runner-facing pull dequantizes transparently."""
    from ray_tpu.rl.learner_group import LearnerGroup

    group = LearnerGroup(_toy_factory, num_learners=2)
    try:
        store = WeightStore("w_lg")
        v1 = group.publish_weights("w_lg", durable=True, delta=True)
        v2 = group.publish_weights("w_lg", durable=True, delta=True)
        vs = store.stats()["versions"]
        # params unchanged between publishes -> the second is all-reuse
        assert vs[str(v2)]["bytes_published"] == 0
        assert vs[str(v2)]["bytes_reused"] > 0
        t1, t2 = store.pull(v1), store.pull(v2)
        for k in t1:
            np.testing.assert_array_equal(t1[k], t2[k])
        v3 = group.publish_weights("w_lg", durable=True, delta=True,
                                   compression="int8")
        assert store.latest() == v3
    finally:
        group.shutdown()
