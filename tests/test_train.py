"""Train layer e2e: JaxTrainer with checkpointing + failure recovery,
plus the one-program train step and its cross-replica-sharded layout:

- the sharded step is BIT-EXACT in fp32 against the fused step over
  multiple steps on the 8-device CPU mesh (params AND opt state after
  all-gather, global-norm clip engaged and included), with even and with
  wildly uneven masks;
- optimizer-state memory per replica is ~1/N of the unsharded state;
- tracing on or off, `step` runs the same executable and returns the same
  bits; the phases are named scopes inside the program; NO XLA
  buffer-donation/alias warnings appear anywhere;
- bucket-plan boundary cases (giant leaf, many tiny leaves);
- the bucketed collective tier (AsyncBucketReducer/ShardedBucketOptimizer)
  reduces correctly across ranks and keeps 1/N opt state;
- JaxTrainer wires grad sync into the train context.

Reference tier: python/ray/train/v2/tests (controller/worker-group/failure
policy units driven end-to-end here on CPU workers).
"""

import dataclasses
import os
import warnings

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train import (
    Checkpoint,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=6)
    yield ray_tpu
    ray_tpu.shutdown()


def _sgd_loop(config):
    """A tiny numpy "training" loop with report + checkpoint."""
    import json

    import numpy as np

    from ray_tpu import train

    ctx = train.get_context()
    w = np.zeros(4)
    start = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        with open(os.path.join(ckpt.as_directory(), "state.json")) as f:
            state = json.load(f)
        w = np.array(state["w"])
        start = state["step"]
    target = np.arange(4.0)
    for step in range(start, config["steps"]):
        w = w + 0.5 * (target - w)
        loss = float(((target - w) ** 2).mean())
        if (step + 1) % config["ckpt_every"] == 0 and ctx.get_world_rank() == 0:
            import tempfile

            with tempfile.TemporaryDirectory() as d:
                with open(os.path.join(d, "state.json"), "w") as f:
                    json.dump({"w": w.tolist(), "step": step + 1}, f)
                train.report({"loss": loss, "step": step + 1},
                             checkpoint=Checkpoint.from_directory(d))
        else:
            train.report({"loss": loss, "step": step + 1})
    return {"final_loss": loss, "rank": ctx.get_world_rank()}


def test_jax_trainer_e2e(cluster, tmp_path):
    trainer = JaxTrainer(
        _sgd_loop,
        train_loop_config={"steps": 6, "ckpt_every": 2},
        scaling_config=ScalingConfig(num_workers=2,
                                     resources_per_worker={"CPU": 1.0}),
        run_config=RunConfig(storage_path=str(tmp_path), name="e2e"),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 6
    assert result.metrics["loss"] < 1e-2
    assert result.checkpoint is not None
    assert os.path.exists(os.path.join(result.checkpoint.path, "state.json"))


def _flaky_loop(config):
    import json

    from ray_tpu import train

    ctx = train.get_context()
    marker = config["marker"]
    start = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        with open(os.path.join(ckpt.as_directory(), "state.json")) as f:
            start = json.load(f)["step"]
    for step in range(start, config["steps"]):
        if step == 3 and ctx.get_world_rank() == 0 and not os.path.exists(marker):
            open(marker, "w").close()
            os._exit(1)  # simulate worker death mid-run
        if ctx.get_world_rank() == 0:
            import tempfile

            with tempfile.TemporaryDirectory() as d:
                with open(os.path.join(d, "state.json"), "w") as f:
                    json.dump({"step": step + 1}, f)
                train.report({"step": step + 1},
                             checkpoint=Checkpoint.from_directory(d))
        else:
            train.report({"step": step + 1})
    return {"done": True, "resumed_from": start}


def test_failure_policy_restart(cluster, tmp_path):
    marker = str(tmp_path / "died_once")
    trainer = JaxTrainer(
        _flaky_loop,
        train_loop_config={"steps": 5, "marker": marker},
        scaling_config=ScalingConfig(num_workers=2,
                                     resources_per_worker={"CPU": 1.0}),
        run_config=RunConfig(storage_path=str(tmp_path), name="flaky",
                             failure_config=FailureConfig(max_failures=2)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 5
    assert os.path.exists(marker)  # the crash really happened


def test_training_failed_raises(cluster, tmp_path):
    def always_fails(config):
        raise RuntimeError("bad loop")

    from ray_tpu.train import TrainingFailedError

    trainer = JaxTrainer(
        always_fails,
        train_loop_config={},
        scaling_config=ScalingConfig(num_workers=1,
                                     resources_per_worker={"CPU": 1.0}),
        run_config=RunConfig(storage_path=str(tmp_path), name="failing"),
    )
    with pytest.raises(TrainingFailedError, match="bad loop"):
        trainer.fit()


# ---------------------------------------------------------------------------
# Overlapped bucketed allreduce + cross-replica sharded optimizer update
# ---------------------------------------------------------------------------


DP = 8  # conftest forces an 8-device CPU mesh


def _bitwise_equal_trees(a, b, repl):
    """Leaf-by-leaf bitwise comparison (gathering sharded leaves)."""
    import jax

    bad = []
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for i, (x, y) in enumerate(zip(la, lb)):
        x = np.asarray(jax.device_put(x, repl))
        y = np.asarray(jax.device_put(y, repl))
        if not np.array_equal(x, y):
            bad.append((i, float(np.abs(
                x.astype(np.float64) - y.astype(np.float64)).max())))
    return bad


def _sharded_bundle(dtype):
    """One tiny-config bundle on the 8-device mesh, clip LOW enough that
    the global-norm clip actually engages every step — plus the captured
    warnings from compiling/running every program of the bundle."""
    import jax
    from ray_tpu.models.transformer import CONFIGS
    from ray_tpu.parallel import TrainStepBundle, create_mesh, make_optimizer

    cfg = dataclasses.replace(CONFIGS["tiny"], max_seq_len=64, dtype=dtype)
    mesh = create_mesh({"data": DP, "fsdp": 1, "seq": 1, "tensor": 1,
                        "expert": 1})
    factory = lambda spec_fn: make_optimizer(  # noqa: E731
        learning_rate=1e-2, warmup_steps=2, total_steps=100, clip=0.05,
        clip_spec_fn=spec_fn)
    with warnings.catch_warnings(record=True) as wrec:
        warnings.simplefilter("always")
        bundle = TrainStepBundle(cfg, mesh, optimizer_factory=factory,
                                 shard_update=True)
        batch = bundle.make_batch(np.random.default_rng(0), 16, 64)
        runs = {}
        # fused (unsharded) reference, 3 steps
        pf, sf = bundle.init(jax.random.PRNGKey(0))
        for _ in range(3):
            pf, sf, lf = bundle._fused_step(pf, sf, batch)
        runs["fused"] = (pf, sf, float(lf))
        # sharded step (what `step` dispatches with shard_update on)
        ps, ss = bundle.init_sharded(jax.random.PRNGKey(0))
        for _ in range(3):
            ps, ss, ls = bundle.step(ps, ss, batch)
        runs["sharded"] = (ps, ss, float(ls))
        # the check program (the benchmark's gradient check reads it), on
        # the layout `step` takes
        runs["fwd_bwd"] = bundle._fwd_bwd(bundle.shard_params(pf), batch)
    return {"bundle": bundle, "batch": batch, "runs": runs,
            "warnings": [str(w.message) for w in wrec]}


@pytest.fixture(scope="module")
def sharded_bundle():
    import jax.numpy as jnp

    return _sharded_bundle(jnp.float32)


@pytest.fixture(scope="module")
def sharded_bundle_bf16():
    """The dtype cell 4 runs, and the case where the cast moves: the
    sharded step casts each shard to bfloat16 BEFORE its gather."""
    import jax.numpy as jnp

    return _sharded_bundle(jnp.bfloat16)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_sharded_update_bitexact_vs_fused(request, dtype):
    """The acceptance contract: the cross-replica sharded-update step
    reproduces the fused step bit-for-bit over 3 steps — params (through
    `unshard_params`) AND optimizer state after all-gather, with the
    global-norm clip (low threshold, so it engages) computed from
    shard-local sqnorms. In float32, and in bfloat16, where the gathered
    copy is the cast one."""
    import jax

    sharded_bundle = request.getfixturevalue(
        "sharded_bundle" if dtype == "float32" else "sharded_bundle_bf16")
    b = sharded_bundle["bundle"]
    pf, sf, lf = sharded_bundle["runs"]["fused"]
    ps, ss, ls = sharded_bundle["runs"]["sharded"]
    # clip engaged: the raw grad norm exceeds the 0.05 threshold
    _, grads = sharded_bundle["runs"]["fwd_bwd"]
    gnorm = float(np.sqrt(sum(
        float(np.sum(np.square(np.asarray(g, dtype=np.float64))))
        for g in jax.tree_util.tree_leaves(grads))))
    assert gnorm > 0.05, "test misconfigured: clip never engages"
    assert _bitwise_equal_trees(pf, b.unshard_params(ps), b.repl) == []
    assert _bitwise_equal_trees(sf, b.unshard_opt_state(ss), b.repl) == []
    assert lf == ls
    assert {str(x.dtype) for x in jax.tree_util.tree_leaves(ps)} == {"float32"}


def test_no_donation_alias_warnings(sharded_bundle, sharded_bundle_bf16):
    """Compiling and running every program of the bundle (fused, fused
    sharded, `_fwd_bwd`) produces zero XLA donation/alias warnings."""
    bad = [w for w in (sharded_bundle["warnings"]
                       + sharded_bundle_bf16["warnings"])
           if "donat" in w.lower() or "alias" in w.lower()]
    assert bad == [], f"XLA donation warnings: {bad[:2]}"


def test_sharded_param_state_is_1_over_n(sharded_bundle):
    """Between steps a replica holds its 1/N of the float32 master of
    every leaf the update rule can shard (the same rule and dim as the
    leaf's moments), not a replicated tree."""
    import jax

    b = sharded_bundle["bundle"]
    ps, ss, _ = sharded_bundle["runs"]["sharded"]
    pf, _, _ = sharded_bundle["runs"]["fused"]
    per = b.param_bytes_per_replica(ps)
    total = b.param_bytes_per_replica(pf)
    assert per == pytest.approx(total / DP, rel=0.05), (per, total)
    mu = ss[1][0].mu
    assert jax.tree_util.tree_map(lambda x: x.sharding.spec, ps) == \
        jax.tree_util.tree_map(lambda x: x.sharding.spec, mu)


def test_harness_contract_one_params_tree(sharded_bundle):
    """What the benchmark's harness does with ONE params tree: `_fwd_bwd`
    and `step` take exactly what `init_sharded` returns, `param_shardings`
    names that layout (a committed array that disagreed with a program's
    `in_shardings` would raise), and the fused program's layout has a name
    of its own."""
    import jax

    b, batch = sharded_bundle["bundle"], sharded_bundle["batch"]
    p, s = b.init_sharded(jax.random.PRNGKey(2))
    is_sharding = lambda x: hasattr(x, "spec")  # noqa: E731
    named = jax.tree_util.tree_leaves(b.param_shardings, is_leaf=is_sharding)
    held = [x.sharding for x in jax.tree_util.tree_leaves(p)]
    assert all(h.is_equivalent_to(n, x.ndim) for h, n, x in
               zip(held, named, jax.tree_util.tree_leaves(p)))
    loss, grads = b._fwd_bwd(p, batch)
    # the gradient arrives on the shards the update reads
    assert jax.tree_util.tree_map(lambda g: g.sharding.spec, grads) == \
        jax.tree_util.tree_map(lambda x: x.sharding.spec, p)
    p1, s1, loss_step = b.step(p, s, batch)
    assert float(loss) == float(loss_step)
    assert [x.sharding for x in jax.tree_util.tree_leaves(p1)] == held
    fused = jax.tree_util.tree_leaves(b.fused_param_shardings,
                                      is_leaf=is_sharding)
    assert any("data" in str(n.spec) for n in named)
    assert not any("data" in str(f.spec) for f in fused)
    with pytest.raises(ValueError, match="[Ss]harding"):
        b._fwd_bwd(b.init(jax.random.PRNGKey(2))[0], batch)


def test_sharded_step_reads_leaves_in_the_models_dtype(sharded_bundle,
                                                       sharded_bundle_bf16):
    """The gathered copy has the dtype the model reads the leaf in: with
    `dtype=bfloat16` every leaf the forward only ever casts (kernels,
    table, head) is bfloat16, the norm scales stay float32; with
    `dtype=float32` there is nothing to cast."""
    import jax

    reads = sharded_bundle_bf16["bundle"]._read_dtypes()
    flat = {jax.tree_util.keystr(k): str(v) for k, v in
            jax.tree_util.tree_leaves_with_path(reads)}
    assert flat and all(
        v == ("float32" if "scale" in k else "bfloat16")
        for k, v in flat.items()), flat
    assert {str(v) for v in jax.tree_util.tree_leaves(
        sharded_bundle["bundle"]._read_dtypes())} == {"float32"}


def test_sharded_opt_state_memory_is_1_over_n(sharded_bundle):
    """Optimizer-state bytes per replica ~ 1/N of the unsharded state
    (replicated scalars keep it from being exactly 1/N)."""
    b = sharded_bundle["bundle"]
    _, ss, _ = sharded_bundle["runs"]["sharded"]
    _, sf, _ = sharded_bundle["runs"]["fused"]
    per = b.opt_state_bytes_per_replica(ss)
    total = b.opt_state_bytes_per_replica(sf)
    assert per < total / (DP / 2), (per, total)  # well under half
    assert per == pytest.approx(total / DP, rel=0.05)


def test_bucket_plan_boundary_cases():
    from ray_tpu.collective.bucketed import plan_buckets

    KB = 1024
    f4 = np.dtype(np.float32)
    # one giant leaf larger than bucket_bytes -> its own bucket
    meta = {"tiny_a": ((8,), f4), "giant": ((1024, 1024), f4),
            "tiny_b": ((8,), f4)}
    plan = plan_buckets(meta, bucket_bytes=64 * KB, world_size=4)
    giant = [b for b in plan.buckets if "giant" in b.paths]
    assert len(giant) == 1 and giant[0].paths[-1] == "giant"
    assert giant[0].nbytes > 64 * KB  # not split, not dropped
    # many tiny leaves pack into ONE bucket
    meta = {f"leaf{i:03d}": ((4,), f4) for i in range(100)}
    plan = plan_buckets(meta, bucket_bytes=64 * KB, world_size=4)
    assert plan.num_buckets == 1
    assert plan.buckets[0].nbytes == 100 * 16
    # packing respects the bound and preserves layer order
    meta = {f"l{i:02d}": ((1024,), f4) for i in range(32)}  # 4KB each
    plan = plan_buckets(meta, bucket_bytes=8 * KB, world_size=4)
    assert all(b.nbytes <= 8 * KB for b in plan.buckets)
    order = [p for b in plan.buckets for p in b.paths]
    assert order == sorted(order)
    # owners balance bytes across ranks
    loads = plan.bytes_per_rank()
    assert max(loads) <= 2 * min(loads)
    with pytest.raises(ValueError):
        plan_buckets(meta, bucket_bytes=0)


def _uneven_mask_batch(sharded_bundle):
    """4 valid tokens on replica 0, 128 on each other replica."""
    import jax

    batch = dict(sharded_bundle["batch"])
    mask = np.zeros((16, 64), np.float32)
    mask[0, :4] = 1.0
    mask[2:] = 1.0
    batch["mask"] = jax.device_put(mask, sharded_bundle["bundle"].batch_sharding)
    return batch


@pytest.fixture
def traced():
    """Tracing on for one test; off again (and the buffer's new spans
    handed back) whatever the test does."""
    from ray_tpu.util import tracing

    was, env = tracing._enabled, os.environ.get("RAY_TPU_ENABLE_TRACING")
    tracing.enable()
    before = len(tracing._buffer)
    try:
        yield lambda: list(tracing._buffer)[before:]
    finally:
        tracing._enabled = was
        if env is None:
            os.environ.pop("RAY_TPU_ENABLE_TRACING", None)
        else:
            os.environ["RAY_TPU_ENABLE_TRACING"] = env


def _one_step(bundle, shard_update, batch):
    """A fresh bundle-shaped step from PRNGKey(0) on the layout asked for;
    `bundle.shard_update` is flipped for the call so that ONE set of
    compiled programs serves both cases."""
    import jax

    was = bundle.shard_update
    bundle.shard_update = shard_update
    try:
        init = bundle.init_sharded if shard_update else bundle.init
        p, s = init(jax.random.PRNGKey(0))
        p, s, loss = bundle.step(p, s, batch)
        if shard_update:
            s = bundle.unshard_opt_state(s)
        return jax.block_until_ready((p, s, loss))
    finally:
        bundle.shard_update = was


@pytest.mark.parametrize("shard_update", [False, True],
                         ids=["fused", "fused_sharded"])
def test_tracing_does_not_select_the_program(sharded_bundle, traced,
                                             shard_update):
    """With tracing enabled `step` returns params, opt state and loss
    bitwise equal to the run with tracing off, compiles no further
    program, and records one `train.step` span (and no phase span: the
    phases are scopes inside the program)."""
    from ray_tpu.util import tracing

    b, batch = sharded_bundle["bundle"], sharded_bundle["batch"]
    program = b._fused_step_sharded if shard_update else b._fused_step
    tracing._enabled = False
    p0, s0, l0 = _one_step(b, shard_update, batch)
    compiled = program._cache_size()
    tracing._enabled = True
    assert tracing.enabled()
    p1, s1, l1 = _one_step(b, shard_update, batch)
    assert program._cache_size() == compiled
    assert _bitwise_equal_trees(p0, p1, b.repl) == []
    assert _bitwise_equal_trees(s0, s1, b.repl) == []
    assert float(l0) == float(l1)
    names = [s["name"] for s in traced() if s["name"].startswith("train.")]
    assert names == ["train.step"]


def test_sharded_step_uneven_masks_bitexact(sharded_bundle):
    """The loss is normalised by the GLOBAL count of valid tokens, not
    averaged over per-replica means: with wildly uneven masks across the
    data shards the sharded step still equals the fused step bitwise."""
    import jax

    b = sharded_bundle["bundle"]
    batch = _uneven_mask_batch(sharded_bundle)
    pf, sf, lf = _one_step(b, False, batch)
    ps, ss, ls = _one_step(b, True, batch)
    assert _bitwise_equal_trees(pf, ps, b.repl) == []
    assert _bitwise_equal_trees(sf, ss, b.repl) == []
    assert float(lf) == float(ls)
    # and it IS the global normalisation: replica 0's four tokens weigh
    # 4 / 1796, not the 1 / 8 a mean of per-replica means would give them
    p0, _ = b.init(jax.random.PRNGKey(0))
    mask = np.asarray(batch["mask"])
    part = {}
    for name, rows in (("replica0", slice(0, 2)), ("others", slice(2, 16))):
        m = np.zeros_like(mask)
        m[rows] = mask[rows]
        part[name] = float(b.eval_step(
            p0, {**batch, "mask": jax.device_put(m, b.batch_sharding)}))
    n0, n = 4.0, float(mask.sum())
    global_mean = (n0 * part["replica0"] + (n - n0) * part["others"]) / n
    mean_of_means = (part["replica0"] + 7 * part["others"]) / 8
    assert float(lf) == pytest.approx(global_mean, rel=1e-5)
    assert abs(float(lf) - mean_of_means) > 1e-4 * float(lf)


def test_check_program_is_the_trained_program(sharded_bundle):
    """`_fwd_bwd` (what the benchmark's gradient check reads) computes the
    step's own loss: bitwise `_fused_step`'s on the same params and
    batch."""
    import jax

    b, batch = sharded_bundle["bundle"], sharded_bundle["batch"]
    p, s = b.init(jax.random.PRNGKey(0))
    loss_check, _ = b._fwd_bwd(b.shard_params(p), batch)
    _, _, loss_step = b._fused_step(p, s, batch)
    assert float(loss_check) == float(loss_step)


def test_phases_are_named_in_the_program(sharded_bundle):
    """A device trace finds the phases by the scopes the lowered step
    carries, in both layouts; the check program carries the backward's."""
    import jax

    b, batch = sharded_bundle["bundle"], sharded_bundle["batch"]

    def abstract(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree, shardings)

    p = abstract(b._abstract_params, b.param_shardings)
    for program, p_sh, opt_sh in (
            (b._fused_step, b.fused_param_shardings, b.opt_shardings),
            (b._fused_step_sharded, b.param_shardings,
             b.opt_shard_shardings)):
        text = program.lower(abstract(b._abstract_params, p_sh),
                             abstract(b._abstract_opt, opt_sh),
                             batch).as_text(debug_info=True)
        assert "train.fwd_bwd" in text and "train.optimizer" in text
    text = b._fwd_bwd.lower(p, batch).as_text(debug_info=True)
    assert "train.fwd_bwd" in text and "train.optimizer" not in text


def test_removed_options_are_gone(sharded_bundle):
    from ray_tpu.parallel import TrainStepBundle

    b = sharded_bundle["bundle"]
    for option in ({"compression": "int8"}, {"grad_dtype": "bf16"},
                   {"bucket_bytes": 1 << 20}):
        with pytest.raises(TypeError):
            TrainStepBundle(b.cfg, b.mesh, shard_update=True, **option)


@pytest.mark.parametrize("program",
                         ["fused", "fused_sharded", "fwd_bwd", "eval"])
def test_every_program_of_the_bundle_runs(sharded_bundle, program):
    """One call of each jitted program on the 8-device CPU mesh: a finite
    loss (and, where the program returns them, finite gradients)."""
    import jax

    b, batch = sharded_bundle["bundle"], sharded_bundle["batch"]
    if program == "fused":
        loss = b._fused_step(*b.init(jax.random.PRNGKey(1)), batch)[2]
    elif program == "fused_sharded":
        loss = b._fused_step_sharded(
            *b.init_sharded(jax.random.PRNGKey(1)), batch)[2]
    elif program == "fwd_bwd":
        loss, grads = b._fwd_bwd(
            b.init_sharded(jax.random.PRNGKey(1))[0], batch)
        assert all(np.isfinite(np.asarray(g)).all()
                   for g in jax.tree_util.tree_leaves(grads))
    else:
        loss = b.eval_step(b.init(jax.random.PRNGKey(1))[0], batch)
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# Bucketed collective tier (multi-controller): AsyncBucketReducer +
# cross-replica ShardedBucketOptimizer
# ---------------------------------------------------------------------------


@ray_tpu.remote
class _GradRank:
    """One data-parallel rank for the collective-tier tests."""

    def __init__(self, rank: int, world: int, base: str):
        from ray_tpu.collective.bucketed import init_sharded_optimizer_groups

        self.rank, self.world, self.base = rank, world, base
        init_sharded_optimizer_groups(world, rank, backend="cpu",
                                      base_name=base)

    def reduce_tree(self, seed: int, bucket_bytes: int):
        import jax
        from ray_tpu.collective.bucketed import (
            AsyncBucketReducer, leaf_meta, plan_buckets)

        tree = _grad_tree(seed)
        plan = plan_buckets(leaf_meta(tree), bucket_bytes=bucket_bytes,
                            world_size=self.world)
        red = AsyncBucketReducer(self.base, plan)
        try:
            out = red.reduce_tree(tree)
        finally:
            red.shutdown()
        return jax.tree_util.tree_map(np.asarray, out)

    def sharded_steps(self, n_steps: int, bucket_bytes: int, clip: float):
        import optax
        from ray_tpu.collective.bucketed import (
            ShardedBucketOptimizer, leaf_meta, plan_buckets)

        params = _grad_tree(1000)  # same init on every rank
        plan = plan_buckets(leaf_meta(params), bucket_bytes=bucket_bytes,
                            world_size=self.world)
        opt = ShardedBucketOptimizer(
            self.base, plan, self.rank, optax.adam(1e-2), params,
            clip_global_norm=clip)
        stats = None
        try:
            for step in range(n_steps):
                grads = _grad_tree(step * self.world + self.rank)
                params, stats = opt.step(grads)
        finally:
            opt.shutdown()
        return {k: np.asarray(v) for k, v in params.items()}, stats


def _grad_tree(seed: int):
    rng = np.random.default_rng(seed)
    return {
        "wide": rng.normal(size=(64, 16)).astype(np.float32),
        "bias": rng.normal(size=(16,)).astype(np.float32),
        "deep": rng.normal(size=(32, 8)).astype(np.float32),
    }


def test_async_bucket_reducer_sums_across_ranks(cluster):
    world = 4
    base = "t_reducer"
    ranks = [_GradRank.options(num_cpus=0.5).remote(r, world, base)
             for r in range(world)]
    outs = ray_tpu.get([a.reduce_tree.remote(seed=r, bucket_bytes=1 << 10)
                        for r, a in enumerate(ranks)], timeout=120)
    # reference: np-stacked sum in rank order (the reducer's op)
    expect = {}
    for key in ("wide", "bias", "deep"):
        expect[key] = np.stack([_grad_tree(r)[key]
                                for r in range(world)]).sum(axis=0)
    for out in outs:  # every rank sees the identical reduced tree
        for key in expect:
            assert np.array_equal(out[key], expect[key])
    for a in ranks:
        ray_tpu.kill(a)


def test_sharded_bucket_optimizer_cross_replica(cluster):
    """Each rank keeps ~1/N of the optimizer state, applies only its
    buckets, and every rank converges to the IDENTICAL full param tree
    (bit-for-bit across ranks) matching a single-process reference that
    consumes the same summed grads."""
    import optax

    world, steps, clip = 4, 2, 0.5
    base = "t_shopt"
    ranks = [_GradRank.options(num_cpus=0.5).remote(r, world, base)
             for r in range(world)]
    outs = ray_tpu.get(
        [a.sharded_steps.remote(steps, 1 << 10, clip) for a in ranks],
        timeout=180)
    params0, stats0 = outs[0]
    # all ranks bitwise identical
    for params_r, stats_r in outs[1:]:
        for key in params0:
            assert np.array_equal(params0[key], params_r[key])
    # opt state is sharded: per-rank bytes well under the full state, and
    # the owned bucket sets partition the plan
    full_state_bytes = sum(a.nbytes * 2 for a in _grad_tree(0).values())
    owned = [set(s["owned_buckets"]) for _, s in outs]
    assert all(s["opt_state_bytes"] < full_state_bytes for _, s in outs)
    for i in range(world):
        for j in range(i + 1, world):
            assert not (owned[i] & owned[j])
    # reference: same summed grads through the same per-leaf math
    ref = _grad_tree(1000)
    opt = optax.adam(1e-2)
    state = opt.init(ref)
    for step in range(steps):
        summed = {}
        for key in ref:
            summed[key] = np.stack([
                _grad_tree(step * world + r)[key] for r in range(world)
            ]).sum(axis=0)
        # clip factor from per-leaf sqnorms folded in leaf order (the
        # optimizer's documented association)
        acc = np.float32(0.0)
        for key in ref:  # dict order == tree order
            acc = np.float32(acc + np.float32(
                np.sum(np.square(summed[key].astype(np.float32)))))
        gnorm = np.float32(np.sqrt(acc))
        factor = np.float32(clip / max(float(gnorm), clip))
        clipped = {k: (v * factor).astype(v.dtype) for k, v in summed.items()}
        upd, state = opt.update(clipped, state, ref)
        ref = optax.apply_updates(ref, upd)
    for key in ref:
        np.testing.assert_allclose(params0[key], np.asarray(ref[key]),
                                   rtol=2e-6, atol=2e-7)
    for a in ranks:
        ray_tpu.kill(a)


def _grad_sync_loop(config):
    """Train-loop side of the wiring test: allreduce a deterministic tree
    through the context's bucket reducer and report what came back."""
    import numpy as np

    from ray_tpu import train

    ctx = train.get_context()
    assert ctx.grad_sync is not None
    tree = {"w": np.full((8, 4), float(ctx.get_world_rank() + 1),
                         np.float32),
            "b": np.ones((4,), np.float32)}
    red = ctx.make_bucket_reducer(tree)
    try:
        out = red.reduce_tree(tree)
    finally:
        red.shutdown()
    train.report({"w_sum": float(out["w"][0, 0]),
                  "b_sum": float(out["b"][0]), "step": 1})
    return {"ok": True}


def test_trainer_grad_sync_e2e(cluster, tmp_path):
    trainer = JaxTrainer(
        _grad_sync_loop,
        train_loop_config={},
        scaling_config=ScalingConfig(num_workers=2,
                                     resources_per_worker={"CPU": 1.0},
                                     grad_sync_backend="cpu",
                                     grad_sync_bucket_bytes=1 << 10),
        run_config=RunConfig(storage_path=str(tmp_path), name="gsync"),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["w_sum"] == 3.0  # 1 + 2 across the two ranks
    assert result.metrics["b_sum"] == 2.0
