"""Quantized + delta comms tier (collective/quant.py + the compression
knobs on the bucketed collectives and PPO grad sync).

Contracts pinned here:

- codec roundtrips hold across block boundaries, ragged tails, and
  non-finite inputs (scales stay finite — a NaN scale would poison the
  whole block);
- error feedback keeps quantized accumulation unbiased (the EQuARX
  mechanism that makes int8 training converge);
- the quantized allreduce moves >= 3.5x fewer wire bytes than fp32 at
  equal tree size, and every rank still ends bitwise-identical to its
  peers;
- compression is STRICTLY opt-in: compression=None paths reproduce the
  PR 12 fp32 behavior exactly (bitwise).
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.collective import quant
from ray_tpu.collective.quant import ErrorFeedback, QuantCodec


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# codec property tests
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,tol", [("int8", 0.01), ("fp8", 0.06),
                                      ("bf16", 0.01)])
@pytest.mark.parametrize("n", [1, 7, 63, 64, 65, 255, 256, 257, 1000])
def test_codec_roundtrip_block_boundaries(name, tol, n):
    codec = QuantCodec(name, 64)
    rng = np.random.default_rng(n)
    x = (rng.normal(size=n) * 10).astype(np.float32)
    qt = quant.quantize(x, codec)
    y = quant.dequantize(qt)
    assert y.shape == x.shape and y.dtype == x.dtype
    assert np.isfinite(qt.scales).all()
    assert np.abs(y - x).max() <= tol * np.abs(x).max()
    if name != "bf16":
        # block scale is per 64 elements; codes are 1 byte/element with
        # the ragged tail truncated (never shipped)
        assert qt.codes.size == n
        assert qt.scales.size == -(-n // 64)


def test_codec_shapes_and_dtypes_roundtrip():
    codec = QuantCodec("int8", 32)
    rng = np.random.default_rng(0)
    for shape in [(3, 5), (2, 3, 4), ()]:
        for dtype in (np.float32, np.float64):
            x = np.asarray(rng.normal(size=shape) * 5, dtype=dtype)
            y = quant.dequantize(quant.quantize(x, codec))
            assert y.shape == x.shape and y.dtype == x.dtype


def test_codec_nonfinite_inputs_keep_scales_finite():
    codec = QuantCodec("int8", 4)
    x = np.array([1.0, np.nan, np.inf, -np.inf, 2.0, -3.0], np.float32)
    qt = quant.quantize(x, codec)
    y = quant.dequantize(qt)
    assert np.isfinite(qt.scales).all()
    assert np.isfinite(y).all()
    # NaN encodes as 0; inf saturates at the block's finite amax
    assert y[1] == 0.0
    assert abs(y[0] - 1.0) < 0.05 and abs(y[4] - 2.0) < 0.05


def test_codec_zeros_roundtrip_exact():
    for name in ("int8", "fp8"):
        qt = quant.quantize(np.zeros(130, np.float32), QuantCodec(name, 64))
        assert np.isfinite(qt.scales).all()  # zero blocks get scale 1.0
        assert np.array_equal(quant.dequantize(qt), np.zeros(130, np.float32))


def test_encode_decode_single_buffer_form():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(33, 7)).astype(np.float32)
    wire, meta = quant.encode_array(x, QuantCodec("int8", 32))
    assert wire.dtype == np.uint8 and wire.ndim == 1
    assert wire.nbytes < 0.35 * x.nbytes
    y = quant.decode_array(wire, meta)
    assert y.shape == x.shape and np.abs(y - x).max() < 0.1


def test_resolve_codec_specs():
    assert quant.resolve_codec(None) is None
    assert quant.resolve_codec("none") is None
    assert quant.resolve_codec("fp32") is None
    c = quant.resolve_codec("int8:128")
    assert (c.name, c.block) == ("int8", 128)
    assert quant.resolve_codec("fp8").block == quant.DEFAULT_BLOCK
    assert quant.resolve_codec(c) is c
    with pytest.raises(ValueError):
        quant.resolve_codec("int4")
    with pytest.raises(TypeError):
        quant.resolve_codec(123)


def test_error_feedback_carries_quantization_error():
    """Accumulating EF-quantized gradients tracks the fp32 accumulation;
    the same codec WITHOUT error feedback drifts ~an order of magnitude
    further (the residual is systematic rounding bias)."""
    codec = QuantCodec("int8", 64)
    ef = ErrorFeedback(codec)
    rng = np.random.default_rng(7)
    g = rng.normal(size=512).astype(np.float32)
    acc_f = np.zeros_like(g)
    acc_ef = np.zeros_like(g)
    acc_raw = np.zeros_like(g)
    for _ in range(50):
        acc_f += g
        acc_ef += quant.dequantize(ef.encode("k", g))
        acc_raw += quant.dequantize(quant.quantize(g, codec))
    drift_ef = np.abs(acc_ef - acc_f).max()
    drift_raw = np.abs(acc_raw - acc_f).max()
    assert drift_ef < 0.1 * drift_raw
    assert ef.residual_norm("k") > 0.0
    ef.reset()
    assert ef.residual_norm("k") == 0.0


# ---------------------------------------------------------------------------
# quantized bucket collectives across actor ranks
# ---------------------------------------------------------------------------


def _grad_tree(seed: int, scale_kb: int = 64):
    rng = np.random.default_rng(seed)
    n = scale_kb * 256 // 2  # total fp32 elements across two leaves
    return {
        "wide": rng.normal(size=(n // 16, 16)).astype(np.float32),
        "deep": rng.normal(size=(n,)).astype(np.float32),
    }


@ray_tpu.remote(num_cpus=0.5)
class _QuantRank:
    def __init__(self, rank, world, base, compression):
        from ray_tpu.collective.bucketed import init_sharded_optimizer_groups

        init_sharded_optimizer_groups(world, rank, backend="cpu",
                                      base_name=base)
        self.rank, self.world = rank, world
        self.base, self.comp = base, compression

    def reduce_tree(self, seed, bucket_bytes):
        from ray_tpu.collective.bucketed import (AsyncBucketReducer,
                                                 leaf_meta, plan_buckets)

        tree = _grad_tree(seed)
        plan = plan_buckets(leaf_meta(tree), bucket_bytes=bucket_bytes,
                            world_size=self.world)
        red = AsyncBucketReducer(self.base, plan, compression=self.comp)
        try:
            return red.reduce_tree(tree), red.wire_stats()
        finally:
            red.shutdown()

    def sharded_steps(self, steps, bucket_bytes, clip):
        import optax

        from ray_tpu.collective.bucketed import (ShardedBucketOptimizer,
                                                 leaf_meta, plan_buckets)

        params = _grad_tree(1000)
        plan = plan_buckets(leaf_meta(params), bucket_bytes=bucket_bytes,
                            world_size=self.world)
        opt = ShardedBucketOptimizer(
            self.base, plan, self.rank, optax.adam(1e-2), params,
            clip_global_norm=clip, compression=self.comp)
        try:
            for step in range(steps):
                grads = _grad_tree(step * self.world + self.rank)
                tree, stats = opt.step(grads)
            return {k: np.asarray(v) for k, v in tree.items()}, stats
        finally:
            opt.shutdown()


def test_quantized_reducer_wire_reduction_and_rank_agreement(cluster):
    """int8 bucket allreduce: >= 3.5x fewer wire bytes than fp32 at equal
    tree size, every rank sees the identical reduced tree, and the result
    tracks the exact sum to quantization tolerance."""
    world = 4
    ranks = [_QuantRank.remote(r, world, "q_red", "int8")
             for r in range(world)]
    outs = ray_tpu.get([a.reduce_tree.remote(r, 1 << 16)
                        for r, a in enumerate(ranks)], timeout=180)
    expect = {}
    for key in ("wide", "deep"):
        expect[key] = np.stack([_grad_tree(r)[key]
                                for r in range(world)]).sum(axis=0)
    for tree, _ in outs:
        for key in expect:
            rel = np.abs(tree[key] - expect[key]).max() / \
                np.abs(expect[key]).max()
            assert rel < 0.02, (key, rel)
    t0, _ = outs[0]
    for tree, _ in outs[1:]:
        for key in t0:
            assert np.array_equal(t0[key], tree[key])
    stats = outs[0][1]
    assert stats["compression"] == "int8"
    assert stats["buckets_quantized"] > 0
    assert stats["wire_reduction_x"] >= 3.5, stats
    for a in ranks:
        ray_tpu.kill(a)


def test_reducer_compression_none_bitwise_parity(cluster):
    """Regression guard: compression=None reproduces the uncompressed
    reduce EXACTLY (bitwise vs the rank-ordered stacked sum — the PR 12
    contract) and never touches the quantized path."""
    world = 2
    ranks = [_QuantRank.remote(r, world, "q_none", None)
             for r in range(world)]
    outs = ray_tpu.get([a.reduce_tree.remote(r, 1 << 16)
                        for r, a in enumerate(ranks)], timeout=120)
    for key in ("wide", "deep"):
        expect = np.stack([_grad_tree(r)[key]
                           for r in range(world)]).sum(axis=0)
        for tree, stats in outs:
            assert np.array_equal(tree[key], expect)
            assert stats["compression"] is None
            assert stats["buckets_quantized"] == 0
            assert stats["bytes_wire"] == 0
    for a in ranks:
        ray_tpu.kill(a)


def test_sharded_optimizer_quantized_ranks_identical(cluster):
    """Quantized ShardedBucketOptimizer: grads ride the int8 reduce and
    param refreshes ship as quantized DELTAS — ranks stay bitwise
    identical to each other and track the fp32 trajectory."""
    import optax

    world, steps, clip = 4, 3, 0.5
    ranks = [_QuantRank.remote(r, world, "q_opt", "int8")
             for r in range(world)]
    outs = ray_tpu.get(
        # bucket_bytes sized for ~4 buckets so ownership (and the owner's
        # upload leg) spreads across ranks
        [a.sharded_steps.remote(steps, 1 << 14, clip) for a in ranks],
        timeout=240)
    p0, s0 = outs[0]
    for p, _ in outs[1:]:
        for key in p0:
            assert np.array_equal(p0[key], p[key])
    assert s0["compression"] == "int8"
    assert s0["broadcast_wire_bytes"] < 0.5 * s0["broadcast_fp32_bytes"]
    assert s0["reduce_wire"]["wire_reduction_x"] >= 3.5
    # fp32 reference trajectory (same summed grads through the same
    # per-leaf math): quantized params stay close
    ref = _grad_tree(1000)
    opt = optax.adam(1e-2)
    state = opt.init(ref)
    for step in range(steps):
        summed = {k: np.stack([_grad_tree(step * world + r)[k]
                               for r in range(world)]).sum(axis=0)
                  for k in ref}
        acc = np.float32(0.0)
        for key in ref:
            acc = np.float32(acc + np.float32(
                np.sum(np.square(summed[key].astype(np.float32)))))
        factor = np.float32(clip / max(float(np.sqrt(acc)), clip))
        clipped = {k: (v * factor).astype(v.dtype)
                   for k, v in summed.items()}
        upd, state = opt.update(clipped, state, ref)
        import optax as _optax

        ref = _optax.apply_updates(ref, upd)
    for key in ref:
        denom = np.abs(np.asarray(ref[key])).max()
        assert np.abs(p0[key] - np.asarray(ref[key])).max() < 0.05 * denom
    for a in ranks:
        ray_tpu.kill(a)


# ---------------------------------------------------------------------------
# PPO int8 convergence parity (the error-feedback convergence test)
# ---------------------------------------------------------------------------


def _ppo_batch(rng, n, obs_dim, n_actions):
    return {
        "obs": rng.standard_normal((n, obs_dim)).astype(np.float32),
        "actions": rng.integers(0, n_actions, n).astype(np.int32),
        "logp": (-np.log(n_actions)
                 + 0.1 * rng.standard_normal(n)).astype(np.float32),
        "advantages": rng.standard_normal(n).astype(np.float32),
        "returns": rng.standard_normal(n).astype(np.float32),
    }


def test_ppo_int8_grad_sync_loss_parity(cluster):
    """The convergence contract of the quantized tier: a 2-learner PPO
    stream with int8+error-feedback grad sync stays within 2% of the fp32
    run's loss, with ranks bitwise-identical to each other."""
    import dataclasses

    import jax

    from ray_tpu.rl.learner_group import LearnerGroup
    from ray_tpu.rl.ppo import PPOConfig, PPOLearner

    obs_dim, n_actions = 4, 2
    base_cfg = PPOConfig(env="CartPole-v1", epochs=2, num_minibatches=4,
                         seed=3)

    def make_group(cfg):
        def factory(rank, world_size, group_name, _cfg=cfg):
            return PPOLearner(_cfg, obs_dim, n_actions,
                              world_size=world_size, rank=rank,
                              group_name=group_name)

        return LearnerGroup(factory, num_learners=2)

    g_fp32 = make_group(base_cfg)
    g_int8 = make_group(dataclasses.replace(base_cfg,
                                            grad_compression="int8"))
    try:
        rng = np.random.default_rng(0)
        losses = {"fp32": [], "int8": []}
        for step in range(6):
            batch = _ppo_batch(rng, 256, obs_dim, n_actions)
            losses["fp32"].append(g_fp32.update(dict(batch))["loss"])
            losses["int8"].append(g_int8.update(dict(batch))["loss"])
        # loss parity within 2% at every step of the stream
        for lf, lq in zip(losses["fp32"], losses["int8"]):
            assert abs(lq - lf) <= 0.02 * max(abs(lf), 1e-3), (lf, lq)
        # quantized ranks still agree with each other bitwise
        params = g_int8.foreach_learner("get_params")
        for a, b in zip(jax.tree_util.tree_leaves(params[0]),
                        jax.tree_util.tree_leaves(params[1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and the parameter trees end close to the fp32 group's
        pf = jax.tree_util.tree_leaves(g_fp32.get_params())
        pq = jax.tree_util.tree_leaves(g_int8.get_params())
        for a, b in zip(pf, pq):
            a, b = np.asarray(a), np.asarray(b)
            # relative on real-magnitude leaves, absolute floor for
            # near-zero bias leaves (whole-tree scale ~1e-1)
            assert np.abs(a - b).max() < 0.05 * np.abs(a).max() + 2e-3
    finally:
        g_fp32.shutdown()
        g_int8.shutdown()
