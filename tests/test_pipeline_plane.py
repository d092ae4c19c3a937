"""Pipeline-plane acceptance tests (ray_tpu/train/pipeline/): MPMD
pipeline-parallel training over stage actor gangs on the CPU tier.

Covers the tentpole flows:
(a) 1F1B schedule golden (exact per-stage send/recv/compute sequence per
    microbatch) + the analytic bubble bound,
(b) 2-stage end-to-end loss/param parity vs the single-mesh fused
    TrainStepBundle step (same init, same data, same optimizer semantics),
    with the timeline golden asserted off the same run (pipe.send /
    pipe.recv spans form matched cross-process flow pairs per microbatch
    in the chrome trace),
(c) stage-actor kill -> gang re-form -> restore from per-stage ckpt
    manifests -> mid-run resume with deterministic replay,
plus the bench smoke (tier-1) for tools/bench_pipeline.py.
"""

import os

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train.pipeline import (
    PipelineConfig,
    PipelineTrainer,
    bubble_upper_bound,
    build_interleaved_schedule,
    build_schedule,
    make_microbatches,
    max_inflight_activations,
    partition_layers,
    simulate,
    stage_param_keys,
)


def _cfg(**kw):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    base = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                n_kv_heads=4, d_ff=64, max_seq_len=32, remat=False,
                dtype=jnp.float32, attention_impl="xla")
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def cluster():
    from ray_tpu.util import tracing

    prev = os.environ.get("RAY_TPU_ENABLE_TRACING")
    os.environ["RAY_TPU_ENABLE_TRACING"] = "1"
    tracing.enable()
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()
    # fully restore tracing state: _enabled is a process-level cache, and
    # leaving it on would silently put every later test module in this
    # pytest process on the span-recording paths
    if prev is None:
        os.environ.pop("RAY_TPU_ENABLE_TRACING", None)
    else:
        os.environ["RAY_TPU_ENABLE_TRACING"] = prev
    tracing._enabled = None


# ---------------------------------------------------------------------------
# schedule geometry (pure, no cluster)
# ---------------------------------------------------------------------------


def test_1f1b_schedule_golden_2x4():
    sched = build_schedule(2, 4)
    assert [tuple(op) for op in sched[0]] == [
        ("fwd", 0, 0), ("send_f", 0, 0),
        ("fwd", 1, 0), ("send_f", 1, 0), ("recv_b", 0, 0), ("bwd", 0, 0),
        ("fwd", 2, 0), ("send_f", 2, 0), ("recv_b", 1, 0), ("bwd", 1, 0),
        ("fwd", 3, 0), ("send_f", 3, 0), ("recv_b", 2, 0), ("bwd", 2, 0),
        ("recv_b", 3, 0), ("bwd", 3, 0),
    ]
    assert [tuple(op) for op in sched[1]] == [
        ("recv_f", 0, 0), ("fwd", 0, 0), ("bwd", 0, 0), ("send_b", 0, 0),
        ("recv_f", 1, 0), ("fwd", 1, 0), ("bwd", 1, 0), ("send_b", 1, 0),
        ("recv_f", 2, 0), ("fwd", 2, 0), ("bwd", 2, 0), ("send_b", 2, 0),
        ("recv_f", 3, 0), ("fwd", 3, 0), ("bwd", 3, 0), ("send_b", 3, 0),
    ]


def test_1f1b_schedule_properties_4x8():
    S, M = 4, 8
    sched = build_schedule(S, M)
    for s, ops in enumerate(sched):
        kinds = [op.kind for op in ops]
        # every microbatch runs exactly one fwd and one bwd per stage
        assert kinds.count("fwd") == M and kinds.count("bwd") == M
        # warmup depth: S-1-s warmup forwards + the first steady-state
        # forward run before the first backward
        first_bwd = kinds.index("bwd")
        assert kinds[:first_bwd].count("fwd") == min(S - s, M)
        # in-flight stash never exceeds the 1F1B bound
        inflight = peak = 0
        for k, *_ in ops:
            if k == "fwd":
                inflight += 1
                peak = max(peak, inflight)
            elif k == "bwd":
                inflight -= 1
        assert peak <= max_inflight_activations(s, S)
        # interior stages send/recv every microbatch both ways
        if 0 < s < S - 1:
            assert kinds.count("send_f") == kinds.count("send_b") == M
            assert kinds.count("recv_f") == kinds.count("recv_b") == M


def test_1f1b_bubble_matches_analytic_bound():
    for S, M in [(2, 4), (2, 8), (4, 8), (4, 16), (8, 32)]:
        sim = simulate(S, M, t_fwd=1.0, t_bwd=2.0)
        bound = bubble_upper_bound(S, M)
        assert sim["bubble_fraction"] <= bound + 1e-9, (S, M)
        # with equal per-mb costs 1F1B achieves the bound exactly
        assert abs(sim["bubble_fraction"] - bound) < 1e-9, (S, M)
    # communication costs only ever add bubble
    assert simulate(4, 8, t_comm=0.5)["bubble_fraction"] >= \
        bubble_upper_bound(4, 8)


def test_interleaved_schedule_golden_2x4_v2():
    """Exact per-rank op streams for S=2, V=2, M=4 (virtual stages
    q = chunk*2 + rank; warmup = 2*(S-1-rank) + (V-1)*S)."""
    sched = build_interleaved_schedule(2, 4, 2)
    assert [tuple(op) for op in sched[0]] == [
        ("fwd", 0, 0), ("send_f", 0, 0),
        ("fwd", 1, 0), ("send_f", 1, 0),
        ("recv_f", 0, 1), ("fwd", 0, 1), ("send_f", 0, 1),
        ("recv_f", 1, 1), ("fwd", 1, 1), ("send_f", 1, 1),
        ("fwd", 2, 0), ("send_f", 2, 0),
        ("recv_b", 0, 1), ("bwd", 0, 1), ("send_b", 0, 1),
        ("fwd", 3, 0), ("send_f", 3, 0),
        ("recv_b", 1, 1), ("bwd", 1, 1), ("send_b", 1, 1),
        ("recv_f", 2, 1), ("fwd", 2, 1), ("send_f", 2, 1),
        ("recv_b", 0, 0), ("bwd", 0, 0),
        ("recv_f", 3, 1), ("fwd", 3, 1), ("send_f", 3, 1),
        ("recv_b", 1, 0), ("bwd", 1, 0),
        ("recv_b", 2, 1), ("bwd", 2, 1), ("send_b", 2, 1),
        ("recv_b", 3, 1), ("bwd", 3, 1), ("send_b", 3, 1),
        ("recv_b", 2, 0), ("bwd", 2, 0),
        ("recv_b", 3, 0), ("bwd", 3, 0),
    ]
    assert [tuple(op) for op in sched[1]] == [
        ("recv_f", 0, 0), ("fwd", 0, 0), ("send_f", 0, 0),
        ("recv_f", 1, 0), ("fwd", 1, 0), ("send_f", 1, 0),
        ("recv_f", 0, 1), ("fwd", 0, 1), ("bwd", 0, 1), ("send_b", 0, 1),
        ("recv_f", 1, 1), ("fwd", 1, 1), ("bwd", 1, 1), ("send_b", 1, 1),
        ("recv_f", 2, 0), ("fwd", 2, 0), ("send_f", 2, 0),
        ("recv_b", 0, 0), ("bwd", 0, 0), ("send_b", 0, 0),
        ("recv_f", 3, 0), ("fwd", 3, 0), ("send_f", 3, 0),
        ("recv_b", 1, 0), ("bwd", 1, 0), ("send_b", 1, 0),
        ("recv_f", 2, 1), ("fwd", 2, 1), ("bwd", 2, 1), ("send_b", 2, 1),
        ("recv_f", 3, 1), ("fwd", 3, 1), ("bwd", 3, 1), ("send_b", 3, 1),
        ("recv_b", 2, 0), ("bwd", 2, 0), ("send_b", 2, 0),
        ("recv_b", 3, 0), ("bwd", 3, 0), ("send_b", 3, 0),
    ]


def test_interleaved_schedule_properties_and_validation():
    # every (chunk, mb) runs exactly one fwd + one bwd on its rank
    for S, M, V in [(2, 4, 2), (4, 8, 2), (2, 4, 4), (3, 6, 2)]:
        sched = build_interleaved_schedule(S, M, V)
        for r, ops in enumerate(sched):
            fwds = [(op.chunk, op.mb) for op in ops if op.kind == "fwd"]
            bwds = [(op.chunk, op.mb) for op in ops if op.kind == "bwd"]
            want = {(c, m) for c in range(V) for m in range(M)}
            assert set(fwds) == want and len(fwds) == M * V, (S, M, V, r)
            assert set(bwds) == want and len(bwds) == M * V, (S, M, V, r)
            # in-flight stash bounded by the interleaved warmup depth
            inflight = peak = 0
            for k, *_ in ops:
                if k == "fwd":
                    inflight += 1
                    peak = max(peak, inflight)
                elif k == "bwd":
                    inflight -= 1
            assert peak <= max_inflight_activations(r, S, V), (S, M, V, r)
    # V=1 degenerates to the plain schedule, exactly
    assert build_interleaved_schedule(2, 4, 1) == build_schedule(2, 4)
    # the chunk rotation only closes on whole groups of S
    with pytest.raises(ValueError, match="divisible"):
        build_interleaved_schedule(2, 3, 2)
    with pytest.raises(ValueError, match="chunk"):
        build_interleaved_schedule(2, 4, 0)


def test_interleaved_bubble_matches_analytic_bound():
    """The simulator (real channel semantics: FIFO edges + finite ring
    depth) hits (S-1)/(S-1+V*M) exactly at equal per-chunk costs — and
    never deadlocks or desyncs, which the simulator raises on."""
    shapes = [(2, 4, 2), (2, 8, 2), (4, 8, 2), (2, 4, 4), (3, 6, 2),
              (4, 4, 2), (2, 8, 1), (4, 8, 1)]
    for S, M, V in shapes:
        for depth in (0, 2):
            sim = simulate(S, M, t_fwd=1.0, t_bwd=2.0, num_chunks=V,
                           channel_depth=depth)
            bound = bubble_upper_bound(S, M, V)
            assert abs(sim["bubble_fraction"] - bound) < 1e-9, \
                (S, M, V, depth)
    # interleaving strictly shrinks the bubble at fixed S, M
    assert bubble_upper_bound(4, 8, 2) < bubble_upper_bound(4, 8, 1)


def test_partition_keys_cover_model_disjointly():
    cfg = _cfg(n_layers=5)
    for S in (1, 2, 3, 5):
        bounds = partition_layers(cfg.n_layers, S)
        assert bounds[0][0] == 0 and bounds[-1][1] == cfg.n_layers
        seen = []
        for s in range(S):
            seen += stage_param_keys(cfg, s, S)
        expected = {"embed", "final_norm", "lm_head"} | {
            f"layer_{i}" for i in range(cfg.n_layers)}
        assert set(seen) == expected and len(seen) == len(set(seen))


def test_tied_embeddings_single_stage_and_rejection():
    import jax
    import optax

    from ray_tpu.train.pipeline import StagePrograms

    cfg = _cfg(tie_embeddings=True)
    # S > 1 cannot host a tied head (the table would live on two stages)
    with pytest.raises(ValueError, match="tie_embeddings"):
        StagePrograms(cfg, 0, 2, optax.sgd(0.1))
    # S == 1 ties logits to the embed table — no phantom lm_head param
    progs = StagePrograms(cfg, 0, 1, optax.sgd(0.1))
    params = progs.init(jax.random.PRNGKey(0))
    assert "lm_head" not in params and "embed" in params
    mbs = make_microbatches(cfg, PipelineConfig(
        num_stages=1, num_microbatches=1, microbatch_size=1, seq_len=8),
        0, 0)
    loss, _ = progs.fwd_loss(params, mbs[0]["tokens"], mbs[0]["targets"],
                             mbs[0]["mask"])
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# end-to-end acceptance flow, one gang + one single-mesh reference:
# (1) 2-stage loss/param parity vs the fused TrainStepBundle step,
# (2) timeline golden off the same run (cross-process flow pairs per mb),
# (3) stage kill -> per-stage manifest restore -> deterministic resume
#     that KEEPS matching the single-mesh run (ckpt round-trip fidelity)
# ---------------------------------------------------------------------------


def test_two_stage_parity_timeline_kill_restore(cluster, tmp_path):
    import jax

    from ray_tpu.parallel.mesh import create_mesh, default_mesh_axes
    from ray_tpu.parallel.train import TrainStepBundle, make_optimizer
    from ray_tpu.util import tracing

    cfg = _cfg()
    M = 4
    pipe = PipelineConfig(num_stages=2, num_microbatches=M,
                          microbatch_size=2, seq_len=16,
                          clip_global_norm=1.0, ckpt_every=2,
                          step_timeout_s=60.0)
    steps = 3
    tracing.clear()
    trainer = PipelineTrainer(cfg, pipe, seed=5, run_name="parity",
                              ckpt_root=str(tmp_path))
    try:
        stats = trainer.train(steps)  # saves per-stage manifests at step 2
        pipe_losses = [s["loss"] for s in stats]

        # -- (1) parity: same init params, same data, the fused step with
        # optax.chain(clip_by_global_norm(1.0), adamw(schedule)) --
        mesh = create_mesh(default_mesh_axes(8))
        bundle = TrainStepBundle(cfg, mesh, optimizer=make_optimizer(),
                                 donate=False)
        params = trainer.init_params
        opt_state = bundle.optimizer.init(params)

        def ref_step(step):
            nonlocal params, opt_state
            mbs = make_microbatches(cfg, pipe, 5, step)
            batch = {k: np.concatenate([m[k] for m in mbs])
                     for k in mbs[0]}
            params, opt_state, loss = bundle._fused_step(
                params, opt_state, batch)
            return float(loss)

        ref_losses = [ref_step(s) for s in range(steps)]
        np.testing.assert_allclose(pipe_losses, ref_losses, rtol=0,
                                   atol=1e-5)

        def assert_param_parity():
            merged = trainer.merged_params()
            ref = jax.tree.leaves({k: params[k] for k in sorted(params)})
            got = jax.tree.leaves({k: merged[k] for k in sorted(merged)})
            for a, b in zip(ref, got):
                np.testing.assert_allclose(
                    np.asarray(a, np.float64), np.asarray(b, np.float64),
                    rtol=0, atol=1e-5)

        assert_param_parity()
        # activations actually crossed the channel plane
        assert stats[0]["activation_bytes_per_mb"] > 0

        # -- (2) timeline golden off the same run: pipe.send/pipe.recv
        # spans pair up across the two stage processes per microbatch,
        # and the chrome trace renders them as matched ph:"s"/"f" flow
        # arrows (the /api/timeline contract) --
        def _spans():
            spans = tracing.get_spans()
            sends = [s for s in spans if s["name"] == "pipe.send"]
            recvs = [s for s in spans if s["name"] == "pipe.recv"]
            # per step: M activation sends + M grad sends, mirrored recvs
            want = 2 * M * steps
            return (sends, recvs) if len(sends) >= want \
                and len(recvs) >= want else None

        deadline = time.time() + 30
        got = _spans()
        while got is None and time.time() < deadline:
            time.sleep(0.5)
            got = _spans()
        assert got is not None, "pipe.send/recv spans never surfaced"
        sends, recvs = got
        by_id = {s["span_id"]: s for s in sends}
        paired = 0
        for r in recvs:
            parent = by_id.get(r.get("parent_id"))
            if parent is None:
                continue
            paired += 1
            assert parent["mb"] == r["mb"]
            assert parent["pid"] != r["pid"], \
                "send/recv must sit on different stage processes"
        assert paired >= 2 * M * steps
        events = tracing.spans_to_chrome_events(sends + recvs)
        flow_s = {e["id"] for e in events if e.get("ph") == "s"}
        flow_f = {e["id"] for e in events if e.get("ph") == "f"}
        assert flow_s and flow_s == flow_f
        assert len(flow_s) >= 2 * M * steps
        # fwd/bwd compute spans carry the per-microbatch tags the
        # timeline groups by (the bubble is visible per microbatch)
        all_spans = tracing.get_spans()
        fwd = [s for s in all_spans if s["name"] == "pipe.fwd"]
        assert {(s["stage"], s["mb"]) for s in fwd} >= {
            (st, mb) for st in (0, 1) for mb in range(M)}

        # -- (3) failure: kill stage 1 and train on. The dead actor (or
        # its wedged neighbor) surfaces on the controller's wait-any; the
        # gang re-forms at a fresh channel generation and restores every
        # stage from its step-2 manifest --
        assert trainer.last_saved_step == 2
        for s in range(2):
            assert os.path.isdir(str(tmp_path / f"stage{s}")), \
                "per-stage ckpt store missing"
        ray_tpu.kill(trainer.actors[1])
        more = trainer.train(5)

        assert trainer.recoveries == 1
        assert trainer.restored_steps == [2], \
            "gang must resume from the step-2 per-stage manifests"
        assert trainer.step == 5
        # deterministic replay: the re-run of step 2 (restored state +
        # regenerated microbatches) reproduces the original loss exactly
        rerun_step2 = next(s for s in more if s["step"] == 2)
        np.testing.assert_allclose(rerun_step2["loss"], stats[2]["loss"],
                                   rtol=0, atol=1e-6)
        # restore fidelity: the post-recovery steps 3 and 4 STILL match
        # the single-mesh run — the per-stage manifests round-tripped
        # params AND optimizer state byte-faithfully
        ref_more = [ref_step(3), ref_step(4)]
        np.testing.assert_allclose(
            [s["loss"] for s in more if s["step"] in (3, 4)], ref_more,
            rtol=0, atol=1e-5)
        assert_param_parity()
    finally:
        trainer.shutdown()
        tracing.clear()


def test_interleaved_two_stage_parity_v2(cluster, tmp_path):
    """S=2, V=2 (4 virtual stages on 2 ranks, non-contiguous chunks):
    fp32 loss AND param parity vs the fused single-mesh step, plus a
    ckpt save/restore round trip through the chunked manifest layout."""
    import jax

    from ray_tpu.parallel.mesh import create_mesh, default_mesh_axes
    from ray_tpu.parallel.train import TrainStepBundle, make_optimizer

    cfg = _cfg(n_layers=4)
    M = 4
    pipe = PipelineConfig(num_stages=2, num_microbatches=M,
                          microbatch_size=2, seq_len=16,
                          clip_global_norm=1.0, virtual_stages=2,
                          ckpt_every=2, step_timeout_s=60.0)
    steps = 3
    trainer = PipelineTrainer(cfg, pipe, seed=9, run_name="ilv_parity",
                              ckpt_root=str(tmp_path))
    try:
        stats = trainer.train(steps)
        pipe_losses = [s["loss"] for s in stats]

        mesh = create_mesh(default_mesh_axes(8))
        bundle = TrainStepBundle(cfg, mesh, optimizer=make_optimizer(),
                                 donate=False)
        params = trainer.init_params
        opt_state = bundle.optimizer.init(params)

        def ref_step(step):
            nonlocal params, opt_state
            mbs = make_microbatches(cfg, pipe, 9, step)
            batch = {k: np.concatenate([m[k] for m in mbs])
                     for k in mbs[0]}
            params, opt_state, loss = bundle._fused_step(
                params, opt_state, batch)
            return float(loss)

        ref_losses = [ref_step(s) for s in range(steps)]
        np.testing.assert_allclose(pipe_losses, ref_losses, rtol=0,
                                   atol=1e-5)
        merged = trainer.merged_params()
        assert set(merged) == set(params)
        for k in sorted(params):
            for a, b in zip(jax.tree.leaves(params[k]),
                            jax.tree.leaves(merged[k])):
                np.testing.assert_allclose(
                    np.asarray(a, np.float64), np.asarray(b, np.float64),
                    rtol=0, atol=1e-5)

        # chunked-manifest layout: the ckpt_every=2 save committed per-rank
        # manifests nesting per virtual stage under ``chunks``, and the
        # chunk param keys across ranks re-merge to the full model's key
        # set (the V=1 kill/restore e2e covers gang recovery; re-forming a
        # second gang here would double this test's wall on the 1-core
        # tier — restore_ckpt's chunk-mismatch guard is unit-exercised by
        # reading the trees back directly)
        assert trainer.last_saved_step == 2
        from ray_tpu import ckpt as ckpt_plane

        seen_keys = set()
        for s in range(pipe.num_stages):
            store = ckpt_plane.CheckpointStore(
                str(tmp_path / f"stage{s}"), name=f"ilv_parity-s{s}")
            man = store.latest()
            assert man is not None and man.step == 2
            tree = ckpt_plane.restore_tree(store, man.ckpt_id)
            assert set(tree["chunks"]) == {str(v * 2 + s) for v in range(2)}
            for sub in tree["chunks"].values():
                seen_keys |= set(sub["params"])
        assert seen_keys == set(params)
    finally:
        trainer.shutdown()


# ---------------------------------------------------------------------------
# bench smoke (tier-1): the PIPE_r* harness runs end to end
# ---------------------------------------------------------------------------


def test_bench_pipeline_smoke(cluster, tmp_path):
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from ray_tpu.util import tracing
    from tools.bench_pipeline import main as bench_main

    out = str(tmp_path / "PIPE_smoke.json")
    # bench the untraced paths (the real PIPE_r* condition): the module
    # fixture's tracing would otherwise record a span a step and a hop
    # inside the timed loops
    tracing._enabled = False
    try:
        rows = bench_main(stages=(2,), microbatches=2, microbatch_size=1,
                          seq_len=16, steps=1, n_layers=2, out=out)
    finally:
        tracing._enabled = True
    names = {r["name"]: r["value"] for r in rows}
    assert names["single_mesh_tokens_per_s"] > 0
    assert names["pipeline_s2_tokens_per_s"] > 0
    assert names["pipeline_s2_activation_bytes_per_microbatch"] > 0
    # the reported bubble obeys the 1F1B bound
    assert names["pipeline_s2_bubble_fraction"] <= \
        names["pipeline_s2_bubble_bound"] + 1e-9
    assert os.path.exists(out)


def test_bucketed_stage_apply_matches_whole_tree(cluster, tmp_path):
    """PR 12: `bucket_bytes` routes a stage through the bucketed optimizer
    apply (per-bucket opt state, `pipe.bucket_apply` spans;
    `PipelineConfig.bucket_bytes` passes it to every stage). Adam-family
    transforms are per-leaf, so the bucketed apply must reproduce the
    whole-tree apply bit-for-bit — asserted on two single-stage actors fed
    IDENTICAL microbatches, one per mode."""
    import cloudpickle
    import flax.linen as nn
    import jax

    from ray_tpu.models.transformer import Transformer
    from ray_tpu.train.pipeline import schedule as sched
    from ray_tpu.train.pipeline.stage import PipelineStage
    from ray_tpu.weights import WeightStore

    cfg = _cfg()
    cfg_blob = cloudpickle.dumps(cfg)
    M = 2
    params = nn.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(7), np.zeros((1, 16), np.int32))["params"])
    store = WeightStore("bk_seed")
    store.publish({"params": params}, durable=True)
    stages = {
        label: PipelineStage.options(num_cpus=1).remote(
            0, 1, cfg_blob, None, f"bk_{label}", 0,
            bucket_bytes=bucket_bytes)
        for label, bucket_bytes in (("whole", None), ("bucketed", 4 << 10))
    }
    try:
        ray_tpu.get([a.init_weights.remote("bk_seed")
                     for a in stages.values()], timeout=120)
        ops = [list(op) for op in sched.build_schedule(1, M)[0]]
        mbs = make_microbatches(cfg, PipelineConfig(
            num_stages=1, num_microbatches=M, microbatch_size=2,
            seq_len=16), seed=11, step=0)
        results = ray_tpu.get(
            [a.run_schedule.remote(0, ops, mbs) for a in stages.values()],
            timeout=120)
        assert results[0]["losses"] == results[1]["losses"]
        ray_tpu.get([a.apply_grads.remote(1.0 / M)
                     for a in stages.values()], timeout=60)
        trees = ray_tpu.get([a.pull_params.remote()
                             for a in stages.values()], timeout=60)
        wl = jax.tree_util.tree_leaves(trees[0])
        bl = jax.tree_util.tree_leaves(trees[1])
        assert len(wl) == len(bl) and len(wl) > 4
        for a, b in zip(wl, bl):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    finally:
        for a in stages.values():
            try:
                ray_tpu.get(a.shutdown.remote(), timeout=10)
            except Exception:
                pass
            ray_tpu.kill(a)
        store.shutdown()


def test_stage_dp_group_bucketed_allreduce(cluster, tmp_path):
    """Two data-parallel replicas of a single-stage pipeline, fed
    DIFFERENT microbatches: run_schedule launches every grad bucket's
    allreduce asynchronously (overlapping the controller round-trip), and
    after apply_grads both replicas hold the IDENTICAL params — proof the
    cross-replica sum reached both sides."""
    import cloudpickle

    from ray_tpu.train.pipeline.stage import PipelineStage
    from ray_tpu.train.pipeline import schedule as sched
    from ray_tpu.weights import WeightStore

    cfg = _cfg()
    cfg_blob = cloudpickle.dumps(cfg)
    M = 2
    # seed one param tree both replicas pull (same init)
    import flax.linen as nn
    import jax

    from ray_tpu.models.transformer import Transformer

    params = nn.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(3), np.zeros((1, 16), np.int32))["params"])
    store = WeightStore("dp_bucket_seed")
    store.publish({"params": params}, durable=True)
    replicas = [
        PipelineStage.options(num_cpus=1).remote(
            0, 1, cfg_blob, None, f"dpb_r{r}", 0,
            bucket_bytes=4 << 10,
            dp_group={"name": "dpb", "world_size": 2, "rank": r,
                      "backend": "cpu"})
        for r in range(2)
    ]
    try:
        ray_tpu.get([a.ready.remote() for a in replicas], timeout=60)
        ray_tpu.get([a.init_weights.remote("dp_bucket_seed")
                     for a in replicas], timeout=120)
        ops = [list(op) for op in sched.build_schedule(1, M)[0]]
        refs = []
        for r, a in enumerate(replicas):
            mbs = make_microbatches(cfg, PipelineConfig(
                num_stages=1, num_microbatches=M, microbatch_size=2,
                seq_len=16), seed=100 + r, step=0)  # different data!
            refs.append(a.run_schedule.remote(0, ops, mbs))
        results = ray_tpu.get(refs, timeout=120)
        assert all(res["reduce_launched"] for res in results)
        sq = ray_tpu.get([a.grad_sqnorm.remote() for a in replicas],
                         timeout=60)
        assert sq[0] == pytest.approx(sq[1])  # both see the summed grads
        ray_tpu.get([a.apply_grads.remote(1.0 / (2 * M))
                     for a in replicas], timeout=60)
        trees = ray_tpu.get([a.pull_params.remote() for a in replicas],
                            timeout=60)
        la = jax.tree_util.tree_leaves(trees[0])
        lb = jax.tree_util.tree_leaves(trees[1])
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y))
    finally:
        for a in replicas:
            try:
                ray_tpu.get(a.shutdown.remote(), timeout=10)
            except Exception:
                pass
            ray_tpu.kill(a)
        store.shutdown()
