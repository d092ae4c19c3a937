"""``benchmarks/trace/reduce.py``: the interval arithmetic on made-up events
whose answers are plain, and the whole reduction on a trace recorded on the
chip (``fixtures/``, from PR 23's first traced run; numbers worked out once by
hand from a dump of that file)."""

import json
import os
import types

import pytest

from benchmarks import registry
from benchmarks.trace import reduce

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "trace", "fixtures")


def counts(**members):
    """``ctx["architecture"]`` of a made-up architecture with these counts."""
    return lambda: types.SimpleNamespace(**members)


def test_union_subtract_length():
    u = reduce.union([(0, 10), (5, 15), (20, 30), (30, 31), (40, 40)])
    assert u == [(0, 15), (20, 31)]
    assert reduce.length(u) == 26
    assert reduce.subtract([(0, 100)], u) == [(15, 20), (31, 100)]
    assert reduce.subtract(u, [(3, 4), (14, 25)]) == [(0, 3), (4, 14), (25, 31)]
    assert reduce.subtract(u, []) == u


def test_leaves_drop_parents_only():
    ev = [("while", 0, 100), ("fusion.1", 0, 40), ("fusion.2", 50, 100),
          ("copy", 120, 130), ("sub-ns marker", 120, 120)]
    assert [e[0] for e in reduce.leaves(ev)] == ["fusion.1", "fusion.2", "copy"]


def test_gap_attribution_innermost_span_wins():
    spans = [("engine.step", 0, 100), ("dispatch_decode", 10, 30),
             ("sample_readback", 60, 90)]
    seg = reduce.innermost_segments(spans)
    assert seg == [("engine.step", 0, 10), ("dispatch_decode", 10, 30),
                   ("engine.step", 30, 60), ("sample_readback", 60, 90),
                   ("engine.step", 90, 100)]
    acc = reduce.attribute([(5, 20), (70, 80), (95, 120)], seg)
    assert acc == {"engine.step": 5 + 5, "dispatch_decode": 10,
                   "sample_readback": 10, "(no span)": 20}


def test_summary_of_made_up_planes():
    ms = 1_000_000
    planes = {"devices": {
        "/device:TPU:0": {
            "modules": [("jit_train_step(1)", 0, 40 * ms),
                        ("jit_train_step(1)", 50 * ms, 90 * ms)],
            "ops": [("fusion.1", 0, 30 * ms),
                    ("all-gather-done.2", 30 * ms, 40 * ms),
                    ("fusion.1", 50 * ms, 80 * ms),
                    ("all-gather-done.2", 75 * ms, 90 * ms)]}},
        "annotations": [("batch_made", 40 * ms, 45 * ms),
                        ("step_dispatched", 45 * ms, 50 * ms)]}
    s = reduce.summarize_planes(planes)
    assert s["window_s"] == pytest.approx(0.090)
    assert s["busy_s"] == pytest.approx(0.080)
    # collectives ran 10 + 15 ms, of which 10 + 10 with nothing else running
    assert s["collective_s"] == pytest.approx(0.025)
    assert s["exposed_collective_s"] == pytest.approx(0.020)
    assert s["modules"]["jit_train_step"] == {"count": 2, "total_s": pytest.approx(0.080)}
    assert dict(map(tuple, s["idle_gaps"])) == {
        "batch_made": pytest.approx(0.005), "step_dispatched": pytest.approx(0.005)}
    ctx = {"trace": s, "spans": {}, "counters": {}, "facts": {}}
    assert reduce.module_ms_per_exec(ctx, "jit_train_step") == pytest.approx(40.0)
    assert reduce.exposed_collective_ms_per_exec(ctx, "jit_train_step") == pytest.approx(10.0)
    assert reduce.idle_share_percent(ctx) == pytest.approx(100 / 9)
    assert reduce.module_ms_per_exec(ctx, "jit_prefill") is None


def test_readers_return_none_when_there_is_nothing_to_read():
    ctx = {"trace": None, "spans": {"x": []}, "counters": {}, "facts": {}}
    for fn, args in [(reduce.idle_share_percent, {}),
                     (reduce.module_ms_per_exec, {"module": "m"}),
                     (reduce.span_quantile, {"span": "x", "q": 0.9}),
                     (reduce.span_value, {"span": "y"}),
                     (reduce.counter_ratio, {"num": "a", "den": "b"}),
                     (reduce.mfu_percent, {"module": "m"}),
                     (reduce.device_op_ms_per_exec, {"op": "^k", "module": "m"}),
                     (reduce.roofline_share_percent, {"op": "^k", "kernel": "k"})]:
        assert fn(ctx, **args) is None


def test_quantile_is_nearest_rank():
    vals = list(range(1, 71))
    assert reduce.quantile(vals, 0.9) == 63
    assert reduce.quantile([5.0], 0.9) == 5.0
    assert reduce.quantile([1, 2, 3, 4], 0.5) == 2.5


def test_mfu_uses_the_traced_window():
    ctx = {"trace": {"window_s": 4.0, "modules": {"jit_train_step": {"count": 10, "total_s": 3.9}}},
           "facts": {"seq_len": 2048, "tokens_per_step": 8192, "chips": 1,
                     "peak_flops_per_s": 197e12},
           "config": {"billions": 4},
           "architecture": counts(
               train_flops_per_token=lambda cfg, seq: cfg["billions"] * 1e9 * seq / 2048)}
    assert reduce.mfu_percent(ctx, "jit_train_step") == pytest.approx(
        100 * 4e9 * (10 * 8192 / 4.0) / 197e12)


def test_operations_are_read_by_name_on_four_devices():
    """Two kinds of one kernel (two shapes) and a kind that only shares its
    prefix, on four devices that each ran two steps; one device ran a call
    less (a trace cut mid-step)."""
    us = 1_000
    per_device = [("%attn_fwd.1 = bf16[8,128]{1,0} custom-call(%q)", 0, 300 * us),
                  ("%attn_fwd.2 = bf16[8,128]{1,0} custom-call(%q)", 400 * us, 700 * us),
                  ("%attn_fwd.3 = bf16[8,256]{1,0} custom-call(%q)", 800 * us, 1000 * us),
                  ("%attn_fwd_prep.4 = bf16[8]{0} fusion(%q)", 1000 * us, 1900 * us)]
    planes = {"devices": {
        f"/device:TPU:{i}": {
            "modules": [("jit_step(7)", 0, 1000 * us), ("jit_step(7)", 1000 * us, 2000 * us)],
            "ops": per_device[1 if i == 3 else 0:]} for i in range(4)},
        "annotations": []}
    s = reduce.summarize_planes(planes)
    assert s["devices"] == 4 and s["modules"]["jit_step"]["count"] == 2
    assert s["op_kinds"]["attn_fwd bf16[8,128]"] == [pytest.approx(525e-6), 1.75]
    assert s["op_kinds"]["attn_fwd bf16[8,256]"] == [pytest.approx(200e-6), 1.0]
    # every kind is kept, the printed table stays the top ten
    assert len(s["op_kinds"]) == 3 and len(s["device_ops"]) == 3
    ctx = {"trace": s, "spans": {}, "counters": {}, "config": {},
           "facts": {"peak_flops_per_s": 100e12, "peak_hbm_bytes_per_s": 1e12},
           "architecture": counts(kernel_cost=lambda kernel, cfg, facts: {
               "attn_fwd": (5e9, 1e6), "streamed": (1e6, 80e6)}[kernel])}
    # "^attn_fwd " takes both shapes and leaves attn_fwd_prep out:
    # 725 us a device over 2 steps
    assert reduce.device_op_ms_per_exec(ctx, "^attn_fwd ", "jit_step") == pytest.approx(0.3625)
    assert reduce.device_op_ms_per_exec(ctx, "^attn_fwd", "jit_step") == pytest.approx(0.8125)
    assert reduce.device_op_ms_per_exec(ctx, "^attn_fwd ", "jit_other") is None
    assert reduce.device_op_ms_per_exec(ctx, "^attn_bwd ", "jit_step") is None
    # 2.75 calls a device; a call needs 5e9 / 100e12 = 50 us of compute
    # (its bytes 1 us): 137.5 of 725 us
    assert reduce.roofline_share_percent(ctx, "^attn_fwd ", "attn_fwd") == \
        pytest.approx(100 * 137.5 / 725)
    # bound by its bytes: 80e6 / 1e12 = 80 us against 0.01 us of compute
    assert reduce.roofline_share_percent(ctx, r"^attn_fwd bf16\[8,256\]", "streamed") == \
        pytest.approx(100 * 80 / 200)
    assert reduce.roofline_share_percent(ctx, "^attn_bwd ", "attn_fwd") is None


def test_op_kind_folds_instances_and_layouts():
    a = "%fusion.1835 = bf16[4,2048,8192]{2,1,0:T(8,128)(2,1)} fusion(bf16[4,2048,2048]{2,1,0} %x), kind=kOutput"
    b = "%fusion.99 = bf16[4,2048,8192]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[4,2048,2048]{2,1,0} %y), kind=kOutput"
    assert reduce.op_kind(a) == reduce.op_kind(b) == "fusion bf16[4,2048,8192]"
    t = "%attn.53 = (bf16[128,2048,64]{2,1,0:T(8,128)(2,1)}, bf16[128,2048,64]{2,1,0}) custom-call(bf16[128,2048,64]{2,1,0} %q)"
    assert reduce.op_kind(t) == "attn (bf16[128,2048,64], bf16[128,2048,64])"
    assert reduce.module_name("jit_train_step(15725366251900410696)") == "jit_train_step"
    assert reduce.COLLECTIVE.match("%all-gather-done.2 = f32[8]{0} all-gather-done(...)")
    assert not reduce.COLLECTIVE.match("%fusion.2 = f32[8]{0} fusion(%all-gather.1)")


FIXTURE = os.path.join(FIXTURES, "smollm2_train_2steps.xplane.pb")


def test_recorded_chip_trace():
    """Two steps of cell 1 (SmolLM2-1.7B, 8 layers, b4 x 2048) cut from PR 23's
    first traced chip run; device lines and the benchmark's annotations only.
    The expected numbers were read straight from the protobuf (picoseconds,
    summed event by event) when the fixture was cut, not with this reducer."""
    s = reduce.summarize(FIXTURE)
    assert s["devices"] == 1
    # XLA Modules: 2 executions, 807,015,477,656 ps
    m = s["modules"]["jit_train_step"]
    assert m["count"] == 2 and m["total_s"] == pytest.approx(0.807015478, rel=1e-6)
    # XLA Ops: 5012 events, none nested or overlapping, 806,946,116,556 ps;
    # the reader truncates each to whole nanoseconds (<= 5012 ns in all)
    assert s["busy_s"] == pytest.approx(0.806946117, abs=6e-6)
    # first module event starts at 48,679,700,500 ps, last op ends at
    # 855,699,915,578 ps, the second module at 855,701,801,828 ps
    assert s["window_s"] == pytest.approx(0.807022101, abs=2e-9)
    assert s["collective_s"] == 0 and s["exposed_collective_s"] == 0
    ops = dict(map(tuple, s["device_ops"]))
    # 48 attention custom-calls (8 layers x [fwd, remat fwd, dq, dkv] x 2
    # steps... counted by name prefix %attn), 328,781,845,394 ps
    attn = sum(v for k, v in ops.items() if k.startswith("attn "))
    assert attn == pytest.approx(0.328781845, abs=1e-6)
    gaps = dict(map(tuple, s["idle_gaps"]))
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"], abs=1e-9)
    assert s["annotations"] == 12
    with open(os.path.join(os.path.dirname(FIXTURES), os.pardir, "configs",
                           "smollm2-1.7b.json")) as f:
        config = json.load(f)
    ctx = {"trace": s, "spans": {}, "counters": {}, "config": config,
           "architecture": lambda: registry.architecture(config),
           "facts": {"seq_len": 2048, "per_chip_batch": 4, "tokens_per_step": 8192,
                     "chips": 1, "peak_flops_per_s": 197e12,
                     "peak_hbm_bytes_per_s": 819e9}}
    assert reduce.module_ms_per_exec(ctx, "jit_train_step") == pytest.approx(403.5077, abs=1e-3)
    assert reduce.idle_share_percent(ctx) == pytest.approx(0.0094, abs=1e-3)
    # 2 x 8192 tokens in 0.807022 s = 20,301.8 tokens/s -> 41.50 % of 197 TFLOP/s
    assert reduce.mfu_percent(ctx, "jit_train_step") == pytest.approx(41.497, abs=1e-2)
    # the three attention kernels, all named ``attn`` when this was recorded
    # and told apart by what they return: 16 calls each (8 layers x 2 steps:
    # one forward a layer, not two) in 110.48 / 91.09 / 127.21 ms, so 6.905 /
    # 5.693 / 7.951 ms a call against 0.349 / 0.523 / 0.698 ms of required
    # compute (test_dense_decoder.py) at 197 TFLOP/s
    assert reduce.device_op_ms_per_exec(ctx, "^attn ", "jit_train_step") == \
        pytest.approx(164.391, abs=1e-3)
    for op, kernel, share in [
            (r"^attn \(bf16\[128,2048,64\], f32", "flash_fwd", 5.054),
            (r"^attn bf16", "flash_bwd_dq", 9.195),
            (r"^attn \(bf16\[128,2048,64\], bf16", "flash_bwd_dkv", 8.779)]:
        assert reduce.roofline_share_percent(ctx, op, kernel) == \
            pytest.approx(share, abs=1e-3)
