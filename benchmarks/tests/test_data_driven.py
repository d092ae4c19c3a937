"""A new cell is data: one ``workloads`` entry plus, at most, new files under
``configs/``, ``traffic/``, ``layer_metrics/`` and ``architectures/`` found by
name. Shown with a dummy configuration, mix, metrics and architecture in a
temporary directory: no file of ``benchmarks/`` is edited, and the harness
reads them."""

import json
import os

import pytest

from benchmarks import registry, traffic
from benchmarks.registry import Cell, peak_for

# an architecture nobody has seen: its module answers the harness's eight
# questions for a "model" that is one matrix, and counts one kernel
DUMMY_ARCHITECTURE = '''
def program_overrides(cfg, max_seq_len):
    return {"d_model": cfg["width"], "max_seq_len": max_seq_len}

def reference_cfg(cfg):
    return {"width": cfg["width"]}

def to_reference_params(params, cfg):
    return {"w": params["matrix"]}

def forward(params, tokens, rcfg):
    return params["w"][tokens]

def loss(params, tokens, targets, rcfg):
    return forward(params, tokens, rcfg).sum()

def train_flops_per_token(cfg, seq_len):
    return 6.0 * cfg["width"] * cfg["vocab_size"]

def total_params(cfg):
    return cfg["width"] * cfg["vocab_size"]

def kernel_cost(kernel, cfg, facts):
    if kernel != "lookup":
        raise KeyError(kernel)
    return 0.0, 2.0 * cfg["width"] * facts["max_num_seqs"]
'''


@pytest.fixture
def dummy(tmp_path):
    root = tmp_path / "bench"
    for d in ("configs", "traffic", "layer_metrics", "architectures"):
        (root / d).mkdir(parents=True)
    (root / "configs" / "dummy.json").write_text(json.dumps({
        "name": "dummy", "adapter": "one_matrix", "width": 4096,
        "vocab_size": 1000, "job": {"kind": "serve"}}))
    (root / "architectures" / "one_matrix.py").write_text(DUMMY_ARCHITECTURE)
    (root / "architectures" / "half_done.py").write_text(
        DUMMY_ARCHITECTURE.replace("def kernel_cost", "def _kernel_cost"))
    (root / "traffic" / "dummy-mix.json").write_text(json.dumps({
        "kind": "serve", "arrival": {"rate_per_s": 8.0},
        "prompt_tokens": {"dist": "lognormal", "median": 100, "sigma": 1.0,
                          "min": 16, "max": 1900},
        "max_tokens": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                       "min": 2, "max": 64},
        "temperature": 0.0, "lead_s": 1.0}))
    (root / "layer_metrics" / "dummy.wait_ms.json").write_text(json.dumps({
        "reduce": "span_quantile", "args": {"span": "wait_s", "q": 0.5, "scale": 1000.0}}))
    (root / "layer_metrics" / "dummy.absent.json").write_text(json.dumps({
        "reduce": "module_ms_per_exec", "args": {"module": "jit_nothing"}}))
    (root / "layer_metrics" / "lookup_roofline.json").write_text(json.dumps({
        "reduce": "roofline_share_percent",
        "args": {"op": "^lookup ", "kernel": "lookup"}}))
    bench = {
        "run_seconds": 5,
        "configs": [{"name": "dummy", "file": "bench/configs/dummy.json"}],
        "workloads": [{"name": "dummy.dummy-mix", "config": "dummy",
                       "traffic": "dummy-mix", "chips": 1}],
        "end_to_end": [{"name": "dummy_e2e", "unit": "ms"},
                       {"name": "other_e2e", "unit": "ms", "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "dummy.wait_ms", "unit": "ms"},
                      {"name": "dummy.absent", "unit": "ms"},
                      {"name": "lookup_roofline", "unit": "%"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return Cell("dummy.dummy-mix", str(tmp_path / "BENCHMARK.json"), root=str(root))


def test_cell_finds_its_files_by_name(dummy):
    assert dummy.config["vocab_size"] == 1000
    assert dummy.mix["arrival"]["rate_per_s"] == 8.0
    assert [m["name"] for m in dummy.end_to_end()] == ["dummy_e2e"]
    ctx = {"trace": None, "spans": {"wait_s": [0.001, 0.003, 0.002]},
           "counters": {}, "facts": {}}
    # the metric with nothing to read is left out, not reported as 0
    assert dummy.per_layer_values(ctx) == {
        "dummy.wait_ms": {"value": 2.0, "unit": "ms"}}


def test_a_configuration_brings_its_architecture(dummy):
    """The module is found by the configuration's ``"adapter"``; a metric
    over one of its kernels is a file naming the reduction, the operation and
    the kernel."""
    arch = dummy.architecture()
    assert arch.program_overrides(dummy.config, 128) == {
        "d_model": 4096, "max_seq_len": 128}
    assert arch is registry.architecture(dummy.config, dummy.root)   # loaded once
    # 3 calls of the kernel on each device took 30 us; a call moves
    # 2 x 4096 x 8 bytes = 65,536, 0.08 us at 819 GB/s: 0.8 % of its roofline
    ctx = {"trace": {"modules": {},
                     "op_kinds": {"lookup f32[8,4096]": [30e-6, 3.0],
                                  "lookup_table f32[1]": [1.0, 1.0]}},
           "spans": {}, "counters": {},
           "facts": {"max_num_seqs": 8, "peak_flops_per_s": 197e12,
                     "peak_hbm_bytes_per_s": 819e9}}
    assert dummy.per_layer_values(ctx) == {"lookup_roofline": {
        "value": pytest.approx(100 * 3 * (65536 / 819e9) / 30e-6), "unit": "%"}}


def test_an_unknown_or_unfinished_architecture_fails_by_name(dummy):
    with pytest.raises(SystemExit, match=r"'two_matrices'.*half_done.*one_matrix"):
        registry.architecture({"name": "x", "adapter": "two_matrices"}, dummy.root)
    with pytest.raises(SystemExit, match=r"'half_done'.*lacks \['kernel_cost'\]"):
        registry.architecture({"name": "x", "adapter": "half_done"}, dummy.root)
    # a file that names none is a dense decoder, which this root does not have
    with pytest.raises(SystemExit, match="'dense_decoder'"):
        registry.architecture({"name": "x"}, dummy.root)


def _window(rs, seconds):
    return [r for r in rs if 0 <= r.due_s < seconds]


def _sizes(rs):
    return [(len(r.prompt), r.max_tokens) for r in rs]


def test_general_generator_reads_the_dummy_mix(dummy):
    a = traffic.serve_schedule(dummy.mix, 1, 5.0, 1000)
    b = traffic.serve_schedule(dummy.mix, 2 ** 31 + 5, 5.0, 1000)
    assert len(_window(a, 5.0)) == len(_window(b, 5.0)) == 40   # 8/s x 5 s
    assert -1.0 <= a[0].due_s < 0 and max(r.due_s for r in a) < 5.0
    assert all(0 <= t < 1000 for r in a for t in r.prompt)
    assert traffic.serve_schedule(dummy.mix, 1, 5.0, 1000) == a


def test_every_seed_offers_the_same_requests_in_another_order():
    mix = traffic.load_mix("alpaca-saturated")
    seconds = 45.0
    runs = [traffic.serve_schedule(mix, s, seconds, 92544)
            for s in (3, 4, 2 ** 31 + 9)]
    n = int(round(mix["arrival"]["rate_per_s"] * seconds))
    wins = [_window(rs, seconds) for rs in runs]
    assert all(len(w) == n for w in wins)
    # the window holds the same prompt lengths, the same answer lengths and
    # the same gaps for every seed ...
    for pick in (lambda r: len(r.prompt), lambda r: r.max_tokens):
        assert len({tuple(sorted(map(pick, w))) for w in wins}) == 1
    gaps = [sorted(round(y - x.due_s, 6) for x, y in
                   zip(w, [r.due_s for r in w[1:]] + [seconds])) for w in wins]
    assert gaps[0] == gaps[1] == gaps[2] and gaps[0][0] < 0.01 < 0.5 < gaps[0][-1]
    # ... paired and ordered by the seed: another seed, another sequence
    assert len({tuple(_sizes(w)) for w in wins}) == 3
    assert wins[0][0].prompt != wins[1][0].prompt
    # the sizes have the means the mix's source publishes
    assert abs(sum(len(r.prompt) for r in wins[0]) / n - 19.31) < 0.5
    assert abs(sum(r.max_tokens for r in wins[0]) / n - 58.45) < 0.5
    for rs in runs:
        assert all(4 <= len(r.prompt) <= 128 and 4 <= r.max_tokens <= 512 for r in rs)
        assert all(x.due_s <= y.due_s for x, y in zip(rs, rs[1:]))
        assert -mix["lead_s"] <= rs[0].due_s < 0
        # a mix that abandons its backlog offers nothing after the window
        assert rs[-1].due_s < seconds
        assert len([r for r in rs if r.due_s < 0]) == int(
            round(mix["arrival"]["rate_per_s"] * mix["lead_s"]))


def test_a_mix_that_drains_goes_on_offering_after_the_window():
    mix = traffic.load_mix("chat-toy")
    a = traffic.serve_schedule(mix, 7, 6.0, 512)
    after = [r for r in a if r.due_s >= 6.0]
    assert len(after) == int(round(mix["arrival"]["rate_per_s"] * mix["drain_s"]))
    assert 6.0 <= after[0].due_s and a[-1].due_s < 6.0 + mix["drain_s"]


def test_warmup_covers_exactly_the_reachable_buckets():
    mix = traffic.load_mix("alpaca-saturated")
    assert traffic.serve_warmup_lengths(mix, 256, 2048) == [248]
    assert traffic.serve_warmup_lengths(mix, 64, 2048) == [56, 120]
    long = dict(mix, prompt_tokens=dict(mix["prompt_tokens"], median=256,
                                        sigma=0.9, max=1536))
    assert traffic.serve_warmup_lengths(long, 256, 2048) == [248, 504, 1016, 2039]


def test_unknown_device_kind_fails():
    assert peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        peak_for("TPU v9 imaginary")
    with pytest.raises(SystemExit):
        peak_for("_source")


def test_every_cell_of_benchmark_json_resolves():
    from benchmarks.registry import REPO

    path = os.path.join(REPO, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = Cell(w["name"], path)
        arch = cell.architecture()
        assert all(callable(getattr(arch, m)) for m in registry.MEMBERS)
        job = cell.config["job"]
        shapes = (traffic.train_shape(cell.mix, job) if job["kind"] == "train"
                  else {})
        names = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in names and len(names) >= 2
        layer = cell.per_layer()
        assert layer
        for m in layer:
            # the metric's own file says how it is read and nothing that
            # BENCHMARK.json already says
            reader = cell.reader(m["name"])
            assert set(reader) <= {"reduce", "args"}
            assert m["moves"] in e2e and m["moves"] in names
            # a kernel's share names a kernel its cell's architecture counts,
            # from the shapes this cell's run knows
            if reader["reduce"] == "roofline_share_percent":
                ops, nbytes = arch.kernel_cost(reader["args"]["kernel"],
                                               cell.config, shapes)
                assert ops > 0 and nbytes > 0
