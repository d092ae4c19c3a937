"""Tier-1 smoke for tools/benchtrack.py: the bench-artifact regression
gate must be green on the repo's checked-in artifacts, and must actually
FIRE on a synthetic regressed artifact (a gate that can't fail guards
nothing)."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from tools import benchtrack  # noqa: E402


def test_check_green_on_repo_artifacts():
    """The tier-1 wiring: every checked-in STRESS/SERVE/PIPE/OBS artifact
    clears its per-metric threshold (and the OBS absolute overhead bars).
    No BENCH artifact is checked in: the tree holds no chip record yet (the
    ledger the driver writes will be it); the BENCH extractor stays tested
    on synthetic artifacts below."""
    failures, passes = benchtrack.check(str(REPO_ROOT))
    assert not failures, "\n".join(failures)
    # the gate saw real artifacts, it did not vacuously pass on nothing
    assert len(passes) >= 10
    families = {line.split()[0] for line in passes}
    assert {"STRESS", "SERVE", "PIPE", "OBS"} <= families


def test_cli_check_exit_codes(tmp_path):
    """`--check` exits 0 on the repo and 1 on a regressed artifact set."""
    out = subprocess.run(
        [sys.executable, os.path.join("tools", "benchtrack.py"), "--check"],
        capture_output=True, text=True, timeout=120, cwd=str(REPO_ROOT))
    assert out.returncode == 0, out.stdout + out.stderr

    (tmp_path / "SERVE_r01.json").write_text(json.dumps(
        {"ttft_p99_ms": 230.0, "latency_p99_ms": 300.0,
         "tokens_per_s": 200.0, "dropped_requests": 0}))
    (tmp_path / "SERVE_r02.json").write_text(json.dumps(
        {"ttft_p99_ms": 500.0, "latency_p99_ms": 310.0,
         "tokens_per_s": 205.0, "dropped_requests": 0}))
    out = subprocess.run(
        [sys.executable, os.path.join("tools", "benchtrack.py"), "--check",
         "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=str(REPO_ROOT))
    assert out.returncode == 1, out.stdout + out.stderr
    assert "ttft_p99_ms" in out.stdout and "FAIL" in out.stdout


def test_regression_directions(tmp_path):
    """Direction-aware thresholds: an MFU drop (higher-better) and a TTFT
    blowup (lower-better) both fire; improvements never do."""
    def bench(n, mfu):
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(
            {"n": n, "parsed": {"metric": "train_mfu_1b", "value": mfu,
                                "step_time_s": 0.5}}))

    bench(1, 0.46)
    bench(2, 0.40)  # -13% > the 5% MFU threshold
    failures, _ = benchtrack.check(str(tmp_path))
    assert any("train_mfu_1b" in f for f in failures), failures

    bench(2, 0.47)  # improvement: green
    failures, passes = benchtrack.check(str(tmp_path))
    assert not failures, failures
    assert any("train_mfu_1b" in p for p in passes)


def test_obs_absolute_bar_fires_without_history(tmp_path):
    """The observability <=5% overhead contract is an ABSOLUTE bar: a
    single round over it fails even with no prior round to compare."""
    (tmp_path / "OBS_r01.json").write_text(json.dumps(
        {"events_delta_pct": 7.2, "train_step_delta_pct": 1.0}))
    failures, _ = benchtrack.check(str(tmp_path))
    assert any("events_delta_pct" in f and "absolute bar" in f
               for f in failures), failures


def test_pipe_analytic_floor_metadata_fires(tmp_path):
    """A PIPE row carrying ``meta.floor`` (the analytic bubble bound) is
    held to it absolutely — a simulated bubble below the bound means the
    measurement lied, even with no prior round."""
    (tmp_path / "PIPE_r01.json").write_text(json.dumps(
        [{"name": "pipeline_s2_bubble_fraction", "value": 0.05,
          "unit": "fraction", "meta": {"floor": 0.1111}}]))
    failures, _ = benchtrack.check(str(tmp_path))
    assert any("analytic floor" in f for f in failures), failures

    (tmp_path / "PIPE_r01.json").write_text(json.dumps(
        [{"name": "pipeline_s2_bubble_fraction", "value": 0.1111,
          "unit": "fraction", "meta": {"floor": 0.1111}}]))
    failures, _ = benchtrack.check(str(tmp_path))
    assert not failures, failures


def test_pipe_host_envelope_rebaselines(tmp_path):
    """Rounds measured on different host envelopes (config row's
    ``meta.host_cpus``) never price round-over-round moves against each
    other; same-envelope rounds still gate."""
    def pipe(n, tps, cpus=None):
        rows = [{"name": "pipeline_s2_tokens_per_s", "value": tps,
                 "unit": "tokens/s"}]
        if cpus is not None:
            rows.append({"name": "config", "value": 0, "unit": "meta",
                         "meta": {"host_cpus": cpus}})
        (tmp_path / f"PIPE_r{n:02d}.json").write_text(json.dumps(rows))

    pipe(1, 9000.0)            # legacy round, unknown envelope
    pipe(2, 900.0, cpus=1)     # 10x "drop" on a 1-core box: re-baseline
    failures, passes = benchtrack.check(str(tmp_path))
    assert not failures, failures
    assert any("host envelope changed" in p for p in passes), passes

    pipe(3, 500.0, cpus=1)     # same envelope: the relative gate fires
    failures, _ = benchtrack.check(str(tmp_path))
    assert any("tokens_per_s" in f for f in failures), failures


def test_trajectory_normalizes_heterogeneous_schemas(tmp_path):
    """BENCH nests under `parsed`, PIPE is a list of name/value entries,
    STRESS is flat — all land in the one trajectory schema, rounds
    ascending, foreign JSON skipped."""
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"parsed": {"metric": "train_mfu_1b", "value": 0.45}}))
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"parsed": {"metric": "train_mfu_1b", "value": 0.44}}))
    (tmp_path / "PIPE_r01.json").write_text(json.dumps(
        [{"name": "pipeline_s2_bubble_fraction", "value": 0.11,
          "unit": "fraction"},
         {"name": "pipeline_s2_tokens_per_s", "value": 8700.0,
          "unit": "tok/s"}]))
    (tmp_path / "STRESS_r01.json").write_text(json.dumps(
        {"tasks_per_s": 2358.6, "mode": "smoke"}))
    (tmp_path / "NOT_A_BENCH.json").write_text("{}")
    (tmp_path / "BENCH_r03.json").write_text("not json at all")

    traj = benchtrack.load_trajectory(str(tmp_path))
    assert set(traj) == {"BENCH", "PIPE", "STRESS"}
    assert [r["round"] for r in traj["BENCH"]] == [1, 2]
    assert traj["PIPE"][0]["metrics"] == {
        "pipeline_s2_bubble_fraction": 0.11,
        "pipeline_s2_tokens_per_s": 8700.0}
    assert traj["STRESS"][0]["metrics"] == {"tasks_per_s": 2358.6}
