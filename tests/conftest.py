"""Test config: force a virtual 8-device CPU mesh before jax initializes.

Mirrors the reference's CPU test tier (SURVEY.md §4): all sharding/collective
tests run on xla_force_host_platform_device_count=8 so CI needs no TPUs.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# Persistent XLA compilation cache for the TEST tier only: the suite
# compiles the same tiny-model programs over and over in fresh processes
# (train/pipeline/rl actors, isolated-subprocess tests, spawned workers
# inherit this env) — cache hits turn those recompiles into loads. Scoped
# per interpreter version under /tmp; harmless if the backend declines it.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import sys as _sys

    _cache = f"/tmp/ray_tpu_test_jax_cache_py{_sys.version_info[0]}{_sys.version_info[1]}"
    os.makedirs(_cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

# let spawned worker processes import functions defined in test modules
_tests_dir = os.path.dirname(os.path.abspath(__file__))
_pp = os.environ.get("PYTHONPATH", "")
if _tests_dir not in _pp.split(":"):
    os.environ["PYTHONPATH"] = f"{_tests_dir}:{_pp}" if _pp else _tests_dir

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "isolated: run this test in a fresh subprocess (native-heap "
        "protection: a jax/arrow segfault there cannot kill the suite)")


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_protocol(item, nextitem):
    """Run @pytest.mark.isolated tests in a fresh interpreter.

    The one known suite-killer is a native-heap interaction between jax/XLA
    and pyarrow that needs ~25 min of accumulated in-process state and then
    segfaults PYTEST itself (README "Known issues"). Subprocess isolation
    keeps `pytest tests/ -q` a single green command: the child's verdict is
    reported through normal TestReports, and a child crash becomes a plain
    test failure instead of a dead suite."""
    if (item.get_closest_marker("isolated") is None
            or os.environ.get("RAY_TPU_TEST_IN_SUBPROCESS")):
        return None  # default protocol

    import subprocess
    import sys
    from _pytest.reports import TestReport

    hook = item.ihook
    hook.pytest_runtest_logstart(nodeid=item.nodeid, location=item.location)
    env = dict(os.environ, RAY_TPU_TEST_IN_SUBPROCESS="1")
    start = __import__("time").time()
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-x", "--no-header",
             item.nodeid],
            cwd=str(item.config.rootpath), env=env,
            capture_output=True, text=True, timeout=900)
        rc, out, err = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        rc = -1
        out = (e.stdout or b"").decode() if isinstance(e.stdout, bytes) \
            else (e.stdout or "")
        err = (e.stderr or b"").decode() if isinstance(e.stderr, bytes) \
            else (e.stderr or "")
        err += "\n[isolated subprocess timed out after 900s]"
    dur = __import__("time").time() - start
    if rc == 0 and " skipped" in out and " passed" not in out:
        # the child ran but skipped (pytest still exits 0): report a skip,
        # not a phantom pass
        outcome = "skipped"
        longrepr = (str(item.fspath), item.location[1] or 0,
                    f"skipped in isolated subprocess:\n{out[-1500:]}")
    elif rc == 0:
        outcome, longrepr = "passed", None
    else:
        outcome = "failed"
        longrepr = (f"isolated subprocess exited rc={rc}\n"
                    f"--- stdout (tail) ---\n{out[-6000:]}\n"
                    f"--- stderr (tail) ---\n{err[-3000:]}")
    reports = [
        TestReport(item.nodeid, item.location, {}, "passed", None,
                   "setup", duration=0.0),
        TestReport(item.nodeid, item.location, {}, outcome, longrepr,
                   "call", duration=dur, start=start, stop=start + dur),
        TestReport(item.nodeid, item.location, {}, "passed", None,
                   "teardown", duration=0.0),
    ]
    for rep in reports:
        hook.pytest_runtest_logreport(report=rep)
    hook.pytest_runtest_logfinish(nodeid=item.nodeid, location=item.location)
    # the default protocol ends every item with teardown_exact(nextitem),
    # popping module/class fixtures the next item doesn't need. Skipping it
    # here leaves the previous module's finalizers on the setup stack and
    # the NEXT file's first test dies with "previous item was not torn
    # down properly".
    try:
        item.session._setupstate.teardown_exact(nextitem)
    except Exception:
        pass
    return True


@pytest.fixture
def ray_local():
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(local_mode=True)
    yield ray_tpu
    ray_tpu.shutdown()
