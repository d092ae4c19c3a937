"""A chip test: ``flash_fwd`` told its rows' lengths (``ops/attention.py``,
PR 54) at the serve cells' shapes, against the same kernel without them.

What the CPU tests cannot show: that Mosaic takes the call with the lengths
prefetched, that a real position's output is the call's without lengths to
the bit ON THE CHIP, and what a program that is passed over costs. Three
shapes, each at several sets of lengths:

- ``bf16[48, 8192, 128]`` (cell 8's ``[1, 8192]``: 48 heads of 128) at 8,192
  / 6,144 / 4,608 positions: the kernel's device time against the time at
  the full bucket may not pass the share of visited key blocks (16 query
  blocks of 512: 136, 78 and 45 visits) by more than ``MARGIN``: a query
  block's own cost (its fetch, its masked diagonal block, its write) falls
  with the blocks kept, 12 and 9 of 16, not with the visits, and a head's
  whole-bucket K and V are fetched whatever the length (my chip run, PR 54:
  7.69 ms without lengths, 7.81 / 4.97 / 3.16 with: 0.636 and 0.404 of the
  full bucket against 0.574 and 0.331 of the visits); the full bucket with
  lengths may not cost more than ``SAME`` over the call without;
- ``[4, 2048]`` of 32 heads of 64 with a padding row, a full row, a row
  ending ON a block's edge and one a position past it;
- ``[1, 4096]`` of 16 latent heads (keys of 192, values of 128) under a
  length of 2,100, and 40 heads of 64 under a window of 512;
- the serve cells' reference check's own call in cell 6
  (``benchmarks/jobs/serve.py:reference_check``): ``[32, 512]`` of 16 latent
  heads, one row of 200 positions before 31 padding rows.

Device times are the ``flash_fwd`` operations' own in a profiler trace
(``benchmarks/trace/reduce.py:read_planes``), five executions a program.

Without a TPU the script does nothing and exits 1: its numbers and its
"to the bit" are the chip's or nobody's (interpret mode is
``tests/test_models_ops.py``'s). Under ``tests/conftest.py`` (which holds
JAX to the CPU) the test only starts this file as a process of its own
where the machine has a chip, and is skipped elsewhere:

    chiprun -- python3 tests/test_chip_flash_lens.py
"""
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MARGIN, SAME, RUNS = 0.1, 1.03, 5
# (rows, bucket, heads, key head size, value head size, window, lengths a set)
TIMED = (1, 8192, 48, 128, 128, 0, ([8192], [6144], [4608]))
CHECKED = ((4, 2048, 32, 64, 64, 0, ([0, 2048, 1024, 1025],)),
           (1, 4096, 16, 192, 128, 0, ([2100],)),
           (1, 4096, 40, 64, 64, 512, ([2100], [1])),
           (32, 512, 16, 192, 128, 0, ([200] + [0] * 31,)))


def _visits(S, block, n):
    """Key blocks the causal kernel visits for the query blocks that hold
    the first ``n`` of ``S`` positions."""
    return sum(i + 1 for i in range(-(-n // block)))


def main() -> dict:
    sys.path.insert(0, REPO)
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.trace import reduce

    # the module: as an attribute of ``ray_tpu.ops`` the name is the function
    A = importlib.import_module("ray_tpu.ops.attention")

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"ok": False, "error": "no TPU: "
                          f"{jax.devices()[0].platform}"}), flush=True)
        return {"ok": False}

    def operands(R, S, H, D, Dv):
        keys = jax.random.split(jax.random.PRNGKey(S + H), 3)
        return tuple(jax.random.normal(key, (R, S, H, d), jnp.float32)
                     .astype(jnp.bfloat16)
                     for key, d in zip(keys, (D, D, Dv)))

    def device_ms(fn, *args):
        """(the kernel's ms a call, the result): the ``flash_fwd`` operations
        of ``RUNS`` executions in one trace."""
        out = jax.block_until_ready(fn(*args))
        tdir = tempfile.mkdtemp(prefix="flash_lens_")
        try:
            jax.profiler.start_trace(tdir)
            for _ in range(RUNS):
                out = fn(*args)
            jax.block_until_ready(out)
            jax.profiler.stop_trace()
            path = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                                    recursive=True))[-1]
            ops = [(s, e) for d in reduce.read_planes(path)["devices"].values()
                   for name, s, e in d["ops"] if "flash_fwd" in name]
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        return sum(e - s for s, e in ops) / 1e6 / RUNS, out

    result, ok = {"device": jax.devices()[0].device_kind}, True
    for case in (TIMED,) + CHECKED:
        R, S, H, D, Dv, window, sets = case
        q, k, v = operands(R, S, H, D, Dv)
        block = A._blocks(S)[0]
        plain = jax.jit(lambda q, k, v: A.flash_attention_fwd(
            q, k, v, True, False, window))
        told = jax.jit(lambda q, k, v, lens: A.attention(
            q, k, v, impl="flash", window=window, lens=lens))
        base_ms, whole = device_ms(plain, q, k, v)
        whole = np.asarray(whole.astype(jnp.float32))
        row = {"shape": [R, S, H, D, Dv], "window": window,
               "without_lens_ms": round(base_ms, 4), "lens": []}
        full_ms = None
        for lens in sets:
            ms, got = device_ms(told, q, k, v, jnp.asarray(lens, jnp.int32))
            got = np.asarray(got.astype(jnp.float32))
            same = all((got[r, :n] == whole[r, :n]).all()
                       for r, n in enumerate(lens))
            zeros = not any(got[r, -(-n // block) * block:].any()
                            for r, n in enumerate(lens))
            one = {"lens": lens, "ms": round(ms, 4), "real_rows_same": same,
                   "zeros_behind": zeros, "blocks": A.q_blocks(S, lens)}
            ok &= same and zeros and bool(np.isfinite(got).all())
            if case is TIMED:
                if lens == [S]:
                    full_ms = ms
                    one["over_without_lens"] = round(ms / base_ms, 4)
                    ok &= ms <= SAME * base_ms
                else:
                    share = _visits(S, block, lens[0]) / _visits(S, block, S)
                    one.update(visited_share=round(share, 4),
                               time_share=round(ms / full_ms, 4))
                    ok &= ms / full_ms <= share + MARGIN
            row["lens"].append(one)
        result.setdefault("calls", []).append(row)
        print(json.dumps(row), flush=True)
    result["ok"] = bool(ok)
    print(json.dumps({"ok": result["ok"], "device": result["device"]}),
          flush=True)
    return result


def test_flash_fwd_passes_over_padding_on_the_chip():
    from ray_tpu.util.accelerators import _count_device_nodes

    if not _count_device_nodes():
        pytest.skip("needs a TPU chip: chiprun -- python3 "
                    "tests/test_chip_flash_lens.py")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=1200)
    print(proc.stderr[-4000:], proc.stdout[-4000:])
    assert proc.returncode == 0, proc.stdout[-2000:]


if __name__ == "__main__":
    sys.exit(0 if main()["ok"] else 1)
