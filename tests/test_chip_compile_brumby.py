"""One stage of Brumby-14B-Base (brumby, the benchmark's file): its programs
compile for the v5e at the published widths.

A compile that passes is not a chip run: nothing here executes, so nothing
here says a result is right or fast (``tests/chip_compile.py`` says why a file
a configuration)."""

import re

import jax.numpy as jnp
import pytest

from chip_compile import _held, _live, _lower_rms_kinds, one_chip, topo  # noqa: F401


def test_brumby_decode_steps_every_state_in_place(one_chip):
    """Decode at 32 slots: in each of the six layers ONE conditional on the
    cache's count of pending positions, around ``retention_read`` (the state
    read, a tile of the scratch slot written) and ``retention_step`` (the
    fold: read and written), both over the whole [6, 32 + 1, 8, 66, 128, 128]
    float32 leaf (6.85 GB), which either branch hands back IN PLACE: one copy
    of it among the live bytes, NO copy of it anywhere in the program, and
    nothing a state's size among the temporaries. The cache holds that leaf,
    the pending positions (7 MB) and their count, and no page; the block
    tables are arguments that address nothing."""
    cache, _, decode = _lower_rms_kinds(one_chip, "brumby-14b-base")
    compiled = decode().compile()
    text = compiled.as_text()
    for kernel in ("retention_step", "retention_read"):
        assert len(set(re.findall(
            rf"%({kernel}\S*) = \(f32\[32,8,128,16\]", text))) == 6
    assert text.count("tpu_custom_call") == 12
    assert len(re.findall(r" conditional\(", text)) == 6
    assert not re.search(r"= f32\[6,33,8,66,128,128\]\S* copy\(", text)
    state, pending, count = cache["retention"]
    assert state.shape == (6, 32 + 1, 8, 66, 128, 128)
    assert state.dtype == jnp.float32
    assert pending.shape == (6, 3, 3, 32, 8, 128)
    assert pending.dtype == jnp.float32
    assert count.shape == ()
    assert set(cache.states) == {"retention"}
    live, temp = _live(compiled)
    held = _held(cache)
    print(f"brumby decode, 32 slots: {live} bytes live, {temp} of "
          f"temporaries; state {held}")
    assert temp < 128 << 20
    assert compiled.memory_analysis().alias_size_in_bytes >= held


@pytest.mark.parametrize("rows,bucket", [(1, 512), (32, 256), (1, 8192)])
def test_brumby_prefill_writes_no_row_of_features(one_chip, rows, bucket):
    """The largest call that carries the 32 slots' decode step, ``[1, 512]``
    (``retention_riding`` in six layers), the benchmark check's every-slot
    ``[32, 256]`` call and the largest bucket, ``[1, 8192]``, beside 7.08 GB
    of weights and 6.64 GB of state: the chunked recurrence in six layers,
    whose features live in the kernel's fast memory alone: NO array of a
    prompt's positions by the symmetric square's width (8,256 exact, 8,320
    by rotation, or a tiled 8,704 / 9,216) is anybody's result; under the
    chip's 15.75 GiB. What an execution holds live is printed (``-s``) and
    stands in PERF.md section 4."""
    _, prefill, _ = _lower_rms_kinds(one_chip, "brumby-14b-base")
    compiled = prefill(rows, bucket).compile()
    text = compiled.as_text()
    assert len(set(re.findall(
        rf"%(retention_scan\S*) = \(bf16\[{rows},{bucket},5120\]", text))) == 6
    riding = len(set(re.findall(r"%(retention_riding\S*) = ", text)))
    assert riding == (6 if (rows, bucket) == (1, 512) else 0)
    assert text.count("tpu_custom_call") == 6 + riding
    assert not re.search(r"\[[\d,]*(?:8256|8320|8704|9216)[,\]]", text)
    live, temp = _live(compiled)
    print(f"brumby prefill [{rows}, {bucket}]: {live} bytes live, "
          f"{temp} of temporaries")
    assert 0 < live < int(15.5 * 2 ** 30)
