"""AI21-Jamba2-3B (jamba, the benchmark's file): its programs compile for the
v5e at the published widths, all 28 layers.

A compile that passes is not a chip run: nothing here executes, so nothing
here says a result is right or fast (``tests/chip_compile.py`` says why a file
a configuration)."""

import re

import jax.numpy as jnp
import pytest

from chip_compile import _held, _live, _lower_rms_kinds, one_chip, topo  # noqa: F401

NAME = "ai21-jamba2-3b"


def test_jamba_decode_steps_every_state_in_place(one_chip):
    """Decode at 192 slots x 3,072: in each of the 26 Mamba layers ONE
    ``ssm_step`` over the whole [26, 192, 16, 5120] float32 leaf, which it
    hands back IN PLACE (no copy of it anywhere in the program); the paged
    kernel in both attention layers at ONE key head, its 20 query rows padded
    to 32; the program holds the arguments and under 128 MB of temporaries."""
    cache, _, decode = _lower_rms_kinds(one_chip, NAME)
    compiled = decode().compile()
    text = compiled.as_text()
    assert len(set(re.findall(
        r"%(ssm_step\S*) = \(f32\[192,5120\]\S*, f32\[26,192,16,5120\]",
        text))) == 26
    assert not re.search(r"= f32\[26,192,16,5120\]\S* copy\(", text)
    assert len(set(re.findall(
        r"%(paged_gqa_decode\S*) = bf16\[192,1,32,128\]", text))) == 2
    assert text.count("tpu_custom_call") == 28
    state, tail = cache["mamba"]
    assert state.shape == (26, 192, 16, 5120) and state.dtype == jnp.float32
    assert tail.shape == (26, 3, 192, 5120) and tail.dtype == jnp.bfloat16
    assert cache["full"].shape == (2, 1 + 192 * 6, 512, 256)
    assert set(cache.states) == {"full", "mamba"} and cache.moe_load is None
    live, temp = _live(compiled)
    held = _held(cache)
    print(f"jamba decode, 192 slots: {live} bytes live, {temp} of "
          f"temporaries; cache {held}")
    assert temp < 128 << 20 and 8.4e9 < live < 9e9
    assert compiled.memory_analysis().alias_size_in_bytes >= held


@pytest.mark.parametrize("rows,bucket", [(1, 1024), (192, 256)])
def test_jamba_prefill_fits_beside_every_slots_state(one_chip, rows, bucket):
    """The longest bucket as the engine calls it, ``[1, 1024]`` with a slot
    (eight chunks of ``ssm_scan`` a layer), CARRYING the 192 slots' decode
    step (``ssm_riding`` in 26 layers, ``paged_gqa_riding`` in two: at 192
    slots every ``[1, S]`` of this cell carries), and the benchmark check's
    every-slot ``[192, 256]`` call, the largest program of the cell (49,152
    rows: 7.4 GB of temporaries beside 6.06 GB of weights and 2.39 GB of
    state, tails and pages), which is what caps the slots: under the chip's
    15.75 GiB."""
    _, prefill, _ = _lower_rms_kinds(one_chip, NAME)
    compiled = prefill(rows, bucket).compile()
    text = compiled.as_text()
    assert len(set(re.findall(
        rf"%(ssm_scan\S*) = \(f32\[{rows},{bucket},5120\]", text))) == 26
    assert len(set(re.findall(
        rf"%(flash_fwd\S*) = \(bf16\[{rows * 20},{bucket},128\]", text))) == 2
    riding = len(set(re.findall(r"%((?:ssm|paged_gqa)_riding\S*) = ", text)))
    assert riding == (28 if rows == 1 else 0)
    assert text.count("tpu_custom_call") == 28 + riding
    live, temp = _live(compiled)
    print(f"jamba prefill [{rows}, {bucket}]: {live} bytes live, "
          f"{temp} of temporaries")
    assert 0 < live < int(15.5 * 2 ** 30)
