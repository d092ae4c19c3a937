"""The hybrid model's programs (Phi-4-mini-flash-reasoning, the benchmark's file)
compile for the v5e at the published widths.

A compile that passes is not a chip run: nothing here executes, so nothing
here says a result is right or fast (``tests/chip_compile.py`` says why a file
a configuration)."""

import re

import pytest

from chip_compile import _held, _live, _lower_hybrid, one_chip, topo  # noqa: F401


def test_hybrid_decode_compiles_with_the_paged_kernels(one_chip):
    """Decode at 48 slots, one period of the pattern: the paged kernel under
    the two names the trace's metrics read (once for each window layer's
    rings, once each for the full and the cross layer over the shared pages),
    every kind of state written in place (the whole cache aliased) and no
    copy of pages or rings made for a kernel: the temporaries stay far below
    one window layer's rings (126 MB)."""
    cache, _, decode = _lower_hybrid(one_chip)
    compiled = decode().compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%(window_gqa_decode\S*) = bf16\[48,10,16,128\]",
                              text))) == 2
    assert len(set(re.findall(r"%(paged_gqa_decode\S*) = bf16\[48,10,16,128\]",
                              text))) == 2
    assert text.count("tpu_custom_call") == 4
    live, temp = _live(compiled)
    held = _held(cache)
    print(f"hybrid decode, 8 layers, 48 slots: {live} bytes live, {temp} of "
          f"temporaries; cache {held}")
    assert cache["full"].shape == (1, 961, 512, 2560)
    assert cache["window"].shape == (2, 48, 512, 2560)
    assert cache["mamba"].state.shape == (3, 48, 16, 5120)
    assert temp < cache["window"].size * 2 // 2 // 4
    assert compiled.memory_analysis().alias_size_in_bytes >= held


@pytest.mark.parametrize("bucket", [256, 8192])
def test_hybrid_prefill_compiles_with_scan_and_window(one_chip, bucket):
    """The engine's [1, S] prefill at the mix's least and largest bucket: the
    scan kernel in each Mamba layer, the flash kernel over 64-wide scores and
    128-wide values in the window layers (key blocks left of the window
    skipped) and in the full layer; the cross layer attends from one row and
    needs none. What an execution holds live is printed (``-s``); the full
    depth is in PERF.md section 4."""
    _, prefill, _ = _lower_hybrid(one_chip)
    compiled = prefill(bucket).compile()
    text = compiled.as_text()
    assert len(set(re.findall(
        rf"%(ssm_scan\S*) = \(f32\[1,{bucket},5120\]", text))) == 3
    assert len(set(re.findall(
        rf"%(flash_fwd\S*) = \(bf16\[40,{bucket},128\]", text))) == 3
    assert text.count("tpu_custom_call") == 6
    live, temp = _live(compiled)
    print(f"hybrid prefill, 8 layers, [1, {bucket}]: {live} bytes live, "
          f"{temp} of temporaries")
    assert 0 < live < 16 << 30
