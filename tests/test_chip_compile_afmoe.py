"""One rank of Trinity-Large-Preview (afmoe, the benchmark's file): its programs
compile for the v5e at the published widths.

A compile that passes is not a chip run: nothing here executes, so nothing
here says a result is right or fast (``tests/chip_compile.py`` says why a file
a configuration)."""

import re

import pytest

from chip_compile import (_float32_rows_a_choice, _held, _live,
                          _lower_rms_kinds, one_chip, topo)  # noqa: F401


def test_afmoe_decode_reads_rings_and_live_pages_and_nothing_else(one_chip):
    """Decode at 32 slots x 16,896: the paged kernel over the rings of the four
    sliding layers (8 blocks of 512 a ring) and over the one full layer's live
    pages, plain heads (8 groups of 6 query rows padded to 16, 128 lanes);
    three grouped matmuls in each of the four expert layers over the 32 held
    experts; the cache written in place, and NO array a slot's whole length
    long: nothing is gathered over ``Lmax``."""
    cache, _, decode = _lower_rms_kinds(one_chip)
    compiled = decode().compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%(window_gqa_decode\S*) = bf16\[32,8,16,128\]",
                              text))) == 4
    assert len(set(re.findall(r"%(paged_gqa_decode\S*) = bf16\[32,8,16,128\]",
                              text))) == 1
    assert len(set(re.findall(r"%(moe_gmm_decode\S*) = bf16\[128,3072\]",
                              text))) == 12
    assert text.count("tpu_custom_call") == 17
    assert cache["full"].shape == (1, 1057, 512, 2048)
    assert cache["window"].shape == (4, 32, 4096, 2048)
    assert cache.moe_load.shape == (4, 32)
    assert set(cache.states) == {"full", "window"}
    assert not re.search(r"\[32,(16896|33,512),", text)
    live, temp = _live(compiled)
    held = _held(cache)
    print(f"afmoe decode, 32 slots: {live} bytes live, {temp} of "
          f"temporaries; cache {held}")
    assert temp < 64 << 20
    assert compiled.memory_analysis().alias_size_in_bytes >= held


@pytest.mark.parametrize("rows,bucket", [(1, 16384), (32, 256), (1, 512)])
def test_afmoe_prefill_fits_beside_every_slots_state(one_chip, rows, bucket):
    """The engine's largest call, ``[1, 16384]``, the benchmark check's
    every-slot ``[32, 256]`` call, and the largest of the engine's calls that
    carry the 32 slots' decode step beside the prompt, ``[1, 512]`` (the paged
    kernel over four rings and one layer of pages: PR 42), beside 8.65 GB of
    weights and 4.36 GB of pages and rings: five flash calls (the window's in
    four of them) and twelve grouped matmuls, under the chip's 15.75 GiB.
    What an execution holds live is printed (``-s``) and stands in PERF.md
    section 4."""
    _, prefill, _ = _lower_rms_kinds(one_chip)
    compiled = prefill(rows, bucket).compile()
    text = compiled.as_text()
    assert len(set(re.findall(
        rf"%(flash_fwd\S*) = \(bf16\[{rows * 48},{bucket},128\]", text))) == 5
    riding = len(set(re.findall(r"%((?:window|paged)_gqa_riding\S*) = ", text)))
    assert riding == (5 if (rows, bucket) == (1, 512) else 0)
    assert text.count("tpu_custom_call") == 17 + riding
    assert len(set(re.findall(r"%(moe_gmm_prefill\S*) = bf16\[", text))) == 12
    assert not _float32_rows_a_choice(text, 4, 3072)
    live, temp = _live(compiled)
    print(f"afmoe prefill [{rows}, {bucket}]: {live} bytes live, "
          f"{temp} of temporaries")
    assert 0 < live < int(15.5 * 2 ** 30)
