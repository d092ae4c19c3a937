"""LFM2-8B-A1B (lfm2_moe, the benchmark's file): its programs compile for the
v5e at the published widths.

A compile that passes is not a chip run: nothing here executes, so nothing
here says a result is right or fast (``tests/chip_compile.py`` says why a file
a configuration)."""

import re

import pytest

from chip_compile import (_float32_rows_a_choice, _held, _live,
                          _lower_rms_kinds, one_chip, topo)  # noqa: F401


def test_lfm2_decode_shifts_rows_and_reads_live_pages(one_chip):
    """Decode at 128 slots x 2,560: the paged kernel over the three attention
    layers' live pages with two plain 64-lane key heads to a group (4 groups
    of 8 query rows padded to 16, 128 lanes: whole tiles of a page's row);
    three grouped matmuls in each of the twelve sparse layers over 512
    assignments (two row tiles of 256); the eleven convolutions' rows shifted
    in the cache, which is written in place; nothing gathered over a slot's
    whole length."""
    cache, _, decode = _lower_rms_kinds(one_chip, "lfm2-8b-a1b")
    compiled = decode().compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%(paged_gqa_decode\S*) = bf16\[128,4,16,128\]",
                              text))) == 3
    assert len(set(re.findall(r"%(moe_gmm_decode\S*) = bf16\[512,(?:1792|2048)\]",
                              text))) == 36
    assert text.count("tpu_custom_call") == 39
    assert cache["full"].shape == (3, 1281, 256, 1024)
    assert cache["conv"].shape == (11, 2, 128, 2048)
    assert cache.moe_load.shape == (12, 32)
    assert set(cache.states) == {"full", "conv"}
    assert not re.search(r"\[128,(2560|10,256),", text)
    live, temp = _live(compiled)
    held = _held(cache)
    print(f"lfm2 decode, 128 slots: {live} bytes live, {temp} of "
          f"temporaries; cache {held}")
    assert temp < 256 << 20
    assert compiled.memory_analysis().alias_size_in_bytes >= held


@pytest.mark.parametrize("rows,bucket", [(1, 128), (1, 2048), (128, 256)])
def test_lfm2_prefill_fits_beside_every_slots_state(one_chip, rows, bucket):
    """The least and the largest bucket the mix reaches as the engine calls
    them, ``[1, S]`` with a slot (the three between hold 11.40, 11.42 and
    11.47 GB: compiled once, AOT, PR 40; this file is the suite's longest),
    and the benchmark check's every-slot ``[128, 256]`` call (32,768 rows x
    top-4 = 131,072 sorted rows in each expert layer), beside 9.33 GB of
    weights and 2.03 GB of pages and rows: three flash calls and 36 grouped
    matmuls (over the prompt's rows and, in the engine's call, the 128 decode
    rows it carries since PR 42, whose attention is the paged kernel's in the
    three attention layers), under the chip's 15.75 GiB. What an execution
    holds live is printed (``-s``) and stands in PERF.md section 4."""
    _, prefill, _ = _lower_rms_kinds(one_chip, "lfm2-8b-a1b")
    compiled = prefill(rows, bucket).compile()
    text = compiled.as_text()
    assert len(set(re.findall(
        rf"%(flash_fwd\S*) = \(bf16\[{rows * 32},{bucket},64\]", text))) == 3
    riding = len(set(re.findall(r"%(paged_gqa_riding\S*) = ", text)))
    assert riding == (3 if rows == 1 else 0)
    assert text.count("tpu_custom_call") == 39 + riding
    assert len(set(re.findall(r"%(moe_gmm_prefill\S*) = bf16\[", text))) == 36
    assert not _float32_rows_a_choice(text, 4, 2048)
    live, temp = _live(compiled)
    print(f"lfm2 prefill [{rows}, {bucket}]: {live} bytes live, "
          f"{temp} of temporaries")
    assert 0 < live < int(15.5 * 2 ** 30)
