"""One rank of LongCat-Flash-Omni's language model (longcat_flash): its programs
compile for the v5e at the published widths.

A compile that passes is not a chip run: nothing here executes, so nothing
here says a result is right or fast (``tests/chip_compile.py`` says why a file
a configuration)."""

import re

import pytest

from chip_compile import _held, _live, _lower_rms_kinds, one_chip, topo  # noqa: F401


def test_longcat_decode_reads_eight_sublayers_rows_in_place(one_chip):
    """Decode at 32 slots x 8,704: ``mla_decode`` once in each of the EIGHT
    sublayers (two a published layer) over the [8, 545, 512, 640] rows, which
    are written in place; three grouped matmuls in each of the FOUR expert
    branches over the 16 held experts and 384 assignments (two row tiles of 256), by assignment (a
    decode step is far under the least count that goes by what is held)."""
    cache, _, decode = _lower_rms_kinds(one_chip, "longcat-flash-omni")
    compiled = decode().compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%(mla_decode\S*) = ", text))) == 8
    assert len(set(re.findall(r"%(moe_gmm_decode\S*) = bf16\[512,(?:2048|6144)\]",
                              text))) == 12
    assert text.count("tpu_custom_call") == 20
    assert cache["latent"].shape == (8, 32 * 17 + 1, 512, 640)
    assert cache.moe_load.shape == (4, 17)
    assert set(cache.states) == {"latent"}
    live, temp = _live(compiled)
    held = _held(cache)
    print(f"longcat decode, 32 slots: {live} bytes live, {temp} of "
          f"temporaries; cache {held}")
    assert temp < 128 << 20
    assert compiled.memory_analysis().alias_size_in_bytes >= held


@pytest.mark.parametrize("rows,bucket", [(1, 512), (32, 256), (1, 8192)])
def test_longcat_prefill_holds_no_buffer_of_every_assignment(one_chip, rows,
                                                             bucket):
    """The largest call that carries the 32 slots' decode step, ``[1, 512]``,
    the benchmark check's every-slot ``[32, 256]`` call and the largest
    bucket, ``[1, 8192]`` (8,192 rows x top-12 = 98,304 assignments each, of
    which about 2,048 are held here), beside 10.35 GB of weights and 2.86 GB
    of latent rows: eight flash calls at 64 heads over 192-wide q . k, the
    three grouped products of each of four expert branches inside ONE loop a
    branch over windows of 4,096 sorted rows, and NO array as long as the
    assignments by the hidden or the expert width (by assignment that is 1.2
    GB of gathered rows alone a branch): under the chip's 15.75 GiB."""
    from ray_tpu.ops.moe import held_window

    _, prefill, _ = _lower_rms_kinds(one_chip, "longcat-flash-omni")
    compiled = prefill(rows, bucket).compile()
    text = compiled.as_text()
    assert len(set(re.findall(
        rf"%(flash_fwd\S*) = \(bf16\[{rows * 64},{bucket},128\]", text))) == 8
    riding = (rows, bucket) == (1, 512)
    assert len(set(re.findall(r"%(mla_decode\S*) = ", text))) == 8 * riding
    T = rows * bucket + 32 * riding
    window = held_window(T * 12, 16, 768, 256)
    assert window == {512: 512, 256: 4096, 8192: 4096}[bucket]
    assert len(set(re.findall(
        rf"%(moe_gmm_prefill\S*) = bf16\[{window},(?:2048|6144)\]", text))) == 12
    assert not re.search(rf"\[(?:{T * 12}|12,{T}),(?:2048|6144)\]", text)
    live, temp = _live(compiled)
    print(f"longcat prefill [{rows}, {bucket}]: {live} bytes live, "
          f"{temp} of temporaries")
    assert 0 < live < int(15.5 * 2 ** 30)
