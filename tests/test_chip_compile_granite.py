"""One rank of Granite-4.0-H-Small (granitemoehybrid, the benchmark's file): its
programs compile for the v5e at the published widths.

A compile that passes is not a chip run: nothing here executes, so nothing
here says a result is right or fast (``tests/chip_compile.py`` says why a file
a configuration)."""

import re

import jax.numpy as jnp
import pytest

from chip_compile import (_float32_rows_a_choice, _held, _live,
                          _lower_rms_kinds, one_chip, topo)  # noqa: F401


def test_granite_decode_steps_every_state_in_place(one_chip):
    """Decode at 40 slots x 4,608: ``ssd_step`` once in each of the nine
    Mamba-2 layers over the whole [9, 40, 128, 8192] float32 leaf, which
    like the pages and the tails is written IN PLACE (no copy of 1.5 GB of
    state among the temporaries); the paged kernel over the one attention
    layer's live pages; three grouped matmuls in each of the ten expert
    layers over the 36 held experts and 400 assignments."""
    cache, _, decode = _lower_rms_kinds(one_chip, "granite-4.0-h-small")
    compiled = decode().compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%(ssd_step\S*) = \(f32\[40,1,8192\]", text))) == 9
    assert len(set(re.findall(r"%(paged_gqa_decode\S*) = bf16\[40,8,16,128\]",
                              text))) == 1
    assert len(set(re.findall(r"%(moe_gmm_decode\S*) = bf16\[\d+,(?:768|4096)\]",
                              text))) == 30
    assert text.count("tpu_custom_call") == 40
    state, tail = cache["mamba2"]
    assert state.shape == (9, 40, 128, 8192) and state.dtype == jnp.float32
    assert tail.shape == (9, 3, 40, 8448)
    assert cache["full"].shape == (1, 361, 512, 2048)
    assert cache.moe_load.shape == (10, 36)
    assert set(cache.states) == {"full", "mamba2"}
    live, temp = _live(compiled)
    held = _held(cache)
    print(f"granite decode, 40 slots: {live} bytes live, {temp} of "
          f"temporaries; cache {held}")
    assert temp < 64 << 20
    assert compiled.memory_analysis().alias_size_in_bytes >= held


@pytest.mark.parametrize("rows,bucket", [(1, 256), (40, 256), (1, 4096)])
def test_granite_prefill_fits_beside_every_slots_state(one_chip, rows, bucket):
    """The least bucket as the engine calls it, ``[1, 256]`` with a slot,
    CARRYING the 40 slots' decode step (``ssd_riding`` in nine layers,
    ``paged_gqa_riding`` in one), and the benchmark check's every-slot ``[40,
    256]`` call, the largest program of the cell (10,240 rows x top-10), beside
    9.51 GB of weights and 2.29 GB of state, tails and pages: the chunked scan
    in nine layers, one flash call, thirty grouped matmuls, under the chip's
    15.75 GiB; and the largest bucket, ``[1, 4096]`` (40,960 sorted rows a
    layer; too long to carry a step), which held 13.80 GB while the way back
    laid a float32 ``[4096, 10, 4096]`` out and holds 12.67 since PR 46."""
    _, prefill, _ = _lower_rms_kinds(one_chip, "granite-4.0-h-small")
    compiled = prefill(rows, bucket).compile()
    text = compiled.as_text()
    assert len(set(re.findall(
        rf"%(ssd_scan\S*) = \(f32\[{rows},{bucket},8192\]", text))) == 9
    assert len(set(re.findall(
        rf"%(flash_fwd\S*) = \(bf16\[{rows * 32},{bucket},128\]", text))) == 1
    riding = len(set(re.findall(r"%((?:ssd|paged_gqa)_riding\S*) = ", text)))
    assert riding == (10 if (rows, bucket) == (1, 256) else 0)
    assert text.count("tpu_custom_call") == 40 + riding
    assert len(set(re.findall(r"%(moe_gmm_prefill\S*) = bf16\[", text))) == 30
    assert not _float32_rows_a_choice(text, 10, 4096)
    live, temp = _live(compiled)
    print(f"granite prefill [{rows}, {bucket}]: {live} bytes live, "
          f"{temp} of temporaries")
    assert 0 < live < int(15.5 * 2 ** 30)
