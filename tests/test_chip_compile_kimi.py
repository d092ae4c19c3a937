"""One rank of Kimi-Linear-48B-A3B (kimi_linear, the benchmark's file): its
programs compile for the v5e at the published widths.

A compile that passes is not a chip run: nothing here executes, so nothing
here says a result is right or fast (``tests/chip_compile.py`` says why a file
a configuration)."""

import re

import jax.numpy as jnp
import pytest

from chip_compile import _held, _live, _lower_rms_kinds, one_chip, topo  # noqa: F401


def test_kimi_decode_steps_every_state_in_place(one_chip):
    """Decode at 64 slots x 16,896: ``kda_step`` once in each of the six
    delta-rule layers over the whole [6, 64, 32, 128, 128] float32 leaf, which
    like the latent rows and the tails is written IN PLACE (no copy of 0.8 GB
    of state among the temporaries); ``mla_decode`` over the two latent
    layers' live pages, found by their rank among the latent layers; three
    grouped matmuls in each of the seven expert layers over the 64 held
    experts and 512 assignments."""
    cache, _, decode = _lower_rms_kinds(one_chip, "kimi-linear-48b-a3b")
    compiled = decode().compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%(kda_step\S*) = \(f32\[64,32,128\]", text))) == 6
    assert len(set(re.findall(r"%(mla_decode\S*) = ", text))) == 2
    assert len(set(re.findall(r"%(moe_gmm_decode\S*) = bf16\[\d+,(?:1024|2304)\]",
                              text))) == 21
    assert text.count("tpu_custom_call") == 29
    state, tail = cache["kda"]
    assert state.shape == (6, 64, 32, 128, 128) and state.dtype == jnp.float32
    assert tail.shape == (6, 3, 64, 12288)
    assert cache["latent"].shape == (2, 64 * 33 + 1, 512, 640)
    assert cache.moe_load.shape == (7, 64)
    assert set(cache.states) == {"latent", "kda"}
    live, temp = _live(compiled)
    held = _held(cache)
    print(f"kimi decode, 64 slots: {live} bytes live, {temp} of "
          f"temporaries; cache {held}")
    assert temp < 128 << 20
    assert compiled.memory_analysis().alias_size_in_bytes >= held


@pytest.mark.parametrize("rows,bucket", [(1, 512), (64, 256), (1, 16384)])
def test_kimi_prefill_fits_beside_every_slots_state(one_chip, rows, bucket):
    """The least bucket the mix reaches as the engine calls it, ``[1, 512]``
    with a slot, CARRYING the 64 slots' decode step (``kda_riding`` in six
    layers; the latent mixer runs a prompt's rows and a step's in one program),
    the benchmark check's every-slot ``[64, 256]`` call (16,384 rows x top-8)
    and the largest bucket, ``[1, 16384]``, beside 7.54 GB of weights and 3.6
    GB of state, tails and latent rows: the chunked delta rule in six layers,
    two flash calls over 192-wide q . k, twenty-one grouped matmuls, under the
    chip's 15.75 GiB. The kernel writes ``o`` normalised, gated and in the
    stored type, ``[rows, bucket, 4096]`` as ``o_proj`` reads it, and no
    float32 array of a prompt's positions by all heads' lanes, flat or by
    head (what the layer's elementwise passes wrote while XLA made them:
    the float32 ``f``, ``g`` and ``o``), is anybody's result."""
    _, prefill, _ = _lower_rms_kinds(one_chip, "kimi-linear-48b-a3b")
    compiled = prefill(rows, bucket).compile()
    text = compiled.as_text()
    assert len(set(re.findall(
        rf"%(kda_scan\S*) = \(bf16\[{rows},{bucket},4096\]", text))) == 6
    assert not re.findall(
        rf"= \(?f32\[{rows},{bucket},(?:4096|32,128)\]", text)
    assert len(set(re.findall(r"%(flash_fwd\S*) = ", text))) == 2
    riding = len(set(re.findall(r"%(kda_riding\S*) = ", text)))
    assert riding == (6 if (rows, bucket) == (1, 512) else 0)
    assert len(set(re.findall(r"%(mla_decode\S*) = ", text))) == riding // 3
    assert len(set(re.findall(r"%(moe_gmm_prefill\S*) = bf16\[", text))) == 21
    live, temp = _live(compiled)
    print(f"kimi prefill [{rows}, {bucket}]: {live} bytes live, "
          f"{temp} of temporaries")
    assert 0 < live < int(15.5 * 2 ** 30)
