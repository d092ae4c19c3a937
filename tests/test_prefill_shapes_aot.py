"""The two ways a prefill program is made must make the same program
(``llm/prefill_shapes.py``): jit's own at a bucket's first use, ``[1, S]``,
and for a call of several rows a process held to the CPU that exports for the
chip. Compiled for a described v5e, no chip (what fits and which kernels,
never a time)."""

import jax
import numpy as np
import pytest

from prefill_rows import kernels
from chip_compile import _live, _rms_kinds, one_chip, topo  # noqa: F401


@pytest.mark.parametrize("name,shape,held", [
    # cell 9: every shape of the engine's carries the 128 slots' decode step
    ("lfm2-8b-a1b", (2, 1024),
     {"flash_fwd": 3, "moe_gmm_prefill": 36, "paged_gqa_riding": 3}),
    # cell 8: [2, 256] carries the 32 slots' (rings beside pages), and a call
    # whose rows dwarf them is the plain program
    ("trinity-large-preview", (2, 256),
     {"flash_fwd": 5, "moe_gmm_prefill": 12, "paged_gqa_riding": 1,
      "window_gqa_riding": 4}),
    ("trinity-large-preview", (2, 1024),
     {"flash_fwd": 5, "moe_gmm_prefill": 12})])
def test_exported_rows_hold_the_kernels_of_jits_own_lowering(
        one_chip, monkeypatch, name, shape, held):
    """A shape of several rows of ``benchmarks/configs/<name>.json``
    (``attention_impl`` left at ``"auto"`` as every serve cell leaves it),
    with the decode step's operands where the engine's call carries one
    (``llm/engine.py:_RIDE_ROWS``): the program the exporting process hands
    back compiles to the kernels of a direct lowering for the chip, the flash
    forward among them and the paged kernel of the step it carries, and holds
    live what that holds, under the chip's 15.75 GiB."""
    from ray_tpu import utils
    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm import prefill_shapes
    from ray_tpu.llm.engine import _RIDE_ROWS

    e, cfg, params, cache = _rms_kinds(one_chip, name, "auto")
    logits = jax.ShapeDtypeStruct((e.max_num_seqs, cfg.vocab_size),
                                  np.float32, sharding=one_chip)
    shapes = prefill_shapes.RowShapes(
        cfg, params, cache, logits, e.pages_per_seq, lambda: None,
        lambda R, S: R * S <= _RIDE_ROWS * e.max_num_seqs)
    shapes._platform = "tpu"  # this process has no chip; the engine's has
    rows = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), shapes._rows(*shape))
    blob, = shapes._export([shape])
    exported = prefill_shapes._load(blob).lower(params, cache, *rows).compile()
    monkeypatch.setattr(utils, "_LOWERS_FOR", "tpu")
    direct = mr.prefill.lower(params, cfg, cache, *rows).compile()
    assert kernels(exported) == kernels(direct) == held
    live, temp = _live(exported)
    print(f"{name} exported {list(shape)}: {live} bytes live, {temp} of "
          f"temporaries; jit's own {_live(direct)}")
    assert 0 < live < int(15.5 * 2 ** 30)
    assert abs(live - _live(direct)[0]) < 2 ** 27
