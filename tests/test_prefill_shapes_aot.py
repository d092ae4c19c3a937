"""The two ways a prefill program is made must make the same program
(``llm/prefill_shapes.py``): jit's own at a bucket's first use, ``[1, S]``,
and for a call of several rows a process held to the CPU that exports for the
chip. Compiled for a described v5e, no chip (what fits and which kernels,
never a time)."""

import jax
import numpy as np

from prefill_rows import kernels
from test_chip_compile import _live, _rms_kinds, one_chip, topo  # noqa: F401


def test_exported_rows_hold_the_kernels_of_jits_own_lowering(one_chip,
                                                             monkeypatch):
    """Cell 9's ``[2, 1024]`` (``benchmarks/configs/lfm2-8b-a1b.json``,
    ``attention_impl`` left at ``"auto"`` as every serve cell leaves it): the
    program the exporting process hands back compiles to the kernels of a
    direct lowering for the chip, the flash forward among them, and holds
    live what that holds, under the chip's 15.75 GiB."""
    from ray_tpu import utils
    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm import prefill_shapes

    e, cfg, params, cache = _rms_kinds(one_chip, "lfm2-8b-a1b", "auto")
    logits = jax.ShapeDtypeStruct((e.max_num_seqs, cfg.vocab_size),
                                  np.float32, sharding=one_chip)
    shapes = prefill_shapes.RowShapes(cfg, params, cache, logits,
                                      e.pages_per_seq, lambda: None)
    shapes._platform = "tpu"  # this process has no chip; the engine's has
    rows = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), shapes._rows(2, 1024))
    blob, = shapes._export([(2, 1024)])
    exported = prefill_shapes._load(blob).lower(params, cache, *rows).compile()
    monkeypatch.setattr(utils, "_LOWERS_FOR", "tpu")
    direct = mr.prefill.lower(params, cfg, cache, *rows).compile()
    assert kernels(exported) == kernels(direct) == {
        "flash_fwd": 3, "moe_gmm_prefill": 36}
    live, temp = _live(exported)
    print(f"lfm2 exported [2, 1024]: {live} bytes live, {temp} of "
          f"temporaries; jit's own {_live(direct)}")
    assert 0 < live < int(15.5 * 2 ** 30)
    assert abs(live - _live(direct)[0]) < 2 ** 27
