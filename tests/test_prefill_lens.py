"""The four places a prefill call reaches the flash kernel hand it the rows'
lengths (``llm/model_runner.py``, PR 54): the "dense" kind, the "latent"
kind's expanded attention, the grouped and window kinds of a model with
``layer_kinds``, and the decoder-hybrid-decoder's differential attention.

On the CPU the engines' ``attention_impl="auto"`` is the reference path, so no
engine test runs the kernel; here each kind's tiny model (the widths of its own
test file) runs ONE ``[2, 1024]`` prefill call through ``flash_interpret``: a
prompt of 300 positions (the second of its two 512-position query blocks lies
behind its end) beside a padding row (both do), and its logits are held to
the same call through ``reference_attention``, which computes every position.
That every ``flash_fwd`` of the program was told the lengths is read off the
jaxpr."""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import LLMConfig

S, PROMPT, SLOTS, PAGE = 1024, 300, 3, 128


# the test file whose tiny model (``OVERRIDES``) has the kind
TINY = {"latent": "test_latent_moe", "window-and-full": "test_afmoe",
        "sambay": "test_hybrid"}


def _overrides(kind):
    if kind == "dense":
        return dict(dtype=jnp.float32, remat=False)
    return importlib.import_module(TINY[kind]).OVERRIDES


@pytest.mark.parametrize("kind", ["dense", "latent", "window-and-full",
                                  "sambay"])
def test_prefill_tells_the_flash_kernel_its_rows_lengths(kind):
    import flax.linen as nn
    from test_models_ops import _walk

    from ray_tpu.models.transformer import Transformer

    cfg = dataclasses.replace(
        LLMConfig(model_id="tiny").transformer_config(),
        **dict(_overrides(kind), max_seq_len=S))
    params = nn.meta.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    tokens = np.zeros((2, S), np.int32)
    tokens[0, :PROMPT] = np.random.default_rng(0).integers(
        3, cfg.vocab_size, PROMPT)
    tables = np.zeros((2, S // PAGE), np.int32)
    tables[0] = 1 + np.arange(S // PAGE)
    rows = [jnp.asarray(tokens), jnp.asarray([PROMPT, 0], jnp.int32),
            jnp.asarray(tables)]
    if cfg.layer_kinds:  # a model that keeps state by slot is told the slot
        rows.append(jnp.asarray([1, SLOTS], jnp.int32))

    def logits(impl):
        model = dataclasses.replace(cfg, attention_impl=impl)
        cache = mr.init_cache(model, 2 + S // PAGE, PAGE, SLOTS)
        if impl != "xla":
            calls = [e for e in _walk(jax.make_jaxpr(
                lambda c: mr.prefill(params, model, c, *rows))(cache).jaxpr)
                if e.primitive.name == "pallas_call"
                and e.params["name"] == "flash_fwd"]
            assert calls and all(
                e.params["grid_mapping"].num_index_operands == 1
                for e in calls)
        return np.asarray(mr.prefill(params, model, cache, *rows)[0][0])

    got, want = logits("flash_interpret"), logits("xla")
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max(),
                               rtol=2e-4)
