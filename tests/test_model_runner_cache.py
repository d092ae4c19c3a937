"""``llm/model_runner.py`` keeps ONE cache type and ONE forward composition for
every model but the decoder-hybrid-decoder. What must not move with that:
the leaves ``init_cache`` gives each tiny configuration (values written from
the tree before the three cache classes became one), and the policy of which
models' prefill call may carry a decode step."""

import jax
import jax.numpy as jnp
import pytest

import prefill_rows
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import LLMConfig

NUM_PAGES, PAGE, SLOTS = 7, 4, 3


def _cfg(model):
    import test_afmoe
    import test_hybrid
    import test_latent_moe
    import test_lfm2
    import test_moe

    overrides = {"dense": {}, "sparse": test_moe.OVERRIDES,
                 "latent": test_latent_moe.OVERRIDES,
                 "afmoe": test_afmoe.OVERRIDES, "lfm2": test_lfm2.OVERRIDES,
                 "sambay": test_hybrid.OVERRIDES}[model]
    return LLMConfig(model_id="tiny",
                     model_overrides=overrides).transformer_config()


# init_cache(cfg, 7, 4, 3) at the parent of PR 43: every leaf, under the
# kind that keeps it since PR 57 ("<kind>.<field>" where a state has several)
LEAVES = {
    "dense": {"dense.k": ((2, 7, 4, 2, 16), "bfloat16"),
              "dense.v": ((2, 7, 4, 2, 16), "bfloat16")},
    "sparse": {"dense.k": ((2, 7, 4, 4, 16), "float32"),
               "dense.v": ((2, 7, 4, 4, 16), "float32"),
               "moe_load": ((2, 8), "int32")},
    "latent": {"latent": ((3, 7, 4, 128), "float32"),
               "moe_load": ((2, 8), "int32")},
    "afmoe": {"full": ((1, 7, 4, 128), "float32"),
              "window": ((4, 3, 8, 128), "float32"),
              "moe_load": ((4, 4), "int32")},
    "lfm2": {"full": ((2, 7, 4, 64), "float32"),
             "conv": ((5, 2, 3, 64), "float32"),
             "moe_load": ((5, 8), "int32")},
    "sambay": {"full": ((1, 7, 4, 48), "float32"),
               "window": ((2, 3, 8, 48), "float32"),
               "mamba.state": ((3, 3, 16, 96), "float32"),
               "mamba.tail": ((3, 3, 3, 96), "float32")},
}


@pytest.mark.parametrize("model", sorted(LEAVES))
def test_init_cache_gives_each_model_the_leaves_it_had(model):
    cache = mr.init_cache(_cfg(model), NUM_PAGES, PAGE, SLOTS)
    assert type(cache) is mr.Cache
    got = {name: (tuple(leaf.shape), str(leaf.dtype))
           for name, leaf in prefill_rows.named(cache).items()}
    assert got == LEAVES[model]
    # among a program's arguments the leaves lie in the table's order
    assert [tuple(leaf.shape) for leaf in jax.tree.leaves(cache)] == [
        shape for shape, _ in LEAVES[model].values()]


@pytest.mark.parametrize("model", ["dense", "sparse", "latent", "sambay"])
def test_no_decode_rows_ride_a_model_whose_policy_says_no(model):
    """``rides`` is a policy since every model's programs are ``_forward``:
    it answers as it did while it was a limit of the code, and ``prefill``
    refuses ``riders`` before it traces anything."""
    cfg = _cfg(model)
    assert not mr.rides(cfg)
    cache = mr.init_cache(cfg, NUM_PAGES, PAGE, SLOTS)
    MP = 2
    rows = (jnp.zeros((1, 8), jnp.int32), jnp.ones(1, jnp.int32),
            jnp.ones((1, MP), jnp.int32))
    if cfg.layer_kinds:
        rows += (jnp.zeros(1, jnp.int32),)
    riders = (jnp.zeros(SLOTS, jnp.int32), jnp.zeros(SLOTS, jnp.int32),
              jnp.zeros((SLOTS, MP), jnp.int32), jnp.zeros(SLOTS, bool))
    with pytest.raises(ValueError, match="no decode rows ride"):
        mr.prefill(None, cfg, cache, *rows, riders=riders)
    # refused before anything was donated
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(cache))


@pytest.mark.parametrize("model", ["afmoe", "lfm2"])
def test_the_models_that_rode_still_ride(model):
    assert mr.rides(_cfg(model))
