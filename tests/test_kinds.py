"""``llm/kinds/``: ONE table of layer kinds. Every record answers the whole
contract, what it counts is a key of the engine's ``metrics``, the cache holds
a state for EVERY kind a model names (two recurrent kinds in one model got
one kind's shapes before PR 57), and a kind the table lacks is refused by
name. ``tools/program_digest.py``, the proof that a change moved no program,
says the same of a tree twice."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.llm import kinds
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import EngineConfig, LLMConfig
from ray_tpu.llm.engine import JaxLLMEngine
from ray_tpu.llm.kinds import KINDS
from ray_tpu.models.transformer import CONFIGS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the kinds ``_forward`` runs; the decoder-hybrid-decoder's own two have
# facts and their arithmetic in its two loops (its "mamba" layers keep the
# state ``kinds/mamba.py`` says and are stepped by those loops)
MIXERS = ("dense", "latent", "full", "window", "conv", "mamba", "mamba2",
          "kda", "retention")


@pytest.fixture(scope="module")
def tiny():
    return JaxLLMEngine(LLMConfig(model_id="tiny", engine_config=EngineConfig(
        max_num_seqs=2, max_model_len=32, page_size=8)))


def test_the_table_names_every_kind_once():
    assert set(MIXERS) | {"gmu", "cross"} == set(KINDS)
    # the order of the table is the order of the cache's leaves: "mamba"
    # stands where it stood before it was a whole record (cell 7's programs)
    assert list(KINDS) == ["dense", "latent", "full", "window", "cross",
                           "gmu", "conv", "mamba", "mamba2", "kda",
                           "retention"]
    assert all(name == kind.name for name, kind in KINDS.items())
    counters = [c for kind in KINDS.values() for c in kind.counters]
    assert len(counters) == len(set(counters))


@pytest.mark.parametrize("name", sorted(KINDS))
def test_a_record_answers_the_whole_contract(name, tiny):
    kind = KINDS[name]
    assert all(isinstance(getattr(kind, fact), bool)
               for fact in ("paged", "attends", "recurrent"))
    assert not (kind.paged and kind.recurrent)
    # what it counts is there at 0 in an engine of a model without the kind
    assert all(tiny.metrics[c] == 0 for c in kind.counters)
    assert callable(kind.alloc) and callable(kind.after)
    host = kind.Host(tiny.mcfg, 2, 8)
    assert isinstance(host, kinds.Host) and host.layers == 2
    assert callable(host.count_prompt) and callable(host.count_step)
    for what in ("inputs", "prompt", "step", "out"):
        assert callable(getattr(kind, what, None)) == (name in MIXERS), what
    with pytest.raises(AttributeError):
        kind.no_such_thing


# a tiny model of each kind beside another: what init_cache needs of the config
WIDTHS = dict(
    n_layers=4, n_heads=4, n_kv_heads=2, window=8, conv_taps=3, ssm_inner=64,
    ssm_state=16, ssm_heads=4, ssm_conv=4, kda_heads=2, kda_head_dim=16,
    kda_conv=4, kv_latent_rank=32, qk_rope_head_dim=16, retention_degree=2,
    dtype=jnp.float32)
PAGES, PAGE, SLOTS = 5, 4, 3


def _cache(*layer_kinds):
    cfg = dataclasses.replace(CONFIGS["tiny"], **dict(
        WIDTHS, layer_kinds=layer_kinds, n_layers=len(layer_kinds)))
    return cfg, mr.init_cache(cfg, PAGES, PAGE, SLOTS)


@pytest.mark.parametrize("pair", [("mamba2", "kda"), ("conv", "retention")])
def test_two_recurrent_kinds_each_hold_their_own_state(pair):
    """At the parent ``init_cache`` was an ``elif`` chain over ONE ``ssm`` and
    ONE ``conv`` leaf: "mamba2" beside "kda" got Mamba's shapes alone."""
    first, second = pair
    cfg, cache = _cache(first, "full", second, second)
    assert set(cache.states) == {first, second, "full"}
    hd = cfg.head_dim
    want = {
        "mamba2": ((1, SLOTS, 16, 64), (1, 3, SLOTS, 64 + 2 * 16),
                   (1, 3, SLOTS, 64 + 16), (1, 3, SLOTS, 4), ()),
        "kda": ((2, SLOTS, 2, 16, 16), (2, 3, SLOTS, 3 * 2 * 16)),
        "conv": ((1, 2, SLOTS, cfg.d_model),),
        "retention": ((2, SLOTS + 1, 2, hd // 2 + 2, hd, hd),
                      (2, 3, 3, SLOTS, 2, hd), ()),
    }
    for kind in pair:
        got = tuple(tuple(leaf.shape) for leaf in jax.tree.leaves(cache[kind]))
        assert got == want[kind], kind
        alone = KINDS[kind].alloc(cfg, cfg.layer_kinds.count(kind), SLOTS,
                                  PAGES, PAGE)
        assert jax.tree.structure(alone) == jax.tree.structure(cache[kind])
    assert cache["full"].shape == (1, PAGES, PAGE, 2 * 2 * hd)
    # the leaves of a program's arguments lie in the table's order
    order = [kind for kind in KINDS if kind in cache]
    assert jax.tree.leaves(cache) == [
        leaf for kind in order for leaf in jax.tree.leaves(cache[kind])]


def test_the_paged_kinds_and_only_they_are_page_leaves(tiny):
    assert tiny._page_leaves() == ["dense"] and KINDS["dense"].paged
    for held in (("latent", "kda"), ("full", "window", "conv"),
                 ("retention",)):
        _, cache = _cache(*held)
        paged = [kind for kind in cache.states if KINDS[kind].paged]
        assert paged == [k for k in held if k in ("dense", "latent", "full")]
        assert mr._page_size(cache) == (PAGE if paged else 0)


def test_a_kind_the_table_lacks_is_refused_by_name():
    with pytest.raises(ValueError, match="'sparse_mla'.*llm/kinds"):
        _cache("full", "sparse_mla")


def test_metrics_hold_every_records_counters_and_the_engines_own(tiny):
    counters = {c for kind in KINDS.values() for c in kind.counters}
    assert counters <= set(tiny.metrics)
    assert {"flash_q_blocks", "decode_steps", "moe_decode_max_load"} \
        <= set(tiny.metrics) - counters
    assert list(tiny._kinds) == ["dense"]


@pytest.mark.parametrize("preset", ["tiny", "moe-tiny"])
def test_program_digest_says_the_same_twice(preset):
    """The tool lowers a preset's decode step and prefill buckets for the CPU
    and prints a digest a program; two runs of one tree agree."""
    def run():
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "program_digest.py"),
             preset], cwd=REPO, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.splitlines()

    first = run()
    names = [line.split()[0] for line in first]
    assert names[0] == preset + ".decode_step" and len(names) >= 6
    assert all(name.startswith(preset + ".prefill_") for name in names[1:])
    assert all(len(line.split()[1]) == 16 for line in first)
    assert run() == first
