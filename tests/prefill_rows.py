"""What the four model files' tests of a prefill call of several rows share
(``llm/engine.py:prefill_groups``; ISSUE 41): a burst admitted in one step
against the same requests one a step, and what a padding row writes."""

import collections
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.config import SamplingParams

# where a cache leaf keeps a page or a slot: (axis, "page" | "slot")
_LEAVES = {"k": (1, "page"), "v": (1, "page"), "rows": (1, "page"),
           "pages": (1, "page"), "rings": (1, "slot"), "ssm": (1, "slot"),
           "conv": (2, "slot")}


def kernels(compiled) -> collections.Counter:
    """The Pallas calls of an executable for the chip, by kernel name."""
    return collections.Counter(re.findall(
        r'%([A-Za-z_]\w*?)(?:\.\d+)* = [^\n]*custom_call_target='
        r'"tpu_custom_call"', compiled.as_text()))


def burst_equals_one_a_step(eng, prompts, max_tokens=6):
    """``prompts`` served one after another (which also uses each length
    bucket once, so its shapes of several rows get compiled), then all added
    before one ``step()``: the same greedy tokens, in fewer prefill calls than
    requests. Returns the burst's counters."""
    sp = SamplingParams(max_tokens=max_tokens)
    alone = [eng.generate([p], sp, decode_text=False)[0].token_ids
             for p in prompts]
    assert eng._row_shapes.wait(600)
    before = dict(eng.metrics)
    burst = eng.generate(prompts, sp, decode_text=False)
    assert [o.token_ids for o in burst] == alone
    d = {k: eng.metrics[k] - v for k, v in before.items()}
    assert d["admitted"] == len(prompts) and d["prefill_steps"] == 1
    assert d["prefill_calls"] == d["prefill_phase_calls"] < len(prompts)
    assert d["compiles"] == 0
    return d


def padding_rows_write_nothing(eng, prompt, slot=1, first_page=2):
    """``prompt`` through ``[1, S]`` and through ``[2, S]`` beside a padding
    row (length 0, a block table of zeros, the slot past the last), each on a
    cache filled with a sentinel: the same logits row, the same state in the
    request's pages and slot, the same ``moe_load``, and the sentinel
    everywhere else but on the scratch page. Then a call of padding alone:
    nothing but the scratch page changes, no expert gets a row, and
    ``place_rows`` leaves the logits buffer as it was."""
    mr, e, mcfg = eng._mr, eng.ecfg, eng.mcfg
    B, MP = e.max_num_seqs, e.pages_per_seq
    S = eng._prefill_bucket(len(prompt))
    own = np.arange(first_page, first_page + math.ceil(len(prompt) / e.page_size))
    told = bool(mcfg.layer_kinds)

    def call(rows):
        """``rows``: True = the prompt, False = padding."""
        cache = jax.tree.map(lambda x: jnp.full_like(x, 3), mr.init_cache(
            mcfg, e.num_pages, e.page_size, B))
        R = len(rows)
        toks = np.zeros((R, S), np.int32)
        lens = np.zeros(R, np.int32)
        tables = np.zeros((R, MP), np.int32)
        slots = np.full(R, B, np.int32)
        for i, real in enumerate(rows):
            if real:
                toks[i, :len(prompt)], lens[i] = prompt, len(prompt)
                tables[i, :len(own)], slots[i] = own, slot
        logits, cache = mr.prefill(
            eng.params, mcfg, cache, jnp.asarray(toks), jnp.asarray(lens),
            jnp.asarray(tables), *((jnp.asarray(slots),) if told else ()))
        return np.asarray(logits), cache, jnp.asarray(slots)

    def split(cache):
        """(the request's own state, everything else but the scratch page)
        of every leaf that keeps pages or slots."""
        mine, rest = {}, {}
        for name, (axis, kind) in _LEAVES.items():
            leaf = getattr(cache, name, None)
            if leaf is None:
                continue
            leaf = np.moveaxis(np.asarray(leaf, np.float32), axis, 0)
            keep = np.ones(len(leaf), bool)
            if kind == "page":
                keep[0] = False
                keep[own] = False
                mine[name] = leaf[own]
            elif told:  # rings and rows belong to the slot prefill was told
                keep[slot] = False
                mine[name] = leaf[slot]
            rest[name] = leaf[keep]
        return mine, rest

    one, cache1, _ = call([True])
    two, cache2, _ = call([True, False])
    np.testing.assert_allclose(two[0], one[0], rtol=1e-4, atol=1e-4)
    mine1, rest1 = split(cache1)
    mine2, rest2 = split(cache2)
    assert mine1 and all(np.abs(v - 3).max() > 0 for v in mine1.values())
    for name in mine1:
        np.testing.assert_allclose(mine2[name], mine1[name], rtol=1e-4,
                                   atol=1e-5, err_msg=name)
    for name in rest1:
        assert (rest1[name] == 3).all() and (rest2[name] == 3).all(), name
    if cache1.moe_load is not None:
        np.testing.assert_array_equal(np.asarray(cache2.moe_load),
                                      np.asarray(cache1.moe_load))
        routed = len(prompt) * mcfg.experts_per_token * cache1.moe_load.shape[0]
        held = int(np.asarray(cache1.moe_load).sum())
        assert 0 < held <= routed
        assert held == routed or mcfg.n_experts_held < mcfg.n_experts

    logits, cache0, slots = call([False, False])
    for name, (axis, kind) in _LEAVES.items():
        leaf = getattr(cache0, name, None)
        if leaf is not None:
            leaf = np.moveaxis(np.asarray(leaf, np.float32), axis, 0)
            assert (leaf[1 if kind == "page" else 0:] == 3).all(), name
    if cache0.moe_load is not None:
        assert not np.asarray(cache0.moe_load).any()
    buffer = jnp.full((B, mcfg.vocab_size), 7.0, jnp.float32)
    placed = mr.place_rows(buffer, jnp.asarray(logits), slots)
    assert (np.asarray(placed) == 7.0).all()
