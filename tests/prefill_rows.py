"""What the four model files' tests of a prefill call of several rows share
(``llm/engine.py:prefill_groups``; ISSUE 41): a burst admitted in one step
against the same requests one a step, and what a padding row writes; and
what the tests of a prefill call that carries a decode step share
(``model_runner.prefill``'s ``riders``; ISSUE 42), on the CPU and on the chip:
the call against the two programs one after the other, staggered requests
through the engine, and the engine's calls fed each request's own tokens."""

import collections
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.config import SamplingParams

# where a leaf of a kind's state keeps a page or a slot: (axis, "page" |
# "slot"), under the kind's name or, a state of several, "<kind>.<field>"
_LEAVES = {"dense.k": (1, "page"), "dense.v": (1, "page"),
           "latent": (1, "page"), "full": (1, "page"), "window": (1, "slot"),
           "conv": (2, "slot"), "retention.state": (1, "slot"),
           **{f"{kind}.{field}": (axis, "slot")
              for kind in ("mamba", "mamba2", "kda")
              for field, axis in (("state", 1), ("tail", 2))}}


def held(cache) -> set:
    """The kinds whose state a model's cache holds, and "moe_load" where it
    has one."""
    return set(cache.states) | (
        set() if cache.moe_load is None else {"moe_load"})


def named(cache) -> dict:
    """name -> leaf of every leaf of ``cache``: "<kind>", or "<kind>.<field>"
    where a kind's state is a NamedTuple, and "moe_load"."""
    out = {} if cache.moe_load is None else {"moe_load": cache.moe_load}
    for kind, state in cache.states.items():
        fields = getattr(state, "_asdict", lambda: {"": state})()
        out.update({f"{kind}.{f}".rstrip("."): x for f, x in fields.items()})
    return out


def leaf(cache, name):
    """The leaf ``_LEAVES`` names, or None where the cache has no such."""
    return named(cache).get(name)


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
            got = leaf(cache, name)
            if got is None:
                continue
            got = np.moveaxis(np.asarray(got, np.float32), axis, 0)
            keep = np.ones(len(got), bool)
            if kind == "page":
                keep[0] = False
                keep[own] = False
                mine[name] = got[own]
            elif told:  # rings and rows belong to the slot prefill was told
                keep[slot] = False
                mine[name] = got[slot]
            rest[name] = got[keep]
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
        got = leaf(cache0, name)
        if got is not None:
            got = np.moveaxis(np.asarray(got, np.float32), axis, 0)
            assert (got[1 if kind == "page" else 0:] == 3).all(), name
    if cache0.moe_load is not None:
        assert not np.asarray(cache0.moe_load).any()
    buffer = jnp.full((B, mcfg.vocab_size), 7.0, jnp.float32)
    placed = mr.place_rows(buffer, jnp.asarray(logits), slots)
    assert (np.asarray(placed) == 7.0).all()


def riders_equal_a_step_after_the_call(eng, rng, tol=1e-4, settle=None):
    """A prefill call that carries a decode step (``prefill``'s ``riders``)
    against the same call and then ``decode_step``, from one cache: slot 0
    decodes (a prompt of 9, two steps in), slot 1 never held anything and is
    not active, and the last slot, which held a longer request that is gone
    (its pages handed on, its rings and rows stale), is filled again by the
    call's first row; the second row is padding. The prompt's logits, the
    step's logits and every leaf of the cache agree to ``tol`` of their norm;
    what the carrying call leaves of a slot that is neither filled nor active
    is what it found; its ``moe_load`` is the two programs' summed.
    ``settle``: for a model whose two ways keep the same state in two forms
    (power retention: a carried step folds its pending positions, a step
    alone may keep them beside the state), what brings both caches to one
    form before their leaves are compared."""
    mr, e, cfg = eng._mr, eng.ecfg, eng.mcfg
    B, MP, P = e.max_num_seqs, e.pages_per_seq, e.page_size
    assert mr.rides(cfg) and B >= 3
    V, new = cfg.vocab_size, B - 1
    tables = np.zeros((B, MP), np.int32)
    lens, last = np.zeros(B, np.int32), np.zeros(B, np.int32)
    active = np.zeros(B, bool)

    def rows(slot_toks, R, S):
        """The arguments of a call of ``R`` rows: {slot: tokens}, padding
        behind them."""
        toks, n = np.zeros((R, S), np.int32), np.zeros(R, np.int32)
        tab, slots = np.zeros((R, MP), np.int32), np.full(R, B, np.int32)
        for i, (slot, t) in enumerate(slot_toks.items()):
            toks[i, :len(t)], n[i], tab[i], slots[i] = t, len(t), tables[slot], slot
        return tuple(map(jnp.asarray, (toks, n, tab, slots)))

    def fill(cache, slot, toks, pages, S):
        tables[slot] = 0
        tables[slot, :len(pages)] = pages
        logits, cache = mr.prefill(eng.params, cfg, cache,
                                   *rows({slot: toks}, 1, S))
        lens[slot], last[slot] = len(toks), int(np.argmax(logits[0]))
        return cache

    def step_rows():
        return tuple(jnp.asarray(a.copy()) for a in (last, lens, tables, active))

    cache = mr.init_cache(cfg, e.num_pages, P, B)
    gone = rng.integers(0, V, 21).tolist()
    cache = fill(cache, new, gone, np.arange(1, 1 + math.ceil(22 / P)), 32)
    cache = fill(cache, 0, rng.integers(0, V, 9).tolist(),
                 np.arange(8, 8 + math.ceil(12 / P)), 16)
    active[[0, new]] = True
    for _ in range(2):  # both decode; then the longer one is gone
        logits, cache = mr.decode_step(eng.params, cfg, cache, *step_rows())
        last[:] = np.argmax(np.asarray(logits), axis=-1)
        lens[active] += 1
    active[new], lens[new] = False, 0
    prompt = rng.integers(0, V, 7).tolist()
    tables[new] = 0
    tables[new, :2] = [2, 1]   # pages the longer request had, in another order
    lens[new] = len(prompt)    # as the engine's seq_lens has it at admission
    call, step = rows({new: prompt}, 2, 16), step_rows()

    found = jax.tree.map(np.asarray, cache)
    logits1, c1 = mr.prefill(eng.params, cfg, jax.tree.map(jnp.copy, cache),
                             *call)
    filled = jax.tree.map(np.asarray, c1)
    step1, c1 = mr.decode_step(eng.params, cfg, c1, *step)
    (logits2, step2), c2 = mr.prefill(eng.params, cfg, cache, *call, step)
    if settle is not None:
        c1, c2 = settle(c1), settle(c2)

    def close(got, want, what):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        assert np.linalg.norm(got - want) <= tol * np.linalg.norm(want), what

    close(logits2[0], logits1[0], "the prompt's logits")
    close(step2[0], step1[0], "the step's logits")
    assert step2.shape == (B, V) and logits2.shape == (2, V)
    for name, (axis, kind) in _LEAVES.items():
        if leaf(c2, name) is None:
            continue
        got, after, before, then = (
            np.moveaxis(np.asarray(leaf(c, name), np.float32), axis, 0)
            for c in (c2, c1, found, filled))
        assert np.abs(after - before).max() > 0, name
        if kind == "page":  # but the scratch page
            close(got[1:], after[1:], name)
            continue
        close(got[0], after[0], name + " of the slot that decodes")
        close(got[new], then[new], name + " of the slot that is filled")
        assert np.abs(then[new] - before[new]).max() > 0, name
        np.testing.assert_array_equal(got[1], before[1], err_msg=name)
    if c2.moe_load is not None:
        np.testing.assert_array_equal(
            np.asarray(c2.moe_load), filled.moe_load + np.asarray(c1.moe_load))


def staggered_equal_alone(eng, prompts, max_tokens=6):
    """``prompts`` served alone, then added one every second ``step()`` so
    that each but the first is admitted while others decode: every request
    gets the tokens it got alone, and the counters add up as the engine's
    docstring says (a decode step whose rows rode a prefill call is a decode
    step with no program, sampler call or read of its own). Returns the
    staggered run's counters."""
    sp = SamplingParams(max_tokens=max_tokens)
    alone = [eng.generate([p], sp, decode_text=False)[0].token_ids
             for p in prompts]
    assert eng._row_shapes.wait(600)
    from benchmarks.jobs.common import CompileCounter

    before, got, left = dict(eng.metrics), {}, list(enumerate(prompts))
    steps, events = 0, CompileCounter()
    while left or eng.has_unfinished():
        if left and steps % 2 == 0:
            i, p = left.pop(0)
            eng.add_request(f"r{i}", p, sp)
        for out in eng.step():
            if out.finished:
                got[out.request_id] = out.token_ids
        steps += 1
    assert [got[f"r{i}"] for i in range(len(prompts))] == alone
    d = {k: eng.metrics[k] - v for k, v in before.items()}
    assert d["admitted"] == d["prefill_steps"] == len(prompts)
    assert 0 < d["riding_steps"] <= len(prompts) - 1
    assert d["generated_tokens"] == len(prompts) * max_tokens
    assert d["sample_calls"] == d["prefill_steps"] + d["decode_steps"] \
        - d["riding_steps"]
    assert d["decode_phase_calls"] == d["decode_steps"] - d["riding_steps"]
    assert d["prefill_phase_calls"] == d["prefill_calls"]
    assert d["itl_tokens"] == d["generated_tokens"] - d["admitted"]
    # nothing compiled: not by the engine's count, not by the benchmark's
    # (JAX's compile events; one inside its window fails a run's ``correct``)
    assert d["compiles"] == d["dropped_tokens"] == events.count == 0
    assert not eng.plain_buckets and not eng._row_shapes.failed
    return d


def admitted_beside_decoders_equal_alone(eng, decoding, burst, max_tokens=6):
    """``decoding`` admitted in one step, ``burst`` in the next, so that the
    burst's phase (its calls in the order the engine gives them) finds slots
    decoding: every request gets the tokens it got alone. Returns the
    counters of the burst's step."""
    sp = SamplingParams(max_tokens=max_tokens)
    prompts = list(decoding) + list(burst)
    alone = [eng.generate([p], sp, decode_text=False)[0].token_ids
             for p in prompts]
    assert eng._row_shapes.wait(600)
    assert not eng.plain_buckets and not eng._row_shapes.failed
    got = {}

    def step():
        got.update((o.request_id, o.token_ids) for o in eng.step()
                   if o.finished)

    for i, p in enumerate(decoding):
        eng.add_request(f"r{i}", p, sp)
    step()
    before = dict(eng.metrics)
    for i, p in enumerate(burst, len(decoding)):
        eng.add_request(f"r{i}", p, sp)
    step()
    d = {k: eng.metrics[k] - v for k, v in before.items()}
    while eng.has_unfinished():
        step()
    assert [got[f"r{i}"] for i in range(len(prompts))] == alone
    assert d["admitted"] == len(burst) and d["compiles"] == 0
    return d


def teacher_forced_riding(eng, seqs, gap=1):
    """Requests through the programs as the engine calls them for a model
    whose prefill call carries a decode step: ``seqs`` = {slot: (tokens,
    prompt length, first page)}, admitted in that order, each by a ``[1, S]``
    call told its slot whose ``riders`` are the decode step of whoever
    decodes by then (nobody, for the first), or by the plain call where the
    engine's ``[1, S]`` carries none (``eng._carries``: the step follows it),
    the next one as soon as the one before it has taken ``gap`` tokens;
    between admissions ``decode_step``. Every request is fed its own tokens.
    Returns {slot: logits [1 + tokens behind the prompt, vocab]}, on
    ``eng.cache``."""
    mr, e, cfg = eng._mr, eng.ecfg, eng.mcfg
    B, MP, P = e.max_num_seqs, e.pages_per_seq, e.page_size
    tables = np.zeros((B, MP), np.int32)
    last, lens = np.zeros(B, np.int32), np.zeros(B, np.int32)
    active = np.zeros(B, bool)
    got, fed, waiting = {}, {}, list(seqs)

    def step_rows():
        for slot in np.flatnonzero(active):
            toks, n, _ = seqs[slot]
            last[slot], lens[slot] = toks[n + fed[slot]], n + fed[slot]
        # copies: on the CPU a program may read the host's arrays in place
        return tuple(jnp.asarray(a.copy()) for a in (last, lens, tables, active))

    def took(step_logits):
        step_logits = np.asarray(step_logits)
        for slot in np.flatnonzero(active):
            got[slot].append(step_logits[slot])
            fed[slot] += 1
            active[slot] = seqs[slot][1] + fed[slot] < len(seqs[slot][0])

    newest = None
    while waiting or active.any():
        if waiting and (newest is None or fed[newest] >= gap
                        or not active[newest]):
            newest = slot = waiting.pop(0)
            toks, n, first = seqs[slot]
            S = eng._prefill_bucket(n)
            need = math.ceil(len(toks) / P)
            tables[slot, :need] = np.arange(first, first + need)
            batch = np.zeros((1, S), np.int32)
            batch[0, :n] = toks[:n]
            call = (jnp.asarray(batch), jnp.asarray([n], jnp.int32),
                    jnp.asarray(tables[slot:slot + 1]),
                    jnp.asarray([slot], jnp.int32))
            if eng._carries(1, S):
                (logits, step), eng.cache = mr.prefill(
                    eng.params, cfg, eng.cache, *call, step_rows())
                first_logits = np.asarray(logits[0])
                took(step)
            else:
                logits, eng.cache = mr.prefill(eng.params, cfg, eng.cache,
                                               *call)
                first_logits = np.asarray(logits[0])
            got[slot], fed[slot], active[slot] = [first_logits], 0, True
        else:
            step, eng.cache = mr.decode_step(eng.params, cfg, eng.cache,
                                             *step_rows())
            took(step)
    return {slot: np.stack(v) for slot, v in got.items()}
