"""LFM2-8B-A1B (``lfm2_moe``): gated short convolutions in three layers of
four, grouped-query attention with a per-head q/k norm before RoPE in the
fourth, two dense layers and then a sigmoid router with a selection bias over
experts that are all held. The paged engine (pages for the attention layers,
two rows a slot for each convolution, ONE cache manager shared with cells 7
and 8; decode through ``ops/paged_attention.py`` with two plain key heads to a
group) against the benchmark's plain reference
``benchmarks/architectures/lfm2_moe.py``.

The model runs in float32 at a small size with the real pattern (7 layers:
conv, conv dense, then ``a c c c a`` sparse; hidden 64, 4 query and 2 key
heads of 16, 8 experts top-2, 3 taps, pages of 4), where the only differences
left between the two sides are the order of float32 sums: 1e-4 of the logits'
norm admits that and nothing else, as the spoiled references show (each moves
the logits by more than 30 times that).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.architectures import lfm2_moe as ref
from benchmarks.registry import REPO, Cell
from ray_tpu.llm import LLMConfig
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import JaxLLMEngine
from ray_tpu.models.transformer import CONFIGS, Transformer

TOL = 1e-4
VOCAB, EXPERTS, PAGE, BUCKET = 128, 8, 4, 16
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv"]
# the small model under the published key names
PUBLISHED = dict(
    name="lfm2-tiny", model_type="lfm2_moe", hidden_act="silu",
    conv_bias=False, use_expert_bias=True, tie_word_embeddings=True,
    conv_L_cache=3, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=128, moe_intermediate_size=32,
    vocab_size=VOCAB, num_hidden_layers=7, num_dense_layers=2,
    layer_types=TYPES, num_experts=EXPERTS, num_experts_per_tok=2,
    norm_topk_prob=True, routed_scaling_factor=1, norm_eps=1e-5,
    rope_theta=1000000, torch_dtype="float32",
    initializer={"attention": 0.3, "conv": 0.16, "mlp": 0.15, "experts": 0.2,
                 "embedding": 0.5})
OVERRIDES = dict(ref.program_overrides(PUBLISHED, 64), dtype=jnp.float32,
                 remat=False)
RCFG = ref.reference_cfg(PUBLISHED)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _engine(**engine):
    geometry = dict(max_num_seqs=3, max_model_len=64, page_size=PAGE,
                    prefill_bucket_min=BUCKET, expect_experts=EXPERTS,
                    expect_state_layers=5, expect_conv_taps=3,
                    # too few for three requests at once: one is preempted
                    num_pages=14)
    eng = JaxLLMEngine(LLMConfig(
        model_id="tiny", model_overrides=OVERRIDES,
        engine_config=EngineConfig(**dict(geometry, **engine))))
    # Transformer.init draws every norm's scale at one, and a head norm of
    # ones commutes with RoPE: drawn here, so that their order shows
    rng = np.random.default_rng(0)
    for lp in eng.params["params"].values():
        for n in ("q_norm", "k_norm"):
            if isinstance(lp, dict) and n in lp.get("attn", {}):
                lp["attn"][n]["scale"] = jnp.asarray(
                    1 + 0.5 * rng.normal(size=16), jnp.float32)
    return eng


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _reference(eng, toks, **wrong):
    """The reference's logits [len(toks), vocab]; ``wrong``: facts of the
    layer it is told to get wrong, or ``no_expert_bias``."""
    params = ref.to_reference_params(eng.params["params"], PUBLISHED)
    if wrong.pop("no_expert_bias", False):
        params = dict(params, layers=[
            dict(lp, expert_bias=lp["expert_bias"] * 0.0)
            if "expert_bias" in lp else lp for lp in params["layers"]])
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(
            params, jnp.asarray(toks)[None], dict(RCFG, **wrong))[0])


class _Run:
    """The engine's own programs on one cache, as the engine calls them: a
    ``[1, bucket]`` prefill told its slot, and decode steps over every slot."""

    def __init__(self, eng):
        e = eng.ecfg
        self.eng, self.e = eng, e
        self.cache = mr.init_cache(eng.mcfg, e.num_pages, e.page_size,
                                   e.max_num_seqs)
        self.tables = np.zeros((e.max_num_seqs, e.pages_per_seq), np.int32)
        self.active = np.zeros(e.max_num_seqs, bool)
        self.last = np.zeros(e.max_num_seqs, np.int32)
        self.lens = np.zeros(e.max_num_seqs, np.int32)

    def prefill(self, slot, toks, pages, bucket=BUCKET):
        """``toks`` into ``slot`` on the page ids ``pages`` -> logits [vocab]."""
        self.tables[slot] = 0
        self.tables[slot, :len(pages)] = pages
        batch = np.zeros((1, bucket), np.int32)
        batch[0, :len(toks)] = toks
        logits, self.cache = mr.prefill(
            self.eng.params, self.eng.mcfg, self.cache, jnp.asarray(batch),
            jnp.asarray([len(toks)], jnp.int32),
            jnp.asarray(self.tables[slot:slot + 1]),
            jnp.asarray([slot], jnp.int32))
        self.active[slot], self.lens[slot] = True, len(toks)
        return np.asarray(logits[0])

    def decode(self, tokens):
        """One step; ``tokens``: {slot: the token it is fed} -> {slot: logits}."""
        for s, t in tokens.items():
            self.last[s] = t
        logits, self.cache = mr.decode_step(
            self.eng.params, self.eng.mcfg, self.cache,
            jnp.asarray(self.last), jnp.asarray(self.lens),
            jnp.asarray(self.tables), jnp.asarray(self.active))
        out = {s: np.asarray(logits[s]) for s in tokens}
        # only now: on the CPU the program may read the host's arrays in place
        self.lens[self.active] += 1
        return out

    def sequence(self, slot, toks, n, pages, bucket=BUCKET):
        """Prefill ``toks[:n]`` and feed the rest: [len(toks) - n + 1, vocab]."""
        got = [self.prefill(slot, toks[:n], pages, bucket)]
        got += [self.decode({slot: t})[slot] for t in toks[n:]]
        return np.stack(got)


def _pages(first, positions):
    return np.arange(first, first + -(-positions // PAGE))


# -- (a) the engine against the reference -------------------------------------------


@pytest.mark.parametrize("prompt_len,bucket", [
    (1, 16),    # shorter than the taps: one real row of state and a zero
    (2, 16),    # the two rows exactly
    (3, 16),    # the taps exactly
    (15, 16),   # one short of the bucket: a padded row behind the prompt
    (16, 16),   # a bucket with no padding
    (21, 32),   # the next bucket, six pages
])
def test_engine_matches_reference(engine, prompt_len, bucket):
    """Prefill's last-position logits and then ten decode steps through the
    rows and over page boundaries (pages of 4), in a slot that is not the
    first and on pages that are not the first; padding behind the prompt
    never reaches the state, and a prompt shorter than the taps leaves zeros
    where it has no position."""
    toks = np.random.default_rng(prompt_len).integers(0, VOCAB, prompt_len + 10)
    run = _Run(engine)
    got = run.sequence(2, toks, prompt_len, _pages(5, len(toks)), bucket)
    want = _reference(engine, toks)[prompt_len - 1:]
    assert _rel(got, want) < TOL, _rel(got, want)
    conv = np.asarray(run.cache["conv"])
    assert conv.shape == (5, 2, 3, 64) and "window" not in run.cache
    assert set(run.cache.states) == {"full", "conv"}
    assert run.cache["full"].shape[0] == 2
    assert np.abs(conv[:, :, 2]).max(axis=-1).min() > 0
    load = np.asarray(run.cache.moe_load)     # the last step: one row, top-2
    assert load.shape == (5, EXPERTS) and (load.sum(1) == 2).all()


def test_prompt_of_one_token_leaves_one_real_row(engine):
    run = _Run(engine)
    run.prefill(1, [7], _pages(1, 1))
    conv = np.asarray(run.cache["conv"])                  # [layers, 2, B, d]
    assert np.abs(conv[:, 0, 1]).max() == 0
    assert np.abs(conv[:, 1, 1]).max(axis=-1).min() > 0
    assert np.abs(conv[:, :, [0, 2]]).max() == 0          # nobody else's rows


def test_slot_used_again_after_a_longer_request(engine):
    """A slot and its pages handed to a second, shorter request: what the
    first left in the rows and in the pages past the second's positions is
    never read."""
    rng = np.random.default_rng(5)
    long, short = rng.integers(0, VOCAB, 27), rng.integers(0, VOCAB, 9)
    run = _Run(engine)
    run.sequence(1, long, 21, _pages(3, 27), 32)
    run.active[1] = False
    got = run.sequence(1, short, 2, _pages(3, 9))
    assert _rel(got, _reference(engine, short)[1:]) < TOL


def test_preempted_request_prefilled_again(engine):
    """Recompute preemption: a request that decoded five tokens is prefilled
    again from prompt + generated into another slot and other pages, and goes
    on as if nothing had happened; meanwhile its old slot decodes garbage."""
    toks = np.random.default_rng(6).integers(0, VOCAB, 6 + 5 + 6)
    want = _reference(engine, toks)
    run = _Run(engine)
    first = run.sequence(0, toks[:11], 6, _pages(1, 11))
    assert _rel(first, want[5:11]) < TOL
    run.active[0] = False
    again = run.sequence(2, toks, 11, _pages(9, len(toks)))
    assert _rel(again, want[10:]) < TOL


def test_two_requests_beside_each_other(engine):
    """Two prefill calls back to back (one admission phase), then steps that
    decode both: each slot's rows and pages are its own."""
    rng = np.random.default_rng(8)
    a, b = rng.integers(0, VOCAB, 3 + 7), rng.integers(0, VOCAB, 14 + 7)
    run = _Run(engine)
    got = {0: [run.prefill(0, a[:3], _pages(1, 10))],
           1: [run.prefill(1, b[:14], _pages(4, 21))]}
    for i in range(7):
        out = run.decode({0: a[3 + i], 1: b[14 + i]})
        for s in got:
            got[s].append(out[s])
    assert _rel(np.stack(got[0]), _reference(engine, a)[2:]) < TOL
    assert _rel(np.stack(got[1]), _reference(engine, b)[13:]) < TOL


def test_every_slot_prefill_call(engine):
    """The benchmark's check calls prefill with every slot's row and no slot
    argument: row b fills slot b, and a row of length 0 leaves zeros."""
    e, cfg = engine.ecfg, engine.mcfg
    toks = np.random.default_rng(3).integers(0, VOCAB, 11 + 3)
    run = _Run(engine)
    run.tables[0, :4] = np.arange(1, 5)
    batch = np.zeros((e.max_num_seqs, BUCKET), np.int32)
    batch[0, :11] = toks[:11]
    logits, run.cache = mr.prefill(
        engine.params, cfg, run.cache, jnp.asarray(batch),
        jnp.asarray([11, 0, 0], jnp.int32), jnp.asarray(run.tables))
    assert np.abs(np.asarray(run.cache["conv"])[:, :, 1:]).max() == 0
    run.active[0], run.lens[0] = True, 11
    got = [np.asarray(logits[0])] + [run.decode({0: t})[0] for t in toks[11:]]
    assert _rel(np.stack(got), _reference(engine, toks)[10:]) < TOL


# -- (b) every recalled part shows in the logits -----------------------------------


@pytest.mark.parametrize("wrong", [
    {"taps_reversed": True},          # w[:, 0] on the position itself
    {"output_gate": False},           # the gate C left out
    {"gate_before_conv": False},      # B * conv(z) for conv(B * z)
    {"state_lag": 1},                 # the kept rows one position late
    {"no_expert_bias": True},
    {"qk_norm_before_rope": False},
], ids=lambda w: next(iter(w)))
def test_wrong_part_fails_the_comparison(engine, wrong):
    toks = np.random.default_rng(7).integers(0, VOCAB, 13 + 6)
    got = _Run(engine).sequence(0, toks, 13, _pages(1, len(toks)))
    assert _rel(got, _reference(engine, toks)[12:]) < TOL
    assert _rel(got, _reference(engine, toks, **wrong)[12:]) > 30 * TOL


# -- (c) the paged kernel with two plain key heads to a group ----------------------


def test_plain_heads_share_a_group_as_they_share_a_tile():
    """``_grouped_query`` lays the queries of key ``j`` of a group at that
    key's lanes and ``_paged_attention`` hands each head its own lanes back:
    against attention written out, on pages in any order. At ``head_dim`` 128
    a group is one key head, the layout cells 7 and 8 compiled."""
    cfg = dataclasses.replace(CONFIGS["tiny"], **OVERRIDES)
    assert mr._keys_per_group(cfg) == 2
    assert mr._keys_per_group(dataclasses.replace(
        cfg, head_size=128, n_kv_heads=8)) == 1
    assert mr._keys_per_group(dataclasses.replace(
        cfg, head_size=64, n_kv_heads=8, n_heads=32)) == 2
    from ray_tpu.ops.mla import live_pages

    rng = np.random.default_rng(0)
    B, H, KVH, hd, P, NP = 3, 4, 2, 16, 4, 9
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    pages = jnp.asarray(rng.normal(size=(1, NP, P, 2 * KVH * hd)), jnp.float32)
    tables = jnp.asarray([[3, 1, 0], [5, 8, 2], [4, 0, 0]], jnp.int32)
    lens = jnp.asarray([5, 11, 0], jnp.int32)       # positions 0..len are live
    active = jnp.asarray([True, True, False])
    got = mr._paged_attention(q, pages, live_pages(lens, active, tables, P),
                              0, "paged_gqa_decode", cfg)
    assert got.shape == (B, H, hd)
    for b in (0, 1):
        rows = np.asarray(pages[0, tables[b]]).reshape(-1, 2 * KVH * hd)
        rows = rows[:int(lens[b]) + 1]
        for h in range(H):
            kh = h // (H // KVH)
            k = rows[:, kh * hd:(kh + 1) * hd]
            v = rows[:, (KVH + kh) * hd:(KVH + kh + 1) * hd]
            s = k @ np.asarray(q[b, h]) / np.sqrt(hd)
            p = np.exp(s - s.max())
            np.testing.assert_allclose(got[b, h], (p / p.sum()) @ v,
                                       rtol=2e-5, atol=2e-6)


# -- the engine, its counters, the training module, the files ----------------------


def test_engine_serves_admits_two_at_once_and_preempts():
    """Requests through ``JaxLLMEngine.step()`` with too few pages for all of
    them: three are admitted in one step, one is preempted and prefilled
    again, and every greedy token is the reference's own argmax."""
    eng = _engine()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (11, 2, 19)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=9), decode_text=False)
    for prompt, out in zip(prompts, outs):
        assert len(out.token_ids) == 9
        # teacher-forced over the engine's own tokens: the chain is greedy
        # if every token is the argmax at the position before it
        want = _reference(eng, prompt + out.token_ids)[len(prompt) - 1:-1]
        assert out.token_ids == np.argmax(want, axis=-1).tolist()
    m = eng.metrics
    assert m["preempted"] >= 1 and m["admitted"] > m["prefill_steps"]
    assert m["moe_decode_layer_steps"] == 5 * (
        m["decode_steps"] - m["riding_steps"])
    assert m["moe_decode_assignments"] == m["moe_decode_routed_assignments"]
    assert m["shared_kv_live_tokens"] > 0 == m["window_live_tokens"]
    assert m["prefill_cross_rows"] == 0


@pytest.mark.parametrize("lens,rows,bucket", [((11, 2, 14), 4, 16),
                                              ((20, 9), 2, 32)])
def test_burst_admitted_in_one_step_shares_a_prefill_call(lens, rows, bucket):
    """Requests admitted in one step are rows of ONE prefill call (three in a
    call of four with a padding row; a 32 bucket and a 16 bucket at 32): each
    row's pages by its block table, its two rows by its slot, and the tokens
    those the same requests generate one a step."""
    import prefill_rows

    eng = _engine(num_pages=None)   # room for all of them at once
    rng = np.random.default_rng(5)
    d = prefill_rows.burst_equals_one_a_step(
        eng, [rng.integers(0, VOCAB, n).tolist() for n in lens])
    assert (d["prefill_calls"], d["prefill_batch_tokens"]) == (1, rows * bucket)
    assert d["preempted"] == 0


def test_decode_rows_ride_a_prefill_call(engine):
    """``prefill`` with ``riders`` is the call and then ``decode_step``: the
    prompt's logits, the step's logits, the pages and the conv rows of the slot
    that decodes and of the slot that is filled again, beside a padding row
    and a slot that is not active (whose state it leaves as it found it)."""
    import prefill_rows

    prefill_rows.riders_equal_a_step_after_the_call(
        engine, np.random.default_rng(7), TOL)
    with pytest.raises(ValueError, match="no decode rows ride"):
        mr.prefill(engine.params, dataclasses.replace(
            engine.mcfg, layer_kinds=()), engine.cache, None, None, None,
            riders=())


def test_riding_calls_match_reference():
    """Three requests through the calls the engine makes when it admits
    beside decoding slots: the second's ``[1, 16]`` call carries the first's
    third step, the third's ``[1, 32]`` (a slot used before, pages in the
    middle of the pool) the other two's, ``decode_step`` in between and after:
    every position's logits against the reference (what the chip test runs at
    the published widths)."""
    import prefill_rows

    eng = _engine(num_pages=40)
    rng = np.random.default_rng(11)
    seqs = {1: (rng.integers(0, VOCAB, 11 + 7), 11, 3),
            0: (rng.integers(0, VOCAB, 2 + 6), 2, 9),
            2: (rng.integers(0, VOCAB, 19 + 4), 19, 20)}
    got = prefill_rows.teacher_forced_riding(eng, seqs, gap=2)
    for slot, (toks, n, _) in seqs.items():
        assert got[slot].shape == (len(toks) - n + 1, VOCAB)
        assert _rel(got[slot], _reference(eng, toks)[n - 1:]) < TOL, slot


def test_staggered_requests_ride_and_get_the_tokens_they_get_alone():
    """Requests admitted while others decode: the decoding slots' step rides
    the admitted request's prefill call (one program, one sampler call, one
    read), every request's greedy tokens are those it gets alone, and the
    routing counters move with the decode-only steps alone."""
    import prefill_rows

    eng = _engine(num_pages=None)
    rng = np.random.default_rng(8)
    d = prefill_rows.staggered_equal_alone(
        eng, [rng.integers(0, VOCAB, n).tolist() for n in (11, 5, 14)])
    layers = eng.cache.moe_load.shape[0]
    assert d["moe_decode_layer_steps"] == layers * (
        d["decode_steps"] - d["riding_steps"])
    assert d["shared_kv_live_tokens"] > 0


@pytest.mark.parametrize("slots,decoding,burst,calls", [
    # [2, 32] then [1, 16]: both carry, the first takes the step
    (5, (9,), (20, 25, 5), 2),
    # [1, 64] is a plain program at 3 slots and [1, 16] carries: it goes first
    (3, (9,), (40, 5), 2),
    # [4, 16] of three beside two that decode
    (5, (9, 3), (11, 12, 5), 1)])
def test_a_burst_beside_decoding_slots_gets_the_tokens_it_gets_alone(
        slots, decoding, burst, calls):
    """Several requests admitted in ONE step while slots decode: the phase's
    first call carries the step, the rows of its other calls land in the same
    buffer, one sampler call serves all, and every request's tokens are those
    it gets alone, whichever of its calls' programs carry."""
    import prefill_rows

    eng = _engine(num_pages=None, max_num_seqs=slots)
    rng = np.random.default_rng(12)
    d = prefill_rows.admitted_beside_decoders_equal_alone(
        eng, *([rng.integers(0, VOCAB, n).tolist() for n in lens]
               for lens in (decoding, burst)))
    assert d["prefill_calls"] == calls
    assert (d["prefill_steps"], d["decode_steps"], d["riding_steps"],
            d["sample_calls"]) == (1, 1, 1, 1)


def test_nothing_rides_where_nothing_decodes_or_no_decode_is_asked():
    """A step that admits with no slot active runs the same prefill program
    with nobody marked active and then ``decode_step``, as before;
    ``step(decode=False)`` and ``prefill_only`` carry nobody either, and
    leave the decoding slots where they were."""
    eng = _engine(num_pages=None)
    rng = np.random.default_rng(9)
    sp = SamplingParams(max_tokens=5)
    eng.add_request("a", rng.integers(0, VOCAB, 6).tolist(), sp)
    eng.step()
    m = dict(eng.metrics)
    assert (m["prefill_steps"], m["decode_steps"], m["riding_steps"]) == (1, 1, 0)
    assert m["sample_calls"] == 2
    first = eng.prefill_only("b", rng.integers(0, VOCAB, 9).tolist(),
                             SamplingParams(max_tokens=1))
    assert first["finished"] and len(first["generated"]) == 1
    eng.add_request("c", rng.integers(0, VOCAB, 3).tolist(), sp)
    eng.step(decode=False)
    d = {k: eng.metrics[k] - v for k, v in m.items()}
    assert (d["prefill_steps"], d["decode_steps"], d["riding_steps"]) == (2, 0, 0)
    done = {}
    while eng.has_unfinished():
        done.update((o.request_id, o) for o in eng.step() if o.finished)
    assert sorted(done) == ["a", "c"]
    assert all(len(o.token_ids) == 5 for o in done.values())
    assert not eng.plain_buckets


class _NoRoomAt32:
    """``model_runner`` with a compiler that finds no room for the ``[1, 32]``
    prefill program that carries a decode step (or fails on it with
    ``error``)."""

    def __init__(self, mr, error=None):
        self._mr = mr
        self._error = error or jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: ran out of memory in hbm")

    def __getattr__(self, name):
        return getattr(self._mr, name)

    def prefill(self, params, cfg, cache, tokens, *rows):
        if tokens.shape == (1, 32) and len(rows) == 4:
            raise self._error
        return self._mr.prefill(params, cfg, cache, tokens, *rows)


def test_a_bucket_the_compiler_refuses_stays_the_plain_program():
    """The ``[1, 32]`` program with decode rows does not compile: the bucket's
    first use falls back to the plain program, the engine says why
    (``plain_buckets``), a step that admits at that bucket runs
    ``decode_step`` after the call as before, the 16 bucket goes on carrying
    the others' step, and every token is the one served alone."""
    import prefill_rows

    eng = _engine(num_pages=None)
    eng._mr = _NoRoomAt32(eng._mr)
    rng = np.random.default_rng(10)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (11, 20, 5)]
    sp = SamplingParams(max_tokens=6)
    alone = [eng.generate([p], sp, decode_text=False)[0].token_ids
             for p in prompts]
    assert list(eng.plain_buckets) == [32]
    assert "RESOURCE_EXHAUSTED" in eng.plain_buckets[32]
    before, got = dict(eng.metrics), {}
    for i, p in enumerate(prompts):  # one admitted a step, the others decoding
        eng.add_request(f"r{i}", p, sp)
        got.update((o.request_id, o.token_ids) for o in eng.step() if o.finished)
    while eng.has_unfinished():
        got.update((o.request_id, o.token_ids) for o in eng.step() if o.finished)
    assert [got[f"r{i}"] for i in range(3)] == alone
    d = {k: eng.metrics[k] - v for k, v in before.items()}
    # the 5-token prompt's call carried a step; the 20-token prompt's did not
    assert (d["prefill_steps"], d["riding_steps"], d["compiles"]) == (3, 1, 0)
    assert d["sample_calls"] == 3 + d["decode_steps"] - 1


def test_an_error_that_is_no_refusal_of_the_compiler_is_raised():
    """Only the compiler's own "no room" makes a bucket plain: any other
    error of the carrying program's first use is the caller's to see."""
    eng = _engine(num_pages=None)
    eng._mr = _NoRoomAt32(eng._mr, TypeError("a bug in the program"))
    rng = np.random.default_rng(10)
    with pytest.raises(TypeError, match="a bug in the program"):
        eng.generate([rng.integers(0, VOCAB, 20).tolist()],
                     SamplingParams(max_tokens=2), decode_text=False)
    assert not eng.plain_buckets


def test_padding_row_changes_no_page_row_by_slot_or_load(engine):
    import prefill_rows

    rng = np.random.default_rng(6)
    prefill_rows.padding_rows_write_nothing(
        engine, rng.integers(0, VOCAB, 7).tolist())


def test_engine_refuses_what_it_cannot_do(engine):
    with pytest.raises(ValueError, match="the model has 5"):
        _engine(expect_state_layers=0)
    with pytest.raises(ValueError, match="of 0 taps, the model's have 3"):
        _engine(expect_conv_taps=0)
    with pytest.raises(ValueError, match="holds 8 of 8"):
        _engine(expect_experts=4)
    with pytest.raises(ValueError):
        engine.export_kv("nobody")
    with pytest.raises(ValueError, match="conv_bias"):
        ref.program_overrides(dict(PUBLISHED, conv_bias=True), 64)


def test_training_module_matches_reference(engine):
    """``Transformer.apply`` (the module whose tree the engine reads) on a
    whole sequence, where its capacity drops nothing."""
    cfg = dataclasses.replace(engine.mcfg, capacity_factor=float(EXPERTS))
    toks = np.random.default_rng(9).integers(0, VOCAB, 21)
    got = Transformer(cfg).apply(engine.params, jnp.asarray(toks)[None])[0]
    assert _rel(got, _reference(engine, toks)) < TOL
    leaves = jax.tree_util.tree_leaves(engine.params["params"])
    assert sum(x.size for x in leaves) == cfg.num_params() \
        == ref.total_params(PUBLISHED)


def test_router_divides_by_the_published_sum():
    """``select_experts`` takes the sum's epsilon as an argument: 1e-6 where
    a configuration asks, and without it cells 6 and 8's 1e-20 to the bit."""
    from ray_tpu.ops.moe import select_experts

    logits = jnp.asarray(np.random.default_rng(0).normal(size=(6, 8)) - 12.0,
                         jnp.float32)
    bias = jnp.zeros(8)
    as_before = select_experts(logits, 2, True, "sigmoid", bias, 2.0)
    w20, e20, _ = select_experts(logits, 2, True, "sigmoid", bias, 2.0, 1e-20)
    w6, e6, s = select_experts(logits, 2, True, "sigmoid", bias, 2.0, 1e-6)
    np.testing.assert_array_equal(as_before[0], w20)
    np.testing.assert_array_equal(e6, e20)
    chosen = np.take_along_axis(np.asarray(s), np.asarray(e6), axis=-1)
    np.testing.assert_allclose(
        w6, chosen / (chosen.sum(-1, keepdims=True) + 1e-6) * 2.0, rtol=1e-6)
    assert float(jnp.abs(w6 - w20).max()) > 1e-3    # scores of ~1e-5: it shows


def test_adapter_counts_the_published_cut():
    cell = Cell("lfm2-8b-a1b.chat-saturated-b128",
                os.path.join(REPO, "BENCHMARK.json"))
    conf = cell.config
    over = ref.program_overrides(conf, 2560)
    assert over["layer_kinds"] == ("conv", "conv") + ("full", "conv", "conv",
                                                      "conv") * 3
    assert (over["n_experts"], over["conv_taps"], over["router_norm_eps"]) == (
        32, 3, 1e-6)
    mcfg = dataclasses.replace(CONFIGS["tiny"], **over)
    assert mcfg.head_dim == 64 and mcfg.n_experts_held == 32
    assert mcfg.num_params() == ref.total_params(conf) == 4_667_077_376
    assert mr._keys_per_group(mcfg) == 2
    e = EngineConfig(**conf["job"]["engine"])
    assert (e.max_num_seqs, e.num_pages) == (128, 1 + 128 * 10)
    assert json.dumps(conf)  # plain data
