"""AFMoE (Trinity-Large-Preview) as ONE RANK of an expert-parallel deployment:
gated attention with a per-head q/k norm, sliding layers that rotate and a
full layer that does not, sandwich norms, a scaled embedding, a sigmoid router
over all the deployment's experts of which this rank holds a share. The paged
engine (pages for the full layer, rings for the sliding ones, ONE cache
manager shared with the decoder-hybrid-decoder; decode through
``ops/paged_attention.py`` with plain grouped-query heads) and
``ops/moe.py:expert_layer(held=...)`` against the benchmark's plain reference
``benchmarks/architectures/afmoe.py``.

The model runs in float32 at a small size with the real pattern (5 layers:
one dense, then a whole period sliding, sliding, full, sliding of expert
layers; hidden 64, 4 query and 2 key heads of 32, which is NOT hidden / heads;
16 routed experts top-2 of which this rank holds 4; window 8, pages of 4), where
the only differences left between the two sides are the order of float32
sums: 1e-4 of the logits' norm admits that and nothing else, as the spoiled
references show.
"""

import copy
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import prefill_rows
from benchmarks.architectures import afmoe as ref
from benchmarks.registry import REPO, Cell
from ray_tpu.llm import LLMConfig
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import JaxLLMEngine
from ray_tpu.models.transformer import Transformer
from ray_tpu.ops.moe import expert_layer

TOL = 1e-4
WINDOW, VOCAB, RANKS, HELD = 8, 128, 4, 4
TYPES = ["sliding_attention"] * 3 + ["full_attention"] + ["sliding_attention"]
# the small model under the published key names
PUBLISHED = dict(
    name="afmoe-tiny", model_type="afmoe", hidden_act="silu", n_group=1,
    topk_group=1, num_expert_groups=1, num_limited_groups=1,
    rope_scaling=None, score_func="sigmoid", tie_word_embeddings=False,
    mup_enabled=True, num_shared_experts=1, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    intermediate_size=128, moe_intermediate_size=64, vocab_size=VOCAB,
    num_hidden_layers=5, num_dense_layers=1, layer_types=TYPES + TYPES,
    num_experts=HELD, num_experts_per_tok=2, route_norm=True,
    route_scale=2.448, rms_norm_eps=1e-5, rope_theta=10000,
    sliding_window=WINDOW, torch_dtype="float32",
    expert_parallel={"routed_experts": RANKS * HELD, "ranks": RANKS, "rank": 1},
    initializer={"attention": 0.2, "mlp": 0.15, "experts": 0.15,
                 "embedding": 0.05})
OVERRIDES = dict(ref.program_overrides(PUBLISHED, 64), dtype=jnp.float32,
                 remat=False)
RCFG = ref.reference_cfg(PUBLISHED)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _engine(overrides=OVERRIDES, **engine):
    geometry = dict(max_num_seqs=3, max_model_len=64, page_size=4,
                    prefill_bucket_min=16, expect_experts=HELD,
                    expect_routed_experts=RANKS * HELD)
    return JaxLLMEngine(LLMConfig(
        model_id="tiny", model_overrides=overrides,
        engine_config=EngineConfig(**dict(geometry, **engine))))


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _reference(eng, toks, spoil=None):
    params = ref.to_reference_params(eng.params["params"], PUBLISHED)
    rcfg = dict(RCFG)
    if spoil is not None:
        params = jax.tree_util.tree_map(lambda a: a, params)
        spoil(params, rcfg)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(params, jnp.asarray(toks)[None], rcfg)[0])


def _engine_logits(eng, seqs, prompt_lens, steps, bucket=16, slots=None,
                   first_page=1):
    """Prefill (one ``[1, bucket]`` call a sequence, as the engine makes them,
    into slot ``slots[i]``) then ``steps`` teacher-forced decode steps through
    rings and pages. Returns {slot: [1 + steps, vocab] logits}."""
    e, cfg = eng.ecfg, eng.mcfg
    B, MP = e.max_num_seqs, e.pages_per_seq
    slots = list(range(len(seqs))) if slots is None else slots
    tables = np.zeros((B, MP), np.int32)
    active = np.zeros(B, bool)
    cache = mr.init_cache(cfg, e.num_pages, e.page_size, B)
    assert prefill_rows.held(cache) == {"full", "window", "moe_load"}
    got, page = {}, first_page
    for s, toks, n in zip(slots, seqs, prompt_lens):
        need = -(-len(toks) // e.page_size)
        tables[s, :need] = np.arange(page, page + need)
        page += need
        batch = np.zeros((1, bucket), np.int32)
        batch[0, :n] = toks[:n]
        logits, cache = mr.prefill(
            eng.params, cfg, cache, jnp.asarray(batch),
            jnp.asarray([n], jnp.int32), jnp.asarray(tables[s:s + 1]),
            jnp.asarray([s], jnp.int32))
        got[s] = [np.asarray(logits[0])]
        active[s] = True
    last = np.zeros(B, np.int32)
    seq_lens = np.zeros(B, np.int32)
    for i in range(steps):
        for s, toks, n in zip(slots, seqs, prompt_lens):
            last[s] = toks[n + i]
            seq_lens[s] = n + i
        logits, cache = mr.decode_step(
            eng.params, cfg, cache, jnp.asarray(last), jnp.asarray(seq_lens),
            jnp.asarray(tables), jnp.asarray(active))
        for s in got:
            got[s].append(np.asarray(logits[s]))
    return {s: np.stack(v) for s, v in got.items()}, cache


# -- (a) the engine against the reference -------------------------------------------


@pytest.mark.parametrize("prompt_len,bucket", [
    (5, 16),    # shorter than the window, a padded bucket
    (8, 16),    # the window exactly
    (13, 16),   # longer than the window: the ring has wrapped in prefill
    (16, 16),   # a bucket with no padding, two windows
    (27, 32),   # three wraps and seven pages
])
def test_engine_matches_reference(engine, prompt_len, bucket):
    """Prefill's last-position logits and then a dozen decode steps: across the
    window's edge, a ring's wrap, further pages and RoPE inside a ring (keys
    rotated at their own positions, in whatever order the ring holds them), in
    a slot that is not the first, beside a second sequence of another
    length."""
    rng = np.random.default_rng(prompt_len)
    steps = 12
    seqs = [rng.integers(0, VOCAB, prompt_len + steps),
            rng.integers(0, VOCAB, 9 + steps)]
    got, cache = _engine_logits(engine, seqs, [prompt_len, 9], steps, bucket,
                                slots=[2, 0])
    for s, toks, n in ((2, seqs[0], prompt_len), (0, seqs[1], 9)):
        want = _reference(engine, toks[:n + steps])[n - 1:]
        assert _rel(got[s], want) < TOL, (s, _rel(got[s], want))
    # what routing did in the last step: four expert layers, the held experts
    load = np.asarray(cache.moe_load)
    assert load.shape == (4, HELD) and 0 < load.sum() <= 2 * 2 * 4


def test_every_slot_prefill_call(engine):
    """The benchmark's check calls prefill with every slot's row and no slot
    argument: row b fills slot b, and a row of length 0 disturbs nothing."""
    e, cfg = engine.ecfg, engine.mcfg
    rng = np.random.default_rng(3)
    toks = rng.integers(0, VOCAB, 11 + 3)
    B, MP = e.max_num_seqs, e.pages_per_seq
    tables = np.zeros((B, MP), np.int32)
    tables[0, :4] = np.arange(1, 5)
    batch = np.zeros((B, 16), np.int32)
    batch[0, :11] = toks[:11]
    lens = np.array([11, 0, 0], np.int32)
    active = np.array([True, False, False])
    cache = mr.init_cache(cfg, e.num_pages, e.page_size, B)
    logits, cache = mr.prefill(engine.params, cfg, cache, jnp.asarray(batch),
                               jnp.asarray(lens), jnp.asarray(tables))
    got = [np.asarray(logits[0])]
    for i in range(3):
        logits, cache = mr.decode_step(
            engine.params, cfg, cache,
            jnp.asarray([toks[11 + i], 0, 0], jnp.int32),
            jnp.asarray([11 + i, 0, 0], jnp.int32), jnp.asarray(tables),
            jnp.asarray(active))
        got.append(np.asarray(logits[0]))
    want = _reference(engine, toks)[10:]
    assert _rel(np.stack(got), want) < TOL
    assert np.isfinite(np.asarray(logits)).all()


# -- (b) the share ties to the model ---------------------------------------------


def _uncut_layer(seed=0, T=24):
    """An expert layer's inputs and ALL its experts' weights, in float32."""
    rng = np.random.default_rng(seed)
    d, f, R = 64, 64, RANKS * HELD
    normal = lambda *s, std=1.0: jnp.asarray(          # noqa: E731
        rng.normal(size=s) * std, jnp.float32)
    lp = {"router": normal(d, R, std=0.2), "expert_bias": normal(R, std=0.02),
          "gate_proj": normal(R, d, f, std=0.15),
          "up_proj": normal(R, d, f, std=0.15),
          "down_proj": normal(R, f, d, std=0.15)}
    return normal(T, d), lp


def _rank(lp, r):
    own = slice(r * HELD, (r + 1) * HELD)
    return dict(lp, **{n: lp[n][own] for n in ("gate_proj", "up_proj",
                                               "down_proj")})


@pytest.mark.parametrize("side", ["reference", "program"])
def test_routed_parts_of_all_ranks_sum_to_the_uncut_layer(side):
    """What each of the deployment's ranks computes of ``sum_e w_e SwiGLU_e``,
    summed over the ranks, is the uncut layer's routed part (the shared expert
    is added once, by whoever adds it): nothing is counted twice and nothing
    is left out, in the reference and in ``expert_layer(held=...)``."""
    x, lp = _uncut_layer()
    valid = jnp.ones(x.shape[0], bool)
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_experts(x, lp, dict(RCFG, first_expert=0))
        if side == "reference":
            parts = [ref.routed_experts(x, _rank(lp, r),
                                        dict(RCFG, first_expert=r * HELD))
                     for r in range(RANKS)]
        else:
            parts, loads = zip(*[expert_layer(
                x, valid, lp["router"], *(_rank(lp, r)[n] for n in (
                    "gate_proj", "up_proj", "down_proj")),
                top_k=2, norm_topk_prob=True, router_kind="sigmoid",
                router_bias=lp["expert_bias"], router_scale=2.448,
                held=(r * HELD, HELD)) for r in range(RANKS)])
            # every assignment is some rank's, once
            assert sum(int(l.sum()) for l in loads) == x.shape[0] * 2
    assert _rel(sum(parts), whole) < 1e-5
    assert _rel(parts[1], whole) > 0.3     # one rank alone is not the layer


# -- (c) every recalled part shows in the logits -----------------------------------


def _no_gate(p, rcfg):
    rcfg["attention_gate"] = False


def _no_expert_bias(p, rcfg):
    for lp in p["layers"]:
        if "expert_bias" in lp:
            lp["expert_bias"] = lp["expert_bias"] * 0.0


def _no_rope_in_sliding(p, rcfg):
    rcfg["rotated"] = ()


def _rope_in_full_too(p, rcfg):
    rcfg["rotated"] = ("sliding_attention", "full_attention")


def _no_window(p, rcfg):
    rcfg["sliding_window"] = 0


def _no_embed_scale(p, rcfg):
    rcfg["embed_scale"] = 1.0


def _no_post_norm(p, rcfg):
    rcfg["sandwich_norm"] = False


def _no_qk_norm(p, rcfg):
    rcfg["qk_norm"] = False


def _experts_of_another_rank(p, rcfg):
    rcfg["first_expert"] = 0


def _no_route_scale(p, rcfg):
    rcfg["route_scale"] = 1.0


@pytest.mark.parametrize("spoil", [
    _no_gate, _no_expert_bias, _no_rope_in_sliding, _rope_in_full_too,
    _no_window, _no_embed_scale, _no_post_norm, _no_qk_norm,
    _experts_of_another_rank, _no_route_scale])
def test_dropped_part_fails_the_comparison(engine, spoil):
    rng = np.random.default_rng(7)
    toks = rng.integers(0, VOCAB, 13 + 6)
    got = _engine_logits(engine, [toks], [13], 6)[0][0]
    assert _rel(got, _reference(engine, toks)[12:]) < TOL
    assert _rel(got, _reference(engine, toks, spoil)[12:]) > 30 * TOL


# -- (d) expert_layer told it holds everything is the function cells 5 and 6 run ---


@pytest.mark.parametrize("kind,bias", [("softmax", False), ("sigmoid", True)])
def test_held_all_is_the_unshared_layer_to_the_bit(kind, bias):
    x, lp = _uncut_layer(seed=1, T=19)
    R = RANKS * HELD
    valid = jnp.asarray(np.arange(19) % 5 != 0)
    kw = dict(top_k=2, norm_topk_prob=kind == "sigmoid", router_kind=kind,
              router_bias=lp["expert_bias"] if bias else None,
              router_scale=2.448 if bias else 1.0)
    args = (x, valid, lp["router"], lp["gate_proj"], lp["up_proj"],
            lp["down_proj"])
    y0, load0 = expert_layer(*args, **kw)
    y1, load1 = expert_layer(*args, held=(0, R), **kw)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(load0), np.asarray(load1))
    assert int(load0.sum()) == int(valid.sum()) * 2


# -- (e) one cache manager: where a request's state lies changes no bit -----------


def _hybrid_engine():
    import test_hybrid

    return test_hybrid._engine()


@pytest.mark.parametrize("model", ["afmoe", "hybrid"])
def test_cache_manager_places_state_to_the_bit(engine, model):
    """The same request in slot 0 on the first pages and in slot 2 on later
    ones, beside another request: pages by the block tables, rings by slot
    (and a decoder-hybrid-decoder's rows by slot), through the ONE manager
    both models share: the logits are the same to the bit, and the cache has
    the manager's layout (a layer axis on the pages of each, one paged layer
    in cell 7's model)."""
    eng = engine if model == "afmoe" else _hybrid_engine()
    if model == "afmoe":
        run = _engine_logits
    else:
        import test_hybrid

        def run(*a, first_page=1, **kw):
            assert first_page == 1
            return test_hybrid._engine_logits(*a, **kw), None
    rng = np.random.default_rng(11)
    toks = rng.integers(0, VOCAB, 13 + 8)
    other = rng.integers(0, VOCAB, 6 + 8)
    alone = run(eng, [toks], [13], 8)[0][0]
    moved = run(eng, [other, toks], [6, 13], 8, slots=[0, 2])[0][2]
    np.testing.assert_array_equal(alone, moved)
    e, cfg = eng.ecfg, eng.mcfg
    cache = mr.init_cache(cfg, e.num_pages, e.page_size, e.max_num_seqs)
    kinds = cfg.layer_kinds
    assert cache["full"].shape[:3] == (kinds.count("full"), e.num_pages,
                                     e.page_size)
    assert cache["window"].shape[:3] == (kinds.count("window"), e.max_num_seqs,
                                     cfg.window)
    assert ("mamba" in cache) == ("mamba" in kinds)
    # a ring is read in blocks of a page where the window is whole pages
    assert mr._ring_blocks(cache["window"], cfg, e.page_size).shape[1:3] == (
        e.max_num_seqs * (cfg.window // e.page_size), e.page_size)


# -- the engine, its counters, the training module, the files ----------------------


def test_engine_serves_and_counts_the_share():
    """Requests through ``JaxLLMEngine.step()``: greedy tokens are the
    reference's own argmax chain, and the routing counters tell the held
    assignments from the routed ones."""
    eng = _engine()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (11, 5, 19)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=7), decode_text=False)
    for prompt, out in zip(prompts, outs):
        toks = list(prompt)
        for t in out.token_ids:
            assert t == int(np.argmax(_reference(eng, toks)[-1]))
            toks.append(t)
    m = eng.metrics
    assert m["moe_decode_layer_steps"] == 4 * (
        m["decode_steps"] - m["riding_steps"])
    assert m["moe_decode_routed_assignments"] == 2 * 4 * (
        m["generated_tokens"] - m["admitted"])
    assert 0 < m["moe_decode_assignments"] < m["moe_decode_routed_assignments"]
    assert m["moe_decode_experts_touched"] <= m["moe_decode_assignments"]
    assert m["shared_kv_live_tokens"] > m["window_live_tokens"] / 4 > 0
    assert m["prefill_cross_rows"] == 0


@pytest.mark.parametrize("lens,rows,bucket", [((11, 5, 14), 4, 16),
                                              ((19, 9), 2, 32)])
def test_burst_admitted_in_one_step_shares_a_prefill_call(lens, rows, bucket):
    """Requests admitted in one step are rows of ONE prefill call: each row's
    pages by its block table, its rings by its slot, and the tokens those the
    same requests generate one a step."""
    eng = _engine()
    rng = np.random.default_rng(5)
    d = prefill_rows.burst_equals_one_a_step(
        eng, [rng.integers(0, VOCAB, n).tolist() for n in lens])
    assert (d["prefill_calls"], d["prefill_batch_tokens"]) == (1, rows * bucket)


def test_decode_rows_ride_a_prefill_call(engine):
    """``prefill`` with ``riders`` is the call and then ``decode_step``: the
    prompt's logits, the step's logits, the pages and the rings of the slot
    that decodes and of the slot that is filled again, beside a padding row
    and a slot that is not active (whose state it leaves as it found it)."""
    prefill_rows.riders_equal_a_step_after_the_call(
        engine, np.random.default_rng(7), TOL)
    with pytest.raises(ValueError, match="no decode rows ride"):
        mr.prefill(engine.params, dataclasses.replace(
            engine.mcfg, layer_kinds=()), engine.cache, None, None, None,
            riders=())


def test_riding_calls_match_reference():
    """Three requests through the calls the engine makes when it admits
    beside decoding slots: the second's ``[1, 16]`` call carries the first's
    third step, the third's ``[1, 32]`` (past the window, so its rings wrap in
    the call) the other two's, ``decode_step`` in between and after:
    every position's logits against the reference (what the chip test runs at
    the published widths)."""
    eng = _engine()
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
    eng = _engine()
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
    eng = _engine(max_num_seqs=slots)
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
    eng = _engine()
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


@pytest.mark.parametrize("model", ["afmoe", "hybrid"])
def test_padding_row_changes_no_page_ring_row_by_slot_or_load(engine, model):
    """Through ``_forward`` and through the decoder-hybrid-decoder's
    ``_hybrid_prefill`` (recurrent rows by slot beside the rings)."""
    eng = engine if model == "afmoe" else _hybrid_engine()
    rng = np.random.default_rng(6)
    prefill_rows.padding_rows_write_nothing(
        eng, rng.integers(0, eng.mcfg.vocab_size, 7).tolist())


def test_engine_refuses_what_it_cannot_do(engine):
    with pytest.raises(ValueError, match="holds 4 of 16"):
        _engine(expect_experts=RANKS * HELD)
    with pytest.raises(ValueError, match="4 experts a layer of 4 routed"):
        _engine(expect_routed_experts=0)
    with pytest.raises(ValueError, match="rings"):
        engine.export_kv("nobody")


def test_training_module_matches_reference(engine):
    """``Transformer.apply`` (the module whose tree the engine reads) on a
    whole sequence, where its capacity drops nothing."""
    cfg = dataclasses.replace(engine.mcfg, capacity_factor=float(RANKS * HELD))
    toks = np.random.default_rng(9).integers(0, VOCAB, 21)
    got = Transformer(cfg).apply(engine.params, jnp.asarray(toks)[None])[0]
    assert _rel(got, _reference(engine, toks)) < TOL


def test_adapter_counts_the_published_cut():
    cell = Cell("trinity-large-preview.shortlong-saturated-b32",
                os.path.join(REPO, "BENCHMARK.json"))
    conf = cell.config
    assert cell.architecture() is not None
    # the arithmetic of ISSUE 37, from the widths alone
    assert ref.total_params(conf) == 4_321_903_872
    over = ref.program_overrides(conf, 16896)
    assert over["layer_kinds"] == ("window", "window", "window", "full",
                                   "window")
    assert (over["n_experts"], over["experts_held"]) == (256, (0, 32))
    assert over["head_size"] == 128 != over["d_model"] // over["n_heads"]
    mcfg = dataclasses.replace(engine_cfg(), **over)
    assert mcfg.num_params() == ref.total_params(conf)
    assert ref.experts_touched(conf, 32) == 12
    eng = conf["job"]["engine"]
    assert EngineConfig(**eng).num_pages == 1 + 32 * 33
    # every published width is in the file unchanged
    assert [conf[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "moe_intermediate_size", "intermediate_size",
        "num_experts_per_tok", "sliding_window", "route_scale")] == [
        3072, 48, 8, 128, 3072, 12288, 4, 4096, 2.448]
    assert sorted(conf["reduced"]) == sorted([
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size",
        "max_position_embeddings"])
    assert json.dumps(conf)  # plain data


def engine_cfg():
    from ray_tpu.models.transformer import CONFIGS

    return CONFIGS["tiny"]


def test_to_reference_params_round_trip(engine):
    p = ref.to_reference_params(engine.params["params"], PUBLISHED)
    assert len(p["layers"]) == 5
    assert "router" not in p["layers"][0] and "router" in p["layers"][1]
    assert p["layers"][1]["gate_proj"].shape == (HELD, 64, 64)
    assert p["layers"][1]["router"].shape == (64, RANKS * HELD)
    assert p["layers"][0]["q_proj"].shape == (64, 4 * 32)
    assert copy.copy(RCFG)["first_expert"] == HELD
