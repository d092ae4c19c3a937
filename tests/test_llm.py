"""LLM layer tests (CPU tier — SURVEY.md §4: accelerator features need a
hardware-free tier). Covers: paged-KV decode vs. the training forward,
continuous batching determinism, page-boundary growth, serve + data
integration."""

import contextlib

import numpy as np
import pytest

from ray_tpu.llm.config import EngineConfig, LLMConfig, SamplingParams


def make_config(**ekw):
    eng = dict(max_num_seqs=4, max_model_len=128, page_size=16,
               prefill_bucket_min=16)
    eng.update(ekw)
    return LLMConfig(model_id="tiny", engine_config=EngineConfig(**eng),
                     model_overrides={"attention_impl": "xla"})


@pytest.fixture(scope="module")
def engine():
    from ray_tpu.llm.engine import JaxLLMEngine

    return JaxLLMEngine(make_config(), seed=0)


def test_decode_matches_training_forward(engine):
    """Greedy generation through the paged cache must equal argmax over the
    training model's full forward re-run each step (same params)."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import Transformer

    model = Transformer(engine.mcfg)
    prompt = engine.tokenizer.encode("check equivalence")
    out = engine.generate([list(prompt)], SamplingParams(max_tokens=6))[0]

    toks = list(prompt)
    expect = []
    for _ in range(6):
        logits = model.apply(engine.params, jnp.asarray([toks]))
        nxt = int(jnp.argmax(logits[0, -1]))
        expect.append(nxt)
        if nxt == engine.tokenizer.eos_token_id:
            break
        toks.append(nxt)
    assert out.token_ids == expect


def _plain_kv(params, cfg, tokens):
    """K (after RoPE) and V of every layer for one whole sequence, by a dense
    causal forward with no cache: [(k [T, KVH, HD], v [T, KVH, HD])] * L."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import _rope

    p = params["params"]
    T = len(tokens)
    pos = jnp.arange(T)[None]
    x = p["embed"][jnp.asarray(tokens)][None]

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale

    out = []
    for i in range(cfg.n_layers):
        lp = p[f"layer_{i}"]
        h = norm(x, lp["attn_norm"]["scale"])
        q = _rope(jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["q_proj"]["kernel"]),
                  pos, cfg.rope_theta)
        k = _rope(jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["k_proj"]["kernel"]),
                  pos, cfg.rope_theta)
        v = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["v_proj"]["kernel"])
        out.append((np.asarray(k[0]), np.asarray(v[0])))
        rep = cfg.n_heads // cfg.n_kv_heads
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, 2)) / cfg.head_dim ** 0.5
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), jnp.repeat(v, rep, 2))
        x = x + jnp.einsum("bshk,hkd->bsd", a, lp["attn"]["o_proj"]["kernel"])
        h = norm(x, lp["mlp_norm"]["scale"])
        m = lp["mlp"]
        x = x + (jax.nn.silu(h @ m["gate_proj"]["kernel"]) * (h @ m["up_proj"]["kernel"])
                 ) @ m["down_proj"]["kernel"]
    return out


@pytest.mark.parametrize("n_kv_heads", [2, 4], ids=["gqa4-2", "mha4-4"])
def test_cache_rows_land_at_their_pages_and_nothing_else_moves(n_kv_heads):
    """``prefill`` then four ``decode_step``s on a cache of random bits, with
    unordered non-contiguous pages, an inactive slot, padded prompts and
    sequences that cross a page boundary: every written row sits at (layer,
    block_table[b, pos // P], pos % P) and holds the K/V a dense forward
    gives, and every other row outside scratch page 0 keeps its bits."""
    import dataclasses

    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm.kinds.attention import KV
    from ray_tpu.models.transformer import CONFIGS, Transformer

    cfg = dataclasses.replace(CONFIGS["tiny"], n_kv_heads=n_kv_heads,
                              dtype=jnp.float32, attention_impl="xla")
    params = nn.meta.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)))
    P, NP, S, steps = 4, 21, 8, 4
    block_tables = np.array([[7, 3, 12, 5], [9, 2, 15, 1], [14, 6, 11, 4],
                             [8, 10, 13, 16]], np.int32)  # pages 17-20: no one's
    lengths = np.array([6, 3, 8, 0], np.int32)  # two padded, one full, one idle
    active = lengths > 0
    rng = np.random.default_rng(0)
    fed = rng.integers(1, cfg.vocab_size, (4, S + steps)).astype(np.int32)
    shape = (cfg.n_layers, NP, P, n_kv_heads, cfg.head_dim)
    before = [rng.standard_normal(shape).astype(np.float32) for _ in "kv"]
    cache = mr.Cache({"dense": KV(*(jnp.asarray(a) for a in before))})

    prompt = np.where(np.arange(S)[None] < lengths[:, None], fed[:, :S], 0)
    _, cache = mr.prefill(params, cfg, cache, jnp.asarray(prompt),
                          jnp.asarray(lengths), jnp.asarray(block_tables))
    seqs = [list(prompt[b, :lengths[b]]) for b in range(4)]
    for t in range(steps):
        last = fed[:, S + t]
        _, cache = mr.decode_step(
            params, cfg, cache, jnp.asarray(last),
            jnp.asarray([len(s) for s in seqs], jnp.int32),
            jnp.asarray(block_tables), jnp.asarray(active))
        for b in np.flatnonzero(active):
            seqs[b].append(last[b])
    assert [len(s) for s in seqs] == [10, 7, 12, 0]  # 7 and 12 crossed a page

    import prefill_rows

    assert prefill_rows.held(cache) == {"dense"}
    got = [np.asarray(leaf) for leaf in cache["dense"]]
    untouched = np.ones(shape[:3], bool)
    untouched[:, 0] = False  # scratch page: masked writes land there
    for b in np.flatnonzero(active):
        pos = np.arange(len(seqs[b]))
        page, off = block_tables[b, pos // P], pos % P
        for i, kv in enumerate(_plain_kv(params, cfg, seqs[b])):
            for g, want in zip(got, kv):
                np.testing.assert_allclose(g[i, page, off], want,
                                           rtol=1e-4, atol=1e-5)
            untouched[i, page, off] = False
    assert untouched.sum() == cfg.n_layers * (20 * P - 29)
    for g, was in zip(got, before):
        assert np.array_equal(g[untouched], was[untouched])


def test_continuous_batching_matches_sequential(engine):
    prompts = ["hello world", "the quick brown fox", "a", "zzzz"]
    batched = engine.generate(prompts, SamplingParams(max_tokens=8))
    singles = [engine.generate([p], SamplingParams(max_tokens=8))[0]
               for p in prompts]
    assert [o.token_ids for o in batched] == [o.token_ids for o in singles]
    assert all(o.finished for o in batched)


@pytest.mark.parametrize("sp", [
    SamplingParams(max_tokens=8),
    SamplingParams(max_tokens=8, temperature=0.9, top_k=16, seed=5),
], ids=["greedy", "seeded"])
def test_burst_of_four_length_buckets_matches_one_at_a_time(engine, sp):
    """Prompts of 9, 25, 50 and 100 tokens (buckets 16 to 128) admitted in
    one step: the burst gives every prompt the tokens it gets when served
    alone. Under a cap of 128 padded tokens the longest is a call of its own,
    the 64 and 32 buckets share a ``[2, 64]`` call, and the shortest, whose
    bucket is a quarter of 64 but finds no row left under the cap, is the
    ``[1, 16]`` call it always was: the device computed 128 + 128 + 16
    positions in three calls, not 4 x 128 in one or in four."""
    prompts = [list(range(3, 3 + n)) for n in (9, 25, 50, 100)]
    singles = [engine.generate([p], sp)[0].token_ids for p in prompts]
    assert engine._row_shapes.wait(300)
    before = dict(engine.metrics)
    burst = engine.generate(prompts, sp)
    assert [o.token_ids for o in burst] == singles
    d = {k: engine.metrics[k] - v for k, v in before.items()}
    assert d["prefill_batch_tokens"] == 128 + 2 * 64 + 16
    assert (d["prefill_steps"], d["admitted"], d["prefill_calls"]) == (1, 4, 3)


# -- several admitted requests' rows in one prefill call (ISSUE 41) ----------


@pytest.mark.parametrize("buckets,cap,ready,want", [
    # one admitted request is the [1, S] call of today
    ([256], 2560, None, [(1, 256, [0])]),
    ([512, 512], 2560, None, [(2, 512, [0, 1])]),
    # S is the longest member's bucket; the order of admission is kept
    ([512, 1024], 2560, None, [(2, 1024, [1, 0])]),
    # more than 512 positions of padding for the one call saved: apart
    ([256, 1024], 2560, None, [(1, 1024, [1]), (1, 256, [0])]),
    ([4096, 2048], 16896, None, [(1, 4096, [0]), (1, 2048, [1])]),
    # R x S never passes the cap
    ([2048, 512], 2560, None, [(1, 2048, [0]), (1, 512, [1])]),
    ([1024] * 3, 2560, None, [(2, 1024, [0, 1]), (1, 1024, [2])]),
    # three take the row bucket of four, with one row of padding, while
    # that row is within 512 positions for each of the two calls saved
    ([512] * 3, 2560, None, [(4, 512, [0, 1, 2])]),
    ([1024] * 3, 16896, None, [(4, 1024, [0, 1, 2])]),
    ([4096] * 3, 16896, None, [(2, 4096, [0, 1]), (1, 4096, [2])]),
    ([2048] * 4, 16896, None, [(2, 2048, [0, 1]), (2, 2048, [2, 3])]),
    ([256] * 5, 2560, None, [(4, 256, [0, 1, 2, 3]), (1, 256, [4])]),
    ([128, 512, 256, 512, 128], 2560, None,
     [(4, 512, [1, 3, 2, 0]), (1, 128, [4])]),
    # a group forms only at a shape that is ready
    ([512] * 3, 2560, {(2, 512)}, [(2, 512, [0, 1]), (1, 512, [2])]),
    ([512] * 2, 2560, {(4, 512)}, [(1, 512, [0]), (1, 512, [1])]),
    ([512] * 3, 2560, set(), [(1, 512, [0]), (1, 512, [1]), (1, 512, [2])]),
    ([], 2560, None, []),
])
def test_prefill_groups_by_hand(buckets, cap, ready, want):
    from ray_tpu.llm.engine import prefill_groups

    usable = (lambda R, S: True) if ready is None else (
        lambda R, S: (R, S) in ready)
    assert prefill_groups(buckets, cap, usable) == want


@pytest.mark.parametrize("seed", range(6))
def test_prefill_groups_place_every_request_once_within_the_limits(seed):
    """Random admitted sets against the rule's own promises: every request in
    exactly one call, its first rows; ``R`` 1, 2 or 4 and more than half
    filled; no group over the cap or at a shape that is not ready; ``S`` the
    longest member's bucket; no more than 512 positions of padding beyond the
    members' own buckets for every call saved."""
    from ray_tpu.llm.engine import prefill_groups, row_buckets

    rng = np.random.default_rng(seed)
    sizes = [128, 256, 512, 1024, 2048, 4096]
    grouped = 0
    for _ in range(200):
        buckets = rng.choice(sizes, rng.integers(1, 10)).tolist()
        cap = int(rng.choice([2048, 2560, 4096, 8192, 16896]))
        unready = {(int(rng.choice([2, 4])), int(rng.choice(sizes)))
                   for _ in range(rng.integers(0, 4))}
        calls = prefill_groups(buckets, cap, lambda R, S: (R, S) not in unready)
        placed = sorted(i for _, _, members in calls for i in members)
        assert placed == list(range(len(buckets)))
        for R, S, members in calls:
            own = [buckets[i] for i in members]
            assert R in (1, 2, 4) and R // 2 < len(members) <= R
            assert S == max(own)
            if R > 1:
                assert R * S <= cap and R in row_buckets(S, cap)
                assert (R, S) not in unready
                assert R * S - sum(own) <= 512 * (len(own) - 1)
                grouped += 1
    assert grouped > 100


def test_padding_row_changes_no_page_and_no_logits_row(engine):
    import prefill_rows

    prefill_rows.padding_rows_write_nothing(engine, list(range(5, 26)))


def test_counters_after_a_known_burst():
    """Six prompts into six slots in one step, after each bucket was used
    once: 16, 16, 16 and 32, 32 and 64 under a cap of 128. The longest takes
    one 32 along (``[2, 64]`` fits the cap; four rows of 64 do not), then the
    other 32 leads the three 16s in ``[4, 32]``."""
    from ray_tpu.llm.engine import JaxLLMEngine

    eng = JaxLLMEngine(make_config(max_num_seqs=6), seed=0)
    sp = SamplingParams(max_tokens=3)
    for n in (9, 25, 50):
        eng.generate([list(range(3, 3 + n))], sp)
    assert eng._row_shapes.wait(300)
    m = eng.metrics
    # (2, S) and (4, S) of every bucket used, while R x S <= 128: asked for
    # at the bucket's first use, none failed
    assert m["prefill_shapes_wanted"] == m["prefill_shapes_ready"] == 5
    assert m["compiles"] == 4 and not eng._row_shapes.failed
    before = dict(m)
    lens = (5, 9, 14, 20, 30, 40)
    for i, n in enumerate(lens):
        eng.add_request(f"r{i}", list(range(3, 3 + n)), sp)
    assert eng.step() == []
    d = {k: m[k] - v for k, v in before.items()}
    assert (d["prefill_steps"], d["admitted"], d["prefill_calls"]) == (1, 6, 2)
    assert d["prefill_tokens"] == sum(lens)
    assert d["prefill_batch_tokens"] == 2 * 64 + 4 * 32
    assert d["prefill_phase_calls"] == 0   # moves with the read
    while eng.has_unfinished():
        eng.step()
    d = {k: m[k] - v for k, v in before.items()}
    assert d["prefill_phase_calls"] == d["prefill_calls"] == 2
    assert d["compiles"] == d["prefill_shapes_ready"] == 0
    assert d["generated_tokens"] == 6 * 3


@pytest.mark.parametrize("platform,kernels", [("cpu", []),
                                              ("tpu", ["flash_fwd"])])
def test_exporting_process_chooses_as_the_platform_it_lowers_for(platform,
                                                                 kernels):
    """The process that traces the programs of several rows is held to the
    CPU whatever the serving process runs on. What is chosen at trace time
    (``attention_impl="auto"``, every serve cell's, takes the flash kernel on
    the chip alone) has to come out as the serving process's own ``[1, S]``
    trace has it: a program exported for the chip holds ``flash_fwd``."""
    import re

    from jax import export

    from ray_tpu.llm.engine import JaxLLMEngine

    eng = JaxLLMEngine(LLMConfig(
        model_id="tiny", engine_config=EngineConfig(
            max_num_seqs=2, max_model_len=256, prefill_bucket_min=128),
        model_overrides={"n_heads": 1, "n_kv_heads": 1, "max_seq_len": 256}),
        seed=0)
    assert (eng.mcfg.attention_impl, eng.mcfg.head_dim) == ("auto", 64)
    shapes = eng._row_shapes
    shapes._platform = platform
    blob, = shapes._export([(2, 128)])
    text = export.deserialize(blob).mlir_module()
    assert sorted(set(re.findall(r'kernel_name = "(\w+)"', text))) == kernels


@pytest.mark.parametrize("broken", ["one", "all"])
def test_a_shape_that_cannot_be_made_is_named_and_never_used(
        engine, broken, monkeypatch, caplog):
    """A program of several rows that the exporting process or the compiler
    refuses (no room on the device, say) is never ready: it stands in
    ``failed`` with the reason, the log names it, ``prefill_shapes_ready``
    stays under ``prefill_shapes_wanted``, the other shapes are made all the
    same, and the requests that would have shared it go as the one-row calls
    they always were."""
    from ray_tpu.llm import prefill_shapes
    from ray_tpu.llm.engine import JaxLLMEngine

    eng = JaxLLMEngine(make_config(), params=engine.params, seed=0)
    if broken == "one":
        export = prefill_shapes.RowShapes._export

        def refused(self, shapes):
            for shape, blob in zip(shapes, export(self, shapes)):
                yield RuntimeError("RESOURCE_EXHAUSTED: no room") \
                    if shape == (2, 32) else blob
        monkeypatch.setattr(prefill_shapes.RowShapes, "_export", refused)
    else:  # the exporting process cannot lower for it: an error a shape
        eng._row_shapes._platform = "no-such-platform"
    sp = SamplingParams(max_tokens=3)
    prompts = [list(range(3, 28)), list(range(40, 60))]  # both of bucket 32
    alone = [eng.generate([p], sp)[0].token_ids for p in prompts]
    with caplog.at_level("WARNING", logger=prefill_shapes.__name__):
        assert eng._row_shapes.wait(300)
    m, made = eng.metrics, eng._row_shapes
    lost = 1 if broken == "one" else 2  # of the one bucket's [2, 32], [4, 32]
    assert m["prefill_shapes_wanted"] == 2 == m["prefill_shapes_ready"] + lost
    assert len(made.failed) == lost and (2, 32) in made.failed
    assert (2, 32) not in made.ready and len(made.ready) == 2 - lost
    assert f"{2 - lost} of 2 ready" in caplog.text
    assert "[2, 32] not made" in caplog.text
    if broken == "one":
        assert "RESOURCE_EXHAUSTED" in made.failed[2, 32]
    before = m["prefill_calls"]
    assert [o.token_ids for o in eng.generate(prompts, sp)] == alone
    assert m["prefill_calls"] - before == 2


def test_preempted_request_is_prefilled_again_beside_a_new_one(engine):
    """Two long requests run the pool dry; the one sent back waits at the head
    of the queue with a new request behind it, and the step that frees the
    pages admits both: one ``[2, 64]`` call holds the preempted request's
    prompt + generated tokens and the newcomer's prompt. Greedy tokens are a
    roomy engine's."""
    from ray_tpu.llm.engine import JaxLLMEngine

    sp = SamplingParams(max_tokens=30)
    prompts = {"a": list(range(3, 33)), "b": list(range(40, 70)),
               "new": list(range(80, 97))}
    roomy = JaxLLMEngine(make_config(max_num_seqs=3), params=engine.params,
                         seed=0)
    expect = {k: roomy.generate([p], sp)[0].token_ids
              for k, p in prompts.items()}
    eng = JaxLLMEngine(make_config(max_num_seqs=3, num_pages=7),
                       params=engine.params, seed=0)
    for n in (10, 20, 40):   # each bucket once: its shapes of several rows
        eng.generate([list(range(3, 3 + n))], SamplingParams(max_tokens=2))
    assert eng._row_shapes.wait(300)
    m = eng.metrics
    eng.add_request("a", prompts["a"], sp)
    eng.add_request("b", prompts["b"], sp)
    got, shared = {}, []
    while eng.has_unfinished():
        if m["preempted"] == 1 and "new" in prompts:
            eng.add_request("new", prompts.pop("new"), sp)
        before = (m["admitted"], m["prefill_calls"])
        for o in eng.step():
            if o.finished:
                got[o.request_id] = o.token_ids
        if m["admitted"] - before[0] == 2 and m["preempted"]:
            shared.append(m["prefill_calls"] - before[1])
    assert m["preempted"] == 1 and shared == [1]
    assert got == expect
    assert sorted(eng._free_pages) == list(range(1, 7))


def _plain_loop(config, params, requests, eos):
    """The order the engine's tokens are held to, written out with nothing in
    flight: admit into free slots in arrival order, prefill each alone at its
    bucket, ONE sampler call, read, emit; one decode step over the active
    slots, a sampler call, read, emit. ``requests``: (id, prompt, params)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import model_runner as mr

    e, cfg = config.engine_config, config.transformer_config()
    B, MP = e.max_num_seqs, e.pages_per_seq
    cache = mr.init_cache(cfg, 1 + B * MP, e.page_size)
    tables = 1 + np.arange(B * MP, dtype=np.int32).reshape(B, MP)  # fixed
    rng = jax.random.PRNGKey(0)
    slots, waiting = [None] * B, list(requests)
    out = {rid: [] for rid, _, _ in requests}
    lens, last = np.zeros(B, np.int32), np.zeros(B, np.int32)
    prefill_logits = np.zeros((B, cfg.vocab_size), np.float32)

    def sample(logits):
        nonlocal rng
        rng, sub = jax.random.split(rng)
        sps = [s[2] if s else SamplingParams() for s in slots]
        seeds = [-1 if sp.seed is None else sp.seed for sp in sps]
        steps = [len(out[s[0]]) if s else 0 for s in slots]
        return np.asarray(mr.sample_tokens(
            jnp.asarray(logits), sub,
            jnp.asarray([sp.temperature for sp in sps], jnp.float32),
            jnp.asarray([sp.top_k for sp in sps], jnp.int32),
            jnp.asarray([sp.top_p for sp in sps], jnp.float32),
            jnp.asarray(seeds, jnp.int32), jnp.asarray(steps, jnp.int32),
            max_top_k=e.max_top_k))

    def emit(i, token):
        rid, prompt, sp = slots[i]
        out[rid].append(token)
        last[i] = token
        if (token == eos or token in sp.stop_token_ids
                or len(out[rid]) >= sp.max_tokens
                or len(prompt) + len(out[rid]) >= e.max_model_len):
            slots[i] = None

    while waiting or any(slots):
        admitted = [i for i in range(B) if slots[i] is None][:len(waiting)]
        for i in admitted:
            slots[i] = waiting.pop(0)
            prompt = slots[i][1]
            S = e.prefill_bucket_min
            while S < len(prompt):
                S *= 2
            toks = np.zeros((1, min(S, e.max_model_len)), np.int32)
            toks[0, :len(prompt)] = prompt
            lens[i] = len(prompt)
            logits, cache = mr.prefill(
                params, cfg, cache, jnp.asarray(toks),
                jnp.asarray(lens[i:i + 1]), jnp.asarray(tables[i:i + 1]))
            prefill_logits[i] = np.asarray(logits[0])
        if admitted:
            toks = sample(prefill_logits)
            for i in admitted:
                emit(i, int(toks[i]))
        active = np.array([s is not None for s in slots])
        if active.any():
            logits, cache = mr.decode_step(
                params, cfg, cache, jnp.asarray(last), jnp.asarray(lens),
                jnp.asarray(tables), jnp.asarray(active))
            toks = sample(logits)
            for i in np.flatnonzero(active):
                lens[i] += 1
                emit(i, int(toks[i]))
    return out


@pytest.mark.parametrize("sampling", [
    {}, {"temperature": 0.9, "top_k": 16}], ids=["greedy", "seeded"])
def test_tokens_equal_the_synchronous_loops(engine, sampling):
    """Seven requests over four slots, prompts of four length buckets,
    answers of 3 to 12 tokens, one ended by a stop token: the engine, which
    reads every token a step late, gives each request token for token what the
    plain synchronous loop gives it, and nothing after the stop token."""
    from ray_tpu.llm.engine import JaxLLMEngine

    def requests(stop=()):
        return [(f"q{i}", list(range(3 + i, 3 + i + n)), SamplingParams(
            max_tokens=most, stop_token_ids=stop if i == 2 else (),
            seed=100 + i if sampling else None, **sampling))
            for i, (n, most) in enumerate(zip((5, 20, 40, 9, 70, 12, 33),
                                              (6, 12, 9, 3, 5, 10, 4)))]

    cfg = make_config()
    eos = engine.tokenizer.eos_token_id
    free = _plain_loop(cfg, engine.params, requests(), eos)["q2"]
    stop = free[2]
    assert stop not in free[:2] and len(free) > 3
    want = _plain_loop(cfg, engine.params, requests((stop,)), eos)
    assert want["q2"] == free[:3]

    eng = JaxLLMEngine(cfg, params=engine.params, seed=0)
    for rid, prompt, sp in requests((stop,)):
        eng.add_request(rid, prompt, sp)
    got, emitted = {}, {rid: 0 for rid in want}
    while eng.has_unfinished():
        for o in eng.step():
            assert o.request_id not in got  # nothing after its last token
            emitted[o.request_id] += 1
            if o.finished:
                got[o.request_id] = o
    assert {rid: o.token_ids for rid, o in got.items()} == want
    assert emitted == {rid: len(toks) for rid, toks in want.items()}
    assert got["q2"].finish_reason == "stop"
    m = eng.metrics
    assert m["generated_tokens"] == sum(map(len, want.values()))
    assert m["dropped_tokens"] >= 1 and m["overlapped_steps"] == m["steps"] - 1
    assert m["admitted"] == 7 and not any(eng._slots)
    assert sorted(eng._free_pages) == list(range(1, eng.ecfg.num_pages))


def test_generation_crosses_page_boundaries(engine):
    """Prompt of 14 + 40 new tokens crosses several 16-token pages."""
    prompt = list(range(3, 17))
    out = engine.generate([prompt], SamplingParams(max_tokens=40))[0]
    assert len(out.token_ids) == 40 or out.finish_reason == "stop"


def test_sampling_seeded_and_bounded(engine):
    from ray_tpu.llm.engine import JaxLLMEngine

    sp = SamplingParams(max_tokens=12, temperature=0.8, top_k=8)
    e1 = JaxLLMEngine(make_config(), params=engine.params, seed=7)
    e2 = JaxLLMEngine(make_config(), params=engine.params, seed=7)
    a = e1.generate(["seeded"], sp)[0].token_ids
    b = e2.generate(["seeded"], sp)[0].token_ids
    assert a == b
    assert len(a) <= 12


def test_per_request_seed_batch_independent(engine):
    """seed=N must reproduce regardless of what else is in the batch."""
    from ray_tpu.llm.engine import JaxLLMEngine

    sp = SamplingParams(max_tokens=10, temperature=1.0, seed=42)
    alone = engine.generate(["seeded prompt"], sp)[0].token_ids
    e2 = JaxLLMEngine(make_config(), params=engine.params, seed=999)
    mixed = e2.generate(["seeded prompt", "other a", "other b"], sp)
    assert mixed[0].token_ids == alone


def _shortlist_sampler():
    """``sample_tokens`` as it stood before it branched, kept plain as the
    oracle: the shortlist, the masks, the keys and the draw for EVERY batch,
    and a select between the draw and the argmax at the end."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("max_top_k",))
    def sample(logits, rng, temps, top_ks, top_ps, seeds, steps,
               max_top_k=64):
        B, V = logits.shape
        K = min(max_top_k, V)
        greedy = jnp.argmax(logits, axis=-1)
        vals, idx = jax.lax.top_k(logits, K)
        safe_t = jnp.maximum(temps, 1e-6)[:, None]
        scaled = vals / safe_t
        ranks = jnp.arange(K, dtype=jnp.int32)[None]
        k_lim = jnp.where(top_ks <= 0, K, jnp.minimum(top_ks, K))[:, None]
        mask = ranks < k_lim
        probs = jax.nn.softmax(jnp.where(mask, scaled, -1e30), axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        mask = mask & ((cum - probs) < top_ps[:, None])
        final = jnp.where(mask, scaled, -1e30)
        global_keys = jax.random.split(rng, B)
        seeded_keys = jax.vmap(
            lambda s, st: jax.random.fold_in(jax.random.PRNGKey(s), st)
        )(jnp.maximum(seeds, 0).astype(jnp.uint32), steps.astype(jnp.uint32))
        keys = jnp.where((seeds >= 0)[:, None], seeded_keys, global_keys)
        sampled_pos = jax.vmap(jax.random.categorical)(keys, final)
        sampled = jnp.take_along_axis(idx, sampled_pos[:, None], axis=1)[:, 0]
        return jnp.where(temps <= 0, greedy, sampled).astype(jnp.int32)
    return sample


# vocabulary, then a slot's temperature, top_k, top_p, seed (-1: the engine's
# stream) and position in its stream
_SAMPLER_BATCHES = {
    "all-greedy": (300, [(0.0, 0, 1.0, -1, 0), (0.0, 5, 0.9, 11, 3),
                         (0.0, 0, 0.5, -1, 0), (0.0, 100, 1.0, 4, 2),
                         (0.0, 0, 1.0, -1, 0), (0.0, 64, 0.3, 7, 9)]),
    "all-greedy-vocab-under-the-shortlist": (
        40, [(0.0, 0, 1.0, -1, 0), (0.0, 3, 0.9, 2, 1)]),
    "mixed-greedy-seeded-unseeded": (
        300, [(0.0, 0, 1.0, -1, 0), (0.7, 5, 0.9, 11, 3),
              (1.3, 0, 0.5, -1, 0), (0.0, 3, 1.0, 4, 2),
              (0.9, 64, 1.0, 7, 9), (2.0, 100, 0.3, -1, 0)]),
    "mixed-one-slot-samples": (
        300, [(0.0, 0, 1.0, -1, 0), (0.0, 0, 1.0, 3, 5), (0.0, 8, 0.8, -1, 0),
              (1.0, 8, 0.8, -1, 0), (0.0, 0, 1.0, -1, 0)]),
    "mixed-vocab-under-the-shortlist": (
        40, [(0.0, 0, 1.0, -1, 0), (1.1, 3, 0.9, 2, 1), (0.6, 0, 0.7, -1, 0)]),
    "all-sample": (300, [(0.8, 16, 1.0, 5, 0), (1.0, 0, 0.9, -1, 0),
                         (1.5, 4, 0.6, 6, 12)]),
}


def _check_batch_against_the_old_formula(vocab, slots):
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import model_runner as mr

    old = _shortlist_sampler()
    temps, top_ks, top_ps, seeds, steps = zip(*slots)
    sampling = tuple(jnp.asarray(a, dt) for a, dt in (
        (temps, jnp.float32), (top_ks, jnp.int32), (top_ps, jnp.float32),
        (seeds, jnp.int32), (steps, jnp.int32)))
    drawn = False
    for trial in range(6):
        logits = jax.random.normal(jax.random.PRNGKey(100 + trial),
                                   (len(slots), vocab), jnp.float32)
        rng = jax.random.PRNGKey(trial)
        got = np.asarray(mr.sample_tokens(logits, rng, *sampling))
        np.testing.assert_array_equal(
            got, np.asarray(old(logits, rng, *sampling)))
        assert got.dtype == np.int32
        best = np.asarray(logits).argmax(-1)
        cold = np.asarray(temps) <= 0
        np.testing.assert_array_equal(got[cold], best[cold])
        drawn |= bool((got != best).any())
    # the slots that sample do draw: not every token of theirs is the argmax
    assert drawn == (max(temps) > 0)


def _run(eng, arrivals):
    """Steps ``eng`` to the end, adding ``arrivals[n]`` = (id, prompt,
    params) before step ``n``. Returns the tokens by request and, for every
    sampler call, whether the engine counted it greedy."""
    got, greedy = {}, []
    calls = (eng.metrics["sample_calls"], eng.metrics["sample_greedy_calls"])
    n = 0
    while eng.has_unfinished() or n <= max(arrivals):
        for rid, prompt, sp in arrivals.get(n, ()):
            eng.add_request(rid, prompt, sp)
        for o in eng.step():
            if o.finished:
                got[o.request_id] = o.token_ids
        now = (eng.metrics["sample_calls"], eng.metrics["sample_greedy_calls"])
        g = now[1] - calls[1]
        greedy += [True] * g + [False] * (now[0] - calls[0] - g)
        calls, n = now, n + 1
    return got, greedy


def _check_neighbours_do_not_move_a_request(engine):
    """A greedy request that starts alone (the argmax branch), is joined by a
    seeded one and then by one on the engine's own stream (the shortlist
    branch) and outlives both (the argmax branch again) gets the tokens it
    gets alone; so does the seeded one, whoever sits beside it."""
    from ray_tpu.llm.engine import JaxLLMEngine

    g = ("g", list(range(3, 12)), SamplingParams(max_tokens=20))
    s = ("s", list(range(20, 31)), SamplingParams(
        max_tokens=6, temperature=0.9, top_k=16, top_p=0.95, seed=5))
    n = ("n", list(range(40, 47)), SamplingParams(
        max_tokens=3, temperature=1.3))
    alone = {}
    for r in (g, s):
        eng = JaxLLMEngine(make_config(), params=engine.params, seed=1)
        alone.update(_run(eng, {0: [r]})[0])
    assert len(alone["g"]) == 20 and len(alone["s"]) == 6
    assert alone["s"] != _run(
        JaxLLMEngine(make_config(), params=engine.params, seed=1),
        {0: [("s", s[1], SamplingParams(max_tokens=6))]})[0]["s"]

    eng = JaxLLMEngine(make_config(), params=engine.params, seed=2)
    got, greedy = _run(eng, {0: [g], 3: [s], 5: [n]})
    assert got["g"] == alone["g"] and got["s"] == alone["s"]
    assert len(got["n"]) == 3
    # alone, then beside the two that sample, then alone again
    flips = [a != b for a, b in zip(greedy, greedy[1:])]
    assert greedy[0] and greedy[-1] and flips.count(True) == 2
    assert 3 <= greedy.index(False) and greedy[::-1].index(False) >= 3


def _check_one_conditional_holds_the_shortlist(engine):
    """The compiled program branches once, on the device, and the shortlist
    is computed inside a branch: the entry computation holds no top-k."""
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import model_runner as mr

    B, V = 4, 300
    text = mr.sample_tokens.lower(
        jnp.zeros((B, V)), jax.random.PRNGKey(0), jnp.zeros(B),
        jnp.zeros(B, jnp.int32), jnp.ones(B), jnp.full(B, -1, jnp.int32),
        jnp.zeros(B, jnp.int32)).compile().as_text()
    assert text.startswith("HloModule jit_sample_tokens")
    conds = re.findall(
        r" conditional\(.*branch_computations=\{([^}]*)\}", text)
    assert len(conds) == 1 and text.count(" conditional(") == 1
    branches = [b.strip().lstrip("%") for b in conds[0].split(",")]
    assert len(branches) == 2
    # a computation's text, from its header line to its closing brace
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.S | re.M)}
    entry = re.search(r"^ENTRY %([\w.\-]+) ", text, re.M).group(1)
    holds = [name for name, body in bodies.items() if "TopK" in body]
    assert holds and set(holds) <= set(branches) and entry not in holds
    # the other branch is the argmax alone: no key, no draw, no shortlist
    other, = set(branches) - set(holds)
    assert len(bodies[other].splitlines()) <= 6
    assert not re.search(r"while|TopK|exponential|u32\[", bodies[other])


def _check_counters_and_span(engine, monkeypatch):
    """A greedy run counts every sampler call greedy, a run with one request
    that samples counts the calls it sat through as not, and the span says
    the same of each call."""
    import jax

    from ray_tpu.llm.engine import JaxLLMEngine

    spans = []

    class Recorder(contextlib.nullcontext):
        def __init__(self, name, **attrs):
            super().__init__(self)
            if name == "ray_tpu/engine.sample_dispatch":
                spans.append(attrs)

        def set_metadata(self, **attrs):  # engine.admit's, learnt on its way
            pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    eng = JaxLLMEngine(make_config(), params=engine.params, seed=0)
    m = eng.metrics
    assert (m["sample_calls"], m["sample_greedy_calls"]) == (0, 0)
    reqs = [(f"q{i}", list(range(3 + i, 9 + 2 * i)), SamplingParams(
        max_tokens=5 + i)) for i in range(3)]
    _, greedy = _run(eng, {0: reqs})
    assert all(greedy) and len(greedy) == m["sample_calls"] > 5
    assert m["sample_calls"] == m["sample_greedy_calls"] \
        == m["prefill_steps"] + m["decode_steps"]
    assert spans == [{"greedy": True}] * m["sample_calls"]

    before = dict(m)
    del spans[:]
    hot = ("hot", list(range(50, 58)), SamplingParams(
        max_tokens=4, temperature=0.7))
    _, greedy = _run(eng, {0: [
        ("a", *reqs[0][1:]), hot,
        ("b", reqs[2][1], SamplingParams(max_tokens=12))]})
    calls = m["sample_calls"] - before["sample_calls"]
    cold = m["sample_greedy_calls"] - before["sample_greedy_calls"]
    # the one prefill phase and the decode steps "hot" sat in were not greedy;
    # the slot it left is greedy again without anybody taking it
    assert greedy[:4] == [False] * 4 and all(greedy[5:]) and greedy[-1]
    assert 0 < cold < calls == len(greedy)
    assert spans == [{"greedy": g} for g in greedy]
    assert all(type(s["greedy"]) is bool for s in spans)
    assert not (eng._temps > 0).any()


@pytest.mark.parametrize("case", [
    *_SAMPLER_BATCHES, "neighbours-do-not-move-a-request",
    "one-conditional-holds-the-shortlist", "counters-and-span"])
def test_sampler_takes_its_shortlist_only_when_a_slot_samples(
        engine, monkeypatch, case):
    """``sample_tokens`` computes its shortlist, masks, keys and draw only
    for a batch in which some slot samples, and every row's token is what
    the formula it replaced gives: an all-greedy batch is the argmax; a mixed
    batch equals the old program bit for bit; through the engine a request's
    tokens do not move as its batch changes branch; the program is one
    conditional; and the engine counts which branch each call took."""
    if case in _SAMPLER_BATCHES:
        _check_batch_against_the_old_formula(*_SAMPLER_BATCHES[case])
    elif case == "neighbours-do-not-move-a-request":
        _check_neighbours_do_not_move_a_request(engine)
    elif case == "one-conditional-holds-the-shortlist":
        _check_one_conditional_holds_the_shortlist(engine)
    else:
        _check_counters_and_span(engine, monkeypatch)


@pytest.mark.parametrize("name", ["tables", "active", "temps", "top_ks",
                                  "top_ps", "seeds"])
def test_kept_upload_follows_the_host_array(name):
    """What a decode step and its sampler take from the host beside the
    lengths is sent once and handed out again while the host's array reads
    the same; a write to the host's array sends a new copy, and no write
    reaches the copy a queued program was given."""
    from ray_tpu.llm.engine import JaxLLMEngine

    eng = JaxLLMEngine(make_config(), seed=0)
    host = {"tables": eng._block_tables, "active": eng._active,
            "temps": eng._temps, "top_ks": eng._top_ks,
            "top_ps": eng._top_ps, "seeds": eng._seeds}[name]
    first = eng._up(host, name)
    before = host.copy()
    assert eng._up(host, name) is first
    host.flat[0] = 1 if host.flat[0] != 1 else 0
    second = eng._up(host, name)
    assert second is not first
    np.testing.assert_array_equal(np.asarray(first), before)
    np.testing.assert_array_equal(np.asarray(second), host)
    assert eng._up(host, name) is second
    assert eng._up(host) is not second      # unnamed: a transfer every time


def test_kept_uploads_change_with_admission_not_with_a_step(engine):
    """Over a run of decode steps with nothing admitted, released or grown a
    page, only the lengths travel: every kept copy is the same object."""
    engine.add_request("kept", list(range(3, 9)), SamplingParams(max_tokens=9))
    engine.step()
    engine.step()                            # prefill, then the first decode
    kept = {k: v[1] for k, v in engine._kept.items()}
    assert set(kept) == {"tables", "active", "temps", "top_ks", "top_ps",
                         "seeds", "steps"}
    engine.step()
    engine.step()
    assert all(engine._kept[k][1] is v for k, v in kept.items())
    while engine.has_unfinished():
        engine.step()
    assert not engine._active.any()


def test_capacity_rejection():
    """A request that can never fit the page pool raises instead of
    livelocking admission (num_pages too small for prompt+max_tokens)."""
    from ray_tpu.llm.engine import JaxLLMEngine

    cfg = make_config(max_num_seqs=1, max_model_len=64, num_pages=3)
    eng = JaxLLMEngine(cfg, seed=0)
    with pytest.raises(ValueError, match="KV pages"):
        eng.add_request("too-big", list(range(3, 30)),
                        SamplingParams(max_tokens=32))
    # a request that fits still works
    out = eng.generate([list(range(3, 20))], SamplingParams(max_tokens=8))[0]
    assert out.finished


def test_preemption_keeps_generated_tokens(engine):
    """Force page exhaustion mid-generation: preempted requests must keep
    their already-emitted tokens and respect max_tokens overall."""
    from ray_tpu.llm.engine import JaxLLMEngine

    # 2 slots but pages for ~1.5 long sequences -> decode-time exhaustion
    cfg = make_config(max_num_seqs=2, max_model_len=64, num_pages=7)
    eng = JaxLLMEngine(cfg, params=engine.params, seed=0)
    prompts = [list(range(3, 3 + 30)), list(range(40, 40 + 30))]
    outs = eng.generate(prompts, SamplingParams(max_tokens=30))
    assert all(o.finished for o in outs)
    assert all(len(o.token_ids) <= 30 for o in outs)
    # the pool ran dry with a step in flight: the engine read it before it
    # sent the request back, so the second prefill held every token
    m = eng.metrics
    assert m["preempted"] >= 1 and m["overlapped_steps"] >= m["steps"] - 2
    assert m["prefill_tokens"] > 60 and m["dropped_tokens"] == 0
    assert sorted(eng._free_pages) == list(range(1, 7))
    # greedy: outputs must match a roomy engine's outputs despite preemption
    roomy = JaxLLMEngine(make_config(max_num_seqs=2, max_model_len=64),
                         params=engine.params, seed=0)
    expect = roomy.generate(prompts, SamplingParams(max_tokens=30))
    assert [o.token_ids for o in outs] == [o.token_ids for o in expect]


def _steps_until_unread(eng, calls):
    """``calls`` steps, ending with a dispatched step whose tokens the host
    has not read: what each entry point below has to cope with."""
    outs = []
    for _ in range(calls):
        outs += eng.step()
    assert eng._unread and eng.metrics["overlapped_steps"] == calls - 1
    return outs


def _pages_conserved(eng):
    owned = [p for r in eng._slots if r is not None for p in r.pages]
    return sorted(list(eng._free_pages) + owned) == list(
        range(1, eng.ecfg.num_pages))


def test_abort_with_a_step_in_flight(engine):
    """Aborting reads what is in flight first: the other request loses
    nothing, the aborted one is never finished, its slot and pages are free."""
    from ray_tpu.llm.engine import JaxLLMEngine

    sp = SamplingParams(max_tokens=10)
    keep = list(range(3, 15))
    alone = engine.generate([keep], sp)[0].token_ids
    eng = JaxLLMEngine(make_config(), params=engine.params, seed=0)
    eng.add_request("gone", list(range(20, 50)), sp)
    eng.add_request("kept", keep, sp)
    outs = _steps_until_unread(eng, 3)
    eng.abort_request("gone")
    assert not eng._unread and "gone" not in eng._requests
    assert eng._slots[0] is None and _pages_conserved(eng)
    eng.abort_request("gone")  # unknown by now: nothing happens
    while eng.has_unfinished():
        outs += eng.step()
    gone = [o for o in outs if o.request_id == "gone"]
    # the three steps dispatched four tokens for it; all were read, none lost
    assert [len(o.token_ids) for o in gone] == [1, 2, 3, 4]
    assert not any(o.finished for o in gone)
    kept = [o for o in outs if o.request_id == "kept"]
    assert [len(o.token_ids) for o in kept] == list(range(1, 11))
    assert kept[-1].finished and kept[-1].token_ids == alone
    assert sorted(eng._free_pages) == list(range(1, eng.ecfg.num_pages))


def test_export_and_import_with_steps_in_flight(engine):
    """``export_kv`` from an engine and ``add_request_with_kv`` into another,
    each with a step unread: the exported state holds every token dispatched,
    the importing engine's own request loses none, and both go on to the
    tokens they get alone."""
    from ray_tpu.llm.engine import JaxLLMEngine

    sp = SamplingParams(max_tokens=12)
    moved, stays = list(range(3, 21)), list(range(30, 39))
    alone = [o.token_ids for o in engine.generate([moved, stays], sp)]
    a = JaxLLMEngine(make_config(), params=engine.params, seed=0)
    b = JaxLLMEngine(make_config(), params=engine.params, seed=1)
    a.add_request("moved", moved, sp)
    b.add_request("stays", stays, sp)
    _steps_until_unread(a, 3)
    outs = _steps_until_unread(b, 2)
    state = a.export_kv("moved")
    assert state["generated"] == alone[0][:4] and state["seq_len"] == 18 + 3
    assert not a._unread and _pages_conserved(a) and not any(a._slots)
    # what the drain read is handed over by the next step(), then nothing
    assert [len(o.token_ids) for o in a.step()] == [4]
    assert not a.has_unfinished()
    b.add_request_with_kv(state)
    assert not b._unread and _pages_conserved(b)
    while b.has_unfinished():
        outs += b.step()
    done = {o.request_id: o.token_ids for o in outs if o.finished}
    assert done == {"moved": alone[0], "stays": alone[1]}
    assert [len(o.token_ids) for o in outs if o.request_id == "stays"] \
        == list(range(1, 13))
    assert sorted(b._free_pages) == list(range(1, b.ecfg.num_pages))


def test_prefill_only_with_a_step_in_flight(engine):
    """The prefill side's entry point on an engine that is decoding: it reads
    what is in flight, the LAST token of another request among it, and that
    request's answer is handed over by the next ``step()``, not lost."""
    from ray_tpu.llm.engine import JaxLLMEngine

    short = list(range(3, 12))
    alone = engine.generate([short], SamplingParams(max_tokens=3))[0]
    eng = JaxLLMEngine(make_config(), params=engine.params, seed=0)
    eng.add_request("short", short, SamplingParams(max_tokens=3))
    outs = _steps_until_unread(eng, 2)  # its third token is the unread one
    assert [len(o.token_ids) for o in outs] == [1, 2] and not any(eng._slots)
    long, sp = list(range(40, 60)), SamplingParams(max_tokens=8)
    state = eng.prefill_only("p", long, sp)
    assert len(state["generated"]) == 1 and state["seq_len"] == 20
    assert not eng._unread and _pages_conserved(eng)
    assert eng.has_unfinished()  # an answer nobody has been given yet
    last, = eng.step()
    assert (last.request_id, last.finished) == ("short", True)
    assert last.token_ids == alone.token_ids
    assert not eng.has_unfinished()
    # and the exported request goes on elsewhere as if it had never moved
    eng.add_request_with_kv(state)
    done = []
    while eng.has_unfinished():
        done += [o.token_ids for o in eng.step() if o.finished]
    assert done == [engine.generate([long], sp)[0].token_ids]


def test_max_model_len_truncates(engine):
    long_prompt = list(np.random.default_rng(0).integers(3, 200, size=300))
    out = engine.generate([long_prompt], SamplingParams(max_tokens=4))[0]
    assert out.finished


def test_more_requests_than_slots(engine):
    prompts = [f"req {i}" for i in range(10)]  # > max_num_seqs=4
    outs = engine.generate(prompts, SamplingParams(max_tokens=5))
    assert len(outs) == 10 and all(o.finished for o in outs)


def test_save_load_params(tmp_path, engine):
    from ray_tpu.llm.engine import JaxLLMEngine, save_params

    save_params(engine.params, str(tmp_path))
    cfg = make_config()
    cfg.checkpoint_path = str(tmp_path)
    e2 = JaxLLMEngine(cfg)
    a = engine.generate(["persist"], SamplingParams(max_tokens=5))[0]
    b = e2.generate(["persist"], SamplingParams(max_tokens=5))[0]
    assert a.token_ids == b.token_ids


def test_serve_llm(ray_local):
    import ray_tpu
    from ray_tpu.llm.serve_llm import build_llm_deployment
    from ray_tpu.serve import api as serve_api

    app = build_llm_deployment(make_config(), name="llm-test")
    handle = serve_api.run(app)
    out = ray_tpu.get(handle.remote({"prompt": "hi", "max_tokens": 4}),
                      timeout=300)
    assert out["object"] == "text_completion"
    assert out["choices"][0]["finish_reason"] in ("stop", "length")
    chat = ray_tpu.get(handle.remote(
        {"messages": [{"role": "user", "content": "hi"}], "max_tokens": 4}),
        timeout=300)
    assert chat["object"] == "chat.completion"
    serve_api.shutdown()


def test_llm_server_answers_when_model_vocab_exceeds_tokenizer():
    """What the first chip run hit at ``1b`` (vocab 32000 under the byte
    tokenizer's 259): generated ids beyond the tokenizer's range must decode
    to no text — not raise inside the pump and orphan the request — and the
    answer carries the token ids themselves."""
    import asyncio

    from ray_tpu.llm.serve_llm import LLMServer
    from ray_tpu.llm.tokenizer import ByteTokenizer

    assert ByteTokenizer().decode([1, 72 + 3, 300, 31999, 105 + 3]) == "Hi"
    cfg = make_config()
    cfg.model_overrides = dict(cfg.model_overrides, vocab_size=2048)
    server = LLMServer(cfg)

    async def ask():
        return await asyncio.wait_for(
            server({"prompt": "hello", "max_tokens": 12}), 120)

    out = asyncio.run(ask())
    ids = out["choices"][0]["token_ids"]
    assert len(ids) == out["usage"]["completion_tokens"] == 12
    assert any(t >= ByteTokenizer.vocab_size for t in ids), ids

    # a failure while building an answer fails THAT request, it does not hang
    server.engine.tokenizer.decode = lambda ids: 1 / 0

    async def ask_broken():
        return await asyncio.wait_for(
            server({"prompt": "hello", "max_tokens": 2}), 120)

    with pytest.raises(RuntimeError, match="engine step failed"):
        asyncio.run(ask_broken())


def test_llm_server_answers_the_last_request_of_a_burst():
    """The pump goes on calling ``step()`` while a dispatched step is unread:
    the last answers of a burst, which only such a call reads, arrive."""
    import asyncio

    from ray_tpu.llm.serve_llm import LLMServer

    server = LLMServer(make_config())

    async def burst():
        return await asyncio.wait_for(asyncio.gather(*[
            server({"prompt": f"burst {i}", "max_tokens": 1 + i % 3})
            for i in range(7)]), 120)

    outs = asyncio.run(burst())
    assert [o["usage"]["completion_tokens"] for o in outs] == [
        len(o["choices"][0]["token_ids"]) for o in outs]
    assert all(o["choices"][0]["finish_reason"] in ("length", "stop")
               for o in outs)
    eng = server.engine
    assert not eng.has_unfinished() and not server._futures
    assert eng.metrics["admitted"] == 7 and server._pump_task is None


def test_llm_server_releases_every_request_when_a_step_in_flight_fails():
    """A step whose device computation failed raises where its tokens are
    read, and what is dispatched behind it is unread still: the pump drops
    that unread, so every request gets THIS error and gives back its slot and
    pages (an abort that read it would raise again, inside the handler)."""
    import asyncio

    from ray_tpu.llm.serve_llm import LLMServer

    class Failed:
        def __array__(self, *a, **kw):
            raise RuntimeError("device computation failed")

    server = LLMServer(make_config())
    eng = server.engine
    sent, calls = eng._sent, []

    def poisoned(tokens, reqs, *what, **kw):
        calls.append(len(reqs))
        sent(Failed() if len(calls) >= 3 else tokens, reqs, *what, **kw)

    eng._sent = poisoned

    async def burst():
        return await asyncio.wait_for(asyncio.gather(*[
            server({"prompt": f"burst {i}", "max_tokens": 10})
            for i in range(3)], return_exceptions=True), 120)

    outs = asyncio.run(burst())
    assert len(calls) == 4  # one call was dispatched behind the failed one
    for o in outs:
        assert isinstance(o, RuntimeError) and str(o) == (
            "engine step failed: device computation failed")
    assert not eng._unread and not eng._requests and not server._futures
    assert eng._slots == [None] * eng.ecfg.max_num_seqs
    assert sorted(eng._free_pages) == list(range(1, eng.ecfg.num_pages))
    assert not eng.has_unfinished() and server._pump_task is None


@pytest.mark.isolated
def test_data_llm_processor(ray_local):
    from ray_tpu import data as rdata
    from ray_tpu.llm.data_llm import build_llm_processor

    ds = rdata.from_items([{"prompt": f"p{i}"} for i in range(6)],
                          parallelism=2)
    proc = build_llm_processor(
        make_config(), sampling_params=SamplingParams(max_tokens=3))
    try:
        rows = proc(ds).take_all()
        assert len(rows) == 6
        assert all("generated_text" in r for r in rows)
    finally:
        proc.shutdown()
