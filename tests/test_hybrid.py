"""A decoder-hybrid-decoder (SambaY, Phi-4-mini-flash-reasoning): Mamba layers,
window and full differential attention, gated memory units and cross layers
over ONE shared layer of keys and values. The paged engine (three kinds of
state side by side: pages, window rings, recurrent rows; a prefill that runs
the cross-decoder on the last position only; decode through
``ops/paged_attention.py``), the training module and the kernels against the
benchmark's plain reference ``benchmarks/architectures/phi4flash.py``, and what
the benchmark's files say about the model against counts made by hand.

The model runs in float32 at a small size with the real pattern (8 layers: 0-3
the self-decoder's Mamba / window, 4 the Mamba layer that hands its memory on,
5 full attention, 6 a gated memory unit, 7 cross attention; hidden 48, 12 query
and 6 key heads of 4: an odd number of key pairs; window 8), where the only
differences left between the two sides are the order of float32 sums: 1e-4 of
the logits' norm admits that and nothing else, as the spoiled references show.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import prefill_rows
from benchmarks.architectures import phi4flash as ref
from benchmarks.registry import REPO, Cell
from ray_tpu.llm import LLMConfig
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import JaxLLMEngine
from ray_tpu.models.transformer import Transformer
from ray_tpu.ops.attention import flash_attention_fwd, reference_attention
from ray_tpu.ops.mla import live_pages
from ray_tpu.ops.ssm import selective_scan, selective_scan_reference

TOL = 1e-4
WINDOW, VOCAB, LAYERS = 8, 128, 8
# the small model under the published key names
PUBLISHED = dict(
    name="hybrid-tiny", model_type="phi4flash", hidden_act="silu",
    tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
    mb_per_layer=2, embd_pdrop=0, resid_pdrop=0, hidden_size=48,
    num_attention_heads=12, num_key_value_heads=6, intermediate_size=96,
    vocab_size=VOCAB, num_hidden_layers=LAYERS, sliding_window=WINDOW,
    layer_norm_eps=1e-5, mamba_expand=2, mamba_d_state=16, mamba_d_conv=4,
    initializer={"attention": 0.3, "mlp": 0.15, "ssm_proj": 0.15,
                 "ssm_x": 0.3, "embedding": 0.05},
    torch_dtype="float32")
OVERRIDES = dict(ref.program_overrides(PUBLISHED, 64), dtype=jnp.float32,
                 remat=False)
RCFG = ref.reference_cfg(PUBLISHED)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _engine(**engine):
    geometry = dict(max_num_seqs=3, max_model_len=64, page_size=4,
                    prefill_bucket_min=16, expect_state_layers=3)
    return JaxLLMEngine(LLMConfig(
        model_id="tiny", model_overrides=OVERRIDES,
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


def _engine_logits(eng, seqs, prompt_lens, steps, bucket=16, slots=None):
    """Prefill (one ``[1, bucket]`` call a sequence, as the engine makes them,
    into slot ``slots[i]``) then ``steps`` teacher-forced decode steps through
    all three kinds of state. Returns {slot: [1 + steps, vocab] logits}."""
    e, cfg = eng.ecfg, eng.mcfg
    B, MP = e.max_num_seqs, e.pages_per_seq
    slots = list(range(len(seqs))) if slots is None else slots
    tables = np.zeros((B, MP), np.int32)
    active = np.zeros(B, bool)
    cache = mr.init_cache(cfg, e.num_pages, e.page_size, B)
    assert prefill_rows.held(cache) == {"full", "window", "mamba"}
    got, page = {}, 1
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
    return {s: np.stack(v) for s, v in got.items()}


# -- the engine against the reference ---------------------------------------------


@pytest.mark.parametrize("prompt_len,bucket", [
    (5, 16),    # shorter than the window, a padded bucket
    (8, 16),    # the window exactly
    (13, 16),   # longer than the window
    (16, 16),   # a bucket with no padding
    (2, 16),    # shorter than the convolution's tail
])
def test_engine_matches_reference(engine, prompt_len, bucket):
    """Prefill's last-position logits and then a dozen decode steps, across
    the window's edge and over several pages, in a slot that is not the
    first, beside a second sequence of another length."""
    rng = np.random.default_rng(prompt_len)
    steps = 12
    seqs = [rng.integers(0, VOCAB, prompt_len + steps),
            rng.integers(0, VOCAB, 9 + steps)]
    got = _engine_logits(engine, seqs, [prompt_len, 9], steps, bucket,
                         slots=[2, 0])
    for s, toks, n in ((2, seqs[0], prompt_len), (0, seqs[1], 9)):
        want = _reference(engine, toks[:n + steps])[n - 1:]
        assert _rel(got[s], want) < TOL, (s, _rel(got[s], want))


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


# each takes one line of the model out of the REFERENCE's parameters or keys
def _no_lambda(p, rcfg):
    for lp in p["layers"]:
        if "lambda_q1" in lp:
            lp["lambda_q1"] = lp["lambda_q1"] * 0.0
            lp["lambda_q2"] = lp["lambda_q2"] * 0.0


def _no_subnorm(p, rcfg):
    for lp in p["layers"]:
        if "subln" in lp:
            lp["subln"] = lp["subln"] * 0.0 + 0.5


def _no_d_term(p, rcfg):
    for lp in p["layers"]:
        if "D" in lp:
            lp["D"] = lp["D"] * 0.0


def _no_window(p, rcfg):
    rcfg["sliding_window"] = 0


def _no_conv_bias(p, rcfg):
    for lp in p["layers"]:
        if "conv_bias" in lp:
            lp["conv_bias"] = lp["conv_bias"] * 0.0


def _no_norm_bias(p, rcfg):
    for lp in p["layers"]:
        lp["ln1_bias"] = lp["ln1_bias"] * 0.0


@pytest.mark.parametrize("spoil", [_no_lambda, _no_subnorm, _no_d_term,
                                   _no_window, _no_conv_bias, _no_norm_bias])
def test_dropped_part_fails_the_comparison(engine, spoil):
    rng = np.random.default_rng(7)
    toks = rng.integers(0, VOCAB, 13 + 6)
    got = _engine_logits(engine, [toks], [13], 6)[0]
    assert _rel(got, _reference(engine, toks)[12:]) < TOL
    assert _rel(got, _reference(engine, toks, spoil)[12:]) > 30 * TOL


def test_prefill_state_is_the_last_real_position(engine):
    """The same prompt in a bucket it fills and in one twice as long leaves
    the same recurrent rows, rings and logits: padding neither advances the
    state nor enters the convolution's tail."""
    e, cfg = engine.ecfg, engine.mcfg
    toks = np.random.default_rng(5).integers(0, VOCAB, 16)
    tables = jnp.asarray(np.arange(1, e.pages_per_seq + 1)[None], jnp.int32)

    def run(bucket):
        batch = np.zeros((1, bucket), np.int32)
        batch[0, :16] = toks
        cache = mr.init_cache(cfg, e.num_pages, e.page_size, e.max_num_seqs)
        return mr.prefill(engine.params, cfg, cache, jnp.asarray(batch),
                          jnp.asarray([16], jnp.int32), tables,
                          jnp.asarray([1], jnp.int32))

    (la, a), (lb, b) = run(16), run(32)
    assert _rel(lb, la) < 1e-5
    # page 0 is scratch: the padded bucket's padding lands there
    for x, y in zip((a["full"][:, 1:], a["window"], *a["mamba"]),
                    (b["full"][:, 1:], b["window"], *b["mamba"])):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-5)
    assert float(jnp.abs(a["mamba"].state[:, 1]).max()) > 0        # the slot asked for
    assert float(jnp.abs(a["mamba"].state[:, 0]).max()) == 0       # and no other


def test_engine_serves_and_resumes_after_preemption():
    """Through ``step()``: greedy continuations equal the reference's argmax
    chain, and a request preempted for want of pages (prefilled again from
    its tokens, which rebuilds pages, rings and rows) continues as the
    unpreempted one does."""
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(0, VOCAB, n)) for n in (7, 12, 10)]
    sp = SamplingParams(max_tokens=14, temperature=0.0)
    roomy = _engine()
    want = [r.token_ids for r in roomy.generate(prompts, sp, decode_text=False)]
    assert roomy.metrics["preempted"] == 0
    assert roomy.metrics["prefill_cross_rows"] == roomy.metrics["admitted"] == 3
    assert roomy.metrics["shared_kv_read_tokens"] \
        >= roomy.metrics["shared_kv_live_tokens"] > 0
    assert 0 < roomy.metrics["window_live_tokens"] \
        <= roomy.metrics["shared_kv_live_tokens"]
    # against the reference: the first request's chain, token by token
    toks = list(prompts[0])
    for t in want[0]:
        assert int(np.argmax(_reference(roomy, np.asarray(toks))[-1])) == t
        toks.append(t)
    tight = _engine(num_pages=1 + 14)   # 14 pages of 4: not room for all three
    tight.params = roomy.params
    got = [r.token_ids for r in tight.generate(prompts, sp, decode_text=False)]
    assert tight.metrics["preempted"] > 0
    assert got == want


def test_engine_refuses_what_it_cannot_do(engine):
    with pytest.raises(ValueError, match="recurrent state"):
        _engine(expect_state_layers=0)
    with pytest.raises(ValueError, match="window rings"):
        engine.export_kv("nobody")
    with pytest.raises(ValueError, match="window rings"):
        engine.add_request_with_kv({"request_id": "x"})
    with pytest.raises(ValueError, match="max_num_seqs"):
        mr.init_cache(engine.mcfg, 4, 4)


def test_training_module_matches_reference(engine):
    toks = np.random.default_rng(2).integers(0, VOCAB, (2, 24))
    out = Transformer(engine.mcfg).apply(engine.params, jnp.asarray(toks))
    for b in range(2):
        assert _rel(out[b], _reference(engine, toks[b])) < TOL
    leaves = jax.tree_util.tree_leaves(engine.params)
    assert sum(x.size for x in leaves) == engine.mcfg.num_params() \
        == ref.total_params(PUBLISHED)


# -- the kernels alone ---------------------------------------------------------------


@pytest.mark.parametrize("S,inner", [(256, 64), (24, 40)])
def test_chunked_scan_matches_sequential(S, inner):
    rng = np.random.default_rng(S)
    B, N = 2, 16
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    dt, a = jnp.abs(f(B, S, inner)) * 0.1, f(B, S, inner)
    dt = dt.at[1, S // 2:].set(0.0)       # padding behind a prompt
    Bm, Cm, s0 = f(B, S, N), f(B, S, N), f(B, inner, N)
    A = -jnp.exp(f(inner, N))
    y0, s_ref = selective_scan_reference(dt, a, Bm, Cm, A, s0)
    y1, s_got = jax.jit(selective_scan)(dt, a, Bm, Cm, A,
                                        jnp.swapaxes(s0, 1, 2))
    s_got = jnp.swapaxes(s_got, 1, 2)
    assert _rel(y1, y0) < 1e-5 and _rel(s_got, s_ref) < 1e-5
    # the state behind the padding is the state at the last real position
    _, s_half = selective_scan_reference(
        dt[1:, :S // 2], a[1:, :S // 2], Bm[1:, :S // 2], Cm[1:, :S // 2], A,
        s0[1:])
    assert _rel(s_got[1:], s_half) < 1e-5


@pytest.mark.parametrize("S,window", [(256, 8), (512, 200), (1024, 512),
                                      (384, 128), (64, 8)])
def test_window_flash_matches_masked_reference(S, window):
    rng = np.random.default_rng(window)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q, k, v = f(1, S, 2, 16), f(1, S, 2, 16), f(1, S, 2, 32)
    got = flash_attention_fwd(q, k, v, True, True, window)
    want = reference_attention(q, k, v, True, None, window)
    assert float(jnp.abs(got - want).max()) < 1e-5
    # the mask is a window: the reference without one differs
    assert float(jnp.abs(got - reference_attention(q, k, v)).max()) > 1e-2


def test_paged_decode_matches_masked_reference():
    """Three slots (one page, several pages with a partly filled last one,
    inactive) through a shuffled block table, three key pairs (odd) of two
    query pairs each, layer 1 of 2, against dense softmax over the live rows."""
    rng = np.random.default_rng(0)
    B, G, hd, rep, P, MP = 3, 3, 8, 2, 4, 5
    R, W, H = 2 * rep, 2 * hd, 2 * 3 * rep
    NP = 1 + B * MP
    pages = jnp.asarray(rng.normal(size=(2, NP, P, 2 * G * W)), jnp.float32)
    tables = rng.permutation(np.arange(1, NP)).reshape(B, MP).astype(np.int32)
    seq_lens = np.array([0, 18, 7], np.int32)
    active = np.array([True, True, False])
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    cfg = dataclasses.replace(_engine_cfg(), n_heads=H, n_kv_heads=2 * G,
                              d_model=H * hd)
    work = live_pages(jnp.asarray(seq_lens), jnp.asarray(active),
                      jnp.asarray(tables), P)
    got = mr._paged_attention(q, pages, work, 1, "paged_gqa_decode", cfg)
    assert got.shape == (B, H, W)
    for b in range(B):
        live = seq_lens[b] + 1 if active[b] else 0
        rows = np.asarray(pages[1][tables[b]]).reshape(MP * P, -1)[:live]
        for h in range(H):
            g, e = h // R, h % 2
            if not live:
                want = np.zeros(W)
            else:
                k = rows[:, g * W + e * hd:g * W + (e + 1) * hd]
                s = k @ np.asarray(q[b, h]) / np.sqrt(hd)
                p = np.exp(s - s.max())
                want = (p / p.sum()) @ rows[:, G * W + g * W:G * W + (g + 1) * W]
            np.testing.assert_allclose(np.asarray(got[b, h]), want, atol=1e-5)


def _engine_cfg():
    return dataclasses.replace(
        LLMConfig(model_id="tiny").transformer_config(), **OVERRIDES)


# -- the benchmark's files -------------------------------------------------------------


@pytest.fixture(scope="module")
def cell():
    return Cell("phi-4-mini-flash-reasoning.mathturns-saturated-b48",
                os.path.join(REPO, "BENCHMARK.json"))


def test_adapter_counts_the_published_model(cell):
    conf = cell.config
    arch = cell.architecture()
    assert arch.layer_kinds(32) == (("mamba", "window") * 8 + ("mamba", "full")
                                    + ("gmu", "cross") * 7)
    # 9 Mamba, 9 attention, 7 memory units, 7 cross layers, the tied table
    # once: 3,852,562,944 (the issue's table, summed from rows rounded to a
    # tenth of a million, says 3,851 M; the published 3.8 B)
    assert arch.total_params(conf) == 3_852_562_944
    d, f = conf["hidden_size"], conf["intermediate_size"]
    by_hand = (9 * (d * 10240 + 5120 * 192 + 160 * 5120 + 5120 * d
                    + 5120 * (4 + 3 + 16))
               + 9 * (d * 5120 + 5120 + d * d + d + 6 * 64)
               + 7 * 2 * d * 5120 + 7 * (d * d + d + d * d + d + 6 * 64)
               + 32 * (3 * d * f + 4 * d) + 200064 * d + 2 * d)
    assert arch.total_params(conf) == by_hand
    mcfg = dataclasses.replace(_engine_cfg(), **arch.program_overrides(
        conf, conf["job"]["engine"]["max_model_len"]))
    assert mcfg.num_params() == by_hand
    assert mcfg.head_dim == 64 and mcfg.window == 512
    assert conf["job"]["engine"]["expect_state_layers"] \
        == mcfg.layer_kinds.count("mamba") == 9


def test_to_reference_params_round_trip(engine):
    """Every leaf of the program's tree appears once in the reference's, by
    identity of its values, and nothing else does."""
    params = engine.params["params"]
    renamed = ref.to_reference_params(params, PUBLISHED)
    ours = jax.tree_util.tree_leaves(params)
    theirs = [x for x in jax.tree_util.tree_leaves(renamed)
              if not isinstance(x, str)]
    assert len(ours) == len(theirs)
    assert sorted(float(jnp.sum(x.astype(jnp.float32))) for x in ours) \
        == sorted(float(jnp.sum(x.astype(jnp.float32))) for x in theirs)


def test_cell_files(cell):
    conf, mix = cell.config, cell.mix
    entry = {c["name"]: c for c in cell.benchmark["configs"]}[conf["name"]]
    assert entry["reduced"] == ["max_position_embeddings"] == list(conf["reduced"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Phi-4-mini-flash-reasoning")
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert conf[key] == value, key
    e = conf["job"]["engine"]
    assert (e["max_num_seqs"], e["max_model_len"]) == (48, 10240)
    assert mix["prompt_tokens"]["max"] + mix["max_tokens"]["max"] \
        <= e["max_model_len"]
    # every per-layer metric of the cell has a reader, and the kernels it
    # names are counted
    arch = cell.architecture()
    for m in cell.per_layer():
        cell.reader(m["name"])
    for kernel in ("paged_gqa_decode", "window_gqa_decode", "ssm_scan"):
        ops, nbytes = arch.kernel_cost(kernel, conf, {"max_num_seqs": 48})
        assert ops > 0 and nbytes > 0
    assert arch.kernel_cost("paged_gqa_decode", conf, {})[1] \
        == 48 * 256 * 5120
