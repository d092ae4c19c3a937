"""LongCat-Flash-Omni's language model (``longcat_flash``): shortcut-connected
double layers (two latent-attention sublayers and two dense MLPs a layer
beside ONE expert branch, computed from the first sublayer's MLP input and
added where the second ends), low-rank queries with the two LoRA scales, a
softmax router with a selection bias over routed experts AND zero-compute
identity experts, of which one rank holds a few. The paged engine (one latent
row a position and SUBLAYER, the expert layer of ``ops/moe.py`` with its
buffers by what is held) against the benchmark's plain reference
``benchmarks/architectures/longcat_flash.py``.

The model runs in float32 at a small size (2 published layers = 4 sublayers,
hidden 64, 4 heads of 16 + 8 | 16 over a latent of 32, queries through a rank
of 24, MLPs of 96, 16 routed experts of 32 of which rank 1 of 8 holds 2, 8
zero experts, top-4 of the 24 outputs times 6, pages of 4). In float32 the
kernels multiply at the highest precision, so the two sides differ by
float32's own rounding summed over four sublayers: 1e-4 of the logits' norm
admits it, and every spoiled reference moves the logits by more than ten
times that.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.architectures import longcat_flash as ref
from ray_tpu.llm import LLMConfig
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import JaxLLMEngine
from ray_tpu.models.transformer import CONFIGS, Transformer
from ray_tpu.ops import moe

TOL = 1e-4
VOCAB, PAGE, BUCKET = 128, 4, 16
# the small model under the published key names
PUBLISHED = dict(
    name="longcat-tiny", attention_bias=False, vocab_size=VOCAB,
    hidden_size=64, ffn_hidden_size=96, expert_ffn_hidden_size=32,
    num_layers=2, num_attention_heads=4, kv_lora_rank=32, q_lora_rank=24,
    qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=6,
    n_routed_experts=2, max_position_embeddings=256, rms_norm_eps=1e-5,
    rope_theta=10000000, attention_method="MLA", zero_expert_num=8,
    zero_expert_type="identity", moe_topk=4,
    expert_parallel={"routed_experts": 16, "zero_experts": 8, "ranks": 8,
                     "rank": 1},
    torch_dtype="float32",
    initializer={"attention": 0.2, "mlp": 0.15, "experts": 0.3,
                 "embedding": 1.0, "router": 0.3, "router_bias": 0.02})
OVERRIDES = dict(ref.program_overrides(PUBLISHED, 256), dtype=jnp.float32,
                 remat=False)
RCFG = ref.reference_cfg(PUBLISHED)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _engine(overrides=OVERRIDES, **engine):
    geometry = dict(max_num_seqs=3, max_model_len=64, page_size=PAGE,
                    prefill_bucket_min=BUCKET, expect_experts=2,
                    expect_routed_experts=16, expect_zero_experts=8,
                    expect_latent_rank=32,
                    # too few for three requests at once: one is preempted
                    num_pages=14)
    return JaxLLMEngine(LLMConfig(
        model_id="tiny", model_overrides=overrides,
        engine_config=EngineConfig(**dict(geometry, **engine))))


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _reference(eng, toks, published=PUBLISHED, **wrong):
    """The reference's logits [len(toks), vocab]; ``wrong``: facts of the
    model it is told to get wrong."""
    params = ref.to_reference_params(eng.params["params"], published)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(
            params, jnp.asarray(toks)[None],
            dict(ref.reference_cfg(published), **wrong))[0])


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


# -- (a) the router and the expert layer alone -------------------------------------------


@pytest.mark.parametrize("bias,scale", [(False, 1.0), (True, 1.0),
                                        (True, 6.0), (False, 6.0)],
                         ids=["plain", "bias", "bias-and-scale", "scale"])
def test_softmax_router_chooses_by_the_bias_and_weighs_without_it(bias, scale):
    """``select_experts("softmax")``: the top-k of ``p + bias`` choose, the
    chosen ``p`` alone weigh, times ``scale``, not renormalised; with neither
    it is the top-k of the softmax as it was."""
    key = jax.random.split(jax.random.PRNGKey(0), 2)
    logits = jax.random.normal(key[0], (37, 24))
    b = jax.random.normal(key[1], (24,)) * 0.05 if bias else None
    w, e, p = moe.select_experts(logits, 4, False, "softmax", b, scale)
    want_p = np.asarray(jax.nn.softmax(logits, -1))
    order = np.argsort(-(want_p + (0 if b is None else np.asarray(b))), -1)
    assert (np.asarray(e) == order[:, :4]).all() and e.dtype == jnp.int32
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(want_p, order[:, :4], -1) * scale,
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p), want_p, rtol=1e-6)
    if bias:  # the bias changed some row's choice, and entered no weight
        assert (order[:, :4] != np.argsort(-want_p, -1)[:, :4]).any()


def _dense_layer(x, valid, router, bias, wg, wu, wd, top_k, scale, held, zero):
    """The expert layer as it reads, every held expert on every row."""
    x32, routed = x.astype(jnp.float32), router.shape[1] - zero
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(x32 @ router, -1)
        _, e = jax.lax.top_k(p + bias, top_k)
        g = jnp.take_along_axis(p, e, -1) * scale
        first, E = held or (0, routed)
        y = jnp.where(e >= routed, g, 0).sum(-1)[:, None] * x32
        for j in range(E):
            gj = jnp.where(e == first + j, g, 0).sum(-1)
            y = y + gj[:, None] * (
                (jax.nn.silu(x32 @ wg[j]) * (x32 @ wu[j])) @ wd[j])
    load = [int((valid[:, None] & (e == first + j)).sum()) for j in range(E)]
    load.append(int((valid[:, None] & (e >= routed)).sum()))
    return jnp.where(valid[:, None], y, 0), np.asarray(load)


# T rows, routed + zero outputs, (first, held) or None, what the bias favours
LAYER_CASES = {
    "all-experts-here": (256, None, None),
    "held-first": (256, (0, 2), None),
    "held-middle": (256, (6, 2), None),
    "every-choice-zero": (256, (6, 2), "zero"),
    "every-choice-held": (256, (0, 4), "held"),
    "none-held": (256, (6, 2), "elsewhere"),
    "second-pass": (512, (6, 2), "mine"),
    "decode-step": (32, (6, 2), None),
    "padding-rows": (256, (6, 2), "padding"),
}


@pytest.mark.parametrize("case", LAYER_CASES)
def test_expert_layer_with_zero_experts_against_a_dense_evaluation(case):
    """``expert_layer`` with zero experts, all experts here or a rank's two,
    against every expert on every row in float32 (1e-5: both sides multiply
    at the highest precision, the sums differ in their order): a row whose
    four choices are all zero experts, all held, none held, more held rows
    than one window of ``_by_held`` takes (its loop's second pass), a decode
    step's few assignments (the way by assignment), padding rows."""
    T, held, favour = LAYER_CASES[case]
    D, F, routed, zero, top = 32, 16, 16, 8, 4
    E = held[1] if held else routed
    key = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    x = jax.random.normal(key[0], (T, D))
    router = jax.random.normal(key[1], (D, routed + zero)) * 0.3
    wg = jax.random.normal(key[2], (E, D, F)) * 0.3
    wu = jax.random.normal(key[3], (E, D, F)) * 0.3
    wd = jax.random.normal(key[4], (E, F, D)) * 0.3
    bias = np.asarray(jax.random.normal(key[5], (routed + zero,))) * 0.01
    valid = np.ones(T, bool)
    if favour == "zero":
        bias[routed:routed + top] += 5.0
    elif favour == "held":
        bias[:top] += 5.0
    elif favour == "elsewhere":
        bias[held[0]:held[0] + E] -= 5.0
    elif favour == "mine":
        bias[held[0]:held[0] + E] += 5.0
    elif favour == "padding":
        valid = np.arange(T) % 3 != 0
    valid, bias = jnp.asarray(valid), jnp.asarray(bias, jnp.float32)
    y, load = jax.jit(lambda *a: moe.expert_layer(
        *a, top_k=top, norm_topk_prob=False, router_bias=bias,
        router_scale=6.0, held=held, zero_experts=zero))(
            x, valid, router, wg, wu, wd)
    want, want_load = _dense_layer(x, valid, router, bias, wg, wu, wd, top,
                                   6.0, held, zero)
    assert _rel(y, want) < 1e-5
    assert (np.asarray(load) == want_load).all()
    window = moe.held_window(T * top, E if held else 0, routed + zero, 256)
    if favour == "zero":
        assert load[-1] == T * top and not load[:-1].any()
    elif favour == "held":
        assert load[-1] == 0 and load[:-1].sum() == T * top
    elif favour == "elsewhere":
        assert not load[:-1].any()
    elif favour == "mine":   # every row took both held experts: two windows
        assert window == 512 and load[:-1].sum() == 2 * T > window
    elif favour == "padding":
        assert not np.asarray(y)[::3].any()
    if case in ("all-experts-here", "decode-step", "every-choice-held"):
        assert window == 0     # all here, too few, a quarter held
    elif case != "second-pass":
        assert window == 256


def test_no_buffer_of_the_held_way_is_assignments_long():
    """The lowered layer of a rank that holds 2 of 24 outputs: no array of
    ``T x top_k`` (or more) rows by ``D`` or ``F`` columns, which is what the
    way by assignment gathers, multiplies and sums."""
    T, D, F, top = 1024, 32, 16, 4
    s = jax.ShapeDtypeStruct
    args = lambda E: (   # noqa: E731
        s((T, D), jnp.float32), s((T,), bool), s((D, 24), jnp.float32),
        s((E, D, F), jnp.float32), s((E, D, F), jnp.float32),
        s((E, F, D), jnp.float32))
    text = {held: jax.jit(lambda *a: moe.expert_layer(
        *a, top_k=top, norm_topk_prob=False, held=held,
        zero_experts=8)).lower(*args(held[1])).as_text()
        for held in ((6, 2), (0, 4))}
    long = [f"tensor<{T * top}x{D}x", f"tensor<{T * top}x{F}x",
            f"tensor<{top}x{T}x{D}x"]
    assert not any(t in text[(6, 2)] for t in long)
    assert all(t in text[(0, 4)] for t in long)   # a sixth held: as it was


# -- (b) the model against the reference ------------------------------------------------


def test_prefill_then_decode_matches_reference(engine):
    """A prompt of 13 tokens in a 16-bucket, then six teacher-forced decode
    steps, in slot 1 with pages that do not start at 1, against the
    reference's full forward: logits, not tokens."""
    toks = np.random.default_rng(0).integers(0, VOCAB, 13 + 6)
    got = _Run(engine).sequence(1, toks, 13, _pages(3, len(toks)))
    assert _rel(got, _reference(engine, toks)[12:]) < TOL


def test_cache_holds_a_row_a_sublayer_and_counts_the_zero_experts(engine):
    """Two rows a published layer (``rows`` is four sublayers deep, no
    ``pages``), one load a PUBLISHED layer: two held experts and, last, the
    zero experts' count."""
    toks = np.random.default_rng(1).integers(0, VOCAB, 9)
    run = _Run(engine)
    assert run.cache["latent"].shape[0] == 4 and "full" not in run.cache
    run.prefill(0, toks, _pages(1, 9))
    load = np.asarray(run.cache.moe_load)
    assert load.shape == (2, 3)
    assert (load.sum(1) <= 9 * 4).all() and load[:, -1].sum() > 0


def test_slot_used_again_after_a_longer_request(engine):
    rng = np.random.default_rng(5)
    long, short = rng.integers(0, VOCAB, 27), rng.integers(0, VOCAB, 9)
    run = _Run(engine)
    run.sequence(1, long, 21, _pages(3, 27), 32)
    run.active[1] = False
    got = run.sequence(1, short, 2, _pages(3, 9))
    assert _rel(got, _reference(engine, short)[1:]) < TOL


def test_preempted_request_prefilled_again(engine):
    """Recompute preemption: a request that decoded five tokens is prefilled
    again from prompt + generated into another slot and other pages."""
    toks = np.random.default_rng(6).integers(0, VOCAB, 6 + 5 + 6)
    want = _reference(engine, toks)
    run = _Run(engine)
    first = run.sequence(0, toks[:11], 6, _pages(1, 11))
    assert _rel(first, want[5:11]) < TOL
    run.active[0] = False
    again = run.sequence(2, toks, 11, _pages(9, len(toks)))
    assert _rel(again, want[10:]) < TOL


def test_every_slot_prefill_call(engine):
    """The benchmark's check calls prefill with every slot's row and no slot
    argument: row b fills slot b, a row of length 0 reaches no expert."""
    e, cfg = engine.ecfg, engine.mcfg
    toks = np.random.default_rng(3).integers(0, VOCAB, 11 + 3)
    run = _Run(engine)
    run.tables[0, :4] = np.arange(1, 5)
    batch = np.zeros((e.max_num_seqs, BUCKET), np.int32)
    batch[0, :11] = toks[:11]
    logits, run.cache = mr.prefill(
        engine.params, cfg, run.cache, jnp.asarray(batch),
        jnp.asarray([11, 0, 0], jnp.int32), jnp.asarray(run.tables))
    assert (np.asarray(run.cache.moe_load).sum(1) <= 11 * 4).all()
    run.active[0], run.lens[0] = True, 11
    got = [np.asarray(logits[0])] + [run.decode({0: t})[0] for t in toks[11:]]
    assert _rel(np.stack(got), _reference(engine, toks)[10:]) < TOL


@pytest.mark.parametrize("assignments,held,outputs,window", [
    (512, 16, 768, 0),         # a decode step: under _BY_CHOICE_MIN
    (98304, 0, 768, 0),        # every expert is here
    (16384, 32, 256, 0),       # an eighth held: by assignment
    (98304, 16, 768, 4096),    # [1, 8192] at top-12: twice the 2,048 expected
    (2048, 16, 768, 256),      # never under one row tile
    (1024, 2, 3, 0),           # most of them held
])
def test_which_way_a_call_goes_is_held_windows_rule(assignments, held,
                                                    outputs, window):
    assert moe.held_window(assignments, held, outputs, 256) == window


def test_prefill_call_whose_expert_layers_go_by_what_is_held():
    """A ``[1, 256]`` call: 1,024 assignments of which a rank of 2 of 24
    outputs holds a twelfth, so its expert layers take ``_by_held``; then
    decode steps (by assignment) through the same cache."""
    eng = _engine(max_num_seqs=2, max_model_len=256, num_pages=None)
    c = eng.mcfg
    ways = [moe.held_window(rows * c.experts_per_token, c.n_experts_held,
                            c.n_experts + c.zero_experts, 256)
            for rows in (256, 16)]
    assert ways == [256, 0]
    toks = np.random.default_rng(9).integers(0, VOCAB, 250 + 3)
    got = _Run(eng).sequence(1, toks, 250, _pages(2, len(toks)), 256)
    assert _rel(got, _reference(eng, toks)[249:]) < TOL


def test_bfloat16_engine_is_one_rounding_a_product():
    """The same model with bfloat16 products (weights stay float32 here):
    every product's operands rounded once, the stream and the statistics
    float32, against the float32 reference: 2^-9 a product, some thirty
    products deep."""
    eng = _engine(dict(OVERRIDES, dtype=jnp.bfloat16))
    toks = np.random.default_rng(8).integers(0, VOCAB, 13 + 4)
    got = _Run(eng).sequence(1, toks, 13, _pages(2, len(toks)))
    assert _rel(got, _reference(eng, toks)[12:]) < 5e-2


# -- (c) every part shows in the logits -------------------------------------------------

WRONG = [{"without": (part,)} for part in (
    "zero_experts", "zero_renorm", "gate_renorm", "routed_scale",
    "bias_in_gates", "branch_after_first", "branch_from_second",
    "second_attention", "s_q", "s_kv", "s_kv_on_keys", "q_a_norm", "q_lora",
    "latent_scale")] + [{"first_expert": 4}]


@pytest.mark.parametrize(
    "wrong", WRONG, ids=lambda w: "-".join(f"{k}={v}" for k, v in w.items()))
def test_wrong_part_fails_the_comparison(engine, wrong):
    toks = np.random.default_rng(7).integers(0, VOCAB, 13 + 6)
    got = _Run(engine).sequence(0, toks, 13, _pages(1, len(toks)))
    assert _rel(got, _reference(engine, toks)[12:]) < TOL
    moved = _rel(got, _reference(engine, toks, **wrong)[12:])
    print(f"{wrong}: the logits move by {moved:.3g} of their norm")
    assert moved > 10 * TOL


def test_eight_ranks_and_the_zero_experts_once_are_the_uncut_layer(engine):
    """The share: one expert branch's routed part from each of the eight
    ranks' matrices, summed, plus the zero experts' part counted ONCE, is
    what a reference holding all sixteen experts gives (the program's branch
    against rank 1's routed part plus the zero part besides)."""
    rng = np.random.default_rng(4)
    lp = ref.to_reference_params(engine.params["params"], PUBLISHED)["layers"][1]
    u = jnp.asarray(rng.normal(size=(1, 9, 64)), jnp.float32)
    names = ("gate_proj", "up_proj", "down_proj")
    ranks = [{n: lp[n] if r == 1 else jnp.asarray(
        rng.normal(size=lp[n].shape) * 0.3, jnp.float32) for n in names}
        for r in range(8)]
    with jax.default_matmul_precision("highest"):
        zero = ref.expert_branch(u, lp, RCFG, routed_part=False)
        parts = [ref.expert_branch(u, dict(lp, **held),
                                   dict(RCFG, first_expert=2 * r),
                                   zero_part=False)
                 for r, held in enumerate(ranks)]
        whole = ref.expert_branch(
            u, dict(lp, **{n: jnp.concatenate([held[n] for held in ranks])
                           for n in names}), dict(RCFG, first_expert=0))
    assert _rel(sum(parts) + zero, whole) < 1e-6
    assert _rel(zero, whole) > 0.1
    assert all(_rel(part + zero, whole) > 0.1 for part in parts)
    y, load = mr._experts(u, engine.params["params"]["layer_2"], engine.mcfg,
                          jnp.ones((1, 9), bool), "moe_gmm_prefill")
    assert _rel(y, parts[1] + zero) < 1e-5
    assert 0 < int(load[-1]) < 9 * 4 and int(load.sum()) < 9 * 4


def test_tree_holds_pairs_with_one_expert_branch(engine):
    tree = engine.params["params"]
    for i in range(4):
        lp = tree[f"layer_{i}"]
        assert ("moe" in lp) == (i % 2 == 0) and "mlp" in lp
        assert lp["mlp"]["gate_proj"]["kernel"].shape == (64, 96)
        assert lp["attn"]["q_a_proj"]["kernel"].shape == (64, 24)
        assert lp["attn"]["q_b_proj"]["kernel"].shape == (24, 4, 24)
        assert "q_proj" not in lp["attn"]
    m = tree["layer_0"]["moe"]
    assert m["router"]["kernel"].shape == (64, 24)
    assert m["router_bias"].shape == (24,) and m["gate_proj"].shape[0] == 2


# -- (d) decode rows ride a prefill call --------------------------------------------------


def test_decode_rows_ride_a_prefill_call(engine):
    """``prefill`` with ``riders`` is the call and then ``decode_step``: the
    carried branch holds both sides' rows end to end."""
    import prefill_rows

    assert mr.rides(engine.mcfg)
    prefill_rows.riders_equal_a_step_after_the_call(
        engine, np.random.default_rng(7), 1e-4)


def test_riding_calls_match_reference():
    """Three requests through the calls the engine makes when it admits
    beside decoding slots: every position's logits against the reference."""
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


# -- the engine, its counters, the training module ---------------------------------------


def test_engine_serves_preempts_and_counts_the_zero_experts():
    """Requests through ``JaxLLMEngine.step()`` with too few pages for all of
    them: one is preempted and prefilled again, every greedy token is the
    reference's own argmax, the routing counters know the zero experts and
    the latent counters count a latent model's."""
    eng = _engine()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (11, 2, 19)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=9), decode_text=False)
    for prompt, out in zip(prompts, outs):
        assert len(out.token_ids) == 9
        want = _reference(eng, prompt + out.token_ids)[len(prompt) - 1:-1]
        top = np.sort(want, axis=-1)
        sure = top[:, -1] - top[:, -2] > 1e-3
        assert (np.asarray(out.token_ids) == np.argmax(want, axis=-1))[sure].all()
    m = eng.metrics
    assert m["preempted"] >= 1
    assert 0 < m["mla_decode_live_tokens"] <= m["mla_decode_read_tokens"]
    # one entry a PUBLISHED layer
    assert m["moe_decode_layer_steps"] == 2 * (
        m["decode_steps"] - m["riding_steps"])
    assert 0 < m["moe_decode_zero_assignments"] < m["moe_decode_routed_assignments"]
    assert (m["moe_decode_assignments"] + m["moe_decode_zero_assignments"]
            < m["moe_decode_routed_assignments"])
    with pytest.raises(ValueError, match="0 zero-compute experts"):
        _engine(expect_zero_experts=0)
    with pytest.raises(ValueError, match="expects 2 experts a layer of 24"):
        _engine(expect_routed_experts=24)


def test_training_module_is_the_reference():
    """``Transformer`` (``Block`` under ``shortcut_moe``, ``MoEMLP`` with
    zero experts) over a whole sequence against the reference, and
    ``num_params`` against the tree and the adapter's count."""
    # room for every token in every expert: the training side drops none
    cfg = dataclasses.replace(CONFIGS["tiny"], **OVERRIDES, capacity_factor=16.0)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, (1, 12)))
    params = Transformer(cfg).init(jax.random.PRNGKey(0), toks)
    tree = jax.tree.map(lambda x: getattr(x, "value", x), params["params"],
                        is_leaf=lambda x: hasattr(x, "value"))
    got = Transformer(cfg).apply({"params": tree}, toks, mutable=["losses"])[0][0]
    with jax.default_matmul_precision("highest"):
        want = ref.forward(ref.to_reference_params(tree, PUBLISHED), toks, RCFG)[0]
    assert _rel(got, want) < 1e-4
    stored = sum(x.size for x in jax.tree.leaves(tree))
    assert cfg.num_params() == stored == ref.total_params(PUBLISHED)


def test_adapter_refuses_what_the_program_does_not_express():
    for key, value in (("zero_expert_type", "constant"),
                       ("attention_method", "MHA"),
                       ("rope_scaling", {"type": "yarn"}),
                       ("attention_bias", True), ("n_shared_experts", 1),
                       ("q_lora_rank", None), ("mla_scale_q_lora", False)):
        with pytest.raises(ValueError, match=key):
            ref.program_overrides(dict(PUBLISHED, **{key: value}), 64)
    with pytest.raises(ValueError, match="expert_parallel"):
        ref.share(dict(PUBLISHED, zero_expert_num=4))
