"""Model + ops tests on the virtual 8-device CPU mesh."""

import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import CONFIGS, Transformer, lm_loss
from ray_tpu.ops.attention import flash_attention, reference_attention
from ray_tpu.ops.ring_attention import ring_attention, ulysses_attention
from ray_tpu.parallel import TrainStepBundle, create_mesh


# the module: ``ray_tpu.ops.attention`` as an attribute is the function
ATTENTION = sys.modules[flash_attention.__module__]

# (S, head_dim, causal, (block_q, block_k) or None for the kernels' own rule).
# One block (the engine's smallest bucket); two buckets up; a length the
# largest block does not divide (128 x 128 blocks: several diagonal ones);
# the gradient check's length; non-causal; and unequal blocks, where the
# diagonal crosses more than one block of a loop
FLASH_CASES = [
    (128, 128, True, None), (256, 128, True, None), (384, 64, True, None),
    (512, 64, True, None), (256, 64, False, None),
    (512, 64, True, (256, 128)), (512, 64, True, (128, 256)),
]
FLASH_IDS = ["-".join(str(x) for x in c[:3]) + ("" if c[3] is None
                                                else "-%dx%d" % c[3])
             for c in FLASH_CASES]


def _flash_case(monkeypatch, S, D, blocks, seed):
    """bfloat16 operands as the cells send them, the reference's in float32;
    the pallas backward at every head size (the rule's own choice at these
    lengths)."""
    if blocks is not None:
        monkeypatch.setattr(ATTENTION, "_blocks", lambda seq_len: blocks)
    B, H = 1, 2
    q, k, v = (jax.random.normal(r, (B, S, H, D), jnp.float32)
               .astype(jnp.bfloat16)
               for r in jax.random.split(jax.random.PRNGKey(seed), 3))
    return (q, k, v), tuple(x.astype(jnp.float32) for x in (q, k, v))


def _close(got, want, atol=2e-3):
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(want), atol=atol, rtol=2e-2)


def _check_forward(monkeypatch, S, D, causal, blocks):
    qkv, qkv32 = _flash_case(monkeypatch, S, D, blocks, seed=0)
    _close(flash_attention(*qkv, causal, True),  # interpret mode
           reference_attention(*qkv32, causal=causal))


@pytest.mark.parametrize("S,D,causal,blocks", FLASH_CASES, ids=FLASH_IDS)
def test_flash_matches_reference_interpret(monkeypatch, S, D, causal, blocks):
    _check_forward(monkeypatch, S, D, causal, blocks)


@pytest.mark.parametrize("S,D,causal,blocks", FLASH_CASES, ids=FLASH_IDS)
def test_flash_grads_match(monkeypatch, S, D, causal, blocks):
    qkv, qkv32 = _flash_case(monkeypatch, S, D, blocks, seed=1)
    # a weighted sum: with a plain one every row's d(out) is the same
    w = jax.random.normal(jax.random.PRNGKey(2), qkv[0].shape, jnp.float32)

    def f_ref(q, k, v):
        return (reference_attention(q, k, v, causal) * w).sum()

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal, True).astype(jnp.float32)
                * w).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(*qkv32)
    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(*qkv)
    # an entry near zero is a sum of terms of size 1 to 4, each rounded to
    # bfloat16 on its way into a product: 2 to 6 of 65,536 entries miss the
    # forward's 2e-3 by up to 4.4e-3; a kernel without its mask misses by 0.1
    for got, want in zip(g_flash, g_ref):
        _close(got, want, atol=1e-2)


def test_flash_row_with_one_visible_key(monkeypatch):
    """The first query sees the first key alone (every other one masked):
    its output is that key's value, exactly, and its query gets no gradient."""
    (q, k, v), _ = _flash_case(monkeypatch, 256, 64, None, seed=3)
    out, vjp = jax.vjp(lambda q_, k_, v_: flash_attention(q_, k_, v_, True,
                                                          True), q, k, v)
    np.testing.assert_array_equal(np.asarray(out[:, 0].astype(jnp.float32)),
                                  np.asarray(v[:, 0].astype(jnp.float32)))
    dq, _, _ = vjp(jnp.ones_like(out))
    assert not np.asarray(dq[:, 0].astype(jnp.float32)).any()
    assert np.isfinite(np.asarray(out.astype(jnp.float32))).all()


@pytest.mark.parametrize("S,blocks", [(256, None), (512, (256, 128))],
                         ids=["one-block", "diagonal-in-two-blocks"])
def test_flash_comparison_fails_a_kernel_without_its_diagonal_mask(
        monkeypatch, S, blocks):
    """The comparison above is sharp enough: the same kernel with the mask
    left out of the blocks the diagonal crosses does not pass it."""
    monkeypatch.setattr(ATTENTION, "_hide_future", lambda s, *a: s)
    with pytest.raises(AssertionError):
        _check_forward(monkeypatch, S, 64, True, blocks)


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub)


@pytest.mark.parametrize("D", [64, 128])
def test_flash_kernels_feed_the_mxu_bfloat16(D):
    """The mechanism, held on the CPU: with bfloat16 inputs each of the three
    kernels is ONE pallas_call under its own name, every product in it takes
    bfloat16 operands and accumulates in float32, and the exponentials and
    whatever a loop carries (the accumulators, the running max and sum) are
    float32. Two 64-lane heads share a program (one 128-lane tile of the
    projections' layout), which forms each product once a head."""
    x = jnp.zeros((1, 1024, 2, D), jnp.bfloat16)
    heads_a_program = ATTENTION._heads_a_program(2, D)

    def fwd_and_bwd(q, k, v):
        out, vjp = jax.vjp(lambda *a: flash_attention(*a, True, False),
                           q, k, v)
        return vjp(out)

    calls = [e for e in _walk(jax.make_jaxpr(fwd_and_bwd)(x, x, x).jaxpr)
             if e.primitive.name == "pallas_call"]
    assert sorted(e.params["name"] for e in calls) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    products = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
    for call in calls:
        inner = list(_walk(call.params["jaxpr"]))
        dots = [e for e in inner if e.primitive.name == "dot_general"]
        # each product once in the loop below the diagonal, once in the loop
        # across it: the same body
        assert len(dots) == 2 * products[call.params["name"]] * heads_a_program
        for e in dots:
            assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
            assert e.params["preferred_element_type"] == jnp.float32
            assert e.outvars[0].aval.dtype == jnp.float32
        exps = [e for e in inner if e.primitive.name == "exp"]
        assert exps and all(e.invars[0].aval.dtype == jnp.float32
                            for e in exps)
        loops = [e for e in inner if e.primitive.name in ("while", "scan")]
        assert len(loops) == 2
        for e in loops:
            carried = [v.aval for v in e.outvars
                       if jnp.issubdtype(v.aval.dtype, jnp.floating)]
            assert carried and all(a.dtype == jnp.float32 for a in carried)


def _kernel_names(jaxpr):
    """The names of a jaxpr's ``pallas_call``s, nested ones too, as bound."""
    return [e.params["name"] for e in _walk(jaxpr)
            if e.primitive.name == "pallas_call"]


def _grad_jaxpr(S, D, Dv=None, dtype=jnp.bfloat16):
    """The jaxpr of a gradient through ``flash_attention`` (traced, never
    run: a long sequence costs nothing here)."""
    qk = jnp.zeros((1, S, 2, D), dtype)
    v = jnp.zeros((1, S, 2, Dv or D), dtype)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, False).astype(jnp.float32).sum()

    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(qk, qk, v).jaxpr


PAIR = ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


@pytest.mark.parametrize("S,D,Dv,dtype,pair", [
    (256, 64, 64, jnp.bfloat16, True),
    (256, 128, 128, jnp.bfloat16, True),
    (12288, 128, 128, jnp.bfloat16, True),    # the longest the rule admits
    (12800, 128, 128, jnp.bfloat16, False),   # the next multiple of 512
    (10240, 64, 64, jnp.bfloat16, True),      # two heads' tiles to a program
    (10752, 64, 64, jnp.bfloat16, False),
    (4096, 256, 256, jnp.bfloat16, True),     # a wider row leaves less room
    (4608, 256, 256, jnp.bfloat16, False),
    (4096, 128, 128, jnp.float32, True),      # float32 rows are twice as wide
    (4608, 128, 128, jnp.float32, False),
    (1536, 256, 256, jnp.float32, True),
    (2048, 256, 256, jnp.float32, False),
    (256, 192, 128, jnp.bfloat16, False),     # latent attention's head sizes
], ids=["64", "128", "128-at-the-bound", "128-over-the-bound",
        "64-at-the-bound", "64-over-the-bound", "256-at-the-bound",
        "256-over-the-bound", "128-float32-at-the-bound",
        "128-float32-over-the-bound", "256-float32-at-the-bound",
        "256-float32-over-the-bound", "192-with-values-of-128"])
def test_flash_backward_rule_is_a_function_of_the_shapes(S, D, Dv, dtype,
                                                         pair):
    """The pallas pair wherever it takes the shape (one head size, and
    whole-sequence blocks that fit fast memory:
    ``tests/test_chip_compile.py`` compiles the bound), else the forward
    kernel with ``reference_attention``'s backward."""
    assert sorted(_kernel_names(_grad_jaxpr(S, D, Dv, dtype))) == (
        PAIR if pair else ["flash_fwd"])
    if D == Dv:
        assert ATTENTION._use_pallas_bwd(
            D, S, jnp.dtype(dtype).itemsize) is pair
    assert ATTENTION._use_pallas_bwd(D)   # what the benchmark's note prints


@pytest.mark.parametrize("blocks", [(256, 128), (128, 256), (128, 128)],
                         ids=["256x128", "128x256", "128x128"])
def test_flash_backward_is_traced_at_the_patched_blocks(monkeypatch, blocks):
    """The jitted backward keys its trace on the blocks too: a trace at the
    kernels' own blocks, made first, does not stand in for the patched ones
    (``test_flash_grads_match``'s unequal-block cases share shape, dtype,
    ``causal`` and ``interpret`` with ``512-64-True``). Read off the grids of
    the three ``pallas_call``s: a row of programs for each q block (forward,
    dq) or k block (dkv), one program for the two 64-lane heads of a tile."""
    S, programs = 512, 1    # _grad_jaxpr: one row of two 64-lane heads

    def grids():
        return {e.params["name"]: tuple(e.params["grid_mapping"].grid)
                for e in _walk(_grad_jaxpr(S, 64))
                if e.primitive.name == "pallas_call"}

    def want(block_q, block_k):
        return {"flash_fwd": (programs, S // block_q),
                "flash_bwd_dq": (programs, S // block_q),
                "flash_bwd_dkv": (programs, S // block_k)}

    assert grids() == want(*ATTENTION._blocks(S))
    monkeypatch.setattr(ATTENTION, "_blocks", lambda seq_len: blocks)
    assert grids() == want(*blocks)


def test_flash_backward_at_head_dim_128_holds_no_score_array():
    """What cell 4's step loses: at head_dim 128 the gradient is the three
    kernels once each, and no float32 array with two sequence-sized
    dimensions (``reference_attention``'s scores, softmax, dP and dS) is
    left outside them."""
    S = 1024

    def square(jaxpr):
        return [v.aval for e in _walk(jaxpr) for v in e.outvars
                if getattr(v.aval, "dtype", None) == jnp.float32
                and list(v.aval.shape).count(S) >= 2]

    jaxpr = _grad_jaxpr(S, 128)
    assert sorted(_kernel_names(jaxpr)) == PAIR
    assert not square(jaxpr)
    # the check can see one: the reference's own backward
    assert square(jax.make_jaxpr(jax.grad(lambda q: reference_attention(
        q, q, q).astype(jnp.float32).sum()))(
            jnp.zeros((1, S, 2, 128), jnp.bfloat16)).jaxpr)


def _transposes_of_operands(jaxpr):
    """The 4-D transposes of a jaxpr (a kernel's own are 2-D): what brings a
    head's rows together, ``(B, S, H, D) -> (B, H, S, D)``, and back."""
    return [e for e in _walk(jaxpr) if e.primitive.name == "transpose"
            and len(e.params["permutation"]) == 4]


# (S, H, D, Dv, K/V heads, window, differentiated): a differentiated call of
# an even count of 64-lane heads reads and writes [B, S, H x D] as it lies,
# two heads to a program (PR 47); every other call brings a head's rows
# together first, as all did
LAYOUT_CASES = [
    (128, 4, 64, 64, 4, 0, True), (256, 4, 64, 64, 2, 0, True),
    (1024, 4, 64, 64, 4, 0, True), (128, 2, 128, 128, 2, 0, True),
    (256, 2, 128, 128, 1, 0, True), (1024, 2, 128, 128, 2, 0, True),
    (256, 2, 256, 256, 2, 0, True), (1024, 2, 256, 256, 1, 0, True),
    (256, 2, 192, 128, 2, 0, True), (256, 3, 64, 64, 3, 0, True),
    (256, 4, 64, 64, 4, 128, False), (256, 4, 64, 64, 4, 0, False),
    (256, 2, 128, 128, 2, 0, False),
]


def _layout_id(case):
    S, H, D, Dv, KV, window, differentiated = case
    return "".join([f"{S}-{H}x{D}", f"v{Dv}" * (Dv != D), f"-kv{KV}" * (KV != H),
                    "-window" * bool(window),
                    "-forward-only" * (not differentiated)])


@pytest.mark.parametrize("S,H,D,Dv,KV,window,differentiated", LAYOUT_CASES,
                         ids=[_layout_id(c) for c in LAYOUT_CASES])
def test_flash_reads_the_projections_layout_for_pairs_of_64_lane_heads(
        S, H, D, Dv, KV, window, differentiated):
    """Forward and all three gradients against ``reference_attention`` in
    float32 (interpret mode; K and V repeated from fewer heads as the model
    repeats them), and WHICH path the call took, read from its jaxpr: no 4-D
    transpose around the kernels where the call is differentiated and its
    heads are an even count of 64 lanes; the old layout for heads of 128 and
    256 (cell 4's step was slower on the new one), a head of 192 with values
    of 128, an odd count of 64-lane heads, a windowed call and a forward-only
    one."""
    from ray_tpu.ops.attention import attention

    B, rep = 2, H // KV
    rq, rk, rv, rw = jax.random.split(jax.random.PRNGKey(S + H + D), 4)
    q = jax.random.normal(rq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(rk, (B, S, KV, D), jnp.float32)
    v = jax.random.normal(rv, (B, S, KV, Dv), jnp.float32)
    w = jax.random.normal(rw, (B, S, H, Dv), jnp.float32)

    def out(impl):
        def f(q, k, v):
            return attention(q, jnp.repeat(k, rep, axis=2),
                             jnp.repeat(v, rep, axis=2), impl=impl,
                             window=window)
        return f

    def loss(impl):
        return lambda *a: (out(impl)(*a) * w).sum()

    in_place = (differentiated and D == Dv
                and ATTENTION._heads_a_program(H, D) == 2)
    if differentiated:
        jaxpr = jax.make_jaxpr(jax.grad(loss("flash_interpret"),
                                        argnums=(0, 1, 2)))(q, k, v).jaxpr
        names = PAIR if D == Dv else ["flash_fwd"]
    else:
        jaxpr = jax.make_jaxpr(out("flash_interpret"))(q, k, v).jaxpr
        names = ["flash_fwd"]
    assert sorted(_kernel_names(jaxpr)) == names
    moved = _transposes_of_operands(jaxpr)
    if in_place:
        assert not moved
    elif D == Dv and differentiated:
        # q, k, v in and o out; q, k, v, dO in and dq, dk, dv out
        assert len(moved) == 11
    elif not differentiated:
        assert len(moved) == 4          # q, k, v in and o out
    else:
        assert len(moved) >= 4          # ... and the reference's backward

    tol = dict(atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out("flash_interpret")(q, k, v),
                               out("xla")(q, k, v), **tol)
    if differentiated:
        got = jax.grad(loss("flash_interpret"), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(got, want):
            np.testing.assert_allclose(g, r, atol=2e-4, rtol=2e-4)


# a call that knows its rows' lengths: (key / value head size, window, the
# rows' lengths in a bucket of 512 under blocks of 128, the query blocks the
# rows hold among the 4 a row that the grid has: counted by hand). The four
# rows: none (a padding row), the whole bucket, one ending ON a block's edge
# and one a position past it
LENS_ROWS = ([0, 512, 256, 257], [0, 4, 2, 3])
LENS_CASES = {
    "128-one-row": (128, 128, 0, [257], [3]),
    "128-one-row-of-a-position": (128, 128, 0, [1], [1]),
    "128": (128, 128, 0, *LENS_ROWS),
    "192-values-of-128": (192, 128, 0, *LENS_ROWS),
    "64": (64, 64, 0, *LENS_ROWS),
    "64-window-shorter-than-the-rows": (64, 64, 100, *LENS_ROWS),
    "64-window-longer-than-two-rows": (64, 64, 300, *LENS_ROWS),
    "128-window-of-a-block-one-row": (128, 128, 128, [130], [2]),
    "192-values-of-128-window-longer-than-the-bucket": (192, 128, 1024,
                                                        *LENS_ROWS),
}


@pytest.mark.parametrize("D,Dv,window,lens,held", list(LENS_CASES.values()),
                         ids=list(LENS_CASES))
def test_flash_passes_over_the_blocks_behind_a_rows_end(monkeypatch, D, Dv,
                                                        window, lens, held):
    """``attention(..., lens=)`` through the forward-only kernel (interpret
    mode, float32): every real position reads what ``reference_attention``
    gives it and, bit for bit, what the same kernel gives without lengths
    (the same blocks in the same order); the query blocks wholly behind a
    row's end are zeros, what lies behind the end inside the block that
    holds it is finite; and ``q_blocks`` counts what the grid passes over."""
    from ray_tpu.ops.attention import attention, flash_attention_fwd, q_blocks

    S, block, H = 512, 128, 2
    monkeypatch.setattr(ATTENTION, "_blocks", lambda seq_len: (block, block))
    rq, rk, rv = jax.random.split(jax.random.PRNGKey(D + window + len(lens)), 3)
    q = jax.random.normal(rq, (len(lens), S, H, D), jnp.float32)
    k = jax.random.normal(rk, (len(lens), S, H, D), jnp.float32)
    v = jax.random.normal(rv, (len(lens), S, H, Dv), jnp.float32)
    got = np.asarray(attention(q, k, v, impl="flash_interpret", window=window,
                               lens=jnp.asarray(lens, jnp.int32)))
    whole = np.asarray(flash_attention_fwd(q, k, v, True, True, window))
    want = np.asarray(reference_attention(q, k, v, window=window))
    assert np.isfinite(got).all()
    for row, (n, blocks) in enumerate(zip(lens, held)):
        np.testing.assert_allclose(got[row, :n], want[row, :n], atol=2e-5,
                                   rtol=2e-5)
        np.testing.assert_array_equal(got[row, :n], whole[row, :n])
        assert blocks == -(-n // block)
        assert not got[row, blocks * block:].any()
        if n % block:  # the block that holds the end is computed whole
            np.testing.assert_array_equal(got[row, n:blocks * block],
                                          whole[row, n:blocks * block])
    assert q_blocks(S, lens) == (len(lens) * 4, len(lens) * 4 - sum(held))


def _stable_text(lowered):
    """A lowering's StableHLO with each Mosaic kernel's module decoded and
    printed WITHOUT its source locations (file, line and column of every
    operation travel in the serialized kernel: a comment added above it
    would change the bytes)."""
    import base64
    import re

    from jax.extend.mlir import ir
    from jax.interpreters import mlir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def module(found):
        with ctx:
            return ir.Module.parse(base64.b64decode(
                found.group(1))).operation.get_asm(enable_debug_info=False)

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', module,
                  lowered.as_text())


# the serving engine's prefill calls of ``attention()``, one of each kind
# (cell 3's bucket of 16 heads of 128; cell 9's 32 heads of 64; cell 7's 40
# heads of 64 under its window of 512; cell 6's latent heads, keys of 192 and
# values of 128): (q shape, value head size, window, sha256 of the call's
# lowering for the TPU at commit 30ae167, jax 0.9.0)
SERVE_PREFILL_CALLS = {
    "128-lane-heads": ((1, 256, 16, 128), 128, 0,
                       "74310e5f7dbaf8615a0e9e3506c6d0f3"
                       "70305379bab4335bceca79d10d9d25a5"),
    "64-lane-heads": ((1, 512, 32, 64), 64, 0,
                      "836744133ed7b566291dc811e4838c49"
                      "6ab32cf36bc3761f0e4eeb73de36ed01"),
    "window": ((1, 1024, 40, 64), 64, 512,
               "6b9087a23d3482549ce78160b757d5c2"
               "ca467ae5104f7d2444a4c02c9b855ff8"),
    "latent-192-128": ((1, 4096, 16, 192), 128, 0,
                       "826e830c6b0f3c527969c481da34e63f"
                       "b607dabc7ae8cc44dca4ae915f037dcd"),
}


# the same four calls told their rows' lengths, as the engine's prefill tells
# them since PR 54 (the lengths prefetched as scalars, a program behind its
# row's end passed over): sha256 of each lowering at that PR, jax 0.9.0
SERVE_PREFILL_CALLS_WITH_LENS = {
    "128-lane-heads": "21dcd0ba59bbae7a8f73eb43427a08cb"
                      "d6eab52bca57ad2016a9bd6ac99f0faf",
    "64-lane-heads": "e7a658ce4b140bcbf783c00c1556e87c"
                     "f2d77bd342b99d08741e39f08f61dd89",
    "window": "e914390b4ebaf29ec1ff86df24c4c83b"
              "f921c72b6b122577c72bb76fdd533d09",
    "latent-192-128": "c1842238e76849c42014940df359dcb2"
                      "6749fbca23afb0310065eb65ff4a6c4f",
}


@pytest.mark.parametrize("kind,lens", [
    (kind, lens) for lens in (False, True) for kind in SERVE_PREFILL_CALLS],
    ids=[kind + "-lens" * lens for lens in (False, True)
         for kind in SERVE_PREFILL_CALLS])
def test_serve_prefill_attention_lowers_as_it_did(kind, lens):
    """The forward-only calls keep the ``(B*H, S, D)`` layout and the program
    they had before the differentiated calls moved (PR 47): each lowers for
    the TPU to the text it lowered to then. A PR that moves these calls to the
    projections' layout too (ROADMAP S1(l), S2(e)) changes the digests on
    purpose, with the serve cells measured; any other change to them is a
    change to seven cells' prefill that nobody asked for. (A new jax may
    print the same program otherwise: take the digests again at the parent.)
    A call WITHOUT lengths is what a train step's forward lowers to where it
    is not differentiated in place: its digests are PR 47's still. The
    engine's calls carry their rows' lengths (``lens``) and have digests of
    their own."""
    import hashlib

    from ray_tpu.ops.attention import attention

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken with jax 0.9.0")
    shape, dv, window, digest = SERVE_PREFILL_CALLS[kind]
    qk = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape[:3] + (dv,), jnp.bfloat16)
    operands = (qk, qk, v)
    if lens:
        digest = SERVE_PREFILL_CALLS_WITH_LENS[kind]
        operands += (jax.ShapeDtypeStruct(shape[:1], jnp.int32),)
    lowered = jax.jit(lambda q, k, v, lens=None: attention(
        q, k, v, causal=True, impl="flash", window=window, lens=lens)).trace(
            *operands).lower(lowering_platforms=("tpu",))
    assert hashlib.sha256(
        _stable_text(lowered).encode()).hexdigest() == digest


def test_ring_attention_matches_reference():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = create_mesh({"seq": 8})
    rng = jax.random.PRNGKey(2)
    B, S, H, D = 2, 64, 2, 16
    q, k, v = (jax.random.normal(r, (B, S, H, D), jnp.float32)
               for r in jax.random.split(rng, 3))

    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq", causal=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
        check_vma=False)
    out = ring(q, k, v)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-2)


def test_ulysses_matches_reference():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = create_mesh({"seq": 2}, devices=jax.devices()[:2])
    rng = jax.random.PRNGKey(3)
    B, S, H, D = 2, 32, 4, 16
    q, k, v = (jax.random.normal(r, (B, S, H, D), jnp.float32)
               for r in jax.random.split(rng, 3))
    uly = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "seq", causal=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
        check_vma=False)
    out = uly(q, k, v)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-2)


def test_tiny_model_forward_and_loss():
    cfg = CONFIGS["tiny"]
    model = Transformer(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    loss = lm_loss(logits, tokens)
    assert np.isfinite(float(loss))


def test_train_step_dp_fsdp_tp():
    """Full train step jitted over a dp*fsdp*tp mesh: loss decreases."""
    cfg = CONFIGS["tiny"]
    mesh = create_mesh({"data": 2, "fsdp": 2, "seq": 1, "tensor": 2})
    bundle = TrainStepBundle(cfg, mesh)
    params, opt_state = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = bundle.make_batch(rng, batch_size=4, seq_len=64)
    losses = []
    for _ in range(5):
        params, opt_state, loss = bundle.step(params, opt_state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]  # memorizing one batch


def test_train_step_backward_inside_the_shard_map_matches_xla():
    """Cell 4's path at toy size: head_dim 128 with repeated K/V heads through
    ``TrainStepBundle`` on a ``data=2`` mesh, where ``attention`` wraps the
    kernel in a ``shard_map`` and the pallas backward runs inside the map's
    transpose on each device's rows. Loss and every gradient leaf against the
    same step through ``reference_attention``, and the program holds the
    backward pair."""
    import dataclasses

    cfg = dataclasses.replace(
        CONFIGS["tiny"], d_model=256, n_heads=2, n_kv_heads=1,
        attention_impl="flash_interpret", remat=True)
    assert cfg.head_dim == 128
    mesh = create_mesh({"data": 2, "fsdp": 1, "seq": 1, "tensor": 1},
                       devices=jax.devices()[:2])
    bundle = TrainStepBundle(cfg, mesh)
    plain = TrainStepBundle(dataclasses.replace(cfg, attention_impl="xla"),
                            mesh)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    batch = bundle.make_batch(np.random.default_rng(0), batch_size=4,
                              seq_len=128)
    names = _kernel_names(jax.make_jaxpr(bundle._fwd_bwd)(params, batch).jaxpr)
    # under remat the forward runs again inside the backward
    assert sorted(set(names)) == PAIR
    assert names.count("flash_bwd_dq") == names.count("flash_bwd_dkv") \
        == cfg.n_layers
    loss, grads = bundle._fwd_bwd(params, batch)
    want_loss, want = plain._fwd_bwd(params, batch)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-3)
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        # both sides round to bfloat16 at every use; a leaf's distance
        # relative to its norm, as the cells' gradient check takes it
        assert np.linalg.norm(got - ref) <= 2e-2 * np.linalg.norm(ref)


def test_param_shardings_cover_mesh():
    cfg = CONFIGS["tiny"]
    mesh = create_mesh({"data": 1, "fsdp": 4, "seq": 1, "tensor": 2})
    bundle = TrainStepBundle(cfg, mesh)
    specs = jax.tree.leaves(
        bundle.param_shardings, is_leaf=lambda x: hasattr(x, "spec"))
    assert any("tensor" in str(s.spec) for s in specs)
    assert any("fsdp" in str(s.spec) for s in specs)
