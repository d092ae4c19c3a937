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
    the pallas backward at every head size."""
    monkeypatch.setenv("RAY_TPU_FLASH_BWD", "pallas")
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
def test_flash_kernels_feed_the_mxu_bfloat16(monkeypatch, D):
    """The mechanism, held on the CPU: with bfloat16 inputs each of the three
    kernels is ONE pallas_call under its own name, every product in it takes
    bfloat16 operands and accumulates in float32, and the exponentials and
    whatever a loop carries (the accumulators, the running max and sum) are
    float32."""
    monkeypatch.setenv("RAY_TPU_FLASH_BWD", "pallas")
    x = jnp.zeros((1, 1024, 2, D), jnp.bfloat16)

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
        assert len(dots) == 2 * products[call.params["name"]]
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


def test_param_shardings_cover_mesh():
    cfg = CONFIGS["tiny"]
    mesh = create_mesh({"data": 1, "fsdp": 4, "seq": 1, "tensor": 2})
    bundle = TrainStepBundle(cfg, mesh)
    specs = jax.tree.leaves(
        bundle.param_shardings, is_leaf=lambda x: hasattr(x, "spec"))
    assert any("tensor" in str(s.spec) for s in specs)
    assert any("fsdp" in str(s.spec) for s in specs)
