"""Model + ops tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import CONFIGS, Transformer, lm_loss
from ray_tpu.ops.attention import flash_attention, reference_attention
from ray_tpu.ops.ring_attention import ring_attention, ulysses_attention
from ray_tpu.parallel import TrainStepBundle, create_mesh


def test_flash_matches_reference_interpret():
    rng = jax.random.PRNGKey(0)
    B, S, H, D = 2, 256, 4, 64
    q, k, v = (jax.random.normal(r, (B, S, H, D), jnp.float32)
               for r in jax.random.split(rng, 3))
    ref = reference_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, True)  # interpret mode
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-2)


def test_flash_grads_match():
    rng = jax.random.PRNGKey(1)
    B, S, H, D = 1, 128, 2, 64
    q, k, v = (jax.random.normal(r, (B, S, H, D), jnp.float32)
               for r in jax.random.split(rng, 3))

    def f_ref(q, k, v):
        return reference_attention(q, k, v, True).sum()

    def f_flash(q, k, v):
        return flash_attention(q, k, v, True, True).sum()

    g_ref = jax.grad(f_ref)(q, k, v)
    g_flash = jax.grad(f_flash)(q, k, v)
    np.testing.assert_allclose(np.asarray(g_flash), np.asarray(g_ref),
                               atol=2e-3, rtol=2e-2)


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
