"""Model partitioning: the flagship transformer as S pipeline stages.

The cut is at block granularity and **name-preserving**: a stage module
re-creates exactly the parameters the full :class:`Transformer` owns under
the same top-level names (``embed``, ``layer_<i>``, ``final_norm``,
``lm_head``), so

- a stage's parameter tree is a key-subset of the full model's tree —
  :func:`split_params` / :func:`merge_params` are pure dict selection, and
  a pipeline checkpoint saved per stage can be re-partitioned onto a
  DIFFERENT stage count by reading only the leaves each new stage needs
  (no gather, no rewrite);
- ``StageModule.init`` with the full model's seed reproduces the full
  model's values for its slice (flax folds the param RNG over the module
  path, and the paths are identical).

Backward runs as stage-granularity rematerialization: FWD stashes the
microbatch *input* only, BWD re-runs the forward under ``jax.vjp`` — the
standard 1F1B memory trade (activation stash per stage is bounded by the
warmup depth, not the microbatch count; see schedule.py).

MoE aux losses compose across the cut without shipping a scalar: stage
``s``'s vjp takes cotangent ``moe_aux_coef`` on its own sown aux, and the
aux-sensitivity of *downstream* stages arrives folded into the incoming
activation gradient (the chain rule does the bookkeeping).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
    lm_loss,
)
from ray_tpu.utils import import_jax


def partition_layers(n_layers: int, num_stages: int) -> List[Tuple[int, int]]:
    """Balanced contiguous [start, stop) layer ranges, earlier stages get
    the remainder (they also carry the embed table, but block cost
    dominates at depth)."""
    if not 1 <= num_stages <= n_layers:
        raise ValueError(
            f"cannot cut {n_layers} layers into {num_stages} stages")
    base, rem = divmod(n_layers, num_stages)
    out, start = [], 0
    for s in range(num_stages):
        stop = start + base + (1 if s < rem else 0)
        out.append((start, stop))
        start = stop
    return out


def stage_param_keys(cfg: TransformerConfig, stage: int, num_stages: int,
                     boundaries: Optional[List[Tuple[int, int]]] = None
                     ) -> List[str]:
    """The top-level param-dict keys stage ``stage`` owns."""
    bounds = boundaries or partition_layers(cfg.n_layers, num_stages)
    start, stop = bounds[stage]
    keys = [f"layer_{i}" for i in range(start, stop)]
    if stage == 0:
        keys.insert(0, "embed")
    if stage == num_stages - 1:
        keys.append("final_norm")
        if not cfg.tie_embeddings:
            keys.append("lm_head")
    return keys


def rank_chunk_keys(cfg: TransformerConfig, rank: int, num_stages: int,
                    num_chunks: int = 1,
                    boundaries: Optional[List[Tuple[int, int]]] = None
                    ) -> Dict[int, List[str]]:
    """Param keys per model chunk for one pipeline rank hosting
    ``num_chunks`` interleaved chunks (Megatron-style virtual stages).

    Chunk ``v`` on rank ``r`` is virtual stage ``q = v*num_stages + r``
    of the ``num_stages*num_chunks``-way cut — the interleaved placement
    is just the deeper cut re-dealt round-robin, so every key helper
    above applies unchanged at ``P = S*V``. Returns ``{q: [keys...]}``
    in local chunk order (ascending ``v``); the union across all ranks
    partitions the full key set."""
    num_virtual = num_stages * num_chunks
    return {v * num_stages + rank:
            stage_param_keys(cfg, v * num_stages + rank, num_virtual,
                             boundaries)
            for v in range(num_chunks)}


def split_params(full_params: Dict[str, Any], cfg: TransformerConfig,
                 num_stages: int,
                 boundaries: Optional[List[Tuple[int, int]]] = None
                 ) -> List[Dict[str, Any]]:
    """Cut a full model param dict into per-stage subtrees (pure key
    selection — values are shared, not copied)."""
    return [{k: full_params[k]
             for k in stage_param_keys(cfg, s, num_stages, boundaries)}
            for s in range(num_stages)]


def merge_params(stage_params: List[Dict[str, Any]]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for p in stage_params:
        out.update(p)
    return out


def _build_stage_module(cfg: TransformerConfig, start: int, stop: int,
                        first: bool, last: bool):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import Block, RMSNorm

    class StageModule(nn.Module):
        """Layers [start, stop) of the flagship transformer, plus the
        embed table (first stage) / final norm + LM head (last stage).
        Absolute layer names keep param paths identical to the full
        model's."""

        cfg: TransformerConfig

        @nn.compact
        def __call__(self, x, positions=None, segment_ids=None):
            c = self.cfg
            if first:
                tokens = x
                if positions is None:
                    positions = jnp.arange(tokens.shape[1])[None, :].astype(
                        jnp.int32)
                    positions = jnp.broadcast_to(positions, tokens.shape)
                embed = self.param(
                    "embed", nn.with_logical_partitioning(
                        nn.initializers.normal(0.02), ("vocab", "embed")),
                    (c.vocab_size, c.d_model), c.param_dtype)
                x = embed.astype(c.dtype)[tokens]
                x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
            elif positions is None:
                positions = jnp.arange(x.shape[1])[None, :].astype(jnp.int32)
                positions = jnp.broadcast_to(positions, x.shape[:2])
            block = Block
            if c.remat:
                block = nn.remat(
                    Block, prevent_cse=False,
                    policy=jax.checkpoint_policies.nothing_saveable)
            for i in range(start, stop):
                x = block(c, c.is_moe_layer(i), name=f"layer_{i}")(
                    x, positions, segment_ids)
            if not last:
                return x
            x = RMSNorm(c.norm_eps, c.dtype, name="final_norm")(x)
            if c.tie_embeddings:
                # only reachable single-stage (StagePrograms rejects tied
                # heads for S > 1), so `embed` is in scope
                logits = jnp.einsum("bsd,vd->bsv", x, embed.astype(c.dtype))
            else:
                head = self.param(
                    "lm_head", nn.with_logical_partitioning(
                        nn.initializers.normal(0.02), ("embed", "vocab")),
                    (c.d_model, c.vocab_size), c.param_dtype)
                logits = jnp.einsum("bsd,dv->bsv", x, head.astype(c.dtype),
                                    preferred_element_type=jnp.float32)
            return nn.with_logical_constraint(logits,
                                              ("batch", "seq", "vocab"))

    return StageModule(cfg)


class StagePrograms:
    """The jitted programs one pipeline stage runs.

    - first/middle stage: ``fwd(params, x) -> (y, aux)`` and
      ``bwd(params, x, dy) -> (dparams[, dx])`` (vjp with aux cotangent
      ``moe_aux_coef``; the first stage takes no dx — tokens are ints);
    - last stage: ``bwd(params, x, targets, mask) ->
      (loss, aux, dparams, dx)`` — one value_and_grad program yields the
      step's loss AND grads (its FWD op only stashes the input; 1F1B runs
      F and B back to back on the last stage, so a separate forward would
      double its compute). ``fwd_loss`` stays as the eval entry;
    - every stage: ``acc_grads`` (microbatch accumulation),
      ``grad_sqnorm`` (for the controller's coordinated global-norm
      clip) and ``opt_apply(grads, scale, opt_state, params)``.
    """

    def __init__(self, cfg: TransformerConfig, stage: int, num_stages: int,
                 optimizer,
                 boundaries: Optional[List[Tuple[int, int]]] = None):
        if cfg.tie_embeddings and num_stages > 1:
            raise ValueError(
                "tie_embeddings shares the embed table between the first "
                "and last stage; pipeline partitioning needs untied heads")
        jax = import_jax()
        import jax.numpy as jnp
        import optax

        self.cfg = cfg
        self.stage = stage
        self.num_stages = num_stages
        self.first = stage == 0
        self.last = stage == num_stages - 1
        bounds = boundaries or partition_layers(cfg.n_layers, num_stages)
        self.start, self.stop = bounds[stage]
        self.module = _build_stage_module(cfg, self.start, self.stop,
                                          self.first, self.last)
        self.optimizer = optimizer
        coef = jnp.float32(cfg.moe_aux_coef)

        def apply_fn(params, x):
            y, cols = self.module.apply({"params": params}, x,
                                        mutable=["losses"])
            aux = sum(jax.tree.leaves(cols.get("losses", {}))) + 0.0
            return y, jnp.asarray(aux, jnp.float32)

        if self.last:
            def loss_fn(params, x, targets, mask):
                logits, aux = apply_fn(params, x)
                return lm_loss(logits, targets, mask) + coef * aux, aux

            self.fwd_loss = jax.jit(loss_fn)

            # the last stage's FWD op only stashes its input: loss AND
            # grads come from this one value_and_grad program at BWD
            # (1F1B runs them back to back — a separate forward would
            # double the most expensive stage's per-microbatch compute)
            if self.first:  # single-stage pipeline: x is int tokens
                def bwd_last(params, x, targets, mask):
                    grad_fn = jax.value_and_grad(
                        lambda p: loss_fn(p, x, targets, mask),
                        has_aux=True)
                    (loss, aux), dparams = grad_fn(params)
                    return loss, aux, dparams, None
            else:
                def bwd_last(params, x, targets, mask):
                    grad_fn = jax.value_and_grad(
                        lambda p, xx: loss_fn(p, xx, targets, mask),
                        argnums=(0, 1), has_aux=True)
                    (loss, aux), (dparams, dx) = grad_fn(params, x)
                    return loss, aux, dparams, dx

            self.bwd = jax.jit(bwd_last)
        else:
            self.fwd = jax.jit(apply_fn)
            if self.first:
                def bwd_first(params, tokens, dy):
                    _, vjp = jax.vjp(lambda p: apply_fn(p, tokens), params)
                    (dparams,) = vjp((dy, coef))
                    return dparams

                self.bwd = jax.jit(bwd_first)
            else:
                def bwd_mid(params, x, dy):
                    _, vjp = jax.vjp(apply_fn, params, x)
                    dparams, dx = vjp((dy, coef))
                    return dparams, dx

                self.bwd = jax.jit(bwd_mid)

        self.acc_grads = jax.jit(
            lambda acc, g: jax.tree.map(jnp.add, acc, g))
        self.grad_sqnorm = jax.jit(
            lambda g: sum(jnp.vdot(a.astype(jnp.float32),
                                   a.astype(jnp.float32)).real
                          for a in jax.tree.leaves(g)))

        def opt_apply(grads, scale, opt_state, params):
            grads = jax.tree.map(
                lambda a: (a.astype(jnp.float32) * scale).astype(a.dtype),
                grads)
            updates, opt_state = self.optimizer.update(grads, opt_state,
                                                       params)
            return optax.apply_updates(params, updates), opt_state

        self.opt_apply = jax.jit(opt_apply)

    def init(self, rng) -> Dict[str, Any]:
        """Standalone per-stage init (tests; the trainer normally places
        driver-split weights through the weight plane instead)."""
        jax = import_jax()
        import flax.linen as nn
        import jax.numpy as jnp

        c = self.cfg
        S = min(c.max_seq_len, 128)
        if self.first:
            x = jnp.zeros((1, S), dtype=jnp.int32)
        else:
            x = jnp.zeros((1, S, c.d_model), dtype=c.dtype)
        return nn.unbox(self.module.init(rng, x)["params"])

    def opt_init(self, params):
        return self.optimizer.init(params)


def make_stage_optimizer(learning_rate: float = 3e-4,
                         weight_decay: float = 0.1,
                         warmup_steps: int = 100,
                         total_steps: int = 10000,
                         b1: float = 0.9, b2: float = 0.95):
    """Per-stage optimizer matching ``parallel.train.make_optimizer``
    MINUS the global-norm clip: clipping needs the global norm across
    stages, which the pipeline controller coordinates (local sqnorms ->
    one scale for everyone) before ``opt_apply``."""
    import optax

    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay)
