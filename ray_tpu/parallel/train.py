"""GSPMD training step for the flagship model.

Builds the jitted train step the Train layer runs on every host (SURVEY.md
§3.4 — the reference only launches processes; here the compute path is part
of the framework): optax optimizer, bf16 compute / fp32 params, logical
shardings resolved against the mesh so DP/FSDP/TP/SP all come from the same
definition.

One step program per layout of the training state (see OVERLAP.md next to
this file; T3 arxiv 2401.16677 + weight-update sharding arxiv 2004.13336),
and ``step()`` dispatches it whether tracing is on or off:

- ``_fused_step``: forward, backward, clip, AdamW and apply in ONE jitted
  program with donated params + opt state, both on the parameters' logical
  shardings (``fused_param_shardings``: replicated over ``data``).
- ``_fused_step_sharded`` (``shard_update=True``; opt-in, needs a mesh
  ``data`` axis > 1): the float32 master of every leaf lives with the
  moments it is updated from, sharded across the data axis
  (``param_shardings`` names that layout; ``init_sharded`` makes it). The
  step gathers each leaf ONCE, at its head, in the dtype the model reads
  it in (``cfg.dtype`` for the kernels, the table and the head: the shard
  is cast before it travels; norm scales and the like as they are), that
  one copy serves forward, recomputation and backward, the partitioner
  turns the grad all-reduce into a reduce-scatter, each replica updates
  its 1/N slice, and nothing is gathered after the update. **Bit-exact**
  against ``_fused_step`` in float32 and in bfloat16 (pinned-association
  global-norm clip; asserted in tests/test_train.py).
  ``unshard_params`` gathers the float32 tree once, outside the step, for
  a checkpoint, a publish or an eval that wants it whole.

The phases are ``jax.named_scope``s inside the program
(``train.fwd_bwd``, ``train.optimizer``), so a device trace shows where
the step time goes; the host records one ``train.step`` span around the
dispatch when tracing is enabled and never waits for the device.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional, Tuple

import numpy as np

from ray_tpu.models.transformer import Transformer, TransformerConfig, lm_loss
from ray_tpu.parallel.mesh import logical_to_mesh_sharding
from ray_tpu.utils import import_jax

_metrics_lock = threading.Lock()
_metrics: Optional[dict] = None


def _obs() -> dict:
    """Lazily-created train-step metrics on the shared registry (always
    on: every step through TrainStepBundle lands in ``/metrics``)."""
    global _metrics
    with _metrics_lock:
        if _metrics is None:
            from ray_tpu.util.metrics import Histogram

            _metrics = {
                "step": Histogram(
                    "ray_tpu.train.step_seconds",
                    "host wall time of one TrainStepBundle.step call "
                    "(the dispatch; the device runs on behind it)",
                    boundaries=[0.001, 0.01, 0.1, 1, 10]),
            }
        return _metrics


def sharded_clip_by_global_norm(max_norm: float,
                                spec_fn: Optional[Callable] = None):
    """``optax.clip_by_global_norm`` with the global norm computed from
    shard-local sqnorms under a PINNED association.

    ``spec_fn(shape) -> Optional[NamedSharding]`` fixes each leaf's
    reduction layout with ``with_sharding_constraint`` before the sqnorm,
    so the partitioner computes per-shard partial sums + a rank-ordered
    cross-replica sum IDENTICALLY in every program that embeds this clip
    (the fused step and the sharded fused step) — which is what makes the
    sharded update bit-exact against the fused step. With ``spec_fn=None``
    the association is the leaf-local one (single-replica case)."""
    import optax

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        jax = import_jax()
        import jax.numpy as jnp

        del params

        def sq(x):
            xs = x.astype(jnp.float32)
            spec = spec_fn(tuple(x.shape)) if spec_fn is not None else None
            if spec is not None:
                xs = jax.lax.with_sharding_constraint(xs, spec)
            return jnp.sum(jnp.square(xs))

        leaves = [sq(x) for x in jax.tree_util.tree_leaves(updates)]
        acc = leaves[0]
        for leaf in leaves[1:]:  # explicit fold: the tree order IS the
            acc = acc + leaf     # cross-program contract
        g_norm = jnp.sqrt(acc)
        factor = max_norm / jnp.maximum(g_norm, max_norm)
        updates = jax.tree_util.tree_map(
            lambda u: u * factor.astype(u.dtype), updates)
        return updates, state

    return optax.GradientTransformation(init_fn, update_fn)


def make_optimizer(learning_rate: float = 3e-4, weight_decay: float = 0.1,
                   warmup_steps: int = 100, total_steps: int = 10000,
                   b1: float = 0.9, b2: float = 0.95, clip: float = 1.0,
                   clip_spec_fn: Optional[Callable] = None):
    """AdamW + global-norm clip. ``clip_spec_fn`` switches the clip to the
    sharded (pinned-association) form — TrainStepBundle passes its update
    shardings here when ``shard_update`` is on; the default stays plain
    ``optax.clip_by_global_norm`` (bit-identical to previous releases)."""
    import optax

    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    clip_t = (sharded_clip_by_global_norm(clip, clip_spec_fn)
              if clip_spec_fn is not None else optax.clip_by_global_norm(clip))
    return optax.chain(
        clip_t,
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )


# primitives that hand an operand to an inner jaxpr's invar of the same
# position, so a use inside them is a use of the operand itself
_CALLS = frozenset({"jit", "pjit", "remat2", "checkpoint", "closed_call",
                    "core_call", "custom_jvp_call", "custom_vjp_call"})


def _only_cast_to(jaxpr, var, dtype) -> bool:
    """True when every use of ``var`` in ``jaxpr`` (through the calls of
    ``_CALLS``) is a ``convert_element_type`` to ``dtype``: casting the
    leaf before the program then changes no value the program computes."""
    jax = import_jax()

    if any(out is var for out in jaxpr.outvars):
        return False
    used = False
    for eqn in jaxpr.eqns:
        at = [i for i, v in enumerate(eqn.invars) if v is var]
        if not at:
            continue
        used = True
        if eqn.primitive.name == "convert_element_type":
            if eqn.params["new_dtype"] != dtype:
                return False
            continue
        inner = [getattr(j, "jaxpr", j)
                 for j in jax.core.jaxprs_in_params(eqn.params)]
        if (eqn.primitive.name not in _CALLS or len(inner) != 1
                or len(inner[0].invars) != len(eqn.invars)
                or not all(_only_cast_to(inner[0], inner[0].invars[i], dtype)
                           for i in at)):
            return False
    return used


class TrainStepBundle:
    """Everything a training worker needs: init fn, step fn, shardings.

    ``shard_update=True`` (opt-in; requires a mesh ``data`` axis > 1)
    turns on the cross-replica sharded optimizer update — the caller
    must then hold params AND opt state on the sharded layout
    (``init_sharded``, or ``shard_params`` / ``shard_opt_state``);
    ``param_shardings`` names the layout ``step`` and ``_fwd_bwd`` take,
    ``fused_param_shardings`` the one ``init`` / ``_fused_step`` keep.
    ``optimizer_factory(clip_spec_fn)`` lets the
    caller parameterize the optimizer while still receiving the bundle's
    update shardings for the pinned-association clip (pass ``optimizer=``
    for a fixed transform — bit-parity of the sharded step then depends
    on that transform using ``sharded_clip_by_global_norm``)."""

    def __init__(self, cfg: TransformerConfig, mesh, optimizer=None,
                 donate: bool = True, shard_update: bool = False,
                 optimizer_factory: Optional[Callable] = None):
        jax = import_jax()
        import flax.linen as nn
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.cfg = cfg
        self.mesh = mesh
        self.model = Transformer(cfg)
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.dp_size = int(axis_sizes.get("data", 1))
        self.shard_update = bool(shard_update) and self.dp_size > 1

        clip_spec_fn = self._norm_spec if self.shard_update else None
        if optimizer is not None:
            self.optimizer = optimizer
        elif optimizer_factory is not None:
            self.optimizer = optimizer_factory(clip_spec_fn)
        else:
            self.optimizer = make_optimizer(clip_spec_fn=clip_spec_fn)

        def init_boxed(rng):
            B, S = 1, min(cfg.max_seq_len, 128)
            tokens = jax.numpy.zeros((B, S), dtype=jax.numpy.int32)
            params = self.model.init(rng, tokens)["params"]
            opt_state = self.optimizer.init(params)
            return params, opt_state

        def init_fn(rng):
            # state is plain trees everywhere (grads, opt state, published
            # weights); the logical-partition boxes only feed the spec
            # derivation below
            return nn.unbox(init_boxed(rng))

        abstract = jax.eval_shape(init_boxed, jax.random.PRNGKey(0))
        logical = nn.get_partition_spec(abstract)
        shardings = logical_to_mesh_sharding(logical, mesh)
        # the fused program's layout: every leaf on its logical sharding,
        # replicated over ``data``
        self.fused_param_shardings, self.opt_shardings = shardings
        self.batch_sharding = NamedSharding(mesh, P(("data", "fsdp"), "seq"))
        self.repl = NamedSharding(mesh, P())
        self._abstract_params, self._abstract_opt = nn.unbox(abstract)

        # cross-replica update shardings: each leaf gains the "data" axis
        # on its first dim that can absorb it; scalars and odd leaves stay
        # on their base sharding. Adam-family moments mirror a param
        # leaf's shape AND base sharding, so the float32 master and its
        # moments get the same rule and the same dim: the update is local.
        self.opt_shard_shardings = jax.tree_util.tree_map(
            self._update_sharding, self._abstract_opt, self.opt_shardings)
        # the layout of the parameters ``step`` takes and returns (and
        # ``_fwd_bwd`` takes): the sharded master when ``shard_update`` is
        # on, the fused program's layout otherwise
        self.param_shardings = jax.tree_util.tree_map(
            self._update_sharding, self._abstract_params,
            self.fused_param_shardings)

        self.init = jax.jit(init_fn, out_shardings=shardings)
        self.init_sharded = jax.jit(
            init_fn,
            out_shardings=(self.param_shardings, self.opt_shard_shardings))

        from ray_tpu.ops.attention import partitioned_over

        def on_mesh(fn):
            """For functions the jit-partitioned programs below trace: on a
            mesh of several devices the attention kernel must know the
            layout of its operands (Mosaic kernels are not
            auto-partitionable)."""
            if mesh.size == 1:
                return fn

            def traced(*args):
                with partitioned_over(mesh, ("data", "fsdp"), "tensor"):
                    return fn(*args)

            return traced

        @on_mesh
        def loss_fn(params, tokens, targets, mask):
            # "losses" is valid for dense models too (empty -> aux sums to 0)
            logits, cols = self.model.apply(
                {"params": params}, tokens, mutable=["losses"])
            aux = sum(jax.tree.leaves(cols.get("losses", {})))
            return lm_loss(logits, targets, mask) + cfg.moe_aux_coef * aux

        read_dtypes = self._read_dtypes() if self.shard_update else None

        def gather_for_use(params):
            """The sharded step's one gather a leaf, ahead of the forward:
            the shard is cast to the dtype the model reads the leaf in
            (``gather(cast(x)) == cast(gather(x))`` to the bit) and
            constrained onto the fused layout, outside the rematerialised
            blocks, so forward, recomputation and backward share the one
            copy. The cotangent goes back onto the shard's layout BEFORE
            it widens: the partitioner reduce-scatters it in the dtype the
            backward made it in (the plain transpose would ask for a
            replicated cotangent, an all-reduce)."""
            def one(x, dtype, held, used):
                master = x.dtype

                @jax.custom_vjp
                def gather(x):
                    return jax.lax.with_sharding_constraint(
                        x.astype(dtype), used)

                gather.defvjp(
                    lambda x: (gather(x), None),
                    lambda _, ct: (jax.lax.with_sharding_constraint(
                        ct, held).astype(master),))
                return gather(x)

            return jax.tree_util.tree_map(
                one, params, read_dtypes, self.param_shardings,
                self.fused_param_shardings)

        # the phases are scopes inside the one program: metadata a device
        # trace carries, nothing the compiler schedules by
        def programs(read=None):
            """``fwd_bwd`` and ``train_step`` of the loss over
            ``read(params)`` (the params themselves when None)."""
            loss_of = loss_fn if read is None else (
                lambda params, *batch: loss_fn(read(params), *batch))

            def fwd_bwd(params, batch):
                with jax.named_scope("train.fwd_bwd"):
                    return jax.value_and_grad(loss_of)(
                        params, batch["tokens"], batch["targets"],
                        batch.get("mask"))

            def train_step(params, opt_state, batch):
                import optax

                loss, grads = fwd_bwd(params, batch)
                with jax.named_scope("train.optimizer"):
                    updates, opt_state = self.optimizer.update(
                        grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                return params, opt_state, loss

            return fwd_bwd, train_step

        fwd_bwd, train_step = programs()
        batch_shardings = {"tokens": self.batch_sharding,
                           "targets": self.batch_sharding,
                           "mask": self.batch_sharding}
        donate_args = (0, 1) if donate else ()
        self._fused_step = jax.jit(
            train_step,
            in_shardings=(self.fused_param_shardings, self.opt_shardings,
                          batch_shardings),
            out_shardings=(self.fused_param_shardings, self.opt_shardings,
                           self.repl),
            donate_argnums=donate_args,
        )
        # the SHARDED step (shard_update on): the same step over
        # ``gather_for_use(params)``, with the float32 master and the opt
        # state in/out sharded across data: one gather a leaf at the head
        # of the program in the dtype the model reads, a reduce-scatter of
        # the grads, shard-local update math, and nothing gathered after
        # it. Bit-exact vs _fused_step (tests/test_train.py pins it).
        self._fused_step_sharded = None
        if self.shard_update:
            fwd_bwd, train_step = programs(gather_for_use)
            self._fused_step_sharded = jax.jit(
                train_step,
                in_shardings=(self.param_shardings, self.opt_shard_shardings,
                              batch_shardings),
                out_shardings=(self.param_shardings,
                               self.opt_shard_shardings, self.repl),
                donate_argnums=donate_args,
            )

        # the step's own loss and gradients (of the program ``step``
        # dispatches, on its parameter layout), for callers that check
        # them against a reference (the benchmark's gradient check)
        self._fwd_bwd = jax.jit(
            fwd_bwd,
            in_shardings=(self.param_shardings, batch_shardings),
            out_shardings=(self.repl, self.param_shardings),
        )

        def eval_step(params, batch):
            logits, _ = self.model.apply(
                {"params": params}, batch["tokens"], mutable=["losses"])
            return lm_loss(logits, batch["targets"], batch.get("mask"))

        self.eval_step = jax.jit(on_mesh(eval_step))

        # shape/dtype-keyed compile detection for the goodput ledger: a
        # batch key this bundle has not dispatched before means jit will
        # block the call through trace+lower+compile — that wall time is
        # ``compile``, not ``step_compute``, and a NEW key on a warm
        # program is the recompile(-storm) signal
        from ray_tpu.util import goodput as _goodput

        self._compile_watch = _goodput.CompileWatch()

    # -- sharding helpers -------------------------------------------------

    def _update_sharding(self, abstract_leaf, base_sharding):
        """The cross-replica update sharding for one leaf: append the
        ``data`` axis to the first dim that can absorb it (dim size
        divisible by the dim's existing shard count x dp); leaves with no
        such dim stay on their base sharding (replicated update)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        shape = tuple(getattr(abstract_leaf, "shape", ()))
        if not self.shard_update or not shape:
            return base_sharding
        axis_sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        spec = list(getattr(base_sharding, "spec", P()) or P())
        spec += [None] * (len(shape) - len(spec))
        for d, size in enumerate(shape):
            entry = spec[d]
            axes = (() if entry is None
                    else (entry,) if isinstance(entry, str) else tuple(entry))
            if "data" in axes:
                return base_sharding  # already data-sharded
            existing = int(np.prod([axis_sizes.get(a, 1) for a in axes])) \
                if axes else 1
            if size % (existing * self.dp_size) == 0:
                spec[d] = tuple(axes) + ("data",) if axes else "data"
                return NamedSharding(self.mesh, P(*spec))
        return base_sharding

    def _read_dtypes(self):
        """Per parameter leaf, the dtype the model reads it in:
        ``cfg.dtype`` where the forward's every use of the leaf is a cast
        to it (the dense kernels, the embedding table, the head), the
        leaf's own dtype otherwise (norm scales; routers and the like
        elsewhere). Read off the forward's jaxpr, so it follows the
        config's ``dtype`` and whatever layers the config builds."""
        jax = import_jax()
        import jax.numpy as jnp

        tokens = jax.ShapeDtypeStruct(
            (1, min(self.cfg.max_seq_len, 128)), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda params, tokens: self.model.apply(
                {"params": params}, tokens, mutable=["losses"])
        )(self._abstract_params, tokens).jaxpr
        leaves, treedef = jax.tree_util.tree_flatten(self._abstract_params)
        dtype = jnp.dtype(self.cfg.dtype)
        return treedef.unflatten(
            [dtype if _only_cast_to(jaxpr, var, dtype) else leaf.dtype
             for var, leaf in zip(jaxpr.invars, leaves)])

    def _norm_spec(self, shape: Tuple[int, ...]):
        """Shape-only reduction layout for the sharded clip (must be a
        pure function of shape so every program pins the same
        association)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if not shape:
            return None
        for d, size in enumerate(shape):
            if size % self.dp_size == 0:
                spec = [None] * len(shape)
                spec[d] = "data"
                return NamedSharding(self.mesh, P(*spec))
        return None

    # -- state conversion -------------------------------------------------

    def shard_opt_state(self, opt_state):
        """Reshard an (unsharded) opt state onto the cross-replica update
        shardings (adopting state from a fused-step run)."""
        jax = import_jax()

        return jax.device_put(opt_state, self.opt_shard_shardings)

    def unshard_opt_state(self, opt_state):
        """Gather a sharded opt state back onto the fused-step shardings
        (checkpointing through consumers that expect the base layout)."""
        jax = import_jax()

        return jax.device_put(opt_state, self.opt_shardings)

    def shard_params(self, params):
        """Reshard parameters from the fused layout onto the layout
        ``step`` takes (adopting a fused-step run or a restored
        checkpoint)."""
        jax = import_jax()

        return jax.device_put(params, self.param_shardings)

    def unshard_params(self, params):
        """Gather the sharded float32 master back onto the fused layout,
        once and outside the step: what a checkpoint, ``train.publish`` or
        an eval that wants the whole float32 tree asks for."""
        jax = import_jax()

        return jax.device_put(params, self.fused_param_shardings)

    def param_bytes_per_replica(self, params) -> int:
        """Per-device bytes of these parameters (as
        ``opt_state_bytes_per_replica`` counts the opt state)."""
        return self.opt_state_bytes_per_replica(params)

    def opt_state_bytes_per_replica(self, opt_state) -> int:
        """Per-device bytes of this opt state (sharded leaves count one
        shard; replicated leaves count in full — the honest per-replica
        cost)."""
        jax = import_jax()

        total = 0
        for leaf in jax.tree_util.tree_leaves(opt_state):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                total += int(np.asarray(shards[0].data).nbytes)
            else:
                total += int(np.asarray(leaf).nbytes)
        return total

    def opt_state_bytes_total(self) -> int:
        """Unsharded footprint of one full optimizer state (from the
        abstract tree — no state needs to be materialized)."""
        jax = import_jax()

        total = 0
        for leaf in jax.tree_util.tree_leaves(self._abstract_opt):
            shape = tuple(getattr(leaf, "shape", ()))
            itemsize = np.dtype(leaf.dtype).itemsize
            total += (int(np.prod(shape, dtype=np.int64)) * itemsize
                      if shape else itemsize)
        return total

    # -- the step ---------------------------------------------------------

    def step(self, params, opt_state, batch):
        """One optimization step: ONE fused XLA program — the
        sharded-update flavor when ``shard_update`` is on (params and opt
        state must be on the sharded layout, e.g. from ``init_sharded``,
        and come back on it), the plain fused program otherwise.

        Instrumented without selecting what the device runs: the
        ``ray_tpu.train.step_seconds`` histogram, one ``train.step`` span
        around the dispatch (recorded when tracing is enabled), and the
        goodput ledger — ``step_compute`` normally; a compile-watch miss
        (new batch shape/dtype key) routes the call, which jit blocks
        through trace+lower+compile, into the nested ``compile`` bucket
        with the outputs synced so compile wall time is fully captured.
        That first call of a shape is the only one that waits for the
        device."""
        from ray_tpu.util import goodput, tracing

        t0 = time.perf_counter()
        if self.shard_update:
            program, fn = "fused_sharded", self._fused_step_sharded
        else:
            program, fn = "fused", self._fused_step
        kind = self._compile_watch.observe(program, goodput.batch_key(batch))
        with tracing.profile("train.step", category="train"), \
                goodput.region("step_compute"):
            if kind is None:
                out = fn(params, opt_state, batch)
            else:
                with goodput.region("compile"):
                    out = fn(params, opt_state, batch)
                    import_jax().block_until_ready(out)
        goodput.count("steps")
        if kind:
            goodput.count("compiles")
            if kind == "recompile":
                goodput.count("recompiles")
        _obs()["step"].observe(time.perf_counter() - t0)
        return out

    def make_batch(self, rng: np.random.Generator, batch_size: int, seq_len: int):
        """Synthetic LM batch (tokens/targets/mask) laid out for the mesh."""
        jax = import_jax()

        tokens = rng.integers(0, self.cfg.vocab_size, (batch_size, seq_len + 1),
                              dtype=np.int32)
        batch = {
            "tokens": tokens[:, :-1],
            "targets": tokens[:, 1:],
            "mask": np.ones((batch_size, seq_len), np.float32),
        }
        return {k: jax.device_put(v, self.batch_sharding) for k, v in batch.items()}
