"""GSPMD training step for the flagship model.

Builds the jitted train step the Train layer runs on every host (SURVEY.md
§3.4 — the reference only launches processes; here the compute path is part
of the framework): optax optimizer, bf16 compute / fp32 params, logical
shardings resolved against the mesh so DP/FSDP/TP/SP all come from the same
definition.

Overlapped + cross-replica-sharded update (see OVERLAP.md next to this
file; T3 arxiv 2401.16677 + weight-update sharding arxiv 2004.13336):
with ``shard_update=True`` (opt-in; needs a mesh ``data`` axis > 1),
optimizer state and the update computation are sharded across the data axis — grads
leave the backward as a reduce-scatter instead of an all-reduce, each
replica updates its 1/N slice, and the refreshed params all-gather back.
Expressed three ways:

- **untraced sharded step** (the perf path): ONE jitted program with
  shard-annotated opt state + donated buffers; XLA's async collectives
  overlap the grad reduce-scatter with the tail of the backward and the
  param all-gather with the update — and it is **bit-exact in fp32**
  against the fused unsharded step (same-program codegen, pinned-
  association global-norm clip; asserted in tests/test_train.py).
- **traced sharded step** (observability): phase-split programs — a
  shard_map backward emitting per-replica local grads, then one jitted
  reduce-scatter program PER BUCKET (size-bounded layer-order buckets,
  ``bucket_bytes``) dispatched asynchronously, then the sharded optimizer
  program. Each bucket lands as a ``train.bucket_allreduce`` span nested
  under ``train.fwd_bwd`` in ``/api/timeline``.
- the **fused single-program step** stays the untraced / 1-replica
  fallback, byte-identical behavior to previous releases when
  ``shard_update`` is off.
"""

from __future__ import annotations

import threading
import time
from contextlib import nullcontext as _nullcontext
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.models.transformer import Transformer, TransformerConfig, lm_loss
from ray_tpu.parallel.mesh import AXES, LOGICAL_RULES, logical_to_mesh_sharding
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

            bounds = [0.001, 0.01, 0.1, 1, 10]
            _metrics = {
                "step": Histogram(
                    "ray_tpu.train.step_seconds",
                    "full train step wall time (fwd+bwd+optimizer; "
                    "device-synchronized when tracing is enabled)",
                    boundaries=bounds),
                "fwd_bwd": Histogram(
                    "ray_tpu.train.fwd_bwd_seconds",
                    "forward+backward (value_and_grad) phase of the "
                    "traced train step", boundaries=bounds),
                "optimizer": Histogram(
                    "ray_tpu.train.optimizer_seconds",
                    "optimizer update+apply phase of the traced train "
                    "step", boundaries=bounds),
                "bucket_rs": Histogram(
                    "ray_tpu.train.bucket_reduce_seconds",
                    "per-bucket grad reduce-scatter program wall time on "
                    "the traced sharded step", boundaries=bounds),
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
    (the fused step, the sharded single-program step, and the split
    optimizer program) — which is what makes the sharded update bit-exact
    against the fused step. With ``spec_fn=None`` the association is the
    leaf-local one (single-replica case)."""
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


class TrainStepBundle:
    """Everything a training worker needs: init fn, step fn, shardings.

    ``shard_update=True`` (opt-in; requires a mesh ``data`` axis > 1)
    turns on the cross-replica sharded optimizer update — the caller
    must then hold opt state on the sharded layout (``init_sharded`` /
    ``shard_opt_state``); ``bucket_bytes`` bounds the grad buckets the
    traced path reduces individually. ``optimizer_factory(clip_spec_fn)`` lets the
    caller parameterize the optimizer while still receiving the bundle's
    update shardings for the pinned-association clip (pass ``optimizer=``
    for a fixed transform — bit-parity of the sharded step then depends
    on that transform using ``sharded_clip_by_global_norm``)."""

    def __init__(self, cfg: TransformerConfig, mesh, optimizer=None,
                 rules=LOGICAL_RULES, donate: bool = True,
                 shard_update: bool = False,
                 bucket_bytes: int = 32 << 20,
                 optimizer_factory: Optional[Callable] = None,
                 grad_dtype: str = "fp32",
                 compression: Optional[str] = None):
        jax = import_jax()
        import flax.linen as nn
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.collective.quant import resolve_codec

        self.cfg = cfg
        self.mesh = mesh
        self.model = Transformer(cfg)
        self.rules = rules
        self.bucket_bytes = bucket_bytes
        if grad_dtype not in ("fp32", "bf16"):
            raise ValueError(f"grad_dtype must be fp32 or bf16, got "
                             f"{grad_dtype!r}")
        # "bf16": grads are narrowed to bf16 for the cross-replica
        # reduce-scatter (half the collective bytes; explicit on the
        # traced bucket programs, a value-narrowing cast pair on the
        # one-program path) while optimizer state and params stay fp32
        # master copies. Default "fp32" keeps every program bit-identical
        # to previous releases.
        self.grad_dtype = grad_dtype
        # block-quantized wire for the traced bucket programs (the
        # EQuARX-style XLA tier): each data-sharded leaf's reduce-scatter
        # becomes quantize -> all_to_all (uint8 codes + fp32 block scales
        # on the wire) -> fp32 dequant-accumulate. Strictly opt-in; the
        # one-program untraced path never quantizes.
        self._codec = resolve_codec(compression)
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.dp_size = int(axis_sizes.get("data", 1))
        self.shard_update = bool(shard_update) and self.dp_size > 1
        self._warned_untraced = False
        if self._codec is not None and not self.shard_update:
            raise ValueError(
                f"compression={compression!r} requires shard_update=True "
                f"on a mesh with data>1 (data={self.dp_size}) — the "
                f"quantized wire exists only in the traced sharded bucket "
                f"programs; it would be silently ignored here")

        def clip_spec_fn(shape):
            return self._norm_spec(shape)

        if optimizer is not None:
            self.optimizer = optimizer
        elif optimizer_factory is not None:
            self.optimizer = optimizer_factory(
                clip_spec_fn if self.shard_update else None)
        else:
            self.optimizer = make_optimizer(
                clip_spec_fn=clip_spec_fn if self.shard_update else None)

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
        shardings = logical_to_mesh_sharding(logical, mesh, rules)
        self.param_shardings, self.opt_shardings = shardings
        self.batch_sharding = NamedSharding(mesh, P(("data", "fsdp"), "seq"))
        self.repl = NamedSharding(mesh, P())
        self._abstract_params, self._abstract_opt = nn.unbox(abstract)

        # cross-replica update shardings: each leaf gains the "data" axis
        # on its first dim that can absorb it (opt state + grads; params
        # keep their logical shardings — they are consumed replicated on
        # data and re-emitted replicated via the program's all-gather)
        self.grad_shardings = jax.tree_util.tree_map(
            self._update_sharding, self._abstract_params,
            self.param_shardings)
        self.opt_shard_shardings = self._opt_update_shardings()

        self.init = jax.jit(init_fn, out_shardings=shardings)
        self.init_sharded = jax.jit(
            init_fn,
            out_shardings=(self.param_shardings, self.opt_shard_shardings))

        def local_loss_fn(params, tokens, targets, mask):
            # "losses" is valid for dense models too (empty -> aux sums to 0)
            logits, cols = self.model.apply(
                {"params": params}, tokens, mutable=["losses"])
            aux = sum(jax.tree.leaves(cols.get("losses", {})))
            return lm_loss(logits, targets, mask) + cfg.moe_aux_coef * aux

        # the shard_map tier traces this one: already per-device there
        self._loss_fn = local_loss_fn

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

        loss_fn = on_mesh(local_loss_fn)

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, batch["tokens"], batch["targets"], batch.get("mask"))
            grads = self._narrow_grads(grads)
            updates, opt_state = self.optimizer.update(grads, opt_state, params)
            import optax

            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        batch_shardings = {"tokens": self.batch_sharding,
                           "targets": self.batch_sharding,
                           "mask": self.batch_sharding}
        self._batch_shardings = batch_shardings
        donate_args = (0, 1) if donate else ()
        self._fused_step = jax.jit(
            train_step,
            in_shardings=(self.param_shardings, self.opt_shardings,
                          batch_shardings),
            out_shardings=(self.param_shardings, self.opt_shardings, self.repl),
            donate_argnums=donate_args,
        )
        # the SHARDED single-program step (the untraced perf path with
        # shard_update on): same program text, opt state in/out sharded
        # across data — the partitioner emits reduce-scatter for the
        # grads, shard-local update math, and an all-gather for the
        # updated params, all overlappable by XLA's async collectives.
        # Bit-exact vs _fused_step (tests/test_train.py pins it).
        self._fused_step_sharded = jax.jit(
            train_step,
            in_shardings=(self.param_shardings, self.opt_shard_shardings,
                          batch_shardings),
            out_shardings=(self.param_shardings, self.opt_shard_shardings,
                           self.repl),
            donate_argnums=donate_args,
        ) if self.shard_update else None

        # phase-split programs for the TRACED step (fwd+bwd and optimizer
        # as separate XLA programs, so tracing.profile() spans can bound
        # each phase); the untraced path keeps the fused program — and its
        # fusion/donation — untouched
        def fwd_bwd(params, batch):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, batch["tokens"], batch["targets"], batch.get("mask"))
            return loss, self._narrow_grads(grads)

        self._fwd_bwd = jax.jit(
            fwd_bwd,
            in_shardings=(self.param_shardings, batch_shardings),
            out_shardings=(self.repl, self.param_shardings),
        )
        # sharded-update flavor: grads leave the backward already
        # reduce-scattered onto the data axis
        self._fwd_bwd_rs = jax.jit(
            fwd_bwd,
            in_shardings=(self.param_shardings, batch_shardings),
            out_shardings=(self.repl, self.grad_shardings),
        ) if self.shard_update else None

        def opt_apply(grads, opt_state, params):
            import optax

            updates, opt_state = self.optimizer.update(
                grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        # donation restored on the split path (PR 10 left grads undonated
        # to dodge XLA alias warnings). An optimizer program has one more
        # param-shaped input than output (grads + state + params ->
        # state' + params'), so exactly one donated input can never alias;
        # the warning-free maximal sets differ per flavor:
        # - unsharded: donate grads + opt_state — params' aliases the
        #   grads buffer (same full shape), params-in stays live as the
        #   read-only weight-decay/apply operand;
        # - sharded: donate opt_state + params — params' (all-gathered,
        #   full shape) aliases params-in, the 1/N grad shard is the
        #   pigeonhole leftover and stays undonated.
        # tests/test_train.py asserts the log is free of alias warnings.
        self._opt_apply = jax.jit(
            opt_apply,
            in_shardings=(self.param_shardings, self.opt_shardings,
                          self.param_shardings),
            out_shardings=(self.param_shardings, self.opt_shardings),
            donate_argnums=(0, 1) if donate else (),
        )
        self._opt_apply_sharded = jax.jit(
            opt_apply,
            in_shardings=(self.grad_shardings, self.opt_shard_shardings,
                          self.param_shardings),
            out_shardings=(self.param_shardings, self.opt_shard_shardings),
            donate_argnums=(1, 2) if donate else (),
        ) if self.shard_update else None

        # explicit bucketed tier (traced sharded path): needs a pure-DP
        # mesh (every non-data axis size 1) so params fit shard_map's
        # replicated in_spec without materializing gathers
        self._explicit_ok = self.shard_update and all(
            axis_sizes.get(a, 1) == 1 for a in AXES if a != "data")
        self._fwd_bwd_local = None
        self._bucket_programs: Optional[List] = None
        self._bucket_plan = None

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

    def _narrow_grads(self, grads):
        """``grad_dtype="bf16"``: round grads through bf16 before the
        optimizer. On the one-program path this narrows the values the
        cross-replica reduction consumes (the collective's placement is
        XLA's; the traced bucket programs make the bf16 wire explicit);
        opt state and params remain fp32 master copies. A no-op at
        fp32 — the default program is untouched."""
        if self.grad_dtype != "bf16":
            return grads
        jax = import_jax()
        import jax.numpy as jnp

        return jax.tree_util.tree_map(
            lambda g: g.astype(jnp.bfloat16).astype(g.dtype), grads)

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

    def _opt_update_shardings(self):
        """Opt-state shardings for the sharded update: every leaf derives
        its own update sharding from its shape + base sharding — for
        adam-family moments (which mirror a param leaf's shape AND base
        sharding, both coming from the same flax spec derivation) this
        lands on exactly the matching param's update sharding; scalars and
        odd leaves stay on their base sharding."""
        jax = import_jax()

        return jax.tree_util.tree_map(self._update_sharding,
                                      self._abstract_opt,
                                      self.opt_shardings)

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

    # -- bucket plan + explicit bucketed programs -------------------------

    @property
    def bucket_plan(self):
        """Layer-ordered size-bounded bucket plan over the grad tree
        (shared with the collective tier — collective/bucketed.py)."""
        if self._bucket_plan is None:
            from ray_tpu.collective.bucketed import leaf_meta, plan_buckets

            self._bucket_plan = plan_buckets(
                leaf_meta(self._abstract_params),
                bucket_bytes=self.bucket_bytes,
                world_size=self.dp_size)
        return self._bucket_plan

    def _build_explicit(self):
        """The traced sharded tier: a shard_map backward emitting stacked
        per-replica local grads, plus one jitted reduce-scatter program
        per bucket. Built lazily — only the traced path pays the
        compiles."""
        if self._fwd_bwd_local is not None:
            return
        jax = import_jax()
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        loss_fn = self._loss_fn
        dp = self.dp_size
        bspec = P(("data", "fsdp"), "seq")

        def local_fb(params, tokens, targets, mask):
            def f(p, t, tg, m):
                loss, g = jax.value_and_grad(loss_fn)(p, t, tg, m)
                # the fused step's gradient weights every token by
                # 1/sum(global mask); the local loss normalized by the
                # LOCAL mask sum would make sparse replicas count extra
                # (mean-of-means). Reweight each replica's grads by
                # m_local * dp / m_global — exactly 1.0 for equal-count
                # shards (the bit-parity case), the fused weighting
                # otherwise. The bucket programs' trailing 1/dp folds the
                # dp factor back out.
                m_local = jnp.sum(m)
                m_global = jax.lax.psum(m_local, ("data", "fsdp"))
                w = (m_local * np.float32(dp) / m_global).astype(jnp.float32)
                g = jax.tree_util.tree_map(
                    lambda a: a * w.astype(a.dtype), g)
                return loss[None], m_local[None], jax.tree_util.tree_map(
                    lambda a: a[None], g)

            grad_specs = jax.tree_util.tree_map(lambda _: P("data"), params)
            return shard_map(
                f, mesh=mesh,
                in_specs=(P(), bspec, bspec, bspec),
                out_specs=(P("data"), P("data"), grad_specs),
                check_vma=False)(params, tokens, targets, mask)

        self._fwd_bwd_local = jax.jit(
            local_fb,
            in_shardings=(self.param_shardings, self.batch_sharding,
                          self.batch_sharding, self.batch_sharding))

        flat, _ = jax.tree_util.tree_flatten_with_path(self._abstract_params)
        by_path = {jax.tree_util.keystr(k): a for k, a in flat}
        gsh_flat, _ = jax.tree_util.tree_flatten_with_path(
            self.grad_shardings)
        sh_by_path = {jax.tree_util.keystr(k): s for k, s in gsh_flat}
        inv = np.float32(1.0 / dp)

        def _data_dim(sharding) -> Optional[int]:
            """The leaf dim carrying the ``data`` axis in its update
            sharding (the reduce-scatter dim), or None (replicated)."""
            spec = tuple(getattr(sharding, "spec", P()) or P())
            for d, entry in enumerate(spec):
                axes = (() if entry is None
                        else (entry,) if isinstance(entry, str)
                        else tuple(entry))
                if "data" in axes:
                    return d
            return None

        codec = self._codec
        bf16_wire = self.grad_dtype == "bf16"

        def _q_rs_leaf(v, d):
            """Quantized reduce-scatter of one leaf on dim ``d``: split
            into per-owner parts along ``d``, block-quantize each part,
            ``all_to_all`` the uint8 codes + fp32 scales (the wire leg —
            1 byte/element instead of 4), dequant-accumulate in fp32.
            Output == psum_scatter(v, scatter_dimension=d, tiled=True) to
            quantization error. Stateless (no error feedback) — EF lives
            in the explicit tier where residuals can persist."""
            from ray_tpu.collective.quant import jnp_block_encode

            block = codec.block
            vm = jnp.moveaxis(v, d, 0)
            rest = vm.shape[1:]
            seg = vm.shape[0] // dp
            flat = vm.reshape(dp, -1)
            m = flat.shape[1]
            nb = -(-m // block)
            if nb * block != m:
                flat = jnp.pad(flat, ((0, 0), (0, nb * block - m)))
            if codec.name == "bf16":  # narrow wire dtype, no scales
                qg = jax.lax.all_to_all(
                    flat.reshape(dp, nb * block).astype(jnp.bfloat16),
                    "data", split_axis=0, concat_axis=0, tiled=False)
                summed = jnp.sum(qg.astype(jnp.float32), axis=0)[:m]
                return jnp.moveaxis(summed.reshape((seg,) + rest), 0, d)
            q, scale = jnp_block_encode(flat.reshape(dp, nb, block),
                                        codec.name)
            qg = jax.lax.all_to_all(q, "data", split_axis=0, concat_axis=0,
                                    tiled=False)
            sg = jax.lax.all_to_all(scale, "data", split_axis=0,
                                    concat_axis=0, tiled=False)
            vals = qg.astype(jnp.float32) * sg[..., None]
            summed = jnp.sum(vals, axis=0).reshape(-1)[:m]
            return jnp.moveaxis(summed.reshape((seg,) + rest), 0, d)

        def make_bucket_rs(paths):
            dims = [_data_dim(sh_by_path[p]) for p in paths]

            def f(*stacked):
                outs = []
                for x, d in zip(stacked, dims):
                    if d is not None and codec is not None:
                        # quantized wire; tiny/replicated leaves below
                        # stay fp32 (QUANT.md: never quantize the
                        # few-float legs)
                        y = _q_rs_leaf(x[0], d)
                    elif d is not None and bf16_wire:
                        y = jax.lax.psum_scatter(
                            x[0].astype(jnp.bfloat16), "data",
                            scatter_dimension=d,
                            tiled=True).astype(jnp.float32)
                    elif d is not None:
                        y = jax.lax.psum_scatter(
                            x[0], "data", scatter_dimension=d, tiled=True)
                    else:
                        y = jax.lax.psum(x[0], "data")
                    outs.append(y * inv)
                return tuple(outs)

            def out_spec(d, path):
                if d is None:
                    return P()
                ndim = len(by_path[path].shape)
                entries = [None] * ndim
                entries[d] = "data"
                return P(*entries)

            in_specs = tuple(P("data") for _ in paths)
            out_specs = tuple(out_spec(d, p) for d, p in zip(dims, paths))
            return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

        self._bucket_programs = [
            (bucket, make_bucket_rs(bucket.paths))
            for bucket in self.bucket_plan.buckets
        ]
        self._grad_paths = [jax.tree_util.keystr(k) for k, _ in flat]
        _, self._grad_treedef = jax.tree_util.tree_flatten(
            self._abstract_params)

    def _step_traced_sharded(self, params, opt_state, batch):
        """Traced sharded step: local backward, per-bucket async reduce-
        scatter programs (each one a ``train.bucket_allreduce`` span
        nested under ``train.fwd_bwd``), then the sharded optimizer
        program. Matches the untraced sharded step to fp32 tolerance (the
        per-replica backward uses local-batch kernel shapes, so parity
        with the single-program path is allclose, not bitwise — see
        OVERLAP.md)."""
        jax = import_jax()
        from ray_tpu.util import tracing

        obs = _obs()
        self._build_explicit()
        with tracing.profile("train.step", category="train"):
            with tracing.profile("train.fwd_bwd", category="train",
                                 buckets=self.bucket_plan.num_buckets):
                t1 = time.perf_counter()
                losses, mask_counts, local_grads = self._fwd_bwd_local(
                    params, batch["tokens"], batch["targets"],
                    batch.get("mask"))
                flat = jax.tree_util.tree_leaves(local_grads)
                by_path = dict(zip(self._grad_paths, flat))
                # issue every bucket's reduce-scatter asynchronously as
                # soon as the backward's outputs exist; waits happen per
                # bucket so the spans bound real completion
                dispatched = []
                for bucket, prog in self._bucket_programs:
                    dispatched.append(
                        (bucket, prog(*[by_path[p] for p in bucket.paths])))
                reduced: Dict[str, Any] = {}
                for bucket, outs in dispatched:
                    tb = time.perf_counter()
                    with tracing.profile("train.bucket_allreduce",
                                         category="train",
                                         bucket=bucket.index,
                                         nbytes=bucket.nbytes,
                                         leaves=len(bucket.paths)):
                        jax.block_until_ready(outs)
                    obs["bucket_rs"].observe(time.perf_counter() - tb)
                    reduced.update(dict(zip(bucket.paths, outs)))
                grads = jax.tree_util.tree_unflatten(
                    self._grad_treedef,
                    [reduced[p] for p in self._grad_paths])
                obs["fwd_bwd"].observe(time.perf_counter() - t1)
            with tracing.profile("train.optimizer", category="train"):
                t2 = time.perf_counter()
                params, opt_state = self._opt_apply_sharded(
                    grads, opt_state, params)
                jax.block_until_ready(params)
                obs["optimizer"].observe(time.perf_counter() - t2)
        import jax.numpy as jnp

        # mask-count-weighted mean of the per-replica losses (the fused
        # step's global normalization, modulo the aux term's replica mean)
        loss = jnp.sum(losses * mask_counts) / jnp.maximum(
            jnp.sum(mask_counts), 1.0)
        return params, opt_state, loss

    # -- the step ---------------------------------------------------------

    def step(self, params, opt_state, batch):
        """One optimization step, instrumented (built-in spans + the
        ``ray_tpu.train.*`` histograms — no manual instrumentation in the
        train loop). With tracing OFF this dispatches ONE fused XLA
        program — the sharded-update flavor when ``shard_update`` is on
        (opt state must be on the sharded layout, e.g. from
        ``init_sharded`` / ``shard_opt_state``), the plain fused program
        otherwise. With tracing ON the step runs as separately-jitted
        phase programs under a ``train.step`` span tree — including
        per-bucket ``train.bucket_allreduce`` spans on the sharded
        path — so Perfetto shows where the step time goes."""
        from ray_tpu.util import goodput, tracing

        t0 = time.perf_counter()
        if not tracing.enabled():
            if self._codec is not None and not self._warned_untraced:
                # the quantized bucket programs only exist on the traced
                # path — surface the silent-fp32 trap instead of letting
                # benchmarks report compression that never engaged
                self._warned_untraced = True
                import logging

                logging.getLogger(__name__).warning(
                    "TrainStepBundle(compression=%s): tracing is "
                    "disabled, so this step runs the fused fp32 program "
                    "— the quantized wire needs tracing ON "
                    "(RAY_TPU_ENABLE_TRACING=1)", self._codec.spec())
            fn = (self._fused_step_sharded if self.shard_update
                  else self._fused_step)
            program = "fused_sharded" if self.shard_update else "fused"
            out = self._dispatch_attributed(program, fn, params, opt_state,
                                            batch)
            _obs()["step"].observe(time.perf_counter() - t0)
            return out
        if (self.shard_update and self._explicit_ok
                and batch.get("mask") is not None):
            out = self._dispatch_attributed(
                "traced_sharded", self._step_traced_sharded, params,
                opt_state, batch)
            _obs()["step"].observe(time.perf_counter() - t0)
            return out
        jax = import_jax()
        obs = _obs()
        fwd = self._fwd_bwd_rs if self.shard_update else self._fwd_bwd
        opt = self._opt_apply_sharded if self.shard_update else self._opt_apply
        kind = self._compile_watch.observe(
            "phases_rs" if self.shard_update else "phases",
            goodput.batch_key(batch))
        with goodput.region("step_compute"), \
                goodput.region("compile") if kind else _nullcontext():
            with tracing.profile("train.step", category="train"):
                with tracing.profile("train.fwd_bwd", category="train"):
                    t1 = time.perf_counter()
                    loss, grads = fwd(params, batch)
                    jax.block_until_ready(grads)
                    obs["fwd_bwd"].observe(time.perf_counter() - t1)
                with tracing.profile("train.optimizer", category="train"):
                    t2 = time.perf_counter()
                    params, opt_state = opt(grads, opt_state, params)
                    jax.block_until_ready(params)
                    obs["optimizer"].observe(time.perf_counter() - t2)
        goodput.count("steps")
        if kind:
            goodput.count("compiles")
            if kind == "recompile":
                goodput.count("recompiles")
        obs["step"].observe(time.perf_counter() - t0)
        return params, opt_state, loss

    def _dispatch_attributed(self, program, fn, params, opt_state, batch):
        """Dispatch one step program under the goodput ledger:
        ``step_compute`` normally; a compile-watch miss (new batch
        shape/dtype key) routes the call — which jit blocks through
        trace+lower+compile — into the nested ``compile`` bucket, with
        the outputs synced so compile wall time is fully captured."""
        from ray_tpu.util import goodput

        kind = self._compile_watch.observe(program, goodput.batch_key(batch))
        with goodput.region("step_compute"):
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
