"""Bucketed asynchronous gradient collectives + cross-replica sharded update.

The explicit-collective tier of gradient sync, for workers that are
separate processes (the device step's own sync is inside its one GSPMD
program, ``parallel/train.py``): a size-bounded bucket plan over the grad
tree (layer order), an async reducer that ships each bucket through
``ray_tpu.collective`` ops on a background thread — so bucket i's
allreduce runs while the caller is still producing bucket i+1's grads or
applying bucket i-1's update — and a cross-replica **sharded optimizer**
(arxiv 2004.13336): each replica owns ~1/N of the buckets, keeps optimizer
state ONLY for its buckets, applies the update for them, and broadcasts
the refreshed params — optimizer-state memory drops N× on the data axis.

Bucketing rule: leaves are walked in tree (layer) order and packed
greedily into buckets of at most ``bucket_bytes``; a single leaf larger
than the bound becomes its own bucket (never split across buckets at this
tier — intra-leaf sharding is the GSPMD tier's job). Owners are assigned
greedily to the least-loaded rank (deterministic tie-break by rank) so the
per-replica update work and opt-state bytes stay balanced.

Global-norm clip in the sharded update is computed from shard-local
sqnorms: each owner computes per-leaf sqnorms for its buckets (full-leaf
reduction, same shapes as the fused reference), the per-leaf scalars are
allgathered into one vector ordered by global leaf index, and every rank
folds that vector in tree order — the same association
``optax.clip_by_global_norm`` uses, so the clip factor matches the
single-process reference bit-for-bit given bitwise-equal reduced grads.

Every bucket collective lands as a ``train.bucket_allreduce`` span
(nested under whatever span is active at submit time) and in the ``ray_tpu.train.allreduce_seconds``
histogram, so ``/api/timeline`` shows the overlap.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

_metrics_lock = threading.Lock()
_metrics: Optional[dict] = None

DEFAULT_BUCKET_BYTES = 32 << 20


def _obs() -> dict:
    """Bucket-collective metrics on the shared registry (lazy: importing
    this module must not pull the metrics stack into forked workers)."""
    global _metrics
    with _metrics_lock:
        if _metrics is None:
            from ray_tpu.util.metrics import Counter, Histogram

            _metrics = {
                "allreduce": Histogram(
                    "ray_tpu.train.allreduce_seconds",
                    "wall time of one grad-bucket collective (allreduce/"
                    "reduce/broadcast) on the async reducer thread",
                    boundaries=[0.0001, 0.001, 0.01, 0.1, 1, 10]),
                "bucket_bytes": Histogram(
                    "ray_tpu.train.bucket_bytes",
                    "payload bytes of one grad bucket shipped through the "
                    "collective layer",
                    boundaries=[1 << 10, 1 << 16, 1 << 20, 1 << 24, 1 << 28]),
                "buckets": Counter(
                    "ray_tpu.train.buckets_reduced",
                    "grad buckets reduced through the async bucketed "
                    "collective path"),
                "quant_saved": Counter(
                    "ray_tpu.train.quant_bytes_saved",
                    "wire bytes saved by the quantized collective tier vs "
                    "shipping fp32 on both legs (contribute + broadcast)"),
                "quant_encode": Histogram(
                    "ray_tpu.train.quant_encode_seconds",
                    "CPU time spent encoding/decoding one quantized bucket "
                    "payload (quantize + error-feedback + dequantize)",
                    boundaries=[0.00001, 0.0001, 0.001, 0.01, 0.1]),
            }
        return _metrics


@dataclass(frozen=True)
class Bucket:
    """One size-bounded group of grad leaves reduced as a unit."""

    index: int
    paths: Tuple[str, ...]
    nbytes: int
    owner: int  # rank owning this bucket's optimizer shard


@dataclass
class BucketPlan:
    """Layer-ordered bucket partition of a grad tree."""

    buckets: List[Bucket]
    bucket_bytes: int
    world_size: int
    leaf_order: Tuple[str, ...] = ()  # global leaf order (clip fold order)

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    def owned(self, rank: int) -> List[Bucket]:
        return [b for b in self.buckets if b.owner == rank]

    def total_bytes(self) -> int:
        return sum(b.nbytes for b in self.buckets)

    def bytes_per_rank(self) -> List[int]:
        out = [0] * self.world_size
        for b in self.buckets:
            out[b.owner] += b.nbytes
        return out

    def stats(self) -> Dict[str, Any]:
        sizes = [b.nbytes for b in self.buckets] or [0]
        return {
            "num_buckets": self.num_buckets,
            "bucket_bytes": self.bucket_bytes,
            "total_bytes": self.total_bytes(),
            "max_bucket_bytes": max(sizes),
            "min_bucket_bytes": min(sizes),
            "bytes_per_rank": self.bytes_per_rank(),
        }


def leaf_meta(tree: Any) -> "Dict[str, Tuple[Tuple[int, ...], Any]]":
    """``{path: (shape, dtype)}`` for every array leaf, in tree order
    (dicts iterate insertion-ordered; flax param trees are layer-ordered,
    which makes bucket order == layer order)."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for key, leaf in flat:
        path = jax.tree_util.keystr(key)
        out[path] = (tuple(getattr(leaf, "shape", ())),
                     np.dtype(getattr(leaf, "dtype", np.float32)))
    return out


def plan_buckets(meta: "Dict[str, Tuple[Tuple[int, ...], Any]]",
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 world_size: int = 1) -> BucketPlan:
    """Pack leaves (in the given order) into size-bounded buckets.

    - many tiny leaves pack into one bucket until ``bucket_bytes`` would
      be exceeded;
    - one giant leaf larger than ``bucket_bytes`` becomes its own bucket
      (leaves are never split at this tier);
    - owners balance bytes greedily across ``world_size`` ranks.
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    groups: List[Tuple[List[str], int]] = []
    cur: List[str] = []
    cur_bytes = 0
    for path, (shape, dtype) in meta.items():
        nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize \
            if shape else np.dtype(dtype).itemsize
        if cur and cur_bytes + nbytes > bucket_bytes:
            groups.append((cur, cur_bytes))
            cur, cur_bytes = [], 0
        cur.append(path)
        cur_bytes += nbytes
        if cur_bytes >= bucket_bytes:  # giant leaf or a full pack
            groups.append((cur, cur_bytes))
            cur, cur_bytes = [], 0
    if cur:
        groups.append((cur, cur_bytes))
    load = [0] * max(world_size, 1)
    buckets = []
    for i, (paths, nbytes) in enumerate(groups):
        owner = min(range(len(load)), key=lambda r: (load[r], r))
        load[owner] += nbytes
        buckets.append(Bucket(index=i, paths=tuple(paths), nbytes=nbytes,
                              owner=owner))
    return BucketPlan(buckets=buckets, bucket_bytes=bucket_bytes,
                      world_size=max(world_size, 1),
                      leaf_order=tuple(meta.keys()))


def _pack(leaves: Dict[str, np.ndarray]) -> List[Tuple[Any, np.ndarray, list]]:
    """Concatenate same-dtype leaves into flat vectors (one collective op
    per dtype instead of per leaf)."""
    by_dtype: Dict[Any, list] = {}
    for path, arr in leaves.items():
        arr = np.asarray(arr)
        by_dtype.setdefault(arr.dtype, []).append((path, arr))
    out = []
    for dtype, items in by_dtype.items():
        flat = np.concatenate([a.reshape(-1) for _, a in items]) \
            if items else np.zeros(0, dtype)
        out.append((dtype, flat, [(p, a.shape) for p, a in items]))
    return out


def _unpack(packed: List[Tuple[Any, np.ndarray, list]]
            ) -> Dict[str, np.ndarray]:
    out = {}
    for _, flat, layout in packed:
        off = 0
        for path, shape in layout:
            n = int(np.prod(shape, dtype=np.int64)) if shape else 1
            out[path] = flat[off:off + n].reshape(shape)
            off += n
    return out


class BucketHandle:
    """Future for one submitted bucket collective."""

    def __init__(self, bucket: Bucket):
        self.bucket = bucket
        self._done = threading.Event()
        self._result: Optional[Dict[str, np.ndarray]] = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: float = 300.0) -> Dict[str, np.ndarray]:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"bucket {self.bucket.index} collective did not complete "
                f"within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result

    def _set(self, result=None, error=None):
        self._result, self._error = result, error
        self._done.set()


class AsyncBucketReducer:
    """Ship grad buckets through ``ray_tpu.collective`` on a background
    thread, in deterministic bucket order (every rank must submit the
    same buckets in the same order — the collective store matches ops by
    sequence number).

    The group named here should be DEDICATED to this reducer: interleaving
    other collectives on the same group from other threads would desync
    the op sequence across ranks.
    """

    def __init__(self, group_name: str, plan: BucketPlan, *,
                 average: bool = False, compression: Any = None):
        from ray_tpu.collective.quant import ErrorFeedback, resolve_codec

        self.group_name = group_name
        self.plan = plan
        self.average = average
        # strictly opt-in: with compression=None the reduce path below is
        # byte-identical to the uncompressed tier (regression-asserted)
        self.codec = resolve_codec(compression)
        self._ef = ErrorFeedback(self.codec) if self.codec else None
        self._wire_lock = threading.Lock()
        self._wire = {"bytes_fp32_equiv": 0, "bytes_wire": 0,
                      "buckets_quantized": 0, "encode_s": 0.0}
        self._queue: "List[Tuple[Bucket, Dict[str, np.ndarray], Any, BucketHandle]]" = []
        self._cv = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(
            target=self._run, name=f"bucket-reducer-{group_name}", daemon=True)
        self._thread.start()

    # -- submission ------------------------------------------------------

    def submit(self, bucket: Bucket, leaves: Dict[str, np.ndarray]
               ) -> BucketHandle:
        """Queue one bucket's allreduce; returns immediately. The caller
        keeps computing (backward of later buckets / optimizer of earlier
        ones) while the collective runs."""
        from ray_tpu.util import tracing

        handle = BucketHandle(bucket)
        ctx = tracing.current_context()
        with self._cv:
            if self._stop:
                raise RuntimeError("reducer is shut down")
            self._queue.append((bucket, leaves, ctx, handle))
            self._cv.notify()
        return handle

    def reduce_tree(self, tree: Any, timeout: float = 300.0) -> Any:
        """Convenience: bucket-partition a full grad tree, submit every
        bucket (async), wait for all, and reassemble the reduced tree."""
        import jax

        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        by_path = {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat}
        handles = [
            self.submit(b, {p: by_path[p] for p in b.paths})
            for b in self.plan.buckets
        ]
        reduced: Dict[str, np.ndarray] = {}
        for h in handles:
            reduced.update(h.result(timeout))
        leaves = [reduced[jax.tree_util.keystr(k)] for k, _ in flat]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # -- worker ----------------------------------------------------------

    def _run(self):
        while True:
            with self._cv:
                while not self._queue and not self._stop:
                    self._cv.wait(1.0)
                if self._stop and not self._queue:
                    return
                bucket, leaves, ctx, handle = self._queue.pop(0)
            try:
                handle._set(result=self._reduce(bucket, leaves, ctx))
            except BaseException as e:  # surfaced via handle.result()
                handle._set(error=e)

    def _reduce(self, bucket: Bucket, leaves: Dict[str, np.ndarray], ctx
                ) -> Dict[str, np.ndarray]:
        from ray_tpu import collective as col
        from ray_tpu.util import tracing

        obs = _obs()
        t0 = time.time()
        packed = _pack(leaves)
        out = []
        wire_up = wire_down = 0
        for dtype, flat, layout in packed:
            if self.codec is not None and np.issubdtype(dtype, np.floating):
                reduced, up, down = self._reduce_quantized(bucket, dtype,
                                                           flat)
                reduced = reduced.astype(dtype, copy=False)
                wire_up += up
                wire_down += down
            else:
                reduced = np.asarray(col.allreduce(
                    flat, group_name=self.group_name))
            if self.average:
                reduced = reduced / self.plan.world_size
            out.append((dtype, reduced, layout))
        result = _unpack(out)
        end = time.time()
        span_extra = {}
        if self.codec is not None:
            span_extra = {"compression": self.codec.name,
                          "wire_bytes": wire_up + wire_down}
        tracing.record_span(
            "train.bucket_allreduce", t0, end, category="train",
            trace_id=ctx[0] if ctx else tracing.new_trace_id(),
            span_id=tracing.new_span_id(),
            parent_id=ctx[1] if ctx else None,
            bucket=bucket.index, nbytes=bucket.nbytes, owner=bucket.owner,
            leaves=len(bucket.paths), **span_extra)
        obs["allreduce"].observe(end - t0)
        obs["bucket_bytes"].observe(bucket.nbytes)
        obs["buckets"].inc()
        return result

    def _reduce_quantized(self, bucket: Bucket, dtype, flat: np.ndarray
                          ) -> Tuple[np.ndarray, int, int]:
        """One dtype-vector's quantized allreduce: error-feedback encode
        on the contribute leg, fp32 dequant-accumulate at the store's
        reduce point, one re-quantized broadcast leg (see quant.py)."""
        from ray_tpu import collective as col
        from ray_tpu.collective import quant

        obs = _obs()
        t0 = time.perf_counter()
        qt = self._ef.encode((bucket.index, str(dtype)), flat)
        wire = quant.to_wire(qt)
        enc_s = time.perf_counter() - t0
        out_wire = col.allreduce_quantized(wire, self.codec,
                                           group_name=self.group_name)
        t1 = time.perf_counter()
        reduced = quant.dequantize(quant.from_wire(out_wire)).astype(
            np.float32, copy=False)
        enc_s += time.perf_counter() - t1
        up, down = quant.wire_nbytes(wire), quant.wire_nbytes(out_wire)
        fp32_equiv = int(flat.astype(np.float32, copy=False).nbytes) * 2
        obs["quant_encode"].observe(enc_s)
        obs["quant_saved"].inc(max(fp32_equiv - (up + down), 0))
        with self._wire_lock:
            self._wire["bytes_fp32_equiv"] += fp32_equiv
            self._wire["bytes_wire"] += up + down
            self._wire["buckets_quantized"] += 1
            self._wire["encode_s"] += enc_s
        return reduced, up, down

    def wire_stats(self) -> Dict[str, Any]:
        """Cumulative wire-byte accounting of the quantized path (both
        legs; ``bytes_fp32_equiv`` is what the same traffic costs
        uncompressed). Empty-ish when compression is off."""
        with self._wire_lock:
            s = dict(self._wire)
        s["compression"] = self.codec.name if self.codec else None
        if s["bytes_wire"]:
            s["wire_reduction_x"] = round(
                s["bytes_fp32_equiv"] / s["bytes_wire"], 2)
        return s

    def shutdown(self, timeout: float = 30.0):
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout)


def init_sharded_optimizer_groups(world_size: int, rank: int,
                                  backend: str = "cpu",
                                  base_name: str = "train.grads"):
    """Initialize the two collective groups a ``ShardedBucketOptimizer``
    uses in this process: ``base_name`` (dedicated to the async bucket
    reducer) and ``base_name + ".norm"`` (clip allgather + param
    broadcasts, which run on the caller thread)."""
    from ray_tpu import collective as col

    col.init_collective_group(world_size, rank, backend=backend,
                              group_name=base_name)
    col.init_collective_group(world_size, rank, backend=backend,
                              group_name=f"{base_name}.norm")
    return base_name


class ShardedBucketOptimizer:
    """Cross-replica sharded optimizer update over a bucket plan (the
    multi-controller tier of arxiv 2004.13336).

    Rank r keeps optimizer state ONLY for the buckets it owns (~1/N of
    the params by bytes). One ``step``:

    1. every bucket's grads are reduced (async, pipelined) — owners end
       up with the summed grads for their buckets;
    2. owners compute per-leaf sqnorms for the coordinated global-norm
       clip; the per-leaf scalars are allgathered and folded in global
       leaf order on every rank (bit-identical association to
       ``optax.clip_by_global_norm`` over the full tree);
    3. owners apply the optax update for their buckets (per-bucket opt
       state; adam-family transforms are per-leaf so bucket-wise apply
       matches whole-tree apply bit-for-bit);
    4. updated params broadcast from each owner — the broadcast of bucket
       i overlaps the update compute of bucket i+1.

    ``optimizer`` must be a PER-LEAF optax transform (adam family,
    sgd/momentum, weight decay): ``update()`` runs once per owned bucket
    subtree, so a cross-leaf transform (``optax.clip_by_global_norm``)
    buried in the chain would clip per-bucket norms instead of the global
    one — pass ``clip_global_norm=`` for the coordinated clip.
    """

    def __init__(self, group_name: str, plan: BucketPlan, rank: int,
                 optimizer, params: Any, *, clip_global_norm:
                 Optional[float] = None, grad_scale: float = 1.0,
                 compression: Any = None):
        import jax

        from ray_tpu.collective.quant import ErrorFeedback, resolve_codec

        self.group_name = group_name
        self.plan = plan
        self.rank = rank
        self.optimizer = optimizer
        self.clip = clip_global_norm
        self.grad_scale = grad_scale
        # compression=None keeps BOTH legs on the PR 12 fp32 path
        # (bit-identical collective sequence; regression-asserted); a codec
        # quantizes the grad reduce (inside the reducer, with error
        # feedback) AND the param-refresh broadcast — which then ships the
        # quantized param DELTA (new - old) so precision loss is bounded
        # by one step's update and error-fed into the next broadcast.
        self.codec = resolve_codec(compression)
        self._bcast_ef = ErrorFeedback(self.codec) if self.codec else None
        flat, self._treedef = jax.tree_util.tree_flatten_with_path(params)
        self._paths = [jax.tree_util.keystr(k) for k, _ in flat]
        self._leaf_idx = {p: i for i, p in enumerate(self._paths)}
        self._by_path = {p: np.asarray(v) for p, v in
                         zip(self._paths, (v for _, v in flat))}
        self.opt_state = {
            b.index: optimizer.init(self._subtree(b))
            for b in plan.owned(rank)
        }
        self._reducer = AsyncBucketReducer(group_name, plan,
                                           compression=compression)

    def _subtree(self, bucket: Bucket) -> Dict[str, np.ndarray]:
        return {p: self._by_path[p] for p in bucket.paths}

    def opt_state_bytes(self) -> int:
        import jax

        return sum(np.asarray(leaf).nbytes
                   for state in self.opt_state.values()
                   for leaf in jax.tree_util.tree_leaves(state))

    def step(self, grads: Any) -> Tuple[Any, Dict[str, Any]]:
        """One sharded update. ``grads`` is this rank's LOCAL grad tree
        (summed across ranks by the reducer; pre-scale with
        ``grad_scale``, e.g. 1/world for a mean). Returns the updated
        full param tree (identical on every rank) + stats."""
        import jax
        from ray_tpu import collective as col

        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        gmap = {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat}
        if set(gmap) != set(self._paths):
            raise ValueError("grad tree does not match the param tree the "
                             "sharded optimizer was built over")
        t0 = time.perf_counter()
        handles = [self._reducer.submit(b, {p: gmap[p] for p in b.paths})
                   for b in self.plan.buckets]
        reduced: Dict[int, Dict[str, np.ndarray]] = {}
        for h in handles:
            res = h.result()
            if self.grad_scale != 1.0:
                res = {p: a * np.asarray(self.grad_scale, a.dtype)
                       for p, a in res.items()}
            reduced[h.bucket.index] = res
        allreduce_s = time.perf_counter() - t0
        scale = np.float32(1.0)
        gnorm = None
        if self.clip is not None:
            # shard-local per-leaf sqnorms -> allgather -> fold in global
            # leaf order (every rank computes the same factor bitwise)
            local = np.zeros(len(self._paths), np.float32)
            for b in self.plan.owned(self.rank):
                for p in b.paths:
                    a = reduced[b.index][p].astype(np.float32, copy=False)
                    local[self._leaf_idx[p]] = np.sum(np.square(a))
            gathered = np.asarray(col.allgather(
                local, group_name=f"{self.group_name}.norm"))
            per_leaf = gathered.sum(axis=0)  # disjoint -> sum recovers all
            acc = np.float32(0.0)
            for v in per_leaf:
                acc = np.float32(acc + np.float32(v))
            gnorm = np.float32(np.sqrt(acc))
            scale = np.float32(self.clip / max(float(gnorm), self.clip))
        import optax

        t1 = time.perf_counter()
        owned = {b.index: b for b in self.plan.owned(self.rank)}
        updated: Dict[str, np.ndarray] = {}
        for idx, bucket in owned.items():
            g = {p: (reduced[idx][p] * scale).astype(reduced[idx][p].dtype)
                 for p in bucket.paths}
            p_sub = self._subtree(bucket)
            upd, self.opt_state[idx] = self.optimizer.update(
                g, self.opt_state[idx], p_sub)
            new = optax.apply_updates(p_sub, upd)
            updated.update(new)
        optimizer_s = time.perf_counter() - t1
        # broadcast refreshed buckets from their owners (deterministic
        # bucket order on every rank)
        t2 = time.perf_counter()
        bcast_wire = bcast_fp32 = 0
        for b in self.plan.buckets:
            if self.codec is not None:
                up, down = self._broadcast_bucket_quantized(b, updated)
                bcast_wire += up + down
                bcast_fp32 += b.nbytes
                continue
            packed = _pack({p: (updated[p] if b.owner == self.rank
                                else self._by_path[p])
                            for p in b.paths})
            out = []
            for dtype, flatv, layout in packed:
                res = np.asarray(col.broadcast(
                    flatv, src_rank=b.owner,
                    group_name=f"{self.group_name}.norm"))
                out.append((dtype, res, layout))
            for p, a in _unpack(out).items():
                self._by_path[p] = a
        broadcast_s = time.perf_counter() - t2
        leaves = [self._by_path[p] for p in self._paths]
        tree = jax.tree_util.tree_unflatten(self._treedef, leaves)
        stats = {
            "allreduce_s": allreduce_s,
            "optimizer_s": optimizer_s,
            "broadcast_s": broadcast_s,
            "grad_norm": None if gnorm is None else float(gnorm),
            "clip_scale": float(scale),
            "opt_state_bytes": self.opt_state_bytes(),
            "owned_buckets": sorted(owned),
        }
        if self.codec is not None:
            stats["compression"] = self.codec.name
            stats["broadcast_wire_bytes"] = bcast_wire
            stats["broadcast_fp32_bytes"] = bcast_fp32
            stats["reduce_wire"] = self._reducer.wire_stats()
        return tree, stats

    def _broadcast_bucket_quantized(self, bucket: Bucket,
                                    updated: Dict[str, np.ndarray]
                                    ) -> Tuple[int, int]:
        """The compressed param-refresh leg: the owner ships the quantized
        param DELTA of its bucket (with error feedback), every rank —
        owner included — applies ``base + dequant(delta)`` to its local
        copy, so ranks stay bitwise identical while the wire carries
        ~1 byte/element. The owner's exact-vs-broadcast difference is the
        EF residual, folded into the next step's delta."""
        from ray_tpu import collective as col
        from ray_tpu.collective import quant

        group = col.get_group(f"{self.group_name}.norm")
        # quantized deltas only make sense for float leaves — an int32
        # counter whose +1 delta dequantizes to 0.98 would truncate back
        # to base and never advance; non-float leaves ship their raw
        # updated values (same guard as the reduce leg's _pack dispatch)
        float_paths = [p for p in bucket.paths
                       if np.issubdtype(self._by_path[p].dtype,
                                        np.floating)]
        fset = set(float_paths)
        raw_paths = [p for p in bucket.paths if p not in fset]
        payload = None
        enc_s = 0.0
        if bucket.owner == self.rank:
            t0 = time.perf_counter()
            deltas = {p: updated[p].astype(np.float32)
                      - self._by_path[p].astype(np.float32)
                      for p in float_paths}
            items = []
            for dtype, flatv, layout in _pack(deltas):
                qt = self._bcast_ef.encode(("bcast", bucket.index,
                                            str(dtype)), flatv)
                items.append((str(dtype), quant.to_wire(qt), layout))
            enc_s += time.perf_counter() - t0
            payload = (items, {p: updated[p] for p in raw_paths})
        items, raw = group.broadcast_obj(payload, src_rank=bucket.owner)
        t1 = time.perf_counter()
        up = down = 0
        for dtype, wire, layout in items:
            nb = quant.wire_nbytes(wire)
            down += nb
            if bucket.owner == self.rank:
                up += nb
            delta = quant.dequantize(quant.from_wire(wire))
            off = 0
            for p, shape in layout:
                n = int(np.prod(shape, dtype=np.int64)) if shape else 1
                base = self._by_path[p]
                self._by_path[p] = (
                    base.astype(np.float32)
                    + delta[off:off + n].reshape(shape)
                ).astype(base.dtype)
                off += n
        for p, val in raw.items():
            nb = int(np.asarray(val).nbytes)
            down += nb
            if bucket.owner == self.rank:
                up += nb
            self._by_path[p] = np.asarray(val)
        obs = _obs()
        # encode/decode CPU time only — the broadcast rendezvous itself is
        # excluded (matches the metric description and _reduce_quantized)
        obs["quant_encode"].observe(enc_s + time.perf_counter() - t1)
        # uncompressed equivalent: float leaves would ship 4 B/el; raw
        # leaves ship at their actual size either way (no savings there)
        fp32 = sum(int(np.prod(self._by_path[p].shape, dtype=np.int64)) * 4
                   for p in float_paths)
        fp32 += sum(int(self._by_path[p].nbytes) for p in raw_paths)
        obs["quant_saved"].inc(max(fp32 - down, 0))
        return up, down

    def shutdown(self):
        self._reducer.shutdown()
