"""Collective group backends.

Reference: python/ray/util/collective/collective_group/ — ``NCCLGroup``
(nccl_collective_group.py:121) with named-actor rendezvous (:29) and the
torch-gloo CPU group. TPU-native replacements:

- ``CpuStoreGroup``: CI tier. A named store actor rendezvouses contributions
  per op sequence number and computes the reduction; correctness-focused,
  hardware-free (the analog of the reference's gloo tier + CPUCommunicator).
- ``XlaGroup``: device tier. Ops execute as jitted ``shard_map`` collectives
  (psum / all_gather / psum_scatter / ppermute) over a 1-D device mesh. In
  multi-host SPMD (bootstrapped via jax.distributed) the same program lowers
  to ICI/DCN collectives; single-process it uses the local device mesh.
"""

from __future__ import annotations

import time
from typing import Any, List, Optional

import numpy as np

from ray_tpu._private import wire
from ray_tpu.collective.types import ReduceOp

_STORE_PREFIX = "rtpu_collective_store:"


def _reduce_np(arrays: List[np.ndarray], op: ReduceOp) -> np.ndarray:
    stack = np.stack([np.asarray(a) for a in arrays])
    if op == ReduceOp.SUM:
        return stack.sum(axis=0)
    if op == ReduceOp.PRODUCT:
        return np.prod(stack, axis=0)
    if op == ReduceOp.MIN:
        return stack.min(axis=0)
    if op == ReduceOp.MAX:
        return stack.max(axis=0)
    if op == ReduceOp.AVERAGE:
        return stack.mean(axis=0)
    raise ValueError(op)


class CollectiveStore:
    """Named async actor used by the CPU backend for rendezvous + reduction.

    Reference analog: the Rendezvous named actor in
    nccl_collective_group.py:29 (unique-id exchange) — generalized here to
    carry the data plane too, since there is no NCCL under the CPU tier.
    """

    def __init__(self, world_size: int):
        self.world_size = world_size
        self._contrib = {}
        self._results = {}
        self._p2p = {}
        self._events = {}  # key -> asyncio.Event (result ready)
        self._p2p_events = {}

    def _event(self, table: dict, key: str):
        import asyncio

        ev = table.get(key)
        if ev is None:
            ev = table[key] = asyncio.Event()
        return ev

    async def collect(self, key: str, rank: int, payload, op_name: Optional[str]):
        import asyncio

        slot = self._contrib.setdefault(key, {})
        slot[rank] = payload
        ev = self._event(self._events, key)
        if len(slot) == self.world_size and key not in self._results:
            ordered = [slot[r] for r in range(self.world_size)]
            if op_name is None:
                self._results[key] = ordered  # allgather
            elif op_name.startswith("qsum:"):
                # quantized allreduce reduce point: dequant-accumulate the
                # uint8+scales contributions in fp32, re-quantize ONCE for
                # the broadcast leg (collective/quant.py) — wire bytes are
                # quantized in BOTH directions
                from ray_tpu.collective import quant

                self._results[key] = quant.reduce_wire_payloads(
                    ordered, op_name[len("qsum:"):])
            else:
                self._results[key] = _reduce_np(ordered, ReduceOp(op_name))
            ev.set()  # wake every parked member — no polling
        if key not in self._results:
            try:
                await asyncio.wait_for(ev.wait(), 300.0)
            except asyncio.TimeoutError:
                raise TimeoutError(f"collective {key} timed out "
                                   f"({len(slot)}/{self.world_size} arrived)")
        result = self._results[key]
        # last leaver cleans up
        slot[f"done{rank}"] = True
        if sum(1 for k in slot if isinstance(k, str)) == self.world_size:
            self._contrib.pop(key, None)
            self._events.pop(key, None)
            res = self._results.pop(key)
            return res
        return result

    async def put_p2p(self, key: str, payload):
        self._p2p[key] = payload
        self._event(self._p2p_events, key).set()
        return True

    async def del_p2p(self, key: str):
        self._p2p.pop(key, None)
        return True

    async def _wait_p2p(self, key: str, timeout: float, consume: bool):
        import asyncio

        deadline = time.monotonic() + timeout
        while key not in self._p2p:
            ev = self._event(self._p2p_events, key)
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(f"p2p {key} timed out")
            try:
                await asyncio.wait_for(ev.wait(), remaining)
            except asyncio.TimeoutError:
                raise TimeoutError(f"p2p {key} timed out")
        if consume:
            self._p2p_events.pop(key, None)
            return self._p2p.pop(key)
        return self._p2p[key]

    async def peek(self, key: str, timeout: float = 300.0):
        """Non-consuming wait (rendezvous metadata, e.g. rank addresses)."""
        return await self._wait_p2p(key, timeout, consume=False)

    async def get_p2p(self, key: str, timeout: float = 300.0):
        return await self._wait_p2p(key, timeout, consume=True)


class CpuStoreGroup:
    def __init__(self, group_name: str, world_size: int, rank: int):
        import ray_tpu

        self.group_name = group_name
        self.world_size = world_size
        self.rank = rank
        self._seq = 0
        store_cls = ray_tpu.remote(CollectiveStore)
        self.store = store_cls.options(
            name=_STORE_PREFIX + group_name,
            max_concurrency=max(world_size * 2, 8),
            lifetime="detached",
            get_if_exists=True,
            num_cpus=0.1,
        ).remote(world_size)

    def _next_key(self, kind: str) -> str:
        self._seq += 1
        return f"{kind}:{self._seq}"

    def _sync(self, ref):
        import ray_tpu

        return ray_tpu.get(ref, timeout=600)

    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM):
        key = self._next_key("ar")
        out = self._sync(self.store.collect.remote(key, self.rank, np.asarray(tensor), op.value))
        return out

    def allreduce_quantized(self, wire: dict, codec) -> dict:
        """Quantized-SUM allreduce: ``wire`` is this rank's encoded
        contribution (``quant.to_wire``); the store dequant-accumulates in
        fp32 and re-quantizes once, so both wire legs carry
        ``codec.bytes_per_element`` per element instead of 4. Returns the
        encoded sum (decode with ``quant.from_wire`` + ``dequantize``)."""
        key = self._next_key("qar")
        return self._sync(self.store.collect.remote(
            key, self.rank, wire, f"qsum:{codec.spec()}"))

    def broadcast_obj(self, payload, src_rank: int = 0):
        """One-to-all broadcast of an arbitrary payload where ONLY the
        source uploads bytes (plain ``broadcast`` collects a full tensor
        from every rank — pointless upload for N-1 of them). The
        compressed param-broadcast leg rides this."""
        key = self._next_key("bco")
        gathered = self._sync(self.store.collect.remote(
            key, self.rank, payload if self.rank == src_rank else None,
            None))
        return gathered[src_rank]

    def allgather(self, tensor):
        key = self._next_key("ag")
        return self._sync(self.store.collect.remote(key, self.rank, np.asarray(tensor), None))

    def reduce(self, tensor, dst_rank: int = 0, op: ReduceOp = ReduceOp.SUM):
        out = self.allreduce(tensor, op)
        return out if self.rank == dst_rank else np.asarray(tensor)

    def broadcast(self, tensor, src_rank: int = 0):
        key = self._next_key("bc")
        gathered = self._sync(self.store.collect.remote(key, self.rank, np.asarray(tensor), None))
        return gathered[src_rank]

    def reducescatter(self, tensor, op: ReduceOp = ReduceOp.SUM):
        reduced = self.allreduce(tensor, op)
        chunks = np.array_split(reduced, self.world_size, axis=0)
        return chunks[self.rank]

    def alltoall(self, tensor):
        """Each rank contributes world_size chunks along axis 0."""
        key = self._next_key("a2a")
        gathered = self._sync(self.store.collect.remote(key, self.rank, np.asarray(tensor), None))
        mine = [np.array_split(g, self.world_size, axis=0)[self.rank] for g in gathered]
        return np.concatenate(mine, axis=0)

    def send(self, tensor, dst_rank: int, tag: int = 0):
        self._sync(self.store.put_p2p.remote(
            f"p2p:{self.rank}:{dst_rank}:{tag}", np.asarray(tensor)))

    def recv(self, src_rank: int, tag: int = 0):
        return self._sync(self.store.get_p2p.remote(f"p2p:{src_rank}:{self.rank}:{tag}"))

    def barrier(self):
        self.allreduce(np.zeros(1, dtype=np.float32))

    def destroy(self):
        pass


class XlaGroup:
    """Collectives lowered to XLA over the device mesh.

    Each op jit-compiles a shard_map program over a 1-D mesh named ``ici``;
    under multi-controller SPMD every group member executes the same program
    and XLA emits ICI (intra-slice) / DCN (cross-slice) collectives. The
    value each member passes in is its per-device-sharded contribution.
    """

    def __init__(self, group_name: str, world_size: int, rank: int,
                 devices: Optional[list] = None):
        from ray_tpu.utils import import_jax

        jax = import_jax()

        self.group_name = group_name
        self.world_size = world_size
        self.rank = rank
        devs = devices if devices is not None else jax.devices()
        if len(devs) % 1 != 0 or not devs:
            raise ValueError("no devices for XlaGroup")
        self._jax = jax
        from jax.sharding import Mesh

        self.mesh = Mesh(np.array(devs), ("ici",))
        self._cache = {}

    def _shmap(self, fn, in_spec, out_spec):
        import jax
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        return jax.jit(shard_map(
            fn, mesh=self.mesh, in_specs=in_spec, out_specs=out_spec,
            check_vma=False))

    def _op(self, name, builder):
        fn = self._cache.get(name)
        if fn is None:
            fn = builder()
            self._cache[name] = fn
        return fn

    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def build():
            def f(x):
                if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
                    y = jax.lax.psum(x, "ici")
                    if op == ReduceOp.AVERAGE:
                        y = y / self.mesh.size
                elif op == ReduceOp.MAX:
                    y = jax.lax.pmax(x, "ici")
                elif op == ReduceOp.MIN:
                    y = jax.lax.pmin(x, "ici")
                else:
                    raise ValueError(op)
                return y

            return self._shmap(f, P("ici"), P("ici"))

        x = jnp.asarray(tensor)
        return self._op(f"ar_{op}_{x.shape}_{x.dtype}", build)(x)

    def allgather(self, tensor):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def build():
            def f(x):
                return jax.lax.all_gather(x, "ici", axis=0, tiled=True)

            return self._shmap(f, P("ici"), P("ici"))

        x = jnp.asarray(tensor)
        return self._op(f"ag_{x.shape}_{x.dtype}", build)(x)

    def reducescatter(self, tensor, op: ReduceOp = ReduceOp.SUM):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def build():
            def f(x):
                # same convention as every sibling op: the member's axis-0
                # chunk IS its contribution (shape t); it receives its
                # piece of the reduced chunk (shape t/world), so the
                # assembled output is (t,) with member i's piece at [i]
                return jax.lax.psum_scatter(x, "ici", scatter_dimension=0, tiled=True)

            return self._shmap(f, P("ici"), P("ici"))

        if op != ReduceOp.SUM:
            raise ValueError(
                f"XlaGroup.reducescatter supports SUM only (psum_scatter); "
                f"got {op}")
        x = jnp.asarray(tensor)
        if x.shape[0] % (self.mesh.size ** 2) != 0:
            raise ValueError(
                f"reducescatter input axis 0 ({x.shape[0]}) must be "
                f"divisible by devices^2 ({self.mesh.size ** 2}): axis 0 "
                f"splits into per-member chunks, each scattered again")
        return self._op(f"rs_{x.shape}_{x.dtype}", build)(x)

    def alltoall(self, tensor):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def build():
            def f(x):
                return jax.lax.all_to_all(x, "ici", split_axis=0, concat_axis=0,
                                          tiled=True)

            return self._shmap(f, P("ici"), P("ici"))

        x = jnp.asarray(tensor)
        return self._op(f"a2a_{x.shape}_{x.dtype}", build)(x)

    def allreduce_quantized(self, wire: dict, codec) -> dict:
        raise NotImplementedError(
            "the XLA backend has no quantized exchange: its collectives "
            "are compiled programs in the tensors' own dtype; the "
            "explicit store-actor exchange is the CPU backend's "
            "(grad_sync_backend=\"cpu\")")

    def broadcast(self, tensor, src_rank: int = 0):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def build():
            def f(x):
                # mask non-source shards then sum: a broadcast on a mesh
                idx = jax.lax.axis_index("ici")
                masked = jnp.where(idx == src_rank, x, jnp.zeros_like(x))
                return jax.lax.psum(masked, "ici")

            return self._shmap(f, P("ici"), P("ici"))

        x = jnp.asarray(tensor)
        return self._op(f"bc_{src_rank}_{x.shape}_{x.dtype}", build)(x)

    def ppermute(self, tensor, perm):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        perm = tuple(tuple(p) for p in perm)

        def build():
            def f(x):
                return jax.lax.ppermute(x, "ici", perm=perm)

            return self._shmap(f, P("ici"), P("ici"))

        x = jnp.asarray(tensor)
        return self._op(f"pp_{hash(perm)}_{x.shape}_{x.dtype}", build)(x)

    def barrier(self):
        import jax.numpy as jnp

        self.allreduce(jnp.zeros((self.mesh.size,), jnp.float32)).block_until_ready()

    # -- eager p2p via device objects (reference: the accelerator channel
    # tier, torch_tensor_accelerator_channel.py). ICI p2p only exists
    # inside compiled programs (ppermute above); the EAGER tier keeps the
    # tensor resident in the sender's device store and the receiver pulls
    # it directly from the sender's worker — no store hop, no driver hop.

    def _p2p_state(self):
        if getattr(self, "_p2p", None) is None:
            import ray_tpu

            store_cls = ray_tpu.remote(CollectiveStore)
            store = store_cls.options(
                name=_STORE_PREFIX + self.group_name,
                max_concurrency=max(self.world_size * 2, 8),
                lifetime="detached", get_if_exists=True,
                num_cpus=0.1).remote(self.world_size)
            w = ray_tpu._private.worker.global_worker()
            ray_tpu.get(store.put_p2p.remote(
                f"addr:{self.group_name}:{self.rank}", w.address), timeout=60)
            self._p2p = {"store": store, "worker": w,
                         "send_seq": {}, "recv_seq": {}, "addrs": {}}
        return self._p2p

    def _p2p_key(self, src: int, dst: int, tag: int, seq: int) -> bytes:
        import hashlib

        return hashlib.blake2b(
            f"xla_p2p:{self.group_name}:{src}:{dst}:{tag}:{seq}".encode(),
            digest_size=16).digest()

    _P2P_WINDOW = 8  # bounded in-flight sends per (dst, tag)

    def send(self, tensor, dst_rank: int, tag: int = 0,
             timeout: float = 300.0):
        import time as _time

        import jax.numpy as jnp

        import ray_tpu

        st = self._p2p_state()
        k = (dst_rank, tag)
        st["send_seq"][k] = seq = st["send_seq"].get(k, 0) + 1
        key = self._p2p_key(self.rank, dst_rank, tag, seq)
        # backpressure: the receiver frees each slot as it consumes it —
        # block while the message WINDOW sends back is still unconsumed
        old_key = (self._p2p_key(self.rank, dst_rank, tag,
                                 seq - self._P2P_WINDOW)
                   if seq > self._P2P_WINDOW else None)
        deadline = _time.monotonic() + timeout
        while old_key is not None and old_key in st["worker"].device_store:
            if _time.monotonic() > deadline:
                raise TimeoutError(
                    f"send window to rank {dst_rank} full for {timeout}s")
            _time.sleep(0.002)
        # stays device-resident here until the receiver pulls + frees it
        st["worker"].device_store[key] = jnp.asarray(tensor)
        st.setdefault("sent_keys", set()).add(key)
        # rendezvous flag: the receiver blocks on this instead of hammering
        # our worker with GetDeviceObject polls
        ray_tpu.get(st["store"].put_p2p.remote(key.hex(), True),
                    timeout=timeout)

    def recv(self, src_rank: int, tag: int = 0, timeout: float = 300.0):
        import pickle as _pickle

        import jax.numpy as jnp

        import ray_tpu
        from ray_tpu._private.object_store import read_blob
        from ray_tpu._private.serialization import deserialize

        st = self._p2p_state()
        addr = st["addrs"].get(src_rank)
        if addr is None:
            addr = ray_tpu.get(st["store"].peek.remote(
                f"addr:{self.group_name}:{src_rank}"), timeout=timeout)
            st["addrs"][src_rank] = addr
        k = (src_rank, tag)
        st["recv_seq"][k] = seq = st["recv_seq"].get(k, 0) + 1
        key = self._p2p_key(src_rank, self.rank, tag, seq)
        # wait for the sender's ready flag (one blocking store call),
        # then pull the tensor with a single direct worker RPC
        ray_tpu.get(st["store"].get_p2p.remote(key.hex(), timeout),
                    timeout=timeout + 10)
        w = st["worker"]
        client = w._worker_client(addr)
        reply = wire.loads(w._run(client.call(
            "GetDeviceObject", wire.dumps({"oid": key}),
            timeout=60.0, retries=1), 70.0))
        if reply["status"] != "ok":
            raise RuntimeError(
                f"p2p message from rank {src_rank} tag {tag} vanished "
                f"(sender restarted?)")
        # consume-once: release the sender's device-store slot
        w._run(client.call("FreeDeviceObject",
                           wire.dumps({"oid": key}), timeout=10.0,
                           retries=1), 20.0)
        inband, buffers = read_blob(reply["blob"])
        return jnp.asarray(deserialize(inband, buffers))

    def destroy(self):
        self._cache.clear()
        st = getattr(self, "_p2p", None)
        if st is not None:
            import ray_tpu

            # unconsumed sends would otherwise pin device memory for the
            # worker's lifetime; the store's addr key must go too or a
            # re-created group would peek a stale address
            for key in st.get("sent_keys", ()):
                st["worker"].device_store.pop(key, None)
            try:
                ray_tpu.get(st["store"].del_p2p.remote(
                    f"addr:{self.group_name}:{self.rank}"), timeout=10)
            except Exception:
                pass
            self._p2p = None
