"""Compiled ICI edge tier for compiled graphs.

Reference: python/ray/experimental/channel/torch_tensor_accelerator_channel.py
— the reference moves GPU tensors between pipeline stages over NCCL
send/recv instead of the host channel plane. The TPU-native equivalent: an
edge annotated ``.with_tensor_transport("ici")`` lowers to ONE jitted
``shard_map`` ``lax.ppermute`` step over the stage actor's device mesh — the
microbatch hand-off rides the ICI interconnect inside the compiled program;
no serialization, no shm slot, no RPC. On a multi-host slice the same
program lowers to inter-chip collectives under multi-controller SPMD (the
Train worker-group bootstrap); in CI it runs on the virtual 8-device CPU
mesh.
"""

from __future__ import annotations

from typing import Optional

_COMPILE_COUNTS: dict = {}  # transfer key -> times the jit was BUILT (tests)
_CALL_COUNTS: dict = {}  # transfer key -> times the compiled step ran


class IciTransfer:
    """One compiled mesh-shift step: shard i's value moves to shard
    (i + shift) % world. Built once per (mesh, shift); every call after the
    first reuses the compiled executable."""

    def __init__(self, mesh=None, shift: int = 1, axis: str = "ici"):
        from ray_tpu.utils import import_jax

        jax = import_jax()
        if mesh is None:
            import numpy as np
            from jax.sharding import Mesh

            mesh = Mesh(np.array(jax.devices()), (axis,))
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.shift = shift
        n = mesh.devices.size
        perm = [(i, (i + shift) % n) for i in range(n)]
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        spec = P(self.axis)
        axis = self.axis

        def _step(x):
            from jax import lax

            return lax.ppermute(x, axis, perm)

        self._fn = jax.jit(shard_map(
            _step, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
        self.key = (id(mesh), shift)
        _COMPILE_COUNTS[self.key] = _COMPILE_COUNTS.get(self.key, 0) + 1

    def __call__(self, x):
        _CALL_COUNTS[self.key] = _CALL_COUNTS.get(self.key, 0) + 1
        from ray_tpu.utils import import_jax

        jax = import_jax()
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        if not isinstance(x, jax.Array):
            x = jax.device_put(
                x, NamedSharding(self.mesh, P(self.axis)))
        return self._fn(x)


def get_transfer(instance, shift: int = 1) -> IciTransfer:
    """Per-actor cached transfer; the mesh comes from the actor's ``mesh``
    attribute (the slice mesh a stage actor already owns) or defaults to a
    1-D mesh over all visible devices."""
    cache = getattr(instance, "__rtpu_ici_transfers__", None)
    if cache is None:
        cache = {}
        try:
            instance.__rtpu_ici_transfers__ = cache
        except AttributeError:  # raylint: disable=EXC001 slots-only actor class; fall back to uncached transfers
            pass
    t = cache.get(shift)
    if t is None:
        t = IciTransfer(mesh=getattr(instance, "mesh", None), shift=shift)
        cache[shift] = t
    return t


def transfer_stats() -> dict:
    return {"compiles": dict(_COMPILE_COUNTS), "calls": dict(_CALL_COUNTS)}
