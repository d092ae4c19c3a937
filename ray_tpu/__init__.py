"""ray_tpu: a TPU-native distributed computing framework.

Tasks, actors, and a distributed object store over a TPU-topology-aware
scheduler, with collective communication lowering to XLA collectives over
ICI/DCN, plus data / train / tune / serve / RL libraries built on top.

This module intentionally does NOT import jax: the core runtime stays
lightweight so worker processes start fast; accelerator code paths
(models/ops/parallel/train) import jax lazily.
"""

from ray_tpu.utils.jaxtools import compile_cache_dir as _compile_cache_dir

# the program's one compile-cache placement, exported before anything can
# import jax so this process and every child it spawns agree on it
_compile_cache_dir()

from ray_tpu._private.worker import (
    available_resources,
    cancel,
    cluster_resources,
    get,
    get_actor,
    init,
    is_initialized,
    kill,
    nodes,
    put,
    remote,
    shutdown,
    wait,
)
from ray_tpu.actor import method
from ray_tpu.object_ref import ObjectRef
from ray_tpu.runtime_context import get_runtime_context
from ray_tpu import exceptions

__version__ = "0.1.0"

__all__ = [
    "init",
    "shutdown",
    "is_initialized",
    "remote",
    "method",
    "get",
    "put",
    "wait",
    "kill",
    "cancel",
    "get_actor",
    "get_runtime_context",
    "cluster_resources",
    "available_resources",
    "nodes",
    "ObjectRef",
    "exceptions",
    "__version__",
]
