"""Framework utilities (jax bootstrap, timing, tree helpers)."""

from ray_tpu.utils.jaxtools import (compile_cache_dir, compile_cache_entries,
                                    device_facts, import_jax)

__all__ = ["compile_cache_dir", "compile_cache_entries", "device_facts",
           "import_jax", "is_tpu"]


def is_tpu() -> bool:
    """True when jax's default backend is the TPU. Single source of truth
    for bench + kernel dispatch."""
    return import_jax().default_backend() == "tpu"
