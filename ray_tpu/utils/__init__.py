"""Framework utilities (jax bootstrap, timing, tree helpers)."""

from ray_tpu.utils.jaxtools import (compile_cache_dir, compile_cache_entries,
                                    device_facts, import_jax)

__all__ = ["compile_cache_dir", "compile_cache_entries", "device_facts",
           "import_jax", "is_tpu", "lower_for"]

# the platform this process lowers programs for, where that is not the one
# it runs on
_LOWERS_FOR = None


def lower_for(platform: str) -> None:
    """This process lowers programs for ``platform`` and runs them nowhere
    (``llm/prefill_shapes.py:export_job``, held to the CPU, for the serving
    process's chip): what is chosen at trace time by ``is_tpu`` is chosen as
    that platform would."""
    global _LOWERS_FOR
    _LOWERS_FOR = platform


def is_tpu() -> bool:
    """True when programs are for the TPU: jax's default backend, or the
    platform ``lower_for`` named. Single source of truth for bench + kernel
    dispatch."""
    return (_LOWERS_FOR or import_jax().default_backend()) == "tpu"
