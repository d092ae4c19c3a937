"""Central jax import, and the one place the compile cache is placed.

Every process of the program — the driver, workers forked from the zygote,
cold-spawned workers, serve replicas — keeps its persistent XLA compile
cache in the same directory: ``JAX_COMPILATION_CACHE_DIR`` where that is
set from outside, otherwise ``.jax_cache/`` at the root of the checkout
(ignored by git). The path is part of the cache key, so it is never a
temporary name, a pid or a time. The choice is exported into
``os.environ`` when ``ray_tpu`` is first imported: jax reads the variable
at import, and the raylet's spawn environment hands it to every child.

With it goes jax's threshold for what is worth keeping, lowered from a
second of compile time to none (unless set from outside): a process that
starts with a warm cache otherwise compiles every small program again, and
a serve replica's eager weight init alone is over a hundred of them, 13 s
of a 38 s start on a v5e (PERF.md).
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def compile_cache_dir() -> str:
    """The directory this process (and its children) cache compiles in."""
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 DEFAULT_COMPILE_CACHE_DIR)


def compile_cache_entries() -> int:
    """Number of cached executables in the compile cache directory."""
    try:
        return sum(1 for n in os.listdir(compile_cache_dir())
                   if n.endswith("-cache"))
    except FileNotFoundError:
        return 0


def device_facts() -> dict:
    """The devices as THIS process sees them, device 0's memory high-water
    mark, and this process's compile cache. Only a process that may hold the
    chip should ask: the call initialises the backend."""
    jax = import_jax()
    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    return {"pid": os.getpid(),
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)},
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
            "compile_cache_dir": compile_cache_dir(),
            "compile_cache_entries": compile_cache_entries()}


def import_jax():
    cache = compile_cache_dir()
    import jax

    if jax.config.jax_compilation_cache_dir is None:
        # jax was imported before ray_tpu exported the variables
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))
    return jax
