"""Where the program's compile cache lives and what it keeps
(``ray_tpu/utils/jaxtools.py``): set from outside, the environment wins."""

import pytest

from ray_tpu.utils import jaxtools

DIR, MIN_SECS = ("JAX_COMPILATION_CACHE_DIR",
                 "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")


@pytest.mark.parametrize("outside,want", [
    ({}, {DIR: jaxtools.DEFAULT_COMPILE_CACHE_DIR, MIN_SECS: "0"}),
    ({DIR: "/somewhere/else", MIN_SECS: "1.5"},
     {DIR: "/somewhere/else", MIN_SECS: "1.5"}),
], ids=["unset", "set-from-outside"])
def test_cache_placement_and_threshold_are_exported(monkeypatch, outside,
                                                    want):
    """Unset, the cache sits in the checkout and keeps every program however
    short its compile (a warm start then compiles nothing again); children
    inherit both through ``os.environ``."""
    import os

    for key in (DIR, MIN_SECS):
        monkeypatch.delenv(key, raising=False)
    for key, value in outside.items():
        monkeypatch.setenv(key, value)
    assert jaxtools.compile_cache_dir() == want[DIR]
    assert {k: os.environ[k] for k in want} == want
