"""The one g++ build-on-first-use loader for the native components (arena
store, data loader).

The cached library is keyed by CONTENT: ``build/<name>-<digest>.so`` where
the digest covers the source bytes and the compiler flags. A copy of the
tree does not keep mtimes in order, so a library is never reused because it
merely looks newer than its source — it is reused only when it was built
from exactly this source. ``build/`` is ignored by git; nothing in it is
ever needed from a checkout. A failed build is logged as an error with the
compiler's output and returns None, so callers can take their Python
fallbacks in the open."""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional, Sequence

logger = logging.getLogger("ray_tpu.native_build")

_BASE_FLAGS = ("-O2", "-shared", "-fPIC", "-std=c++17")
_lock = threading.Lock()
_cache: dict = {}


def keyed_lib_path(src: str, lib_path: str,
                   extra_flags: Sequence[str] = ()) -> str:
    """``lib_path`` with the digest of (source content, flags) in its name."""
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update("\0".join((*_BASE_FLAGS, *extra_flags)).encode())
    root, ext = os.path.splitext(lib_path)
    return f"{root}-{h.hexdigest()[:16]}{ext}"


def _build(src: str, out: str, extra_flags: Sequence[str]) -> bool:
    os.makedirs(os.path.dirname(out), exist_ok=True)
    tmp = f"{out}.tmp{os.getpid()}"
    cmd = ["g++", *_BASE_FLAGS, *extra_flags, src, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=180)
        os.replace(tmp, out)
        return True
    except subprocess.CalledProcessError as e:
        detail = e.stderr.decode(errors="replace")[-2000:]
    except (subprocess.TimeoutExpired, OSError) as e:
        detail = repr(e)
    logger.error("native build FAILED: %s\n%s", " ".join(cmd), detail)
    return False


def build_and_load(src: str, lib_path: str,
                   extra_flags: Sequence[str] = ()) -> Optional[ctypes.CDLL]:
    with _lock:
        try:
            out = keyed_lib_path(src, lib_path, extra_flags)
        except OSError as e:
            logger.error("native source %s unreadable: %r", src, e)
            return None
        if out in _cache:
            return _cache[out]
        lib = None
        if os.path.exists(out) or _build(src, out, extra_flags):
            try:
                lib = ctypes.CDLL(out)
            except OSError as e:
                # corrupt or wrong-arch artifact: rebuild once
                logger.warning("cached %s does not load (%s); rebuilding",
                               out, e)
                if _build(src, out, extra_flags):
                    lib = ctypes.CDLL(out)
        if lib is not None:
            # libraries of other source versions are dead weight
            root, ext = os.path.splitext(lib_path)
            for stale in glob.glob(f"{root}-*{ext}"):
                if stale != out:
                    try:
                        os.unlink(stale)
                    except OSError:  # raylint: disable=EXC001 a concurrent loader may have removed it already
                        pass
        _cache[out] = lib
        return lib
