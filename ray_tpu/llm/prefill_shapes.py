"""The prefill programs of more than one row, made ready off the serving path.

The engine's one-row prefill call is compiled where jit compiles anything: at
a length bucket's first use, on the serving path, while that one request
waits. A call of two or four rows (``llm/engine.py:prefill_groups``) must not
be had that way: its first use would fall into the middle of serving, and
tracing a program of some dozen kernels holds the interpreter for seconds,
which the serving thread would feel from whichever thread it was done in. So
from a bucket's first use on:

- a process of its own traces the bucket's programs of several rows and hands
  them back serialized, one by one as each is traced (``jax.export``;
  ``export_job``, held to the CPU: it lowers for the serving process's
  platform, chooses what ``utils.is_tpu`` chooses as that platform would,
  and never opens a device). The serving process's interpreter is not held.
  One such process runs at a time: the buckets first used while it traces
  wait and share the next one, so a warm-up that walks through the buckets
  pays for two or three processes, not for one a bucket;
- threads of the serving process compile what comes back (the compiler runs
  outside the interpreter's lock, and reads and fills the persistent cache
  like any compile) into ``ready[(R, S)]``, an executable of (params, cache,
  *rows) under ``model_runner.prefill``'s XLA module name, and ``place[R]``,
  which puts ``R`` rows of logits by slot.

The engine forms a group only at a shape that is ready; until then its
requests go on as one-row calls. A shape that could not be made (the compiler
found no room for it on the device, say) is never ready, stands in ``failed``
with the reason, and is named in the log line that closes the making.
"""

from __future__ import annotations

import functools
import logging
import os
import pickle
import struct
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import export

from ray_tpu import utils
from ray_tpu._private.serialization import loads_trusted
from ray_tpu.llm import model_runner

logger = logging.getLogger(__name__)

# programs compiled at a time: a cold compile is 15-20 s of one core, and the
# cores are the serving path's too
_COMPILERS = 4
_FRAME = struct.Struct("<q")  # a program's bytes; negative: an error's


def _shapes(tree, placed=False):
    """``tree``'s shapes and types, and where ``placed`` its arrays'
    shardings too: a program lowered from those hands its outputs back
    committed, as jit does for the committed state the engine keeps."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if placed else None), tree)


def _ints(*shape):
    return jax.ShapeDtypeStruct(shape, np.int32)


class RowShapes:
    """One engine's programs of several rows. ``want`` is called from the
    serving path and returns at once; ``ready``, ``place`` and ``failed``
    fill from the threads it starts."""

    def __init__(self, cfg, params, cache, logits, pages_per_seq: int,
                 on_ready: Callable[[], None],
                 carries: Callable[[int, int], bool] = lambda R, S: False):
        self._cfg, self._on_ready, self._carries = cfg, on_ready, carries
        # shapes alone: the serving path goes on donating the arrays
        self._params, self._cache = _shapes(params), _shapes(cache, True)
        self._logits, self._mp = _shapes(logits, True), pages_per_seq
        self._platform = jax.default_backend()
        self._lock = threading.Lock()
        self._queue: List[Tuple[int, int]] = []  # wanted, no process yet
        self._maker: Optional[threading.Thread] = None
        self.wanted: List[Tuple[int, int]] = []
        self.ready: Dict[Tuple[int, int], Callable] = {}
        self.place: Dict[int, Callable] = {}
        self.failed: Dict[Tuple[int, int], str] = {}

    def _rows(self, R: int, S: int) -> tuple:
        """The arguments of ``prefill`` at ``[R, S]`` after the cache; a model
        that keeps state by slot is told the slots, and a shape that
        ``carries`` a decode step takes that step's operands (``riders``)."""
        told = (_ints(R),) if self._cfg.layer_kinds else ()
        if self._carries(R, S):
            B = self._logits.shape[0]
            told += ((_ints(B), _ints(B), _ints(B, self._mp),
                      jax.ShapeDtypeStruct((B,), np.bool_)),)
        return (_ints(R, S), _ints(R), _ints(R, self._mp), *told)

    def want(self, shapes: Sequence[Tuple[int, int]]) -> None:
        with self._lock:
            shapes = [s for s in shapes if s not in self.wanted]
            self.wanted += shapes
            self._queue += shapes
            if shapes and self._maker is None:
                self._maker = threading.Thread(
                    target=self._drain, name="prefill-shapes", daemon=True)
                self._maker.start()

    def _drain(self) -> None:
        """One exporting process after another, each for whatever was
        wanted by the time the one before it was done."""
        while True:
            with self._lock:
                shapes, self._queue = self._queue, []
                if not shapes:
                    self._maker = None
                    return
            self._make(shapes)

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Until every shape wanted so far is ready or has failed; False at
        ``timeout``. For tests: serving waits for no shape."""
        maker = self._maker
        if maker is not None:
            maker.join(timeout)
        return self._maker is None

    def _make(self, shapes: List[Tuple[int, int]]) -> None:
        t0 = time.monotonic()
        try:
            with ThreadPoolExecutor(_COMPILERS, "prefill-shapes") as pool:
                for shape, blob in zip(shapes, self._export(shapes)):
                    pool.submit(self._compile, shape, blob)
        except Exception as e:  # the exporting process: what it left undone
            logger.exception("prefill shapes: the exporting process failed")
            for shape in shapes:
                if shape not in self.ready:
                    self.failed.setdefault(shape, f"{type(e).__name__}: {e}")
        bad = {s: self.failed[s] for s in shapes if s in self.failed}
        logger.log(logging.WARNING if bad else logging.INFO,
                   "prefill shapes: %d of %d ready after %.1f s%s",
                   len(shapes) - len(bad), len(shapes), time.monotonic() - t0,
                   "".join(f"; {list(s)} not made: {why[:300]}"
                           for s, why in bad.items()))

    def _compile(self, shape: Tuple[int, int], blob) -> None:
        R, S = shape
        try:
            if isinstance(blob, Exception):
                raise blob
            program = _load(blob).lower(
                self._params, self._cache, *self._rows(R, S)).compile()
            if R not in self.place:
                self.place[R] = model_runner.place_rows.lower(
                    self._logits, jax.ShapeDtypeStruct(
                        (R, self._logits.shape[1]), np.float32),
                    _ints(R)).compile()
        except Exception as e:  # never ready: its requests stay one-row calls
            logger.exception("prefill shape [%d, %d] was not made", R, S)
            self.failed[shape] = f"{type(e).__name__}: {e}"
            return
        with self._lock:
            self.ready[shape] = program
            self._on_ready()

    def _export(self, shapes: List[Tuple[int, int]]) -> Iterator:
        """``shapes`` traced in a process of their own: each one's serialized
        program as soon as it is traced, or the exception it raised there."""
        job = pickle.dumps({
            "platform": self._platform, "cfg": self._cfg,
            "params": self._params, "cache": _shapes(self._cache),
            "rows": [self._rows(R, S) for R, S in shapes]})
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
            [root] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
        with tempfile.TemporaryFile() as err, subprocess.Popen(
                [sys.executable, "-c", "from ray_tpu.llm.prefill_shapes "
                 "import export_job; export_job()"],
                env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=err) as job_process:
            job_process.stdin.write(job)
            job_process.stdin.close()
            for _ in shapes:
                head = job_process.stdout.read(_FRAME.size)
                if len(head) < _FRAME.size:
                    job_process.wait()
                    err.seek(max(err.seek(0, os.SEEK_END) - 2000, 0))
                    raise RuntimeError(
                        f"exit code {job_process.returncode}:\n"
                        + err.read().decode(errors="replace"))
                n, = _FRAME.unpack(head)
                body = job_process.stdout.read(abs(n))
                yield body if n >= 0 else RuntimeError(body.decode())


def _load(blob: bytes):
    """A serialized ``prefill`` as a jitted function of (params, cache,
    *rows): the same XLA module name, the cache donated as the engine's own
    call donates it. ``cfg`` was a static argument and is compiled in."""
    exported = export.deserialize(blob)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill(params, cache, *rows):
        return exported.call(params, cache, *rows)
    return prefill


def export_job() -> None:
    """In the exporting process: the job on the standard input names a
    platform, a model, the shapes of its parameters and cache and the rows of
    each program wanted; the programs leave by the standard output, each
    behind its length, in the job's order."""
    out = os.fdopen(os.dup(1), "wb")
    os.dup2(2, 1)  # whatever else writes to the standard output
    job = loads_trusted(sys.stdin.buffer.read())  # from the process that started us
    utils.lower_for(job["platform"])
    for rows in job["rows"]:
        try:
            body, sign = export.export(
                model_runner.prefill, platforms=[job["platform"]])(
                    job["params"], job["cfg"], job["cache"],
                    *rows).serialize(), 1
        except Exception as e:
            body, sign = f"{type(e).__name__}: {e}"[:4000].encode(), -1
        out.write(_FRAME.pack(sign * len(body)) + body)
        out.flush()
