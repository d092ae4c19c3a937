"""raylint rule set: the invariants this runtime actually depends on.

Each rule encodes a failure mode we have hit (or designed against) in the
distributed runtime — see tools/raylint/README.md for the full rationale and
suppression guidance per rule.

* ASY001 — blocking call inside an ``async def`` body (event-loop stall).
* ASY002 — ``await`` while holding a ``threading`` lock, or a ``threading``
  primitive constructed on the event loop where an ``asyncio`` one belongs.
* SER001 — ``pickle.loads``/``cloudpickle.loads`` outside the sanctioned
  serialization boundary (``_private/serialization.py``, ``_private/wire.py``).
* EXC001 — exception-swallowing ``except ...: pass`` on control-plane paths
  (``_private/``, ``autoscaler/``, ``dag/``) with no log call.
* WIRE001 — a struct defined in a wire-schema module that is not registered
  in the ``wire.py`` registry (it would raise WireError at runtime, or worse,
  tempt someone to pickle it).
* TRC001 — a JAX tracer escaping into actor/object state: a value stored on
  ``self`` or shipped through ``.remote()``/``ray_tpu.put()`` from inside a
  ``jit``/``grad``-traced function.
* ASY003 — a leaked asyncio task: ``asyncio.ensure_future``/``create_task``
  whose result is neither awaited, stored, nor given a done-callback — its
  exception is swallowed until GC (often never); use
  ``ray_tpu._private.async_util.spawn``. Also flags the
  ``self._background.append(ensure_future(...))`` shape: a handle parked in
  long-lived state until shutdown swallows failures just the same.
* LCK001 — lock-order inversion across the GCS -> raylet -> core-worker
  hierarchy: nesting tiered locks against the call direction is the ABBA
  deadlock that wedges a whole node's control plane.
* SUP001 — stale suppression: a ``# raylint: disable=RULE`` comment that
  suppresses zero findings (the code it excused was fixed or moved). Dead
  directives accumulate and silently excuse FUTURE regressions on that
  line; delete them, or add ``SUP001`` to the directive's rule list with a
  reason to keep one deliberately dormant. (Detection lives in core.py —
  it needs the pre-suppression finding set; the class below is the
  registry marker so ``--rules``/``--list-rules`` see it.)

The interprocedural rules (ASY004, LCK002, AWT002, WIRE002) live in
``tools/raylint/rules_interp.py`` on top of the graph/flow layers.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Set

from tools.raylint.core import Finding, Module, Rule, register_rule

# ---------------------------------------------------------------------------
# shared visitor: track whether we are in an async frame
# ---------------------------------------------------------------------------


class _AsyncFrameVisitor(ast.NodeVisitor):
    """Walks a module tracking the innermost function frame. ``in_async`` is
    True only when the nearest enclosing function is an ``async def`` — code
    inside a nested sync ``def`` or ``lambda`` runs off the loop (e.g. an
    executor thunk) and is NOT async context."""

    def __init__(self, module: Module):
        self.module = module
        self.frames: List[str] = []  # "async" | "sync"
        self.findings: List[Finding] = []

    @property
    def in_async(self) -> bool:
        return bool(self.frames) and self.frames[-1] == "async"

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef):
        self.frames.append("async")
        self.generic_visit(node)
        self.frames.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef):
        self.frames.append("sync")
        self.generic_visit(node)
        self.frames.pop()

    def visit_Lambda(self, node: ast.Lambda):
        self.frames.append("sync")
        self.generic_visit(node)
        self.frames.pop()


def _contains_await(nodes) -> bool:
    """True if an await/async-for/async-with occurs in these nodes WITHOUT
    crossing into a nested function definition."""
    stack = list(nodes)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.Await, ast.AsyncFor, ast.AsyncWith)):
            return True
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))
    return False


def _terminal(dotted: Optional[str]) -> str:
    return dotted.rsplit(".", 1)[-1] if dotted else ""


def _is_lock_like(node: ast.AST, resolver) -> bool:
    """Heuristic: an expression that names a mutex (``self._lock``,
    ``_exec_lock``, ``store.mutex`` ...) — but not e.g. ``self.block``."""
    dotted = resolver.dotted(node)
    name = _terminal(dotted).lower()
    return (name in ("lock", "rlock", "mutex")
            or name.endswith(("_lock", "_rlock", "_mutex")))


# ---------------------------------------------------------------------------
# ASY001 — blocking calls in async bodies
# ---------------------------------------------------------------------------

# dotted call -> remediation hint. Every one of these parks the entire event
# loop (every actor task, RPC reply, and heartbeat on this node) until it
# returns.
_BLOCKING_CALLS: Dict[str, str] = {
    "time.sleep": "use `await asyncio.sleep(...)`",
    "subprocess.run": "use `asyncio.create_subprocess_exec` or an executor",
    "subprocess.call": "use `asyncio.create_subprocess_exec` or an executor",
    "subprocess.check_call": "use `asyncio.create_subprocess_exec` or an executor",
    "subprocess.check_output": "use `asyncio.create_subprocess_exec` or an executor",
    "subprocess.getoutput": "use `asyncio.create_subprocess_exec` or an executor",
    "subprocess.getstatusoutput": "use `asyncio.create_subprocess_exec` or an executor",
    "os.system": "use `asyncio.create_subprocess_shell` or an executor",
    "os.wait": "use `asyncio.create_subprocess_exec` and await it",
    "os.waitpid": "use `asyncio.create_subprocess_exec` and await it",
    "urllib.request.urlopen": "run it in an executor thread",
    "requests.get": "run it in an executor thread",
    "requests.post": "run it in an executor thread",
    "requests.put": "run it in an executor thread",
    "requests.patch": "run it in an executor thread",
    "requests.delete": "run it in an executor thread",
    "requests.head": "run it in an executor thread",
    "requests.request": "run it in an executor thread",
    "socket.create_connection": "use `asyncio.open_connection`",
    "ray_tpu.get": "a cluster round-trip blocks the loop; await the async "
                   "API or wrap in `loop.run_in_executor`",
    "ray_tpu.wait": "a cluster round-trip blocks the loop; await the async "
                    "API or wrap in `loop.run_in_executor`",
}

# method names that block when called on a raw socket; only flagged when the
# receiver's name mentions a socket, to keep the false-positive rate near zero
_SOCKET_METHODS = {"recv", "recv_into", "accept", "sendall", "makefile"}


@register_rule
class BlockingCallInAsync(Rule):
    name = "ASY001"
    summary = ("blocking call inside `async def`: stalls every task, RPC and "
               "heartbeat sharing this event loop")

    def check(self, module: Module) -> Iterator[Finding]:
        rule = self

        class V(_AsyncFrameVisitor):
            def visit_Call(self, node: ast.Call):
                if self.in_async:
                    dotted = module.resolver.dotted(node.func)
                    hint = _BLOCKING_CALLS.get(dotted or "")
                    if hint is not None:
                        self.findings.append(rule.finding(
                            module, node,
                            f"blocking `{dotted}(...)` in async context; {hint}"))
                    elif (isinstance(node.func, ast.Attribute)
                          and node.func.attr in _SOCKET_METHODS):
                        recv = module.resolver.dotted(node.func.value) or ""
                        if "sock" in recv.lower():
                            self.findings.append(rule.finding(
                                module, node,
                                f"blocking socket op `.{node.func.attr}(...)` in "
                                f"async context; use asyncio streams"))
                self.generic_visit(node)

        v = V(module)
        v.visit(module.tree)
        return iter(v.findings)


# ---------------------------------------------------------------------------
# ASY002 — threading primitives on the event loop
# ---------------------------------------------------------------------------

_THREADING_PRIMITIVES = {
    "threading.Lock": "asyncio.Lock",
    "threading.RLock": "asyncio.Lock",
    "threading.Condition": "asyncio.Condition",
    "threading.Semaphore": "asyncio.Semaphore",
    "threading.BoundedSemaphore": "asyncio.Semaphore",
    "threading.Event": "asyncio.Event",
    "threading.Barrier": "asyncio.Barrier",
}


@register_rule
class AwaitUnderThreadLock(Rule):
    name = "ASY002"
    summary = ("`await` while holding a threading lock (cross-thread "
               "deadlock), or a threading primitive where an asyncio one "
               "belongs")

    def check(self, module: Module) -> Iterator[Finding]:
        rule = self
        awaited: Set[int] = {
            id(n.value) for n in ast.walk(module.tree) if isinstance(n, ast.Await)
        }

        class V(_AsyncFrameVisitor):
            def visit_With(self, node: ast.With):
                if self.in_async:
                    for item in node.items:
                        expr = item.context_expr
                        # `with lock:` — a Call like `lock.acquire_timeout()`
                        # is out of scope; names/attrs only
                        if isinstance(expr, (ast.Name, ast.Attribute)) \
                                and _is_lock_like(expr, module.resolver) \
                                and _contains_await(node.body):
                            self.findings.append(rule.finding(
                                module, node,
                                "await inside `with <threading lock>`: the "
                                "loop thread parks while holding the lock — "
                                "any thread that then takes the lock and "
                                "schedules loop work deadlocks; use "
                                "`asyncio.Lock` or release before awaiting"))
                self.generic_visit(node)

            def visit_Call(self, node: ast.Call):
                if self.in_async:
                    dotted = module.resolver.dotted(node.func)
                    repl = _THREADING_PRIMITIVES.get(dotted or "")
                    if repl:
                        self.findings.append(rule.finding(
                            module, node,
                            f"`{dotted}()` constructed in async context; its "
                            f"blocking acquire/wait would stall the loop — "
                            f"use `{repl}`"))
                    elif (isinstance(node.func, ast.Attribute)
                          and node.func.attr == "acquire"
                          and id(node) not in awaited
                          and _is_lock_like(node.func.value, module.resolver)):
                        self.findings.append(rule.finding(
                            module, node,
                            "non-awaited `.acquire()` on a lock in async "
                            "context blocks the event loop; use "
                            "`async with` / `await lock.acquire()`"))
                self.generic_visit(node)

        v = V(module)
        v.visit(module.tree)
        return iter(v.findings)


# ---------------------------------------------------------------------------
# ASY003 — leaked asyncio tasks (fire-and-forget without an owner)
# ---------------------------------------------------------------------------

# Spawning calls whose returned task must not be discarded: a task whose
# result nobody ever retrieves reports its exception only when the task
# object is garbage-collected — "Task exception was never retrieved",
# minutes later or never. On the control plane that converts a crashed
# scheduling/flush loop into a silent distributed hang.
_SPAWN_CALLS = {"asyncio.ensure_future", "asyncio.create_task"}
_SPAWN_METHODS = {"ensure_future", "create_task"}


def _is_spawn_call(node: ast.Call, resolver) -> bool:
    dotted = resolver.dotted(node.func)
    if dotted in _SPAWN_CALLS:
        return True
    # loop.create_task(...) / self.loop.create_task(...): method form on
    # anything whose name mentions a loop
    if isinstance(node.func, ast.Attribute) and node.func.attr in _SPAWN_METHODS:
        recv = resolver.dotted(node.func.value) or ""
        return "loop" in recv.lower()
    return False


@register_rule
class LeakedAsyncioTask(Rule):
    name = "ASY003"
    summary = ("fire-and-forget asyncio task: its exception is swallowed "
               "until GC (often never); store/await it or use "
               "async_util.spawn (done-callback logging)")

    def check(self, module: Module) -> Iterator[Finding]:
        findings = []
        for node in ast.walk(module.tree):
            # only a bare expression STATEMENT discards the task; an
            # assignment, await, or chained .add_done_callback(...) keep an
            # owner (appending to long-lived state is handled below)
            if not isinstance(node, ast.Expr):
                continue
            value = node.value
            if isinstance(value, ast.Call) and _is_spawn_call(
                    value, module.resolver):
                findings.append(self.finding(
                    module, value,
                    "spawned task is neither awaited, stored, nor given a "
                    "done-callback — its exception dies with the task "
                    "object; use ray_tpu._private.async_util.spawn(...) "
                    "(or keep a handle / add_done_callback)"))
            elif isinstance(value, ast.Call):
                # lambda bodies passed to call_later/call_soon share the leak
                for arg in value.args:
                    if isinstance(arg, ast.Lambda) \
                            and isinstance(arg.body, ast.Call) \
                            and _is_spawn_call(arg.body, module.resolver):
                        findings.append(self.finding(
                            module, arg.body,
                            "fire-and-forget task spawned inside a lambda "
                            "callback; route through async_util.spawn so "
                            "failures are logged"))
                # `self._background.append(ensure_future(...))`: the handle
                # is kept (so the bare-Expr branch misses it) but nothing
                # ever awaits a list parked until shutdown — the crash is
                # still silent until GC. A LOCAL list (`waiters.append`) is
                # typically awaited in-scope and stays allowed.
                if (isinstance(value.func, ast.Attribute)
                        and value.func.attr in ("append", "add")
                        and isinstance(value.func.value, ast.Attribute)
                        and len(value.args) == 1
                        and isinstance(value.args[0], ast.Call)
                        and _is_spawn_call(value.args[0], module.resolver)):
                    findings.append(self.finding(
                        module, value.args[0],
                        "task appended to long-lived state without failure "
                        "logging: a stored-but-never-awaited task swallows "
                        "its exception until GC; append "
                        "async_util.spawn(...) instead (same handle, "
                        "logged failures)"))
        return iter(findings)


# ---------------------------------------------------------------------------
# SER001 — unpickling outside the serialization boundary
# ---------------------------------------------------------------------------

_UNPICKLE_CALLS = {
    "pickle.loads", "pickle.load", "pickle.Unpickler",
    "cloudpickle.loads", "cloudpickle.load",
}

# The ONLY modules allowed to unpickle: the object-plane serializer and the
# typed wire codec (which by construction never unpickles network input).
_SER_ALLOWLIST = {
    "ray_tpu/_private/serialization.py",
    "ray_tpu/_private/wire.py",
}


@register_rule
class UnpickleOutsideBoundary(Rule):
    name = "SER001"
    summary = ("pickle/cloudpickle load outside _private/serialization.py: "
               "unpickling network input is remote code execution")

    def check(self, module: Module) -> Iterator[Finding]:
        if module.path in _SER_ALLOWLIST:
            return iter(())
        findings = []
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                dotted = module.resolver.dotted(node.func)
                if dotted in _UNPICKLE_CALLS:
                    findings.append(self.finding(
                        module, node,
                        f"`{dotted}(...)` outside the serialization boundary; "
                        f"route through ray_tpu._private.serialization (e.g. "
                        f"`loads_trusted`) so every unpickle site is auditable"))
        return iter(findings)


# ---------------------------------------------------------------------------
# EXC001 — swallowed exceptions on the control plane
# ---------------------------------------------------------------------------

# Handler types that are control flow, not error swallowing, when caught
# alone: bounded waits and lookup misses.
_EXC_EXEMPT = {
    "asyncio.TimeoutError", "TimeoutError", "concurrent.futures.TimeoutError",
    "asyncio.CancelledError", "CancelledError",
    "KeyError", "IndexError", "FileNotFoundError",
    "StopIteration", "StopAsyncIteration", "GeneratorExit",
    "queue.Empty", "queue.Full",
}

# Path components that mark control-plane code. A stall or swallowed error
# here takes down scheduling/heartbeats for the whole node, not one task.
_EXC_PATH_PARTS = {"_private", "autoscaler", "dag"}


def _handler_types(handler: ast.ExceptHandler, resolver) -> List[Optional[str]]:
    t = handler.type
    if t is None:
        return [None]  # bare except
    if isinstance(t, ast.Tuple):
        return [resolver.dotted(e) for e in t.elts]
    return [resolver.dotted(t)]


def _swallows(handler: ast.ExceptHandler) -> bool:
    """Body is nothing but pass / ... / continue / break / bare return —
    i.e. the error is dropped without a trace (a `return value` or any other
    statement at least does something with the failure)."""
    for stmt in handler.body:
        if isinstance(stmt, (ast.Pass, ast.Continue, ast.Break)):
            continue
        if isinstance(stmt, ast.Return) and stmt.value is None:
            continue
        if (isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant)
                and stmt.value.value is Ellipsis):
            continue
        return False
    return True


@register_rule
class SwallowedException(Rule):
    name = "EXC001"
    summary = ("`except ...: pass` on a control-plane path with no log call: "
               "the next symptom is a distributed hang with no trace")

    def check(self, module: Module) -> Iterator[Finding]:
        if not (_EXC_PATH_PARTS & set(module.parts())):
            return iter(())
        findings = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.ExceptHandler) or not _swallows(node):
                continue
            types = _handler_types(node, module.resolver)
            if all(t is not None and t in _EXC_EXEMPT for t in types):
                continue
            shown = ", ".join(t or "<bare>" for t in types)
            findings.append(self.finding(
                module, node,
                f"swallowed `except {shown}` with no log call; add "
                f"`logger.debug(...)` with context, or suppress with a reason "
                f"(`# raylint: disable=EXC001 <why>`)"))
        return iter(findings)


# ---------------------------------------------------------------------------
# TRC001 — JAX tracers escaping into actor/object state
# ---------------------------------------------------------------------------

# Transforms that TRACE their function: inside these bodies every value is a
# Tracer, and letting one escape the trace is at best an
# UnexpectedTracerError at the next use, at worst a silently baked-in
# constant (jit) or a leaked trace-context hold on device buffers.
_TRACING_TRANSFORMS = {
    "jax.jit", "jax.grad", "jax.value_and_grad", "jax.vmap", "jax.pmap",
    "jax.checkpoint", "jax.remat", "jax.custom_jvp", "jax.custom_vjp",
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.cond",
    "jax.shard_map",
}


def _jit_target_names(tree: ast.AST, resolver) -> Set[str]:
    """Names of functions passed to a tracing transform anywhere in the
    module: ``jax.jit(step)``, ``self._fwd = jax.jit(self._fwd_impl)``,
    ``train = jit(train_impl, donate_argnums=0)`` ..."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = resolver.dotted(node.func)
        if dotted not in _TRACING_TRANSFORMS:
            continue
        for arg in node.args[:1]:  # the traced callable is arg 0
            if isinstance(arg, ast.Name):
                names.add(arg.id)
            elif isinstance(arg, ast.Attribute):
                names.add(arg.attr)
    return names


def _is_traced_def(node, resolver) -> bool:
    """Decorated directly (`@jax.jit`), via a call (`@jax.jit`/
    `@partial(jax.jit, ...)`), or by any tracing transform."""
    for dec in node.decorator_list:
        target = dec
        if isinstance(dec, ast.Call):
            dotted = resolver.dotted(dec.func) or ""
            if dotted in ("functools.partial", "partial") and dec.args:
                target = dec.args[0]
            else:
                target = dec.func
        if (resolver.dotted(target) or "") in _TRACING_TRANSFORMS:
            return True
    return False


@register_rule
class TracerEscape(Rule):
    name = "TRC001"
    summary = ("JAX tracer escaping into actor/object state: a traced value "
               "stored on `self` or shipped via `.remote()`/`put()` from a "
               "jit/grad scope")

    def check(self, module: Module) -> Iterator[Finding]:
        resolver = module.resolver
        traced_names = _jit_target_names(module.tree, resolver)
        findings: List[Finding] = []

        def scan_traced_body(fn_node):
            for node in ast.walk(fn_node):
                if isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for t in targets:
                        if (isinstance(t, ast.Attribute)
                                and isinstance(t.value, ast.Name)
                                and t.value.id == "self"
                                and not isinstance(node.value, ast.Constant)):
                            findings.append(self.finding(
                                module, node,
                                f"`self.{t.attr} = ...` inside a traced "
                                f"function: the stored value is a Tracer — "
                                f"it escapes the trace into actor state and "
                                f"dies with UnexpectedTracerError (or bakes "
                                f"in a constant); return it from the jitted "
                                f"function instead"))
                elif isinstance(node, ast.Call):
                    dotted = resolver.dotted(node.func)
                    if dotted in ("ray_tpu.put", "ray.put"):
                        findings.append(self.finding(
                            module, node,
                            f"`{dotted}(...)` inside a traced function "
                            f"ships a Tracer into the object plane; move "
                            f"the put outside the jit/grad scope"))
                    elif (isinstance(node.func, ast.Attribute)
                          and node.func.attr == "remote"):
                        findings.append(self.finding(
                            module, node,
                            "`.remote(...)` inside a traced function: task "
                            "args would be Tracers (and the submission "
                            "itself is a traced side effect that jit will "
                            "elide on cache hits); submit outside the "
                            "traced scope"))

        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if _is_traced_def(node, resolver) or node.name in traced_names:
                scan_traced_body(node)
        return iter(findings)


# ---------------------------------------------------------------------------
# LCK001 — lock-order inversions across the control-plane hierarchy
# ---------------------------------------------------------------------------

# The control plane's lock hierarchy follows its call direction:
# GCS (tier 0) -> raylet (tier 1) -> core worker (tier 2). A thread/task may
# nest lock acquisitions only DOWN the hierarchy (gcs lock, then raylet
# lock, then worker lock). Two call paths nesting in opposite orders is the
# classic ABBA deadlock — and across these components it wedges scheduling
# for the whole node, not one request. Locks are tiered by name
# (`_gcs_lock`, `raylet_mutex`, `_core_worker_lock`, ...); locks whose
# names carry no tier are out of scope, as is any pair within one tier.
_LCK_TIERS = (
    ("gcs", 0),
    ("raylet", 1),
    ("core_worker", 2), ("core", 2), ("worker", 2),
)


def _lock_tier(dotted: Optional[str]) -> Optional[int]:
    name = _terminal(dotted).lower()
    for marker, tier in _LCK_TIERS:
        if marker in name:
            return tier
    return None


@register_rule
class LockOrderInversion(Rule):
    name = "LCK001"
    summary = ("lock acquired AGAINST the GCS -> raylet -> core-worker "
               "hierarchy while a lower-tier lock is held (ABBA deadlock "
               "across control-plane components)")

    def check(self, module: Module) -> Iterator[Finding]:
        rule = self
        resolver = module.resolver

        def lock_exprs(items):
            """(tier, dotted) for each tiered lock taken by a with-item."""
            out = []
            for item in items:
                expr = item.context_expr
                if isinstance(expr, ast.Call):  # `with lock.acquire_timeout()`
                    expr = expr.func
                    if isinstance(expr, ast.Attribute):
                        expr = expr.value
                if isinstance(expr, (ast.Name, ast.Attribute)) \
                        and _is_lock_like(expr, resolver):
                    tier = _lock_tier(resolver.dotted(expr))
                    if tier is not None:
                        out.append((tier, resolver.dotted(expr)))
            return out

        class V(ast.NodeVisitor):
            """Tracks the stack of held tiered locks through with-nesting.
            The stack resets at function boundaries (a nested def runs on
            its own call path)."""

            def __init__(self):
                self.held: List[tuple] = []
                self.findings: List[Finding] = []

            def _visit_with(self, node):
                taken = lock_exprs(node.items)
                # push incrementally: `with a, b:` acquires left-to-right,
                # so b must be checked against a, not only against outer
                # with-statements
                for tier, dotted in taken:
                    for held_tier, held_dotted in self.held:
                        if tier < held_tier:
                            self.findings.append(rule.finding(
                                module, node,
                                f"`{dotted}` (tier {tier}) acquired while "
                                f"holding `{held_dotted}` (tier "
                                f"{held_tier}): lock order must follow "
                                f"GCS -> raylet -> core worker; invert the "
                                f"nesting or release the inner lock first"))
                    self.held.append((tier, dotted))
                self.generic_visit(node)
                if taken:
                    del self.held[-len(taken):]

            def visit_With(self, node):
                self._visit_with(node)

            def visit_AsyncWith(self, node):
                self._visit_with(node)

            def _visit_fn(self, node):
                saved, self.held = self.held, []
                self.generic_visit(node)
                self.held = saved

            def visit_FunctionDef(self, node):
                self._visit_fn(node)

            def visit_AsyncFunctionDef(self, node):
                self._visit_fn(node)

            def visit_Lambda(self, node):
                self._visit_fn(node)

        v = V()
        v.visit(module.tree)
        return iter(v.findings)


# ---------------------------------------------------------------------------
# WIRE001 — wire structs missing from the registry
# ---------------------------------------------------------------------------

# Modules whose dataclasses ARE the control-plane schema: anything defined
# here is meant to cross RPC, so it must be in wire.py's registry or be
# explicitly annotated as process-local.
_WIRE_STRUCT_MODULES = {
    "ray_tpu/_private/common.py",
    "ray_tpu/util/scheduling_strategies.py",
}
_WIRE_REGISTRY_MODULE = "ray_tpu/_private/wire.py"
_WIRE_CACHE_KEY = "wire001.registered"


def _registered_wire_names(project) -> Set[str]:
    """Parse wire.py and collect every class name passed (directly, or via a
    registration loop) to register_struct/register_id."""
    cached = project.cache.get(_WIRE_CACHE_KEY)
    if cached is not None:
        return cached
    names: Set[str] = set()
    path = project.root / _WIRE_REGISTRY_MODULE
    if path.is_file():
        tree = ast.parse(path.read_text(encoding="utf-8"))

        def is_register(call: ast.Call) -> bool:
            fn = call.func
            attr = fn.attr if isinstance(fn, ast.Attribute) else \
                fn.id if isinstance(fn, ast.Name) else ""
            return attr in ("register_struct", "register_id")

        def terminal_name(expr: ast.AST) -> Optional[str]:
            if isinstance(expr, ast.Attribute):
                return expr.attr
            if isinstance(expr, ast.Name):
                return expr.id
            return None

        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and is_register(node) and node.args:
                n = terminal_name(node.args[0])
                if n:
                    names.add(n)
            elif isinstance(node, ast.For):
                # `for c in (ids.JobID, ...): register_id(c)`
                has_register = any(
                    isinstance(sub, ast.Call) and is_register(sub)
                    for sub in ast.walk(node))
                if has_register and isinstance(node.iter, (ast.Tuple, ast.List)):
                    for elt in node.iter.elts:
                        n = terminal_name(elt)
                        if n:
                            names.add(n)
    project.cache[_WIRE_CACHE_KEY] = names
    return names


def _is_dataclass_decorated(node: ast.ClassDef, resolver) -> bool:
    for dec in node.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        dotted = resolver.dotted(target) or ""
        if dotted in ("dataclass", "dataclasses.dataclass"):
            return True
    return False


@register_rule
class UnregisteredWireStruct(Rule):
    name = "WIRE001"
    summary = ("dataclass in a wire-schema module missing from the wire.py "
               "registry: sending it raises WireError at runtime")

    def check(self, module: Module) -> Iterator[Finding]:
        if module.path not in _WIRE_STRUCT_MODULES:
            return iter(())
        registered = _registered_wire_names(module.project)
        findings = []
        for node in module.tree.body:
            if (isinstance(node, ast.ClassDef)
                    and _is_dataclass_decorated(node, module.resolver)
                    and node.name not in registered):
                findings.append(self.finding(
                    module, node,
                    f"wire-schema dataclass `{node.name}` is not registered in "
                    f"wire.py (_register_builtin_types); register it, or mark "
                    f"it process-local with `# raylint: disable=WIRE001 <why>`"))
        return iter(findings)


# ---------------------------------------------------------------------------
# SUP001 — stale suppressions (marker class; detection in core.check_source)
# ---------------------------------------------------------------------------


@register_rule
class StaleSuppression(Rule):
    name = "SUP001"
    summary = ("`# raylint: disable=RULE` that suppresses zero findings: "
               "dead directives excuse future regressions; delete them (or "
               "add SUP001 to the directive's rule list to keep it)")

    def check(self, module: Module) -> Iterator[Finding]:
        return iter(())  # core.check_source runs the real detection


# ---------------------------------------------------------------------------
# CKP001 — checkpoint-plane writes outside the atomic-commit helper
# ---------------------------------------------------------------------------

# Modules whose on-disk artifacts carry the checkpoint plane's atomicity
# invariant: a torn manifest/chunk/pointer write corrupts restore. Every
# file write there must go through ``ckpt.manifest.atomic_write`` (write
# temp + fsync + rename) — the one sanctioned raw-write site, which
# carries its own suppression.
_CKP_PATH_PREFIXES = ("ray_tpu/ckpt/",)
_CKP_PATH_FILES = {"ray_tpu/train/checkpoint.py"}

# attribute calls that write file content directly
_CKP_WRITE_ATTRS = ("write_text", "write_bytes")

# dotted calls that serialize straight into a file object
_CKP_DUMP_CALLS = {"json.dump", "pickle.dump", "cloudpickle.dump",
                   "numpy.save", "np.save"}

# Storage-backend write chokepoints (ckpt/tier): a ChunkBackend or bucket
# client OWNS its tier's durability discipline, so its designated write
# methods may open files directly — PROVIDED the method itself upholds the
# temp+fsync+rename contract. Checked structurally: the method must call
# both ``os.fsync`` and ``os.replace``; a backend write method that opens
# a file without them still flags.
_CKP_BACKEND_CLASS_SUFFIXES = ("Backend", "BucketClient")
_CKP_BACKEND_WRITE_METHODS = {"put", "put_object", "put_manifest",
                              "upload_part", "complete_multipart"}


def _ckp_backend_exempt_calls(module: Module) -> set:
    """ids of Call nodes inside a storage-backend write method that
    provably renames a fsynced temp file into place."""
    exempt: set = set()
    for cls in ast.walk(module.tree):
        if not (isinstance(cls, ast.ClassDef)
                and cls.name.endswith(_CKP_BACKEND_CLASS_SUFFIXES)):
            continue
        for fn in cls.body:
            if not (isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and fn.name in _CKP_BACKEND_WRITE_METHODS):
                continue
            dotted = {module.resolver.dotted(n.func)
                      for n in ast.walk(fn) if isinstance(n, ast.Call)}
            if {"os.fsync", "os.replace"} <= dotted:
                exempt.update(id(n) for n in ast.walk(fn)
                              if isinstance(n, ast.Call))
    return exempt


def _open_write_mode(call: ast.Call) -> bool:
    """True if this ``open(...)`` call names a write/append/create mode.
    A non-constant mode is treated as a write (the caller can suppress
    with a reason if it provably is not)."""
    mode = None
    if len(call.args) >= 2:
        mode = call.args[1]
    for kw in call.keywords:
        if kw.arg == "mode":
            mode = kw.value
    if mode is None:
        return False  # default "r"
    if isinstance(mode, ast.Constant) and isinstance(mode.value, str):
        return any(c in mode.value for c in "wax+")
    return True


@register_rule
class CheckpointWriteOutsideHelper(Rule):
    name = "CKP001"
    summary = ("checkpoint/manifest file write outside "
               "ckpt.manifest.atomic_write: a torn write breaks the plane's "
               "atomicity invariant (a reader may observe a partial file)")

    def check(self, module: Module) -> Iterator[Finding]:
        if not (module.path.startswith(_CKP_PATH_PREFIXES)
                or module.path in _CKP_PATH_FILES):
            return iter(())
        exempt = _ckp_backend_exempt_calls(module)
        findings = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call) or id(node) in exempt:
                continue
            dotted = module.resolver.dotted(node.func)
            if dotted in ("open", "io.open", "builtins.open"):
                if _open_write_mode(node):
                    findings.append(self.finding(
                        module, node,
                        "file opened for writing on a checkpoint-plane "
                        "path; route the bytes through "
                        "`ckpt.manifest.atomic_write` so a crash can "
                        "never leave a torn manifest/chunk visible"))
            elif dotted in _CKP_DUMP_CALLS:
                findings.append(self.finding(
                    module, node,
                    f"`{dotted}(...)` serializes straight into a file on "
                    f"a checkpoint-plane path; serialize to bytes and "
                    f"commit via `ckpt.manifest.atomic_write`"))
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in _CKP_WRITE_ATTRS):
                findings.append(self.finding(
                    module, node,
                    f"`.{node.func.attr}(...)` writes file content "
                    f"directly on a checkpoint-plane path; use "
                    f"`ckpt.manifest.atomic_write`"))
        return iter(findings)


# ---------------------------------------------------------------------------
# OBS001: observability hygiene — metric naming and static span names
# ---------------------------------------------------------------------------

_OBS_METRIC_CTORS = {"Counter", "Gauge", "Histogram"}
_OBS_NAME_PREFIXES = ("ray_tpu_", "ray_tpu.")


def _call_arg(node: ast.Call, index: int, keyword: str) -> Optional[ast.AST]:
    if len(node.args) > index:
        return node.args[index]
    for kw in node.keywords:
        if kw.arg == keyword:
            return kw.value
    return None


@register_rule
class ObservabilityHygiene(Rule):
    name = "OBS001"
    summary = ("observability hygiene: metric instruments must carry the "
               "ray_tpu prefix and a non-empty description, and "
               "tracing.profile() span names must be static strings — an "
               "f-string per request/task is a cardinality bomb in every "
               "span consumer (GCS ring, timeline, Perfetto)")

    def check(self, module: Module) -> Iterator[Finding]:
        if not module.path.startswith("ray_tpu/"):
            return iter(())
        findings: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = module.resolver.dotted(node.func) or ""
            terminal = _terminal(dotted)
            # metrics constructors: resolved through util.metrics (so
            # collections.Counter and friends never match)
            if terminal in _OBS_METRIC_CTORS and "metrics" in dotted:
                findings.extend(self._check_metric(module, node, terminal))
            elif terminal == "profile" and "tracing" in dotted:
                findings.extend(self._check_span(module, node))
        return iter(findings)

    def _check_metric(self, module: Module, node: ast.Call,
                      ctor: str) -> List[Finding]:
        out: List[Finding] = []
        name = _call_arg(node, 0, "name")
        if not (isinstance(name, ast.Constant)
                and isinstance(name.value, str)):
            out.append(self.finding(
                module, node,
                f"{ctor} name must be a static string literal (the "
                f"ray_tpu prefix convention is unverifiable otherwise, "
                f"and dynamic names multiply Prometheus series)"))
        elif not name.value.startswith(_OBS_NAME_PREFIXES):
            out.append(self.finding(
                module, node,
                f"metric `{name.value}` must carry the `ray_tpu_` prefix "
                f"(every exported series is namespaced; unprefixed names "
                f"collide with user/app metrics in /metrics)"))
        desc = _call_arg(node, 1, "description")
        if desc is None or (isinstance(desc, ast.Constant)
                            and not str(desc.value or "").strip()):
            out.append(self.finding(
                module, node,
                f"{ctor} needs a non-empty description — it renders as "
                f"the `# HELP` line of the Prometheus exposition"))
        return out

    def _check_span(self, module: Module, node: ast.Call) -> List[Finding]:
        name = _call_arg(node, 0, "name")
        if isinstance(name, ast.Constant) and isinstance(name.value, str):
            return []
        return [self.finding(
            module, node,
            "tracing.profile() span name must be a static string — "
            "f-strings/concatenation mint one span NAME per request or "
            "task (cardinality bomb in the GCS span table and every "
            "timeline view); put the variable part in span kwargs, e.g. "
            "profile(\"pull\", store=name)")]


# ---------------------------------------------------------------------------
# RSH001: reshard plans must be proven no-gather before transport lowering
# ---------------------------------------------------------------------------

# calls that mint a reshard plan
_RSH_PLAN_SOURCES = {"plan_reshard", "restore_plan"}
# transport-lowering entry points that execute/lower a plan's data movement
_RSH_LOWER_SINKS = {"collective_reshard", "redistribute", "lower_collective"}


@register_rule
class ReshardNoGatherUnasserted(Rule):
    name = "RSH001"
    summary = ("reshard plan reaches a transport lowering without an "
               "explicit `plan.no_gather()` check: a plan that gathers a "
               "full leaf onto one host is exactly the XLA "
               "replicate-then-slice rematerialization the collective "
               "redistribution tier exists to kill (MULTICHIP_r05) — "
               "assert the invariant where the plan is made, or carry a "
               "reasoned suppression")

    def check(self, module: Module) -> Iterator[Finding]:
        if not module.path.startswith("ray_tpu/"):
            return iter(())
        findings: List[Finding] = []
        seen: set = set()
        funcs = [n for n in ast.walk(module.tree)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for fn in funcs:
            plans: dict = {}   # var -> assignment line
            guards: dict = {}  # var -> earliest no_gather() line
            sinks: list = []   # (var, sink call node)
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) \
                        and isinstance(node.value, ast.Call):
                    dotted = module.resolver.dotted(node.value.func) or ""
                    if _terminal(dotted) in _RSH_PLAN_SOURCES:
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                plans[t.id] = node.lineno
                if not isinstance(node, ast.Call):
                    continue
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "no_gather" \
                        and isinstance(node.func.value, ast.Name):
                    var = node.func.value.id
                    guards[var] = min(guards.get(var, node.lineno),
                                      node.lineno)
                dotted = module.resolver.dotted(node.func) or ""
                if _terminal(dotted) in _RSH_LOWER_SINKS:
                    for arg in list(node.args) \
                            + [kw.value for kw in node.keywords]:
                        if isinstance(arg, ast.Name):
                            sinks.append((arg.id, node))
            for var, node in sinks:
                if var not in plans:
                    continue  # plan came from elsewhere (param, attr)
                key = (node.lineno, node.col_offset)
                if key in seen:
                    continue  # nested-def walk saw this sink already
                guard = guards.get(var)
                if guard is not None and guard <= node.lineno:
                    continue
                seen.add(key)
                findings.append(self.finding(
                    module, node,
                    f"`{var}` (a reshard plan from "
                    f"plan_reshard/restore_plan) is lowered to a transport "
                    f"without `{var}.no_gather()` being checked first; a "
                    f"gathering plan must be rejected before any byte "
                    f"moves (use weights.maybe_lower_collective for the "
                    f"logged fallback)"))
        return iter(findings)
