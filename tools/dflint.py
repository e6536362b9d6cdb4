#!/usr/bin/env python
"""dflint — repo-native static analysis for dragonfly2_tpu.

The reference Dragonfly2 leans on `go vet` and the race detector; this is the
Python port's equivalent: AST-level checks for the JAX and concurrency bug
classes that generic linters miss. Run as a tier-1 test (tests/test_lint.py)
so the tree stays clean, or standalone:

    python tools/dflint.py dragonfly2_tpu/ tools/ bench.py
    python tools/dflint.py --list-checks

Checks (see README.md "Static analysis" for the catalog):

  DF011  float()/int()/bool() coercion inside a jit/pmap-traced function
         (concretizes a tracer: TracerConversionError at best, silent
         recompile-per-value at worst)
  DF012  jnp.*/jax.numpy.* call inside a Python for/while loop in modules
         under ops/, models/, parallel/ (unrolled-graph blowup)
  DF013  time.perf_counter timing window around jax/jnp work with no
         synchronization (block_until_ready or a D2H materialization) —
         measures async dispatch, not compute
  DF014  non-hashable literal (list/dict/set) passed for a static_argnums/
         static_argnames parameter of a jitted callable (TypeError at trace)
  DF021  asyncio primitive (Lock/Event/Condition/Semaphore/Queue...) created
         at import or class-body scope (binds to / is shared across the
         wrong event loop)
  DF022  time.sleep() inside `async def` (blocks the event loop; use
         asyncio.sleep)
  DF023  inconsistent lock discipline: a `self._*` attribute mutated under
         `with <lock>:` in one place and without it in another (the classic
         data race the Go race detector catches)
  DF024  hand-rolled retry pacing: await asyncio.sleep() inside an except
         handler in a loop, or with a delay computed from the loop's attempt
         variable — outside dragonfly2_tpu/resilience/, retries must use the
         shared BackoffPolicy (exponential + seeded jitter) instead
  DF025  awaited per-item RPC call inside a for/while loop outside rpc/ —
         the control-plane twin of DF024: one round trip per item serializes
         the loop on network latency; batch into one call (report_pieces,
         train_chunk batching) or hoist the RPC out of the loop
  DF026  ThreadPoolExecutor/threading.Thread constructed on a hot path: a
         for/while body, an `async def` (the per-round/per-piece shape), or
         a same-module function called from a loop — thread/pool spawn costs
         ~100µs+ and unbounded churn; bind workers to WORK (a long-lived
         pool owned by the object, built in __init__), not to items (the
         PieceReportBuffer timer-task and PR 3 per-pump-thread lessons)
  DF028  a module-scope metric family (registry.counter/gauge/histogram or a
         direct observability.metrics constructor) whose name is never
         touched by .inc/.dec/.set/.observe/.labels/.time — nor passed to
         any call — anywhere in the linted tree: a declared-but-never-
         incremented family renders as a frozen 0 forever, which dashboards
         and alert rules read as "healthy" (the PR 11 heartbeat bug class).
         This is dflint's first CROSS-FILE check: declarations in one module
         are cleared by touches in any other.
  DF029  wall-clock read or real sleep inside the sim/ package (virtual-
         clock discipline): the discrete-event simulator orders EVERYTHING
         by its injected VirtualClock — one stray time.time()/
         time.monotonic()/asyncio.sleep()/loop.time() silently mixes wall
         time into event ordering and corrupts the simulation without
         crashing it. Read time through the engine's clock (utils/clock.py);
         the engine's own events/s wall meter is the one suppressed site.
  DF030  an AlertRule whose `metric` (or `denom`) names a family no registry
         constructor in the linted tree declares — DF028's inverse, and the
         second cross-file check: DF028 catches a family nobody moves, DF030
         catches a RULE left pointing at nothing (the silent failure mode of
         renaming a metric family: the rule never errors, it just never
         fires again). Family names are matched against every
         .counter/.gauge/.histogram factory call's composed name
         (namespace_subsystem_name; private-namespace registries match on
         the subsystem_name suffix) and direct metrics.Counter/Gauge/
         Histogram constructions; non-constant metric expressions are
         skipped (unresolvable statically).
  DF031  silent exception swallow: bare/overbroad except whose body is only
         pass/continue/... (no log, no narrowing)
  DF032  mutable default argument (list/dict/set literal or constructor)
  DF033  np.array/np.asarray/np.stack of loop-variable-derived data inside a
         for loop — the numpy twin of DF012: one tiny allocation per row
         turns a columnar pass into O(rows) Python (vectorize with field
         slicing, unique/bincount/reduceat instead)
  DF034  unbounded queue in service code: asyncio.Queue()/LifoQueue()/
         PriorityQueue() without a positive maxsize, or collections.deque()
         without a maxlen, outside tests — under overload an unbounded
         buffer converts backpressure into memory growth and turns a
         brownout into an OOM kill (the ISSUE 17 degradation rule: every
         service-side buffer is bounded or carries a suppression explaining
         why unbounded is safe here)
  DF035  per-candidate Python loop inside a scoring hot-path function
         (evaluate/evaluate_many/_prepare/feature builders/shadow legs)
         outside native/ and scheduler/scheduling.py — the native round
         driver exists because per-round Python glue was the scheduler's
         throughput wall (ISSUE 18); each such loop re-introduces
         O(candidates) Python work per round. Suppress with reason for a
         deliberately-kept serial reference leg.
  DF036  direct mutation of mirrored scheduler state outside the registered
         invalidation hooks (ISSUE 19): the native peer-table mirror stays
         correct ONLY because every version-bumping mutation flows through
         the hook-firing mutators — bump_feat() for feat_version, Task
         add_edge/delete_edge for DAG adjacency, the pool's create/delete
         for membership, MirrorClient registration for _mirror/_mirror_slot.
         A raw `x.feat_version += 1`, a `vertex.parents.add(...)`, or an
         `obj._mirror_slot = ...` outside scheduler/resource.py and
         scheduler/mirror.py bypasses the delta stream: the mirror keeps
         serving the OLD state with no stale-key tripwire (the version
         never moved), which is the one silent-wrongness hole the
         versioned-invalidation design has. Suppress with the reason the
         site cannot desynchronize the mirror.

Suppression:
  - same line:   <code>  # dflint: disable=DF023 <reason>   (comma-separate ids;
                 prose after the id list is the required human reason)
  - whole file:  # dflint: skip-file     (on its own line, first 5 lines)
  Unknown DFnnn-shaped ids in a disable comment are themselves reported (DF001).

Exit codes: 0 clean, 1 violations found, 2 internal error / bad usage.
"""

from __future__ import annotations

import argparse
import ast
import re
import sys
import traceback
from dataclasses import dataclass
from pathlib import Path
from typing import Iterable, Iterator

CHECKS: dict[str, str] = {
    "DF001": "unknown check id in a dflint suppression comment",
    "DF002": "file does not parse (syntax error)",
    "DF011": "tracer coercion: float()/int()/bool() inside a traced function",
    "DF012": "jnp call inside a Python loop (unrolled graph) in ops/models/parallel",
    "DF013": "timed JAX region without synchronization (async dispatch mistimed)",
    "DF014": "non-hashable literal passed for a static jit argument",
    "DF021": "asyncio primitive created at import/class-body scope",
    "DF022": "time.sleep inside async def (blocks the event loop)",
    "DF023": "lock-guarded attribute also mutated outside the lock",
    "DF024": "raw asyncio.sleep retry loop outside the resilience module",
    "DF025": "awaited per-item RPC call inside a loop outside rpc/ (batch it)",
    "DF026": "Thread/ThreadPoolExecutor constructed on a hot path (pool churn)",
    "DF027": "Tracer.span(...) not used as a `with` context manager (leaked span)",
    "DF028": "module-scope metric family never incremented/observed anywhere (dead metric)",
    "DF029": "wall-clock read or real sleep inside sim/ (virtual-clock discipline)",
    "DF030": "AlertRule names a metric family no registry constructor declares (dead rule)",
    "DF031": "bare/overbroad except silently swallowing the error",
    "DF032": "mutable default argument",
    "DF033": "per-row numpy array construction inside a for loop (vectorize)",
    "DF034": "unbounded asyncio.Queue/deque in service code (overload memory bomb)",
    "DF035": "per-candidate Python loop on the scoring hot path (drive it natively)",
    "DF036": "mirrored peer/DAG/feature state mutated outside its invalidation hooks",
}

# numpy constructors whose per-row use inside a loop marks an unvectorized
# pass (DF033). Canonical dotted names; `import numpy as np` and from-imports
# resolve through import_aliases.
NP_ROW_CTORS = {"numpy.array", "numpy.asarray", "numpy.stack"}

# Packages where Python-loop-over-jnp is an unrolled-graph hazard (DF012).
JNP_LOOP_DIRS = {"ops", "models", "parallel"}

# asyncio primitives that bind to (or are shared across) an event loop.
ASYNC_PRIMITIVES = {
    "Lock", "Event", "Condition", "Semaphore", "BoundedSemaphore",
    "Queue", "LifoQueue", "PriorityQueue", "Barrier",
}

# Container methods that mutate in place (DF023).
MUTATOR_METHODS = {
    "append", "extend", "insert", "add", "update", "setdefault", "pop",
    "popitem", "remove", "discard", "clear", "appendleft", "extendleft",
    "popleft", "rotate",
}

# Calls that force completion of queued device work (DF013). A D2H
# materialization (np.asarray / .item() / jax.device_get) is accepted as a
# sync: the host cannot hold a value the device has not finished computing
# (train_gnn.train_async ends every call this way).
SYNC_ATTRS = {"block_until_ready", "item"}
SYNC_DOTTED = {
    "jax.block_until_ready", "jax.device_get", "np.asarray", "numpy.asarray",
    "np.array", "numpy.array", "jax.effects_barrier",
}
SYNC_NAMES = {"_sync"}

# ids are DFnnn-shaped; trailing prose after the id list is the human reason
# and is ignored ("# dflint: disable=DF023 single-threaded asyncio").
_DISABLE_RE = re.compile(r"#\s*dflint:\s*disable=([A-Z]{2}\d{3}(?:\s*,\s*[A-Z]{2}\d{3})*)")
_SKIP_FILE_RE = re.compile(r"^\s*#\s*dflint:\s*skip-file\b")


@dataclass(frozen=True)
class Violation:
    path: str
    line: int
    col: int
    check: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.check} {self.message}"


def walk_pruned(node: ast.AST) -> Iterator[ast.AST]:
    """ast.walk that does NOT descend into nested function/lambda bodies —
    code in a nested def runs later (or never), not in the enclosing scope."""
    yield node
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        return
    for child in ast.iter_child_nodes(node):
        yield from walk_pruned(child)


def dotted(node: ast.AST) -> str:
    """'jax.numpy.dot' for Attribute/Name chains, '' for anything dynamic."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _call_name(node: ast.Call) -> str:
    return dotted(node.func)


def import_aliases(tree: ast.Module) -> dict[str, str]:
    """Local name -> canonical dotted path for from-imports and import-as
    (`from time import sleep` -> {'sleep': 'time.sleep'}), so checks keyed on
    dotted names don't go blind to a from-import refactor."""
    out: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
            for a in node.names:
                if a.name != "*":
                    out[a.asname or a.name] = f"{node.module}.{a.name}"
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    out[a.asname] = a.name
    return out


def _resolved_call_name(node: ast.Call, aliases: dict[str, str]) -> str:
    """_call_name with the leading segment mapped through import aliases."""
    name = _call_name(node)
    if not name:
        return name
    head, sep, rest = name.partition(".")
    if head in aliases:
        return aliases[head] + (sep + rest if rest else "")
    return name


def _is_jit_like(name: str) -> bool:
    return name in {
        "jax.jit", "jit", "jax.pmap", "pmap", "jax.experimental.pjit.pjit", "pjit",
    }


def _jit_decorator(dec: ast.expr) -> bool:
    """True for @jax.jit / @jit / @partial(jax.jit, ...) / @jax.jit(...)."""
    if _is_jit_like(dotted(dec)):
        return True
    if isinstance(dec, ast.Call):
        name = _call_name(dec)
        if _is_jit_like(name):
            return True
        if name in {"partial", "functools.partial"} and dec.args:
            return _is_jit_like(dotted(dec.args[0]))
    return False


def _is_jaxish_call(node: ast.Call) -> bool:
    name = _call_name(node)
    root = name.split(".", 1)[0]
    return root in {"jnp", "jax"} or name.startswith("jax.numpy.")


def _is_sync_call(node: ast.Call) -> bool:
    name = _call_name(node)
    if name in SYNC_DOTTED or name in SYNC_NAMES:
        return True
    # float(x)/int(x)/bool(x) on a device array materializes it (D2H sync)
    if name in ("float", "int", "bool") and len(node.args) == 1:
        return not isinstance(node.args[0], ast.Constant)
    return isinstance(node.func, ast.Attribute) and node.func.attr in SYNC_ATTRS


def _self_attr(node: ast.AST) -> str | None:
    """'x' for an Attribute `self.x`, else None."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def _non_hashable_literal(node: ast.expr) -> bool:
    return isinstance(
        node, (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp)
    )


# ---------------------------------------------------------------------------
# suppression comments


class Suppressions:
    def __init__(self, source: str):
        self.skip_file = False
        self.by_line: dict[int, set[str]] = {}
        self.unknown: list[tuple[int, str]] = []
        for lineno, line in enumerate(source.splitlines(), start=1):
            if lineno <= 5 and _SKIP_FILE_RE.match(line):
                self.skip_file = True
            m = _DISABLE_RE.search(line)
            if not m:
                continue
            ids = {p.strip() for p in m.group(1).split(",") if p.strip()}
            for check_id in ids:
                if check_id not in CHECKS:
                    self.unknown.append((lineno, check_id))
            self.by_line.setdefault(lineno, set()).update(ids)

    def allows(self, v: Violation) -> bool:
        return v.check in self.by_line.get(v.line, ())


# ---------------------------------------------------------------------------
# individual checks


def check_tracer_coercion(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF011: float()/int()/bool() on non-literals inside traced functions."""
    traced: set[ast.AST] = set()

    # decorated defs, and defs/lambdas passed directly to jax.jit(...)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_jit_decorator(d) for d in node.decorator_list):
                traced.add(node)
        elif isinstance(node, ast.Call) and _is_jit_like(_call_name(node)):
            for arg in node.args[:1]:
                if isinstance(arg, ast.Lambda):
                    traced.add(arg)
    # jitted-by-name: g = jax.jit(f) where f is a local def
    defs_by_name = {
        n.name: n
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit_like(_call_name(node)) and node.args:
            target = node.args[0]
            if isinstance(target, ast.Name) and target.id in defs_by_name:
                traced.add(defs_by_name[target.id])

    for fn in traced:
        body = fn.body if not isinstance(fn, ast.Lambda) else [fn.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                if isinstance(node.func, ast.Name) and node.func.id in (
                    "float", "int", "bool"
                ):
                    if len(node.args) == 1 and not isinstance(
                        node.args[0], ast.Constant
                    ):
                        yield Violation(
                            path, node.lineno, node.col_offset, "DF011",
                            f"{node.func.id}() on a value inside a traced "
                            "function concretizes the tracer; compute with "
                            "jnp or move the coercion outside the jit",
                        )


def check_jnp_in_loop(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF012: jnp calls under for/while in ops/, models/, parallel/."""
    if not JNP_LOOP_DIRS.intersection(Path(path).parts):
        return
    loops = [
        n for n in ast.walk(tree) if isinstance(n, (ast.For, ast.While, ast.AsyncFor))
    ]
    seen: set[tuple[int, int]] = set()  # nested loops walk shared bodies
    for loop in loops:
        for stmt in loop.body + loop.orelse:
            for node in walk_pruned(stmt):
                if isinstance(node, ast.Call) and _is_jaxish_call(node):
                    name = _call_name(node)
                    if _is_jit_like(name):
                        continue  # wrapping, not tracing work
                    key = (node.lineno, node.col_offset)
                    if key in seen:
                        continue
                    seen.add(key)
                    yield Violation(
                        path, node.lineno, node.col_offset, "DF012",
                        f"{name}() inside a Python loop unrolls into the "
                        "traced graph; hoist it, vectorize, or use lax.scan/"
                        "fori_loop",
                    )


class _Window:
    __slots__ = ("start", "end", "var")

    def __init__(self, start: int, end: int, var: str):
        self.start, self.end, self.var = start, end, var


def _perf_counter_windows(fn_body: list[ast.stmt]) -> list[_Window]:
    """(assign-line, elapsed-use-line) pairs for `t = time.perf_counter()`
    ... `time.perf_counter() - t` within one function body."""
    assigns: dict[str, list[int]] = {}
    uses: list[tuple[int, str]] = []
    for stmt in fn_body:
        for node in walk_pruned(stmt):
            if (
                isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and _call_name(node.value) == "time.perf_counter"
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
            ):
                assigns.setdefault(node.targets[0].id, []).append(node.lineno)
            if (
                isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Sub)
                and isinstance(node.right, ast.Name)
                and isinstance(node.left, ast.Call)
                and _call_name(node.left) == "time.perf_counter"
            ):
                uses.append((node.lineno, node.right.id))
    windows = []
    for use_line, var in uses:
        starts = [a for a in assigns.get(var, ()) if a < use_line]
        if starts:
            windows.append(_Window(max(starts), use_line, var))
    return windows


def check_unsynced_timing(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF013: perf_counter window around jax/jnp calls with no sync."""
    scopes: list[list[ast.stmt]] = [tree.body]
    scopes.extend(
        n.body
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    )
    for body in scopes:
        windows = _perf_counter_windows(body)
        if not windows:
            continue
        calls: list[tuple[int, ast.Call]] = []
        for stmt in body:
            for node in walk_pruned(stmt):
                if isinstance(node, ast.Call):
                    calls.append((node.lineno, node))
        for w in windows:
            in_window = [c for line, c in calls if w.start < line <= w.end]
            jaxish = [c for c in in_window if _is_jaxish_call(c)]
            if jaxish and not any(_is_sync_call(c) for c in in_window):
                yield Violation(
                    path, w.end, 0, "DF013",
                    f"timing window ({w.var}, lines {w.start}-{w.end}) around "
                    f"{_call_name(jaxish[0])}() has no block_until_ready/D2H "
                    "sync — it measures dispatch, not compute",
                )


def _static_spec(call: ast.Call) -> tuple[list[int], list[str]]:
    """static_argnums/static_argnames from a jax.jit(...) call."""
    nums: list[int] = []
    names: list[str] = []
    for kw in call.keywords:
        vals: list[ast.expr]
        if isinstance(kw.value, (ast.Tuple, ast.List)):
            vals = list(kw.value.elts)
        else:
            vals = [kw.value]
        if kw.arg == "static_argnums":
            nums = [
                v.value
                for v in vals
                if isinstance(v, ast.Constant) and isinstance(v.value, int)
            ]
        elif kw.arg == "static_argnames":
            names = [
                v.value
                for v in vals
                if isinstance(v, ast.Constant) and isinstance(v.value, str)
            ]
    return nums, names


def check_static_arg_literals(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF014: list/dict/set literals passed for static jit args."""
    jitted: dict[str, tuple[list[int], list[str]]] = {}

    for node in ast.walk(tree):
        # g = jax.jit(f, static_argnums=...)
        if (
            isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Call)
            and _is_jit_like(_call_name(node.value))
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
        ):
            nums, names = _static_spec(node.value)
            if nums or names:
                jitted[node.targets[0].id] = (nums, names)
        # @partial(jax.jit, static_argnums=...) / @jax.jit(...) decorated def
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call) and _jit_decorator(dec):
                    nums, names = _static_spec(dec)
                    if nums or names:
                        jitted[node.name] = (nums, names)

    def flag_call(call: ast.Call, nums: list[int], names: list[str]):
        for i in nums:
            if i < len(call.args) and _non_hashable_literal(call.args[i]):
                yield Violation(
                    path, call.args[i].lineno, call.args[i].col_offset, "DF014",
                    f"static arg {i} gets a non-hashable literal — jit static "
                    "args must be hashable (use a tuple/frozenset)",
                )
        for kw in call.keywords:
            if kw.arg in names and _non_hashable_literal(kw.value):
                yield Violation(
                    path, kw.value.lineno, kw.value.col_offset, "DF014",
                    f"static arg {kw.arg!r} gets a non-hashable literal — jit "
                    "static args must be hashable (use a tuple/frozenset)",
                )

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        # g(...) where g is a known jitted name
        if isinstance(node.func, ast.Name) and node.func.id in jitted:
            nums, names = jitted[node.func.id]
            yield from flag_call(node, nums, names)
        # jax.jit(f, static_argnums=...)(x, [..]) immediate call
        elif isinstance(node.func, ast.Call) and _is_jit_like(_call_name(node.func)):
            nums, names = _static_spec(node.func)
            if nums or names:
                yield from flag_call(node, nums, names)


def check_asyncio_primitive_scope(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF021: asyncio.Lock()/Queue()/... at import or class-body scope."""
    aliases = import_aliases(tree)

    def scan(stmts: Iterable[ast.stmt], where: str) -> Iterator[Violation]:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(stmt, ast.ClassDef):
                yield from scan(stmt.body, f"class {stmt.name} body")
                continue
            for node in walk_pruned(stmt):
                if isinstance(node, ast.Call):
                    name = _resolved_call_name(node, aliases)
                    if (
                        name.startswith("asyncio.")
                        and name.split(".")[-1] in ASYNC_PRIMITIVES
                    ):
                        yield Violation(
                            path, node.lineno, node.col_offset, "DF021",
                            f"{name}() at {where} binds to whichever loop "
                            "exists at import time; create it inside the "
                            "owning coroutine or start() path",
                        )

    yield from scan(tree.body, "module scope")


def check_sleep_in_async(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF022: time.sleep inside async def."""
    aliases = import_aliases(tree)
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.AsyncFunctionDef):
            continue
        for stmt in fn.body:
            for node in walk_pruned(stmt):
                if (
                    isinstance(node, ast.Call)
                    and _resolved_call_name(node, aliases) == "time.sleep"
                ):
                    yield Violation(
                        path, node.lineno, node.col_offset, "DF022",
                        "time.sleep() blocks the event loop inside "
                        f"async {fn.name}(); use await asyncio.sleep()",
                    )


_LOCK_CTORS = {
    "threading.Lock": "threading", "threading.RLock": "threading",
    "asyncio.Lock": "asyncio", "Lock": "threading", "RLock": "threading",
}


def check_lock_discipline(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF023: attribute mutated both under a lock and outside one.

    The Go-race-detector shape: state that is *sometimes* accessed under the
    class's lock and sometimes not. Attributes never touched under the lock
    are not flagged (the lock evidently guards something else)."""
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        lock_attrs: set[str] = set()
        for node in ast.walk(cls):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                if _call_name(node.value) in _LOCK_CTORS:
                    for t in node.targets:
                        attr = _self_attr(t)
                        if attr:
                            lock_attrs.add(attr)
        if not lock_attrs:
            continue

        # (attr, guarded, node, in_init) mutation records per method
        mutations: list[tuple[str, bool, ast.AST, bool]] = []

        def visit(node: ast.AST, guard_depth: int, in_init: bool) -> None:
            if isinstance(node, (ast.With, ast.AsyncWith)):
                locked = any(
                    _self_attr(item.context_expr) in lock_attrs
                    or (
                        isinstance(item.context_expr, ast.Call)
                        and _self_attr(item.context_expr.func) in lock_attrs
                    )
                    for item in node.items
                )
                depth = guard_depth + (1 if locked else 0)
                for child in ast.iter_child_nodes(node):
                    visit(child, depth, in_init)
                return
            attr = None
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign) else [node.target]
                )
                flat: list[ast.expr] = []
                for t in targets:  # a, b = ... unpacking counts per element
                    if isinstance(t, (ast.Tuple, ast.List)):
                        flat.extend(t.elts)
                    else:
                        flat.append(t)
                for t in flat:
                    if isinstance(t, ast.Starred):
                        t = t.value
                    if isinstance(t, ast.Subscript):
                        attr = _self_attr(t.value)
                    else:
                        attr = _self_attr(t)
                    if attr:
                        mutations.append((attr, guard_depth > 0, node, in_init))
                attr = None
            elif isinstance(node, ast.Delete):
                for t in node.targets:
                    if isinstance(t, ast.Subscript):
                        attr = _self_attr(t.value)
                        if attr:
                            mutations.append((attr, guard_depth > 0, node, in_init))
                attr = None
            elif isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                if node.func.attr in MUTATOR_METHODS:
                    attr = _self_attr(node.func.value)
                    if attr:
                        mutations.append((attr, guard_depth > 0, node, in_init))
            for child in ast.iter_child_nodes(node):
                visit(child, guard_depth, in_init)

        for method in cls.body:
            if isinstance(method, (ast.FunctionDef, ast.AsyncFunctionDef)):
                init = method.name in ("__init__", "__new__")
                for stmt in method.body:
                    visit(stmt, 0, init)

        guarded_attrs = {
            attr for attr, guarded, _, _ in mutations if guarded
        } - lock_attrs
        for attr, guarded, node, in_init in mutations:
            if attr in guarded_attrs and not guarded and not in_init:
                yield Violation(
                    path, node.lineno, node.col_offset, "DF023",
                    f"self.{attr} is mutated under a lock elsewhere in "
                    f"{cls.name} but not here — hold the lock or document "
                    "why this site is safe",
                )


def check_raw_retry_sleep(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF024: hand-rolled retry pacing outside dragonfly2_tpu/resilience/.

    Two shapes mark a raw retry ladder:
      1. `await asyncio.sleep(...)` lexically inside an `except` handler that
         sits inside a for/while loop (sleep-on-failure-then-retry), and
      2. `await asyncio.sleep(expr)` where expr references the enclosing
         for-loop's induction variable (a linear/exponential backoff formula,
         e.g. `base * (attempt + 1)`).
    Unconditional pacing sleeps in poll loops (sleep(interval) in the loop
    body proper) are NOT flagged — those are schedules, not retries. The
    resilience package itself is exempt: BackoffPolicy.sleep is the one
    place allowed to spell this."""
    if "resilience" in Path(path).parts:
        return
    aliases = import_aliases(tree)

    def is_asyncio_sleep(node: ast.AST) -> bool:
        return (
            isinstance(node, ast.Await)
            and isinstance(node.value, ast.Call)
            and _resolved_call_name(node.value, aliases) == "asyncio.sleep"
        )

    def names_in(expr: ast.AST) -> set[str]:
        return {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}

    seen: set[tuple[int, int]] = set()  # nested loops share bodies

    def emit(node: ast.Await, why: str) -> Iterator[Violation]:
        key = (node.lineno, node.col_offset)
        if key in seen:
            return
        seen.add(key)
        yield Violation(
            path, node.lineno, node.col_offset, "DF024",
            f"{why} — use resilience.BackoffPolicy (exponential + seeded "
            "jitter) instead of a hand-rolled retry sleep",
        )

    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
            continue
        induction: set[str] = set()
        if isinstance(loop, (ast.For, ast.AsyncFor)):
            induction = names_in(loop.target)
        for stmt in loop.body + loop.orelse:
            for node in walk_pruned(stmt):
                # shape 2: sleep delay computed from the attempt variable
                if (
                    induction
                    and is_asyncio_sleep(node)
                    and node.value.args
                    and induction & names_in(node.value.args[0])
                ):
                    yield from emit(
                        node, "asyncio.sleep() delay derived from the retry attempt variable"
                    )
                # shape 1: sleep inside an except handler inside the loop
                if isinstance(node, (ast.Try,)):
                    for handler in node.handlers:
                        for h_stmt in handler.body:
                            for inner in walk_pruned(h_stmt):
                                if is_asyncio_sleep(inner):
                                    yield from emit(
                                        inner,
                                        "asyncio.sleep() inside an except handler in a retry loop",
                                    )


# RPC-client verbs whose awaited per-item use inside a loop marks an
# unbatched control-plane chatter path (DF025). `call` is the raw RpcClient
# entry; the rest are the scheduler/trainer client protocol verbs. The
# receiver type is invisible to an AST pass (transports hide behind
# protocols), so the verb set IS the signal.
RPC_LOOP_METHODS = {
    "call",
    "register_peer", "report_task_metadata", "report_piece_result",
    "report_pieces", "report_peer_result", "announce_task", "announce_host",
    "reschedule", "leave_peer", "leave_host", "stat_task", "sync_probes",
    "train_open", "train_chunk", "train_close",
}


def check_rpc_in_loop(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF025: awaited per-item RPC call inside a for/while loop outside rpc/.

    The control-plane twin of DF024: a loop that awaits one RPC round trip
    per item serializes the loop on the network and multiplies control-plane
    chatter by the item count — the shape that held a full
    report_piece_result round trip inline in the piece-worker path until the
    batched report buffer landed. Detected shape: `await <recv>.<verb>(...)`
    lexically inside a for/while body (the else block is excluded — it runs
    once after the loop) where <verb> is an RPC-client verb
    (RPC_LOOP_METHODS). Retry-of-one-call loops look identical to per-item
    loops statically; sites that genuinely retry a single call suppress with
    that reason. The rpc package itself is exempt — its retry/balancer
    internals are the transport, not per-item chatter."""
    if "rpc" in Path(path).parts:
        return
    seen: set[tuple[int, int]] = set()  # nested loops share bodies
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
            continue
        for stmt in loop.body:
            for node in walk_pruned(stmt):
                if not (
                    isinstance(node, ast.Await)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Attribute)
                    and node.value.func.attr in RPC_LOOP_METHODS
                ):
                    continue
                key = (node.lineno, node.col_offset)
                if key in seen:
                    continue
                seen.add(key)
                yield Violation(
                    path, node.lineno, node.col_offset, "DF025",
                    f"awaited RPC {node.value.func.attr}() once per loop "
                    "iteration — batch the items into one call (report_pieces "
                    "/ chunked upload) or hoist the round trip out of the loop",
                )


# Constructors whose per-item use marks hot-path thread churn (DF026).
# Canonical dotted names; from-imports resolve through import_aliases.
THREAD_CTORS = {"threading.Thread", "concurrent.futures.ThreadPoolExecutor"}


def check_thread_churn(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF026: ThreadPoolExecutor/Thread construction on a hot path.

    Spawning a thread costs ~100µs+ of syscalls and stack setup, and a pool
    constructed per call leaks its threads' lifetime management into the hot
    path — the process-level lesson behind PR 3's per-pump hasher threads
    (halved throughput) and PR 5/7's per-flush timer tasks. Three detected
    shapes:

      1. construction lexically inside a for/while body (per-item spawn);
      2. construction inside an `async def` — coroutines are the per-round/
         per-piece unit here, so a pool built in one is rebuilt per request
         (RoundDispatcher/PiecePipeline build theirs in __init__ instead);
      3. a plain-name call, inside a for/while body, to a SAME-MODULE
         function that constructs one (one level of indirection — the
         `stream()`-helper-in-a-measured-loop shape).

    Long-lived pools built at import, in __init__, or in plain sync helpers
    called once are not flagged. Deliberate per-iteration spawns (bench
    measurement legs, tests) suppress with a reason."""
    aliases = import_aliases(tree)

    def is_thread_ctor(node: ast.AST) -> bool:
        return (
            isinstance(node, ast.Call)
            and _resolved_call_name(node, aliases) in THREAD_CTORS
        )

    seen: set[tuple[int, int]] = set()

    def emit(node: ast.AST, why: str) -> Iterator[Violation]:
        key = (node.lineno, node.col_offset)
        if key in seen:
            return
        seen.add(key)
        yield Violation(
            path, node.lineno, node.col_offset, "DF026",
            f"{why} — bind workers to WORK: construct the thread/pool once "
            "(object __init__ / module setup) and submit items to it",
        )

    # functions that construct a thread/pool anywhere in their body (for
    # shape 3's one-level call-graph walk)
    constructing_fns: set[str] = set()
    for fn in ast.walk(tree):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for node in ast.walk(fn):
                if is_thread_ctor(node):
                    constructing_fns.add(fn.name)
                    break

    # shape 2: construction inside an async def (own body only — a nested
    # sync helper runs when called, which shapes 1/3 cover)
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.AsyncFunctionDef):
            continue
        for stmt in fn.body:
            for node in walk_pruned(stmt):
                if is_thread_ctor(node):
                    yield from emit(
                        node,
                        f"{_call_name(node)}() constructed inside async def "
                        f"{fn.name}() (coroutines run per round/piece)",
                    )

    # shapes 1 + 3: loops
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
            continue
        for stmt in loop.body:
            for node in walk_pruned(stmt):
                if is_thread_ctor(node):
                    yield from emit(
                        node,
                        f"{_call_name(node)}() constructed inside a loop "
                        "(one thread/pool per iteration)",
                    )
                elif (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in constructing_fns
                ):
                    yield from emit(
                        node,
                        f"{node.func.id}() constructs a thread/pool and is "
                        "called once per loop iteration",
                    )


def check_span_without_with(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF027: a `Tracer.span(...)` call not used as a `with` context manager.

    A Span only exports (and only resets the contextvar) in __exit__: a
    span() call whose result is dropped, stored, or awaited past never
    finishes — the trace silently loses the segment AND every later span in
    that task parents to a ghost. The tracer API is with-only by design
    (observability/tracing.py); the one legitimate split-enter/exit shape
    (a span closed by a different callback, e.g. upload's sendfile span)
    suppresses with a reason.

    Receiver heuristic: `<anything>.span(...)` where the receiver is a
    `default_tracer()`/`Tracer(...)` call or a name whose last segment
    mentions "tracer" (tracer, self._tracer, tr). Unrelated .span attributes
    on other objects don't match the heuristic."""
    aliases = import_aliases(tree)

    def tracerish(recv: ast.AST) -> bool:
        if isinstance(recv, ast.Call):
            name = _resolved_call_name(recv, aliases).rsplit(".", 1)[-1]
            return name in {"default_tracer", "Tracer"}
        name = dotted(recv).rsplit(".", 1)[-1].lower()
        return "tracer" in name or name == "tr"

    def is_span_call(node: ast.AST) -> bool:
        return (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "span"
            and tracerish(node.func.value)
        )

    with_items: set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                with_items.add(id(item.context_expr))

    for node in ast.walk(tree):
        if is_span_call(node) and id(node) not in with_items:
            yield Violation(
                path, node.lineno, node.col_offset, "DF027",
                "span() result must enter a `with` block (Span exports and "
                "resets the context only in __exit__; anything else leaks an "
                "unfinished span)",
            )


_BROAD = {"Exception", "BaseException"}


def _is_broad_handler(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    if isinstance(t, (ast.Name, ast.Attribute)):
        return dotted(t).split(".")[-1] in _BROAD
    if isinstance(t, ast.Tuple):
        return any(
            isinstance(e, (ast.Name, ast.Attribute))
            and dotted(e).split(".")[-1] in _BROAD
            for e in t.elts
        )
    return False


# DF029: wall-clock reads inside the sim/ package. Calls that read the
# process clock or sleep for real time — each one a way wall time can leak
# into virtual event ordering. datetime.now/utcnow/today are matched on the
# resolved dotted tail so both `datetime.now()` (from-import) and
# `datetime.datetime.now()` hit.
WALL_CLOCK_CALLS = {
    "time.time", "time.monotonic", "time.monotonic_ns", "time.time_ns",
    "time.perf_counter", "time.perf_counter_ns", "time.sleep",
    "asyncio.sleep",
}
_WALL_DATETIME_TAILS = ("datetime.now", "datetime.utcnow", "datetime.today")


def _in_sim_package(path: str) -> bool:
    parts = Path(path).parts
    return "sim" in parts


def check_wall_clock_in_sim(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF029: any wall-clock/real-sleep call inside sim/ — the virtual-clock
    discipline. Also flags `<something>loop.time()`: an event-loop time read
    is only virtual if the loop is the simulator's, which the linter cannot
    prove — route it through the engine's clock instead."""
    if not _in_sim_package(path):
        return
    aliases = import_aliases(tree)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _resolved_call_name(node, aliases)
        bad = (
            name in WALL_CLOCK_CALLS
            or name.endswith(_WALL_DATETIME_TAILS)
            or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "time"
                and "loop" in dotted(node.func.value).rsplit(".", 1)[-1].lower()
            )
        )
        if bad:
            yield Violation(
                path, node.lineno, node.col_offset, "DF029",
                f"{name or 'loop.time'}() inside sim/ mixes wall time into "
                "virtual event ordering — read the engine's injected clock "
                "(utils/clock.py) instead",
            )


def check_silent_swallow(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF031: broad except whose body is only pass/continue/ellipsis."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not _is_broad_handler(node):
            continue
        silent = all(
            isinstance(s, (ast.Pass, ast.Continue))
            or (
                isinstance(s, ast.Expr)
                and isinstance(s.value, ast.Constant)
                and s.value.value is Ellipsis
            )
            for s in node.body
        )
        if silent:
            kind = "bare except" if node.type is None else f"except {dotted(node.type) or 'Exception'}"
            yield Violation(
                path, node.lineno, node.col_offset, "DF031",
                f"{kind} silently swallows the error — narrow the type, log "
                "at debug level, or suppress with a reason",
            )


def check_np_ctor_in_row_loop(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF033: numpy array construction from per-row data inside a for loop.

    Fires when np.array/np.asarray/np.stack is called inside a for loop with
    an argument that references the loop's induction variable — the
    `np.asarray(row[...])`-per-row shape that made build_dataset O(rows) in
    Python. Calls whose arguments don't involve the loop variable (hoistable
    constants, accumulators) are not flagged, nor are while loops (no row
    variable to derive from), comprehensions, or the for-else block (it runs
    once after the loop, not per iteration)."""
    aliases = import_aliases(tree)
    seen: set[tuple[int, int]] = set()  # nested loops walk shared bodies
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor)):
            continue
        induction = {n.id for n in ast.walk(loop.target) if isinstance(n, ast.Name)}
        if not induction:
            continue
        for stmt in loop.body:
            for node in walk_pruned(stmt):
                if not isinstance(node, ast.Call):
                    continue
                name = _resolved_call_name(node, aliases)
                if name not in NP_ROW_CTORS:
                    continue
                arg_names: set[str] = set()
                for a in list(node.args) + [kw.value for kw in node.keywords]:
                    arg_names |= {n.id for n in ast.walk(a) if isinstance(n, ast.Name)}
                if not (induction & arg_names):
                    continue
                key = (node.lineno, node.col_offset)
                if key in seen:
                    continue
                seen.add(key)
                yield Violation(
                    path, node.lineno, node.col_offset, "DF033",
                    f"{_call_name(node)}() builds an array from loop variable "
                    f"{sorted(induction & arg_names)[0]!r} every iteration — "
                    "vectorize the pass (field slicing, np.unique/bincount/"
                    "reduceat) instead of per-row construction",
                )


# DF035: the scoring-hot-path functions whose per-round cost bounds
# scheduler rounds/s (ISSUE 18 — the native round driver moved this work
# into ONE GIL-released FFI call; Python loops here are the wall it removed)
_HOT_SCORING_FNS = {
    "evaluate", "evaluate_many", "evaluate_async", "_prepare",
    "build_pair_features", "_build_pair_features_rowwise",
    "_export_pair_rows", "_shadow_score", "_shadow_score_batch",
}
_HOT_ITER_NAME = re.compile(r"parent|cand|peer", re.I)


def check_py_loop_on_scoring_hot_path(
    tree: ast.Module, path: str
) -> Iterator[Violation]:
    """DF035: per-candidate Python loop inside a scoring hot-path function.

    Fires on a for loop or comprehension whose iterable names the round's
    candidate set (parents/candidates/peers) inside one of the scoring
    functions the round loop calls per scheduling round. The native layer
    (the loops live in C++ there), scheduler/scheduling.py (the snapshot
    loop under the state lock and the kept serial reference — the
    equivalence baseline), and tests are exempt. A deliberately-kept Python
    leg suppresses with its reason."""
    p = path.replace("\\", "/")
    if (
        "/native/" in p or p.startswith("native/")
        or p.endswith("scheduler/scheduling.py")
        or "tests/" in p or p.rsplit("/", 1)[-1].startswith("test_")
    ):
        return
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if fn.name not in _HOT_SCORING_FNS:
            continue
        seen: set[tuple[int, int]] = set()
        for node in ast.walk(fn):
            iters: list[ast.expr] = []
            if isinstance(node, (ast.For, ast.AsyncFor)):
                iters = [node.iter]
            elif isinstance(
                node, (ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp)
            ):
                iters = [g.iter for g in node.generators]
            for it in iters:
                names = {
                    n.id for n in ast.walk(it) if isinstance(n, ast.Name)
                } | {
                    n.attr for n in ast.walk(it) if isinstance(n, ast.Attribute)
                }
                hit = sorted(n for n in names if _HOT_ITER_NAME.search(n))
                if not hit:
                    continue
                key = (node.lineno, node.col_offset)
                if key in seen:
                    continue
                seen.add(key)
                yield Violation(
                    path, node.lineno, node.col_offset, "DF035",
                    f"per-candidate Python loop over {hit[0]!r} in hot-path "
                    f"{fn.name}() — O(candidates) Python work per scheduling "
                    "round; route the round through the native driver "
                    "(df_round_drive) or vectorize, or suppress with the "
                    "reason this serial leg is kept",
                )


# DF036: attributes whose mutation MUST ride the mirror's invalidation hooks
# (ISSUE 19). feat_version writes belong in bump_feat(); DAG adjacency sets
# (vertex .parents/.children) belong in Task.add_edge/delete_edge; the mirror
# registration fields belong to MirrorClient. The owning modules are exempt —
# they ARE the hooks.
_MIRRORED_VERSION_ATTRS = {"feat_version"}
_MIRROR_REG_ATTRS = {"_mirror", "_mirror_slot"}
_DAG_ADJ_ATTRS = {"parents", "children"}
# set/dict mutators only: DAG adjacency is sets; list-shaped .parents fields
# (ScheduleResult, decision records) mutate via append/extend and stay clean
_SET_MUTATORS = {"add", "discard", "remove", "clear", "update", "pop"}
# resource.py/mirror.py ARE the hooks; utils/dag.py is the adjacency
# primitive the hooked mutators (Task.add_edge/delete_edge, delete_peer)
# call INTO — its internal set surgery is below the mirror's abstraction
_DF036_EXEMPT = (
    "scheduler/resource.py", "scheduler/mirror.py", "utils/dag.py",
)


def check_mirrored_state_mutation(
    tree: ast.Module, path: str
) -> Iterator[Violation]:
    """DF036: mirrored peer/DAG/feature state mutated outside its hooks.

    Fires on (a) assignment or augmented assignment to a `feat_version`
    attribute — the version the mirror's row keys and delta stream hang off;
    (b) set-mutator calls on a `.parents`/`.children` attribute — DAG
    adjacency the mirror replays from the edge hooks; (c) assignment to
    `_mirror`/`_mirror_slot` — registration state only MirrorClient owns.
    The hook-owning modules (scheduler/resource.py, scheduler/mirror.py),
    the native layer, and tests are exempt."""
    p = path.replace("\\", "/")
    if (
        any(p.endswith(e) for e in _DF036_EXEMPT)
        or "/native/" in p or p.startswith("native/")
        or "tests/" in p or p.rsplit("/", 1)[-1].startswith("test_")
    ):
        return
    # `self._mirror = None` / `self._mirror_slot = -1` inside __init__ is
    # the field DECLARATION every mirrorable object carries (unregistered
    # until MirrorClient attaches) — not a mutation of live registration
    # state. Any constant-valued __init__ assignment qualifies.
    init_decls: set[int] = set()
    for fn in ast.walk(tree):
        if isinstance(fn, ast.FunctionDef) and fn.name == "__init__":
            for node in ast.walk(fn):
                if not isinstance(node, ast.Assign):
                    continue
                v = node.value
                if isinstance(v, ast.UnaryOp):  # -1 is UnaryOp(USub, Constant)
                    v = v.operand
                if isinstance(v, ast.Constant):
                    init_decls.add(id(node))
    for node in ast.walk(tree):
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            for t in targets:
                if not isinstance(t, ast.Attribute):
                    continue
                if t.attr in _MIRRORED_VERSION_ATTRS:
                    yield Violation(
                        path, node.lineno, node.col_offset, "DF036",
                        f"direct write to .{t.attr} bypasses the mirror's "
                        "delta stream — the native peer table keeps serving "
                        "stale state with no version tripwire; go through "
                        "bump_feat() (or suppress with the reason this site "
                        "cannot desynchronize the mirror)",
                    )
                elif t.attr in _MIRROR_REG_ATTRS and id(node) not in init_decls:
                    yield Violation(
                        path, node.lineno, node.col_offset, "DF036",
                        f"direct write to .{t.attr} — mirror registration "
                        "state is owned by MirrorClient attach/detach; a "
                        "stray write orphans the slot mapping (suppress with "
                        "the reason if this is deliberate unwiring)",
                    )
        elif isinstance(node, ast.Call):
            f = node.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr in _SET_MUTATORS
                and isinstance(f.value, ast.Attribute)
                and f.value.attr in _DAG_ADJ_ATTRS
            ):
                yield Violation(
                    path, node.lineno, node.col_offset, "DF036",
                    f"direct {f.attr}() on .{f.value.attr} mutates DAG "
                    "adjacency behind the mirror's back — edges must go "
                    "through Task.add_edge/delete_edge so the edge hook "
                    "pushes the child's new parent list (suppress with the "
                    "reason this set is not mirrored adjacency)",
                )


_MUTABLE_CTORS = {
    "list", "dict", "set", "bytearray", "collections.defaultdict",
    "defaultdict", "collections.deque", "deque", "collections.OrderedDict",
    "OrderedDict", "collections.Counter", "Counter",
}


def check_mutable_defaults(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF032: mutable default arguments."""
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        defaults = list(fn.args.defaults) + [
            d for d in fn.args.kw_defaults if d is not None
        ]
        for d in defaults:
            bad = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(d, ast.Call) and _call_name(d) in _MUTABLE_CTORS
            )
            if bad:
                name = getattr(fn, "name", "<lambda>")
                yield Violation(
                    path, d.lineno, d.col_offset, "DF032",
                    f"mutable default in {name}() is shared across calls; "
                    "default to None and construct inside",
                )


# ---------------------------------------------------------------------------
# DF028: dead metric families (cross-file)

# Mutating/labeling touches that prove a family is live. Reads (.value,
# .render) deliberately do NOT count — the bug class is a family that is
# scraped (read) forever but never moved (PR 11 shipped exactly that
# heartbeat shape).
_METRIC_TOUCH = {"inc", "dec", "set", "observe", "labels", "time"}
_METRIC_FACTORY_METHODS = {"counter", "gauge", "histogram"}
_METRIC_CTORS = {
    "dragonfly2_tpu.observability.metrics.Counter",
    "dragonfly2_tpu.observability.metrics.Gauge",
    "dragonfly2_tpu.observability.metrics.Histogram",
}


def _registryish(recv: ast.AST, aliases: dict[str, str]) -> bool:
    """Heuristic for 'this receiver is a MetricsRegistry': a call to
    default_registry()/MetricsRegistry(...), or a name whose last segment
    mentions 'registry'/'reg' or is the conventional `_r`."""
    if isinstance(recv, ast.Call):
        name = _resolved_call_name(recv, aliases).rsplit(".", 1)[-1]
        return name in {"default_registry", "MetricsRegistry"}
    name = dotted(recv).rsplit(".", 1)[-1].lower()
    return "registry" in name or name in {"_r", "reg", "r"}


def metric_family_decls(tree: ast.Module, aliases: dict[str, str]) -> list[tuple[str, int, int]]:
    """(name, line, col) for module-scope `NAME = registry.counter(...)` /
    `NAME = Counter(...)` (observability.metrics constructors, resolved
    through import aliases so collections.Counter never matches)."""
    out: list[tuple[str, int, int]] = []
    for stmt in tree.body:
        if isinstance(stmt, ast.AnnAssign):
            targets = [stmt.target] if stmt.target is not None else []
            value = stmt.value
        elif isinstance(stmt, ast.Assign):
            targets = stmt.targets
            value = stmt.value
        else:
            continue
        if value is None or not isinstance(value, ast.Call):
            continue
        if len(targets) != 1 or not isinstance(targets[0], ast.Name):
            continue
        func = value.func
        is_family = (
            isinstance(func, ast.Attribute)
            and func.attr in _METRIC_FACTORY_METHODS
            and _registryish(func.value, aliases)
        ) or (_resolved_call_name(value, aliases) in _METRIC_CTORS)
        if is_family:
            out.append((targets[0].id, stmt.lineno, stmt.col_offset))
    return out


def metric_family_touches(tree: ast.Module) -> set[str]:
    """Names that look metric-touched anywhere in this file: the receiver of
    an .inc/.dec/.set/.observe/.labels/.time attribute (``metrics.X.inc``,
    ``X.labels``), or a bare Name/Attribute passed as a call argument (test
    helpers take the family itself: ``_metric(sched_metrics.X, ...)``)."""
    out: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in _METRIC_TOUCH:
            name = dotted(node.value).rsplit(".", 1)[-1]
            if name:
                out.add(name)
        elif isinstance(node, ast.Call):
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(a, (ast.Name, ast.Attribute)):
                    name = dotted(a).rsplit(".", 1)[-1]
                    if name:
                        out.add(name)
    return out


def check_unused_metric_families(
    parsed: list[tuple[str, ast.Module]],
) -> Iterator[Violation]:
    """DF028 over the WHOLE run: a family declared at module scope in any
    file, whose name no file touches, is dead. Matching is by bare name
    (the same family is reached as `metrics.X`, `sched_metrics.X`, or a
    from-import `X`), which over-approves same-named families across
    modules — the safe direction for a linter."""
    touches: set[str] = set()
    decls: list[tuple[str, str, int, int]] = []
    for path, tree in parsed:
        aliases = import_aliases(tree)
        for name, line, col in metric_family_decls(tree, aliases):
            decls.append((path, name, line, col))
        touches |= metric_family_touches(tree)
    for path, name, line, col in decls:
        if name not in touches:
            yield Violation(
                path, line, col, "DF028",
                f"metric family {name!r} is declared but never touched by "
                ".inc/.dec/.set/.observe/.labels/.time anywhere in the "
                "linted tree — it renders as a frozen 0 dashboards read as "
                "healthy; wire it up or delete it",
            )


# ---------------------------------------------------------------------------
# DF030: dead alert rules (cross-file, DF028's inverse)

# The default namespace MetricsRegistry() composes into every family name;
# private registries (bench probes, ServiceMetrics) use their own, so rule
# metrics are ALSO matched on the namespace-less subsystem_name suffix.
_METRIC_DEFAULT_NAMESPACE = "dragonfly"


def _registryish_loose(recv: ast.AST, aliases: dict[str, str]) -> bool:
    """DF030's wider receiver heuristic: everything _registryish accepts,
    plus any name mentioning 'reg' (sreg, test_reg, self.registry) — for
    DECLARATION collection a looser net only ever clears more rules, the
    safe direction for a linter."""
    if _registryish(recv, aliases):
        return True
    name = dotted(recv).rsplit(".", 1)[-1].lower()
    return "reg" in name


def metric_declared_keys(
    tree: ast.Module, aliases: dict[str, str]
) -> tuple[set[str], set[str]]:
    """(full_names, suffix_keys) every metric factory call in this file can
    declare — ANY scope, not just module level (ServiceMetrics declares in
    __init__): `reg.counter("name", subsystem="s")` yields full name
    "dragonfly_s_name" and suffix key "s_name"; a direct
    observability.metrics constructor's first arg IS the full name.
    Non-constant names/subsystems are skipped (they cannot clear a rule)."""
    full: set[str] = set()
    suffix: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if (
            isinstance(func, ast.Attribute)
            and func.attr in _METRIC_FACTORY_METHODS
            and _registryish_loose(func.value, aliases)
        ):
            name = None
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                name = node.args[0].value
            subsystem = ""
            skip = False
            for kw in node.keywords:
                if kw.arg == "name" and isinstance(kw.value, ast.Constant):
                    name = kw.value.value
                if kw.arg == "subsystem":
                    if isinstance(kw.value, ast.Constant) and isinstance(kw.value.value, str):
                        subsystem = kw.value.value
                    else:
                        skip = True  # dynamic subsystem: unresolvable
            if name is None or skip:
                continue
            key = f"{subsystem}_{name}" if subsystem else name
            suffix.add(key)
            full.add(f"{_METRIC_DEFAULT_NAMESPACE}_{key}")
        elif _resolved_call_name(node, aliases) in _METRIC_CTORS:
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                full.add(node.args[0].value)
    return full, suffix


def alert_rule_metric_refs(
    tree: ast.Module, aliases: dict[str, str]
) -> list[tuple[str, str, int, int]]:
    """(kwarg, metric_name, line, col) for every AlertRule(metric=..., /
    denom=...) call with a constant string value."""
    out: list[tuple[str, str, int, int]] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        resolved = _resolved_call_name(node, aliases)
        if resolved.rsplit(".", 1)[-1] != "AlertRule":
            continue
        for kw in node.keywords:
            if kw.arg in ("metric", "denom") \
                    and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, str):
                out.append((kw.arg, kw.value.value, node.lineno, node.col_offset))
    return out


def check_dead_alert_rules(
    parsed: list[tuple[str, ast.Module]],
) -> Iterator[Violation]:
    """DF030 over the WHOLE run: an AlertRule metric/denom must name a
    family SOME file's registry constructor declares — exactly (default
    namespace) or by subsystem_name suffix (private namespaces). Matching
    by composed name, so renaming a family without updating its rules fails
    the gate instead of silencing the rule forever."""
    full: set[str] = set()
    suffix: set[str] = set()
    refs: list[tuple[str, str, str, int, int]] = []
    for path, tree in parsed:
        aliases = import_aliases(tree)
        f, s = metric_declared_keys(tree, aliases)
        full |= f
        suffix |= s
        for kwarg, metric, line, col in alert_rule_metric_refs(tree, aliases):
            refs.append((path, kwarg, metric, line, col))
    for path, kwarg, metric, line, col in refs:
        if metric in full:
            continue
        if any(metric.endswith("_" + k) or metric == k for k in suffix):
            continue
        yield Violation(
            path, line, col, "DF030",
            f"AlertRule {kwarg}={metric!r} names a metric family no "
            "registry constructor in the linted tree declares — the rule "
            "can never fire (a renamed family leaves its rules silently "
            "dead); point it at a declared family or delete it",
        )


def check_unbounded_queue(tree: ast.Module, path: str) -> Iterator[Violation]:
    """DF034: asyncio.Queue()/LifoQueue()/PriorityQueue() without a positive
    maxsize, or collections.deque() without a maxlen, in service code.

    Any explicit maxsize/maxlen argument clears the check (a variable bound
    means the author chose one; only the all-defaults spelling — which is
    unbounded — is flagged, and an explicit maxsize=0/maxlen=None reads as
    deliberately unbounded and needs the suppression + reason instead).
    Tests are exempt: a test's queue lives for one case, not for a node's
    uptime under overload."""
    parts = Path(path).parts
    if "tests" in parts or Path(path).name.startswith("test_"):
        return
    aliases = import_aliases(tree)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _resolved_call_name(node, aliases)
        tail = name.split(".")[-1]
        if name.startswith("asyncio.") and tail in (
            "Queue", "LifoQueue", "PriorityQueue"
        ):
            bounded = bool(node.args) or any(
                kw.arg == "maxsize"
                and not (isinstance(kw.value, ast.Constant) and not kw.value.value)
                for kw in node.keywords
            )
            if not bounded:
                yield Violation(
                    path, node.lineno, node.col_offset, "DF034",
                    f"{name}() without maxsize is an unbounded buffer — under "
                    "overload it converts backpressure into memory growth; "
                    "pass a bound (or suppress with the reason it can't grow)",
                )
        elif name in ("collections.deque", "deque"):
            # deque(iterable, maxlen) — a second positional IS the bound
            bounded = len(node.args) >= 2 or any(
                kw.arg == "maxlen"
                and not (isinstance(kw.value, ast.Constant) and kw.value.value is None)
                for kw in node.keywords
            )
            if not bounded:
                yield Violation(
                    path, node.lineno, node.col_offset, "DF034",
                    "deque() without maxlen is an unbounded buffer — under "
                    "overload it converts backpressure into memory growth; "
                    "pass maxlen (or suppress with the reason it can't grow)",
                )


ALL_CHECKS = (
    check_tracer_coercion,
    check_jnp_in_loop,
    check_unsynced_timing,
    check_static_arg_literals,
    check_asyncio_primitive_scope,
    check_sleep_in_async,
    check_lock_discipline,
    check_raw_retry_sleep,
    check_rpc_in_loop,
    check_thread_churn,
    check_span_without_with,
    check_wall_clock_in_sim,
    check_silent_swallow,
    check_mutable_defaults,
    check_np_ctor_in_row_loop,
    check_py_loop_on_scoring_hot_path,
    check_mirrored_state_mutation,
    check_unbounded_queue,
)


# ---------------------------------------------------------------------------
# driver


def _per_file_violations(
    tree: ast.Module, sup: Suppressions, path: str
) -> list[Violation]:
    """DF001 + every per-file check against an already-parsed tree."""
    out: list[Violation] = [
        Violation(path, line, 0, "DF001", f"unknown check id {check_id!r} in suppression")
        for line, check_id in sup.unknown
    ]
    for check in ALL_CHECKS:
        for v in check(tree, path):
            if not sup.allows(v):
                out.append(v)
    return out


def lint_source(source: str, path: str = "<string>") -> list[Violation]:
    """All PER-FILE violations for one file's source, suppressions applied.
    DF028/DF030 are cross-file (a family declared here may be incremented —
    or a rule's family declared — anywhere) and only run in run_sources()/
    the CLI driver."""
    sup = Suppressions(source)
    if sup.skip_file:  # full opt-out, including DF001 (fixture/vendored files)
        return []
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [
            Violation(path, line, 0, "DF001", f"unknown check id {check_id!r} in suppression")
            for line, check_id in sup.unknown
        ] + [
            Violation(path, e.lineno or 1, e.offset or 0, "DF002", f"syntax error: {e.msg}")
        ]
    out = _per_file_violations(tree, sup, path)
    out.sort(key=lambda v: (v.line, v.col, v.check))
    return out


def discover(paths: list[str]) -> list[Path]:
    files: list[Path] = []
    for p in paths:
        pth = Path(p)
        if pth.is_dir():
            files.extend(
                f
                for f in sorted(pth.rglob("*.py"))
                if not any(part.startswith(".") for part in f.parts)
            )
        elif pth.is_file():
            files.append(pth)
        else:
            raise FileNotFoundError(f"no such file or directory: {p}")
    return files


def run_sources(sources: dict[str, str]) -> list[Violation]:
    """Per-file checks plus the cross-file passes (DF028 dead families,
    DF030 dead alert rules) over one run's worth of sources — each file
    parsed ONCE, the tree shared by every pass. skip-file sources contribute
    their metric TOUCHES/DECLARATIONS to the cross-file passes (a fixture
    may legitimately be the only caller or declarer) but are never flagged
    themselves."""
    out: list[Violation] = []
    parsed: list[tuple[str, ast.Module]] = []
    flaggable: dict[str, Suppressions] = {}
    for path, source in sources.items():
        sup = Suppressions(source)
        try:
            tree = ast.parse(source, filename=path)
        except SyntaxError as e:
            if not sup.skip_file:
                out.extend(
                    Violation(path, line, 0, "DF001",
                              f"unknown check id {check_id!r} in suppression")
                    for line, check_id in sup.unknown
                )
                out.append(Violation(
                    path, e.lineno or 1, e.offset or 0, "DF002",
                    f"syntax error: {e.msg}",
                ))
            continue
        parsed.append((path, tree))
        if sup.skip_file:
            continue
        flaggable[path] = sup
        out.extend(_per_file_violations(tree, sup, path))
    for cross_check in (check_unused_metric_families, check_dead_alert_rules):
        for v in cross_check(parsed):
            sup = flaggable.get(v.path)
            if sup is not None and not sup.allows(v):
                out.append(v)
    out.sort(key=lambda v: (v.path, v.line, v.col, v.check))
    return out


def run_paths(paths: list[str]) -> list[Violation]:
    return run_sources(
        {str(f): f.read_text(encoding="utf-8") for f in discover(paths)}
    )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="dflint", description="repo-native JAX + concurrency lints"
    )
    ap.add_argument("paths", nargs="*", help="files or directories to lint")
    ap.add_argument(
        "--list-checks", action="store_true", help="print the check catalog and exit"
    )
    ap.add_argument(
        "--quiet", action="store_true", help="suppress the per-violation lines"
    )
    args = ap.parse_args(argv)

    if args.list_checks:
        for check_id in sorted(CHECKS):
            print(f"{check_id}  {CHECKS[check_id]}")
        return 0
    if not args.paths:
        ap.print_usage(sys.stderr)
        print("dflint: error: no paths given", file=sys.stderr)
        return 2

    try:
        files = discover(args.paths)
    except FileNotFoundError as e:
        print(f"dflint: error: {e}", file=sys.stderr)
        return 2
    violations = run_sources(
        {str(f): f.read_text(encoding="utf-8") for f in files}
    )

    if not args.quiet:
        for v in violations:
            print(v.render())
    status = "clean" if not violations else f"{len(violations)} violation(s)"
    print(f"dflint: {len(files)} file(s), {status}")
    return 1 if violations else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        sys.exit(2)
