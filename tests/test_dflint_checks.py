"""Unit tests for every dflint check: each ID fires on a known-bad fixture
and stays silent on a known-good one, plus suppression/exit-code contracts."""
# dflint: skip-file  (fixture strings deliberately contain bad code/ids)

from __future__ import annotations

import importlib.util
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DFLINT = REPO / "tools" / "dflint.py"

_spec = importlib.util.spec_from_file_location("dflint", DFLINT)
dflint = importlib.util.module_from_spec(_spec)
sys.modules["dflint"] = dflint  # dataclasses resolves types via sys.modules
_spec.loader.exec_module(dflint)


def ids(src: str, path: str = "dragonfly2_tpu/daemon/mod.py") -> list[str]:
    return sorted({v.check for v in dflint.lint_source(textwrap.dedent(src), path)})


def lines(src: str, path: str = "dragonfly2_tpu/daemon/mod.py") -> list[int]:
    return [v.line for v in dflint.lint_source(textwrap.dedent(src), path)]


# ---------------------------------------------------------------------------
# DF011 tracer coercion


def test_df011_fires_on_decorated_jit():
    src = """
    import jax

    @jax.jit
    def f(x):
        return float(x) * 2
    """
    assert ids(src) == ["DF011"]


def test_df011_fires_on_jit_wrapped_lambda_and_named_def():
    src = """
    import jax

    g = jax.jit(lambda x: int(x))

    def h(x):
        return bool(x)

    h_jit = jax.jit(h)
    """
    vs = dflint.lint_source(textwrap.dedent(src), "m.py")
    assert [v.check for v in vs] == ["DF011", "DF011"]


def test_df011_fires_on_partial_jit():
    src = """
    from functools import partial
    import jax

    @partial(jax.jit, static_argnums=(1,))
    def f(x, k):
        return float(x)
    """
    assert ids(src) == ["DF011"]


def test_df011_silent_outside_trace_and_on_constants():
    src = """
    import jax

    @jax.jit
    def f(x):
        return x * float("inf")

    def g(x):
        return float(x)
    """
    assert ids(src) == []


# ---------------------------------------------------------------------------
# DF012 jnp in Python loop


_LOOP_SRC = """
import jax.numpy as jnp

def f(xs):
    out = []
    for x in xs:
        out.append(jnp.sin(x))
    return out
"""


def test_df012_fires_in_ops_models_parallel():
    for d in ("ops", "models", "parallel"):
        assert ids(_LOOP_SRC, f"dragonfly2_tpu/{d}/mod.py") == ["DF012"]


def test_df012_silent_outside_scoped_dirs():
    assert ids(_LOOP_SRC, "dragonfly2_tpu/daemon/mod.py") == []


def test_df012_silent_without_loop_or_inside_nested_def():
    src = """
    import jax.numpy as jnp

    def f(xs):
        return jnp.sin(xs)

    def g(xs):
        fns = []
        for i in range(3):
            fns.append(lambda x: jnp.cos(x))
        return fns
    """
    assert ids(src, "dragonfly2_tpu/ops/mod.py") == []


# ---------------------------------------------------------------------------
# DF013 unsynced timing window


def test_df013_fires_on_unsynced_window():
    src = """
    import time
    import jax.numpy as jnp

    def bench(x):
        t0 = time.perf_counter()
        y = jnp.dot(x, x)
        return time.perf_counter() - t0
    """
    assert ids(src) == ["DF013"]


def test_df013_silent_with_block_until_ready():
    src = """
    import time
    import jax.numpy as jnp

    def bench(x):
        t0 = time.perf_counter()
        y = jnp.dot(x, x)
        y.block_until_ready()
        return time.perf_counter() - t0
    """
    assert ids(src) == []


def test_df013_silent_with_d2h_materialization():
    # float()/np.asarray() pull the value to host — the host cannot hold a
    # value the device has not finished computing, so the pull is a sync
    src = """
    import time
    import numpy as np
    import jax.numpy as jnp

    def bench_a(x):
        t0 = time.perf_counter()
        y = float(jnp.dot(x, x).sum())
        return time.perf_counter() - t0

    def bench_b(x):
        t0 = time.perf_counter()
        y = np.asarray(jnp.dot(x, x))
        return time.perf_counter() - t0
    """
    assert ids(src) == []


def test_df013_silent_without_jax_in_window():
    src = """
    import time

    def bench(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0
    """
    assert ids(src) == []


# ---------------------------------------------------------------------------
# DF014 non-hashable static args


def test_df014_fires_on_list_literal_for_static_argnum():
    src = """
    import jax

    def f(x, opts):
        return x

    g = jax.jit(f, static_argnums=(1,))

    def main(x):
        return g(x, [1, 2])
    """
    assert ids(src) == ["DF014"]


def test_df014_fires_on_dict_literal_for_static_argname():
    src = """
    from functools import partial
    import jax

    @partial(jax.jit, static_argnames=("opts",))
    def f(x, opts=None):
        return x

    def main(x):
        return f(x, opts={"a": 1})
    """
    assert ids(src) == ["DF014"]


def test_df014_silent_on_hashable_static_args():
    src = """
    import jax

    def f(x, opts):
        return x

    g = jax.jit(f, static_argnums=(1,))

    def main(x):
        return g(x, (1, 2))
    """
    assert ids(src) == []


# ---------------------------------------------------------------------------
# DF021 asyncio primitive at import/class scope


def test_df021_fires_at_module_and_class_scope():
    src = """
    import asyncio

    LOCK = asyncio.Lock()

    class A:
        EV = asyncio.Event()
    """
    vs = dflint.lint_source(textwrap.dedent(src), "m.py")
    assert [v.check for v in vs] == ["DF021", "DF021"]


def test_df021_silent_inside_functions():
    src = """
    import asyncio

    def make():
        return asyncio.Queue()

    async def run():
        lock = asyncio.Lock()
        async with lock:
            pass
    """
    # (the unbounded Queue still draws DF034 — DF021's scope check is what
    # this fixture pins: function-local primitives bind the right loop)
    assert "DF021" not in ids(src)


# ---------------------------------------------------------------------------
# DF022 time.sleep in async def


def test_df022_fires_in_async_def():
    src = """
    import time

    async def f():
        time.sleep(1)
    """
    assert ids(src) == ["DF022"]


def test_df022_catches_from_import_alias():
    src = """
    from time import sleep
    from time import sleep as snooze

    async def f():
        sleep(1)
        snooze(2)
    """
    vs = dflint.lint_source(textwrap.dedent(src), "m.py")
    assert [v.check for v in vs] == ["DF022", "DF022"]


def test_df021_catches_from_import_alias():
    src = """
    from asyncio import Lock, Queue

    Q = Queue()

    class A:
        L = Lock()
    """
    vs = dflint.lint_source(textwrap.dedent(src), "m.py")
    assert [v.check for v in vs if v.check == "DF021"] == ["DF021", "DF021"]


def test_df022_silent_in_sync_def_and_asyncio_sleep():
    src = """
    import asyncio
    import time

    def f():
        time.sleep(1)

    async def g():
        await asyncio.sleep(1)

    async def h():
        def inner():
            time.sleep(1)
        return inner
    """
    assert ids(src) == []


# ---------------------------------------------------------------------------
# DF023 inconsistent lock discipline


def test_df023_fires_on_mixed_locked_unlocked_mutation():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = {}

        def put(self, k, v):
            with self._lock:
                self._items[k] = v

        def drop(self, k):
            self._items.pop(k, None)
    """
    vs = dflint.lint_source(textwrap.dedent(src), "m.py")
    assert [v.check for v in vs] == ["DF023"]
    assert vs[0].line == 14


def test_df023_sees_tuple_unpack_targets():
    # the guarded mutation is a tuple unpack; the unlocked one must still flag
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._a = None

        def locked(self):
            with self._lock:
                self._a, other = 1, 2

        def unlocked(self):
            self._a = 3
    """
    vs = dflint.lint_source(textwrap.dedent(src), "m.py")
    assert [v.check for v in vs] == ["DF023"]
    assert vs[0].line == 14


def test_df023_silent_when_discipline_is_consistent():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = {}
            self._free = []

        def put(self, k, v):
            with self._lock:
                self._items[k] = v

        def drop(self, k):
            with self._lock:
                self._items.pop(k, None)

        def note(self, x):
            # never touched under the lock anywhere: the lock does not
            # guard it, so no inconsistency exists
            self._free.append(x)
    """
    assert ids(src) == []


def test_df023_asyncio_lock_variant():
    src = """
    import asyncio

    class C:
        def __init__(self):
            self._lock = asyncio.Lock()
            self._items = {}

        async def put(self, k, v):
            async with self._lock:
                self._items[k] = v

        async def drop(self, k):
            self._items.pop(k, None)
    """
    assert ids(src) == ["DF023"]


# ---------------------------------------------------------------------------
# DF024 raw retry sleep


def test_df024_fires_on_sleep_in_except_in_loop():
    src = """
    import asyncio

    async def pull():
        while True:
            try:
                await fetch()
            except Exception:
                await asyncio.sleep(5.0)
                continue
    """
    vs = dflint.lint_source(textwrap.dedent(src), "dragonfly2_tpu/daemon/mod.py")
    assert [v.check for v in vs] == ["DF024"]
    assert vs[0].line == 9


def test_df024_fires_on_attempt_derived_delay():
    src = """
    import asyncio

    async def call(retries, base):
        for attempt in range(retries):
            ok = await try_once()
            if not ok:
                await asyncio.sleep(base * (attempt + 1))
    """
    assert ids(src) == ["DF024"]


def test_df024_sees_from_import_alias():
    src = """
    from asyncio import sleep as snooze

    async def f():
        for attempt in range(3):
            try:
                await go()
            except OSError:
                await snooze(0.5)
    """
    assert ids(src) == ["DF024"]


def test_df024_silent_on_unconditional_poll_pacing():
    # a poll loop's schedule sleep is pacing, not a retry ladder
    src = """
    import asyncio

    async def poll(interval):
        while True:
            await refresh()
            await asyncio.sleep(interval)
    """
    assert ids(src) == []


def test_df024_silent_inside_resilience_package():
    src = """
    import asyncio

    async def sleep_for(attempt, base):
        for attempt in range(3):
            await asyncio.sleep(base * attempt)
    """
    assert ids(src, path="dragonfly2_tpu/resilience/backoff.py") == []


def test_df024_silent_on_policy_sleep():
    # the shared-policy call is exactly what the check pushes people toward
    src = """
    async def call(policy, retries):
        for attempt in range(retries):
            try:
                return await once()
            except OSError:
                await policy.sleep(attempt)
    """
    assert ids(src) == []


# ---------------------------------------------------------------------------
# DF025 awaited per-item RPC call in a loop


def test_df025_fires_on_per_item_report_in_for_loop():
    src = """
    async def report_all(scheduler, peer_id, indices):
        for idx in indices:
            await scheduler.report_piece_result(peer_id, idx, success=True)
    """
    vs = dflint.lint_source(textwrap.dedent(src), "dragonfly2_tpu/daemon/mod.py")
    assert [v.check for v in vs] == ["DF025"]
    assert vs[0].line == 4


def test_df025_fires_on_raw_call_in_while_loop():
    src = """
    async def drive(client):
        while True:
            await client.call("download", {"url": "u"})
    """
    assert ids(src) == ["DF025"]


def test_df025_silent_outside_loops_and_in_else_block():
    src = """
    async def once(scheduler, peer_id):
        await scheduler.report_piece_result(peer_id, 0, success=True)

    async def scan(scheduler, peer_id, xs):
        for x in xs:
            check(x)
        else:
            await scheduler.report_peer_result(peer_id, success=True)
    """
    assert ids(src) == []


def test_df025_silent_on_non_rpc_methods_in_loop():
    src = """
    async def drain(queue, store):
        for item in queue:
            await store.write_piece(0, item)
            await queue.join()
    """
    assert ids(src) == []


def test_df025_silent_inside_rpc_package():
    # the transport's own retry loop around one call is not per-item chatter
    src = """
    async def call(self, method, payload):
        for attempt in range(self.retries):
            return await self._inner.call(method, payload)
    """
    assert ids(src, path="dragonfly2_tpu/rpc/core.py") == []


def test_df025_not_hidden_by_nested_def():
    # code in a nested def runs later, not per iteration of this loop
    src = """
    async def outer(client, xs):
        for x in xs:
            async def later():
                await client.call("m", x)
            register(later)
    """
    assert ids(src) == []


# ---------------------------------------------------------------------------
# DF026 thread/pool construction on a hot path


def test_df026_fires_on_thread_in_for_loop():
    src = """
    import threading

    def fan_out(pieces):
        for p in pieces:
            t = threading.Thread(target=handle, args=(p,))
            t.start()
    """
    vs = dflint.lint_source(textwrap.dedent(src), "dragonfly2_tpu/daemon/mod.py")
    assert [v.check for v in vs] == ["DF026"]
    assert vs[0].line == 6


def test_df026_fires_on_pool_in_async_def():
    src = """
    from concurrent.futures import ThreadPoolExecutor

    async def handle_round(child):
        pool = ThreadPoolExecutor(max_workers=2)
        return pool.submit(score, child)
    """
    assert ids(src) == ["DF026"]


def test_df026_fires_on_constructing_helper_called_in_loop():
    src = """
    import threading

    def make_sender(payload):
        t = threading.Thread(target=send, args=(payload,))
        t.start()
        return t

    def run(payloads):
        for p in payloads:
            make_sender(p)
    """
    # the construction site inside the helper is NOT flagged (plain sync
    # function), but its per-iteration call site is
    vs = dflint.lint_source(textwrap.dedent(src), "dragonfly2_tpu/daemon/mod.py")
    assert [(v.check, v.line) for v in vs] == [("DF026", 11)]


def test_df026_silent_on_init_and_module_scope():
    src = """
    import threading
    from concurrent.futures import ThreadPoolExecutor

    _GLOBAL_POOL = ThreadPoolExecutor(max_workers=4)

    class Dispatcher:
        def __init__(self, workers):
            self._pool = ThreadPoolExecutor(max_workers=workers)
            self._watchdog = threading.Thread(target=self._watch, daemon=True)
    """
    assert ids(src) == []


def test_df026_silent_on_nested_def_inside_loop():
    # the nested def's body runs when CALLED, not per iteration here
    src = """
    import threading

    def build(items):
        for it in items:
            def later():
                return threading.Thread(target=noop)
            register(later)
    """
    assert ids(src) == []


def test_df026_silent_on_unrelated_ctor_names():
    src = """
    async def handle(items):
        for it in items:
            t = Task(it)
            w = Worker(it)
    """
    assert ids(src) == []


# ---------------------------------------------------------------------------
# DF027 span without with


def test_df027_fires_on_dropped_span_call():
    src = """
    from dragonfly2_tpu.observability.tracing import default_tracer

    def f(tracer):
        tracer.span("work", piece=3)
        default_tracer().span("also-dropped")
    """
    assert ids(src) == ["DF027"]
    assert len(lines(src)) == 2


def test_df027_fires_on_assigned_and_awaited_shapes():
    src = """
    def f(self):
        sp = self._tracer.span("stored")
        return sp
    """
    assert ids(src) == ["DF027"]


def test_df027_silent_on_with_usage():
    src = """
    from dragonfly2_tpu.observability.tracing import default_tracer

    async def f(tracer, tr):
        with tracer.span("a") as sp:
            sp.set_attr("k", 1)
        with default_tracer().span("b"), tr.span("c"):
            pass
    """
    assert ids(src) == []


def test_df027_silent_on_unrelated_span_attrs():
    src = """
    def f(doc, layout):
        doc.span("not a tracer")
        layout.row.span(3)
    """
    assert ids(src) == []


def test_df027_suppression_with_reason():
    src = """
    def f(tracer):
        sp = tracer.span("split-lifecycle")  # dflint: disable=DF027 closed by the response's prepare()
        sp.__enter__()
        return sp
    """
    assert ids(src) == []


def test_df027_fires_inside_async_def_too():
    src = """
    async def f(tracer):
        tracer.span("never-entered")
        await do_work()
    """
    assert ids(src) == ["DF027"]


# ---------------------------------------------------------------------------
# DF031 silent swallow


def test_df031_fires_on_silent_broad_handlers():
    src = """
    def f():
        try:
            work()
        except Exception:
            pass

    def g(xs):
        for x in xs:
            try:
                use(x)
            except:
                continue
    """
    vs = dflint.lint_source(textwrap.dedent(src), "m.py")
    assert [v.check for v in vs] == ["DF031", "DF031"]


def test_df031_silent_on_narrow_or_logged_handlers():
    src = """
    import logging

    logger = logging.getLogger(__name__)

    def f():
        try:
            work()
        except ValueError:
            pass

    def g():
        try:
            work()
        except Exception as e:
            logger.debug("swallowed: %s", e)
    """
    assert ids(src) == []


# ---------------------------------------------------------------------------
# DF032 mutable defaults


def test_df032_fires_on_mutable_defaults():
    src = """
    def f(x, items=[]):
        return items

    def g(x, *, m={}):
        return m

    def h(x, d=dict()):
        return d
    """
    vs = dflint.lint_source(textwrap.dedent(src), "m.py")
    assert [v.check for v in vs] == ["DF032", "DF032", "DF032"]


def test_df032_silent_on_none_and_immutable_defaults():
    src = """
    def f(x, items=None, k=3, name="a", t=(1, 2)):
        return items or []
    """
    assert ids(src) == []


# ---------------------------------------------------------------------------
# DF033 per-row numpy construction in a loop


def test_df033_fires_on_per_row_construction():
    src = """
    import numpy as np

    def f(rows):
        out = []
        for row in rows:
            out.append(np.asarray(row["pair_features"], np.float32))
        return np.stack(out)
    """
    vs = dflint.lint_source(textwrap.dedent(src), "m.py")
    assert [v.check for v in vs] == ["DF033"]
    assert vs[0].line == 7


def test_df033_fires_on_array_stack_and_tuple_targets():
    src = """
    import numpy as np

    def f(probes, groups):
        for (s, d), stats in groups.items():
            agg = np.stack(stats)
        for row in probes:
            v = np.array([row["a"], row["b"]], np.float32)
    """
    vs = dflint.lint_source(textwrap.dedent(src), "m.py")
    assert [v.check for v in vs] == ["DF033", "DF033"]


def test_df033_sees_from_import_alias():
    src = """
    from numpy import asarray

    def f(rows):
        for row in rows:
            x = asarray(row)
    """
    assert ids(src) == ["DF033"]


def test_df033_silent_without_loop_var_or_loop():
    src = """
    import numpy as np

    SCALE = np.array([1.0, 2.0])

    def f(rows, template):
        hoisted = np.asarray(template, np.float32)  # loop-invariant, hoistable
        for row in rows:
            total = np.array(template)  # not derived from the row
            consume(row, total)
        i = 0
        while i < 10:
            i += 1
        return np.stack([hoisted])
    """
    assert ids(src) == []


def test_df033_silent_in_for_else_block():
    src = """
    import numpy as np

    def f(rows):
        for row in rows:
            consume(row)
        else:
            summary = np.array(row)  # runs once after the loop, not per row
        return summary
    """
    assert ids(src) == []


def test_df033_suppression_with_reason():
    src = """
    import numpy as np

    def f(rows):
        for row in rows:
            x = np.asarray(row)  # dflint: disable=DF033 rowloop reference
    """
    assert ids(src) == []


# ---------------------------------------------------------------------------
# DF034 unbounded queue in service code


def test_df034_fires_on_unbounded_queue_and_deque():
    src = """
    import asyncio
    import collections

    class S:
        def start(self):
            self.q = asyncio.Queue()
            self.pq = asyncio.PriorityQueue()
            self.buf = collections.deque()
    """
    assert ids(src) == ["DF034"]
    assert lines(src) == [7, 8, 9]


def test_df034_fires_on_explicitly_unbounded_spellings():
    # maxsize=0 / maxlen=None are the unbounded DEFAULTS written out — still
    # a buffer that grows without limit, still needs the suppression + reason
    src = """
    import asyncio
    from collections import deque

    def f():
        q = asyncio.Queue(maxsize=0)
        d = deque(maxlen=None)
    """
    assert lines(src) == [6, 7]


def test_df034_silent_on_bounded():
    src = """
    import asyncio
    import collections

    def f(items, cap):
        q = asyncio.Queue(maxsize=cap)
        q2 = asyncio.Queue(64)
        d = collections.deque(maxlen=256)
        d2 = collections.deque(items, 32)
    """
    assert ids(src) == []


def test_df034_silent_in_tests():
    src = """
    import asyncio

    def f():
        q = asyncio.Queue()
    """
    assert ids(src, "tests/test_mod.py") == []
    assert ids(src, "dragonfly2_tpu/daemon/test_helper.py") == []


def test_df034_suppression_with_reason():
    src = """
    import collections

    def f():
        d = collections.deque()  # dflint: disable=DF034 drained same-loop
    """
    assert ids(src) == []


# ---------------------------------------------------------------------------
# DF035 per-candidate Python loop on the scoring hot path (ISSUE 18)

_DF035_HOT_SRC = """
def evaluate(self, child, parents):
    rows = [self.row(p) for p in parents]
    for p in parents:
        touch(p)
    return rows
"""


def test_df035_fires_in_hot_function():
    path = "dragonfly2_tpu/scheduler/evaluator.py"
    assert ids(_DF035_HOT_SRC, path) == ["DF035"]
    assert lines(_DF035_HOT_SRC, path) == [3, 4]  # comp + for loop


def test_df035_fires_on_candidate_named_attributes():
    # the iterable can be an attribute chain (self.candidates) — the NAME
    # match covers attribute segments too
    src = """
    def _prepare(self, child, parents):
        return [x for x in self.candidates]
    """
    assert ids(src, "dragonfly2_tpu/scheduler/rollout.py") == ["DF035"]


def test_df035_silent_outside_hot_functions():
    src = """
    def commit(self, parents):
        for p in parents:
            touch(p)
    """
    assert ids(src, "dragonfly2_tpu/scheduler/service.py") == []


def test_df035_silent_on_non_candidate_iterables():
    src = """
    def evaluate(self, child, rows):
        for r in rows:
            touch(r)
    """
    assert ids(src, "dragonfly2_tpu/scheduler/evaluator.py") == []


def test_df035_exempt_paths():
    # the native layer, the snapshot loop's module, and tests keep their
    # per-candidate loops without suppressions
    for path in (
        "dragonfly2_tpu/native/scorer.py",
        "dragonfly2_tpu/scheduler/scheduling.py",
        "tests/test_round_driver.py",
    ):
        assert ids(_DF035_HOT_SRC, path) == [], path


def test_df035_suppression_with_reason():
    src = """
    def evaluate(self, child, parents):
        for p in parents:  # dflint: disable=DF035 kept serial reference leg
            touch(p)
    """
    assert ids(src, "dragonfly2_tpu/scheduler/evaluator.py") == []


# ---------------------------------------------------------------------------
# DF036 mirrored state mutated outside its invalidation hooks


def test_df036_fires_on_direct_feat_version_write():
    src = """
    def refresh(peer):
        peer.feat_version += 1
        peer.host.feat_version = 7
    """
    path = "dragonfly2_tpu/scheduler/service.py"
    assert ids(src, path) == ["DF036"]
    assert lines(src, path) == [3, 4]


def test_df036_fires_on_dag_adjacency_mutators():
    src = """
    def rewire(task, child, pid):
        task.dag.vertex(child).parents.add(pid)
        task.dag.vertex(child).children.discard(pid)
    """
    assert ids(src, "dragonfly2_tpu/scheduler/service.py") == ["DF036"]


def test_df036_fires_on_mirror_registration_write():
    src = """
    def hijack(peer):
        peer._mirror_slot = 3
    """
    assert ids(src, "dragonfly2_tpu/scheduler/service.py") == ["DF036"]


def test_df036_silent_on_init_declaration_and_bump_feat():
    # the __init__-scope None declaration and the hook-firing mutator are
    # the sanctioned shapes
    src = """
    class Host:
        def __init__(self):
            self._mirror = None
            self._mirror_slot = -1

        def bump_feat(self):
            touch(self.feat_version)
    """
    assert ids(src, "dragonfly2_tpu/scheduler/service.py") == []


def test_df036_silent_on_list_shaped_parents():
    # ScheduleResult.parents / record["parents"] are lists: append/extend
    # are not set mutators and Name-rooted accesses are not adjacency
    src = """
    def collect(out, parents):
        out.parents.append(parents[0])
        parents.clear()
    """
    assert ids(src, "dragonfly2_tpu/scheduler/service.py") == []


def test_df036_exempt_paths():
    src = """
    def surgical(v, pid):
        v.parents.discard(pid)
        v.feat_version = 1
    """
    for path in (
        "dragonfly2_tpu/scheduler/resource.py",
        "dragonfly2_tpu/scheduler/mirror.py",
        "dragonfly2_tpu/utils/dag.py",
        "dragonfly2_tpu/native/scorer.py",
        "tests/test_mirror.py",
    ):
        assert ids(src, path) == [], path


def test_df036_suppression_with_reason():
    src = """
    def toggle(sched, client):
        sched._mirror = client  # dflint: disable=DF036 A/B leg toggle of the one attached client
    """
    assert ids(src, "dragonfly2_tpu/cli/dfstress.py") == []


# ---------------------------------------------------------------------------
# DF028 dead metric family (cross-file: run_sources, not lint_source)


def xids(sources: dict[str, str]) -> list[str]:
    return sorted(
        {v.check for v in dflint.run_sources(
            {p: textwrap.dedent(s) for p, s in sources.items()}
        )}
    )


_DECL = """
from dragonfly2_tpu.observability.metrics import default_registry

_r = default_registry()
DEAD_TOTAL = _r.counter("dead_total", "never moved")
LIVE_TOTAL = _r.counter("live_total", "moved below")
LIVE_TOTAL.inc()
"""


def test_df028_fires_on_module_scope_family_never_touched():
    assert xids({"dragonfly2_tpu/x/metrics.py": _DECL}) == ["DF028"]
    vs = dflint.run_sources({"m.py": textwrap.dedent(_DECL)})
    assert len(vs) == 1 and "DEAD_TOTAL" in vs[0].message


def test_df028_cleared_by_touch_in_another_file():
    user = """
    from dragonfly2_tpu.x import metrics

    def f():
        metrics.DEAD_TOTAL.inc()
    """
    assert xids({"dragonfly2_tpu/x/metrics.py": _DECL, "dragonfly2_tpu/x/user.py": user}) == []


def test_df028_cleared_by_labels_and_by_helper_argument():
    labels_user = """
    import metrics
    metrics.DEAD_TOTAL.labels(kind="a").inc()
    """
    assert xids({"m.py": _DECL, "u.py": labels_user}) == []
    # a family passed bare into a helper (the test-suite idiom
    # `_metric(sched_metrics.X, ...)`) counts as touched
    arg_user = """
    import metrics
    def probe(m):
        return m.labels().value
    probe(metrics.DEAD_TOTAL)
    """
    assert xids({"m.py": _DECL, "u.py": arg_user}) == []


def test_df028_direct_ctor_fires_but_collections_counter_does_not():
    src = """
    from dragonfly2_tpu.observability.metrics import Counter
    from collections import Counter as CCounter

    ORPHAN = Counter("orphan_total", "never moved", ())
    WORDS = CCounter()
    WORDS.update("abc")
    """
    vs = dflint.run_sources({"m.py": textwrap.dedent(src)})
    assert [v.check for v in vs] == ["DF028"]
    assert "ORPHAN" in vs[0].message


def test_df028_ignores_instance_scope_and_honors_suppression():
    inst = """
    from dragonfly2_tpu.observability.metrics import default_registry

    class M:
        def __init__(self):
            self.h = default_registry().histogram("h_seconds")
    """
    assert xids({"m.py": inst}) == []
    sup = _DECL.replace(
        'DEAD_TOTAL = _r.counter("dead_total", "never moved")',
        'DEAD_TOTAL = _r.counter("dead_total", "x")  # dflint: disable=DF028 exported for plugins',
    )
    assert xids({"m.py": sup}) == []


def test_df028_not_run_per_file():
    # lint_source is the per-file API; the cross-file pass must not fire
    # there (a lone metrics.py would false-positive on every family)
    assert "DF028" not in ids(_DECL)


# ---------------------------------------------------------------------------
# DF030 dead alert rules (cross-file, DF028's inverse)


_RULE_DECL = """
from dragonfly2_tpu.observability.metrics import default_registry

_r = default_registry()
SYNCS_TOTAL = _r.counter("syncs_total", "moved", subsystem="scheduler")
SYNCS_TOTAL.inc()
"""


def test_df030_fires_on_rule_naming_undeclared_family():
    rule = """
    from dragonfly2_tpu.observability.alerts import AlertRule

    RULES = [AlertRule(name="a", metric="dragonfly_scheduler_sync_total", bound=1.0)]
    """
    vs = dflint.run_sources({
        "m.py": textwrap.dedent(_RULE_DECL), "r.py": textwrap.dedent(rule),
    })
    assert [v.check for v in vs] == ["DF030"]
    assert "dragonfly_scheduler_sync_total" in vs[0].message


def test_df030_cleared_by_declaration_in_another_file():
    rule = """
    from dragonfly2_tpu.observability.alerts import AlertRule

    RULES = [AlertRule(name="a", metric="dragonfly_scheduler_syncs_total", bound=1.0)]
    """
    assert xids({"m.py": _RULE_DECL, "r.py": rule}) == []


def test_df030_checks_denom_too():
    rule = """
    from dragonfly2_tpu.observability.alerts import AlertRule

    R = AlertRule(name="a", kind="ratio",
                  metric="dragonfly_scheduler_syncs_total",
                  denom="dragonfly_scheduler_gone_total", bound=0.1)
    """
    vs = dflint.run_sources({
        "m.py": textwrap.dedent(_RULE_DECL), "r.py": textwrap.dedent(rule),
    })
    assert [v.check for v in vs] == ["DF030"]
    assert "denom" in vs[0].message


def test_df030_private_namespace_matches_on_suffix():
    # a private-namespace registry (bench probes, test fixtures) composes a
    # different prefix; the rule matches on the subsystem_name suffix
    decl = """
    from dragonfly2_tpu.observability.metrics import MetricsRegistry

    sreg = MetricsRegistry(namespace="bench")
    c = sreg.counter("c0_total")
    c.inc()
    """
    rule = """
    from dragonfly2_tpu.observability.alerts import AlertRule

    R = AlertRule(name="a", metric="bench_c0_total", bound=1.0)
    """
    assert xids({"m.py": decl, "r.py": rule}) == []


def test_df030_instance_scope_declaration_counts():
    # ServiceMetrics declares inside __init__ — DF030 collects declarations
    # at ANY scope (unlike DF028's module-scope flag targets)
    decl = """
    from dragonfly2_tpu.observability.metrics import MetricsRegistry

    class M:
        def __init__(self):
            self.registry = MetricsRegistry()
            self.h = self.registry.histogram(
                "lag_seconds", subsystem="loop")
            self.h.observe(0.1)
    """
    rule = """
    from dragonfly2_tpu.observability.alerts import AlertRule

    R = AlertRule(name="a", kind="quantile",
                  metric="dragonfly_loop_lag_seconds", bound=0.25)
    """
    assert xids({"m.py": decl, "r.py": rule}) == []


def test_df030_nonconstant_metric_skipped_and_suppression_honored():
    dynamic = """
    from dragonfly2_tpu.observability.alerts import AlertRule

    def make(name):
        return AlertRule(name="a", metric=name, bound=1.0)
    """
    assert xids({"r.py": dynamic}) == []
    sup = """
    from dragonfly2_tpu.observability.alerts import AlertRule

    R = AlertRule(name="a", metric="dragonfly_not_declared_total", bound=1.0)  # dflint: disable=DF030 family registered by an out-of-tree plugin
    """
    assert xids({"r.py": sup}) == []


def test_df030_not_run_per_file():
    rule = """
    from dragonfly2_tpu.observability.alerts import AlertRule

    R = AlertRule(name="a", metric="dragonfly_never_declared_total", bound=1.0)
    """
    assert "DF030" not in ids(rule)


# ---------------------------------------------------------------------------
# DF029 wall-clock reads inside sim/ (virtual-clock discipline)

_SIM_PATH = "dragonfly2_tpu/sim/engine.py"


def test_df029_fires_on_wall_clock_reads_in_sim():
    src = """
    import time

    def now():
        return time.time()

    def tick():
        return time.monotonic()
    """
    vs = dflint.lint_source(textwrap.dedent(src), _SIM_PATH)
    assert [v.check for v in vs] == ["DF029", "DF029"]
    assert "virtual" in vs[0].message


def test_df029_fires_on_from_import_and_perf_counter_and_sleep():
    src = """
    import asyncio
    from time import perf_counter, sleep

    async def f():
        t0 = perf_counter()
        sleep(0.1)
        await asyncio.sleep(0.1)
        return t0
    """
    # sleep() in async also trips DF022 — both are right; DF029 must cover
    # perf_counter, time.sleep, and asyncio.sleep
    checks = ids(src, _SIM_PATH)
    assert "DF029" in checks
    vs = [v for v in dflint.lint_source(textwrap.dedent(src), _SIM_PATH)
          if v.check == "DF029"]
    assert len(vs) == 3


def test_df029_fires_on_loop_time_and_datetime_now():
    src = """
    import asyncio
    import datetime

    def f(loop):
        a = loop.time()
        b = asyncio.get_event_loop().time()
        return a, b, datetime.datetime.now()
    """
    vs = [v for v in dflint.lint_source(textwrap.dedent(src), _SIM_PATH)
          if v.check == "DF029"]
    # loop.time() hits via the loop-receiver heuristic (the get_event_loop()
    # chain has a dynamic receiver and is out of dotted-name reach);
    # datetime.now via the resolved tail
    assert len(vs) == 2


def test_df029_silent_outside_sim_and_on_injected_clock():
    src = """
    import time

    def now():
        return time.time()
    """
    assert "DF029" not in ids(src, "dragonfly2_tpu/daemon/engine.py")
    clock_src = """
    class Engine:
        def now(self):
            return self.clock.time() + self.clock.monotonic()
    """
    assert ids(clock_src, _SIM_PATH) == []


def test_df029_suppressible_with_reason():
    src = """
    import time

    def meter():
        return time.perf_counter()  # dflint: disable=DF029 wall events/s meter
    """
    assert ids(src, _SIM_PATH) == []


# ---------------------------------------------------------------------------
# suppression handling


def test_same_line_disable_is_honored():
    src = """
    def f(x, items=[]):  # dflint: disable=DF032
        return items
    """
    assert ids(src) == []


def test_disable_only_silences_listed_ids():
    src = """
    def f(x, items=[]):  # dflint: disable=DF031
        return items
    """
    assert ids(src) == ["DF001", "DF032"] or ids(src) == ["DF032"]


def test_multi_id_disable():
    src = """
    import time

    async def f(x, items=[]):
        time.sleep(1); g(items)  # noqa
    """
    # sanity: both fire without suppression
    assert ids(src) == ["DF022", "DF032"]
    src2 = """
    import time

    async def f(x, items=[]):  # dflint: disable=DF032
        time.sleep(1)  # dflint: disable=DF022
    """
    assert ids(src2) == []


def test_skip_file_is_honored():
    src = """\
    # dflint: skip-file
    def f(x, items=[]):
        return items
    """
    assert ids(src) == []


def test_unknown_check_id_is_rejected():
    src = """
    def f(x, items=[]):  # dflint: disable=DF999
        return items
    """
    got = ids(src)
    assert "DF001" in got
    # the bogus id must not silence the real finding either
    assert "DF032" in got


def test_syntax_error_is_reported_not_crashed():
    assert ids("def f(:\n    pass\n") == ["DF002"]


# ---------------------------------------------------------------------------
# CLI exit codes: 0 clean / 1 violations / 2 crash-bad-usage


def _run_cli(args: list[str]) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, str(DFLINT), *args],
        capture_output=True,
        text=True,
        timeout=60,
    )


def test_cli_exit_0_on_clean_file(tmp_path):
    f = tmp_path / "clean.py"
    f.write_text("x = 1\n")
    p = _run_cli([str(f)])
    assert p.returncode == 0, p.stdout + p.stderr
    assert "clean" in p.stdout


def test_cli_exit_1_on_violations(tmp_path):
    f = tmp_path / "bad.py"
    f.write_text("def f(x, items=[]):\n    return items\n")
    p = _run_cli([str(f)])
    assert p.returncode == 1, p.stdout + p.stderr
    assert "DF032" in p.stdout


def test_cli_exit_2_on_missing_path():
    p = _run_cli(["/no/such/path_xyz"])
    assert p.returncode == 2


def test_cli_exit_2_on_no_paths():
    p = _run_cli([])
    assert p.returncode == 2


def test_cli_list_checks():
    p = _run_cli(["--list-checks"])
    assert p.returncode == 0
    for check_id in ("DF011", "DF023", "DF032"):
        assert check_id in p.stdout
