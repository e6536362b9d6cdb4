"""The collector's watch (observability/gcwatch.py): windows count the
collections by generation with their total and longest milliseconds, a
generation-2 collection is a `python.gc` span under whatever span it paused,
and without the watch installed a window claims nothing."""

from __future__ import annotations

import gc

import pytest

from dragonfly2_tpu.observability import tracing
from dragonfly2_tpu.observability.gcwatch import GcWatch


@pytest.fixture
def watch():
    w = GcWatch()
    w.install(tracing.Tracer(sample_rate=1.0))
    yield w
    w.uninstall()


def test_without_the_hook_a_window_claims_nothing():
    w = GcWatch()
    assert w.open() is None and w.close(None) is None
    gc.collect()
    assert not w.installed


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_window_counts_a_collection_by_its_generation(watch, generation):
    window = watch.open()
    gc.collect(generation)
    report = watch.close(window)
    assert report["collections"][generation] >= 1
    assert 0 < report["max_ms"] <= report["ms"]


def test_a_closed_window_stops_counting_and_others_go_on(watch):
    first, second = watch.open(), watch.open()
    gc.collect()
    closed = watch.close(first)
    gc.collect()
    gc.collect()
    still = watch.close(second)
    assert still["collections"][2] >= closed["collections"][2] + 2
    assert first.report() == closed  # nothing counted into it after its close


def test_a_full_collection_is_a_span_under_the_span_it_paused():
    tracer = tracing.Tracer(sample_rate=1.0)
    w = GcWatch()
    w.install(tracer)
    try:
        with tracer.span("trainer.gnn.call") as call:
            gc.collect(0)   # generations 0 and 1 are counted, not spans
            gc.collect()
    finally:
        w.uninstall()
    spans = [s for s in tracer.finished() if s.name == "python.gc"]
    assert spans and all(s.attrs["generation"] == 2 for s in spans)
    assert spans[0].parent_id == call.span_id and spans[0].trace_id == call.trace_id
    assert spans[0].duration_ms > 0
    # the collecting thread's current span is the one it paused, again
    assert tracing.Tracer.current() is None


def test_an_unsampled_trace_leaves_no_collector_span():
    tracer = tracing.Tracer(sample_rate=0.0)
    w = GcWatch()
    w.install(tracer)
    try:
        window = w.open()
        gc.collect()
        report = w.close(window)
    finally:
        w.uninstall()
    assert report["collections"][2] >= 1 and tracer.finished() == []


def test_install_is_once_and_uninstall_takes_the_hook_away():
    w = GcWatch()
    w.install(tracing.Tracer())
    w.install(tracing.Tracer())
    assert gc.callbacks.count(w._on_gc) == 1
    w.uninstall()
    assert w._on_gc not in gc.callbacks and w.open() is None


def test_a_span_that_ends_inside_a_collection_while_the_tracer_holds_its_lock():
    """The tracer's lock is re-entrant: a collection that starts while this
    thread exports a span (json inside the lock allocates) ends its own span
    inside the export, not in a deadlock."""
    tracer = tracing.Tracer(sample_rate=1.0)
    w = GcWatch()
    w.install(tracer)
    try:
        with tracer._lock:
            gc.collect()
    finally:
        w.uninstall()
    assert [s.name for s in tracer.finished()] == ["python.gc"]


def test_windows_opened_and_closed_from_many_threads_lose_none():
    """`open` and `close` replace the window tuple under a lock while
    collections read it: from more threads than cores, with the interpreter
    switching threads every few microseconds, every window closed is gone and
    none still open is lost."""
    import sys
    import threading

    w = GcWatch()
    w.install(tracing.Tracer(sample_rate=0.0))
    kept = []
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def churn():
        mine = w.open()
        kept.append(mine)
        for _ in range(300):
            w.close(w.open())
            if _ % 100 == 0:
                gc.collect(0)

    try:
        threads = [threading.Thread(target=churn) for _ in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert sorted(map(id, w._windows)) == sorted(map(id, kept))
        gc.collect()
        assert all(k.collections[2] >= 1 for k in kept)
    finally:
        sys.setswitchinterval(switch)
        w.uninstall()
