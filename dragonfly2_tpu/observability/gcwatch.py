"""The garbage collector's pauses, counted where they fall.

A process that installs the watch (the trainer's server, at boot) hears
every collection through `gc.callbacks`: its generation and how long it ran,
on the monotonic clock. Nothing keeps a log of them. A caller that wants to
know what the collector took over some stretch opens a `GcWindow` and closes
it at the stretch's end; the window holds the collections by generation, their
total and their longest milliseconds. Each generation-2 collection is also a
`python.gc` span of the tracer (attr `generation`), entered when the collector
starts and left when it stops, on the collecting thread: inside a sampled span
(a `trainer.gnn.call`) it is a child of that span, and on a profiler's host
plane it is an event on the device trace's clock.

Cost with the watch installed: two clock reads and a loop over the open
windows (one to three) a collection; a generation-2 collection adds what a
span costs (an object and a contextvar where unsampled).
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any


class GcWindow:
    """What the collector took from `open` to `close`: collections by
    generation, their total and longest milliseconds."""

    __slots__ = ("collections", "ms", "max_ms")

    def __init__(self) -> None:
        self.collections = [0, 0, 0]
        self.ms = 0.0
        self.max_ms = 0.0

    def report(self) -> dict:
        return {"collections": list(self.collections), "ms": round(self.ms, 3), "max_ms": round(self.max_ms, 3)}


class GcWatch:
    """The process's one hook into `gc.callbacks` (`install`, idempotent)."""

    def __init__(self) -> None:
        self.installed = False
        self._tracer: Any = None
        # a tuple, replaced whole under the lock: a collection iterates the
        # one it read while another thread opens or closes a window
        self._windows: tuple[GcWindow, ...] = ()
        self._lock = threading.Lock()
        self._t0 = 0.0
        self._span: Any = None

    def install(self, tracer: Any) -> None:
        """Hear every collection from now on; generation-2 ones become spans
        of `tracer`."""
        self._tracer = tracer
        if not self.installed:
            gc.callbacks.append(self._on_gc)
            self.installed = True

    def uninstall(self) -> None:
        if self.installed:
            gc.callbacks.remove(self._on_gc)
            self.installed = False

    def open(self) -> GcWindow | None:
        """A window counting from now, or None where no watch is installed
        (nothing would count: a window of zeros would claim no pauses)."""
        if not self.installed:
            return None
        window = GcWindow()
        with self._lock:
            self._windows = self._windows + (window,)
        return window

    def close(self, window: GcWindow | None) -> dict | None:
        """Stop counting into `window`; its report, or None for no window."""
        if window is None:
            return None
        with self._lock:
            self._windows = tuple(w for w in self._windows if w is not window)
        return window.report()

    def _on_gc(self, phase: str, info: dict) -> None:
        # the collector cannot run again while its callbacks do, so one
        # collection is in flight per process: `_t0` and `_span` are its
        try:
            generation = info["generation"]
            if phase == "start":
                if generation == 2 and self._tracer is not None:
                    self._span = self._tracer.span(  # dflint: disable=DF027 entered at the collector's start, left at its stop
                        "python.gc", generation=generation
                    )
                    self._span.__enter__()
                self._t0 = time.perf_counter()
                return
            ms = (time.perf_counter() - self._t0) * 1e3
            if self._span is not None:
                span, self._span = self._span, None
                span.__exit__(None, None, None)
            for w in self._windows:
                w.collections[generation] += 1
                w.ms += ms
                if ms > w.max_ms:
                    w.max_ms = ms
        except Exception:  # noqa: BLE001 — a callback that raises is printed by gc on every collection
            self._span = None


_watch = GcWatch()


def default_watch() -> GcWatch:
    return _watch
