"""Trainer metric families + the per-step training-run telemetry hook.

The reference shipped a trainer/metrics package with families and no
training loop; this repo had the opposite — a real training loop that
emitted ONE span and a registry row per run (ISSUE 15's black box). These
families put per-step learner signals on the trainer's existing metrics
plane: the timeseries recorder samples them (trainer/server.py starts the
default recorder), so loss/grad-norm curves and steps-per-s ride /debug/ts,
the stats frame, and dftop like any other service's health — the MFU/
throughput methodology of PAPERS.md "Scalable Training of Language Models
using JAX pjit and TPUv4" applied to the cluster's own learners.

TrainRunTelemetry is the hook object the trainers call: train_mlp.train and
train_gnn.train_async accept `telemetry=` and report host-visible steps as
they complete. It also keeps a BOUNDED per-run loss curve (stride-halving
downsample, ≤ _CURVE_CAP points) for the run manifest `train_history`
serves — dfml prints these curves without ever shipping full step logs.
"""

from __future__ import annotations

import math
import statistics
import threading

from dragonfly2_tpu.observability.metrics import default_registry
from dragonfly2_tpu.utils import clock as clockmod

_r = default_registry()

TRAIN_STEPS_TOTAL = _r.counter(
    "steps_total",
    "Optimizer steps completed, per model type (rate = steps/s)",
    subsystem="train", labels=("model",),
)
TRAIN_EXAMPLES_TOTAL = _r.counter(
    "examples_total",
    "Training examples consumed (steps x batch size), per model type",
    subsystem="train", labels=("model",),
)
TRAIN_LOSS = _r.gauge(
    "loss",
    "Most recent training-step loss, per model type (curves ride /debug/ts)",
    subsystem="train", labels=("model",),
)
TRAIN_GRAD_NORM = _r.gauge(
    "grad_norm",
    "Most recent global gradient norm, per model type (pre-clip; a "
    "diverging run shows here steps before the loss does)",
    subsystem="train", labels=("model",),
)
TRAIN_RUNS_TOTAL = _r.counter(
    "runs_total",
    "Training runs by outcome (ok | error | skipped)",
    subsystem="train", labels=("result",),
)
TRAIN_LAST_RUN_LOSS = _r.gauge(
    "last_run_loss",
    "Final loss of the most recent completed run (gnn when trained, else "
    "mlp) — the stats-frame / dftop headline",
    subsystem="train",
)

# per-run curve bound: past this many retained points every other one is
# dropped and the retention stride doubles — deterministic, bounded, and the
# curve keeps its overall shape (classic stride-halving decimation)
_CURVE_CAP = 160


class TrainRunTelemetry:
    """Per-step telemetry sink for ONE model's training inside one run.

    The trainers call on_step() with host-visible losses as they land (the
    MLP every sampled step, the GNN once per scan call with the whole call's
    losses) — each call updates the dragonfly_train_* families above and the
    bounded curve. Thread-safe: the trainers run on worker threads while the
    trainer's event loop answers status RPCs.

    Clock-injected (DF029): rates derive from the injected monotonic clock,
    so a virtual-clock harness measures virtual steps/s deterministically.
    """

    def __init__(
        self,
        model: str,
        *,
        batch_size: int = 0,
        clock: clockmod.Clock | None = None,
    ):
        self.model = model
        self.batch_size = int(batch_size)
        self._clock = clock or clockmod.SYSTEM
        self._lock = threading.Lock()
        self.steps = 0
        self.examples = 0
        self.last_loss = math.nan
        self.last_grad_norm: float | None = None
        self._curve: list[tuple[int, float]] = []
        self._curve_stride = 1
        # mesh + per-device bytes of the placed run (train_gnn._placement);
        # None for a trainer that places nothing (the MLP)
        self.placement: dict | None = None
        # the scan programs the process keeps after the run, and whether one
        # of them served it (on_kept; None for the MLP, which keeps none)
        self.kept: dict | None = None
        # the scan calls' periods and turns, and whether the run traced its
        # program (on_calls: both trainers report it at the run's end)
        self.calls: dict | None = None
        # the run's rate from its calls (on_calls), once it has them
        self._calls_rate: float | None = None
        # set by a caller whose event loop hands the calls back: the (start,
        # end) of every ingest handler that loop ran during the run, on the
        # calls' clock (the trainer service's; None where nobody counts them)
        self.loop_ingest: list[tuple[float, float]] | None = None
        # steps/s anchors at the FIRST report, not construction: the gap
        # between them is XLA setup + first-call compile (5-30 s on CPU),
        # which would understate a short run's throughput 10x+. The first
        # report's own steps are excluded too (they include the compile).
        self._t_first: float | None = None
        self._steps_at_first = 0
        self._t_last = self._clock.monotonic()

    def on_step(
        self,
        loss: float,
        grad_norm: float | None = None,
        *,
        steps: int = 1,
        examples: int | None = None,
    ) -> None:
        """Report `steps` completed optimizer steps whose latest loss is
        `loss`. examples defaults to steps x batch_size."""
        n = int(steps)
        ex = int(examples) if examples is not None else n * self.batch_size
        loss = float(loss)
        with self._lock:
            self.steps += n
            self.examples += ex
            self.last_loss = loss
            if grad_norm is not None:
                self.last_grad_norm = float(grad_norm)
            self._t_last = self._clock.monotonic()
            if self._t_first is None:
                self._t_first = self._t_last
                self._steps_at_first = self.steps
            if self.steps % self._curve_stride == 0 or not self._curve:
                self._curve.append((self.steps, loss))
                if len(self._curve) > _CURVE_CAP:
                    self._curve = self._curve[::2]
                    self._curve_stride *= 2
        TRAIN_STEPS_TOTAL.inc(n, model=self.model)
        if ex:
            TRAIN_EXAMPLES_TOTAL.inc(ex, model=self.model)
        TRAIN_LOSS.set(loss, model=self.model)
        if grad_norm is not None:
            TRAIN_GRAD_NORM.set(float(grad_norm), model=self.model)

    def on_placed(self, placement: dict) -> None:
        """Record where the run's arrays were placed (once, after set-up)."""
        with self._lock:
            self.placement = placement

    def on_kept(self, *, programs: int, served: bool) -> None:
        """Record, once placed, how many compiled scan programs the process
        keeps and whether one kept from an earlier run served this one."""
        with self._lock:
            self.kept = {"programs": programs, "served": served}

    def on_calls(
        self,
        calls: list[tuple[float, float, float]],
        *,
        traced: int,
        first_steps: int,
        gc_ms: float | None = None,
    ) -> None:
        """Record, once at the run's end, how the host paced the run's scan
        calls: `calls` holds each call's (start, enqueued, end) on one
        monotonic clock, the enqueue being the jitted call's return and the
        rest the D2H pull. A period runs from one call's start to the next
        one's, a turn from one call's end to the next one's start (reports,
        the log line, the thread hand-off). The first call compiles or loads
        the program unless the trainer kept it from an earlier run, so
        periods, dispatches and pulls count from the second and the first
        call's start to end is `first_ms`; `traced` is how often the run
        traced the scan program (1 where it built it, 0 where the kept one
        served: `first_ms` is then a period like any other). `stall_ms` is the
        time by which periods exceeded 1.5 x their median: what the host held
        the chip back, in a run nobody traced; `dispatch_ms_max` and
        `pull_ms_max` say on which side of the enqueue the longest wait fell,
        `gc_ms` what the collector took while the calls ran (None where no
        `observability.gcwatch` is installed). With `loop_ingest` set, a gap
        is the host's part of the wait between two calls, one call's end to
        the next one's enqueue; `in_ingest` lists the calls during which, or
        in whose gap after, an ingest handler ran on the loop, and
        `gap_ms_in_ingest` / `gap_ms_clear` are the median gap after such a
        call and after the others. The run's rate
        is the steps after the first call (`first_steps` of them in it) over
        the second call's start to the last one's end."""
        starts = [a for a, _, _ in calls]
        periods = [(b - a) * 1e3 for a, b in zip(starts[1:], starts[2:])]
        turns = [(nxt - end) * 1e3 for (_, _, end), nxt in zip(calls, starts[1:])]
        p50 = statistics.median(periods) if periods else None
        summary = {
            "count": len(calls),
            "traced": traced,
            "first_ms": _round3((calls[0][2] - calls[0][0]) * 1e3 if calls else None),
            "period_ms_p50": _round3(p50),
            "period_ms_max": _round3(max(periods, default=None)),
            "turn_ms_p50": _round3(statistics.median(turns) if turns else None),
            "turn_ms_max": _round3(max(turns, default=None)),
            "stall_ms": _round3(sum(max(0.0, p - 1.5 * p50) for p in periods)),
            "dispatch_ms_max": _round3(max(((b - a) * 1e3 for a, b, _ in calls[1:]), default=None)),
            "pull_ms_max": _round3(max(((c - b) * 1e3 for _, b, c in calls[1:]), default=None)),
            "gc_ms": gc_ms,
        }
        if self.loop_ingest is not None:
            summary.update(_ingest_marks(calls, self.loop_ingest))
        with self._lock:
            self.calls = summary
            post = self.steps - first_steps
            wall = calls[-1][2] - calls[1][0] if len(calls) > 1 else 0.0
            self._calls_rate = post / wall if post > 0 and wall > 0 else None

    def steps_per_sec(self) -> float | None:
        with self._lock:
            return self._steps_per_sec_locked()

    def _steps_per_sec_locked(self) -> float | None:
        if self.calls is not None:
            return self._calls_rate
        if self._t_first is None:
            return None
        wall = self._t_last - self._t_first
        post = self.steps - self._steps_at_first
        if post <= 0 or wall <= 0:
            return None  # one report = no interval to rate over
        return post / wall

    def curve(self) -> list[tuple[int, float]]:
        with self._lock:
            return list(self._curve)

    def summary(self) -> dict:
        """Per-model slice of the run manifest (trainer/service.py)."""
        with self._lock:
            sps = self._steps_per_sec_locked()
            if sps is not None:
                sps = round(sps, 2)
            return {
                "steps": self.steps,
                "examples": self.examples,
                "final_loss": None if math.isnan(self.last_loss) else round(self.last_loss, 6),
                "grad_norm": (
                    None if self.last_grad_norm is None
                    else round(self.last_grad_norm, 6)
                ),
                "steps_per_sec": sps,
                "curve": [(s, round(v, 6)) for s, v in self._curve],
                "placement": self.placement,
                "kept": self.kept,
                "calls": self.calls,
            }


def _round3(value: float | None) -> float | None:
    return None if value is None else round(value, 3)


def _ingest_marks(calls: list[tuple[float, float, float]], busy: list[tuple[float, float]]) -> dict:
    """Which calls an ingest handler on the loop ran during or after (from
    the call's start to the next call's enqueue; the last call's end), and
    the median gap (one call's end to the next one's enqueue) after those
    calls and after the others."""
    ends = [enqueued for _, enqueued, _ in calls[1:]] + [calls[-1][2]] if calls else []
    marked = [i for i, ((start, _, _), until) in enumerate(zip(calls, ends))
              if any(a < until and b > start for a, b in busy)]
    hit, gaps = set(marked), {True: [], False: []}
    for i, ((_, _, end), (_, enqueued, _)) in enumerate(zip(calls, calls[1:])):
        gaps[i in hit].append((enqueued - end) * 1e3)
    return {
        "in_ingest": marked,
        "gap_ms_in_ingest": _round3(statistics.median(gaps[True]) if gaps[True] else None),
        "gap_ms_clear": _round3(statistics.median(gaps[False]) if gaps[False] else None),
    }
