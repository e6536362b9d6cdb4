"""The system under test, started the way it is deployed, with the benchmark's
probes beside it.

    python benchmarks/trainer_child.py <repo root> -- <flags of dragonfly2_tpu.trainer.server>

runs `dragonfly2_tpu.trainer.server.main()` — flags only, nothing built by
hand — in this process, which is the one process that opens the accelerator.
The harness (run.py) stays free of jax and talks to the server over its RPC
port like any scheduler. What only the process that holds the chip can see is
answered here, on a control channel (JSON lines on stdin, replies on stdout
after CTL_PREFIX):

  steps        every report the trainers made to their telemetry hook
               (`trainer.metrics.TrainRunTelemetry.on_step`: model, steps so
               far, loss, gradient norm) with the monotonic time it was made —
               the window's edges and the first steps' losses come from it
  compiles     `jax.monitoring` compile and compile-cache events, timed
  trace_start  `jax.profiler.start_trace` / `stop_trace` around a window; a
  trace_stop   marker annotation ties the profiler's clock to the host's

The hook is wrapped, not replaced: the program's own call runs first and its
arguments are passed through untouched.
"""

from __future__ import annotations

import json
import sys
import threading
import time
import weakref

from serverproc import CTL_PREFIX
from trace_reduce import MARKER


class Probes:
    def __init__(self) -> None:
        self.steps: list[tuple] = []
        self.compiles: list[tuple] = []
        # one number per telemetry sink (one sink per model per training run);
        # keyed by the object, not by id(): an id is reused once a sink is freed
        self._sinks: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()
        self._n_sinks = 0

    def install(self) -> None:
        from jax import monitoring

        from dragonfly2_tpu.trainer import metrics as train_metrics

        original = train_metrics.TrainRunTelemetry.on_step
        steps, sinks = self.steps, self._sinks

        def on_step(sink, loss, grad_norm=None, **kw):
            original(sink, loss, grad_norm, **kw)
            run = sinks.get(sink)
            if run is None:
                run = sinks[sink] = self._n_sinks
                self._n_sinks += 1
            steps.append((
                time.monotonic(), run, sink.model, sink.steps, float(loss),
                None if grad_norm is None else float(grad_norm),
            ))

        train_metrics.TrainRunTelemetry.on_step = on_step
        compiles = self.compiles
        monitoring.register_event_duration_secs_listener(
            lambda event, secs, **kw: compiles.append((time.monotonic(), event, float(secs)))
        )
        monitoring.register_event_listener(
            lambda event, **kw: compiles.append((time.monotonic(), event, None))
        )


def _control_loop(probes: Probes) -> None:
    import jax

    for line in sys.stdin:
        try:
            req = json.loads(line)
            cmd = req["cmd"]
            if cmd == "steps":
                out = {"events": probes.steps[req.get("since", 0):]}
            elif cmd == "compiles":
                out = {"events": list(probes.compiles)}
            elif cmd == "trace_start":
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 2
                jax.profiler.start_trace(req["dir"], profiler_options=options)
                with jax.profiler.TraceAnnotation(MARKER):
                    out = {"marker_unix_ns": time.time_ns(), "marker_monotonic": time.monotonic()}
            elif cmd == "trace_stop":
                jax.profiler.stop_trace()
                out = {"stopped_monotonic": time.monotonic()}
            else:
                out = {"error": f"unknown command {cmd!r}"}
        except Exception as e:  # the harness reports it; the server keeps running
            out = {"error": f"{type(e).__name__}: {e}"[:500]}
        print(CTL_PREFIX + json.dumps(out), flush=True)


def main(argv: list[str]) -> None:
    repo, sep, flags = argv[0], argv[1], argv[2:]
    if sep != "--":
        raise SystemExit("usage: trainer_child.py <repo root> -- <server flags>")
    sys.path.insert(0, repo)
    probes = Probes()
    probes.install()
    threading.Thread(target=_control_loop, args=(probes,), daemon=True, name="bench-ctl").start()
    from dragonfly2_tpu.trainer import server

    sys.argv = ["dragonfly2_tpu.trainer.server", *flags]
    server.main()


if __name__ == "__main__":
    main(sys.argv[1:])
