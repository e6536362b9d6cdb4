"""The one general traffic driver: reads a traffic mix (benchmarks/traffic/*.json)
and a configuration, feeds the trainer over its RPC surface and times the
window. A mix is parameters only:

  window          "scan_calls": one upload, and the window lies inside the
                  training run it starts, from one completed scan call to a
                  later one (metric: train_steps_per_s);
                  "runs": whole upload-to-published-model cycles, back to back
                  until the window's seconds have passed and `min_runs` cycles
                  are done, the one in flight finished and counted (metric:
                  retrain_s, the median cycle)
  min_runs        ("runs") the fewest cycles a window holds, however long they
                  take: a median over fewer than three is one cycle's reading,
                  and over five it leaves two slow cycles out
  runs_in_setup   whole cycles completed before the window opens (the cold one)
  warm_calls      scan calls of the measured run that belong to set-up
  mlp_steps       `--mlp-steps` for the server, null for what ships
  gnn_steps       `--gnn-steps`: null for what ships (configuration's
                  steps.gnn_steps), a number, or a rule that sizes one long run
                  from the window's seconds alone: so many steps a second of
                  window after the warm calls, whatever the program's speed (a
                  run that ends early closes the window on its last call)
  trace_seconds / trace_runs   how much of the window a --trace 1 run traces

Every upload sends the same seeded records, chunked as the scheduler's
announcer chunks them (cluster.chunk_rows rows a trip).
"""

from __future__ import annotations

import asyncio
import math
import time
from pathlib import Path

POLL_S = 0.02
# `trace_stop` may take what is left of the run's budget, less what the end of
# the training run, the export and post_child.py need after it, and never less
# than the floor: the profiler's collection grows with the trace (ops a step x
# steps x chips), so its limit is the run's and no flat one
TRACE_STOP_RESERVE_S = 240.0
TRACE_STOP_FLOOR_S = 120.0


def trace_stop_limit_s(deadline: float, now: float) -> float:
    """Seconds the trainer's child may take to answer `trace_stop`."""
    return max(TRACE_STOP_FLOOR_S, deadline - now - TRACE_STOP_RESERVE_S)


def server_step_flags(config: dict, traffic: dict, seconds: float) -> tuple[int, int]:
    """(--gnn-steps, --mlp-steps) this mix asks the server for."""
    mlp_steps = traffic["mlp_steps"]
    if mlp_steps is None:
        mlp_steps = config["steps"]["mlp_steps"]
    rule = traffic["gnn_steps"]
    if rule is None:
        gnn_steps = config["steps"]["gnn_steps"]
    elif isinstance(rule, dict):
        spc = config["optimizer"]["gnn"]["steps_per_call"]
        in_window = math.ceil(seconds * rule["warm_calls_plus_steps_per_window_second"] / spc) * spc
        gnn_steps = traffic["warm_calls"] * spc + in_window
    else:
        gnn_steps = int(rule)
    return gnn_steps, int(mlp_steps)


class Driver:
    def __init__(self, client, trainer, config: dict, traffic: dict, records, *,
                 seconds: float, trace_dir: Path | None, deadline: float):
        self.client, self.trainer = client, trainer
        self.config, self.traffic = config, traffic
        self.downloads, self.probes = records
        self.seconds, self.trace_dir, self.deadline = seconds, trace_dir, deadline
        self.uploads = 0
        self.trace: dict | None = None

    # ---- the RPC surface, as a scheduler uses it ----

    async def upload(self) -> dict:
        rows = self.config["cluster"]["chunk_rows"]
        t_open = time.monotonic()
        token = await self.client.train_open("benchmark-feeder", 0)
        for kind, arr in (("downloads", self.downloads), ("probes", self.probes)):
            for start in range(0, len(arr), rows):
                await self.client.train_chunk(token, kind, arr[start : start + rows])
        await self.client.train_close(token)
        self.uploads += 1
        return {"t_open": t_open, "t_closed": time.monotonic()}

    async def wait_run_done(self, n: int) -> dict:
        """Poll `status` until the n-th run has ended; returns the status."""
        while True:
            status = await self.client.status()
            if status["trains_started"] >= n and not status["training"]:
                return status
            if time.monotonic() > self.deadline:
                raise RuntimeError(f"run {n} did not end in time; status {status}")
            await asyncio.sleep(POLL_S)

    # ---- tracing ----

    def _trace_start(self) -> None:
        out = self.trainer.ctl("trace_start", dir=str(self.trace_dir))
        self.trace = {"started_monotonic": time.monotonic(), **out}

    def _trace_stop(self) -> None:
        t = time.monotonic()
        limit = trace_stop_limit_s(self.deadline, t)
        self.trace.update(self.trainer.ctl("trace_stop", timeout=limit))
        self.trace.update(stop_s=time.monotonic() - t, stop_limit_s=limit)

    # ---- windows ----

    async def run(self) -> dict:
        for i in range(self.traffic["runs_in_setup"]):
            await self.upload()
            status = await self.wait_run_done(i + 1)
            if (status["last_result"] or {}).get("error"):
                raise RuntimeError(f"set-up run failed: {status['last_result']}")
        if self.traffic["window"] == "scan_calls":
            return await self._window_scan_calls()
        if self.traffic["window"] == "runs":
            return await self._window_runs()
        raise ValueError(f"unknown window kind {self.traffic['window']!r}")

    def _completed_calls(self, events: list) -> list:
        """Completed scan calls of the newest GNN run: its reports at a whole
        number of calls (time, run, model, steps so far, loss, gradient norm)."""
        spc = self.config["optimizer"]["gnn"]["steps_per_call"]
        gnn = [e for e in events if e[2] == "gnn"]
        return [e for e in gnn if e[1] == gnn[-1][1] and e[3] % spc == 0] if gnn else []

    async def _window_scan_calls(self) -> dict:
        warm_steps = self.traffic["warm_calls"] * self.config["optimizer"]["gnn"]["steps_per_call"]
        up = await self.upload()
        events: list = []
        start = stop = trace_stop_at = None
        next_status = 0.0
        while stop is None:
            events += self.trainer.ctl("steps", since=len(events))["events"]
            calls = self._completed_calls(events)
            now = time.monotonic()
            if start is None:
                start = next((e for e in calls if e[3] >= warm_steps), None)
                if start is not None:
                    # inside the window the trainer is left alone: no status
                    # poll until the window may close
                    next_status = start[0] + self.seconds - 0.2
                    if self.trace_dir is not None:
                        self._trace_start()
                        trace_stop_at = time.monotonic() + self.traffic["trace_seconds"]
            else:
                stop = next((e for e in calls if e[0] >= start[0] + self.seconds), None)
            if trace_stop_at is not None and now >= trace_stop_at:
                self._trace_stop()
                trace_stop_at = None
            if stop is None and now >= next_status:
                next_status = now + 2.0
                status = await self.client.status()
                if status["trains_started"] >= self.uploads and not status["training"]:
                    # the run ended before the window's seconds had passed: the
                    # window closes on its last completed call
                    events += self.trainer.ctl("steps", since=len(events))["events"]
                    calls = self._completed_calls(events)
                    if start is None or not calls or calls[-1][3] <= start[3]:
                        raise RuntimeError(f"the run ended before a window could open; status {status}")
                    stop = calls[-1]
                elif now > self.deadline:
                    raise RuntimeError("the window did not close in time")
            if stop is None:
                # the next look comes when the trace has to stop or the window
                # may close (then every 50 ms, a status poll every 2 s)
                wake = now + 0.05
                if start is not None:
                    wake = max(wake, min(start[0] + self.seconds - 0.2, trace_stop_at or math.inf))
                await asyncio.sleep(wake - now)
        if trace_stop_at is not None:
            self._trace_stop()
        await self.wait_run_done(self.uploads)
        events += self.trainer.ctl("steps", since=len(events))["events"]
        return {
            "kind": "scan_calls", "window_start": start[0], "window_stop": stop[0],
            "steps": stop[3] - start[3], "window_s": stop[0] - start[0],
            "uploads": [up], "step_events": events, "trace": self.trace,
        }

    async def _window_runs(self) -> dict:
        t_start = time.monotonic()
        runs = []
        while True:
            if self.trace_dir is not None and not runs:
                self._trace_start()
            up = await self.upload()
            status = await self.wait_run_done(self.uploads)
            up["t_done"] = time.monotonic()
            up["error"] = (status["last_result"] or {}).get("error")
            runs.append(up)
            if self.trace is not None and len(runs) == self.traffic["trace_runs"]:
                self._trace_stop()
            if up["t_done"] - t_start >= self.seconds and len(runs) >= self.traffic["min_runs"]:
                break
        events = self.trainer.ctl("steps", since=0)["events"]
        return {
            "kind": "runs", "window_start": t_start, "window_stop": runs[-1]["t_done"],
            "window_s": runs[-1]["t_done"] - t_start, "uploads": runs,
            "step_events": events, "trace": self.trace,
        }
