"""What every window shares: the RPC surface as a scheduler uses it (one
feeder's records, chunked as the announcer chunks them, cluster.chunk_rows
rows a trip), the wait for a run's end, the trace's start and stop, the
server's step flags, and how a cell's files are found by name and loaded.

A deployment brings, in the order a `model_config` builder needs them:

  generators/<config's "generator">.py    `generate(cluster, seed)` -> the
      feeders, a list of {"hostname", "scheduler_id", "downloads", "probes"}
      (the RPC surface's record arrays): what is sent, and by whom. No jax,
      nothing of the program: the feeder never opens the accelerator, and the
      reference reads the same records. Every count that sets a compiled shape
      is fixed by the configuration, never drawn (telemetry_gen.py says why).
  traffic/<traffic>.json                  a mix: parameters only (below).
  windows/<mix's "window">.py             all the harness knows of one kind of
      window: `drive(driver)` feeds the trainer through a Driver, opens and
      closes the window and returns it ("kind", "window_start", "window_stop",
      "window_s", "uploads", "step_events", "trace"); `end_to_end(window,
      traffic)` -> (the metric it reads, detail, attempted);
      `setup_split(window, t_ready)` -> its part of set-up's timeline;
      `traced_stretch(window, config, traffic)` -> (scan program's name, or
      None and the host's (t0, t1)): the traced run's window;
      `checked(window, runs)` -> {"run", "commits", "same_pool"}: the run the
      reference follows, the feeders whose commits its pool held, in the order
      they were committed, and the runs that trained on the same pool.

A mix's parameters (each window's module says which it reads):

  min_runs        ("runs") the fewest cycles a window holds, however long they
                  take: a median over fewer than three is one cycle's reading,
                  and over five it leaves two slow cycles out
  runs_in_setup   whole cycles of feeder 0 completed before the window opens
  warm_calls      scan calls of the measured run that belong to set-up
  mlp_steps       `--mlp-steps` for the server, null for what ships
  gnn_steps       `--gnn-steps`: null for what ships (configuration's
                  steps.gnn_steps), a number, or a rule that sizes one long run
                  from the window's seconds alone: so many steps a second of
                  window after the warm calls, whatever the program's speed (a
                  run that ends early closes the window on its last call)
  trace_seconds / trace_runs   how much of the window a --trace 1 run traces
"""

from __future__ import annotations

import asyncio
import importlib.util
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


def find_named(roots: list, kind: str, file_name: str) -> Path:
    """`<kind>/<file_name>` under the first of `roots` that has it (a test's
    own deployment beside its BENCHMARK.json, then the benchmark's)."""
    for path in (Path(root) / kind / file_name for root in roots):
        if path.is_file():
            return path
    raise SystemExit(f"no {kind}/{file_name} under {' or '.join(map(str, roots))}")


def load_file(path: Path):
    """The module a cell names (a generator, a window, a metric's reader)."""
    spec = importlib.util.spec_from_file_location(f"{path.parent.name}_{abs(hash(str(path)))}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resent_commits(window: dict, runs: list) -> dict | None:
    """`checked` of a window that sends feeder 0's records again and again:
    the reference follows the last run that published a GNN; its pool held one
    commit of feeder 0 for every run up to it; the runs with as many pair rows
    trained on the same pool (at the cells' sizes one upload fills it: all)."""
    trained = [i for i, r in enumerate(runs) if (r.get("models") or {}).get("gnn")]
    if not trained:
        return None
    pairs = runs[trained[-1]]["dataset"]["pairs"]
    return {"run": trained[-1], "commits": [0] * (trained[-1] + 1),
            "same_pool": [i for i in trained if runs[i]["dataset"]["pairs"] == pairs]}


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
    def __init__(self, client, trainer, config: dict, traffic: dict, feeders: list, *,
                 seconds: float, trace_dir: Path | None, deadline: float):
        self.client, self.trainer = client, trainer
        self.config, self.traffic, self.feeders = config, traffic, feeders
        self.seconds, self.trace_dir, self.deadline = seconds, trace_dir, deadline
        self.uploads = 0
        self.trace: dict | None = None

    # ---- the RPC surface, as a scheduler uses it ----

    async def send(self, token: str, feeder: dict) -> None:
        rows = self.config["cluster"]["chunk_rows"]
        for kind in ("downloads", "probes"):
            for start in range(0, len(feeder[kind]), rows):
                await self.client.train_chunk(token, kind, feeder[kind][start : start + rows])

    async def upload(self, feeder: dict | None = None) -> dict:
        feeder = feeder or self.feeders[0]
        t_open = time.monotonic()
        token = await self.client.train_open(feeder["hostname"], feeder["scheduler_id"])
        await self.send(token, feeder)
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

    def trace_start(self) -> None:
        out = self.trainer.ctl("trace_start", dir=str(self.trace_dir))
        self.trace = {"started_monotonic": time.monotonic(), **out}

    def trace_stop(self) -> None:
        t = time.monotonic()
        limit = trace_stop_limit_s(self.deadline, t)
        self.trace.update(self.trainer.ctl("trace_stop", timeout=limit))
        self.trace.update(stop_s=time.monotonic() - t, stop_limit_s=limit)

    # ---- set-up's cycles, then the mix's window ----

    async def run(self, window) -> dict:
        for i in range(self.traffic["runs_in_setup"]):
            await self.upload()
            status = await self.wait_run_done(i + 1)
            if (status["last_result"] or {}).get("error"):
                raise RuntimeError(f"set-up run failed: {status['last_result']}")
        return await window.drive(self)
