"""One upload, and the window lies inside the training run it starts, from
one completed scan call to a later one at least the run's seconds after it
(metric: train_steps_per_s). Reads the mix's warm_calls and trace_seconds."""

import asyncio
import math
import time

from traffic_driver import resent_commits as checked  # noqa: F401  (one feeder, one commit)


def _completed_calls(config: dict, events: list) -> list:
    """Completed scan calls of the newest GNN run: its reports at a whole
    number of calls (time, run, model, steps so far, loss, gradient norm)."""
    spc = config["optimizer"]["gnn"]["steps_per_call"]
    gnn = [e for e in events if e[2] == "gnn"]
    return [e for e in gnn if e[1] == gnn[-1][1] and e[3] % spc == 0] if gnn else []


async def drive(driver) -> dict:
    config, traffic, trainer, seconds = driver.config, driver.traffic, driver.trainer, driver.seconds
    warm_steps = traffic["warm_calls"] * config["optimizer"]["gnn"]["steps_per_call"]
    up = await driver.upload()
    events: list = []
    start = stop = trace_stop_at = None
    next_status = 0.0
    while stop is None:
        events += trainer.ctl("steps", since=len(events))["events"]
        calls = _completed_calls(config, events)
        now = time.monotonic()
        if start is None:
            start = next((e for e in calls if e[3] >= warm_steps), None)
            if start is not None:
                # inside the window the trainer is left alone: no status
                # poll until the window may close
                next_status = start[0] + seconds - 0.2
                if driver.trace_dir is not None:
                    driver.trace_start()
                    trace_stop_at = time.monotonic() + traffic["trace_seconds"]
        else:
            stop = next((e for e in calls if e[0] >= start[0] + seconds), None)
        if trace_stop_at is not None and now >= trace_stop_at:
            driver.trace_stop()
            trace_stop_at = None
        if stop is None and now >= next_status:
            next_status = now + 2.0
            status = await driver.client.status()
            if status["trains_started"] >= driver.uploads and not status["training"]:
                # the run ended before the window's seconds had passed: the
                # window closes on its last completed call
                events += trainer.ctl("steps", since=len(events))["events"]
                calls = _completed_calls(config, events)
                if start is None or not calls or calls[-1][3] <= start[3]:
                    raise RuntimeError(f"the run ended before a window could open; status {status}")
                stop = calls[-1]
            elif now > driver.deadline:
                raise RuntimeError("the window did not close in time")
        if stop is None:
            # the next look comes when the trace has to stop or the window
            # may close (then every 50 ms, a status poll every 2 s)
            wake = now + 0.05
            if start is not None:
                wake = max(wake, min(start[0] + seconds - 0.2, trace_stop_at or math.inf))
            await asyncio.sleep(wake - now)
    if trace_stop_at is not None:
        driver.trace_stop()
    await driver.wait_run_done(driver.uploads)
    events += trainer.ctl("steps", since=len(events))["events"]
    return {
        "kind": "scan_calls", "window_start": start[0], "window_stop": stop[0],
        "steps": stop[3] - start[3], "window_s": stop[0] - start[0],
        "uploads": [up], "step_events": events, "trace": driver.trace,
    }


def end_to_end(window: dict, traffic: dict) -> tuple[dict, dict, int]:
    return ({"train_steps_per_s": window["steps"] / window["window_s"]}, {"steps": window["steps"]},
            len(window["uploads"]))


def setup_split(window: dict, t_ready: float) -> dict:
    """Upload; then dataset build, MLP stage, GNN init + placement + compile
    or cache load + first scan call; then the other warm calls."""
    up = window["uploads"][0]
    gnn_reports = [e[0] for e in window["step_events"] if e[2] == "gnn"]
    if not gnn_reports:
        return {}
    return {"upload_s": up["t_closed"] - up["t_open"], "close_to_first_scan_call_s": gnn_reports[0] - up["t_closed"],
            "warm_calls_s": window["window_start"] - gnn_reports[0]}


def traced_stretch(window: dict, config: dict, traffic: dict) -> tuple:
    """Whole call periods of the scan program, by the device's own clock."""
    return config["scan_program"], None
