"""Whole upload-to-published-model cycles of feeder 0, back to back until the
window's seconds have passed and `min_runs` cycles are done, the one in flight
finished and counted (metric: retrain_s, the median cycle). Reads the mix's
min_runs, runs_in_setup and trace_runs."""

import statistics
import time

from traffic_driver import resent_commits as checked  # noqa: F401  (every cycle commits feeder 0 again)


async def drive(driver) -> dict:
    traffic = driver.traffic
    t_start = time.monotonic()
    runs = []
    while True:
        if driver.trace_dir is not None and not runs:
            driver.trace_start()
        up = await driver.upload()
        status = await driver.wait_run_done(driver.uploads)
        up["t_done"] = time.monotonic()
        up["error"] = (status["last_result"] or {}).get("error")
        runs.append(up)
        if driver.trace is not None and len(runs) == traffic["trace_runs"]:
            driver.trace_stop()
        if up["t_done"] - t_start >= driver.seconds and len(runs) >= traffic["min_runs"]:
            break
    events = driver.trainer.ctl("steps", since=0)["events"]
    return {
        "kind": "runs", "window_start": t_start, "window_stop": runs[-1]["t_done"],
        "window_s": runs[-1]["t_done"] - t_start, "uploads": runs,
        "step_events": events, "trace": driver.trace,
    }


def end_to_end(window: dict, traffic: dict) -> tuple[dict, dict, int]:
    """The median cycle, each from its `train_open` to the poll that saw its
    model published: one cycle that the machine paused (PERF.md, section 6) is
    then not the window's reading; every cycle stays in `detail`."""
    cycles = [u["t_done"] - u["t_open"] for u in window["uploads"]]
    retrain_s = statistics.median(cycles)
    detail = {"cycles_s": cycles, "ingest_s": [u["t_closed"] - u["t_open"] for u in window["uploads"]],
              "cycles_over_5pct": sum(1 for c in cycles if c > 1.05 * retrain_s),
              "mean_cycle_s": window["window_s"] / len(cycles)}
    return {"retrain_s": retrain_s}, detail, len(cycles) + traffic["runs_in_setup"]


def setup_split(window: dict, t_ready: float) -> dict:
    return {"cold_cycles_s": window["window_start"] - t_ready}


def traced_stretch(window: dict, config: dict, traffic: dict) -> tuple:
    """The first `train_open` to the poll that saw the last traced cycle's model."""
    return None, (window["uploads"][0]["t_open"], window["uploads"][traffic["trace_runs"] - 1]["t_done"])
