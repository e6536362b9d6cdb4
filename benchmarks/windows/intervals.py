"""Whole upload-to-published-model cycles back to back, as runs.py drives
them (metric: retrain_s, the median cycle; the same fields, so the retrain
cell's stage readers read it unchanged), but cycle k sends feeder k: set-up's
cycle sends feeder 0, the window's first cycle feeder 1, and so on, a
scheduler whose every upload names another interval's hosts. A window that
outlasts the configuration's feeders stops with an error that names it.
Reads the mix's min_runs, runs_in_setup and trace_runs.

`checked` follows the last run that published a GNN. Which commits its pool
held is worked out here from the records, not taken from the program's
`pool_rotations`: the trainer commits each upload into its pool and swaps in a
fresh one once the pool holds more than `cluster.pool_max_hosts` hosts or
`cluster.pool_max_edges` edges (0: no cap), counting host ids as the pool
interns them (child and parent of every successful download that names a
parent, source and destination of every probe) and distinct (source,
destination) edges."""

from __future__ import annotations

import time
from pathlib import Path

import numpy as np
from traffic_driver import load_file

RUNS = load_file(Path(__file__).with_name("runs.py"))
setup_split = RUNS.setup_split
traced_stretch = RUNS.traced_stretch


async def drive(driver) -> dict:
    traffic, feeders, config = driver.traffic, driver.feeders, driver.config
    # set-up: the intervals every window sends are built before it opens
    for k in range(min(len(feeders), traffic["runs_in_setup"] + traffic["min_runs"])):
        feeders[k]
    t_start = time.monotonic()
    runs = []
    while True:
        if driver.uploads >= len(feeders):
            raise RuntimeError(f"{config['name']}: the window's cycle {len(runs) + 1} needs feeder {driver.uploads}, "
                               f"the configuration has {len(feeders)} (cluster.intervals)")
        feeder = feeders[driver.uploads]
        if driver.trace_dir is not None and not runs:
            driver.trace_start()
        up = await driver.upload(feeder)
        status = await driver.wait_run_done(driver.uploads)
        up["t_done"] = time.monotonic()
        up["error"] = (status["last_result"] or {}).get("error")
        up["feeder"] = driver.uploads - 1
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
        "pools": pools_of(feeders[: driver.uploads], config["cluster"]),
        "pool_rotations": status["pool_rotations"],
    }


def pools_of(feeders: list, cluster: dict) -> list[list[int]]:
    """For each upload in the order sent, the feeders whose commits the pool
    held when that upload's run trained (the run keeps the pool it committed
    into, even where the close then rotated it)."""
    named = []  # per feeder: the ids it names, and each probe's source and destination among them
    for f in feeders:
        d, p = f["downloads"], f["probes"]
        ok = d["success"] & (d["parent_host_id"] != b"")
        ids, at = np.unique(np.concatenate([p["src_host_id"], p["dst_host_id"], d["child_host_id"][ok],
                                            d["parent_host_id"][ok]]), return_inverse=True)
        named.append((ids, at[: len(p)], at[len(p) : 2 * len(p)]))
    every = np.unique(np.concatenate([ids for ids, _, _ in named]))
    hosts = [np.searchsorted(every, ids) for ids, _, _ in named]
    edges = [np.unique(code[src].astype(np.int64) * len(every) + code[dst]) for code, (_, src, dst) in zip(hosts, named)]
    out, held, pool_hosts, pool_edges = [], [], np.zeros(0, np.int64), np.zeros(0, np.int64)
    for k in range(len(feeders)):
        held = held + [k]
        pool_hosts, pool_edges = np.union1d(pool_hosts, hosts[k]), np.union1d(pool_edges, edges[k])
        out.append(held)
        if 0 < cluster["pool_max_hosts"] < len(pool_hosts) or 0 < cluster["pool_max_edges"] < len(pool_edges):
            held, pool_hosts, pool_edges = [], np.zeros(0, np.int64), np.zeros(0, np.int64)
    return out


def end_to_end(window: dict, traffic: dict) -> tuple[dict, dict, int]:
    """runs.py's reading, and in `detail` what each cycle sent and what its
    pool held by the window's reckoning, and the trainer's own count of
    rotations at the window's end."""
    read, detail, attempted = RUNS.end_to_end(window, traffic)
    cycles = window["uploads"]
    detail.update(feeders=[u["feeder"] for u in cycles], pool_commits=[window["pools"][u["feeder"]] for u in cycles],
                  pool_rotations=window["pool_rotations"])
    return read, detail, attempted


def checked(window: dict, runs: list) -> dict | None:
    """The last run that published a GNN, the commits its pool held by the
    window's reckoning, and the runs whose pool held the same commits."""
    trained = [i for i, r in enumerate(runs) if (r.get("models") or {}).get("gnn")]
    pools = window["pools"]
    if not trained or len(pools) != len(runs):
        return None
    run = trained[-1]
    return {"run": run, "commits": pools[run], "same_pool": [i for i in trained if pools[i] == pools[run]]}
