"""Several schedulers feeding one trainer, closed loop. A cycle: every
feeder's session opens at once; feeder 0 sends its chunks and closes, which
starts a run (A); the other feeders then send side by side, a chunk of each
in turn, and close while run A trains, each close checked against the
trainer's status first (an error says so where a close would come after run A
ended); the drainer coalesces those closes into one run (B); the next cycle
opens when run B's model is published. A cycle that does not start exactly
two runs, one of them coalesced, is an error that says what it did. Set-up is
`cycles_in_setup` such cycles (`Driver.run`'s would send feeder 0 alone).
Metric: retrain_s, the median cycle, from its first `train_open` to the poll
that saw run B published. Reads the mix's cycles_in_setup, min_runs (cycles)
and trace_runs.

`uploads` holds an entry a run, oldest first, where runs.py holds one a cycle:
run A's from the cycle's first `train_open` to feeder 0's close and to the
poll that saw A end (the drainer starts B in the turn A ends), run B's from the
same open to the last close and to the poll that saw B published, each with
its `cycle` and `run`. So a reader of one run's manifest at a time reads each
run of the window; one that reads an upload's clock or a session's counts as a
cycle's does not (PERF.md says which).

`checked` follows the last run B. Its pool held every commit so far in the
order the window closed the sessions (recorded, set-up's too). The trainer
never rotates this pool (an error says so where it did); the runs that
trained on the same pool are worked out here from the records: the order in
which feeders first committed, how often each did, and the pair chunks the
pool kept under `pool_rows_cap`."""

from __future__ import annotations

import asyncio
import itertools
import math
import statistics
import time
from collections import Counter

RUNS_A_CYCLE = 2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


async def poll_until(driver, done, what: str) -> dict:
    while True:
        status = await driver.client.status()
        if done(status):
            return status
        if time.monotonic() > driver.deadline:
            raise RuntimeError(f"{what} did not happen in time; status {status}")
        await asyncio.sleep(0.02)


async def side_by_side(driver, tokens: list, feeders: list) -> None:
    """The feeders' chunks, a chunk of each in turn, each turn's in flight together."""
    rows = driver.config["cluster"]["chunk_rows"]
    trips = [[(kind, f[kind][s : s + rows]) for kind in ("downloads", "probes") for s in range(0, len(f[kind]), rows)]
             for f in feeders]
    for turn in itertools.zip_longest(*trips):
        await asyncio.gather(*(driver.client.train_chunk(t, *trip) for t, trip in zip(tokens, turn) if trip))


async def cycle(driver) -> dict:
    client, feeders = driver.client, driver.feeders
    base = await client.status()
    t_open = time.monotonic()
    tokens = [await client.train_open(f["hostname"], f["scheduler_id"]) for f in feeders]
    open_together = (await client.status())["open_sessions"]
    await driver.send(tokens[0], feeders[0])
    await client.train_close(tokens[0])
    t_first_closed, closes = time.monotonic(), [0]
    await side_by_side(driver, tokens[1:], feeders[1:])
    for k, token in enumerate(tokens[1:], 1):
        status = await client.status()
        if not (status["training"] and status["trains_started"] == base["trains_started"] + 1):
            raise RuntimeError(f"feeder {k}'s close would come after run A ended, not while it trains; status {status}")
        await client.train_close(token)
        closes.append(k)
    t_closed = time.monotonic()
    n = base["trains_started"] + RUNS_A_CYCLE
    await poll_until(driver, lambda s: s["trains_started"] >= n, "run A's end")
    t_a_done = time.monotonic()
    status = await driver.wait_run_done(n)
    t_done = time.monotonic()
    runs = status["trains_started"] - base["trains_started"]
    coalesced = status["trains_coalesced"] - base["trains_coalesced"]
    if (runs, coalesced) != (RUNS_A_CYCLE, 1):
        raise RuntimeError(f"a cycle started {runs} runs, {coalesced} of them coalesced, not two and one")
    if (status["last_result"] or {}).get("error"):
        raise RuntimeError(f"run B failed: {status['last_result']}")
    return {"t_open": t_open, "t_first_closed": t_first_closed, "t_closed": t_closed, "t_a_done": t_a_done,
            "t_done": t_done, "open_together": open_together, "closes": closes,
            "late_closes_in_run": len(closes) - 1, "runs": runs, "coalesced": coalesced,
            "pool_rotations": status["pool_rotations"]}


def run_notes(manifest: dict) -> dict:
    """What the detail shows of one run's manifest (None where it does not say)."""
    ingest, gnn = manifest.get("ingest") or {}, manifest["models"].get("gnn") or {}
    calls = gnn.get("calls") or {}
    mlp_calls = (manifest["models"].get("mlp") or {}).get("calls") or {}
    return {"sessions": ingest.get("sessions"), "schedulers": ingest.get("schedulers"),
            "in_run_s": ingest.get("in_run_s"), "chunks_in_run": ingest.get("chunks_in_run"),
            "gnn_served": (gnn.get("kept") or {}).get("served"), "mlp_traced": mlp_calls.get("traced"),
            "calls_in_ingest": None if "in_ingest" not in calls else len(calls["in_ingest"]),
            "gap_ms_in_ingest": calls.get("gap_ms_in_ingest"), "gap_ms_clear": calls.get("gap_ms_clear")}


def chunk_pairs(feeders: list, rows: int) -> list[list[int]]:
    """Per feeder, the pair rows of each of its download trips that yields any."""
    out = []
    for f in feeders:
        d = f["downloads"]
        ok = d["success"] & (d["parent_host_id"] != b"")
        out.append([n for n in (int(ok[s : s + rows].sum()) for s in range(0, len(d), rows)) if n])
    return out


async def drive(driver) -> dict:
    traffic = driver.traffic
    closes, setup = [], []
    for _ in range(traffic["cycles_in_setup"]):
        setup.append(await cycle(driver))
        closes += setup[-1]["closes"]
    t_start = time.monotonic()
    cycles = []
    while True:
        if driver.trace_dir is not None and not cycles:
            driver.trace_start()
        cycles.append(await cycle(driver))
        closes += cycles[-1]["closes"]
        if driver.trace is not None and len(cycles) == traffic["trace_runs"]:
            driver.trace_stop()
        if cycles[-1]["t_done"] - t_start >= driver.seconds and len(cycles) >= traffic["min_runs"]:
            break
    if cycles[-1]["pool_rotations"]:
        raise RuntimeError(f"the trainer rotated its pool {cycles[-1]['pool_rotations']} times: "
                           "this window's reckoning of the checked pool holds for a pool that never rotates")
    manifests = (await driver.client.train_history(limit=64, with_curves=False))["runs"][::-1]
    compiles = driver.trainer.ctl("compiles")["events"]
    uploads = []
    for k, c in enumerate(cycles):
        c["compile_s"] = sum(s for t, event, s in compiles if event == COMPILE_EVENT and c["t_open"] <= t <= c["t_done"])
        uploads += [{"t_open": c["t_open"], "t_closed": c["t_first_closed"], "t_done": c["t_a_done"], "cycle": k, "run": "A"},
                    {"t_open": c["t_open"], "t_closed": c["t_closed"], "t_done": c["t_done"], "cycle": k, "run": "B"}]
    return {
        "kind": "runs", "window_start": t_start, "window_stop": cycles[-1]["t_done"],
        "window_s": cycles[-1]["t_done"] - t_start, "uploads": uploads, "cycles": cycles,
        "setup_cycles": len(setup), "closes": closes,
        "chunk_pairs": chunk_pairs(driver.feeders, driver.config["cluster"]["chunk_rows"]),
        "pool_rows_cap": driver.config["cluster"]["pool_rows_cap"],
        "run_notes": [run_notes(m) for m in manifests[-len(uploads):]],
        "step_events": driver.trainer.ctl("steps", since=0)["events"], "trace": driver.trace,
    }


def end_to_end(window: dict, traffic: dict) -> tuple[dict, dict, int]:
    """The median cycle; every cycle's length, runs, coalescing, close order
    and compile seconds, and each run's notes, in `detail`."""
    cycles = window["cycles"]
    lengths = [c["t_done"] - c["t_open"] for c in cycles]
    retrain_s = statistics.median(lengths)
    notes = window["run_notes"]
    detail = {
        "cycles_s": lengths, "cycles_over_5pct": sum(1 for c in lengths if c > 1.05 * retrain_s),
        "mean_cycle_s": window["window_s"] / len(cycles),
        "ingest_s": [c["t_closed"] - c["t_open"] for c in cycles],
        "first_close_s": [c["t_first_closed"] - c["t_open"] for c in cycles],
        "runs_per_cycle": [c["runs"] for c in cycles], "coalesced_per_cycle": [c["coalesced"] for c in cycles],
        "open_together": [c["open_together"] for c in cycles], "close_order": [c["closes"] for c in cycles],
        "late_closes_in_run": [c["late_closes_in_run"] for c in cycles],
        "compile_s": [c["compile_s"] for c in cycles], "pool_rotations": cycles[-1]["pool_rotations"],
        "runs": notes,
    }
    return {"retrain_s": retrain_s}, detail, RUNS_A_CYCLE * (len(cycles) + window["setup_cycles"])


def setup_split(window: dict, t_ready: float) -> dict:
    return {"cold_cycles_s": window["window_start"] - t_ready}


def traced_stretch(window: dict, config: dict, traffic: dict) -> tuple:
    """The first window cycle's first `train_open` to the poll that saw the last traced cycle's run B."""
    cycles = window["cycles"]
    return None, (cycles[0]["t_open"], cycles[traffic["trace_runs"] - 1]["t_done"])


def pool_of(commits: list[int], chunk_pairs: list[list[int]], cap: int) -> tuple:
    """What the pool after `commits` (feeders, in commit order) trains on: the
    order in which feeders first committed (host numbering), how often each
    did, less the common factor (the aggregates' means and rates), and the pair
    chunks kept (a commit appends its chunks, then the oldest go while the
    rest alone still covers the cap)."""
    times = Counter(commits)
    common = math.gcd(*times.values())
    order = tuple(dict.fromkeys(commits))
    kept, held = [], 0
    for f in commits:
        kept += [(f, k, n) for k, n in enumerate(chunk_pairs[f])]
        held += sum(chunk_pairs[f])
        while cap > 0 and len(kept) > 1 and held - kept[0][2] >= cap:
            held -= kept.pop(0)[2]
    return order, tuple(times[f] // common for f in order), tuple(kept)


def checked(window: dict, runs: list) -> dict | None:
    """The last run B, the commits its pool held, and the runs whose pool was
    the same by `pool_of`."""
    n_cycles = window["setup_cycles"] + len(window["cycles"])
    trained = [i for i, r in enumerate(runs) if (r.get("models") or {}).get("gnn")]
    if len(runs) != RUNS_A_CYCLE * n_cycles or not trained or trained[-1] != len(runs) - 1:
        return None
    closes, per = window["closes"], len(window["closes"]) // n_cycles
    # run A of cycle k trained on the commits up to that cycle's first close, run B on its last
    commits = [closes[: k * per + (1 if i % RUNS_A_CYCLE == 0 else per)] for k in range(n_cycles) for i in range(RUNS_A_CYCLE)]
    pools = [pool_of(c, window["chunk_pairs"], window["pool_rows_cap"]) for c in commits]
    last = trained[-1]
    return {"run": last, "commits": commits[last], "same_pool": [i for i in trained if pools[i] == pools[last]]}
