"""Two feeders' sessions open together and their chunks interleave; feeder
0's close starts a run, feeder 1's lands while that run trains, and the window
waits for the second run's model: the one trained on the pool that holds both
commits. Nothing is timed for a metric: a rehearsal of what several schedulers
ask of the harness and the trainer."""

import asyncio
import time

STATUS_KEYS = ("trains_started", "trains_succeeded", "trains_coalesced", "pool_rotations", "open_sessions")


async def drive(driver) -> dict:
    client = driver.client
    t_open = time.monotonic()
    tokens = [await client.train_open(f["hostname"], f["scheduler_id"]) for f in driver.feeders]
    both_open = (await client.status())["open_sessions"]
    await asyncio.gather(*(driver.send(t, f) for t, f in zip(tokens, driver.feeders)))
    await client.train_close(tokens[0])
    while not (status := await client.status())["trains_started"]:
        if time.monotonic() > driver.deadline:
            raise RuntimeError(f"the first close started no run; status {status}")
        await asyncio.sleep(0.02)
    if not status["training"]:
        raise RuntimeError("the first run ended before the second close could land in it")
    await client.train_close(tokens[1])
    t_closed = time.monotonic()
    status = await driver.wait_run_done(2)
    t_done = time.monotonic()
    return {
        "kind": "both_sessions", "window_start": t_open, "window_stop": t_done, "window_s": t_done - t_open,
        "uploads": [{"t_open": t_open, "t_closed": t_closed, "t_done": t_done}],
        "step_events": driver.trainer.ctl("steps", since=0)["events"], "trace": driver.trace,
        "status": {"open_together": both_open, **{k: status[k] for k in STATUS_KEYS}},
    }


def end_to_end(window: dict, traffic: dict) -> tuple[dict, dict, int]:
    """No metric; the trainer's counters go into the result's `detail`; two runs are due."""
    return {}, {"status": window["status"]}, 2


def setup_split(window: dict, t_ready: float) -> dict:
    return {}


def traced_stretch(window: dict, config: dict, traffic: dict) -> tuple:
    return None, (window["window_start"], window["window_stop"])


def checked(window: dict, runs: list) -> dict | None:
    """The second run: its pool held feeder 0's commit, then feeder 1's; the
    first run trained on feeder 0's alone and is not held to it."""
    if len(runs) < 2 or not (runs[1].get("models") or {}).get("gnn"):
        return None
    return {"run": 1, "commits": [0, 1], "same_pool": [1]}
