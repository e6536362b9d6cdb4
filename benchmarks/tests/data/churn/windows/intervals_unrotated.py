"""The intervals window with a planted fault in its reckoning: the checked
run's pool is said to hold every commit sent up to it, as if the pool had
never rotated. The reference then rebuilds another pool than the one the run
trained on, and the run must not come out correct."""

from pathlib import Path

from traffic_driver import load_file

WINDOW = load_file(Path(__file__).resolve().parents[4] / "windows" / "intervals.py")
drive, end_to_end, setup_split, traced_stretch = WINDOW.drive, WINDOW.end_to_end, WINDOW.setup_split, WINDOW.traced_stretch


def checked(window: dict, runs: list) -> dict | None:
    out = WINDOW.checked(window, runs)
    if out is not None:
        out["commits"] = list(range(out["run"] + 1))
    return out
