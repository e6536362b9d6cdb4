"""What several readers share: the scan program's executions in the traced
window of a steady run, the cycles of a retrain window, the idle share."""

from __future__ import annotations

import statistics


def scan_calls(ctx: dict) -> list | None:
    """Executions of the scan program that START inside the traced window
    (which opens on one execution's start and closes on the last one's), or
    None without a device trace."""
    view = ctx.get("view")
    if view is None:
        return None
    runs = [m for m in view.module_runs(ctx["config"]["scan_program"])
            if view.start_ns <= m[2] < view.stop_ns]
    return runs or None


def idle_pct(ctx: dict) -> float | None:
    """Share of the traced window in which no op ran on the device (a steady
    window: whole call periods; a retrain window: the first `train_open` to
    the manifest)."""
    view = ctx.get("view")
    if view is None or view.window_s <= 0:
        return None
    return 100.0 * (1.0 - view.busy_s() / view.window_s)


def steps_in_window(ctx: dict) -> int | None:
    calls = scan_calls(ctx)
    return None if calls is None else len(calls) * ctx["config"]["optimizer"]["gnn"]["steps_per_call"]


def window_runs(ctx: dict) -> list[tuple[dict, dict]] | None:
    """(upload timing, run manifest) of every whole cycle of a "runs" window,
    oldest first, or None in another kind of window."""
    window = ctx["window"]
    if window["kind"] != "runs":
        return None
    uploads = window["uploads"]
    manifests = ctx["runs"][-len(uploads):]
    if len(manifests) != len(uploads):
        return None
    return list(zip(uploads, manifests))


def median(values: list[float]) -> float | None:
    """The median over the window's cycles, as `retrain_s` is of their whole
    times: a stage's reading and the end-to-end reading then leave the same
    paused cycle out."""
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def evaluation(manifest: dict, model: str) -> dict:
    """`models.<model>.evaluation` of a run manifest, empty where the run
    trained no such model."""
    return (manifest["models"].get(model) or {}).get("evaluation") or {}


def train_seconds(manifest: dict, model: str) -> float | None:
    """`<model>.evaluation.train_seconds` of a run manifest."""
    return evaluation(manifest, model).get("train_seconds")
