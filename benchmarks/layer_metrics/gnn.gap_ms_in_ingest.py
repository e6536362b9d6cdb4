"""Run manifest `models.gnn.calls.gap_ms_in_ingest`, median over the
window's runs A (a cycle's first run, during which the other schedulers'
sessions fold in and close): the host's part of the wait between two scan
calls, one call's end to the next one's enqueue, after the calls during or
after which an ingest handler ran on the trainer's event loop, by the
program's own always-on count. The same median after the other calls is the
manifest's `gap_ms_clear`, which the window's `detail.runs` shows beside it.
Nothing to read from a program whose manifests have no such gap, or in a
window without runs A."""

from _common import median, window_runs


def read(ctx):
    runs = window_runs(ctx)
    if runs is None:
        return None
    return median([((m["models"].get("gnn") or {}).get("calls") or {}).get("gap_ms_in_ingest")
                   for upload, m in runs if upload.get("run") == "A"])
