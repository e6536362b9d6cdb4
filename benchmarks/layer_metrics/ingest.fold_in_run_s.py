"""Run manifest `ingest.in_run_s` summed over each cycle's runs, median over
the window's cycles: the decode, fold and merge seconds the trainer spent on
uploads while a run of its own trained (`trainer/service.py`, always on), on
the event loop that also hands that run's scan calls back. Where several
schedulers upload together, the sessions that close after the first fold in
while its run trains. Nothing to read from a program whose manifests have no
`in_run_s`, or in another kind of window."""

from _common import median, window_runs


def read(ctx):
    runs = window_runs(ctx)
    if runs is None:
        return None
    cycles: dict = {}
    for i, (upload, manifest) in enumerate(runs):
        seconds = (manifest.get("ingest") or {}).get("in_run_s")
        if seconds is None:
            return None
        key = upload.get("cycle", i)
        cycles[key] = cycles.get(key, 0.0) + seconds
    return median(list(cycles.values()))
