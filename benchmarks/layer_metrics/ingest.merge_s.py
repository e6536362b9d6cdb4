"""Run manifest `ingest.merge_s`, median over the window's cycles:
`train_close`'s `merge_from` of the
session into the pool, and the rotation check, on the trainer's own
monotonic clock (`trainer/service.py`, always on). `ingest.decode_s`,
`ingest.fold_s` and `ingest.merge_s` split `service.ingest_s`, the feeder's
clock over the same upload; the remainder is the feeder's packing, the wire
and the loop's turns (the manifest's `ingest.wait_s`, the server's side of
it). Nothing to read from a program whose manifests have no `ingest`, or in
another kind of window."""

from _common import median, window_runs


def read(ctx):
    runs = window_runs(ctx)
    if runs is None:
        return None
    values = [(m.get("ingest") or {}).get("merge_s") for _, m in runs]
    return None if None in values else median(values)
