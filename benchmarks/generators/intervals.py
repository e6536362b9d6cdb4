"""One scheduler whose every upload names the hosts of another interval, as
upstream's scheduler leaves them (BASELINE.md: hosts collected every 6 h,
telemetry uploaded every 7 days): interval i is telemetry_gen.py's records at
that interval's host count (`cluster.hosts_per_interval`, in turn) with seed
+ i, and its host ids moved up by `cluster.hosts_replaced` x i, so each
interval's window over the ids drops that many of the oldest hosts and adds as
many new ones. Every count that sets a compiled shape is the configuration's,
whatever the interval (downloads, their failed and parent-less rows a chunk,
probes): every interval folds to the same pair rows, and the pool's pairs have
one length for every seed.

`generate` returns `cluster.intervals` feeders, each built the first time it
is asked for: any interval can be built alone, so the reference builds only the
commits it checks."""

from __future__ import annotations

from collections.abc import Sequence

import numpy as np
import telemetry_gen

HOST_FIELDS = {"downloads": ("child_host_id", "parent_host_id"), "probes": ("src_host_id", "dst_host_id")}


def host_ids(first: int, n: int) -> np.ndarray:
    """telemetry_gen's ids (`host-` and six digits), from `first` on."""
    return np.char.add(b"host-", np.char.zfill(np.arange(first, first + n).astype("S6"), 6)).astype("S64")


def interval(cluster: dict, seed: int, i: int) -> dict:
    """The feeder of interval i: its records, its hosts moved up the window."""
    n = cluster["hosts_per_interval"][i % len(cluster["hosts_per_interval"])]
    records = dict(zip(("downloads", "probes"), telemetry_gen.generate(
        cluster["downloads"], cluster["probes"], n, seed + i, chunk_rows=cluster["chunk_rows"],
        frac_failed=cluster["frac_failed"], frac_no_parent=cluster["frac_no_parent"])))
    drawn, moved = host_ids(0, n), host_ids(cluster["hosts_replaced"] * i, n)
    for kind, names in HOST_FIELDS.items():
        for name in names:
            ids = records[kind][name]
            named = ids != b""  # a download without a parent keeps naming none
            ids[named] = moved[np.searchsorted(drawn, ids[named])]
    return {"hostname": "benchmark-feeder", "scheduler_id": 0, **records}


class Intervals(Sequence):
    """The intervals of one seed, each built once, on first use."""

    def __init__(self, cluster: dict, seed: int):
        self.cluster, self.seed = cluster, seed
        self._built: dict[int, dict] = {}

    def __len__(self) -> int:
        return self.cluster["intervals"]

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[k] for k in range(*i.indices(len(self)))]
        if not 0 <= i < len(self):
            raise IndexError(f"interval {i} of {len(self)}")
        if i not in self._built:
            self._built[i] = interval(self.cluster, self.seed, i)
        return self._built[i]


def generate(cluster: dict, seed: int) -> Intervals:
    return Intervals(cluster, seed)
