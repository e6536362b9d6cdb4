"""One cluster's `uniform` records dealt out to `cluster.schedulers` scheduler
replicas, each its own feeder (`scheduler-<i>`, id i), as a cluster of
several schedulers holds them (SURVEY.md section 1): a download goes to the
scheduler its task hashes onto, a probe to the scheduler of the host that
probed. The feeders' records together are telemetry_gen.py's for the seed,
row for row in the order dealt, but for the task ids, which `uniform` leaves
empty. Every count that sets a compiled shape stays the configuration's:

  downloads  whole chunks of `chunk_rows`, `download_chunks_per_scheduler` of
             them to each scheduler in turn, so each keeps telemetry_gen's
             exact failed and parent-less rows a chunk and every scheduler's
             upload folds to the same pair rows for every seed; each row then
             gets one of `cluster.tasks` task ids, drawn among those whose
             crc32 modulo the schedulers is the scheduler that sends it (crc32
             in place of upstream's consistent-hash ring)
  probes     host i reports to scheduler i mod the schedulers
             (`hosts_per_scheduler`); a probe goes to its source's scheduler,
             so the feeders send each probe once
"""

from __future__ import annotations

import zlib

import numpy as np
import telemetry_gen


def host_index(ids: np.ndarray, n_hosts: int) -> np.ndarray:
    """The index of telemetry_gen's host ids (`host-` and six digits)."""
    hosts = np.char.add(b"host-", np.char.zfill(np.arange(n_hosts).astype("S6"), 6)).astype("S64")
    return np.searchsorted(hosts, ids)


def task_owners(n_tasks: int, schedulers: int) -> tuple[np.ndarray, np.ndarray]:
    """(task ids, the scheduler each hashes onto)."""
    names = np.char.add(b"task-", np.arange(n_tasks).astype("S8"))
    return names, np.array([zlib.crc32(t) % schedulers for t in names.tolist()])


def generate(cluster: dict, seed: int) -> list[dict]:
    n, rows = cluster["schedulers"], cluster["chunk_rows"]
    downloads, probes = telemetry_gen.generate_for(cluster, seed)
    chunks = cluster["download_chunks_per_scheduler"]
    if len(chunks) != n or sum(chunks) * rows != len(downloads):
        raise ValueError(f"download_chunks_per_scheduler {chunks} do not deal {len(downloads)} downloads "
                         f"in chunks of {rows} to {n} schedulers")
    owner_of_host = np.arange(cluster["hosts"]) % n
    if np.bincount(owner_of_host, minlength=n).tolist() != cluster["hosts_per_scheduler"]:
        raise ValueError(f"hosts_per_scheduler {cluster['hosts_per_scheduler']} is not hosts i mod {n}")
    prober = owner_of_host[host_index(probes["src_host_id"], cluster["hosts"])]
    names, owner_of_task = task_owners(cluster["tasks"], n)
    rng = np.random.default_rng([seed, n])
    bounds = np.cumsum([0, *chunks]) * rows
    feeders = []
    for i in range(n):
        d = downloads[bounds[i] : bounds[i + 1]].copy()
        mine = names[owner_of_task == i]
        d["task_id"] = mine[rng.integers(0, len(mine), len(d))]
        feeders.append({"hostname": f"scheduler-{i}", "scheduler_id": i, "downloads": d, "probes": probes[prober == i]})
    return feeders
