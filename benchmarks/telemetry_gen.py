"""Seeded raw telemetry for the trainer: the benchmark's copy of
`dragonfly2_tpu.trainer.synthetic.synth_telemetry_records` (same fields, same
distributions, vectorized numpy), changed in one way: every COUNT that sets a
shape the trainer compiles for is fixed by the configuration and the traffic,
never drawn, so that two seeds give the same sizes in another order —

  hosts      every host id appears (the first `n_hosts` probes have each host
             once as source), so the graph always has exactly `n_hosts` rows;
  pairs      in every chunk of `chunk_rows` downloads exactly
             round(frac_failed * chunk_rows) fail and exactly
             round(frac_no_parent * chunk_rows) have no parent (placed by a
             permutation, not by a coin per row). So each chunk folds into
             the same number of pair rows, the trainer's rolling pair pool
             (which evicts whole chunks) has the same length for every seed
             and after every re-sent upload, and the scan step's compiled
             program is found in the cache again.

Imports no jax and nothing of the program: the feeder never opens the
accelerator, and the reference reads the same records. The record layouts are
the RPC surface's own (a copy of `telemetry.records` DOWNLOAD_DTYPE and
PROBE_DTYPE: what a scheduler's announcer sends, chunk by chunk, as .npy).
"""

from __future__ import annotations

import numpy as np

DOWNLOAD_DTYPE = np.dtype([
    ("task_id", "S64"), ("child_peer_id", "S64"), ("parent_peer_id", "S64"),
    ("child_host_id", "S64"), ("parent_host_id", "S64"),
    ("piece_count", "i4"), ("piece_size", "i8"), ("content_length", "i8"),
    ("bandwidth_bps", "f4"), ("piece_cost_ms_mean", "f4"),
    ("success", "?"), ("back_to_source", "?"),
    ("pair_features", "f4", (16,)), ("created_at", "f8"),
])
PROBE_DTYPE = np.dtype([
    ("src_host_id", "S64"), ("dst_host_id", "S64"),
    ("rtt_mean_ms", "f4"), ("rtt_std_ms", "f4"), ("rtt_min_ms", "f4"),
    ("probe_count", "i4"), ("created_at", "f8"),
])


def pair_rows_per_chunk(chunk_rows: int, frac_failed: float, frac_no_parent: float) -> int:
    """Pair rows (successful, with a parent) each chunk of downloads yields."""
    return chunk_rows - round(frac_failed * chunk_rows) - round(frac_no_parent * chunk_rows)


def generate(
    n_downloads: int,
    n_probes: int,
    n_hosts: int,
    seed: int,
    *,
    chunk_rows: int = 4096,
    frac_failed: float = 0.05,
    frac_no_parent: float = 0.05,
) -> tuple[np.ndarray, np.ndarray]:
    """(downloads, probes) structured arrays for one upload."""
    if n_probes < n_hosts:
        raise ValueError(f"{n_probes} probes cannot name {n_hosts} hosts as source once each")
    if n_downloads % chunk_rows:
        raise ValueError(f"{n_downloads} downloads are not whole chunks of {chunk_rows}")
    rng = np.random.default_rng(seed)
    hosts = np.char.add(b"host-", np.char.zfill(np.arange(n_hosts).astype("S6"), 6)).astype("S64")

    d = np.zeros(n_downloads, DOWNLOAD_DTYPE)
    d["child_host_id"] = hosts[rng.integers(0, n_hosts, n_downloads)]
    d["parent_host_id"] = hosts[rng.integers(0, n_hosts, n_downloads)]
    # rank of each row inside its chunk under a random order: the first ranks
    # fail, the next have no parent
    rank = np.argsort(rng.random((n_downloads // chunk_rows, chunk_rows)), axis=1).reshape(-1)
    n_failed = round(frac_failed * chunk_rows)
    n_orphan = round(frac_no_parent * chunk_rows)
    d["success"] = rank >= n_failed
    d["parent_host_id"][(rank >= n_failed) & (rank < n_failed + n_orphan)] = b""
    d["bandwidth_bps"] = rng.lognormal(19.0, 1.5, n_downloads).astype(np.float32)
    d["pair_features"] = rng.random((n_downloads, 16), dtype=np.float32)

    p = np.zeros(n_probes, PROBE_DTYPE)
    src = rng.integers(0, n_hosts, n_probes)
    src[:n_hosts] = rng.permutation(n_hosts)
    p["src_host_id"] = hosts[src]
    p["dst_host_id"] = hosts[rng.integers(0, n_hosts, n_probes)]
    p["rtt_mean_ms"] = (rng.random(n_probes) * 50).astype(np.float32)
    p["rtt_std_ms"] = (rng.random(n_probes) * 5).astype(np.float32)
    p["rtt_min_ms"] = (rng.random(n_probes) * 20).astype(np.float32)
    p["probe_count"] = rng.integers(1, 40, n_probes)
    return d, p


def generate_for(cluster: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """`generate` with the counts of a configuration's `cluster` group."""
    return generate(
        cluster["downloads"], cluster["probes"], cluster["hosts"], seed, chunk_rows=cluster["chunk_rows"],
        frac_failed=cluster["frac_failed"], frac_no_parent=cluster["frac_no_parent"],
    )
