"""The uniform records dealt out to `cluster.schedulers` feeders as a
scheduler cluster holds them: every download gets one of `cluster.tasks` task
ids and goes to the scheduler its id hashes onto (crc32 modulo the count, in
place of upstream's consistent-hash ring); every scheduler probes the same
hosts itself, so each feeder sends its own probe records (feeder 0 the
uniform ones, feeder i those of seed + i)."""

import zlib

import numpy as np
import telemetry_gen


def generate(cluster: dict, seed: int) -> list[dict]:
    downloads, probes = telemetry_gen.generate_for(cluster, seed)
    n = cluster["schedulers"]
    task = np.random.default_rng([seed, n]).integers(0, cluster["tasks"], len(downloads))
    downloads["task_id"] = np.char.add(b"task-", task.astype("S8"))
    owner = np.array([zlib.crc32(t) % n for t in downloads["task_id"].tolist()])
    feeders = []
    for i in range(n):
        if i:
            probes = telemetry_gen.generate(cluster["chunk_rows"], cluster["probes"], cluster["hosts"], seed + i,
                                            chunk_rows=cluster["chunk_rows"])[1]
        feeders.append({"hostname": f"scheduler-{i}", "scheduler_id": i,
                        "downloads": downloads[owner == i], "probes": probes})
    return feeders
