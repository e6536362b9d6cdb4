"""One scheduler holds every record, every host drawn uniformly: the seeded
telemetry of telemetry_gen.py (which says what the counts fix and why), sent
by one feeder."""

import telemetry_gen


def generate(cluster: dict, seed: int) -> list[dict]:
    downloads, probes = telemetry_gen.generate_for(cluster, seed)
    return [{"hostname": "benchmark-feeder", "scheduler_id": 0, "downloads": downloads, "probes": probes}]
