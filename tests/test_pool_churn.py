"""A cluster whose hosts churn between uploads, through the trainer service:
every upload names another interval's hosts (a window over host ids that
advances between intervals), so two uploads' edges overflow `pool_max_edges`
and the pool rotates after every second close. Each run's dataset is the fold
of the commits since the last rotation, and the run manifest's `pool` and the
`trainer.ingest.merge` span say which pool that was."""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from dragonfly2_tpu.observability import tracing
from dragonfly2_tpu.telemetry.records import DOWNLOAD_DTYPE, PROBE_DTYPE, pack_records
from dragonfly2_tpu.trainer import dataset as datasetlib
from dragonfly2_tpu.trainer.service import TrainerConfig, TrainerService
from test_dataset_ingest import assert_dataset_equal

HOSTS_PER_INTERVAL = [24, 22, 26, 20]
HOSTS_REPLACED = 6  # the window over host ids advances by this many each interval
PROBES_PER_HOST = 4
# one interval's edges (at most 26 x 4) stay under it, two intervals' go over
POOL_MAX_EDGES = 130


def interval(i: int) -> tuple[np.ndarray, np.ndarray]:
    """One upload: downloads and probes over the hosts of interval i, every
    host a probe source (so the upload names exactly its interval's hosts)."""
    rng = np.random.default_rng(i)
    n = HOSTS_PER_INTERVAL[i]
    hosts = np.array([f"host-{HOSTS_REPLACED * i + j}".encode() for j in range(n)], dtype="S64")
    d = np.zeros(64, DOWNLOAD_DTYPE)
    d["child_host_id"] = hosts[rng.integers(0, n, len(d))]
    d["parent_host_id"] = hosts[rng.integers(0, n, len(d))]
    d["success"] = rng.random(len(d)) > 0.1
    d["bandwidth_bps"] = rng.lognormal(19.0, 1.5, len(d)).astype(np.float32)
    d["pair_features"] = rng.random((len(d), 16), dtype=np.float32)
    p = np.zeros(n * PROBES_PER_HOST, PROBE_DTYPE)
    p["src_host_id"] = np.repeat(hosts, PROBES_PER_HOST)
    p["dst_host_id"] = hosts[(np.repeat(np.arange(n), PROBES_PER_HOST) + rng.integers(1, n, len(p))) % n]
    p["rtt_mean_ms"] = (rng.random(len(p)) * 50).astype(np.float32)
    p["rtt_std_ms"] = (rng.random(len(p)) * 5).astype(np.float32)
    p["rtt_min_ms"] = (rng.random(len(p)) * 20).astype(np.float32)
    p["probe_count"] = rng.integers(1, 40, len(p))
    return d, p


@pytest.fixture(scope="module")
def churned(tmp_path_factory):
    """Four interval uploads, one after the other, each run ended before the
    next upload opens: the datasets the runs built, the service, and the
    finished `trainer.ingest.merge` spans."""
    svc = TrainerService(TrainerConfig(
        model_dir=str(tmp_path_factory.mktemp("models")), pool_max_edges=POOL_MAX_EDGES, min_pairs=10**9,
    ))
    built = []
    finalize = datasetlib.FrozenIngest.finalize

    def recording(self, **kw):
        built.append(finalize(self, **kw))
        return built[-1]

    async def upload(i: int) -> None:
        """Interval i in one chunk of each kind, and the run it queues."""
        d, p = interval(i)
        token = (await svc.train_open({"hostname": "scheduler"}))["token"]
        await svc.train_chunk({"token": token, "kind": "downloads", "data": pack_records(d)})
        await svc.train_chunk({"token": token, "kind": "probes", "data": pack_records(p)})
        await svc.train_close({"token": token})
        await svc.wait_idle()

    async def body():
        for i in range(len(HOSTS_PER_INTERVAL)):
            await upload(i)  # closed loop: the next upload waits for this run

    tracer = tracing.default_tracer()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(datasetlib.FrozenIngest, "finalize", recording)
        mp.setattr(tracer, "sample_rate", 1.0)
        asyncio.run(body())
    merges = [s.to_dict() for s in tracer.finished() if s.name == "trainer.ingest.merge"][-len(HOSTS_PER_INTERVAL):]
    return built, svc, merges


def test_the_pool_rotates_after_every_second_close_and_each_run_trains_on_the_commits_since(churned):
    built, svc, _ = churned
    assert svc.pool_rotations == 2 and len(built) == len(svc.run_history) == 4
    # the commits each run's pool held: one upload's hosts, then two uploads', in turn
    for run, commits in zip(built, ([0], [0, 1], [2], [2, 3])):
        want = datasetlib.DatasetAccumulator(max_pair_rows=svc.cfg.pool_rows)
        for i in commits:
            d, p = interval(i)
            want.add_downloads(d)
            want.add_probes(p)
        assert_dataset_equal(run, want.finalize())


def test_the_manifest_and_the_merge_span_say_which_pool_a_run_trained_on(churned):
    _, svc, merges = churned
    pools = [m["pool"] for m in svc.run_history]
    # interval i names hosts 6i .. 6i + n_i: two uploads' pool holds both windows
    assert [(p["epoch"], p["commits"], p["hosts"], p["hosts_added"], p["hosts_stale"], p["rotated"]) for p in pools] == [
        (0, 1, 24, 24, 0, False),
        (0, 2, 28, 4, 6, True),    # hosts 0..27; the newest upload named 6..27
        (1, 1, 26, 26, 0, False),  # a fresh pool: hosts 12..37
        (1, 2, 26, 0, 6, True),    # the newest upload named 18..37, none new
    ]
    assert all(p["edges"] <= POOL_MAX_EDGES for p in pools[0::2]) and all(p["edges"] > POOL_MAX_EDGES for p in pools[1::2])
    assert [(m["attrs"]["hosts_added"], m["attrs"]["rotated"]) for m in merges] == [
        (p["hosts_added"], p["rotated"]) for p in pools]
