"""Telemetry→dataset ingest pipeline: vectorized ≡ rowloop equivalence,
incremental accumulator semantics, non-blocking trainer service, announcer
snapshot cut, and the event-loop heartbeat during a real GNN train."""

from __future__ import annotations

import asyncio
import hashlib
import time

import numpy as np
import pytest

from dragonfly2_tpu.rpc.core import RpcServer
from dragonfly2_tpu.rpc.trainer import RemoteTrainerClient, register_trainer
from dragonfly2_tpu.scheduler.announcer import TrainerAnnouncer
from dragonfly2_tpu.telemetry import TelemetryStorage
from dragonfly2_tpu.telemetry.records import DOWNLOAD_DTYPE, PROBE_DTYPE, pack_records
from dragonfly2_tpu.trainer import dataset as datasetlib, train_gnn, train_mlp
from dragonfly2_tpu.trainer.service import TrainerConfig, TrainerService
from dragonfly2_tpu.trainer.synthetic import synth_telemetry_records


def synth_telemetry(n_downloads, n_probes, n_hosts, seed=0, **kw):
    """The shared generator (trainer.synthetic — same one the bench uses),
    with the slightly-dirtier defaults the equivalence suite wants."""
    kw.setdefault("frac_failed", 0.1)
    kw.setdefault("frac_no_parent", 0.1)
    return synth_telemetry_records(n_downloads, n_probes, n_hosts, seed, **kw)


def assert_dataset_equal(got: datasetlib.Dataset, want: datasetlib.Dataset, *, exact=False):
    """got ≡ want. Node numbering, neighbor tables, pair indices and labels
    must match EXACTLY; edge features may differ by float32-vs-float64
    accumulation order unless `exact` (identical-value probes) is claimed."""
    assert got.host_index == want.host_index
    np.testing.assert_array_equal(got.graph.neighbors, want.graph.neighbors)
    np.testing.assert_array_equal(got.graph.mask, want.graph.mask)
    np.testing.assert_array_equal(got.graph.node_feats, want.graph.node_feats)
    if exact:
        np.testing.assert_array_equal(got.graph.edge_feats, want.graph.edge_feats)
    else:
        np.testing.assert_allclose(
            got.graph.edge_feats, want.graph.edge_feats, rtol=1e-5, atol=1e-7
        )
    np.testing.assert_array_equal(got.pairs.child, want.pairs.child)
    np.testing.assert_array_equal(got.pairs.parent, want.pairs.parent)
    np.testing.assert_array_equal(got.pairs.feats, want.pairs.feats)
    np.testing.assert_array_equal(got.pairs.label, want.pairs.label)


# ---------------------------------------------------------------------------
# vectorized build_dataset ≡ rowloop reference


@pytest.mark.parametrize("seed", range(5))
def test_vectorized_equals_rowloop_randomized(seed):
    d, p = synth_telemetry(2000, 600, 50, seed=seed)
    assert_dataset_equal(
        datasetlib.build_dataset(d, p), datasetlib._build_dataset_rowloop(d, p)
    )


def test_equivalence_empty_inputs():
    z = np.zeros(0)  # the service's 0-row placeholder is NOT structured
    assert_dataset_equal(
        datasetlib.build_dataset(z, z),
        datasetlib._build_dataset_rowloop(z, z),
        exact=True,
    )
    d = np.zeros(0, DOWNLOAD_DTYPE)
    p = np.zeros(0, PROBE_DTYPE)
    ds = datasetlib.build_dataset(d, p)
    assert ds.num_nodes == 8 and ds.num_pairs == 1  # min_nodes pad + pair default


def test_equivalence_no_probes_and_no_downloads():
    d, p = synth_telemetry(300, 0, 12, seed=1)
    assert_dataset_equal(
        datasetlib.build_dataset(d, p), datasetlib._build_dataset_rowloop(d, p)
    )
    d2, p2 = synth_telemetry(0, 200, 12, seed=2)
    assert_dataset_equal(
        datasetlib.build_dataset(d2, p2), datasetlib._build_dataset_rowloop(d2, p2)
    )


def test_equivalence_all_back_to_source():
    d, p = synth_telemetry(250, 120, 10, seed=3, frac_no_parent=1.0)
    assert_dataset_equal(
        datasetlib.build_dataset(d, p), datasetlib._build_dataset_rowloop(d, p)
    )


def test_equivalence_all_failed_downloads():
    # hosts enter the table only via probes; failed-row parents must still
    # count toward total_cnt (zero success rate) exactly like the rowloop
    d, p = synth_telemetry(300, 150, 10, seed=4, frac_failed=1.0)
    assert_dataset_equal(
        datasetlib.build_dataset(d, p), datasetlib._build_dataset_rowloop(d, p)
    )


def test_equivalence_over_degree_with_exact_rtt_ties():
    # one source probing 3x max_neighbors destinations on a coarse RTT grid:
    # identical-value ties force the top-k cut through the stable
    # insertion-order tie-break, and grid means are exact in both paths
    n_dst = 48
    hosts = np.array([f"h{i:04d}".encode() for i in range(n_dst + 1)], dtype="S64")
    rng = np.random.default_rng(5)
    p = np.zeros(3 * n_dst, PROBE_DTYPE)
    p["src_host_id"] = hosts[0]
    p["dst_host_id"] = np.tile(hosts[1:], 3)
    rtt = np.repeat(rng.integers(1, 5, n_dst) * 0.25, 1).astype(np.float32)
    p["rtt_mean_ms"] = np.tile(rtt, 3)  # every snapshot identical per edge
    p["rtt_std_ms"] = 0.5
    p["rtt_min_ms"] = np.tile(rtt, 3) / 2
    p["probe_count"] = 10
    d = np.zeros(0, DOWNLOAD_DTYPE)
    got = datasetlib.build_dataset(d, p, max_neighbors=16)
    want = datasetlib._build_dataset_rowloop(d, p, max_neighbors=16)
    assert_dataset_equal(got, want, exact=True)
    assert got.graph.mask[0].sum() == 16  # over-degree cut applied


# ---------------------------------------------------------------------------
# DatasetAccumulator: incremental ≡ one-shot


@pytest.mark.parametrize("chunk", [7, 173, 4096])
def test_accumulator_chunked_equals_oneshot(chunk):
    d, p = synth_telemetry(1500, 500, 40, seed=6, rtt_grid=0.25)
    acc = datasetlib.DatasetAccumulator()
    for s in range(0, len(d), chunk):
        acc.add_downloads(d[s : s + chunk])
    for s in range(0, len(p), chunk):
        acc.add_probes(p[s : s + chunk])
    assert_dataset_equal(acc.finalize(), datasetlib.build_dataset(d, p))
    assert acc.download_rows == len(d) and acc.probe_rows == len(p)


def test_accumulator_finalize_is_repeatable_and_incremental():
    d, p = synth_telemetry(400, 150, 20, seed=7)
    acc = datasetlib.DatasetAccumulator()
    acc.add_downloads(d)
    acc.add_probes(p)
    first = acc.finalize()
    assert_dataset_equal(acc.finalize(), first)  # non-destructive
    d2, p2 = synth_telemetry(200, 80, 30, seed=8)
    acc.add_downloads(d2)
    acc.add_probes(p2)
    again = acc.finalize()
    assert again.num_pairs > first.num_pairs
    # earlier hosts keep their node rows — incremental growth, not rebuild
    for host, idx in first.host_index.items():
        assert again.host_index[host] == idx


def test_accumulator_pair_pool_eviction_keeps_newest():
    d, p = synth_telemetry(900, 0, 15, seed=9, frac_failed=0.0, frac_no_parent=0.0)
    acc = datasetlib.DatasetAccumulator(max_pair_rows=300)
    for s in range(0, len(d), 100):
        acc.add_downloads(d[s : s + 100])
    # same rolling semantics as the old per-session pool: evict oldest whole
    # chunks while the rest alone still covers the cap
    assert 300 <= acc.pair_rows <= 400
    ds = acc.finalize()
    tail = datasetlib.build_dataset(d[-acc.pair_rows :], p)
    np.testing.assert_array_equal(ds.pairs.label, tail.pairs.label)
    # aggregates are NOT evicted: every host ever seen keeps its node row
    assert len(ds.host_index) == 15


def test_merge_from_equals_direct_folds():
    """Pool semantics: committing two session accumulators via merge_from
    must equal folding both sessions' chunks into one accumulator."""
    d1, p1 = synth_telemetry(400, 150, 25, seed=20)
    d2, p2 = synth_telemetry(300, 100, 40, seed=21)  # overlapping + new hosts
    a = datasetlib.DatasetAccumulator()
    a.add_downloads(d1)
    a.add_probes(p1)
    b = datasetlib.DatasetAccumulator()
    b.add_downloads(d2)
    b.add_probes(p2)
    pool = datasetlib.DatasetAccumulator()
    pool.merge_from(a)
    pool.merge_from(b)
    ref = datasetlib.DatasetAccumulator()
    for arr_d, arr_p in ((d1, p1), (d2, p2)):
        ref.add_downloads(arr_d)
        ref.add_probes(arr_p)
    assert_dataset_equal(pool.finalize(), ref.finalize())
    assert pool.download_rows == 700 and pool.probe_rows == 250
    # empty merge is a no-op
    pool.merge_from(datasetlib.DatasetAccumulator())
    assert_dataset_equal(pool.finalize(), ref.finalize())


def test_accumulator_freeze_isolated_from_later_folds():
    d, p = synth_telemetry(300, 100, 12, seed=10)
    acc = datasetlib.DatasetAccumulator()
    acc.add_downloads(d)
    acc.add_probes(p)
    frozen = acc.freeze()
    want = acc.finalize()
    d2, p2 = synth_telemetry(200, 50, 25, seed=11)
    acc.add_downloads(d2)
    acc.add_probes(p2)
    assert_dataset_equal(frozen.finalize(), want, exact=True)


# ---------------------------------------------------------------------------
# host ids and edge keys interned as integers: every id the records can carry


def _xor_hash(words):
    """A degenerate hash for the tests: four values, so distinct ids share
    one in every batch and the exact path runs for hits and for new ids."""
    return np.bitwise_xor.reduce(words, axis=1) & np.uint64(3)


def _renamed(arrays, names):
    """The records with host-i's id replaced by names(i); b"" stays b""."""
    out = []
    for arr in arrays:
        arr = arr.copy()
        for col in ("child_host_id", "parent_host_id", "src_host_id", "dst_host_id"):
            if col in arr.dtype.names:
                old = arr[col]
                named = old != b""
                arr[col][named] = [names(int(h[5:])) for h in old[named].tolist()]
        out.append(arr)
    return out


ID_SETS = {
    # upstream's host ids: SHA-256 hex digests, all 64 characters
    "hex64": dict(names=lambda i: hashlib.sha256(b"%d" % i).hexdigest().encode()),
    # ids that differ only in their last byte
    "last_byte": dict(names=lambda i: b"h" * 63 + bytes([i + 1])),
    # short ids that S64 pads with NULs beside full-width ones
    "short_and_full": dict(names=lambda i: b"host-%06d" % i if i % 2 else (b"%04d" % i) * 16),
    # half the downloads back-to-source: b"" parents
    "empty_parents": dict(names=lambda i: b"host-%06d" % i, frac_no_parent=0.5),
    # every batch's ids share four hashes: the exact path for hits, for
    # new ids within one batch, and in merge_from
    "forced_collisions": dict(names=lambda i: hashlib.sha256(b"%d" % i).hexdigest().encode(), hash=_xor_hash),
    # a second upload of hosts the pool has never seen, merged into it
    "merge_new_hosts": dict(names=lambda i: b"host-%06d" % i, second=200),
}


@pytest.mark.parametrize("id_set", sorted(ID_SETS))
def test_interning_over_id_sets(id_set, monkeypatch):
    case = ID_SETS[id_set]
    if "hash" in case:
        monkeypatch.setattr(datasetlib, "_hash_ids", case["hash"])
    frac = case.get("frac_no_parent", 0.1)
    d, p = _renamed(synth_telemetry(900, 400, 60, seed=30, frac_no_parent=frac), case["names"])
    offset = case.get("second", 30)
    d2, p2 = _renamed(synth_telemetry(500, 250, 60, seed=31, frac_no_parent=frac), lambda i: case["names"](i + offset))

    # one upload: chunked fold == one-shot == the row-by-row walk
    want = datasetlib._build_dataset_rowloop(d, p)
    assert_dataset_equal(datasetlib.build_dataset(d, p), want)
    sessions = []
    for dd, pp in ((d, p), (d2, p2)):
        acc = datasetlib.DatasetAccumulator()
        for s in range(0, len(dd), 37):
            acc.add_downloads(dd[s : s + 37])
        for s in range(0, len(pp), 37):
            acc.add_probes(pp[s : s + 37])
        sessions.append(acc)
    assert_dataset_equal(sessions[0].finalize(), want)
    assert_dataset_equal(sessions[1].finalize(), datasetlib._build_dataset_rowloop(d2, p2))

    # both committed into a pool == both folded into one accumulator
    pool = datasetlib.DatasetAccumulator()
    pool.merge_from(sessions[0])
    merged_before = pool.keys.collisions
    pool.merge_from(sessions[1])
    direct = datasetlib.DatasetAccumulator()
    for dd, pp in ((d, p), (d2, p2)):
        direct.add_downloads(dd)
        direct.add_probes(pp)
    assert_dataset_equal(pool.finalize(), direct.finalize())
    assert pool.num_hosts == direct.num_hosts == len(direct.finalize().host_index)
    if id_set == "merge_new_hosts":
        assert pool.num_hosts == sessions[0].num_hosts + sessions[1].num_hosts
    collided = [sessions[0].keys.collisions, pool.keys.collisions - merged_before]
    if "hash" in case:
        assert min(collided) > 0
    else:
        assert collided == [0, 0]


def _known_upload():
    """Three downloads and three probes over hosts a, b, c, d, e."""
    d = np.zeros(4, DOWNLOAD_DTYPE)
    d["child_host_id"] = [b"a", b"a", b"d", b"e"]
    d["parent_host_id"] = [b"b", b"c", b"b", b""]
    d["success"] = [True, True, False, True]
    p = np.zeros(3, PROBE_DTYPE)
    p["src_host_id"] = [b"a", b"b", b"a"]
    p["dst_host_id"] = [b"b", b"a", b"b"]
    return d, p


def test_key_counts_of_a_known_upload():
    """Per call, `looked_up` counts the positions whose key a table already
    held and `admitted` the distinct new keys: hosts [a, b, a, c] admit 3,
    parents [b, c, b] admit 2, probes [a, b, b, a, a, b] look up 6, edges
    [ab, ba, ab] admit 2. A commit into an empty pool admits every key of
    the session once; a second commit of the same upload looks them up."""
    d, p = _known_upload()
    acc = datasetlib.DatasetAccumulator()
    acc.add_downloads(d)
    acc.add_probes(p)
    assert acc.keys == datasetlib.KeyCounts(looked_up=6, admitted=7, collisions=0)
    pool = datasetlib.DatasetAccumulator()
    pool.merge_from(acc)
    assert pool.keys == datasetlib.KeyCounts(looked_up=0, admitted=7, collisions=0)
    pool.merge_from(acc)
    assert pool.keys == datasetlib.KeyCounts(looked_up=7, admitted=7, collisions=0)


def test_the_manifest_carries_the_key_counts(run, tmp_path, monkeypatch):
    async def upload(svc):
        d, p = _known_upload()
        token = (await svc.train_open({"hostname": "s"}))["token"]
        await svc.train_chunk({"token": token, "kind": "downloads", "data": pack_records(d)})
        await svc.train_chunk({"token": token, "kind": "probes", "data": pack_records(p)})
        await svc.train_close({"token": token})
        await svc.wait_idle()

    async def body(svc):
        await upload(svc)
        await upload(svc)  # the same records again: the pool holds every key
        return [m["ingest"] for m in svc.run_history]

    svc = TrainerService(TrainerConfig(model_dir=str(tmp_path / "a"), min_pairs=10**9))
    first, second = run(body(svc))
    # the session's folds, then its commit: into the empty pool, then into one that holds it
    assert (first["keys_looked_up"], first["keys_admitted"], first["collisions"]) == (6, 14, 0)
    assert (second["keys_looked_up"], second["keys_admitted"], second["collisions"]) == (13, 7, 0)
    # one hash for every id: all but the first id of each table take the exact path
    monkeypatch.setattr(datasetlib, "_hash_ids", lambda words: np.zeros(len(words), np.uint64))
    svc = TrainerService(TrainerConfig(model_dir=str(tmp_path / "b"), min_pairs=10**9))
    forced = run(body(svc))
    assert [m["keys_admitted"] for m in forced] == [14, 7] and min(m["collisions"] for m in forced) > 0


# ---------------------------------------------------------------------------
# trainer service: incremental fold, row accounting, TTL, non-blocking close


def test_train_chunk_running_row_counter(run, tmp_path):
    async def body():
        # min_pairs above the data volume: the close must commit + queue but
        # train nothing (this test pins accounting, not training)
        svc = TrainerService(TrainerConfig(model_dir=str(tmp_path), min_pairs=10_000))
        token = (await svc.train_open({"hostname": "s"}))["token"]
        d, p = synth_telemetry(120, 40, 10, seed=12)
        out = await svc.train_chunk({"token": token, "kind": "downloads", "data": pack_records(d)})
        assert out["rows"] == 120
        out = await svc.train_chunk({"token": token, "kind": "probes", "data": pack_records(p)})
        assert out["rows"] == 160  # running counter, not a per-call re-sum
        # chunks fold into the SESSION accumulator on arrival; the shared
        # pool sees nothing until the close commits (exactly-once)
        sess = svc._sessions[token]
        assert sess.acc.download_rows == 120 and sess.acc.probe_rows == 40
        assert svc._acc.download_rows == 0
        with pytest.raises(ValueError):
            await svc.train_chunk({"token": token, "kind": "bogus", "data": pack_records(d)})
        await svc.train_close({"token": token})
        assert svc._acc.download_rows == 120 and svc._acc.probe_rows == 40
        await svc.wait_idle()

    run(body())


def test_session_ttl_eviction(run, tmp_path):
    async def body():
        svc = TrainerService(TrainerConfig(model_dir=str(tmp_path), session_ttl=0.05))
        stale = (await svc.train_open({"hostname": "old"}))["token"]
        slow = (await svc.train_open({"hostname": "slow-stream"}))["token"]
        d, _ = synth_telemetry(10, 0, 4, seed=19)
        await asyncio.sleep(0.04)
        # an upload still streaming chunks past the TTL is NOT stale —
        # activity refreshes its clock
        await svc.train_chunk({"token": slow, "kind": "downloads", "data": pack_records(d)})
        await asyncio.sleep(0.04)
        fresh = (await svc.train_open({"hostname": "new"}))["token"]  # triggers eviction
        assert svc.sessions_evicted == 1
        assert slow in svc._sessions
        with pytest.raises(KeyError):
            await svc.train_chunk({"token": stale, "kind": "downloads", "data": pack_records(np.zeros(0, DOWNLOAD_DTYPE))})
        with pytest.raises(KeyError):
            await svc.train_close({"token": stale})
        await svc.train_close({"token": fresh})
        await svc.wait_idle()

    run(body())


def test_train_close_queues_without_blocking(run, tmp_path):
    async def body():
        svc = TrainerService(TrainerConfig(model_dir=str(tmp_path)))
        started, release = [], asyncio.Event()

        async def slow_training(sess):
            started.append(sess.token)
            await release.wait()
            return {"version": sess.token, "num_pairs": 0, "num_nodes": 0}

        svc._run_training = slow_training
        t1 = (await svc.train_open({}))["token"]
        t2 = (await svc.train_open({}))["token"]
        out1 = await svc.train_close({"token": t1})
        await asyncio.sleep(0.01)  # let the drainer enter run #1
        t0 = time.perf_counter()
        out2 = await svc.train_close({"token": t2})
        close_s = time.perf_counter() - t0
        # the old path awaited the WHOLE previous training run here
        assert close_s < 0.05, f"train_close blocked {close_s:.3f}s behind a running train"
        assert out1["queued"] and out2["queued"]
        st = await svc.status()
        assert st["training"] and st["queue_depth"] == 1
        assert started == [t1]  # strictly serialized: run #2 not started yet
        release.set()
        await svc.wait_idle()
        assert svc.trains_started == 2 and svc.trains_succeeded == 2
        assert svc.last_result["version"] == t2

    run(body())


def test_drainer_coalesces_same_pool_closes(run, tmp_path):
    async def body():
        svc = TrainerService(TrainerConfig(model_dir=str(tmp_path)))
        ran, release = [], asyncio.Event()

        async def slow_training(sess):
            ran.append(sess.token)
            await release.wait()
            return {"version": sess.token, "num_pairs": 0, "num_nodes": 0}

        svc._run_training = slow_training
        tokens = [(await svc.train_open({}))["token"] for _ in range(4)]
        await svc.train_close({"token": tokens[0]})
        await asyncio.sleep(0.01)  # drainer enters run #1 and blocks
        for t in tokens[1:]:
            await svc.train_close({"token": t})  # dflint: disable=DF025 test drives N sequential closes to pin drainer coalescing
        release.set()
        await svc.wait_idle()
        # the 3 closes that landed mid-train share the pool: ONE run covers
        # them (the pool already aggregated all three commits)
        assert ran == [tokens[0], tokens[3]]
        assert svc.trains_started == 2 and svc.trains_coalesced == 2

    run(body())


def test_pool_rotation_bounds_aggregates(run, tmp_path):
    async def body():
        svc = TrainerService(
            TrainerConfig(model_dir=str(tmp_path), pool_max_hosts=8, min_pairs=10_000)
        )
        d, p = synth_telemetry(100, 30, 20, seed=16)  # 20 hosts > cap of 8
        token = (await svc.train_open({}))["token"]
        await svc.train_chunk({"token": token, "kind": "downloads", "data": pack_records(d)})
        await svc.train_chunk({"token": token, "kind": "probes", "data": pack_records(p)})
        await svc.train_close({"token": token})
        await svc.wait_idle()
        # the queued train still saw the over-cap pool it folded into...
        assert svc.last_result["num_nodes"] == 20
        # ...but the shared pool was rotated fresh so aggregates stay bounded
        assert svc.pool_rotations == 1
        st = await svc.status()
        assert st["pool_hosts"] == 0 and st["pool_edges"] == 0

    run(body())


# ---------------------------------------------------------------------------
# announcer: snapshot cut — rows appended mid-upload survive the clear


class _RecordingTrainer:
    """Stands in for RemoteTrainerClient; appends rows to the live store
    mid-upload to model telemetry arriving while the RPCs are in flight."""

    def __init__(self, store: TelemetryStorage, late_rows: int):
        self.store = store
        self.late_rows = late_rows
        self.uploaded = {"downloads": 0, "probes": 0}
        self.closed = False

    async def train_open(self, hostname, scheduler_id):
        return "tok"

    async def train_chunk(self, token, kind, records):
        self.uploaded[kind] += len(records)
        while self.late_rows > 0:
            self.late_rows -= 1
            self.store.downloads.append(
                child_host_id=b"late-child", parent_host_id=b"late-parent",
                success=True, bandwidth_bps=1.0,
            )
        return sum(self.uploaded.values())

    async def train_close(self, token):
        self.closed = True

    async def close(self):
        pass


def test_announcer_clear_cut_keeps_midupload_rows(run, tmp_path):
    async def body():
        store = TelemetryStorage(tmp_path / "t")
        d, p = synth_telemetry(300, 50, 10, seed=13)
        for row in d:
            store.downloads.append(**{k: row[k] for k in d.dtype.names if k != "created_at"})
        for row in p:
            store.probes.append(**{k: row[k] for k in p.dtype.names if k != "created_at"})
        ann = TrainerAnnouncer(store, "127.0.0.1:1", hostname="sch")
        await ann.trainer.close()
        ann.trainer = _RecordingTrainer(store, late_rows=7)
        out = await ann.upload_once()
        assert out["downloads"] == 300 and out["probes"] == 50
        assert ann.trainer.uploaded == {"downloads": 300, "probes": 50}
        # the cut: everything uploaded is gone, everything late survives
        left = store.downloads.load_all()
        assert len(left) == 7
        assert set(bytes(r) for r in left["child_host_id"]) == {b"late-child"}
        assert len(store.probes.load_all()) == 0
        await ann.stop()

    run(body())


def test_snapshot_at_backup_cap_loses_nothing(tmp_path):
    # at the max_backups cap a PRUNING flush would delete the oldest
    # unuploaded file an instant before the cut reads it — the cut flush
    # must skip pruning (reproduces the review finding: 14 rows present,
    # only 10 made the snapshot)
    store = TelemetryStorage(tmp_path, rotate_rows=4, max_backups=3)
    d, _ = synth_telemetry(14, 0, 5, seed=17)
    for row in d:
        store.downloads.append(**{k: row[k] for k in d.dtype.names if k != "created_at"})
    assert len(store.downloads.load_all()) == 14
    arr, cut = store.downloads.snapshot()
    assert len(arr) == 14
    store.downloads.discard(cut)
    assert len(store.downloads.load_all()) == 0
    # ordinary append-path flushes still prune
    d2, _ = synth_telemetry(20, 0, 5, seed=18)
    for row in d2:
        store.downloads.append(**{k: row[k] for k in d2.dtype.names if k != "created_at"})
    store.downloads.flush()
    assert len(store.downloads._files()) <= 3


def test_snapshot_discard_roundtrip(tmp_path):
    store = TelemetryStorage(tmp_path, rotate_rows=16)
    d, _ = synth_telemetry(40, 0, 5, seed=14)  # spans files + buffer
    for row in d:
        store.downloads.append(**{k: row[k] for k in d.dtype.names if k != "created_at"})
    arr, cut = store.downloads.snapshot()
    assert len(arr) == 40 and len(cut) >= 3  # buffer flushed into the cut
    # rows appended after the cut belong to the next cycle
    store.downloads.append(child_host_id=b"x", parent_host_id=b"y", success=True)
    store.downloads.discard(cut)
    assert len(store.downloads.load_all()) == 1


# ---------------------------------------------------------------------------
# heartbeat: the trainer keeps answering RPCs while a GNN train runs


def test_status_rpc_heartbeat_during_gnn_train(run, tmp_path):
    """Acceptance: a status RPC answers in <100 ms (median over the whole
    train, covering dataset build, MLP, and the GNN scan-step loop) while
    training runs. Median keeps the 2-core CI image's scheduling blips from
    flaking the test; the loop samples continuously until training ends."""

    async def body():
        svc = TrainerService(
            TrainerConfig(
                model_dir=str(tmp_path / "models"),
                mlp=train_mlp.MLPTrainConfig(hidden=(16,), steps=20, batch_size=64),
                gnn=train_gnn.GNNTrainConfig(
                    hidden=16, embed_dim=8, num_layers=2, batch_size=64, warmup_steps=2
                ),
                gnn_steps=30,
                gnn_steps_per_call=2,  # frequent yields back to the loop
            )
        )
        server = RpcServer(host="127.0.0.1", port=0)
        register_trainer(server, svc)
        await server.start()
        client = RemoteTrainerClient(server.address)
        try:
            d, p = synth_telemetry(400, 120, 16, seed=15, frac_failed=0.0)
            token = await client.train_open("sch", 0)
            await client.train_chunk(token, "downloads", d)
            await client.train_chunk(token, "probes", p)
            await client.train_close(token)

            latencies = []
            sampled_mid_train = 0
            deadline = time.perf_counter() + 120.0
            while time.perf_counter() < deadline:
                t0 = time.perf_counter()
                st = await client.status()
                latencies.append(time.perf_counter() - t0)
                if st["training"]:
                    sampled_mid_train += 1
                elif sampled_mid_train:
                    break  # training observed, then finished
                await asyncio.sleep(0.01)
            await svc.wait_idle()
            assert sampled_mid_train >= 5, "train finished before the heartbeat sampled it"
            assert svc.last_result and "gnn" in svc.last_result, svc.last_result
            median_ms = float(np.median(latencies)) * 1000
            assert median_ms < 100, (
                f"status RPC median {median_ms:.1f} ms during training "
                f"(n={len(latencies)}, mid-train={sampled_mid_train})"
            )
        finally:
            await client.close()
            await server.stop()

    run(body())
