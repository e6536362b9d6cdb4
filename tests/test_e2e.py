"""Cluster-in-a-box E2E: origin + scheduler + multiple peer engines on
localhost (the reference's kind-cluster dfget E2E shape, test/e2e/dfget_test.go
sha256 comparison — without k8s, per SURVEY.md §4 takeaway)."""

import asyncio
import hashlib
import inspect
import time

import pytest
from aiohttp import web

from dragonfly2_tpu.daemon.conductor import ConductorConfig
from dragonfly2_tpu.daemon.engine import InProcessSchedulerClient, PeerEngine
from dragonfly2_tpu.scheduler.service import SchedulerService
from dragonfly2_tpu.telemetry import TelemetryStorage
from dragonfly2_tpu.utils.pieces import parse_http_range


class Origin:
    """Localhost origin fixture with Range support + request counters.

    Adversarial knobs (ref test/tools/ fixtures):
      send_content_length=False — chunked responses with no Content-Length
        and no HEAD metadata (ref test/tools/no-content-length)
      corrupt_range_shift=N — Range responses silently serve data shifted by
        N bytes (right length, wrong bytes): digest validation must catch it
    """

    def __init__(
        self,
        files: dict[str, bytes],
        *,
        support_range: bool = True,
        send_content_length: bool = True,
        corrupt_range_shift: int = 0,
        response_delay_s: float = 0.0,
    ):
        self.files = files
        self.support_range = support_range
        self.send_content_length = send_content_length
        self.corrupt_range_shift = corrupt_range_shift
        self.response_delay_s = response_delay_s  # per-GET latency fixture
        self.requests = 0
        self.bytes_sent = 0
        self.port = 0
        self.inflight = 0
        self.max_inflight = 0
        # every 206's (start, length) — lets restart tests assert WHICH
        # bytes rode the wire, not just how many
        self.range_log: list[tuple[int, int]] = []
        self._runner = None

    async def __aenter__(self):
        app = web.Application()
        app.router.add_get("/{name}", self._handle)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, "127.0.0.1", 0)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        return self

    async def __aexit__(self, *exc):
        await self._runner.cleanup()

    async def _handle(self, request):
        name = request.match_info["name"]
        if name not in self.files:
            raise web.HTTPNotFound()
        data = self.files[name]
        if not self.send_content_length:
            # ref test/tools/no-content-length: no HEAD metadata, chunked
            # body, no ranges — the client must stream to EOF
            if request.method == "HEAD":
                raise web.HTTPMethodNotAllowed("HEAD", ["GET"])
            self.requests += 1
            self.bytes_sent += len(data)
            resp = web.StreamResponse(headers={"Accept-Ranges": "none"})
            resp.enable_chunked_encoding()
            await resp.prepare(request)
            for i in range(0, len(data), 256 * 1024):
                await resp.write(data[i : i + 256 * 1024])
            await resp.write_eof()
            return resp
        if request.method == "HEAD":  # metadata probe: no payload on the wire
            return web.Response(
                headers={
                    "Content-Length": str(len(data)),
                    "Accept-Ranges": "bytes" if self.support_range else "none",
                }
            )
        self.requests += 1
        rng_header = request.headers.get("Range")
        delay = self.response_delay_s
        if callable(delay):
            r = (
                parse_http_range(rng_header, len(data))
                if rng_header and self.support_range
                else None
            )
            delay = delay(r)
        if delay:
            self.inflight += 1
            self.max_inflight = max(self.max_inflight, self.inflight)
            try:
                # a callable may return an awaitable (a gate) instead of seconds
                await (delay if inspect.isawaitable(delay) else asyncio.sleep(delay))
            finally:
                self.inflight -= 1
        rng = rng_header
        if rng and self.support_range:
            r = parse_http_range(rng, len(data))
            shift = self.corrupt_range_shift
            src = data[r.start + shift : r.start + shift + r.length]
            body = src.ljust(r.length, b"\x00")[: r.length]
            self.range_log.append((r.start, r.length))
            self.bytes_sent += len(body)
            return web.Response(
                status=206,
                body=body,
                headers={"Content-Range": f"bytes {r.start}-{r.end}/{len(data)}"},
            )
        self.bytes_sent += len(data)
        headers = {} if self.support_range else {"Accept-Ranges": "none"}
        return web.Response(body=data, headers=headers)

    def url(self, name: str) -> str:
        return f"http://127.0.0.1:{self.port}/{name}"


def fast_conductor():
    return ConductorConfig(metadata_poll_interval=0.02, piece_timeout=10.0)


def make_engine(tmp_path, client, name, **kw):
    return PeerEngine(
        storage_root=tmp_path / name,
        scheduler=client,
        hostname=name,
        conductor_config=fast_conductor(),
        **kw,
    )


@pytest.fixture
def payload():
    # multi-piece at the test piece size is impractical with 4MiB pieces;
    # use a payload big enough for several pieces by shrinking piece size via
    # monkeypatched compute? No: pieces are 4MiB; use 10MiB => 3 pieces.
    return bytes(range(256)) * (40 * 1024)  # 10 MiB -> 3 pieces of 4 MiB


class TestE2E:
    def test_single_peer_back_to_source(self, run, tmp_path, payload):
        async def body():
            svc = SchedulerService(telemetry=TelemetryStorage(tmp_path / "telemetry"))
            client = InProcessSchedulerClient(svc)
            async with Origin({"model.bin": payload}) as origin:
                e1 = make_engine(tmp_path, client, "peer1")
                await e1.start()
                try:
                    out = tmp_path / "dl1.bin"
                    ts = await e1.download_task(origin.url("model.bin"), output=out)
                    assert out.read_bytes() == payload
                    assert ts.is_complete() and ts.meta.done
                    st = svc.stat_task(ts.meta.task_id)
                    assert st["state"] == "succeeded"
                finally:
                    await e1.stop()

        run(body())

    def test_second_peer_downloads_from_first(self, run, tmp_path, payload):
        async def body():
            svc = SchedulerService(telemetry=TelemetryStorage(tmp_path / "telemetry"))
            client = InProcessSchedulerClient(svc)
            async with Origin({"model.bin": payload}) as origin:
                e1 = make_engine(tmp_path, client, "peer1")
                e2 = make_engine(tmp_path, client, "peer2")
                await e1.start()
                await e2.start()
                try:
                    url = origin.url("model.bin")
                    await e1.download_task(url)
                    origin_requests_after_first = origin.requests

                    out = tmp_path / "dl2.bin"
                    await e2.download_task(url, output=out)
                    assert hashlib.sha256(out.read_bytes()).hexdigest() == hashlib.sha256(payload).hexdigest()
                    # peer2 got its bytes from peer1, not the origin
                    assert origin.requests == origin_requests_after_first
                    assert e1.upload.bytes_served == len(payload)
                finally:
                    await e1.stop()
                    await e2.stop()

        run(body())

    def test_concurrent_peers_share(self, run, tmp_path, payload):
        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"f.bin": payload}) as origin:
                url = origin.url("f.bin")
                engines = [make_engine(tmp_path, client, f"peer{i}") for i in range(4)]
                for e in engines:
                    await e.start()
                try:
                    first = await engines[0].download_task(url)
                    assert first.is_complete()
                    results = await asyncio.gather(
                        *(e.download_task(url) for e in engines[1:])
                    )
                    for ts in results:
                        assert ts.is_complete()
                    # all later peers combined pulled nothing more from origin
                    total_upload = sum(e.upload.bytes_served for e in engines)
                    assert origin.bytes_sent == len(payload)
                    assert total_upload >= 3 * len(payload) * 0.99
                finally:
                    for e in engines:
                        await e.stop()

        run(body())

    def test_back_to_source_pieces_fetch_concurrently(self, run, tmp_path, payload):
        """Ranged back-to-source pulls pieces over CONCURRENT origin
        connections (ref ConcurrentOption multi-connection source download):
        a slow origin must see overlapping piece requests, and a 3-piece
        download must take ~one delay, not three."""

        async def body():
            svc = SchedulerService(telemetry=TelemetryStorage(tmp_path / "telemetry"))
            client = InProcessSchedulerClient(svc)
            async with Origin({"model.bin": payload}, response_delay_s=0.3) as origin:
                e1 = make_engine(tmp_path, client, "peer1")
                await e1.start()
                try:
                    ts = await e1.download_task(origin.url("model.bin"))
                    assert ts.is_complete()
                    # the load-bearing claim: origin saw OVERLAPPING piece
                    # requests (wall-clock bounds would flake on a loaded box)
                    assert origin.max_inflight >= 2
                finally:
                    await e1.stop()

        run(body())

    def test_p2p_skips_redundant_full_verify(self, run, tmp_path, payload, monkeypatch):
        """A p2p download whose every piece was validated against an expected
        digest skips the end-of-task full re-hash (one whole read+hash pass
        per task — seconds per checkpoint shard); back-to-source, which
        computes its own digests, still runs it."""
        from dragonfly2_tpu.daemon.storage import TaskStorage

        calls = []
        orig = TaskStorage.verify

        def counting_verify(self):
            calls.append(self.meta.task_id)
            return orig(self)

        monkeypatch.setattr(TaskStorage, "verify", counting_verify)

        async def body():
            svc = SchedulerService(telemetry=TelemetryStorage(tmp_path / "telemetry"))
            client = InProcessSchedulerClient(svc)
            async with Origin({"model.bin": payload}) as origin:
                e1 = make_engine(tmp_path, client, "peer1")
                e2 = make_engine(tmp_path, client, "peer2")
                await e1.start()
                await e2.start()
                try:
                    url = origin.url("model.bin")
                    await e1.download_task(url)
                    assert len(calls) >= 1  # back-to-source verified in full
                    before_p2p = len(calls)
                    out = tmp_path / "dl2.bin"
                    await e2.download_task(url, output=out)
                    assert out.read_bytes() == payload
                    assert len(calls) == before_p2p  # p2p path: no second pass
                finally:
                    await e1.stop()
                    await e2.stop()

        run(body())

    def test_streaming_parent_digests_do_not_skip_verify(self, run, tmp_path, payload, monkeypatch):
        """The full-verify skip requires digests from parents that had
        COMPLETED (and so verified) the task. A child that raced a still-
        downloading parent learned self-computed digests over unverified
        bytes — its end-of-task full verify must still run."""
        from dragonfly2_tpu.daemon.storage import TaskStorage

        verified_tasks = []
        orig = TaskStorage.verify

        def counting_verify(self):
            verified_tasks.append(self.meta.task_id)
            return orig(self)

        monkeypatch.setattr(TaskStorage, "verify", counting_verify)

        async def body():
            svc = SchedulerService(telemetry=TelemetryStorage(tmp_path / "telemetry"))
            client = InProcessSchedulerClient(svc)
            # origin stalls ONLY the last piece's range for seconds: e1 holds
            # pieces 0-1 quickly but stays mid-download, a deterministic
            # window in which e2 syncs digests from the not-yet-done parent
            last_start = 8 << 20  # piece 2 of the 10 MiB payload
            delays = lambda r: 3.0 if (r is None or r.start >= last_start) else 0.05
            async with Origin({"model.bin": payload}, response_delay_s=delays) as origin:
                e1 = make_engine(tmp_path, client, "peer1")
                e2 = make_engine(tmp_path, client, "peer2")
                await e1.start()
                await e2.start()
                try:
                    url = origin.url("model.bin")
                    t1 = asyncio.create_task(e1.download_task(url))
                    # wait until e1 verifiably holds SOME pieces but not all
                    deadline = time.monotonic() + 10
                    while time.monotonic() < deadline:
                        held = e1.storage.tasks()
                        if held and 0 < held[0].finished_count() < 3:
                            break
                        await asyncio.sleep(0.02)
                    else:
                        pytest.fail("e1 never reached a partial state")
                    ts2 = await e2.download_task(url)
                    await t1
                    assert ts2.is_complete()
                    # e2 really pulled from e1 (the test is vacuous if e2
                    # escalated back-to-source, which always full-verifies)
                    assert e1.upload.bytes_served > 0
                    # e2 must have full-verified: its piece digests came from
                    # a parent that was not done at sync time
                    assert verified_tasks.count(ts2.meta.task_id) >= 2  # e1 + e2
                finally:
                    await e1.stop()
                    await e2.stop()

        run(body())

    def test_deferred_writes_landing_at_round_end_do_not_spin_the_drain(
        self, run, tmp_path, payload, monkeypatch
    ):
        """A child of a parent that is still downloading runs one dispatch
        round per batch of pieces the parent announces, and the rounds after
        the first are the ones the write-behind governor may defer. A
        deferred write that finished in the same event-loop turn that ended
        its round froze the loop: on Python 3.12 `asyncio.gather` over done
        tasks completes without yielding, so `while pending: await
        gather(...)` never let the tasks' `discard` callbacks run (the hang of
        `test_streaming_parent_digests_do_not_skip_verify`, ~1 run in 25).
        Pinned here with gates, no sleeps: the origin holds piece 2 until the
        child holds pieces 0-1, writes are deferred, and every store write of
        the child is released by its round's workers finishing, the turn
        before the drain looks at the pending set."""
        from dragonfly2_tpu.daemon.storage import TaskStorage

        orig_write = TaskStorage.write_piece_view
        # writes land inline (no thread hop): a released write completes in
        # the very turn its gate opens
        monkeypatch.setattr(TaskStorage, "_INLINE_HASH_BYTES", 64 << 20)
        cfg = ConductorConfig(
            metadata_poll_interval=0.02, piece_timeout=10.0, defer_piece_writes=True
        )
        piece2 = asyncio.Event()
        landed_at_round_end = []

        async def write_at_round_end(self, index, data, *, digest):
            if "peer2" in self.data_path.parts:
                workers = [
                    t for t in asyncio.all_tasks()
                    if t.get_coro().__qualname__ == "PeerTaskConductor._piece_worker"
                ]
                await asyncio.wait(workers)  # the round's workers are gone
                landed_at_round_end.append(index)
            d = await orig_write(self, index, data, digest=digest)
            if "peer2" in self.data_path.parts and self.finished_count() == 2:
                piece2.set()  # the child holds 0-1: the parent may finish
            return d

        monkeypatch.setattr(TaskStorage, "write_piece_view", write_at_round_end)

        async def body():
            client = InProcessSchedulerClient(SchedulerService())
            last_start = 8 << 20  # piece 2 of the 10 MiB payload
            gate = lambda r: piece2.wait() if (r is None or r.start >= last_start) else 0
            async with Origin({"model.bin": payload}, response_delay_s=gate) as origin:
                e1 = make_engine(tmp_path, client, "peer1")
                e2 = PeerEngine(
                    storage_root=tmp_path / "peer2", scheduler=client,
                    hostname="peer2", conductor_config=cfg,
                )
                await e1.start()
                await e2.start()
                try:
                    url = origin.url("model.bin")
                    t1 = asyncio.create_task(e1.download_task(url))
                    while not (e1.storage.tasks() and e1.storage.tasks()[0].finished_count() == 2):
                        await asyncio.sleep(0.01)
                    out = tmp_path / "dl2.bin"
                    await e2.download_task(url, output=out)
                    await t1
                    assert out.read_bytes() == payload
                    assert e1.upload.bytes_served == len(payload)  # all p2p
                    # two rounds, each ended by its writes: 0-1, then 2
                    assert sorted(landed_at_round_end) == [0, 1, 2]
                finally:
                    await e1.stop()
                    await e2.stop()

        run(body())

    def test_seed_peer_trigger(self, run, tmp_path, payload):
        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"f.bin": payload}) as origin:
                seed = make_engine(tmp_path, client, "seed1", host_type="seed")
                await seed.start()
                svc.seed_trigger = seed.seed_task
                normal = make_engine(tmp_path, client, "peerN")
                await normal.start()
                try:
                    out = tmp_path / "dlN.bin"
                    # First normal peer registers; scheduler triggers the seed;
                    # peer itself also goes back-to-source in round 1 design.
                    await normal.download_task(origin.url("f.bin"), output=out)
                    assert out.read_bytes() == payload
                    await asyncio.sleep(0.3)  # let seed finish
                    seed_ts = seed.storage.find_completed_task(
                        normal.make_meta(origin.url("f.bin")).task_id
                    )
                    assert seed_ts is not None  # seed holds the task for future peers
                finally:
                    await seed.stop()
                    await normal.stop()

        run(body())

    def test_tiny_file_inline(self, run, tmp_path):
        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            tiny = b"tiny payload!"
            async with Origin({"t.bin": tiny}) as origin:
                e1 = make_engine(tmp_path, client, "p1")
                e2 = make_engine(tmp_path, client, "p2")
                await e1.start()
                await e2.start()
                try:
                    url = origin.url("t.bin")
                    await e1.download_task(url)
                    before = origin.requests
                    out = tmp_path / "t2.bin"
                    await e2.download_task(url, output=out)
                    assert out.read_bytes() == tiny
                    assert origin.requests == before  # rode the direct piece
                finally:
                    await e1.stop()
                    await e2.stop()

        run(body())

    def test_no_range_origin(self, run, tmp_path):
        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            data = b"x" * 100_000
            async with Origin({"f": data}, support_range=False) as origin:
                e1 = make_engine(tmp_path, client, "p1")
                await e1.start()
                try:
                    out = tmp_path / "o.bin"
                    await e1.download_task(origin.url("f"), output=out)
                    assert out.read_bytes() == data
                finally:
                    await e1.stop()

        run(body())

    def test_reuse_fast_path(self, run, tmp_path, payload):
        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"f": payload}) as origin:
                e1 = make_engine(tmp_path, client, "p1")
                await e1.start()
                try:
                    url = origin.url("f")
                    await e1.download_task(url)
                    n = origin.requests
                    await e1.download_task(url)  # second download: pure reuse
                    assert origin.requests == n
                finally:
                    await e1.stop()

        run(body())

    def test_piece_push_latency(self, run, tmp_path):
        """A child must receive a freshly-written parent piece in well under
        the old 200 ms poll period — piece announcements are pushed via
        long-poll, not polled (VERDICT Next #3; ref SyncPieceTasks streams)."""

        async def body():
            import time as _time

            from dragonfly2_tpu.daemon.conductor import PeerTaskConductor
            from dragonfly2_tpu.daemon.storage import StorageManager
            from dragonfly2_tpu.daemon.upload import UploadServer
            from dragonfly2_tpu.scheduler.service import (
                HostInfo, ParentInfo, RegisterResult, TaskMeta,
            )

            # Parent: real upload server over a task with 2 of 3 pieces done.
            piece, total = 4 << 20, 10 << 20
            data = bytes(range(256)) * (40 * 1024)
            parent_sm = StorageManager(tmp_path / "parent")
            tid = "pushlat01"
            pts = parent_sm.register_task(tid, url="http://x/f")
            pts.set_task_info(content_length=total, piece_size=piece, total_pieces=3)
            await pts.write_piece(0, data[:piece])
            await pts.write_piece(1, data[piece : 2 * piece])
            upload = UploadServer(parent_sm, port=0)
            await upload.start()

            class StubScheduler:
                """Hands out the one parent; absorbs reports."""

                async def register_peer(self, peer_id, meta, host):
                    return RegisterResult(
                        scope="normal", task_id=tid,
                        parents=[ParentInfo("parent1", "h1", "127.0.0.1", upload.port)],
                        content_length=total, piece_size=piece, total_pieces=3,
                    )

                async def report_task_metadata(self, *a, **k): ...
                async def report_piece_result(self, *a, **k): ...
                async def report_peer_result(self, *a, **k): ...
                async def leave_peer(self, *a, **k): ...

                async def reschedule(self, peer_id):
                    raise AssertionError("push path must not burn reschedules")

            from dragonfly2_tpu.daemon.source import SourceRegistry

            conductor = PeerTaskConductor(
                peer_id="child1",
                meta=TaskMeta(task_id=tid, url="http://x/f"),
                host=HostInfo(id="c", ip="127.0.0.1", hostname="c"),
                scheduler=StubScheduler(),
                storage=StorageManager(tmp_path / "child"),
                sources=SourceRegistry(),
                config=ConductorConfig(piece_timeout=10.0),
            )
            dl = asyncio.ensure_future(conductor.run())
            try:
                # Wait until the child has consumed the two available pieces.
                t_dead = _time.monotonic() + 10
                while _time.monotonic() < t_dead:
                    cts = conductor.ts
                    if cts is not None and cts.finished_count() == 2:
                        break
                    await asyncio.sleep(0.01)
                assert conductor.ts is not None and conductor.ts.finished_count() == 2
                await asyncio.sleep(0.3)  # child is now parked on the long-poll
                t_write = _time.monotonic()
                await pts.write_piece(2, data[2 * piece :])
                ts = await dl
                t_done = _time.monotonic()
                assert ts.is_complete()
                # full final piece: push notify + one 4MiB localhost fetch.
                # Bound is loose for CI noise but still far under what
                # repeated 200ms polling rounds would cost.
                assert t_done - t_write < 1.0, f"push latency {t_done - t_write:.3f}s"
            finally:
                if not dl.done():
                    dl.cancel()
                await upload.stop()

        run(body())

    def test_no_content_length_origin(self, run, tmp_path):
        """ref test/tools/no-content-length: chunked origin, no HEAD, no CL —
        the unknown-length streaming path must still produce a digest-exact
        copy and later peers must ride P2P off it."""

        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            data = bytes(range(256)) * 30_000  # ~7.3 MiB
            async with Origin({"f": data}, send_content_length=False) as origin:
                e1 = make_engine(tmp_path, client, "p1")
                e2 = make_engine(tmp_path, client, "p2")
                await e1.start()
                await e2.start()
                try:
                    url = origin.url("f")
                    out1 = tmp_path / "ncl1.bin"
                    await e1.download_task(url, output=out1)
                    assert out1.read_bytes() == data
                    n = origin.requests
                    out2 = tmp_path / "ncl2.bin"
                    await e2.download_task(url, output=out2)
                    assert out2.read_bytes() == data
                    assert origin.requests == n  # peer2 rode P2P
                finally:
                    await e1.stop()
                    await e2.stop()

        run(body())

    def test_corrupt_range_origin_rejected(self, run, tmp_path, payload):
        """Adversarial origin: Range responses shifted one byte (right
        length, wrong bytes). With a task digest the download must FAIL
        loudly, and the poisoned copy must not be marked done/reusable."""

        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            digest = "sha256:" + hashlib.sha256(payload).hexdigest()
            async with Origin({"f": payload}, corrupt_range_shift=1) as origin:
                e1 = make_engine(tmp_path, client, "p1")
                await e1.start()
                try:
                    with pytest.raises(Exception) as ei:
                        await e1.download_task(origin.url("f"), digest=digest)
                    assert "digest" in str(ei.value).lower()
                    meta = e1.make_meta(origin.url("f"), digest=digest)
                    assert e1.storage.find_completed_task(meta.task_id) is None
                finally:
                    await e1.stop()

        run(body())

    def test_parent_kill_mid_task_reschedules(self, run, tmp_path, payload):
        """Mid-download parent death: child must reschedule and finish via
        back-to-source with a byte-exact result (ref reschedule path,
        service_v1.go:1033-1151)."""

        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"f": payload}) as origin:
                url = origin.url("f")
                parent = make_engine(tmp_path, client, "parent")
                await parent.start()
                # throttle the child so the parent dies MID-task
                child = make_engine(
                    tmp_path, client, "child", total_download_rate_bps=4e6
                )
                await child.start()
                try:
                    await parent.download_task(url)  # parent holds all pieces
                    task = asyncio.ensure_future(
                        child.download_task(url, output=tmp_path / "ck.bin")
                    )
                    # wait until the child has SOME bytes but not all
                    deadline = asyncio.get_running_loop().time() + 10
                    while asyncio.get_running_loop().time() < deadline:
                        cts = child.storage.get(
                            child.make_meta(url).task_id
                        )
                        if cts is not None and 0 < cts.finished_count() < 3:
                            break
                        await asyncio.sleep(0.02)
                    # kill the parent mid-task: upload server gone + scheduler
                    # told the host left (the keepalive-loss path)
                    await parent.upload.stop()
                    svc.leave_host(parent.host_id)
                    ts = await asyncio.wait_for(task, 60)
                    assert ts.is_complete()
                    assert (tmp_path / "ck.bin").read_bytes() == payload
                    # the finish came from origin (back-to-source), not the corpse
                    assert origin.bytes_sent > len(payload)
                finally:
                    await parent.stop()
                    await child.stop()

        run(body())

    def test_telemetry_records_p2p_transfer(self, run, tmp_path, payload):
        async def body():
            svc = SchedulerService(telemetry=TelemetryStorage(tmp_path / "tel"))
            client = InProcessSchedulerClient(svc)
            async with Origin({"f": payload}) as origin:
                e1 = make_engine(tmp_path, client, "p1")
                e2 = make_engine(tmp_path, client, "p2")
                await e1.start()
                await e2.start()
                try:
                    url = origin.url("f")
                    await e1.download_task(url)
                    await e2.download_task(url)
                finally:
                    await e1.stop()
                    await e2.stop()
            svc.telemetry.flush()
            recs = svc.telemetry.downloads.load_all()
            assert len(recs) >= 2
            p2p = recs[recs["parent_peer_id"] != b""]
            assert len(p2p) >= 1
            assert p2p["bandwidth_bps"].max() > 0

        run(body())
