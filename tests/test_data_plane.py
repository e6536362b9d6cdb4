"""Data plane v2 (ISSUE 13): TLS fast path (cipher autoselect, bulk-BIO
transport, session resumption, kTLS null-probe), striped multi-parent fetch
with slowest-stripe tail steal, and the adaptive write-behind governor."""

import asyncio
import hashlib
import socket
import ssl

import pytest
from aiohttp import web

from dragonfly2_tpu.daemon import metrics
from dragonfly2_tpu.daemon.conductor import (
    ConductorConfig,
    ParentState,
    PeerTaskConductor,
    PieceDispatcher,
    WriteBehindGovernor,
)
from dragonfly2_tpu.daemon.engine import InProcessSchedulerClient, PeerEngine
from dragonfly2_tpu.daemon.rawrange import RawRangeClient
from dragonfly2_tpu.daemon.storage import StorageManager
from dragonfly2_tpu.daemon.upload import UploadServer
from dragonfly2_tpu.scheduler.service import HostInfo, ParentInfo, SchedulerService
from dragonfly2_tpu.security import transport as tport
from dragonfly2_tpu.security.ca import CertificateAuthority, write_issued
from dragonfly2_tpu.utils.pieces import Range

from tests.test_e2e import Origin, fast_conductor, make_engine


@pytest.fixture(scope="module")
def certs(tmp_path_factory):
    """One CA + loopback leaf for the whole module (the openssl-CLI backend
    shells out per issuance; per-test issuance would dominate wall-clock)."""
    td = tmp_path_factory.mktemp("dp-ca")
    ca = CertificateAuthority(td / "ca")
    leaf = ca.issue("data-plane-test", sans=["127.0.0.1", "localhost"])
    return write_issued(leaf, td / "leaf")


@pytest.fixture()
def data_tls(certs):
    # microbench=False: the probe is exercised by its own test; every other
    # test just needs working contexts
    return tport.DataPlaneTls.from_paths(
        certs["cert"], certs["key"], certs["ca"], microbench=False
    )


@pytest.fixture
def payload():
    return bytes(range(256)) * (40 * 1024)  # 10 MiB -> 3 pieces of 4 MiB


# ---------------------------------------------------------------------------
# cipher policy + probes


class TestCipherPolicy:
    def test_env_override_and_validation(self, monkeypatch):
        monkeypatch.setenv("DRAGONFLY_PIECE_CIPHER", "chacha20")
        assert tport.cipher_policy() == "chacha20"
        monkeypatch.setenv("DRAGONFLY_PIECE_CIPHER", "rot13")
        with pytest.raises(ValueError):
            tport.cipher_policy()
        monkeypatch.delenv("DRAGONFLY_PIECE_CIPHER")
        assert tport.cipher_policy(force="aes-gcm") == "aes-gcm"

    def test_cpuinfo_prior(self):
        accel = tport.detect_aes_accel()
        assert accel in (True, False, None)
        picked = tport.cipher_policy()
        if accel is False:
            assert picked == "chacha20"
        else:
            assert picked == "aes-gcm"

    def test_data_policy_pins_tls12_and_cipher(self, certs):
        ctx = tport.data_server_ssl_context(
            certs["cert"], certs["key"], certs["ca"], policy="chacha20"
        )
        assert ctx.minimum_version == ssl.TLSVersion.TLSv1_2
        assert ctx.maximum_version == ssl.TLSVersion.TLSv1_2
        names = {c["name"] for c in ctx.get_ciphers()}
        # TLS1.3 suite names always list; the negotiable 1.2 set must be
        # chacha-only (no AES-GCM 1.2 suites survive the policy string)
        assert any("CHACHA20" in n for n in names)
        assert not any("AES" in n and not n.startswith("TLS_") for n in names)

    def test_ktls_probe_null_reports(self):
        out = tport.probe_ktls()
        assert set(out) == {"available", "reason"}
        assert isinstance(out["available"], bool) and out["reason"]
        # this image's kernel has no tls ULP — kTLS CANNOT be available, and
        # a True here would mean the probe fabricated support
        assert out["available"] is False

    def test_cipher_microbench_measures_both(self, certs):
        rates = tport.measure_cipher_rates(
            certs["cert"], certs["key"], certs["ca"], mb=1
        )
        assert rates["aes-gcm"] > 0 and rates["chacha20"] > 0
        assert rates["picked"] in ("aes-gcm", "chacha20")
        assert rates["picked"] == max(
            ("aes-gcm", "chacha20"), key=lambda p: rates[p]
        )

    def test_session_cache_lru(self):
        cache = tport.TlsSessionCache(max_entries=2)
        assert cache.get(("a", 1)) is None and cache.misses == 1
        cache.put(("a", 1), None)  # None sessions never cached
        assert len(cache) == 0


# ---------------------------------------------------------------------------
# the bulk-BIO transport


async def _accept_one(server_sock, ctx):
    loop = asyncio.get_running_loop()
    conn, _ = await loop.sock_accept(server_sock)
    conn.setblocking(False)
    return await tport.AsyncTlsTransport.accept(conn, ctx)


class TestAsyncTlsTransport:
    def _ctxs(self, certs):
        srv = tport.data_server_ssl_context(certs["cert"], certs["key"], certs["ca"])
        cli = tport.data_client_ssl_context(certs["ca"], certs["cert"], certs["key"])
        return srv, cli

    def test_roundtrip_recv_into_and_resumption(self, run, certs):
        srv_ctx, cli_ctx = self._ctxs(certs)
        body = bytes(range(256)) * 4096  # 1 MiB

        async def connect_once(port, session=None):
            loop = asyncio.get_running_loop()
            s = socket.socket()
            s.setblocking(False)
            await loop.sock_connect(s, ("127.0.0.1", port))
            return await tport.AsyncTlsTransport.connect(s, cli_ctx, session=session)

        async def main():
            ls = socket.socket()
            ls.bind(("127.0.0.1", 0))
            ls.listen(2)
            ls.setblocking(False)
            port = ls.getsockname()[1]

            async def serve():
                t = await _accept_one(ls, srv_ctx)
                # echo a header-ish line then the body (exercises recv +
                # recv_into on the client side)
                await t.sendall(b"OK\r\n" + body)
                t.close()

            server_task = asyncio.ensure_future(serve())
            t1 = await connect_once(port)
            assert t1.session_reused is False
            head = await t1.recv(4)
            assert head == b"OK\r\n"
            buf = bytearray(len(body))
            view = memoryview(buf)
            off = 0
            while off < len(body):
                n = await t1.recv_into(view[off:])
                assert n > 0
                off += n
            assert bytes(buf) == body
            sess = t1.session
            assert sess is not None
            t1.close()
            await server_task

            # second connect resumes with the first's session
            server_task = asyncio.ensure_future(serve())
            t2 = await connect_once(port, session=sess)
            assert t2.session_reused is True
            assert (await t2.recv(4)) == b"OK\r\n"
            got = await t2.recv(len(body))
            while len(got) < len(body):
                got += await t2.recv(len(body) - len(got))
            assert got == body
            t2.close()
            await server_task
            ls.close()

        run(main())

    def test_peer_close_surfaces_as_zero(self, run, certs):
        srv_ctx, cli_ctx = self._ctxs(certs)

        async def main():
            ls = socket.socket()
            ls.bind(("127.0.0.1", 0))
            ls.listen(1)
            ls.setblocking(False)
            port = ls.getsockname()[1]

            async def serve():
                t = await _accept_one(ls, srv_ctx)
                await t.sendall(b"xy")
                t.close()  # close_notify then FIN

            server_task = asyncio.ensure_future(serve())
            loop = asyncio.get_running_loop()
            s = socket.socket()
            s.setblocking(False)
            await loop.sock_connect(s, ("127.0.0.1", port))
            t = await tport.AsyncTlsTransport.connect(s, cli_ctx)
            assert (await t.recv(2)) == b"xy"
            buf = bytearray(8)
            assert await t.recv_into(memoryview(buf)) == 0  # EOF, not an exception
            t.close()
            await server_task
            ls.close()

        run(main())

    def test_close_unblocks_threaded_drain(self, run, certs):
        """The piece-timeout contract: close() from the loop thread must
        wake a drain worker blocked in recv(2) on a stalled parent — close
        alone does not on Linux; the shutdown(2) inside close() does. A
        regression here leaks one executor thread per stalled-parent timeout
        until the default pool is exhausted daemon-wide."""
        import time

        srv_ctx, cli_ctx = self._ctxs(certs)

        async def main():
            ls = socket.socket()
            ls.bind(("127.0.0.1", 0))
            ls.listen(1)
            ls.setblocking(False)
            port = ls.getsockname()[1]
            stall = asyncio.Event()

            async def serve():
                t = await _accept_one(ls, srv_ctx)
                await t.sendall(b"x" * 1024)  # partial body, then stall
                await stall.wait()
                t.close()

            server_task = asyncio.ensure_future(serve())
            loop = asyncio.get_running_loop()
            s = socket.socket()
            s.setblocking(False)
            await loop.sock_connect(s, ("127.0.0.1", port))
            t = await tport.AsyncTlsTransport.connect(s, cli_ctx)
            buf = bytearray(1 << 20)  # wants far more than the server sends
            drain = asyncio.ensure_future(
                t.recv_body_into(memoryview(buf), 0)  # no timeout: only close can wake it
            )
            await asyncio.sleep(0.2)  # worker drains the 1 KiB, blocks in recv
            t0 = time.monotonic()
            t.close()
            with pytest.raises(IOError):
                await drain
            assert time.monotonic() - t0 < 2.0  # woke immediately, no hang
            stall.set()
            await server_task
            ls.close()

        run(main())

    def test_drain_idle_timeout_self_unblocks(self, run, certs):
        """Belt-and-braces leg: even with no close() ever arriving, the
        armed socket timeout fails the drain after the idle bound, so a
        worker can never outlive its caller indefinitely (and the client's
        drain semaphore is released on the same clock)."""
        srv_ctx, cli_ctx = self._ctxs(certs)

        async def main():
            ls = socket.socket()
            ls.bind(("127.0.0.1", 0))
            ls.listen(1)
            ls.setblocking(False)
            port = ls.getsockname()[1]
            stall = asyncio.Event()

            async def serve():
                t = await _accept_one(ls, srv_ctx)
                await t.sendall(b"x" * 1024)
                await stall.wait()
                t.close()

            server_task = asyncio.ensure_future(serve())
            loop = asyncio.get_running_loop()
            s = socket.socket()
            s.setblocking(False)
            await loop.sock_connect(s, ("127.0.0.1", port))
            t = await tport.AsyncTlsTransport.connect(s, cli_ctx)
            buf = bytearray(1 << 20)
            with pytest.raises(IOError, match="timed out"):
                await t.recv_body_into(memoryview(buf), 0, timeout=0.3)
            t.close()
            stall.set()
            await server_task
            ls.close()

        run(main())


# ---------------------------------------------------------------------------
# rawrange + upload server over mTLS


def _register_payload_task(root, payload) -> tuple[StorageManager, str]:
    sm = StorageManager(root)
    ts = sm.register_task("abc123task", url="http://x/f")
    from dragonfly2_tpu.utils.pieces import compute_piece_size, piece_count

    psize = compute_piece_size(len(payload))
    ts.set_task_info(
        content_length=len(payload), piece_size=psize,
        total_pieces=piece_count(len(payload), psize),
    )
    return sm, "abc123task"


class TestTlsPiecePath:
    def test_rawrange_fetch_over_mtls_with_resumption(self, run, tmp_path, data_tls, payload):
        """The shipping wire: UploadServer(tls) serving a real task file,
        RawRangeClient(tls) fetching ranges — bit-exact bytes, handshake
        metrics moving, and a post-prune reconnect resuming the session."""

        async def main():
            sm, task_id = _register_payload_task(tmp_path / "srv", payload)
            ts = sm.get(task_id)
            from dragonfly2_tpu.utils.pieces import piece_range

            for idx in range(ts.meta.total_pieces):
                r = piece_range(idx, ts.meta.piece_size, len(payload))
                await ts.write_piece(idx, payload[r.start : r.start + r.length])
            ts.mark_done()

            srv = UploadServer(sm, tls=data_tls.server_ctx)
            await srv.start()
            client = RawRangeClient(tls=data_tls)
            try:
                full0 = metrics.PIECE_TLS_HANDSHAKES_TOTAL.labels(resumed="false").value
                res0 = metrics.PIECE_TLS_HANDSHAKES_TOTAL.labels(resumed="true").value
                path_qs = f"/download/{task_id[:3]}/{task_id}?peerId=p1"
                r = Range(0, ts.meta.piece_size)
                body = await client.get_range(
                    "127.0.0.1", srv.port, path_qs, r.header(), r.length
                )
                assert bytes(body) == payload[: r.length]
                assert (
                    metrics.PIECE_TLS_HANDSHAKES_TOTAL.labels(resumed="false").value
                    == full0 + 1
                )

                # pooled keep-alive: the second range pays NO handshake
                r2 = Range(ts.meta.piece_size, ts.meta.piece_size)
                body2 = await client.get_range(
                    "127.0.0.1", srv.port, path_qs, r2.header(), r2.length
                )
                assert bytes(body2) == payload[r2.start : r2.start + r2.length]
                assert (
                    metrics.PIECE_TLS_HANDSHAKES_TOTAL.labels(resumed="false").value
                    == full0 + 1
                )

                # drop the pool (idle prune / reconnect storm): the fresh
                # connect resumes the cached session — abbreviated handshake
                client._idle_ttl = -1.0
                client.prune()
                client._idle_ttl = 60.0
                body3 = await client.get_range(
                    "127.0.0.1", srv.port, path_qs, r.header(), r.length
                )
                assert bytes(body3) == payload[: r.length]
                assert (
                    metrics.PIECE_TLS_HANDSHAKES_TOTAL.labels(resumed="true").value
                    == res0 + 1
                )
            finally:
                await client.close()
                await srv.stop()

        run(main())

    def test_plain_client_rejected_by_mtls_server(self, run, tmp_path, data_tls, payload):
        """Secure-by-default means a non-TLS client cannot pull pieces."""

        async def main():
            sm, task_id = _register_payload_task(tmp_path / "srv2", payload)
            ts = sm.get(task_id)
            await ts.write_piece(0, payload[: ts.meta.piece_size])
            srv = UploadServer(sm, tls=data_tls.server_ctx)
            await srv.start()
            client = RawRangeClient()  # no tls bundle
            try:
                r = Range(0, ts.meta.piece_size)
                with pytest.raises((IOError, ConnectionError)):
                    await client.get_range(
                        "127.0.0.1", srv.port,
                        f"/download/{task_id[:3]}/{task_id}?peerId=p1",
                        r.header(), r.length, timeout=5.0,
                    )
            finally:
                await client.close()
                await srv.stop()

        run(main())

    def test_malformed_request_answered_400_then_closed(self, run, tmp_path, data_tls, payload):
        """A bad request line must come back as an HTTP 400 over the wire —
        not a silent drop with a server-side traceback — and the connection
        closes after it (the framing may be desynced past recovery)."""

        async def main():
            sm, task_id = _register_payload_task(tmp_path / "srv400", payload)
            ts = sm.get(task_id)
            await ts.write_piece(0, payload[: ts.meta.piece_size])
            srv = UploadServer(sm, tls=data_tls.server_ctx)
            await srv.start()
            loop = asyncio.get_running_loop()
            s = socket.socket()
            s.setblocking(False)
            await loop.sock_connect(s, ("127.0.0.1", srv.port))
            t = await tport.AsyncTlsTransport.connect(s, data_tls.client_ctx)
            try:
                # a POST with a BODY: the unread body bytes queued server-
                # side are the RST trap — close() without draining them
                # would destroy the 400 in flight
                await t.sendall(
                    b"POST /download/abc/abc123task HTTP/1.1\r\n"
                    b"Content-Length: 65536\r\n\r\n" + b"p" * 65536
                )
                resp = bytearray()
                while b"\r\n\r\n" not in resp:
                    chunk = await t.recv(4096)
                    if not chunk:
                        break
                    resp += chunk
                assert resp.startswith(b"HTTP/1.1 400")
                assert b"connection: close" in resp.lower()
                # server drops the connection after the error response: the
                # stream drains to EOF rather than waiting for a next request
                while True:
                    chunk = await asyncio.wait_for(t.recv(4096), 5.0)
                    if not chunk:
                        break
            finally:
                t.close()
                await srv.stop()

        run(main())

    def test_engine_p2p_over_mtls_bit_exact(self, run, tmp_path, data_tls, payload):
        """Two engines on the mTLS piece plane: seed back-to-source, child
        pulls every piece over TLS (upload server counters prove it), sha256
        bit-exact. The PR 6 posture at the new wire speed."""

        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"model.bin": payload}) as origin:
                e1 = make_engine(tmp_path, client, "tlspeer1", data_tls=data_tls)
                e2 = make_engine(tmp_path, client, "tlspeer2", data_tls=data_tls)
                await e1.start()
                await e2.start()
                try:
                    url = origin.url("model.bin")
                    await e1.download_task(url)
                    served0 = e1.upload.bytes_served
                    out = tmp_path / "tls-dl.bin"
                    await e2.download_task(url, output=out)
                    assert (
                        hashlib.sha256(out.read_bytes()).hexdigest()
                        == hashlib.sha256(payload).hexdigest()
                    )
                    # every byte rode e1's TLS upload server
                    assert e1.upload.bytes_served - served0 == len(payload)
                finally:
                    await e1.stop()
                    await e2.stop()

        run(body())


# ---------------------------------------------------------------------------
# striped multi-parent fetch


def _two_parent_state(window=4):
    d = PieceDispatcher(epsilon=0.0, stripe_window=window)
    d.update_parents(
        [
            ParentInfo("pa", "ha", "127.0.0.1", 1001),
            ParentInfo("pb", "hb", "127.0.0.1", 1002),
        ]
    )
    d.set_pieces("pa", {0, 1, 2, 3})
    d.set_pieces("pb", {0, 1, 2, 3})
    return d


class TestStripedDispatcher:
    def test_balanced_pick_spreads_by_in_flight(self):
        d = _two_parent_state()
        first = d.pick(0, striped=True)
        d.begin(first)
        second = d.pick(1, striped=True)
        assert second.info.peer_id != first.info.peer_id
        d.begin(second)
        # tie again: deterministic min over (in_flight, -score)
        third = d.pick(2, striped=True)
        assert third is not None
        d.end(first)
        # pa freed a slot: next pick goes back to it
        assert d.pick(3, striped=True).info.peer_id == first.info.peer_id

    def test_window_full_falls_back_to_least_loaded(self):
        d = _two_parent_state(window=1)
        a = d.pick(0, striped=True)
        d.begin(a)
        b = d.pick(1, striped=True)
        d.begin(b)
        # both windows full: still returns a parent (queue provides the
        # real backpressure), the least-loaded one
        s = d.pick(2, striped=True)
        assert s is not None

    def test_exclude_routes_around_parent(self):
        d = _two_parent_state()
        got = d.pick(0, striped=True, exclude=frozenset(("pa",)))
        assert got.info.peer_id == "pb"
        assert d.pick(0, striped=True, exclude=frozenset(("pa", "pb"))) is None

    def test_unstriped_pick_is_score_max(self):
        d = _two_parent_state()
        d.parents["pa"].record(True, 10.0)
        d.parents["pa"].record(True, 10.0)
        d.parents["pb"].record(False, 0.0)
        # in_flight load must NOT divert the classic pick
        d.begin(d.parents["pa"])
        assert d.pick(0).info.peer_id == "pa"


def _child_conductor(tmp_path, client, engine, url, name, cfg=None):
    meta = engine.make_meta(url)
    return PeerTaskConductor(
        peer_id=f"{name}-peer",
        meta=meta,
        host=HostInfo(id=f"{name}-host", ip="127.0.0.1", hostname=name),
        scheduler=client,
        storage=StorageManager(tmp_path / name),
        sources=__import__(
            "dragonfly2_tpu.daemon.source", fromlist=["SourceRegistry"]
        ).SourceRegistry(),
        config=cfg or fast_conductor(),
    )


class TestStripedFetch:
    def test_two_parents_both_serve_stripes(self, run, tmp_path, payload):
        """A hot 2-parent task stripes across both parents' upload servers:
        bit-exact result, every parent served at least one piece, and the
        stripe histogram sees width 2."""

        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"hot.bin": payload}) as origin:
                url = origin.url("hot.bin")
                e1 = make_engine(tmp_path, client, "sp1")
                e2 = make_engine(tmp_path, client, "sp2")
                await e1.start()
                await e2.start()
                try:
                    await e1.download_task(url)
                    await e2.download_task(url)
                    served1, served2 = e1.upload.bytes_served, e2.upload.bytes_served
                    conductor = _child_conductor(tmp_path, client, e1, url, "stripe-child")
                    conductor.dispatcher.epsilon = 0.0  # deterministic split
                    ts = await asyncio.wait_for(conductor.run(), 60)
                    assert ts.is_complete()
                    data = await ts.read_range(Range(0, ts.meta.content_length))
                    assert data == payload
                    # striping engaged: BOTH parents landed pieces
                    assert len(conductor.pieces_by_parent) == 2, conductor.pieces_by_parent
                    assert sum(conductor.pieces_by_parent.values()) == ts.meta.total_pieces
                    # and both actually moved bytes on the wire
                    assert e1.upload.bytes_served > served1
                    assert e2.upload.bytes_served > served2
                    assert (
                        (e1.upload.bytes_served - served1)
                        + (e2.upload.bytes_served - served2)
                        == len(payload)
                    )
                finally:
                    await e1.stop()
                    await e2.stop()

        run(body())

    def test_striped_off_single_parent_assignment(self, run, tmp_path, payload):
        """striped_fetch=False restores the classic score-max funnel (the
        A/B baseline): one parent serves everything when ε=0."""

        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"cold.bin": payload}) as origin:
                url = origin.url("cold.bin")
                e1 = make_engine(tmp_path, client, "np1")
                e2 = make_engine(tmp_path, client, "np2")
                await e1.start()
                await e2.start()
                try:
                    await e1.download_task(url)
                    await e2.download_task(url)
                    cfg = fast_conductor()
                    cfg.striped_fetch = False
                    conductor = _child_conductor(
                        tmp_path, client, e1, url, "nostripe-child", cfg
                    )
                    conductor.dispatcher.epsilon = 0.0
                    ts = await asyncio.wait_for(conductor.run(), 60)
                    data = await ts.read_range(Range(0, ts.meta.content_length))
                    assert data == payload
                    assert len(conductor.pieces_by_parent) == 1
                finally:
                    await e1.stop()
                    await e2.stop()

        run(body())

    def test_tail_steal_rescues_slow_stripe(self, run, tmp_path, payload):
        """A parent whose serve path stalls holds its stripe hostage; an
        idle worker must steal the piece from the healthy parent, the task
        completes bit-exact, and downloaded-byte accounting stays exactly
        one payload (the winner-lands-once guard)."""

        class StallingBucket:
            def __init__(self, delay):
                self.delay = delay

            async def acquire(self, n):
                await asyncio.sleep(self.delay)

        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"steal.bin": payload}) as origin:
                url = origin.url("steal.bin")
                e1 = make_engine(tmp_path, client, "sl1")
                e2 = make_engine(tmp_path, client, "sl2")
                await e1.start()
                await e2.start()
                try:
                    await e1.download_task(url)
                    await e2.download_task(url)
                    # e1's serves now stall far past the steal threshold
                    e1.upload.bucket = StallingBucket(5.0)
                    cfg = fast_conductor()
                    cfg.steal_min_ms = 120.0
                    cfg.piece_timeout = 20.0
                    bytes0 = metrics.DOWNLOAD_BYTES.value
                    won0 = metrics.PIECE_STEALS_TOTAL.labels(won="true").value
                    conductor = _child_conductor(
                        tmp_path, client, e1, url, "steal-child", cfg
                    )
                    conductor.dispatcher.epsilon = 0.0
                    ts = await asyncio.wait_for(conductor.run(), 60)
                    data = await ts.read_range(Range(0, ts.meta.content_length))
                    assert data == payload
                    # at least one stolen piece won (e1 held >= 1 stripe and
                    # could never finish inside the steal threshold)
                    assert conductor.steals_won >= 1
                    assert (
                        metrics.PIECE_STEALS_TOTAL.labels(won="true").value
                        - won0
                        == conductor.steals_won
                    )
                    # accounting: the payload landed EXACTLY once
                    assert metrics.DOWNLOAD_BYTES.value - bytes0 == len(payload)
                    assert (
                        sum(conductor.pieces_by_parent.values())
                        == ts.meta.total_pieces
                    )
                finally:
                    await e1.stop()
                    await e2.stop()

        run(body())


# ---------------------------------------------------------------------------
# adaptive write-behind


class TestWriteBehindGovernor:
    def test_forced_modes_skip_measurement(self):
        g = WriteBehindGovernor(True, cpu_count=2)
        assert g.defer is True and not g.measuring
        g = WriteBehindGovernor(False, cpu_count=64)
        assert g.defer is False and not g.measuring

    def test_two_core_host_stays_inline(self):
        g = WriteBehindGovernor(None, cpu_count=2)
        assert g.measuring and g.defer is False  # inline while measuring
        g.note(0.1, 0.05)
        g.note(0.1, 0.05)
        assert g.decide() is False  # the PR 3 inversion: no spare cores
        assert g.snapshot()["mode"] == "inline"

    def test_spare_cores_and_real_writes_defer(self):
        g = WriteBehindGovernor(None, cpu_count=8)
        g.note(0.1, 0.04)
        g.note(0.1, 0.04)
        assert g.decide() is True
        assert g.snapshot()["mode"] == "deferred"

    def test_negligible_writes_stay_inline_even_with_cores(self):
        g = WriteBehindGovernor(None, cpu_count=8)
        g.note(0.2, 0.001)
        g.note(0.2, 0.001)
        assert g.decide() is False

    def test_tiny_round_keeps_measuring(self):
        g = WriteBehindGovernor(None, cpu_count=8)
        g.note(0.1, 0.1)
        assert g.decide() is False and g.measuring  # 1 sample: undecided
        g.note(0.1, 0.1)
        assert g.decide() is True and not g.measuring

    def test_decision_exports_metrics(self):
        g = WriteBehindGovernor(None, cpu_count=8)
        g.note(0.3, 0.2)
        g.note(0.3, 0.2)
        g.decide()
        assert metrics.WRITE_BEHIND_MODE.labels(mode="deferred").value == 1.0
        assert metrics.WRITE_BEHIND_STAGE_MS.labels(stage="recv").value == pytest.approx(600.0)
        assert metrics.WRITE_BEHIND_STAGE_MS.labels(stage="write").value == pytest.approx(400.0)

    def test_engine_p2p_decides_a_mode(self, run, tmp_path, payload):
        """End to end: a real P2P download drives the governor through
        measure → decide, and the one-hot mode gauge lands on exactly one
        non-measuring state."""

        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"wb.bin": payload}) as origin:
                url = origin.url("wb.bin")
                e1 = make_engine(tmp_path, client, "wb1")
                e2 = make_engine(tmp_path, client, "wb2")
                await e1.start()
                await e2.start()
                try:
                    await e1.download_task(url)
                    out = tmp_path / "wb-dl.bin"
                    await e2.download_task(url, output=out)
                    assert out.read_bytes() == payload
                    modes = {
                        m: metrics.WRITE_BEHIND_MODE.labels(mode=m).value
                        for m in ("inline", "deferred", "forced_inline", "forced_deferred")
                    }
                    assert sum(modes.values()) == 1.0, modes
                finally:
                    await e1.stop()
                    await e2.stop()

        run(body())


class TestExactlyOnceAccounting:
    def test_duplicate_landing_accounts_once(self, run, tmp_path, payload):
        """storage._land_piece dedups racing WRITES but returns success to
        both writers — the conductor's _accounted guard is what keeps
        bytes/metrics/reports exactly-once when a steal and its original
        both land. Drive _account_piece_success twice for one piece."""

        class _Sched:
            def __init__(self):
                self.successes = []

            async def register_peer(self, *a, **k): ...
            async def report_piece_result(self, peer_id, idx, *, success,
                                          cost_ms=0.0, parent_id=""):
                if success:
                    self.successes.append(idx)

        async def body():
            sched = _Sched()
            conductor = PeerTaskConductor(
                peer_id="dup-peer",
                meta=__import__(
                    "dragonfly2_tpu.scheduler.service", fromlist=["TaskMeta"]
                ).TaskMeta(task_id="dup-task", url="d7y://x/dup-task"),
                host=HostInfo(id="dup-host", ip="127.0.0.1", hostname="dup"),
                scheduler=sched,
                storage=StorageManager(tmp_path / "dup"),
                sources=__import__(
                    "dragonfly2_tpu.daemon.source", fromlist=["SourceRegistry"]
                ).SourceRegistry(),
                config=ConductorConfig(batch_piece_reports=False),
            )
            state = ParentState(
                __import__(
                    "dragonfly2_tpu.scheduler.service", fromlist=["ParentInfo"]
                ).ParentInfo("pa", "ha", "127.0.0.1", 1)
            )
            bytes0 = metrics.DOWNLOAD_BYTES.value
            await conductor._account_piece_success(state, 3, 10.0, 4096)
            await conductor._account_piece_success(state, 3, 12.0, 4096)
            assert conductor.bytes_from_parents == 4096  # once, not twice
            assert metrics.DOWNLOAD_BYTES.value - bytes0 == 4096
            assert conductor.pieces_by_parent == {"pa": 1}
            assert sched.successes == [3]  # one scheduler report
            assert state.successes == 2  # the parent's samples both count

        run(body())
