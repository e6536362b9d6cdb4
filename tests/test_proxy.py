"""Proxy + registry mirror + stream-task tests (ref client/daemon/proxy,
transport; tested the in-process way, SURVEY.md §4)."""

import asyncio
import hashlib

import aiohttp
import pytest
from aiohttp import web

from dragonfly2_tpu.daemon.engine import InProcessSchedulerClient, PeerEngine
from dragonfly2_tpu.daemon.proxy import (
    ProxyConfig,
    ProxyRule,
    ProxyServer,
    RegistryMirrorConfig,
)
from dragonfly2_tpu.scheduler.service import SchedulerService
from tests.test_e2e import Origin, fast_conductor, make_engine

PAYLOAD = bytes(range(256)) * 2048  # 512 KiB


def proxy_session(proxy: ProxyServer) -> aiohttp.ClientSession:
    return aiohttp.ClientSession()


async def proxy_get(proxy: ProxyServer, url: str, headers: dict | None = None):
    async with aiohttp.ClientSession() as sess:
        async with sess.get(
            url, proxy=f"http://127.0.0.1:{proxy.port}", headers=headers or {}
        ) as resp:
            return resp.status, dict(resp.headers), await resp.read()


class TestProxyRules:
    def test_decide_first_match_wins(self):
        cfg = ProxyConfig(
            rules=[
                ProxyRule(regex=r"\.bin$", use_p2p=True),
                ProxyRule(regex=r"example\.com", direct=True),
            ]
        )
        p = ProxyServer(engine=None, config=cfg)
        assert p._decide("GET", "http://example.com/a.bin")[0] == "p2p"
        assert p._decide("GET", "http://example.com/a.txt")[0] == "passthrough"
        assert p._decide("GET", "http://other.com/x")[0] == "passthrough"
        # non-GET never rides p2p
        assert p._decide("POST", "http://example.com/a.bin")[0] == "passthrough"

    def test_decide_redirect_rewrites_host(self):
        cfg = ProxyConfig(
            rules=[ProxyRule(regex=r"cdn\.example\.com", redirect="http://mirror.local:9999")]
        )
        p = ProxyServer(engine=None, config=cfg)
        route, url = p._decide("GET", "http://cdn.example.com/file.bin?x=1")
        assert route == "p2p"
        assert url == "http://mirror.local:9999/file.bin?x=1"

    def test_decide_registry_blobs(self):
        cfg = ProxyConfig(
            registry_mirror=RegistryMirrorConfig(base_url="http://127.0.0.1:5000")
        )
        p = ProxyServer(engine=None, config=cfg)
        blob = "http://127.0.0.1:5000/v2/library/nginx/blobs/sha256:" + "a" * 64
        manifest = "http://127.0.0.1:5000/v2/library/nginx/manifests/latest"
        assert p._decide("GET", blob)[0] == "p2p"
        assert p._decide("GET", manifest)[0] == "passthrough"

    def test_mirror_base_url_trailing_slash_normalized(self):
        cfg = RegistryMirrorConfig(base_url="http://127.0.0.1:5000/")
        assert cfg.base_url == "http://127.0.0.1:5000"
        p = ProxyServer(engine=None, config=ProxyConfig(registry_mirror=cfg))
        blob = "http://127.0.0.1:5000/v2/x/blobs/sha256:" + "b" * 64
        assert p._decide("GET", blob)[0] == "p2p"


class TestProxyE2E:
    def test_p2p_route_serves_via_engine(self, run, tmp_path):
        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"model.bin": PAYLOAD}) as origin:
                engine = make_engine(tmp_path, client, "proxypeer")
                await engine.start()
                proxy = ProxyServer(
                    engine,
                    config=ProxyConfig(rules=[ProxyRule(regex=r"\.bin$")]),
                )
                await proxy.start()
                try:
                    status, headers, data = await proxy_get(proxy, origin.url("model.bin"))
                    assert status == 200
                    assert data == PAYLOAD
                    assert headers.get("X-Dragonfly-Via") == "p2p"
                    assert int(headers["Content-Length"]) == len(PAYLOAD)
                    # the engine stored it as a task → second fetch reuses
                    reqs = origin.requests
                    status, headers, data2 = await proxy_get(proxy, origin.url("model.bin"))
                    assert data2 == PAYLOAD
                    assert origin.requests == reqs  # served from local storage
                finally:
                    await proxy.stop()
                    await engine.stop()

        run(body())

    def test_passthrough_route(self, run, tmp_path):
        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"page.txt": b"hello proxy"}) as origin:
                engine = make_engine(tmp_path, client, "proxypeer2")
                await engine.start()
                proxy = ProxyServer(engine, config=ProxyConfig())  # no rules
                await proxy.start()
                try:
                    status, headers, data = await proxy_get(proxy, origin.url("page.txt"))
                    assert status == 200
                    assert data == b"hello proxy"
                    assert "X-Dragonfly-Via" not in headers
                finally:
                    await proxy.stop()
                    await engine.stop()

        run(body())

    def test_lowercase_range_header_skips_p2p(self, run, tmp_path):
        async def body():
            class MustNotBeUsed:
                async def stream_task(self, url, **kw):  # pragma: no cover
                    raise AssertionError("ranged request must not ride p2p")

            data = b"0123456789abcdef"
            async with Origin({"r.bin": data}) as origin:
                proxy = ProxyServer(
                    MustNotBeUsed(), config=ProxyConfig(rules=[ProxyRule(regex=r"\.bin$")])
                )
                await proxy.start()
                try:
                    # raw socket: send a lowercase range header (case-insensitive per RFC)
                    reader, writer = await asyncio.open_connection("127.0.0.1", proxy.port)
                    writer.write(
                        f"GET {origin.url('r.bin')} HTTP/1.1\r\n"
                        f"range: bytes=0-3\r\n\r\n".encode()
                    )
                    await writer.drain()
                    resp = await reader.read()
                    writer.close()
                    assert b"206" in resp.split(b"\r\n", 1)[0]
                    assert resp.endswith(b"0123")
                finally:
                    await proxy.stop()

        run(body())

    def test_chunked_post_body_forwarded(self, run, tmp_path):
        async def body():
            seen = {}
            app = web.Application()

            async def echo(req):
                seen["body"] = await req.read()
                return web.Response(text="ok")

            app.router.add_post("/echo", echo)
            runner = web.AppRunner(app, access_log=None)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = site._server.sockets[0].getsockname()[1]

            proxy = ProxyServer(None, config=ProxyConfig())
            await proxy.start()
            try:
                reader, writer = await asyncio.open_connection("127.0.0.1", proxy.port)
                writer.write(
                    f"POST http://127.0.0.1:{port}/echo HTTP/1.1\r\n"
                    "Transfer-Encoding: chunked\r\n\r\n"
                    "5\r\nhello\r\n6\r\n world\r\n0\r\n\r\n".encode()
                )
                await writer.drain()
                resp = await reader.read()
                writer.close()
                assert b"200" in resp.split(b"\r\n", 1)[0]
                assert seen["body"] == b"hello world"
            finally:
                await proxy.stop()
                await runner.cleanup()

        run(body())

    def test_p2p_fallback_to_passthrough_on_engine_failure(self, run, tmp_path):
        async def body():
            class BrokenEngine:
                async def stream_task(self, url, **kw):
                    raise IOError("engine down")

            async with Origin({"f.bin": b"fallback bytes"}) as origin:
                proxy = ProxyServer(
                    BrokenEngine(), config=ProxyConfig(rules=[ProxyRule(regex=r"\.bin$")])
                )
                await proxy.start()
                try:
                    status, _h, data = await proxy_get(proxy, origin.url("f.bin"))
                    assert status == 200
                    assert data == b"fallback bytes"
                finally:
                    await proxy.stop()

        run(body())

    def test_registry_mirror_blob_and_manifest(self, run, tmp_path):
        blob_bytes = PAYLOAD[: 128 * 1024]
        blob_digest = "sha256:" + hashlib.sha256(blob_bytes).hexdigest()

        async def body():
            # fake OCI registry
            app = web.Application()

            async def manifest(_req):
                return web.json_response({"schemaVersion": 2}, content_type="application/vnd.oci.image.manifest.v1+json")

            async def blob(req):
                rng = req.headers.get("Range")
                if rng:
                    from dragonfly2_tpu.utils.pieces import parse_http_range

                    r = parse_http_range(rng, len(blob_bytes))
                    return web.Response(
                        status=206,
                        body=blob_bytes[r.start : r.start + r.length],
                        headers={"Content-Range": f"bytes {r.start}-{r.end}/{len(blob_bytes)}"},
                    )
                return web.Response(body=blob_bytes)

            app.router.add_get("/v2/library/app/manifests/latest", manifest)
            app.router.add_get(f"/v2/library/app/blobs/{blob_digest}", blob)
            runner = web.AppRunner(app, access_log=None)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            reg_port = site._server.sockets[0].getsockname()[1]

            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            engine = make_engine(tmp_path, client, "mirrorpeer")
            await engine.start()
            proxy = ProxyServer(
                engine,
                config=ProxyConfig(
                    registry_mirror=RegistryMirrorConfig(
                        base_url=f"http://127.0.0.1:{reg_port}"
                    )
                ),
            )
            await proxy.start()
            try:
                # clients talk to the mirror in origin-form, like containerd
                # with a mirror endpoint configured
                async with aiohttp.ClientSession() as sess:
                    base = f"http://127.0.0.1:{proxy.port}"
                    async with sess.get(f"{base}/v2/library/app/manifests/latest") as r:
                        assert r.status == 200
                        assert (await r.json())["schemaVersion"] == 2
                    async with sess.get(f"{base}/v2/library/app/blobs/{blob_digest}") as r:
                        assert r.status == 200
                        got = await r.read()
                        assert got == blob_bytes
                        assert r.headers.get("X-Dragonfly-Via") == "p2p"
            finally:
                await proxy.stop()
                await engine.stop()
                await runner.cleanup()

        run(body())


class TestStreamTask:
    def test_stream_yields_full_content(self, run, tmp_path):
        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"s.bin": PAYLOAD}) as origin:
                engine = make_engine(tmp_path, client, "streampeer")
                await engine.start()
                try:
                    length, it = await engine.stream_task(origin.url("s.bin"))
                    assert length == len(PAYLOAD)
                    got = b"".join([c async for c in it])
                    assert got == PAYLOAD
                    # reuse path streams from storage
                    length2, it2 = await engine.stream_task(origin.url("s.bin"))
                    assert b"".join([c async for c in it2]) == PAYLOAD
                finally:
                    await engine.stop()

        run(body())

    def test_abandoned_stream_releases_pin(self, run, tmp_path):
        """A caller that obtains (length, body) but never iterates the
        generator must not leak the operation pin — a leaked pin makes the
        task permanently reclaim-immune (ADVICE r4)."""

        async def body():
            import gc

            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({"s.bin": PAYLOAD}) as origin:
                engine = make_engine(tmp_path, client, "streamleak")
                await engine.start()
                try:
                    length, it = await engine.stream_task(origin.url("s.bin"))
                    ts = engine.storage.tasks()[0]
                    assert ts.pins >= 1  # stream holds the operation pin
                    del it  # abandoned without a single __anext__
                    gc.collect()
                    for _ in range(50):  # let any producer task settle
                        await asyncio.sleep(0.01)
                        if ts.pins == 0:
                            break
                    assert ts.pins == 0
                    # iterated streams still release exactly once
                    _, it2 = await engine.stream_task(origin.url("s.bin"))
                    assert b"".join([c async for c in it2]) == PAYLOAD
                    gc.collect()
                    await asyncio.sleep(0)
                    assert ts.pins == 0
                finally:
                    await engine.stop()

        run(body())

    def test_stream_failure_propagates(self, run, tmp_path):
        async def body():
            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with Origin({}) as origin:  # 404 origin
                engine = make_engine(tmp_path, client, "streamfail")
                await engine.start()
                try:
                    with pytest.raises(Exception):
                        length, it = await engine.stream_task(origin.url("missing.bin"))
                        async for _ in it:
                            pass
                finally:
                    await engine.stop()

        run(body())


# ---- HTTPS interception (ref cert.go MITM + proxy_sni.go) ----


class TlsOrigin(Origin):
    """Origin serving TLS with a cluster-CA-issued cert for localhost."""

    def __init__(self, files, ssl_ctx, **kw):
        super().__init__(files, **kw)
        self._ssl_ctx = ssl_ctx

    async def __aenter__(self):
        app = web.Application()
        app.router.add_get("/{name}", self._handle)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, "127.0.0.1", 0, ssl_context=self._ssl_ctx)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        return self

    def url(self, name: str) -> str:
        return f"https://localhost:{self.port}/{name}"


@pytest.fixture
def tls_world(tmp_path):
    """CA + origin server context + client/source trust contexts."""
    import ssl

    from dragonfly2_tpu.security.ca import CertificateAuthority
    from dragonfly2_tpu.security.mitm import CertForger

    ca = CertificateAuthority(tmp_path / "ca")
    issued = ca.issue("localhost", sans=["localhost", "127.0.0.1"])
    d = tmp_path / "origin-tls"
    d.mkdir()
    (d / "crt.pem").write_bytes(issued.cert_pem)
    (d / "key.pem").write_bytes(issued.key_pem)
    server_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    server_ctx.load_cert_chain(d / "crt.pem", d / "key.pem")
    trust_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    trust_ctx.load_verify_locations(cadata=ca.ca_pem.decode())
    return {
        "ca": ca,
        "forger": CertForger(ca),
        "server_ctx": server_ctx,
        "trust_ctx": trust_ctx,
    }


class TestHttpsInterception:
    def test_connect_mitm_serves_via_p2p(self, run, tmp_path, tls_world):
        """An HTTPS request through the proxy is MITM'd (forged leaf accepted
        against the cluster CA) and the decrypted GET rides the P2P engine."""

        async def body():
            from dragonfly2_tpu.daemon.proxy import HttpsHijack
            from dragonfly2_tpu.daemon.source import SourceRegistry

            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with TlsOrigin({"f.bin": PAYLOAD}, tls_world["server_ctx"]) as origin:
                engine = make_engine(tmp_path, client, "mitmpeer")
                engine.sources = SourceRegistry(http_ssl=tls_world["trust_ctx"])
                await engine.start()
                proxy = ProxyServer(
                    engine,
                    config=ProxyConfig(
                        rules=[ProxyRule(regex=r"\.bin$")],
                        https_hijack=HttpsHijack(forger=tls_world["forger"]),
                        upstream_ssl=tls_world["trust_ctx"],
                    ),
                )
                await proxy.start()
                try:
                    async with aiohttp.ClientSession() as sess:
                        async with sess.get(
                            origin.url("f.bin"),
                            proxy=f"http://127.0.0.1:{proxy.port}",
                            ssl=tls_world["trust_ctx"],
                        ) as resp:
                            assert resp.status == 200
                            data = await resp.read()
                            assert resp.headers.get("X-Dragonfly-Via") == "p2p"
                    assert data == PAYLOAD
                finally:
                    await proxy.stop()
                    await engine.stop()

        run(body())

    def test_connect_non_matching_host_tunnels(self, run, tmp_path, tls_world):
        """CONNECT targets outside the hijack patterns stay a blind tunnel:
        the client sees the origin's real certificate, not a forged one."""

        async def body():
            from dragonfly2_tpu.daemon.proxy import HttpsHijack

            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with TlsOrigin({"t.txt": b"tunnel"}, tls_world["server_ctx"]) as origin:
                engine = make_engine(tmp_path, client, "tunpeer")
                await engine.start()
                proxy = ProxyServer(
                    engine,
                    config=ProxyConfig(
                        https_hijack=HttpsHijack(
                            forger=tls_world["forger"], hosts=(r"^hijack-only\.example$",)
                        ),
                    ),
                )
                await proxy.start()
                try:
                    async with aiohttp.ClientSession() as sess:
                        async with sess.get(
                            origin.url("t.txt"),
                            proxy=f"http://127.0.0.1:{proxy.port}",
                            ssl=tls_world["trust_ctx"],
                        ) as resp:
                            assert resp.status == 200
                            assert await resp.read() == b"tunnel"
                            # served by the origin's own cert through the
                            # tunnel — the forged-leaf cache stays empty
                            assert "localhost" not in tls_world["forger"]._cache
                finally:
                    await proxy.stop()
                    await engine.stop()

        run(body())

    def test_connect_mitm_keepalive_two_requests(self, run, tmp_path, tls_world):
        """Two sequential requests ride ONE CONNECT tunnel: length-framed
        responses are marked keep-alive, and a client 'Connection: close' on
        the second request is honored (registry clients do token-fetch +
        manifest on one connection)."""

        async def body():
            from dragonfly2_tpu.daemon.proxy import HttpsHijack
            from dragonfly2_tpu.daemon.source import SourceRegistry

            async def read_response(reader):
                status = (await reader.readline()).decode().split()[1]
                headers = {}
                while True:
                    line = await reader.readline()
                    if line in (b"\r\n", b"\n", b""):
                        break
                    k, v = line.decode().split(":", 1)
                    headers[k.strip().lower()] = v.strip()
                body = await reader.readexactly(int(headers.get("content-length", "0")))
                return status, headers, body

            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            files = {"a.bin": PAYLOAD, "b.txt": b"second-req"}
            async with TlsOrigin(files, tls_world["server_ctx"]) as origin:
                engine = make_engine(tmp_path, client, "kapeer")
                engine.sources = SourceRegistry(http_ssl=tls_world["trust_ctx"])
                await engine.start()
                proxy = ProxyServer(
                    engine,
                    config=ProxyConfig(
                        rules=[ProxyRule(regex=r"\.bin$")],
                        https_hijack=HttpsHijack(forger=tls_world["forger"]),
                        upstream_ssl=tls_world["trust_ctx"],
                    ),
                )
                await proxy.start()
                try:
                    reader, writer = await asyncio.open_connection("127.0.0.1", proxy.port)
                    writer.write(
                        f"CONNECT localhost:{origin.port} HTTP/1.1\r\n\r\n".encode()
                    )
                    await writer.drain()
                    assert b"200" in await reader.readline()
                    while (await reader.readline()) not in (b"\r\n", b"\n", b""):
                        pass
                    # client-side TLS upgrade through the loop API +
                    # transport rewire (the idiom the proxy itself uses)
                    loop = asyncio.get_running_loop()
                    transport = await loop.start_tls(
                        writer.transport, writer.transport.get_protocol(),
                        tls_world["trust_ctx"], server_hostname="localhost",
                    )
                    writer._transport = transport
                    writer.write(b"GET /a.bin HTTP/1.1\r\nHost: localhost\r\n\r\n")
                    await writer.drain()
                    st, h, data = await read_response(reader)
                    assert st == "200" and data == PAYLOAD
                    assert h.get("connection") == "keep-alive"
                    assert h.get("x-dragonfly-via") == "p2p"
                    writer.write(
                        b"GET /b.txt HTTP/1.1\r\nHost: localhost\r\n"
                        b"Connection: close\r\n\r\n"
                    )
                    await writer.drain()
                    st, h, data = await read_response(reader)
                    assert st == "200" and data == b"second-req"
                    assert h.get("connection") == "close"
                    writer.close()
                finally:
                    await proxy.stop()
                    await engine.stop()

        run(body())

    def test_sni_hijack_serves_via_p2p(self, run, tmp_path, tls_world):
        """Raw TLS to the SNI proxy (no CONNECT): SNI is peeked, TLS is
        terminated with a forged leaf, and the request rides P2P."""

        async def body():
            from dragonfly2_tpu.daemon.proxy import HttpsHijack, SniProxy
            from dragonfly2_tpu.daemon.source import SourceRegistry

            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with TlsOrigin({"s.bin": PAYLOAD}, tls_world["server_ctx"]) as origin:
                engine = make_engine(tmp_path, client, "snipeer")
                engine.sources = SourceRegistry(http_ssl=tls_world["trust_ctx"])
                await engine.start()
                proxy = ProxyServer(
                    engine,
                    config=ProxyConfig(
                        rules=[ProxyRule(regex=r"\.bin$")],
                        upstream_ssl=tls_world["trust_ctx"],
                    ),
                )
                await proxy.start()
                sni = SniProxy(
                    proxy,
                    hijack=HttpsHijack(forger=tls_world["forger"]),
                    resolve=lambda name: ("127.0.0.1", origin.port),
                )
                await sni.start()
                try:
                    async with aiohttp.ClientSession() as sess:
                        async with sess.get(
                            f"https://localhost:{sni.port}/s.bin",
                            ssl=tls_world["trust_ctx"],
                        ) as resp:
                            assert resp.status == 200
                            data = await resp.read()
                            assert resp.headers.get("X-Dragonfly-Via") == "p2p"
                    assert data == PAYLOAD
                finally:
                    await sni.stop()
                    await proxy.stop()
                    await engine.stop()

        run(body())

    def test_sni_tunnel_passthrough(self, run, tmp_path, tls_world):
        """Without hijack config the SNI proxy splices a blind tunnel to the
        upstream named by the ClientHello."""

        async def body():
            from dragonfly2_tpu.daemon import metrics
            from dragonfly2_tpu.daemon.proxy import SniProxy

            svc = SchedulerService()
            client = InProcessSchedulerClient(svc)
            async with TlsOrigin({"u.txt": b"sni tunnel"}, tls_world["server_ctx"]) as origin:
                engine = make_engine(tmp_path, client, "snitun")
                await engine.start()
                proxy = ProxyServer(engine, config=ProxyConfig())
                await proxy.start()
                sni = SniProxy(
                    proxy, resolve=lambda name: ("127.0.0.1", origin.port)
                )
                await sni.start()
                before = metrics.PROXY_REQUEST_TOTAL.labels(via="sni_tunnel").value
                try:
                    async with aiohttp.ClientSession() as sess:
                        async with sess.get(
                            f"https://localhost:{sni.port}/u.txt",
                            ssl=tls_world["trust_ctx"],
                        ) as resp:
                            assert resp.status == 200
                            assert await resp.read() == b"sni tunnel"
                    after = metrics.PROXY_REQUEST_TOTAL.labels(via="sni_tunnel").value
                    assert after == before + 1
                finally:
                    await sni.stop()
                    await proxy.stop()
                    await engine.stop()

        run(body())

    def test_sni_parser(self):
        """ClientHello SNI extraction on a real hello produced by ssl."""
        import ssl as _ssl

        from dragonfly2_tpu.security.mitm import parse_client_hello_sni

        ctx = _ssl.SSLContext(_ssl.PROTOCOL_TLS_CLIENT)
        ctx.check_hostname = False
        ctx.verify_mode = _ssl.CERT_NONE
        inbio, outbio = _ssl.MemoryBIO(), _ssl.MemoryBIO()
        obj = ctx.wrap_bio(inbio, outbio, server_hostname="registry.example.com")
        try:
            obj.do_handshake()
        except _ssl.SSLWantReadError:
            pass
        hello = outbio.read()
        assert parse_client_hello_sni(hello) == ("ok", "registry.example.com")
        assert parse_client_hello_sni(hello[:3]) == ("incomplete", None)
        assert parse_client_hello_sni(b"GET / HTTP/1.1\r\n") == ("none", None)
