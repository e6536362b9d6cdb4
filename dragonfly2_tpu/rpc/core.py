"""Framed msgpack RPC core.

Wire format: 4-byte big-endian length + msgpack map
  request:  {"i": id, "m": method, "p": payload, "t"?: traceparent}
  response: {"i": id, "r": result} | {"i": id, "e": {"code", "message"}}
Payloads are msgpack-native types (dicts/lists/str/bytes/numbers); service
adapters convert dataclasses at the boundary. "t" is the optional compact
trace context (W3C traceparent string, sampled flag included): the client
stamps it when a trace is active in its caller's context, the server opens
a continuation span around the handler — the otelgrpc-interceptor
equivalent (SURVEY §5) without widening any payload schema.

Server: asyncio.start_server (tcp or unix), method registry, per-server QPS
token bucket (reference default 10k QPS / 20k burst,
pkg/rpc/scheduler/server/server.go:43-44), error mapping.
Client: one connection with request multiplexing, auto-reconnect, retry with
exponential backoff + jitter (resilience.BackoffPolicy, ref interceptor
chain's retry), a per-target circuit breaker, and deadline-aware request
timeouts (min of the per-op timeout and the caller's propagated budget).
"""

from __future__ import annotations

import asyncio
import logging
import struct
from typing import Any, Awaitable, Callable

import msgpack

from dragonfly2_tpu.observability.tracing import SpanContext, Tracer, default_tracer
from dragonfly2_tpu.resilience import deadline as dl
from dragonfly2_tpu.resilience import faultline
from dragonfly2_tpu.resilience.backoff import BackoffPolicy
from dragonfly2_tpu.resilience.breaker import CircuitBreaker
from dragonfly2_tpu.utils.ratelimit import TokenBucket

logger = logging.getLogger(__name__)

_LEN = struct.Struct(">I")
MAX_FRAME = 256 << 20  # direct pieces / piece payloads stay well under this


class RpcError(Exception):
    def __init__(self, message: str, code: str = "internal", retry_after_s: float = 0.0):
        super().__init__(message)
        self.code = code
        # overload hint (ISSUE 17): a server answering "come back in N
        # seconds" rides it in the error frame; clients pre-charge their
        # process-wide RetryBudget with it so one overloaded answer mutes
        # EVERY caller's retries against that target class, not just this one
        self.retry_after_s = retry_after_s


class ConnectionClosed(RpcError):
    def __init__(self) -> None:
        super().__init__("connection closed", code="unavailable")


# Frames at/above this size take the zero-copy paths: bodies are read into a
# preallocated buffer (readinto-style — readexactly would assemble the chunk
# list with an extra full-frame join copy) and written without the
# header+body concatenation copy. Below it, syscall count beats copy cost.
_BIG_FRAME = 64 << 10


async def _read_frame(reader: asyncio.StreamReader) -> dict:
    if faultline.ACTIVE is not None:
        await faultline.ACTIVE.fire("rpc.read")
    header = await reader.readexactly(_LEN.size)
    (length,) = _LEN.unpack(header)
    if length > MAX_FRAME:
        raise RpcError(f"frame too large: {length}", code="resource_exhausted")
    if length >= _BIG_FRAME:
        # piece-payload-sized frame: land chunks directly in one
        # preallocated buffer and unpack from the memoryview — no chunk-list
        # join, no second full-frame allocation
        buf = bytearray(length)
        view = memoryview(buf)
        off = 0
        while off < length:
            chunk = await reader.read(length - off)
            if not chunk:
                raise asyncio.IncompleteReadError(bytes(view[:off]), length)
            view[off : off + len(chunk)] = chunk
            off += len(chunk)
        return msgpack.unpackb(view, raw=False)
    body = await reader.readexactly(length)
    return msgpack.unpackb(body, raw=False)


class WriteCoalescer:
    """Per-connection outbound frame queue: control-plane frames coalesce
    into one writer.write + ONE drain per event-loop batch instead of a
    write+drain round trip per call.

    send() packs and enqueues synchronously — the faultline `rpc.write`
    injection point fires HERE, per frame, so chaos semantics are unchanged
    (an injected fault raises to the caller before the frame is queued, and
    the rpc client's retry path owns recovery exactly as before). A single
    flusher task drains the queue: consecutive small frames are joined into
    one write, frames at/above _BIG_FRAME keep their two-buffer zero-concat
    write, and ordering is queue order. Every frame enqueued while a drain()
    is parked rides the next batch — under concurrent request load (piece
    workers, batched report flushes, server responses) that turns N
    write+drain pairs per loop iteration into one.

    Nobody holds a lock across drain() anymore: enqueue is synchronous on
    the loop thread, and backpressure is the flusher awaiting drain before
    taking the next batch (the transport's high-water mark parks exactly the
    writes that need parking, not every caller)."""

    __slots__ = ("_writer", "_chunks", "_task")

    def __init__(self, writer: asyncio.StreamWriter):
        self._writer = writer
        self._chunks: list[bytes] = []
        self._task: asyncio.Task | None = None

    def send(self, msg: dict) -> None:
        if faultline.ACTIVE is not None:
            faultline.ACTIVE.check("rpc.write")
        body = msgpack.packb(msg, use_bin_type=True)
        header = _LEN.pack(len(body))
        if len(body) >= _BIG_FRAME:
            # kept as separate chunks: the flusher writes them without the
            # header+body concatenation copy (a full-frame copy per
            # direct-piece/piece-body frame otherwise)
            self._chunks.append(header)
            self._chunks.append(body)
        else:
            self._chunks.append(header + body)
        if self._task is None or self._task.done():
            self._task = asyncio.ensure_future(self._drain_loop())

    async def _drain_loop(self) -> None:
        try:
            while self._chunks:
                chunks, self._chunks = self._chunks, []
                if self._writer.is_closing():
                    return
                run: list[bytes] = []  # consecutive small frames to join
                for c in chunks:
                    if len(c) >= _BIG_FRAME:
                        if run:
                            self._writer.write(run[0] if len(run) == 1 else b"".join(run))
                            run.clear()
                        self._writer.write(c)
                    else:
                        run.append(c)
                if run:
                    self._writer.write(run[0] if len(run) == 1 else b"".join(run))
                await self._writer.drain()
        except (ConnectionError, OSError) as e:
            # peer gone mid-write (reset/broken pipe): close the transport so
            # the recv side fails pending calls NOW; retry paths own recovery
            logger.debug("coalesced write failed: %r", e)
            self._chunks.clear()
            self._writer.close()


Handler = Callable[[Any], Awaitable[Any]]

VSOCK_SCHEME = "vsock://"


def parse_vsock(address: str) -> tuple[int, int]:
    """``vsock://<cid>:<port>`` → (cid, port). Parity with the reference's
    vsock transport (pkg/rpc/vsock.go:1-59) for VM-isolated clients (e.g.
    Kata containers) talking to a host daemon over AF_VSOCK."""
    rest = address[len(VSOCK_SCHEME):]
    cid_s, sep, port_s = rest.partition(":")
    if not sep or not cid_s.isdigit() or not port_s.isdigit():
        raise ValueError(f"bad vsock address {address!r}: want vsock://<cid>:<port>")
    return int(cid_s), int(port_s)


def vsock_socket():
    """A fresh AF_VSOCK stream socket; raises OSError where the kernel (or
    platform) lacks vsock support."""
    import socket

    if not hasattr(socket, "AF_VSOCK"):
        raise OSError("AF_VSOCK unsupported on this platform")
    return socket.socket(socket.AF_VSOCK, socket.SOCK_STREAM)


class RpcServer:
    def __init__(
        self,
        *,
        host: str = "127.0.0.1",
        port: int = 0,
        unix_path: str | None = None,
        vsock_port: int | None = None,
        qps_limit: float = 10_000,
        qps_burst: float = 20_000,
        ssl: Any = None,
    ):
        self._handlers: dict[str, Handler] = {}
        self.host = host
        self.port = port
        self.unix_path = unix_path
        self.vsock_port = vsock_port  # listen on AF_VSOCK (any CID) when set
        self.ssl = ssl  # ssl.SSLContext for TLS/mTLS (security.ca helpers)
        self._server: asyncio.AbstractServer | None = None
        self._conns: set[asyncio.StreamWriter] = set()
        self._bucket = TokenBucket(qps_limit, qps_burst)
        self.register("_ping", self._ping)

    async def _ping(self, payload: Any) -> str:
        return "pong"

    def register(self, method: str, handler: Handler) -> None:
        self._handlers[method] = handler

    def register_service(self, obj: Any, methods: list[str]) -> None:
        """Expose async methods of obj taking/returning msgpack-able payloads."""
        for name in methods:
            self.register(name, getattr(obj, name))

    async def start(self) -> None:
        if self.vsock_port is not None:
            import socket

            s = vsock_socket()
            s.bind((socket.VMADDR_CID_ANY, self.vsock_port))
            self._server = await asyncio.start_server(self._on_conn, sock=s)
        elif self.unix_path:
            self._server = await asyncio.start_unix_server(self._on_conn, path=self.unix_path)
        else:
            self._server = await asyncio.start_server(
                self._on_conn, self.host, self.port, ssl=self.ssl
            )
            self.port = self._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            # Drop live connections too: wait_closed() (3.12+) waits for
            # connection handlers, which otherwise run until the peer hangs up.
            for w in list(self._conns):
                w.close()
            await self._server.wait_closed()
            self._server = None

    @property
    def address(self) -> str:
        if self.vsock_port is not None:
            import socket

            return f"{VSOCK_SCHEME}{socket.VMADDR_CID_HOST}:{self.vsock_port}"
        return self.unix_path or f"{self.host}:{self.port}"

    async def _on_conn(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter) -> None:
        tasks: set[asyncio.Task] = set()
        # One coalescer per connection (created inside the connection
        # coroutine, so its flusher binds to the serving loop). Concurrent
        # handler responses enqueue synchronously and ride one write+drain
        # per loop batch — the old per-connection write lock held across
        # drain() serialized every responder behind the slowest flush.
        wq = WriteCoalescer(writer)
        self._conns.add(writer)
        ssl_obj = writer.get_extra_info("ssl_object")
        if ssl_obj is not None:
            # one line per TLS connection: which suite actually negotiated
            # (cert-rollover/cipher-policy debugging without a pcap)
            logger.debug(
                "rpc conn from %s: %s %s", writer.get_extra_info("peername"),
                ssl_obj.version(), (ssl_obj.cipher() or ("?",))[0],
            )
        try:
            while True:
                try:
                    msg = await _read_frame(reader)
                except (asyncio.IncompleteReadError, OSError):
                    # peer hung up, or the transport (or an injected rpc.read
                    # fault) failed the read — either way this connection is
                    # done; the client's retry path owns recovery
                    break
                if not isinstance(msg, dict):
                    logger.warning("malformed frame (%s), closing connection", type(msg).__name__)
                    break
                t = asyncio.ensure_future(self._dispatch(msg, wq))
                tasks.add(t)
                t.add_done_callback(tasks.discard)
        finally:
            self._conns.discard(writer)
            for t in tasks:
                t.cancel()
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError, OSError):
                pass

    async def _dispatch(self, msg: dict, wq: WriteCoalescer) -> None:
        rid = msg.get("i")
        method = msg.get("m", "")
        handler = self._handlers.get(method)
        if handler is None:
            out = {"i": rid, "e": {"code": "unimplemented", "message": f"no method {method!r}"}}
        elif not self._bucket.try_acquire():
            out = {"i": rid, "e": {"code": "resource_exhausted", "message": "rate limited"}}
        else:
            # continuation span when the caller shipped trace context: the
            # handler (and everything it awaits — nested rpc calls, piece
            # fetches, in-process service methods) inherits it through the
            # contextvar. An unsampled context still flows so downstream
            # spans stay unrecorded (all-or-nothing); no "t" costs one get.
            # Non-string "t" (skewed/hostile peer) is ignored, NOT raised:
            # this parse runs before the error-response try below, and an
            # exception here would kill the dispatch task and leave the
            # caller hanging out its full timeout with no response frame.
            t = msg.get("t")
            remote = SpanContext.from_traceparent(t) if isinstance(t, str) else None
            try:
                if remote is not None:
                    with default_tracer().span(
                        "rpc.server", parent=remote, method=method
                    ):
                        result = await handler(msg.get("p"))
                else:
                    result = await handler(msg.get("p"))
                out = {"i": rid, "r": result}
            except RpcError as e:
                err = {"code": e.code, "message": str(e)}
                if e.retry_after_s > 0:
                    err["retry_after_s"] = e.retry_after_s
                out = {"i": rid, "e": err}
            except Exception as e:
                logger.exception("rpc handler %s failed", method)
                out = {"i": rid, "e": {"code": "internal", "message": f"{type(e).__name__}: {e}"}}
        try:
            wq.send(out)
        except OSError as e:
            # an injected rpc.write fault (or a dead transport caught at
            # enqueue): the client's retry path owns recovery
            logger.debug("response write for %s failed: %r", method, e)


class RpcClient:
    def __init__(
        self,
        address: str,
        *,
        timeout: float = 30.0,
        retries: int = 3,
        retry_backoff: float = 0.2,
        backoff: BackoffPolicy | None = None,
        breaker: CircuitBreaker | None = None,
        retry_budget=None,
        target_class: str | None = None,
        ssl: Any = None,
    ):
        self.address = address
        self.timeout = timeout
        self.retries = retries
        self.retry_backoff = retry_backoff  # kept: seeds the default policy base
        # Cluster retry budget (ISSUE 17): per-process token bucket shared by
        # every client retrying against the same TARGET CLASS ("scheduler",
        # "manager", ...). None (the default) keeps per-client behavior —
        # composition roots opt in where storm amplification is possible.
        if retry_budget is None and target_class:
            from dragonfly2_tpu.resilience.budget import budget_for

            retry_budget = budget_for(target_class)
        self.retry_budget = retry_budget
        # exponential + jitter, capped well under the per-op timeout so the
        # retry budget is spent on attempts, not waiting
        self.backoff = backoff or BackoffPolicy(
            base=retry_backoff, multiplier=2.0, max_delay=5.0, jitter=0.5
        )
        # per-target state: one client == one address, so this breaker IS the
        # per-target breaker (the balancer keeps one client per scheduler)
        self.breaker = breaker or CircuitBreaker()
        self.ssl = ssl  # ssl.SSLContext (security.ca.client_ssl_context)
        self._reader: asyncio.StreamReader | None = None
        self._writer: asyncio.StreamWriter | None = None
        self._wq: WriteCoalescer | None = None
        self._pending: dict[int, asyncio.Future] = {}
        self._next_id = 0
        self._recv_task: asyncio.Task | None = None
        # Safe outside a running loop: asyncio.Lock binds to a loop lazily, on
        # first await, and each client is used from a single loop (DF021 audit).
        self._conn_lock = asyncio.Lock()

    async def _connect(self) -> None:
        async with self._conn_lock:
            if self._writer is not None and not self._writer.is_closing():
                return
            # vsock:// is explicit; tcp only when the address ends in
            # ":<digits>"; anything else (absolute, relative, or
            # colon-containing paths) is a unix socket
            host, _, port_s = self.address.rpartition(":")
            if self.address.startswith(VSOCK_SCHEME):
                cid, vport = parse_vsock(self.address)
                s = vsock_socket()
                s.setblocking(False)
                await asyncio.get_running_loop().sock_connect(s, (cid, vport))
                self._reader, self._writer = await asyncio.open_connection(sock=s)
            elif not port_s.isdigit():
                self._reader, self._writer = await asyncio.open_unix_connection(self.address)
            else:
                host, port = self.address.rsplit(":", 1)
                self._reader, self._writer = await asyncio.open_connection(
                    host, int(port), ssl=self.ssl
                )
            self._wq = WriteCoalescer(self._writer)
            self._recv_task = asyncio.ensure_future(self._recv_loop(self._reader))

    async def _recv_loop(self, reader: asyncio.StreamReader) -> None:
        try:
            while True:
                msg = await _read_frame(reader)
                fut = self._pending.pop(msg.get("i"), None)
                if fut is None or fut.done():
                    continue
                if "e" in msg:
                    err = msg["e"]
                    fut.set_exception(RpcError(
                        err.get("message", ""), err.get("code", "internal"),
                        retry_after_s=float(err.get("retry_after_s", 0.0)),
                    ))
                else:
                    fut.set_result(msg.get("r"))
        except (asyncio.IncompleteReadError, OSError, asyncio.CancelledError):
            # OSError covers transport failures AND injected rpc.read faults
            # (FaultError is an IOError); the finally below fails the pending
            # futures so call() reconnects and retries
            pass
        finally:
            for fut in self._pending.values():
                if not fut.done():
                    fut.set_exception(ConnectionClosed())
            self._pending.clear()
            # Reset connection state so the next call() reconnects instead of
            # writing into the dead socket and waiting out its timeout.
            if self._reader is reader:
                if self._writer is not None:
                    self._writer.close()
                # _conn_lock guards only the connect handshake; these resets
                # are a single scheduling slice on the loop thread (no await),
                # so they cannot interleave with a _connect() holding the lock
                # — and the `is reader` guard above pins the incarnation.
                self._reader = self._writer = None  # dflint: disable=DF023 loop-thread reset, no await around it
                self._wq = None  # dflint: disable=DF023 loop-thread reset, no await around it
                self._recv_task = None  # dflint: disable=DF023 loop-thread reset, no await around it

    def _effective_timeout(self, timeout: float | None, method: str) -> float:
        """min(per-op timeout, propagated deadline remaining). An exhausted
        budget fails fast instead of issuing a request that cannot finish."""
        per_op = timeout or self.timeout
        rem = dl.remaining()
        if rem is None:
            return per_op
        if rem <= 0:
            raise RpcError(
                f"{method}: deadline exhausted before call", code="deadline_exceeded"
            )
        return min(per_op, rem)

    async def call(self, method: str, payload: Any = None, *, timeout: float | None = None) -> Any:
        last_err: Exception | None = None
        # trace context resolved ONCE per call: each attempt gets its own
        # client span (attempt index is an attribute, so retries are visible
        # in the trace), and the span's own context rides the frame's "t"
        # key. No active trace → no span objects, no wire bytes.
        traced = Tracer.current() is not None
        for attempt in range(self.retries + 1):
            if not self.breaker.allow():
                raise RpcError(
                    f"circuit open to {self.address}"
                    + (f" (last: {last_err})" if last_err else ""),
                    code="unavailable",
                )
            # outside the try: an exhausted caller budget is not the target's
            # fault and must not feed the breaker
            per_op = timeout or self.timeout
            effective = self._effective_timeout(timeout, method)
            try:
                if traced:
                    with default_tracer().span(
                        "rpc.client",
                        method=method,
                        address=self.address,
                        attempt=attempt,
                        deadline_remaining_s=round(effective, 3),
                    ) as sp:
                        result = await self._call_once(
                            method, payload, effective,
                            trace=sp.context.traceparent(),
                        )
                else:
                    result = await self._call_once(method, payload, effective)
                self.breaker.record_success()
                return result
            except (ConnectionClosed, ConnectionError, OSError) as e:
                self.breaker.record_failure()
                last_err = e
                self._drop_connection()
                if attempt < self.retries:  # no pointless sleep before raising
                    self._spend_retry(method, last_err)
                    await self.backoff.sleep(attempt)
            except RpcError as e:
                if e.code == "deadline_exceeded":
                    if effective >= per_op:
                        # silent for the FULL per-op window: counts against
                        # the target
                        self.breaker.record_failure()
                    # else: the caller's nearly-spent budget shrank the
                    # window — a healthy target may simply not have had time;
                    # record nothing either way
                else:
                    # any decoded response (even an error) proves the target alive
                    self.breaker.record_success()
                if e.retry_after_s > 0 and self.retry_budget is not None:
                    # server's overload hint: mute the WHOLE process's
                    # retries against this target class for the window
                    self.retry_budget.charge(e.retry_after_s)
                if e.code == "resource_exhausted" and attempt < self.retries:
                    last_err = e
                    self._spend_retry(method, last_err)
                    await self.backoff.sleep(attempt)
                    continue
                raise
        raise last_err or RpcError("rpc call failed")

    def _spend_retry(self, method: str, last_err: Exception | None) -> None:
        """Consult the cluster retry budget before ONE retry attempt (first
        attempts are free). Beyond budget — or inside a server-hinted
        retry_after window — fail fast so the caller moves to its next
        fallback instead of amplifying load on a sick target."""
        b = self.retry_budget
        if b is None:
            return
        if not b.spend():
            raise RpcError(
                f"{method}: retry budget exhausted for "
                f"{b.name or self.address}"
                + (f" (last: {last_err})" if last_err else ""),
                code="unavailable",
            )

    async def _call_once(
        self, method: str, payload: Any, timeout: float, trace: str | None = None
    ) -> Any:
        await self._connect()
        self._next_id += 1
        rid = self._next_id
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._pending[rid] = fut
        msg = {"i": rid, "m": method, "p": payload}
        if trace is not None:
            msg["t"] = trace
        try:
            # enqueue is synchronous (injected rpc.write faults raise HERE and
            # feed the retry path); the coalescer's flusher owns the drain, so
            # concurrent calls in one loop batch share a single write+drain
            self._wq.send(msg)
            return await asyncio.wait_for(fut, timeout)
        except asyncio.TimeoutError:
            self._pending.pop(rid, None)
            raise RpcError(f"{method} timed out after {timeout}s", code="deadline_exceeded")
        finally:
            self._pending.pop(rid, None)

    def _drop_connection(self) -> None:
        if self._recv_task is not None:
            self._recv_task.cancel()
            # sync method: runs to completion on the loop thread, atomic
            # w.r.t. any coroutine holding _conn_lock
            self._recv_task = None  # dflint: disable=DF023 sync method, atomic on the loop thread
        if self._writer is not None:
            self._writer.close()
        self._reader = self._writer = None  # dflint: disable=DF023 sync method, atomic on the loop thread
        self._wq = None  # dflint: disable=DF023 sync method, atomic on the loop thread

    async def close(self) -> None:
        writer = self._writer
        self._drop_connection()
        # In-flight futures must fail NOW, not hang until their timeout: the
        # recv task's finally does this too, but its cancellation completes on
        # a later loop cycle — close() callers (shutdown paths) need it done
        # before they proceed.
        for fut in list(self._pending.values()):
            if not fut.done():
                fut.set_exception(ConnectionClosed())
        self._pending.clear()
        if writer is not None:
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError, OSError):
                pass

    def tls_info(self) -> "dict | None":
        """Negotiated TLS parameters of the live connection, or None when
        plain/disconnected: {"cipher", "version"}. The control plane rides
        asyncio's stock SSL (small frames — the data plane's fast-path
        transport lives in security/transport.py); this surfaces what
        actually negotiated so dfstress/debug tooling can report the wire
        posture next to its numbers."""
        if self._writer is None:
            return None
        ssl_obj = self._writer.get_extra_info("ssl_object")
        if ssl_obj is None:
            return None
        cipher = ssl_obj.cipher()
        return {
            "cipher": cipher[0] if cipher else None,
            "version": ssl_obj.version(),
        }

    async def healthy(self) -> bool:
        try:
            return await self.call("_ping", timeout=2.0) == "pong"
        except (RpcError, ConnectionError, OSError, asyncio.TimeoutError) as e:
            logger.debug("health probe of %s failed: %r", self.address, e)
            return False
