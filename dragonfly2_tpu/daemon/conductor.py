"""Peer-task conductor: drives one task download end to end.

Parity with reference client/daemon/peer/peertask_conductor.go:68-1157 — the
survey's flagged hard part ("1,565 LoC of subtle concurrency: three bitmaps +
broker + dispatcher + per-parent sync streams + traffic shaper + back-source
cutover"). Redesigned as an explicit asyncio pipeline instead of goroutine
spaghetti:

  register → (back-to-source | P2P) → piece workers → storage → report → done

P2P mode: a score-based PieceDispatcher (ref piece_dispatcher.go:33-124,
ε-random exploration) assigns each missing piece to a parent that has it;
N workers pull assignments, HTTP-range the bytes from the parent's upload
server, verify, write, and report. Parent piece availability is pushed via
long-poll on the parents' /metadata endpoint (`?since=<version>&wait=` parks
until the parent's piece state advances — replacing the reference's bidi
SyncPieceTasks streams). Failures block the parent and trigger a scheduler
reschedule; after the retry budget the conductor cuts over to back-to-source
for the remaining pieces (ref partial back-source path).
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from dataclasses import dataclass, field
from typing import Protocol

import aiohttp

from dragonfly2_tpu.daemon.rawrange import AddressFamilyError
from dragonfly2_tpu.daemon.source import SourceError, SourceRegistry
from dragonfly2_tpu.daemon.storage import StorageManager, TaskStorage
from dragonfly2_tpu.observability.tracing import default_tracer
from dragonfly2_tpu.resilience import deadline as dl
from dragonfly2_tpu.resilience import faultline
from dragonfly2_tpu.resilience.backoff import BackoffPolicy
from dragonfly2_tpu.rpc.core import RpcError
from dragonfly2_tpu.scheduler.service import HostInfo, ParentInfo, RegisterResult, TaskMeta
from dragonfly2_tpu.utils import digest as digestlib
from dragonfly2_tpu.utils.aio import gather_all_cancel_on_error
from dragonfly2_tpu.utils.bitset import Bitset
from dragonfly2_tpu.utils.pieces import Range, compute_piece_size, piece_count, piece_range
from dragonfly2_tpu.utils.ratelimit import TokenBucket

logger = logging.getLogger(__name__)


def _url_host(ip: str) -> str:
    """IPv6 literals must be bracketed in URLs (yarl rejects bare colons —
    an unbracketed v6 parent URL would fail as InvalidURL and charge the
    parent, defeating the raw-client's aiohttp fallback entirely)."""
    return f"[{ip}]" if ":" in ip else ip


class SchedulerClient(Protocol):
    """What the conductor needs from the control plane. Implemented in-process
    (wrapping SchedulerService) and over the wire (rpc client)."""

    async def register_peer(self, peer_id: str, meta: TaskMeta, host: HostInfo) -> RegisterResult: ...
    async def report_task_metadata(self, task_id: str, *, content_length: int,
                                   piece_size: int, digest: str = "",
                                   direct_piece: bytes = b"") -> None: ...
    async def report_piece_result(self, peer_id: str, piece_index: int, *, success: bool,
                                  cost_ms: float = 0.0, parent_id: str = "") -> None: ...
    async def report_pieces(self, peer_id: str, reports) -> int: ...
    async def report_peer_result(self, peer_id: str, *, success: bool,
                                 bandwidth_bps: float = 0.0) -> None: ...
    async def reschedule(self, peer_id: str) -> RegisterResult: ...
    async def leave_peer(self, peer_id: str) -> None: ...


class PieceReportBuffer:
    """Per-conductor buffer of SUCCESSFUL piece reports, flushed through the
    report_pieces batch RPC — the control-plane fast path that replaces one
    awaited report_piece_result round trip per piece in the piece-worker
    path. Failed pieces never enter the buffer: they drive rescheduling and
    are reported individually and promptly by the caller.

    Flush triggers: buffer reaches max_batch, buffered reports go
    flush_interval stale (bounds report staleness for long rounds), the
    conductor flushes at dispatch-round end, and close() flushes at task
    completion (before report_peer_result, so the scheduler's telemetry sees
    the full finished set).

    ONE long-lived flusher task per conductor serves the size and staleness
    triggers (PR 5 carry-over / ROADMAP): the earlier shape re-spawned a
    staleness-timer task per flush cycle and a detached task per size
    trigger — per-piece task churn on the hot path (the pattern dflint DF026
    now flags for threads/pools). add() is still synchronous: it appends,
    sets an event, and the flusher does the rest; `flusher_starts` counts
    task creations so tests can pin the no-churn contract.

    Exactly-once under rpc.write faults: flush() atomically takes the
    buffered triples and awaits ONE report_pieces call; the rpc client
    retries connection-level failures (an injected rpc.write fault raises
    before the frame leaves, so a retry cannot double-deliver — and a
    timeout AFTER a server-side apply re-applies as a no-op because the
    scheduler's apply is idempotent per piece index). If the call fails past
    the client's retry budget the triples are merged back for the next flush
    — piece accounting is never dropped, matching the at-least-once goal the
    chaos suite pins."""

    def __init__(self, scheduler, peer_id: str, *, max_batch: int = 64,
                 flush_interval: float = 0.25, log=None):
        self._sched = scheduler
        self.peer_id = peer_id
        self.max_batch = max_batch
        self.flush_interval = flush_interval
        self.log = log or logger
        self._buf: list[tuple[int, float, str]] = []
        self._lock = asyncio.Lock()  # serializes flushes (ordering + no double-take)
        self._flusher: asyncio.Task | None = None
        # events are created in __init__ (asyncio binds them to a loop lazily), set by
        # add(): _wake = "buffer went non-empty", _full = "size trigger hit"
        self._wake = asyncio.Event()
        self._full = asyncio.Event()
        self.rpcs = 0  # report_pieces calls that completed (bench/test counter)
        self.buffered = 0  # pieces that rode a batch instead of a unary RPC
        self.flusher_starts = 0  # long-lived task creations (leak canary: stays 1)

    def add(self, piece_index: int, cost_ms: float = 0.0, parent_id: str = "") -> None:
        """Enqueue one successful piece report. Sync — the piece worker goes
        straight back to its queue; no RPC await, no task spawned, on the
        piece path."""
        self._buf.append((piece_index, cost_ms, parent_id))  # dflint: disable=DF023 loop-thread append, no await around it; the lock serializes FLUSHES, not enqueues
        self.buffered += 1
        if len(self._buf) >= self.max_batch:
            self._full.set()
        if self._flusher is None or self._flusher.done():
            # lazy start (add is the first point with a running loop); a
            # flusher that DIED (cancelled mid-close, crashed) is restarted
            # so a reused buffer never silently stops flushing
            self.flusher_starts += 1
            self._flusher = asyncio.ensure_future(self._flusher_loop())
        else:
            self._wake.set()

    async def _flusher_loop(self) -> None:
        """The single long-lived flusher: parks while the buffer is empty,
        then flushes when the buffer fills (size trigger) or flush_interval
        after it went non-empty (staleness trigger) — the same externally
        observable schedule the per-flush timer tasks produced, without
        creating a task per cycle."""
        while True:
            if not self._buf:
                await self._wake.wait()
                self._wake.clear()
                if not self._buf:  # spurious wake (a direct flush drained us)
                    continue
            if len(self._buf) < self.max_batch:
                try:
                    await asyncio.wait_for(self._full.wait(), self.flush_interval)
                except asyncio.TimeoutError:
                    pass  # staleness trigger: flush whatever is buffered
            self._full.clear()  # dflint: disable=DF023 loop-thread event signaling; the lock serializes FLUSHES — flush()'s own clear just runs inside its locked drain
            await self.flush()
            if self._buf:
                # flush failed past the rpc client's retries and re-merged:
                # PACE the retry. A re-merged buffer >= max_batch would skip
                # the staleness wait above and hammer a dead scheduler in a
                # tight loop (fast-failing RPCs make it a busy spin).
                await asyncio.sleep(self.flush_interval)

    async def flush(self) -> None:
        """Drain the buffer in one report_pieces RPC (or a few, if adds land
        while a flush is in flight). Never raises on RPC failure: a flush
        that fails past the rpc client's retries re-merges its batch and
        leaves recovery to the next trigger. Cancellation (aclose cancelling
        the staleness timer mid-flush) also re-merges before propagating —
        the taken batch must never ride out of scope with the exception, or
        the close flush would snapshot an incomplete finished set."""
        async with self._lock:
            while self._buf:
                batch, self._buf = self._buf, []
                try:
                    # flush span: how often the buffer ships and how full it
                    # is are exactly the control-plane amortization questions
                    # a trace should answer (≤1 flush per dispatch round)
                    with default_tracer().span(
                        "conductor.report_flush", batch=len(batch)
                    ):
                        await self._sched.report_pieces(self.peer_id, batch)  # dflint: disable=DF025 this IS the batch flush; the loop only drains reports that arrived during the awaited call
                    self.rpcs += 1
                except Exception as e:  # noqa: BLE001 — advisory accounting:
                    # keep the pieces for the next flush trigger; the download
                    # itself must never fail on a report (same contract as the
                    # unbatched path's debug-logged best-effort reports)
                    self._buf = batch + self._buf
                    self.log.debug("piece-report flush of %d failed: %r", len(batch), e)
                    return
                except BaseException:
                    # CancelledError is a BaseException since 3.8: without this
                    # re-merge a timer task cancelled at the awaited RPC would
                    # lose its taken batch silently (a server-side apply that
                    # already landed re-applies as a no-op — idempotent).
                    self._buf = batch + self._buf
                    raise
            # Drained: a size-trigger signal set by adds this flush consumed
            # is now stale — left set, the flusher's next cycle would skip
            # the staleness wait and ship a tiny batch (a direct round-end
            # flush racing the size trigger reintroduced near-unary RPCs).
            # The failure paths above return/raise with the buffer non-empty
            # and deliberately leave the signal armed for a prompt retry.
            self._full.clear()

    async def aclose(self) -> None:
        """Task-completion flush; stops the long-lived flusher.

        Unlike mid-round flushes (which can leave failures to the next
        trigger), this is the LAST trigger: a flush that fails past the rpc
        client's retries gets a few more backed-off attempts here, because
        dropping the residue would lose piece accounting at exactly the
        moment report_peer_result snapshots the finished set into telemetry
        (the chaos suite pins no-loss under rpc.write faults)."""
        if self._flusher is not None:
            self._flusher.cancel()
            # await the cancellation: a flusher parked inside flush()'s RPC
            # holds the flush lock and must finish its BaseException re-merge
            # before the close flush below can take the (complete) buffer
            await asyncio.gather(self._flusher, return_exceptions=True)
            self._flusher = None
        backoff = BackoffPolicy(base=0.05, max_delay=1.0)
        for attempt in range(4):
            if attempt:
                await backoff.sleep(attempt - 1)
            await self.flush()
            if not self._buf:
                return
        self.log.warning(
            "dropping %d unreported piece results at task close", len(self._buf)
        )

    async def close_with_result(self, *, success: bool,
                                bandwidth_bps: float = 0.0) -> bool:
        """Task-completion close that rides the residual piece batch AND the
        final peer result in ONE report_batch RPC (one frame, one scheduler
        lock pass) instead of aclose()'s flush followed by a separate unary
        report_peer_result. Returns True when the result landed; False when
        the transport has no report_batch (older scheduler: unimplemented
        over the wire, or a client predating the method) — the caller then
        falls back to aclose() + unary report_peer_result, which this method
        has already half-done by flushing what it could.

        Retry safety matches the unary pair it replaces: both legs are
        idempotent server-side (piece dedupe + terminal-FSM result skip), so
        the rpc client's retries and the backed-off attempts here cannot
        double-account."""
        fn = getattr(self._sched, "report_batch", None)
        if fn is None:
            await self.aclose()
            return False
        if self._flusher is not None:
            self._flusher.cancel()
            await asyncio.gather(self._flusher, return_exceptions=True)
            self._flusher = None
        result = {"success": success, "bandwidth_bps": bandwidth_bps}
        backoff = BackoffPolicy(base=0.05, max_delay=1.0)
        for attempt in range(4):
            if attempt:
                await backoff.sleep(attempt - 1)
            async with self._lock:
                batch, self._buf = self._buf, []
                try:
                    with default_tracer().span(
                        "conductor.report_close", batch=len(batch)
                    ):
                        await fn(self.peer_id, batch, result)
                    self.rpcs += 1
                    return True
                except RpcError as e:
                    self._buf = batch + self._buf
                    if e.code == "unimplemented":
                        break  # rolling upgrade: scheduler predates the method
                    self.log.debug(
                        "batched close of %d failed: %r", len(batch), e
                    )
                except Exception as e:  # noqa: BLE001 — same advisory
                    # contract as flush(): the download never fails on a report
                    self._buf = batch + self._buf
                    self.log.debug(
                        "batched close of %d failed: %r", len(batch), e
                    )
                except BaseException:
                    self._buf = batch + self._buf
                    raise
        # could not land the combo: drain pieces the plain way and tell the
        # caller to send the unary result itself
        await self.aclose()
        return False


@dataclass
class ParentState:
    info: ParentInfo
    pieces: set[int] = field(default_factory=set)
    successes: int = 0
    failures: int = 0
    cost_ewma_ms: float = 0.0
    blocked: bool = False
    # fetches currently riding this parent (striped mode's per-parent
    # window); maintained by PieceDispatcher.begin/end around each fetch
    in_flight: int = 0

    def score(self) -> float:
        """Higher is better: success rate shaded by recent piece cost."""
        total = self.successes + self.failures
        rate = (self.successes + 1) / (total + 2)  # Laplace prior
        cost_penalty = self.cost_ewma_ms / 10_000.0
        return rate - cost_penalty

    def record(self, success: bool, cost_ms: float) -> None:
        if success:
            self.successes += 1
            alpha = 0.3
            self.cost_ewma_ms = (
                cost_ms if self.cost_ewma_ms == 0 else alpha * cost_ms + (1 - alpha) * self.cost_ewma_ms
            )
        else:
            self.failures += 1
            if self.failures >= 3:
                self.blocked = True


class PieceDispatcher:
    """Pick the parent for each piece: best score with ε-random exploration
    (ref piece_dispatcher.go:103-124 exploration/exploitation split).

    Striped mode (`pick(..., striped=True)`) turns the pick into a
    load-balancing decision: among the parents that hold the piece, prefer
    the one with the fewest fetches in flight (score breaks ties), and keep
    each parent's concurrent fetches under `stripe_window`. Assignment
    happens at FETCH time, so the stripes are emergent, not precomputed — a
    slow parent's window stays full longer and it naturally receives fewer
    pieces, which is exactly the tail-aware split the GNN-training paper
    applies to its straggler stage (PAPERS.md: parallelize the slowest
    stage, not just the aggregate). When every window is full the pick
    falls back to least-loaded (never returns None just because the task is
    briefly window-bound — the piece queue provides the real backpressure).
    """

    def __init__(
        self,
        epsilon: float = 0.1,
        rng: random.Random | None = None,
        *,
        stripe_window: int = 4,
    ):
        self.parents: dict[str, ParentState] = {}
        self.epsilon = epsilon
        self.stripe_window = stripe_window
        self._rng = rng or random.Random()

    def update_parents(self, parents: list[ParentInfo]) -> None:
        keep = {p.peer_id for p in parents}
        for pid in list(self.parents):
            if pid not in keep:
                del self.parents[pid]
        for p in parents:
            if p.peer_id not in self.parents:
                self.parents[p.peer_id] = ParentState(p)

    def set_pieces(self, parent_id: str, pieces: set[int]) -> None:
        if parent_id in self.parents:
            self.parents[parent_id].pieces = pieces

    def pick(
        self,
        piece_index: int,
        *,
        striped: bool = False,
        exclude: "frozenset[str] | set[str] | tuple" = (),
    ) -> ParentState | None:
        candidates = [
            s for s in self.parents.values()
            if not s.blocked and piece_index in s.pieces and s.info.peer_id not in exclude
        ]
        if not candidates:
            return None
        if self._rng.random() < self.epsilon:
            return self._rng.choice(candidates)
        if not striped or len(candidates) == 1:
            return max(candidates, key=ParentState.score)
        windowed = [s for s in candidates if s.in_flight < self.stripe_window]
        pool = windowed or candidates
        return min(pool, key=lambda s: (s.in_flight, -s.score()))

    def begin(self, state: ParentState) -> None:
        state.in_flight += 1

    def end(self, state: ParentState) -> None:
        state.in_flight = max(0, state.in_flight - 1)

    def usable(self) -> list[ParentState]:
        return [s for s in self.parents.values() if not s.blocked]


@dataclass
class ConductorConfig:
    piece_workers: int = 4
    # ranged back-to-source pulls this many pieces concurrently (the
    # reference's ConcurrentOption multi-connection source download,
    # piece_manager.go:67); 1 = sequential
    source_concurrency: int = 4
    download_rate_bps: float = 512 << 20  # per-peer default (ref constants.go:45)
    piece_timeout: float = 30.0
    # Fallback re-check cadence when no push event arrives; piece announcements
    # themselves are pushed via parent long-poll, not polled on this interval.
    metadata_poll_interval: float = 0.2
    longpoll_wait: float = 25.0
    # How long to keep riding live parents' push channels with nothing to do
    # before asking the scheduler for new parents.
    no_progress_reschedule: float = 5.0
    reschedule_limit: int = 5
    watchdog_timeout: float = 600.0
    # Retry pacing for piece-level recovery (shared BackoffPolicy shape).
    retry_backoff_base: float = 0.1
    retry_backoff_max: float = 2.0
    # A piece whose worker raised past _download_one_piece is re-enqueued at
    # most this many times before it is reported failed to the scheduler and
    # left to the dispatch loop (and ultimately cutover) to recover.
    piece_requeue_limit: int = 2
    # Ranged back-to-source: per-piece fetch retries before the whole task
    # fails (origin blips must not kill a 95%-done download).
    source_piece_retries: int = 3
    # Successful piece reports batch through the report_pieces RPC (one
    # flush per dispatch round / flush interval instead of one awaited
    # round trip per piece); failed pieces always report individually and
    # immediately (they drive rescheduling). Disable to get the r05 unary
    # path (the chaos suite's equivalence baseline).
    batch_piece_reports: bool = True
    report_batch_size: int = 64
    report_flush_interval: float = 0.25
    # Hand filled piece buffers to writer tasks WITHOUT awaiting them, so one
    # worker pipelines recv of piece N+1 into the store write of piece N.
    # On the 2-core CI image the piece-worker pool already overlaps
    # recv/hash/write across workers on both cores and the extra in-flight
    # write tasks measured ~10% SLOWER (343 vs 311 MB/s in the 4-worker
    # pipeline A/B); on hosts with cores to spare the deferral buys
    # single-worker pipelining. That inversion is why the default is now
    # None = ADAPTIVE: the first dispatch round runs inline while measuring
    # its recv/write stage totals, and WriteBehindGovernor flips deferral on
    # only where the measurement says it pays (spare cores + writes a real
    # fraction of the round). True/False force the static modes (the A/B
    # legs and the chaos equivalence baseline). Backpressure either way: the
    # buffer pool's bounded leases park recv when writers fall behind.
    defer_piece_writes: "bool | None" = None
    # Multi-parent striped fetch: when a hot task has several ready parents,
    # balance piece assignment across them (per-parent in-flight windows)
    # instead of funneling ~everything to the single best-scored parent, so
    # single-task fetch bandwidth aggregates across parents' per-peer
    # serving ceilings. Scheduler accounting is unchanged — every piece
    # still reports with its parent id.
    striped_fetch: bool = True
    stripe_window: int = 4
    # Slowest-stripe steal: when the piece queue is empty but pieces are
    # still in flight (the tail), an idle worker re-fetches a piece that has
    # been riding a slow parent for > max(steal_min_ms, steal_cost_factor *
    # that parent's cost EWMA) from a different parent, and the first copy
    # to land wins (the loser's fetch is cancelled; landing + accounting are
    # guarded so bytes/pieces never double-count).
    tail_steal: bool = True
    steal_min_ms: float = 400.0
    steal_cost_factor: float = 3.0


class WriteBehindGovernor:
    """Runtime write-behind decision (ConductorConfig.defer_piece_writes=None).

    PR 3 measured the static trade-off inverting with core count, so the
    default can't be a constant. The first dispatch round runs INLINE while
    `note()` accumulates the round's recv and write stage totals (two clock
    reads per piece, only while measuring); `decide()` then flips deferral
    on iff (a) there are cores beyond the two the recv+hash overlap already
    uses, and (b) writes are a real fraction of the measured round — on a
    2-core host, or when writes vanish into page cache, deferral only adds
    task churn. The decision and both measurements export as metrics
    (`write_behind_mode{mode}` one-hot, `write_behind_stage_ms{stage}`), so
    the PR 12 timeseries plane records what was decided and from what.
    """

    # writes below this fraction of recv+write don't buy enough overlap to
    # pay for per-piece writer tasks
    MIN_WRITE_FRAC = 0.10
    MIN_SAMPLES = 2

    def __init__(self, forced: "bool | None", *, cpu_count: int | None = None):
        import os

        self.forced = forced
        self.cpus = cpu_count if cpu_count is not None else (os.cpu_count() or 1)
        self.recv_s = 0.0
        self.write_s = 0.0
        self.samples = 0
        self.decided: bool | None = forced
        if forced is not None:
            self._export("forced_deferred" if forced else "forced_inline")

    @property
    def measuring(self) -> bool:
        return self.decided is None

    @property
    def defer(self) -> bool:
        return bool(self.decided)

    def note(self, recv_s: float, write_s: float) -> None:
        if self.decided is None:
            self.recv_s += recv_s
            self.write_s += write_s
            self.samples += 1

    def decide(self) -> bool:
        """Called at first-round end; keeps measuring if the round was too
        small to mean anything (a 1-piece task decides nothing)."""
        if self.decided is not None:
            return self.decided
        if self.samples < self.MIN_SAMPLES:
            return False  # stay inline, keep measuring next round
        total = self.recv_s + self.write_s
        write_frac = self.write_s / total if total > 0 else 0.0
        self.decided = self.cpus > 2 and write_frac >= self.MIN_WRITE_FRAC
        self._export("deferred" if self.decided else "inline")
        return self.decided

    def _export(self, mode: str) -> None:
        from dragonfly2_tpu.daemon import metrics

        for m in ("inline", "deferred", "forced_inline", "forced_deferred"):
            metrics.WRITE_BEHIND_MODE.set(1.0 if m == mode else 0.0, mode=m)
        metrics.WRITE_BEHIND_STAGE_MS.set(round(self.recv_s * 1e3, 3), stage="recv")
        metrics.WRITE_BEHIND_STAGE_MS.set(round(self.write_s * 1e3, 3), stage="write")

    def snapshot(self) -> dict:
        return {
            "mode": (
                "measuring" if self.decided is None
                else {True: "deferred", False: "inline"}[self.decided]
            ),
            "forced": self.forced,
            "recv_ms": round(self.recv_s * 1e3, 3),
            "write_ms": round(self.write_s * 1e3, 3),
            "samples": self.samples,
        }


@dataclass
class _InflightFetch:
    """One piece fetch in flight (striped mode): enough state for the tail
    steal to judge slowness and cancel the loser."""

    idx: int
    task: "asyncio.Task | None"  # set right after creation (fetch needs the entry)
    started: float
    parent_id: str = ""
    stolen: bool = False
    steal_attempts: int = 0  # bounded: a failing steal must not retry forever


class PeerTaskConductor:
    def __init__(
        self,
        *,
        peer_id: str,
        meta: TaskMeta,
        host: HostInfo,
        scheduler: SchedulerClient,
        storage: StorageManager,
        sources: SourceRegistry,
        config: ConductorConfig | None = None,
        http_session: aiohttp.ClientSession | None = None,
        headers: dict[str, str] | None = None,
        shaper=None,
        raw_client=None,
        pipeline=None,
        data_tls=None,
        flow_weight: float = 1.0,
    ):
        from dragonfly2_tpu.utils.dflog import with_context

        self.peer_id = peer_id
        self.meta = meta
        self.host = host
        self.scheduler = scheduler
        # every line this conductor logs carries its task+peer ids
        # (ref dflog WithPeer/WithTask structured context)
        self.log = with_context(logger, task_id=meta.task_id, peer_id=peer_id)
        self.storage = storage
        self.sources = sources
        self.headers = headers or None  # origin request headers (auth etc.)
        self.cfg = config or ConductorConfig()
        self.dispatcher = PieceDispatcher(stripe_window=self.cfg.stripe_window)
        # DataPlaneTls bundle: parents' metadata + piece endpoints speak
        # https/mTLS (the shared raw client carries its own copy; this one
        # drives the aiohttp session + URL scheme)
        self._data_tls = data_tls
        self._scheme = "https" if data_tls is not None else "http"
        # With a node-wide shaper (daemon/traffic_shaper.py) the conductor
        # draws from a dynamically-allocated slice of the HOST budget; the
        # standalone per-task bucket is the no-engine fallback (tests, direct
        # conductor use). flow_weight is the task's tenant priority: the
        # shaper splits contended bandwidth weight-proportionally.
        if shaper is not None:
            self.bucket = shaper.open_flow(peer_id, weight=flow_weight)
        else:
            self.bucket = TokenBucket(self.cfg.download_rate_bps, burst=64 << 20)
        self._session = http_session
        self._owns_session = http_session is None
        # engine-shared RawRangeClient when provided (keep-alive conns to
        # parents survive across this host's tasks); else lazily owned
        self._raw_client = raw_client
        self._owns_raw = raw_client is None
        # engine-shared PiecePipeline (pooled buffers + hash threads reused
        # across every transfer on the host); else lazily owned
        self._pipeline_obj = pipeline
        self._owns_pipeline = pipeline is None
        # deferred store writes: a piece worker hands its filled buffer to a
        # writer task and immediately recycles a fresh buffer into recv; the
        # dispatch loop drains these at round end (see _spawn_piece_write)
        self._pending_writes: set[asyncio.Task] = set()
        # adaptive write-behind: measures the first dispatch round, then
        # decides (ConductorConfig.defer_piece_writes documents the why)
        self._write_behind = WriteBehindGovernor(self.cfg.defer_piece_writes)
        # striped-fetch state: fetches in flight (tail-steal registry) and
        # which parents actually landed pieces (stripe-parents histogram +
        # the stripe smoke's both-parents-served proof)
        self._inflight: dict[int, _InflightFetch] = {}
        self.pieces_by_parent: dict[str, int] = {}
        self.steals_attempted = 0
        self.steals_won = 0
        # pieces this conductor has ACCOUNTED (bytes/metrics/report): the
        # exactly-once guard for duplicate landings. storage._land_piece
        # dedups the WRITE of racing copies but returns success to both
        # writers — without this set, a steal and its original racing into
        # the landing path would both reach _account_piece_success and
        # double-count DOWNLOAD_TRAFFIC_BYTES (the invariant the chaos
        # suite and stripe smoke pin).
        self._accounted: set[int] = set()
        self.ts: TaskStorage | None = None
        self.bytes_from_parents = 0
        self.bytes_from_source = 0
        # refetch accounting for crash-safe resume: pieces already on disk
        # when this conductor started (recovered from a previous run) vs
        # pieces it NEWLY LANDED — the restart suite pins
        # preexisting + fetched == total (recovered pieces never ride again
        # on ranged/p2p paths; a close-delimited full-body fallback re-carries
        # their bytes — visible in bytes_from_source — but still never
        # re-lands or re-reports them)
        self.pieces_preexisting = 0
        self.pieces_fetched = 0
        self._piece_digests: dict[str, str] = {}  # learned from parent metadata
        # Whether the final full-content re-hash can be skipped: true only if
        # EVERY byte of the task was landed by THIS conductor with each piece
        # validated against an expected digest at write time, and every such
        # digest came from a parent that had itself completed (and therefore
        # verified) the task — a mid-download parent's digests are self-
        # computed from bytes IT has not verified yet (see _run_inner).
        self._pieces_unverified = 0
        self._digests_from_done_parents = True
        self._had_preexisting_pieces = False
        self._peer_reported = False
        self._t0 = 0.0
        self._sync_tasks: dict[str, asyncio.Task] = {}  # parent_id -> long-poll loop
        self._update_event = asyncio.Event()  # any parent state/metadata change
        self._backoff = BackoffPolicy(
            base=self.cfg.retry_backoff_base,
            multiplier=2.0,
            max_delay=self.cfg.retry_backoff_max,
            jitter=0.5,
        )
        self._piece_errors: dict[int, int] = {}  # index -> worker-level failures
        # cluster retry budgets (ISSUE 17): process-wide token buckets, one
        # per target class. First attempts are free; RETRIES spend — beyond
        # the budget the conductor fails fast to its next fallback (another
        # parent, back-to-source) instead of amplifying a cluster-wide storm.
        from dragonfly2_tpu.resilience.budget import budget_for

        self._sched_budget = budget_for("scheduler")
        self._parent_budget = budget_for("parent")
        # Successful piece reports ride a per-conductor batch buffer when the
        # client speaks report_pieces (all shipped clients do; test fakes may
        # not — they get the unary path).
        self._reports: PieceReportBuffer | None = None
        if self.cfg.batch_piece_reports and hasattr(scheduler, "report_pieces"):
            self._reports = PieceReportBuffer(
                scheduler, peer_id,
                max_batch=self.cfg.report_batch_size,
                flush_interval=self.cfg.report_flush_interval,
                log=self.log,
            )

    # ---- entry ----

    async def run(self) -> TaskStorage:
        """Download the task fully; returns its storage. Raises on failure.

        The watchdog is a deadline scope, not just a wait_for: nested rpc
        calls and piece fetches see min(remaining, per-op) timeouts through
        the propagated budget, and an engine-level scope (user --timeout)
        narrows it further."""
        self._t0 = time.monotonic()
        try:
            with dl.scope(self.cfg.watchdog_timeout) as budget:
                result = await asyncio.wait_for(self._run_inner(), budget.remaining())
            return result
        except BaseException:
            await self._safe_report_peer(success=False)
            raise
        finally:
            if self.ts is not None:
                self.ts.unpin()  # storage reclaim may evict us again
            close = getattr(self.bucket, "close", None)
            if close is not None:
                close()  # release this task's slice of the host budget
            if self._owns_session and self._session is not None:
                await self._session.close()
            if self._owns_raw and self._raw_client is not None:
                await self._raw_client.close()
            if self._owns_pipeline and self._pipeline_obj is not None:
                self._pipeline_obj.close()

    async def _run_inner(self) -> TaskStorage:
        reg = await self._register_admitted()
        if getattr(reg, "error", ""):
            raise IOError(f"task {self.meta.task_id}: registration refused: {reg.error}")
        self.ts = self.storage.register_task(
            self.meta.task_id,
            url=self.meta.url,
            digest=self.meta.digest,
            tag=self.meta.tag,
            application=self.meta.application,
        )
        self.ts.pin()  # immune to storage reclaim while this download runs
        self.pieces_preexisting = self.ts.finished_count()
        self._had_preexisting_pieces = self.pieces_preexisting > 0

        if reg.scope == "empty":
            self.ts.set_task_info(content_length=0, piece_size=1, total_pieces=0)
            self.ts.mark_done()
            await self._safe_report_peer(success=True)
            return self.ts
        if reg.scope == "tiny" and reg.direct_piece:
            await self._finish_tiny(reg.direct_piece)
            return self.ts
        if reg.back_to_source:
            await self._download_back_to_source()
        else:
            self._apply_task_info(reg)
            await self._download_p2p(reg.parents)

        # The full-content re-hash is redundant when every piece this
        # conductor landed was already validated against an expected digest
        # from the piece-metadata channel — the same per-piece trust chain the
        # reference's piece MD5 check uses (piece_manager.go processPieceFromSource
        # digest verification). Skipping it saves one full read+hash pass per
        # task — seconds per checkpoint shard on the fan-out path. It still
        # runs when any piece lacked a digest (back-to-source computes its
        # own) or when pieces predate this conductor (unknown provenance).
        every_piece_validated = (
            not self._had_preexisting_pieces
            and self._pieces_unverified == 0
            and self._digests_from_done_parents
            and self.ts.meta.total_pieces > 0
        )
        if not every_piece_validated:
            # verify() hashes the whole file — off the event loop, or a 100
            # MiB task would freeze every concurrent transfer for the pass
            if not await asyncio.to_thread(self.ts.verify):
                await self._safe_report_peer(success=False)
                raise digestlib.InvalidDigestError(
                    f"task {self.meta.task_id}: content digest mismatch"
                )
        self.ts.mark_done()
        await self._safe_report_peer(success=True)
        return self.ts

    async def _register_admitted(self) -> RegisterResult:
        """register_peer honoring the scheduler's typed `overloaded` answer
        (ISSUE 17 admission-control rung): the refusal carries a
        retry_after_s hint — pre-charge the scheduler retry budget, wait it
        out (jittered, bounded by the task budget), and re-register instead
        of failing the task. Any other refusal surfaces unchanged."""
        reg = await self.scheduler.register_peer(self.peer_id, self.meta, self.host)
        for attempt in range(1, 4):
            if getattr(reg, "error", "") != "overloaded":
                return reg
            retry_after = float(getattr(reg, "retry_after_s", 0.0)) or 1.0
            self._sched_budget.charge(retry_after)
            remaining = dl.remaining()
            if remaining is not None and remaining <= retry_after:
                return reg  # the wait would outlive the task budget
            # jitter UP only: arriving before retry_after would re-hit the
            # admission gate; spreading later de-synchronizes the shed crowd
            delay = retry_after * (1.0 + 0.5 * random.random())
            self.log.info(
                "scheduler overloaded; re-registering in %.1fs (attempt %d)",
                delay, attempt,
            )
            await asyncio.sleep(delay)
            reg = await self.scheduler.register_peer(self.peer_id, self.meta, self.host)  # dflint: disable=DF025 bounded 3-attempt admission handshake paced by the server's retry_after hint — one peer re-registering, not per-item fan-out
        return reg

    def _apply_task_info(self, reg: RegisterResult) -> None:
        if reg.content_length is not None and self.ts.meta.content_length < 0:
            self.ts.set_task_info(
                content_length=reg.content_length,
                piece_size=reg.piece_size,
                total_pieces=reg.total_pieces,
                digest=reg.digest or self.meta.digest,
            )

    async def _finish_tiny(self, data: bytes) -> None:
        self.ts.set_task_info(
            content_length=len(data), piece_size=max(1, len(data)), total_pieces=1
        )
        if not self.ts.has_piece(0):
            await self.ts.write_piece(0, data)
            self.pieces_fetched += 1
        self.ts.mark_done()
        await self._safe_report_peer(success=True)

    # ---- back-to-source (ref pieceManager.DownloadSource) ----

    async def _download_back_to_source(self) -> None:
        # source bytes carry no expected piece digests (we compute them as we
        # write) — the end-of-task full verify must run when a digest is known
        self._pieces_unverified += 1
        url = self.meta.url
        info = await self.sources.info(url, self.headers)
        if self.ts.meta.content_length < 0:
            if info.content_length < 0:
                await self._download_source_unknown_length(info)
                return
            psize = compute_piece_size(info.content_length)
            self.ts.set_task_info(
                content_length=info.content_length,
                piece_size=psize,
                total_pieces=piece_count(info.content_length, psize),
                digest=self.meta.digest,
            )
            await self.scheduler.report_task_metadata(
                self.meta.task_id,
                content_length=info.content_length,
                piece_size=psize,
                digest=self.meta.digest,
            )
        m = self.ts.meta
        if m.content_length == 0:
            self.ts.mark_done()
            return
        if info.supports_range:
            await self._download_source_ranged()
        else:
            await self._download_source_sequential()
        if m.content_length <= 128:
            data = await self.ts.read_range(Range(0, m.content_length))
            await self.scheduler.report_task_metadata(
                self.meta.task_id,
                content_length=m.content_length,
                piece_size=m.piece_size,
                direct_piece=data,
            )

    async def _download_source_ranged(self) -> None:
        """Pull missing pieces via CONCURRENT Range requests (the reference's
        multi-connection source download, piece_manager.go:67 ConcurrentOption):
        pieces write at disjoint offsets, so N in-flight ranges parallelize
        the origin link the way p2p piece workers parallelize parents. Each
        piece retries independently (shared backoff policy); a piece that
        exhausts its retries fails the task, cancelling its siblings."""
        m = self.ts.meta
        sem = asyncio.Semaphore(max(1, self.cfg.source_concurrency))

        async def fetch_once(idx: int) -> None:
            from dragonfly2_tpu.daemon import metrics

            if self.ts.has_piece(idx):
                return  # idempotent under retry: the piece already landed
            r = piece_range(idx, m.piece_size, m.content_length)
            t0 = time.monotonic()
            # pooled buffer + hash-on-receive: chunks land straight in a
            # reused buffer (no bytearray growth reallocs, no final bytes()
            # copy) and the piece digest is computed as they arrive instead
            # of in write_piece's second pass
            pipeline = self._pipeline()
            pooled = await pipeline.pool.acquire(r.length)
            # origin pieces join the trace too: the cutover path must be
            # attributable in the same timeline as parent fetches
            with default_tracer().span(
                "conductor.piece", piece=idx, bytes=r.length, path="origin"
            ):
                try:
                    pump = pipeline.hash_pump(pooled.view)
                    try:
                        off = 0
                        async for chunk in self.sources.download(self.meta.url, r, self.headers):
                            if off + len(chunk) > r.length:
                                raise IOError(
                                    f"source piece {idx}: got more than {r.length} bytes"
                                )
                            pooled.view[off : off + len(chunk)] = chunk
                            off += len(chunk)
                            pump.feed(off)
                            await self.bucket.acquire(len(chunk))
                        if off != r.length:
                            raise IOError(f"source piece {idx}: got {off}, want {r.length}")
                        d = await pump.finish()
                    except BaseException:
                        pump.abort()
                        raise
                    await self.ts.write_piece_view(idx, pooled.view, digest=d)
                finally:
                    pooled.release()
            self.bytes_from_source += r.length
            # same accounting as the sequential path (_write_source_piece):
            # cutover dashboards need parent vs back_to_source piece counts
            # to sum to the task's total
            metrics.PIECE_DOWNLOAD_TOTAL.inc(source="back_to_source")
            metrics.DOWNLOAD_BYTES.inc(r.length)
            await self._report_piece_success(idx, (time.monotonic() - t0) * 1000)

        async def fetch(idx: int) -> None:
            # Pieces retry independently with exponential backoff: an origin
            # blip (reset, truncated body, 5xx) must cost one piece a retry,
            # not the whole TaskGroup a cancellation cascade.
            async with sem:
                last: Exception | None = None
                for attempt in range(self.cfg.source_piece_retries + 1):
                    try:
                        await fetch_once(idx)
                        return
                    except (SourceError, IOError, aiohttp.ClientError, asyncio.TimeoutError) as e:
                        last = e
                        remaining = dl.remaining()
                        if remaining is not None and remaining <= 0:
                            break  # budget gone: fail now, the watchdog is racing us
                        if attempt < self.cfg.source_piece_retries:
                            self.log.debug(
                                "source piece %d attempt %d failed: %r", idx, attempt, e
                            )
                            await self._backoff.sleep(attempt)
                raise last if last is not None else IOError(f"source piece {idx} failed")

        await gather_all_cancel_on_error(
            fetch(idx) for idx in self.ts.finished.missing_until(m.total_pieces)
        )

    async def _download_source_sequential(self) -> None:
        """Origin without Range support: stream the whole body once, carving
        pieces as they fill (ref DownloadSource without ConcurrentOption)."""
        m = self.ts.meta
        buf = bytearray()
        idx = 0
        t0 = time.monotonic()
        async for chunk in self.sources.download(self.meta.url, headers=self.headers):
            buf.extend(chunk)
            await self.bucket.acquire(len(chunk))
            while len(buf) >= m.piece_size and idx < m.total_pieces - 1:
                piece, buf = bytes(buf[: m.piece_size]), bytearray(buf[m.piece_size :])
                await self._write_source_piece(idx, piece, t0)
                idx += 1
                t0 = time.monotonic()
        if idx != m.total_pieces - 1 or len(buf) != m.content_length - idx * m.piece_size:
            raise IOError(
                f"source stream ended early: piece {idx}, {len(buf)} buffered"
            )
        await self._write_source_piece(idx, bytes(buf), t0)

    async def _write_source_piece(self, idx: int, data: bytes, t0: float) -> None:
        from dragonfly2_tpu.daemon import metrics

        self.bytes_from_source += len(data)
        if self.ts.has_piece(idx):
            # recovered piece on a resumed task: the close-delimited stream
            # re-carried its bytes (no Range support — unavoidable), but it
            # is already landed and reported; re-landing would re-hash and
            # re-count it, and a re-report would double piece accounting
            return
        await self.ts.write_piece(idx, data)
        metrics.PIECE_DOWNLOAD_TOTAL.inc(source="back_to_source")
        metrics.DOWNLOAD_BYTES.inc(len(data))
        await self._report_piece_success(idx, (time.monotonic() - t0) * 1000)

    async def _download_source_unknown_length(self, info) -> None:
        """Origin without Content-Length: stream whole body, then size pieces."""
        buf = bytearray()
        async for chunk in self.sources.download(self.meta.url, headers=self.headers):
            buf.extend(chunk)
            await self.bucket.acquire(len(chunk))
        data = bytes(buf)
        psize = compute_piece_size(len(data))
        self.ts.set_task_info(
            content_length=len(data),
            piece_size=psize,
            total_pieces=piece_count(len(data), psize),
            digest=self.meta.digest,
        )
        for idx in range(self.ts.meta.total_pieces):
            if self.ts.has_piece(idx):
                continue  # recovered piece: already landed, never re-land
            r = piece_range(idx, psize, len(data))
            await self.ts.write_piece(idx, data[r.start : r.start + r.length])
            self.pieces_fetched += 1
        self.bytes_from_source += len(data)
        await self.scheduler.report_task_metadata(
            self.meta.task_id,
            content_length=len(data),
            piece_size=psize,
            direct_piece=data if len(data) <= 128 else b"",
        )

    # ---- P2P (ref pullPiecesWithP2P + downloadPieceWorker) ----

    async def _download_p2p(self, parents: list[ParentInfo]) -> None:
        self.dispatcher.update_parents(parents)
        session = self._http()
        reschedules = 0
        round_no = 0
        last_update = time.monotonic()

        try:
            while True:
                self._sync_parents(session)
                if self.ts.meta.content_length < 0:
                    # Parents are still back-to-source themselves and haven't
                    # learned the object size; wait for their metadata rather
                    # than burning the reschedule budget.
                    if not self.dispatcher.usable():
                        reschedules += 1
                        if reschedules > self.cfg.reschedule_limit \
                                or not self._reschedule_allowed(reschedules):
                            await self._download_back_to_source()
                            return
                        reg = await self._reschedule()  # dflint: disable=DF025 one budget-bounded reschedule per empty dispatch round, not per-item chatter
                        if reg.back_to_source:
                            await self._download_back_to_source()
                            return
                        self.dispatcher.update_parents(reg.parents)
                    await self._wait_update()
                    continue
                if self.ts.meta.content_length == 0 or self.ts.is_complete():
                    return
                total = self.ts.meta.total_pieces
                missing = list(self.ts.finished.missing_until(total))
                available = [i for i in missing if self.dispatcher.pick(i) is not None]
                if not available:
                    if any(not t.done() for t in self._sync_tasks.values()):
                        # Live parents just have nothing new yet — keep riding
                        # the push channel; spend the reschedule budget only
                        # after a real no-progress window.
                        if await self._wait_update():
                            last_update = time.monotonic()
                            continue
                        if time.monotonic() - last_update < self.cfg.no_progress_reschedule:
                            continue
                    reschedules += 1
                    if reschedules > self.cfg.reschedule_limit \
                            or not self._reschedule_allowed(reschedules):
                        self.log.info(
                            "peer %s: cutover to back-to-source for %d pieces",
                            self.peer_id, len(missing),
                        )
                        await self._download_back_to_source()
                        return
                    reg = await self._reschedule()  # dflint: disable=DF025 one budget-bounded reschedule per no-progress window, not per-item chatter
                    if reg.back_to_source:
                        await self._download_back_to_source()
                        return
                    self.dispatcher.update_parents(reg.parents)
                    last_update = time.monotonic()  # fresh no-progress window
                    await self._wait_update()
                    continue

                queue: asyncio.Queue[int] = asyncio.Queue(
                    maxsize=max(1, len(available))
                )
                for i in available:
                    queue.put_nowait(i)
                round_no += 1
                # the round span parents every piece span its workers open
                # (tasks created inside inherit the contextvar context) plus
                # the round-end report flush — the traced unit ROADMAP #1's
                # "per-round glue" lever is accounted in
                with default_tracer().span(
                    "conductor.dispatch_round",
                    round=round_no, pieces=len(available),
                    workers=min(self.cfg.piece_workers, len(available)),
                ):
                    workers = [
                        asyncio.ensure_future(self._piece_worker(session, queue))
                        for _ in range(min(self.cfg.piece_workers, len(available)))
                    ]
                    try:
                        await queue.join()
                    finally:
                        # on every exit from the round, so that no worker is
                        # left to spawn a write after the drain below
                        for w in workers:
                            w.cancel()
                        await asyncio.gather(*workers, return_exceptions=True)
                    # writes the workers deferred must land before the loop
                    # re-reads the bitset, or still-in-flight pieces would look
                    # missing and be refetched
                    await self._drain_writes()
                    # adaptive write-behind: the first measured round decides
                    # the mode for the rest of the task (no-op once decided)
                    if self._write_behind.measuring:
                        self._write_behind.decide()
                    # dispatch-round-end flush: the scheduler learns this
                    # round's pieces in ONE report_pieces RPC (≤1 flush per
                    # round unless the size/interval triggers fired mid-round)
                    if self._reports is not None:
                        await self._reports.flush()
                last_update = time.monotonic()
        finally:
            await self._drain_writes()
            for t in self._sync_tasks.values():
                t.cancel()
            await asyncio.gather(*self._sync_tasks.values(), return_exceptions=True)
            self._sync_tasks.clear()

    def _reschedule_allowed(self, reschedules: int) -> bool:
        """The first reschedule is normal protocol (free); RETRIES spend
        from the process-wide scheduler retry budget. Denied → the caller
        fails fast to back-to-source instead of joining a reschedule storm
        against an overloaded scheduler."""
        if reschedules <= 1 or self._sched_budget.spend():
            return True
        self.log.info(
            "reschedule retry budget exhausted (%s); failing fast to source",
            self._sched_budget.name,
        )
        return False

    async def _reschedule(self) -> RegisterResult:
        """reschedule with scheduler-restart recovery: a scheduler that lost
        this peer (process restart wiped its resource pool, or GC evicted
        us) answers not_found — re-register instead of failing the task, and
        push back what the fresh scheduler is missing (task metadata + the
        pieces this peer already holds) so it rebuilds its view from
        announces alone. The daemons' existing backoff+breaker path already
        covers the reconnect; this covers the state."""
        try:
            return await self.scheduler.reschedule(self.peer_id)
        except KeyError:
            pass  # in-process client surfaces the raw lookup failure
        except RpcError as e:
            if e.code != "not_found":
                raise
        self.log.info("scheduler lost peer %s: re-registering", self.peer_id)
        reg = await self._register_admitted()
        if getattr(reg, "error", ""):
            raise IOError(
                f"task {self.meta.task_id}: re-registration refused: {reg.error}"
            )
        if self.ts is not None and self.ts.meta.content_length >= 0:
            try:
                # announce_task, not report_pieces: possession is declared
                # metrics-free (a success report would re-count
                # DOWNLOAD_TRAFFIC_BYTES for bytes the old incarnation of
                # this scheduler may already have counted, and feed 0.0 cost
                # samples into the peer's parent-selection feature). The
                # announce adopts the row just re-registered (same peer_id),
                # sets task metadata, and marks the held pieces.
                await self.scheduler.announce_task(
                    self.peer_id, self.meta, self.host,
                    content_length=self.ts.meta.content_length,
                    piece_size=self.ts.meta.piece_size,
                    piece_indices=sorted(self.ts.finished.indices()),
                    digest=self.ts.meta.digest,
                )
            except Exception as e:  # noqa: BLE001 — advisory rebuild; the
                # download itself only needs the registration to stand
                self.log.debug("post-re-register state push failed: %r", e)
        return reg

    async def _wait_update(self) -> bool:
        """Park until any parent sync loop reports progress (piece landed,
        metadata learned, parent died). Returns True if an update arrived,
        False on the fallback-timeout re-check. This replaces the fixed
        polling interval on the hot path: piece-arrival latency is now one
        push round-trip, not up to a poll period."""
        try:
            await asyncio.wait_for(
                self._update_event.wait(), timeout=self.cfg.metadata_poll_interval
            )
            arrived = True
        except asyncio.TimeoutError:
            arrived = False
        self._update_event.clear()
        return arrived

    def _sync_parents(self, session: aiohttp.ClientSession) -> None:
        """Ensure one long-poll sync loop per usable parent (ref
        pieceTaskSyncManager.syncPeers); drop loops for removed parents."""
        current = {s.info.peer_id for s in self.dispatcher.usable()}
        for pid in list(self._sync_tasks):
            t = self._sync_tasks[pid]
            if pid not in current or t.done():
                if pid not in current:
                    t.cancel()
                elif not t.cancelled() and t.exception() is not None:
                    self.log.warning("parent %s sync loop died: %r", pid, t.exception())
                del self._sync_tasks[pid]
        for state in self.dispatcher.usable():
            if state.info.peer_id not in self._sync_tasks:
                self._sync_tasks[state.info.peer_id] = asyncio.ensure_future(
                    self._parent_sync_loop(session, state)
                )

    async def _parent_sync_loop(self, session: aiohttp.ClientSession, state: ParentState) -> None:
        """Long-poll one parent's metadata endpoint: the first request returns
        immediately with current state; subsequent requests park server-side
        until the parent's task state changes past the seen version (ref
        pieceTaskSynchronizer.receive push loop)."""
        version = -1
        errors = 0  # consecutive failures feed the shared backoff ladder
        url = (
            f"{self._scheme}://{_url_host(state.info.ip)}:{state.info.download_port}"
            f"/metadata/{self.meta.task_id}"
        )
        while not state.blocked:
            try:
                if faultline.ACTIVE is not None:
                    await faultline.ACTIVE.fire("parent.metadata")
                # `have` makes piece_digests a delta (digests we already hold
                # are never re-sent — O(pieces) total instead of O(pieces²))
                have = 0
                for k in self._piece_digests:
                    have |= 1 << int(k)
                # park no longer than the remaining task budget allows
                wait = self.cfg.longpoll_wait
                remaining = dl.remaining()
                if remaining is not None:
                    wait = max(0.1, min(wait, remaining))
                async with session.get(
                    url,
                    params={
                        "since": str(version),
                        "wait": str(wait),
                        "have": format(have, "x"),
                    },
                    timeout=aiohttp.ClientTimeout(total=wait + 10),
                ) as resp:
                    if resp.status != 200:
                        state.record(False, 0)
                        self._update_event.set()
                        errors += 1
                        # parent may not know the task yet
                        await self._backoff.sleep(errors - 1)
                        continue
                    data = await resp.json()
                errors = 0
                version = data.get("version", version)
                finished_hex = data.get("finished_hex")
                if finished_hex is not None:
                    state.pieces = set(Bitset(int(finished_hex, 16)).indices())
                else:  # older peers announce an index list
                    state.pieces = set(data.get("finished_pieces", ()))
                parent_done = bool(data.get("done"))
                for k, v in data.get("piece_digests", {}).items():
                    # validate BEFORE storing: keys feed the have-bitset
                    # (1 << int(k)) on every later sync — one non-numeric or
                    # out-of-range key from a bad parent must not poison
                    # metadata sync with every OTHER parent forever
                    if not (isinstance(k, str) and k.isdigit()):
                        continue
                    if k not in self._piece_digests:
                        self._piece_digests[k] = v
                        if not parent_done:
                            # streaming parent: its digests are self-computed
                            # over bytes it hasn't end-to-end verified yet, so
                            # the final full verify must still run here
                            self._digests_from_done_parents = False
                if self.ts.meta.content_length < 0 and data.get("content_length", -1) >= 0:
                    self.ts.set_task_info(
                        content_length=data["content_length"],
                        piece_size=data["piece_size"],
                        total_pieces=data["total_pieces"],
                        digest=data.get("digest", ""),
                    )
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — a bad parent (garbage JSON,
                # missing fields, network error) must count against it and back
                # off, never kill the sync loop silently
                state.record(False, 0)
                self._update_event.set()
                self.log.debug("parent %s metadata sync error: %r", state.info.peer_id, e)
                errors += 1
                await self._backoff.sleep(errors - 1)
                continue
            self._update_event.set()

    # ---- striped fetch: per-parent windows + slowest-stripe tail steal ----

    def _steal_active(self) -> bool:
        return (
            self.cfg.tail_steal
            and self.cfg.striped_fetch
            and len(self.dispatcher.usable()) > 1
        )

    def _steal_candidate(self) -> "tuple[_InflightFetch | None, float]":
        """(entry, seconds-until-mature): the most overdue in-flight fetch
        that has an alternative parent, or (None, 0) when nothing in flight
        is stealable at all. A fetch matures for stealing after
        max(steal_min_ms, steal_cost_factor * its parent's cost EWMA)."""
        now = time.monotonic()
        best: _InflightFetch | None = None
        best_delay = float("inf")
        for entry in self._inflight.values():
            if entry.stolen or not entry.parent_id or entry.steal_attempts >= 2:
                continue
            alt = self.dispatcher.pick(
                entry.idx, striped=True, exclude=frozenset((entry.parent_id,))
            )
            if alt is None:
                continue  # nobody else holds this piece: nothing to steal to
            st = self.dispatcher.parents.get(entry.parent_id)
            ewma = st.cost_ewma_ms if st is not None else 0.0
            mature_s = max(
                self.cfg.steal_min_ms, self.cfg.steal_cost_factor * ewma
            ) / 1e3
            delay = (entry.started + mature_s) - now
            if delay < best_delay:
                best, best_delay = entry, delay
        if best is None:
            return None, 0.0
        return best, max(0.0, best_delay)

    async def _steal_piece(self, session, entry: _InflightFetch) -> None:
        """Duplicate-fetch a tail piece from a different parent; first copy
        to LAND wins (the landing path's has_piece guard makes the loser's
        write+accounting a no-op, so DOWNLOAD_TRAFFIC_BYTES never double
        counts). A winning steal cancels the loser's fetch so the round
        doesn't wait out the slow parent anyway."""
        from dragonfly2_tpu.daemon import metrics

        entry.stolen = True
        entry.steal_attempts += 1
        self.steals_attempted += 1
        won = False
        try:
            # won = OUR fetch landed the piece and claimed its exactly-once
            # attribution. has_piece alone is not a win test: the ORIGINAL
            # can land and still be mid-accounting (task not done), and
            # counting that as a steal win would both overstate steal
            # efficacy and cancel the original's in-flight success report.
            won = await self._download_one_piece(
                session, entry.idx, exclude=frozenset((entry.parent_id,)),
                inline_write=True,
            )
        except Exception as e:  # noqa: BLE001 — a failed steal must not kill
            # the worker loop (the original fetch still owns the piece)
            self.log.debug("tail steal of piece %d failed: %r", entry.idx, e)
        current = self._inflight.get(entry.idx)
        if won:
            if current is entry and not entry.task.done():
                # the steal landed while the original is still grinding: cut
                # the loser loose (its cleanup releases its buffer; the
                # worker sees the cancellation as "stolen" and moves on)
                entry.task.cancel()
            self.steals_won += 1
            metrics.PIECE_STEALS_TOTAL.inc(won="true")
        else:
            entry.stolen = False  # original may still need recovery/steals
            metrics.PIECE_STEALS_TOTAL.inc(won="false")

    async def _next_assignment(self, session, queue: asyncio.Queue) -> int:
        """queue.get with tail-steal: an idle worker (empty queue, pieces
        still in flight) re-fetches the slowest mature stripe instead of
        parking. Waits are bounded by the next candidate's maturity and
        always yield to fresh queue work the moment it appears."""
        while True:
            try:
                return queue.get_nowait()
            except asyncio.QueueEmpty:
                pass
            if not self._steal_active() or not self._inflight:
                return await queue.get()
            entry, delay = self._steal_candidate()
            if entry is None:
                return await queue.get()
            if delay <= 0:
                await self._steal_piece(session, entry)
                continue
            try:
                return await asyncio.wait_for(queue.get(), timeout=delay)
            except asyncio.TimeoutError:
                continue  # candidate matured (or the flight set changed)

    async def _run_piece_fetch(self, session, idx: int) -> None:
        """One piece fetch, registered for tail stealing when striping is
        live. The fetch runs as its own task so a winning steal can cancel
        it; a cancellation that was NOT a steal (round teardown) propagates
        to the worker exactly as before."""
        if not self._steal_active():
            await self._download_one_piece(session, idx)
            return
        entry = _InflightFetch(idx=idx, task=None, started=time.monotonic())
        fetch = asyncio.ensure_future(
            self._download_one_piece(session, idx, inflight=entry)
        )
        entry.task = fetch
        self._inflight[idx] = entry
        try:
            await fetch
        except asyncio.CancelledError:
            if not fetch.cancelled():
                # the WORKER is being cancelled (teardown): take the fetch
                # down with us and propagate
                fetch.cancel()
                raise
            # else: a steal won and cancelled the fetch — the piece is
            # landed (or will be refetched next round); not a failure
        finally:
            if self._inflight.get(idx) is entry:
                del self._inflight[idx]

    async def _piece_worker(self, session: aiohttp.ClientSession, queue: asyncio.Queue) -> None:
        while True:
            idx = await self._next_assignment(session, queue)
            try:
                if not self.ts.has_piece(idx):
                    await self._run_piece_fetch(session, idx)
            except Exception as e:
                # _download_one_piece handles the expected fetch/verify
                # failures itself; anything landing HERE (storage write error,
                # report rpc failure, injected storage fault) used to be
                # debug-logged and silently dropped until the 600 s watchdog
                # fired. Re-enqueue bounded; past the bound, report the piece
                # failed so the dispatcher/cutover logic sees it immediately.
                n = self._piece_errors.get(idx, 0) + 1
                self._piece_errors[idx] = n
                if n <= self.cfg.piece_requeue_limit and not self.ts.has_piece(idx) \
                        and self._parent_budget.spend():
                    # the immediate re-enqueue is a RETRY and spends the
                    # parent retry budget; denied → the piece reports failed
                    # below and recovers via dispatch/reschedule/cutover
                    # (another parent or the source) without the extra hammer
                    self.log.debug(
                        "piece %d worker failed (attempt %d), re-enqueueing: %r", idx, n, e
                    )
                    queue.put_nowait(idx)
                else:
                    self.log.warning(
                        "piece %d failed past the re-enqueue budget", idx, exc_info=True
                    )
                    try:
                        await self.scheduler.report_piece_result(  # dflint: disable=DF025 failed pieces report individually BY DESIGN (they drive rescheduling promptly); successes batch via PieceReportBuffer
                            self.peer_id, idx, success=False
                        )
                    except Exception as report_err:  # noqa: BLE001 — the report is
                        # best-effort; the dispatch loop re-sees the piece anyway
                        self.log.debug("piece %d failure report failed: %r", idx, report_err)
            finally:
                queue.task_done()

    async def _download_one_piece(
        self,
        session: aiohttp.ClientSession,
        idx: int,
        *,
        exclude: frozenset = frozenset(),
        inflight: "_InflightFetch | None" = None,
        inline_write: bool = False,
    ) -> bool:
        """Returns True when THIS fetch landed the piece and claimed its
        attribution (False: no parent, failure, or another copy won)."""
        striped = self.cfg.striped_fetch and len(self.dispatcher.usable()) > 1
        state = self.dispatcher.pick(idx, striped=striped, exclude=exclude)
        if state is None:
            return False
        if inflight is not None:
            inflight.parent_id = state.info.peer_id
        m = self.ts.meta
        r = piece_range(idx, m.piece_size, m.content_length)
        path_qs = (
            f"/download/{self.meta.task_id[:3]}/{self.meta.task_id}?peerId={self.peer_id}"
        )
        t0 = time.monotonic()
        # per-op timeout capped by the propagated task budget; the floor
        # matters because aiohttp treats total=0 as "no timeout", which is
        # exactly wrong for an exhausted budget
        piece_timeout = max(0.001, dl.timeout(self.cfg.piece_timeout))
        use_raw = r.length >= self._RAW_FETCH_BYTES
        # per-piece span with the PR 3 pipeline's stage decomposition lifted
        # into attributes (recv/hash-wait, write in the nested write span):
        # this is what lets dftrace say WHERE a slow piece spent its time.
        # Stage clocks are read only when the trace is sampled — an
        # unsampled piece pays the span object and nothing else.
        self.dispatcher.begin(state)  # per-parent window accounting (striping)
        try:
            with default_tracer().span(
                "conductor.piece",
                piece=idx, parent_peer=state.info.peer_id, bytes=r.length,
                path="raw" if use_raw else "http",
            ) as piece_span:
                return await self._fetch_and_land_piece(
                    session, state, idx, r, path_qs, piece_timeout, t0,
                    use_raw, piece_span, inline_write=inline_write,
                )
        finally:
            self.dispatcher.end(state)

    async def _fetch_and_land_piece(
        self, session, state, idx, r, path_qs, piece_timeout, t0,
        use_raw, piece_span, *, inline_write: bool = False,
    ) -> bool:
        pooled = None
        digest = ""
        data = b""
        sampled = piece_span.sampled
        # stage clocks run when the trace wants them OR while the write-
        # behind governor is measuring its first round (two monotonic reads
        # per piece, nothing else)
        clocked = sampled or self._write_behind.measuring
        recv_s = 0.0
        try:
            if faultline.ACTIVE is not None:
                await faultline.ACTIVE.fire("parent.fetch")
            await self.bucket.acquire(r.length)
            if use_raw:
                # big pieces ride the zero-copy pipeline: the body lands
                # straight in a POOLED buffer (sock_recv_into, no per-piece
                # allocation) and is sha256'd AS IT ARRIVES on the pipeline's
                # hash thread — recv and hash run on two cores instead of two
                # serial passes on one (daemon/rawrange.py + pipeline.py).
                # Truncate/corrupt faults fire inside the recv loop — the
                # pipeline's read point — so chaos proofs cover this path.
                pipeline = self._pipeline()
                pooled = await pipeline.pool.acquire(r.length)
                pump = pipeline.hash_pump(pooled.view)
                try:
                    t_recv = time.monotonic() if clocked else 0.0
                    await self._raw_http().get_range_into(
                        state.info.ip, state.info.download_port, path_qs,
                        r.header(), pooled.view, timeout=piece_timeout,
                        on_chunk=pump.feed, fault_point="parent.piece_body",
                    )
                    t_hash = time.monotonic() if clocked else 0.0
                    if clocked:
                        recv_s = t_hash - t_recv
                    if sampled:
                        piece_span.set_attr("recv_ms", round(recv_s * 1e3, 3))
                    digest = await pump.finish()
                    if sampled:
                        # the hash overlaps recv; this is the residual WAIT
                        # for the hash thread after the last byte landed
                        piece_span.set_attr(
                            "hash_wait_ms", round((time.monotonic() - t_hash) * 1e3, 3)
                        )
                except AddressFamilyError:
                    # this host cannot speak the parent's address family over
                    # a raw socket (e.g. IPv6 parent, odd local stack): not
                    # the parent's fault — retry below via aiohttp, whose
                    # resolver handles mixed stacks (ADVICE r05 #1)
                    pump.abort()
                    pooled.release()
                    pooled = None
                    use_raw = False
                    piece_span.set_attr("path", "http")
                    self.log.debug(
                        "parent %s: raw socket family unavailable for %s, "
                        "falling back to aiohttp", state.info.peer_id, state.info.ip,
                    )
                except BaseException:
                    pump.abort()
                    pooled.release()
                    pooled = None
                    raise
            if not use_raw:
                headers = {"Range": r.header()}
                ctx = default_tracer().current_context()
                if ctx is not None:
                    # the aiohttp fallback carries the same traceparent the
                    # raw client stamps, so IPv6/small pieces join the trace
                    headers["traceparent"] = ctx.traceparent()
                t_recv = time.monotonic() if sampled else 0.0
                async with session.get(
                    f"{self._scheme}://{_url_host(state.info.ip)}:{state.info.download_port}{path_qs}",
                    headers=headers,
                    timeout=aiohttp.ClientTimeout(total=piece_timeout),
                ) as resp:
                    if resp.status != 206:
                        raise IOError(f"parent returned HTTP {resp.status}")
                    data = await resp.read()
                if sampled:
                    piece_span.set_attr(
                        "recv_ms", round((time.monotonic() - t_recv) * 1e3, 3)
                    )
                if faultline.ACTIVE is not None:
                    # damage the payload AFTER the fetch so the digest check
                    # (and only it) stands between a corrupt parent and disk
                    data = faultline.ACTIVE.mutate("parent.piece_body", data)
        except (aiohttp.ClientError, asyncio.TimeoutError, IOError) as e:
            piece_span.set_attr("failed", True)
            await self._record_piece_failure(
                state, idx, (time.monotonic() - t0) * 1000, f"failed: {e}"
            )
            return False
        cost = (time.monotonic() - t0) * 1000
        if self.ts.has_piece(idx):
            # another fetch of this piece landed while ours was on the wire
            # (tail steal, or a worker-requeue race): the winner already
            # wrote + accounted it — landing again would double-count
            # DOWNLOAD_TRAFFIC_BYTES and re-hash a finished piece
            if pooled is not None:
                pooled.release()
            return False
        expected = self._piece_digests.get(str(idx), "")
        if not expected:
            self._pieces_unverified += 1
        if use_raw:
            if expected and digest != expected:
                # checked HERE, before any write is (possibly deferred to a
                # writer task): the parent must be charged and the piece
                # retried immediately, not after a write round-trip
                pooled.release()
                await self._record_piece_failure(
                    state, idx, cost,
                    f"corrupt: digest {digest[:12]} != {expected[:12]}", corrupt=True,
                )
                return False
            # the store write runs on a worker thread either way
            # (write_piece_view offloads big writes); deferring additionally
            # lets THIS worker recycle a fresh buffer into recv before the
            # write lands — the governor decides at runtime, see
            # ConductorConfig.defer_piece_writes for the measured trade-off.
            # Steal fetches force INLINE (`inline_write`): the stealer's
            # win test is whether its own chain claimed attribution, and a
            # spawned write would make every deferred-mode steal read as a
            # loss — never cancelling the slow loser and re-stealing the
            # same piece until its cap.
            if self._write_behind.defer and not inline_write:
                self._spawn_piece_write(state, idx, pooled, digest, cost, r.length)
                return False  # outcome unknowable here; only steals need it
            return await self._write_fetched_piece(
                state, idx, pooled, digest, cost, r.length, recv_s=recv_s
            )
        try:
            await self.ts.write_piece(idx, data, expected_digest=expected)
        except (ValueError, digestlib.InvalidDigestError) as e:
            await self._record_piece_failure(state, idx, cost, f"corrupt: {e}", corrupt=True)
            return False
        return await self._account_piece_success(state, idx, cost, len(data))

    async def _record_piece_failure(
        self, state, idx, cost, why: str, *, corrupt: bool = False
    ) -> None:
        """Shared failure accounting for every per-piece rejection path:
        charge the parent, tell the scheduler, log (warning for corruption —
        it implicates the parent's data, debug for routine fetch errors)."""
        state.record(False, cost)
        await self.scheduler.report_piece_result(
            self.peer_id, idx, success=False, cost_ms=cost, parent_id=state.info.peer_id
        )
        log = self.log.warning if corrupt else self.log.debug
        log("piece %d from %s %s", idx, state.info.peer_id, why)

    def _spawn_piece_write(self, state, idx, pooled, digest, cost, length) -> None:
        t = asyncio.ensure_future(
            self._write_fetched_piece(state, idx, pooled, digest, cost, length)
        )
        self._pending_writes.add(t)
        t.add_done_callback(self._pending_writes.discard)

    async def _write_fetched_piece(
        self, state, idx, pooled, digest, cost, length, recv_s: float = 0.0
    ) -> bool:
        """Land a digest-verified pooled buffer in storage (writer side of
        the recv/hash/write overlap; awaited inline or spawned per the
        write-behind decision). A write failure leaves the piece's bitset
        bit unset, so the dispatch loop refetches it — the same bounded
        recovery the worker-level re-enqueue gives small-piece writes.
        Returns True when this write claimed the piece's attribution."""
        try:
            try:
                measuring = self._write_behind.measuring
                t_w = time.monotonic() if measuring else 0.0
                # write stage span (inline: nested under conductor.piece;
                # deferred: a sibling task span in the same round) — the
                # third leg of the recv/hash/write stage decomposition
                with default_tracer().span(
                    "conductor.piece_write", piece=idx, bytes=length
                ):
                    await self.ts.write_piece_view(idx, pooled.view, digest=digest)
                if measuring:
                    # the governor's decision inputs: this piece's recv vs
                    # write stage durations (inline mode, first round)
                    self._write_behind.note(recv_s, time.monotonic() - t_w)
            finally:
                pooled.release()
        except Exception as e:
            n = self._piece_errors.get(idx, 0) + 1
            self._piece_errors[idx] = n
            if n <= self.cfg.piece_requeue_limit and not self.ts.has_piece(idx):
                self.log.debug(
                    "piece %d deferred write failed (attempt %d), will refetch: %r",
                    idx, n, e,
                )
                return False
            self.log.warning("piece %d failed past the write-retry budget", idx,
                             exc_info=True)
            try:
                await self.scheduler.report_piece_result(self.peer_id, idx, success=False)
            except Exception as report_err:  # noqa: BLE001 — best-effort advisory;
                # the dispatch loop re-sees the piece anyway
                self.log.debug("piece %d failure report failed: %r", idx, report_err)
            return False
        return await self._account_piece_success(state, idx, cost, length)

    async def _account_piece_success(self, state, idx, cost, length) -> bool:
        """Returns True when THIS call claimed the piece's (exactly-once)
        attribution — the signal `_steal_piece` uses to decide whether its
        fetch actually won the race or merely observed the other copy's
        landing."""
        # the serving parent earns its success/cost sample either way — it
        # DID deliver valid bytes, even if another copy landed first
        state.record(True, cost)
        if idx in self._accounted:
            # duplicate landing (steal + original racing: storage deduped
            # the write, both callers got success): bytes, metrics, and the
            # scheduler report must count EXACTLY once — the first copy to
            # reach accounting wins attribution.
            return False
        self._accounted.add(idx)
        self.bytes_from_parents += length
        pid = state.info.peer_id
        self.pieces_by_parent[pid] = self.pieces_by_parent.get(pid, 0) + 1
        from dragonfly2_tpu.daemon import metrics

        metrics.PIECE_DOWNLOAD_TOTAL.inc(source="parent")
        metrics.DOWNLOAD_BYTES.inc(length)
        await self._report_piece_success(idx, cost, pid)
        return True

    async def _report_piece_success(self, idx: int, cost_ms: float, parent_id: str = "") -> None:
        """Success-report fast path: enqueue into the batch buffer (sync, no
        RPC on the piece path) or fall back to the unary best-effort report.
        Either way a landed piece is never failed by its report (the
        worker-level catch would re-enqueue a piece that needs no refetch)."""
        self.pieces_fetched += 1
        if self._reports is not None:
            self._reports.add(idx, cost_ms, parent_id)
            return
        try:
            await self.scheduler.report_piece_result(
                self.peer_id, idx, success=True, cost_ms=cost_ms, parent_id=parent_id
            )
        except Exception as e:  # noqa: BLE001 — advisory report; the piece IS on disk
            self.log.debug("piece %d success report failed: %r", idx, e)

    async def _drain_writes(self) -> None:
        """Barrier for deferred store writes (round end / teardown). It runs
        once the round's workers are gone, so no write can be spawned while
        it waits, and one gather over a snapshot is the whole barrier. Do not
        loop until the set is empty: a finished task leaves it by a done
        callback one loop turn later, and Python 3.12's gather over finished
        tasks completes without yielding, so such a loop starves that
        callback forever. Write tasks handle their own failures, so gather
        only shields teardown from surprise cancellation races."""
        await asyncio.gather(*self._pending_writes, return_exceptions=True)

    # ---- helpers ----

    def _http(self) -> aiohttp.ClientSession:
        if self._session is None or self._session.closed:
            # 1 MiB read buffer: the 64 KiB default hits the stream reader's
            # high-water mark hundreds of times per 16 MiB checkpoint piece,
            # each a transport pause/resume round-trip on the event loop
            connector = None
            if self._data_tls is not None:
                # parents' metadata long-polls + small-piece fallbacks ride
                # the same mTLS client identity the raw path handshakes with
                connector = aiohttp.TCPConnector(ssl=self._data_tls.client_ctx)
            self._session = aiohttp.ClientSession(
                read_bufsize=1 << 20, connector=connector
            )
        return self._session

    # pieces at/above this size fetch via the raw recv_into client; below it
    # aiohttp's robustness is worth its copy (the copy is noise there)
    _RAW_FETCH_BYTES = 256 << 10

    def _raw_http(self) -> "RawRangeClient":
        if self._raw_client is None:
            from dragonfly2_tpu.daemon.rawrange import RawRangeClient

            # standalone conductors (tests, direct use) must still speak the
            # data plane's wire posture — a plain client against mTLS
            # parents would charge every parent with handshake garbage
            self._raw_client = RawRangeClient(tls=self._data_tls)
        return self._raw_client

    def _pipeline(self):
        if self._pipeline_obj is None:
            from dragonfly2_tpu.daemon.pipeline import PiecePipeline

            self._pipeline_obj = PiecePipeline()
        return self._pipeline_obj

    async def _safe_report_peer(self, *, success: bool) -> None:
        if self._peer_reported:  # failure paths raise after reporting: once only
            return
        self._peer_reported = True
        if success and self.pieces_by_parent:
            # stripe width for this task: how many distinct parents actually
            # served pieces (1 = classic single-parent assignment)
            from dragonfly2_tpu.daemon import metrics

            metrics.PIECE_STRIPE_PARENTS.observe(float(len(self.pieces_by_parent)))
        elapsed = max(1e-6, time.monotonic() - self._t0)
        bw = (self.bytes_from_parents + self.bytes_from_source) / elapsed
        if self._reports is not None:
            # task-completion flush BEFORE the peer result: report_peer_result
            # snapshots the peer's finished set into telemetry, so buffered
            # pieces must land first. close_with_result rides both in ONE
            # report_batch RPC when the scheduler speaks it; False means the
            # pieces were flushed the plain way and the unary result below
            # still owes.
            try:
                if await self._reports.close_with_result(
                    success=success, bandwidth_bps=bw
                ):
                    return
            except Exception:
                self.log.exception("batched close failed for %s", self.peer_id)
        try:
            await self.scheduler.report_peer_result(
                self.peer_id, success=success, bandwidth_bps=bw
            )
        except Exception:
            self.log.exception("report_peer_result failed for %s", self.peer_id)
