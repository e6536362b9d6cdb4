"""Trainer service: receives telemetry datasets, trains, registers models.

Completes the reference's unfinished ML loop (SURVEY.md §3.4): the reference
defined the Train client-stream contract (pkg/rpc/trainer/server/server.go:59,
TrainMLPRequest/TrainGNNRequest chunks) and a trainer/ skeleton with config +
metrics but no training loop, and the manager's CreateModel was a TODO stub
(manager/rpcserver/manager_server_v2.go:739-743). Here:

  train_open → train_chunk* → train_close   (the client-stream, unrolled over
  our unary RPC; chunks are npz-serialized columnar telemetry arrays)

Ingest is incremental and the event loop stays free throughout:

  - train_chunk folds each chunk straight into the session's
    DatasetAccumulator (vectorized: about 2.3 ms a 4,096-row chunk at
    32,768 hosts on a TPU v5e machine's host) instead of
    retaining raw record arrays; train_close commits the session's
    aggregates into the shared rolling pool via merge_from — exactly-once,
    so a failed-and-retried upload never double-counts. The pool
    (pool_rows) is aggregated state + a bounded columnar pair pool, not a
    list of per-session uploads, and rotates fresh past
    pool_max_hosts/pool_max_edges.
  - train_close never blocks the caller: the session joins a queue and one
    background drainer serializes training runs (the scheduler's upload RPC
    used to wait for a full prior train here).
  - Dataset materialization and the MLP train run on worker threads; the GNN
    runs through train_gnn.train_async, whose scan-step loop yields between
    jitted calls — the heartbeat test pins status-RPC latency mid-train.
  - Sessions opened but never closed are evicted past session_ttl; an
    evicted (uncommitted) session contributes nothing to the pool.

then each run trains the MLP bandwidth predictor (config 1) and — when probe
records exist — the GraphSAGE topology scorer (config 2/3, sharded over
whatever mesh is live), writes artifacts, and registers + activates versions
in the manager's model registry.
"""

from __future__ import annotations

import asyncio
import collections
import logging
import time
from dataclasses import asdict, dataclass, field, fields, replace
from pathlib import Path
from typing import Any, Callable

import numpy as np

from dragonfly2_tpu.observability.gcwatch import default_watch as gc_watch
from dragonfly2_tpu.observability.tracing import default_tracer
from dragonfly2_tpu.telemetry.records import unpack_records
from dragonfly2_tpu.trainer import (
    artifacts,
    dataset as datasetlib,
    metrics as train_metrics,
    train_gnn,
    train_mlp,
)
from dragonfly2_tpu.utils import jaxenv

logger = logging.getLogger(__name__)

# run manifests kept for `train_history` (one per training run, bounded)
RUN_HISTORY_CAP = 64


@dataclass
class IngestCounts:
    """What an upload cost the server, on its monotonic clock: always on, a
    few clock reads a handler. `decode_s` is `unpack_records` over the chunks,
    `fold_s` the accumulator's `add_downloads` / `add_probes`, `merge_s` the
    close's `merge_from` and rotation check, `wait_s` the time from one
    handler's end to the next one's start (the server waiting on the feeder
    and the wire), `open_to_close_s` from `train_open`'s start to
    `train_close`'s end. `in_run_s` is the part of decode, fold and merge
    that ran while the drainer was inside a run (on the loop that hands the
    run's scan calls back), `chunks_in_run` the chunks folded then.
    `keys_looked_up`, `keys_admitted`, `collisions` are what the fold's and
    the merge's get-or-add tables resolved (`dataset.KeyCounts`). A run's
    manifest carries its sessions' sum under `ingest` (`sessions` > 1 where
    the drainer coalesced closes)."""

    sessions: int = 1
    chunks: int = 0
    bytes: int = 0
    rows: int = 0
    decode_s: float = 0.0
    fold_s: float = 0.0
    merge_s: float = 0.0
    wait_s: float = 0.0
    open_to_close_s: float = 0.0
    in_run_s: float = 0.0
    chunks_in_run: int = 0
    keys_looked_up: int = 0
    keys_admitted: int = 0
    collisions: int = 0

    def add(self, other: "IngestCounts") -> None:
        for f in fields(self):
            setattr(self, f.name, getattr(self, f.name) + getattr(other, f.name))

    def add_keys(self, after: datasetlib.KeyCounts, before: datasetlib.KeyCounts | None = None) -> None:
        """Count what an accumulator's tables resolved since `before`."""
        before = before or datasetlib.KeyCounts()
        self.keys_looked_up += after.looked_up - before.looked_up
        self.keys_admitted += after.admitted - before.admitted
        self.collisions += after.collisions - before.collisions

    def report(self) -> dict:
        return {k: round(v, 4) if isinstance(v, float) else v for k, v in asdict(self).items()}


@dataclass
class TrainSession:
    token: str
    scheduler_hostname: str = ""
    scheduler_id: int = 0
    # every session folds into its OWN accumulator; train_close commits it
    # into the shared pool (merge_from) — exactly-once, even across retries
    acc: datasetlib.DatasetAccumulator = field(
        default_factory=datasetlib.DatasetAccumulator
    )
    ingest: IngestCounts = field(default_factory=IngestCounts)
    opened_at: float = field(default_factory=time.time)
    last_activity: float = field(default_factory=time.time)
    # monotonic: the open handler's start, the last handler's end
    t_open: float = 0.0
    t_idle: float = 0.0
    # `trainer.ingest`, open to close: begun at train_open (continuing the
    # caller's trace, or a root drawn once against the sample rate), never
    # made current, since it outlives the handler that opens it
    span: Any = None
    # the session span's context: chunks and merge are its children, and so
    # is the background train run, which outlives the RPC that queued it
    # (the drainer task's own captured contextvar points at whichever close
    # FIRST started it — wrong for every later run). One upload and its run
    # are one trace, sampled all or nothing
    trace_ctx: Any = None
    # what the collector took from train_open to the run's manifest
    # (observability/gcwatch.py; None where no watch is installed)
    gc: Any = None
    # multi-source attribution (federation): the newest upload of every
    # scheduler whose session committed into the pool this run trains on, in
    # commit order: name -> its `trainer.ingest` trace id (None where that
    # trace is not sampled), stamped at close time (a later close into the
    # same pool holds every earlier one). The registry rows' `contributors`
    # and the run manifest's `ingest.schedulers` / `ingest.traces`, so each
    # scheduler's upload trace leads to the model that holds its records
    uploads: dict = field(default_factory=dict)
    # the pool this session committed into, as the commit left it (the run
    # manifest's `pool`): `epoch` rotations before it, `commits` merged into
    # it since the last rotation, its `hosts` and `edges`, the `hosts_added`
    # by this commit, the `hosts_stale` this commit did not name (hosts the
    # scheduler's host GC has dropped since), and whether the close `rotated`
    pool: dict | None = None


@dataclass
class TrainerConfig:
    model_dir: str = "/tmp/dragonfly2_tpu_models"
    mlp: train_mlp.MLPTrainConfig = field(default_factory=train_mlp.MLPTrainConfig)
    gnn: train_gnn.GNNTrainConfig = field(default_factory=train_gnn.GNNTrainConfig)
    gnn_steps: int = 300
    gnn_steps_per_call: int = 10  # scan length per jitted call (loop yields between)
    min_pairs: int = 16        # skip training below this much signal
    min_probe_rows: int = 8
    # Rolling dataset pool: uploads accumulate (newest pairs kept up to the
    # cap) so schedulers on short upload cadences still reach training mass;
    # 0 = train strictly on each upload in isolation.
    pool_rows: int = 500_000
    # Host/edge aggregates can't be evicted row-wise (they're sums), so the
    # pool is ROTATED — swapped for a fresh accumulator — once host churn
    # pushes it past either cap. Bounds memory and per-train graph size on a
    # long-lived trainer in a cluster with ephemeral host ids; queued
    # sessions keep a reference to the pool they folded into, so a rotation
    # never yanks data from an in-flight train. 0 disables.
    pool_max_hosts: int = 65536
    pool_max_edges: int = 1_000_000
    # Sessions opened but never closed are dropped after this many seconds
    # (checked at every open/close); 0 disables eviction.
    session_ttl: float = 3600.0


async def _export(model: str, save: Callable[[], Any]) -> tuple[Any, float]:
    """Run one model's artifact save in a worker thread under the
    `trainer.export` span; returns (what `save` returned, its seconds) —
    the manifest's `evaluation.export_seconds`, read beside the span."""
    def run() -> tuple[Any, float]:
        t0 = time.perf_counter()
        with default_tracer().span("trainer.export", model=model):
            out = save()
        return out, round(time.perf_counter() - t0, 3)

    return await asyncio.to_thread(run)


class TrainerService:
    def __init__(self, config: TrainerConfig | None = None, *, manager: Any = None):
        """manager: RemoteManagerClient (or None to skip registry)."""
        self.cfg = config or TrainerConfig()
        self.manager = manager
        # platform / device_kind / device_count, read ONCE here: this is the
        # process that holds the accelerator, and every status reply and run
        # manifest carries it so a JAX-free caller can tell a TPU run from a
        # CPU one
        self.device = jaxenv.device_report()
        self._acc = datasetlib.DatasetAccumulator(max_pair_rows=self.cfg.pool_rows)
        # TrainSession.uploads of the CURRENT pool epoch — cleared on
        # rotation with the pool it describes
        self._pool_uploads: dict[str, str | None] = {}
        # uploads committed into the CURRENT pool epoch
        self._pool_commits = 0
        self._sessions: dict[str, TrainSession] = {}
        self._next = 0
        self._queue: collections.deque[TrainSession] = collections.deque()  # dflint: disable=DF034 depth is bounded by one pending close per scheduler (the drainer coalesces same-pool entries); a maxlen would silently DROP a committed training run from the far end
        self._drainer: asyncio.Task | None = None
        # while the drainer is inside a run: the (start, end) of every ingest
        # handler the loop ran, which the run's GNN call record marks its scan
        # calls by; None between runs
        self._ingest_in_run: list[tuple[float, float]] | None = None
        self.last_result: dict | None = None
        self.trains_started = 0
        self.trains_succeeded = 0
        self.sessions_evicted = 0
        self.pool_rotations = 0
        self.trains_coalesced = 0
        # per-run manifests, newest last (ISSUE 15): run id, dataset size,
        # per-model step count / final loss / bounded loss curve, wall,
        # artifact paths — the `train_history` RPC's backing store and what
        # `dfml train` prints. Deliberately NOT persisted: like the manager's
        # stats-frame rings, a restarted trainer rebuilds history by training.
        self.run_history: collections.deque[dict] = collections.deque(
            maxlen=RUN_HISTORY_CAP
        )

    # ---- RPC surface (adapter passes payload dicts straight through) ----

    async def train_open(self, p: dict) -> dict:
        t_open = time.perf_counter()
        self._evict_stale()
        self._next += 1
        token = f"sess-{self._next}-{int(time.time())}"
        hostname = p.get("hostname", "")
        span = default_tracer().span(  # dflint: disable=DF027 begun here, finished by train_close or eviction
            "trainer.ingest", scheduler=hostname
        ).begin()
        sess = self._sessions[token] = TrainSession(
            token,
            scheduler_hostname=hostname,
            scheduler_id=p.get("scheduler_id", 0),
            t_open=t_open,
            span=span,
            trace_ctx=span.context,
            gc=gc_watch().open(),
        )
        sess.t_idle = time.perf_counter()
        return {"token": token}

    async def train_chunk(self, p: dict) -> dict:
        t_start = time.perf_counter()
        sess = self._sessions.get(p["token"])
        if sess is None:
            raise KeyError(f"unknown train session {p['token']!r}")
        counts, data, kind = sess.ingest, p["data"], p["kind"]
        with default_tracer().span("trainer.ingest.chunk", parent=sess.trace_ctx, kind=kind) as sp:
            arr = unpack_records(data)
            t_decoded = time.perf_counter()
            if kind == "downloads":
                sess.acc.add_downloads(arr)
            elif kind == "probes":
                sess.acc.add_probes(arr)
            else:
                raise ValueError(f"unknown dataset kind {kind!r}")
            if sp.sampled:
                sp.set_attr("rows", len(arr))
                sp.set_attr("bytes", len(data))
        t_end = time.perf_counter()
        counts.chunks += 1
        counts.bytes += len(data)
        counts.rows += len(arr)
        counts.decode_s += t_decoded - t_start
        counts.fold_s += t_end - t_decoded
        counts.wait_s += t_start - sess.t_idle
        self._count_in_run(counts, t_start, t_end, chunks=1)
        sess.t_idle = t_end
        sess.last_activity = time.time()
        return {"rows": counts.rows}

    async def train_close(self, p: dict) -> dict:
        t_start = time.perf_counter()
        sess = self._sessions.pop(p["token"], None)
        if sess is None:
            raise KeyError(f"unknown train session {p['token']!r}")
        self._evict_stale()
        with default_tracer().span("trainer.ingest.merge", parent=sess.trace_ctx) as sp:
            self._commit(sess)
            if sp.sampled:
                sp.set_attr("hosts_added", sess.pool["hosts_added"])
                sp.set_attr("rotated", sess.pool["rotated"])
        t_end = time.perf_counter()
        counts = sess.ingest
        counts.merge_s += t_end - t_start
        counts.wait_s += t_start - sess.t_idle
        counts.open_to_close_s += t_end - sess.t_open
        self._count_in_run(counts, t_start, t_end, chunks=0)
        sess.span.finish()
        # never await the previous run here: queue the session and let the
        # drainer serialize training (one run at a time) off this RPC's back
        self._queue.append(sess)
        if self._drainer is None or self._drainer.done():
            self._drainer = asyncio.ensure_future(self._drain())
        return {"queued": True, "queue_depth": len(self._queue)}

    def _count_in_run(self, counts: IngestCounts, t_start: float, t_end: float, *, chunks: int) -> None:
        """A handler that ran while the drainer is inside a run: its seconds
        and chunks into the session's `in_run_s` / `chunks_in_run`, its
        (start, end) into the run's record of the loop's ingest."""
        if self._ingest_in_run is None:
            return
        self._ingest_in_run.append((t_start, t_end))
        counts.in_run_s += t_end - t_start
        counts.chunks_in_run += chunks

    def _commit(self, sess: TrainSession) -> None:
        named, hosts_before, commits = sess.acc.num_hosts, 0, 1
        name = sess.scheduler_hostname or f"scheduler-{sess.scheduler_id}"
        trace = sess.span.trace_id if sess.span.sampled else None
        sess.ingest.add_keys(sess.acc.keys)  # the session's folds
        if self.cfg.pool_rows > 0:
            hosts_before, keys_before = self._acc.num_hosts, replace(self._acc.keys)
            # commit the session's aggregates into the shared pool — the
            # ONLY point session data becomes visible to training, so an
            # upload that failed mid-stream (and will be retried in full)
            # contributed nothing; the queued train keeps its reference to
            # THIS pool even if a later close rotates in a fresh one
            self._acc.merge_from(sess.acc)
            sess.ingest.add_keys(self._acc.keys, keys_before)
            sess.acc = self._acc
            # federation attribution: a model trained on the pool carries
            # every scheduler that fed THIS pool epoch, not just the closer
            self._pool_uploads.pop(name, None)
            self._pool_uploads[name] = trace
            sess.uploads = dict(self._pool_uploads)
            self._pool_commits += 1
            commits = self._pool_commits
        else:
            sess.uploads = {name: trace}
        pool, epoch = sess.acc, self.pool_rotations
        rotated = self._maybe_rotate_pool()  # `pool` stays the one this session committed into
        sess.pool = {
            "epoch": epoch, "commits": commits, "hosts": pool.num_hosts, "edges": pool.num_edges,
            "hosts_added": pool.num_hosts - hosts_before, "hosts_stale": pool.num_hosts - named,
            "rotated": rotated,
        }

    async def status(self, p: Any = None) -> dict:
        running = self._drainer is not None and not self._drainer.done()
        return {
            **self.device,
            "training": running,
            "queue_depth": len(self._queue),
            "open_sessions": len(self._sessions),
            "pool_pairs": self._acc.pair_rows,
            "pool_hosts": self._acc.num_hosts,
            "pool_edges": self._acc.num_edges,
            "pool_rotations": self.pool_rotations,
            "trains_coalesced": self.trains_coalesced,
            "trains_started": self.trains_started,
            "trains_succeeded": self.trains_succeeded,
            "last_result": self.last_result,
        }

    async def train_history(self, p: dict | None = None) -> dict:
        """Per-run manifests, newest first (bounded at RUN_HISTORY_CAP).
        `limit` trims; `with_curves=False` drops the loss curves for a
        compact listing."""
        p = p or {}
        limit = int(p.get("limit", RUN_HISTORY_CAP))
        with_curves = bool(p.get("with_curves", True))
        runs = list(self.run_history)[-limit:][::-1]
        if not with_curves:
            runs = [
                {
                    **r,
                    "models": {
                        m: {k: v for k, v in info.items() if k != "curve"}
                        for m, info in (r.get("models") or {}).items()
                    },
                }
                for r in runs
            ]
        return {"runs": runs, "total": len(self.run_history)}

    async def wait_idle(self) -> None:
        while self._drainer is not None and not self._drainer.done():
            await self._drainer

    # ---- session lifecycle ----

    def _maybe_rotate_pool(self) -> bool:
        """Aggregates (host table, edge sums, node counters) only grow —
        swap in a fresh pool once host churn blows past the caps. Sessions
        already queued hold their own reference to the old pool. Returns
        whether it rotated."""
        cfg = self.cfg
        over_hosts = cfg.pool_max_hosts > 0 and self._acc.num_hosts > cfg.pool_max_hosts
        over_edges = cfg.pool_max_edges > 0 and self._acc.num_edges > cfg.pool_max_edges
        if over_hosts or over_edges:
            logger.warning(
                "rotating dataset pool (%d hosts, %d edges, %d pairs) — aggregate caps hit",
                self._acc.num_hosts, self._acc.num_edges, self._acc.pair_rows,
            )
            self._acc = datasetlib.DatasetAccumulator(max_pair_rows=cfg.pool_rows)
            self._pool_uploads = {}
            self._pool_commits = 0
            self.pool_rotations += 1
        return over_hosts or over_edges

    def _evict_stale(self) -> None:
        """Drop sessions with no traffic for session_ttl. Keyed on
        last_activity, not opened_at — an upload legitimately streaming
        chunks for longer than the TTL must not be yanked mid-stream."""
        ttl = self.cfg.session_ttl
        if ttl <= 0:
            return
        now = time.time()
        stale = [t for t, s in self._sessions.items() if now - s.last_activity > ttl]
        for token in stale:
            sess = self._sessions.pop(token)
            self.sessions_evicted += 1
            gc_watch().close(sess.gc)
            if sess.span.sampled:
                sess.span.set_attr("evicted", True)
            sess.span.finish()
            logger.warning(
                "evicting stale train session %s from %s (idle %.0fs, %d rows)",
                token, sess.scheduler_hostname, now - sess.last_activity, sess.ingest.rows,
            )

    # ---- training driver ----

    async def _drain(self) -> None:
        """Single background consumer: one training run at a time, in close
        order. train_close re-creates the task if it ever finds it done.

        Consecutive queued sessions that committed into the SAME pool are
        coalesced into one run (the pool already aggregates all of them —
        k closes landing during one slow train would otherwise trigger k
        near-identical back-to-back trains); the surviving session's
        scheduler identity is the one the registry rows carry."""
        while self._queue:
            sess = self._queue.popleft()
            while self._queue and self._queue[0].acc is sess.acc:
                nxt = self._queue.popleft()
                # the run pays for every upload it coalesced; the collector's
                # window runs from the first one's open
                nxt.ingest.add(sess.ingest)
                gc_watch().close(nxt.gc)
                nxt.gc = sess.gc
                sess = nxt
                self.trains_coalesced += 1
            self.trains_started += 1
            await self._train(sess)

    async def _train(self, sess: TrainSession) -> None:
        # parent = the session's `trainer.ingest`: the announcer's upload
        # continues through ingest into the train and model publish, even
        # though the RPC returned long ago
        t_run = time.perf_counter()
        started_at = time.time()
        self._ingest_in_run = []
        try:
            with default_tracer().span(
                "trainer.train_run", parent=sess.trace_ctx,
                scheduler=sess.scheduler_hostname,
            ) as sp:
                result = await self._run_training(sess)
                self.last_result = result
                self.trains_succeeded += 1
                if sp.sampled:
                    sp.set_attr("version", result.get("version", ""))
                    sp.set_attr("num_pairs", result.get("num_pairs", 0))
                    # the other uploads this model holds, each the root of a
                    # trace of its own that ended at its close
                    others = [t for t in sess.uploads.values() if t and t != sess.span.trace_id]
                    if others:
                        sp.set_attr("upload_traces", ",".join(others))
                if self.manager is not None:
                    with default_tracer().span("trainer.publish"):
                        await self._register_models(sess, result)
            self._note_run(sess, result, started_at, time.perf_counter() - t_run)
        except Exception as e:
            # the service stays up, but the failure is the run's result: what
            # raised goes into last_result and the manifest, not only the log
            logger.exception("training run failed")
            error = f"{type(e).__name__}: {e}"[:500]
            self.last_result = {"error": error}
            # same manifest shape as success/skip — ONE append path, so the
            # schema can never drift between outcomes
            self._note_run(
                sess, {"version": f"run-{self.trains_started}", "error": error},
                started_at, time.perf_counter() - t_run, status="error",
            )
        finally:
            self._ingest_in_run = None

    def _note_run(
        self,
        sess: TrainSession,
        result: dict,
        started_at: float,
        wall: float,
        *,
        status: str | None = None,
    ) -> None:
        """Append the run manifest + move the run-level families. A run that
        built a dataset but trained nothing (below min_pairs) is 'skipped' —
        visible in history, never conflated with a trained run; a failed run
        passes status='error' through the SAME shape."""
        models = {
            m: {
                "artifact": info.get("artifact"),
                "digest": (info.get("digest") or "")[:16],
                "evaluation": {
                    k: v for k, v in (info.get("evaluation") or {}).items()
                    if k != "contributors"
                },
                **(info.get("telemetry") or {}),
                "native_export_error": info.get("native_export_error"),
            }
            for m in ("mlp", "gnn")
            if (info := result.get(m))
        }
        if status is None:
            status = "ok" if models else "skipped"
        train_metrics.TRAIN_RUNS_TOTAL.inc(result=status)
        final = None
        if "gnn" in models:
            final = models["gnn"].get("final_loss")
        elif "mlp" in models:
            final = models["mlp"].get("final_loss")
        if final is not None and np.isfinite(final):
            train_metrics.TRAIN_LAST_RUN_LOSS.set(float(final))
        self.run_history.append({
            "run_id": result.get("version", f"run-{self.trains_started}"),
            "started_at": round(started_at, 3),
            "wall_s": round(wall, 3),
            "status": status,
            "error": result.get("error"),
            **self.device,
            "device_peak_bytes": result.get("device_peak_bytes"),
            "scheduler": sess.scheduler_hostname,
            "dataset": {
                "pairs": result.get("num_pairs", 0),
                "nodes": result.get("num_nodes", 0),
                "build_seconds": result.get("build_seconds", 0.0),
            },
            "ingest": {
                **sess.ingest.report(),
                "schedulers": list(sess.uploads), "traces": list(sess.uploads.values()),
            },
            "pool": sess.pool,
            "gc": gc_watch().close(sess.gc),
            "models": models,
        })

    async def _run_training(self, sess: TrainSession) -> dict:
        acc = sess.acc  # the pool it merged into at close; rotation-safe
        t_build = time.perf_counter()
        # freeze() is a cheap loop-side snapshot; the O(nodes+edges+pairs)
        # materialization runs on a worker thread while chunks keep folding
        # and other sessions' closes merge into the same pool: the run reads
        # the snapshot alone, never the live pool
        with default_tracer().span("trainer.dataset_build"):
            frozen = acc.freeze()
            probe_rows = acc.probe_rows
            ds = await asyncio.to_thread(frozen.finalize)
        build_seconds = time.perf_counter() - t_build
        # monotonic suffix: the drainer starts queued runs back-to-back, so
        # two runs inside the same wall-clock second are the normal case and
        # a bare timestamp would collide artifact dirs + registry versions
        version = f"v{int(time.time())}-{self.trains_started}"
        out: dict[str, Any] = {
            "version": version,
            "num_pairs": ds.num_pairs,
            "num_nodes": ds.num_nodes,
            "build_seconds": round(build_seconds, 4),
        }

        if ds.num_pairs >= self.cfg.min_pairs:
            tr, ev = datasetlib.split_pairs(ds.pairs)
            mlp_tel = train_metrics.TrainRunTelemetry(
                "mlp", batch_size=min(self.cfg.mlp.batch_size, len(tr.child))
            )
            t0 = time.perf_counter()
            with default_tracer().span("trainer.train_mlp", pairs=ds.num_pairs):
                params, evaluation = await asyncio.to_thread(
                    train_mlp.train, self.cfg.mlp, tr, eval_pairs=ev,
                    log=logger.info, telemetry=mlp_tel,
                )
            evaluation["train_seconds"] = round(time.perf_counter() - t0, 2)
            def _save_mlp() -> tuple[Path, str]:
                path = artifacts.save_artifact(
                    Path(self.cfg.model_dir) / f"mlp-{version}",
                    model_type="mlp", version=version, params=params,
                    config={"hidden": list(self.cfg.mlp.hidden)},
                )
                if ds.feature_sketch is not None:
                    # the training-reference feature sketch rides the
                    # artifact — written BEFORE the digest, so it is
                    # integrity-covered like every other file (ISSUE 15)
                    artifacts.save_sketch(path, ds.feature_sketch)
                return path, artifacts.artifact_digest(path)

            (path, digest), evaluation["export_seconds"] = await _export("mlp", _save_mlp)
            out["mlp"] = {
                "artifact": str(path), "digest": digest,
                "evaluation": evaluation, "telemetry": mlp_tel.summary(),
            }

        if ds.num_pairs >= self.cfg.min_pairs and probe_rows >= self.cfg.min_probe_rows:
            cfg = self.cfg.gnn
            gnn_tel = train_metrics.TrainRunTelemetry(
                "gnn", batch_size=cfg.batch_size
            )
            gnn_tel.loop_ingest = self._ingest_in_run
            t0 = time.perf_counter()
            with default_tracer().span("trainer.train_gnn", nodes=ds.num_nodes):
                state, losses = await train_gnn.train_async(
                    cfg, ds.graph, ds.pairs,
                    steps=self.cfg.gnn_steps,
                    steps_per_call=self.cfg.gnn_steps_per_call,
                    log=logger.info,
                    telemetry=gnn_tel,
                )
            train_seconds = time.perf_counter() - t0
            evaluation = {
                "final_loss": losses[-1] if losses else float("nan"),
                "steps": len(losses),
                "train_seconds": round(train_seconds, 2),
            }

            def _save_gnn() -> tuple[Path, str, str | None]:
                path = artifacts.save_artifact(
                    Path(self.cfg.model_dir) / f"gnn-{version}",
                    model_type="gnn", version=version, params=state.params,
                    config={
                        "hidden": cfg.hidden, "embed_dim": cfg.embed_dim,
                        "num_layers": cfg.num_layers,
                    },
                )
                artifacts.save_graph(path, ds.graph, ds.host_index)
                if ds.feature_sketch is not None:
                    # training-reference sketch, digest-covered (ISSUE 15):
                    # the serving scheduler compares live scoring features
                    # against THIS distribution (feature drift)
                    artifacts.save_sketch(path, ds.feature_sketch)
                native_error = None
                try:
                    with default_tracer().span("trainer.export.native"):
                        artifacts.save_native(
                            path, train_gnn.make_model(cfg), state.params, ds.graph
                        )
                except Exception as e:
                    # the flax artifact still serves, so the run is not failed
                    # — but the missing scorer.dfsc is named in the result
                    logger.exception("native scorer export failed; flax artifact only")
                    native_error = f"{type(e).__name__}: {e}"[:500]
                # digest LAST: it must cover every file the loader will read
                return path, artifacts.artifact_digest(path), native_error

            (path, digest, native_error), evaluation["export_seconds"] = await _export(
                "gnn", _save_gnn
            )
            out["gnn"] = {
                "artifact": str(path), "digest": digest,
                "evaluation": evaluation, "telemetry": gnn_tel.summary(),
                "native_export_error": native_error,
            }
        out["device_peak_bytes"] = jaxenv.peak_device_bytes()
        return out

    async def _register_models(self, sess: TrainSession, result: dict) -> None:
        """Finish the reference's CreateModel stub: version rows + activation.

        Models register CLUSTER-WIDE (scheduler_id 0): ONE trainer ingests
        telemetry from every federation member and each member's model watch
        falls back to the scheduler_id-0 row, so a single activation fans the
        version out to all of them. The evaluation dict carries the
        contributing schedulers — the attribution proof the cross-scheduler
        cluster test pins."""
        contributors = sorted(sess.uploads)
        for mtype in ("mlp", "gnn"):
            info = result.get(mtype)
            if not info:
                continue
            try:
                # publish_model routes through the manager's rollout policy:
                # gated types land as CANDIDATE and earn activation through
                # the shadow window; ungated types activate immediately (the
                # pre-ISSUE-11 behavior, and the default with no policy).
                # The artifact digest rides the row so schedulers verify
                # integrity before attach.
                row = await self.manager.publish_model(
                    mtype, result["version"],
                    scheduler_id=0,
                    evaluation={**info["evaluation"], "contributors": contributors},
                    artifact_path=info["artifact"],
                    artifact_digest=info.get("digest", ""),
                )
                logger.info(
                    "model %s %s registered (state=%s)",
                    mtype, result["version"], row.get("state"),
                )
            except Exception:
                logger.exception("model registry update failed for %s", mtype)
