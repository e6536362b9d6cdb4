"""Host microbenchmarks around one headline: scheduler parent-scoring throughput.
(The training step is measured by `benchmarks/run.py`; PERF.md and the ledger
hold its numbers.)

Runs on the accelerator JAX finds and names it in the result (`platform`,
`device_kind`, `device_count`). Prints exactly ONE JSON line:
  metric       scheduler_scoring_calls_per_sec — batched scoring rounds/sec,
               each round scoring 40 candidate parents (the reference's
               filter-40→top-4 shape, scheduler/config/constants.go:36-40)
  vs_baseline  against the 10k calls/s north-star target (BASELINE.md; the
               reference's intended path was a TF-Serving RPC per round and
               was never implemented)
  extra        scoring p50 latency, the MLP's training rate on the host, and
               the host sections (control plane, observability, round loop,
               federation, ...).

This file is both supervisor and worker. The supervisor (default entry)
never imports jax — the chip belongs to one process at a time — and probes
the backend in a throwaway SUBPROCESS with a hard wall-clock timeout (a TPU
attach failure can be a native-code hang no in-process signal interrupts),
then runs the worker. It always prints the JSON line, and it never changes
device on its own: no chip means exit 1, and so does a device section that
raised. A CPU run happens only when the CALLER sets DF_BENCH_FORCE_CPU=1,
and its numbers are named `"platform": "cpu"`, never device metrics.
"""

from __future__ import annotations

import contextlib
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

_SECTION_TIMEOUT_S = int(os.environ.get("DF_BENCH_SECTION_TIMEOUT", "420"))
_PROBE_TIMEOUT_S = int(os.environ.get("DF_BENCH_PROBE_TIMEOUT", "240"))
# The worker must outlive its own worst case: fifteen SIGALRM-bounded
# sections plus backend init/compile margin — otherwise the supervisor would
# kill it and discard sections that did complete.
_WORKER_TIMEOUT_S = max(
    int(os.environ.get("DF_BENCH_WORKER_TIMEOUT", "1500")),
    15 * _SECTION_TIMEOUT_S + _PROBE_TIMEOUT_S + 120,
)


def _payload(value: float, extra: dict) -> str:
    """The single-JSON-line contract, in one place for all three emitters."""
    return json.dumps(
        {
            "metric": "scheduler_scoring_calls_per_sec",
            "value": round(value, 1),
            "unit": "calls/s (40 candidates/call)",
            "vs_baseline": round(value / 10_000, 3),
            "extra": extra,
        }
    )

_PROBE_SRC = """
import jax
if __import__("os").environ.get("DF_BENCH_FORCE_CPU"):
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
d = jax.devices()
(jnp.ones((8, 8), jnp.float32) @ jnp.ones((8, 8), jnp.float32)).block_until_ready()
print("PROBE_OK", d[0].platform, flush=True)
"""


# sections whose failure means the device path is broken: any of them
# raising makes the worker (and so the supervisor) exit non-zero
DEVICE_SECTIONS = ("jax_scoring", "mlp_train")


class _SectionTimeout(Exception):
    pass


@contextlib.contextmanager
def _deadline(seconds: int):
    """SIGALRM watchdog for worker sections. Catches Python-visible stalls;
    native hangs are covered by the supervisor's subprocess timeout."""

    def _raise(signum, frame):
        raise _SectionTimeout(f"section exceeded {seconds}s")

    old = signal.signal(signal.SIGALRM, _raise)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _probe_backend() -> str | None:
    """Touch the device in a throwaway subprocess (it inherits this
    process's environment, DF_BENCH_FORCE_CPU included). Returns the platform
    name or None if init failed/hung within the timeout."""
    try:
        out = subprocess.run(
            [sys.executable, "-c", _PROBE_SRC],
            timeout=_PROBE_TIMEOUT_S,
            capture_output=True,
            text=True,
        )
    except subprocess.TimeoutExpired:
        print(f"bench: backend probe hung >{_PROBE_TIMEOUT_S}s", file=sys.stderr, flush=True)
        return None
    for line in out.stdout.splitlines():
        if line.startswith("PROBE_OK"):
            return line.split()[1]
    tail = (out.stderr or "").strip().splitlines()[-3:]
    print("bench: backend probe failed: " + " | ".join(tail), file=sys.stderr, flush=True)
    return None


def _supervise() -> None:
    """Probe the backend, run the worker, guarantee one JSON line. Exits with
    the worker's code; 1 when no backend answered or the worker gave no line.
    CPU only when the caller asked for it (DF_BENCH_FORCE_CPU)."""
    force_cpu = bool(os.environ.get("DF_BENCH_FORCE_CPU"))
    platform = _probe_backend()
    if platform is None and not force_cpu:
        time.sleep(15.0)  # the chip may be transiently held; one backoff retry
        platform = _probe_backend()
    if platform is None or (platform == "cpu" and not force_cpu):
        # JAX carries on on the CPU when it finds no accelerator; this
        # benchmark does not
        why = "no JAX backend reachable" if platform is None else (
            "JAX found no accelerator (platform cpu); set DF_BENCH_FORCE_CPU=1 "
            "for a CPU run of the host-side sections"
        )
        print(_payload(0.0, {"platform": platform, "errors": {"init": why}}), flush=True)
        sys.exit(1)

    env = dict(os.environ, DF_BENCH_STAGE="worker")
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            timeout=_WORKER_TIMEOUT_S,
            capture_output=True,
            text=True,
        )
        sys.stderr.write(out.stderr or "")
        worker_err = f"worker rc={out.returncode}"
        for line in (out.stdout or "").splitlines():
            if line.startswith("{"):
                print(line, flush=True)
                sys.exit(out.returncode)
    except subprocess.TimeoutExpired as e:
        sys.stderr.write((e.stderr or b"").decode("utf-8", "replace") if isinstance(e.stderr, bytes) else (e.stderr or ""))
        worker_err = f"worker hung >{_WORKER_TIMEOUT_S}s"
    print(_payload(0.0, {"platform": platform, "errors": {"worker": worker_err}}), flush=True)
    sys.exit(1)


def bench_scoring(rounds: int = 2000, candidates: int = 40) -> tuple[float, float, float]:
    """The jax fallback scorer: single-round rate + p50, and the multi-round
    amortized rate (GNNScorer.score_rounds — the shape the micro-batcher
    serves when g++ is absent). Returns (single rps, single p50 ms, multi
    rps)."""
    from dragonfly2_tpu.models.scorer import GNNScorer
    from dragonfly2_tpu.trainer import synthetic, train_gnn

    cluster = synthetic.make_cluster(num_nodes=1024, num_neighbors=16, num_pairs=4096, seed=7)
    cfg = train_gnn.GNNTrainConfig()
    model = train_gnn.make_model(cfg)
    state = train_gnn.init_state(cfg, cluster.graph, rng_seed=7)
    scorer = GNNScorer(model, state.params)
    scorer.refresh(cluster.graph)

    rng = np.random.default_rng(7)
    child = rng.integers(0, 1024, size=candidates).astype(np.int32)
    parent = rng.integers(0, 1024, size=candidates).astype(np.int32)
    feats = cluster.pairs.feats[:candidates]

    for _ in range(20):  # warmup + compile
        scorer.score(feats, child=child, parent=parent)

    lat = np.empty(rounds)
    t0 = time.perf_counter()
    for i in range(rounds):
        s = time.perf_counter()
        scorer.score(feats, child=child, parent=parent)
        lat[i] = time.perf_counter() - s
    total = time.perf_counter() - t0
    single_rps = rounds / total
    single_p50 = float(np.percentile(lat, 50) * 1000)

    M = _ROUNDS_PER_FFI_CALL
    mc = np.tile(child, (M, 1))
    mp = np.tile(parent, (M, 1))
    mf = np.tile(feats, (M, 1, 1))
    for _ in range(10):
        scorer.score_rounds(mf, child=mc, parent=mp)
    calls = max(50, rounds // (4 * M))
    t0 = time.perf_counter()
    for _ in range(calls):
        scorer.score_rounds(mf, child=mc, parent=mp)
    multi_rps = calls * M / (time.perf_counter() - t0)
    return single_rps, single_p50, multi_rps


_ROUNDS_PER_FFI_CALL = 8  # M queued rounds per amortized native call


def bench_native_scoring(
    rounds: int = 5000, candidates: int = 40, rounds_per_call: int = _ROUNDS_PER_FFI_CALL
) -> tuple[float, float, float, float]:
    """The production serving path (north-star config 5): C++ scorer with
    cached embeddings, no JAX on the hot path. Measures BOTH entry points:
    the single-round call (p50 latency) and the multi-round amortized call
    (df_scorer_score_rounds, `rounds_per_call` queued rounds per FFI hop —
    the 10k-calls/s path). Returns (amortized rounds/s, single-round p50 ms,
    single-round rounds/s, multi-round call p50 ms); all-None when no C++
    toolchain is available (skipped ≠ measured-zero, VERDICT #8)."""
    import shutil

    if shutil.which("g++") is None:
        print("bench: native_scoring skipped (no g++ toolchain)", file=sys.stderr, flush=True)
        return None, None, None, None
    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.native import NativeScorer, export_scorer_artifact
    from dragonfly2_tpu.trainer import synthetic, train_gnn

    cluster = synthetic.make_cluster(num_nodes=1024, num_neighbors=16, num_pairs=4096, seed=7)
    cfg = train_gnn.GNNTrainConfig()
    model = train_gnn.make_model(cfg)
    state = train_gnn.init_state(cfg, cluster.graph, rng_seed=7)
    g = jax.tree.map(jnp.asarray, cluster.graph)
    z = np.asarray(
        jax.jit(lambda p, gg: model.apply(p, gg, method=model.embed))(state.params, g)
    )
    import tempfile
    from pathlib import Path

    with tempfile.TemporaryDirectory() as td:
        scorer = NativeScorer(export_scorer_artifact(state.params, z, Path(td) / "s.dfsc"))
        rng = np.random.default_rng(7)
        child = rng.integers(0, 1024, size=candidates).astype(np.int32)
        parent = rng.integers(0, 1024, size=candidates).astype(np.int32)
        feats = cluster.pairs.feats[:candidates].astype(np.float32)
        for _ in range(50):
            scorer.score(feats, child=child, parent=parent)
        # best-of-3 sustained windows (rate) + latency percentiles pooled
        # over ALL windows' samples: the single-window version let unrelated
        # host load (the bench box is one shared core) shave ~10% off the
        # recorded rate run-to-run
        lat = np.empty(3 * rounds)
        single_rps = 0.0
        for w in range(3):
            t0 = time.perf_counter()
            for i in range(rounds):
                s = time.perf_counter()
                scorer.score(feats, child=child, parent=parent)
                lat[w * rounds + i] = time.perf_counter() - s
            single_rps = max(single_rps, rounds / (time.perf_counter() - t0))
        single_p50 = float(np.percentile(lat, 50) * 1000)

        # amortized path: M queued rounds per FFI call
        M = rounds_per_call
        mc = np.tile(child, (M, 1))
        mp = np.tile(parent, (M, 1))
        mf = np.tile(feats, (M, 1, 1))
        for _ in range(20):
            scorer.score_rounds(mf, child=mc, parent=mp)
        calls = max(200, rounds // M)
        mlat = np.empty(3 * calls)
        multi_rps = 0.0
        for w in range(3):
            t0 = time.perf_counter()
            for i in range(calls):
                s = time.perf_counter()
                scorer.score_rounds(mf, child=mc, parent=mp)
                mlat[w * calls + i] = time.perf_counter() - s
            multi_rps = max(multi_rps, calls * M / (time.perf_counter() - t0))
        multi_call_p50 = float(np.percentile(mlat, 50) * 1000)
        scorer.close()
    return multi_rps, single_p50, single_rps, multi_call_p50


def bench_mlp_train(steps: int = 200) -> tuple[float, float]:
    """North-star config 1: the MLP bandwidth predictor over download-record
    features, HOST CPU (the config's own hardware — it runs on the scheduler
    host, no accelerator). Returns (steps/s, final train mse)."""
    import jax

    from dragonfly2_tpu.trainer import synthetic, train_mlp

    cluster = synthetic.make_cluster(num_nodes=512, num_neighbors=16, num_pairs=32768, seed=7)
    cfg = train_mlp.MLPTrainConfig(steps=steps, batch_size=2048)
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        # Difference of two runs cancels what both pay alike (the index draw,
        # the pairs' upload, init, the last pull): steps/s over the extra
        # steps of the long run is the steady-state rate. Equal only if
        # neither timed run compiles: a run is scan calls of at most
        # `STEPS_PER_CALL` steps, a program a chunk length, so one untimed run
        # meets both lengths first (the whole chunks' and the short run's).
        short_steps = 3
        short_cfg = train_mlp.MLPTrainConfig(steps=short_steps, batch_size=2048)
        warm_cfg = train_mlp.MLPTrainConfig(steps=train_mlp.STEPS_PER_CALL + short_steps, batch_size=2048)
        train_mlp.train(warm_cfg, cluster.pairs, seed=7)
        t0 = time.perf_counter()
        train_mlp.train(short_cfg, cluster.pairs, seed=7)
        t_short = time.perf_counter() - t0
        t0 = time.perf_counter()
        _params, ev = train_mlp.train(cfg, cluster.pairs, seed=7)
        t_long = time.perf_counter() - t0
    if t_long <= t_short:
        raise RuntimeError(
            f"mlp_train: long run {t_long:.3f}s not slower than short run "
            f"{t_short:.3f}s — set-up cost did not cancel, no rate can be read"
        )
    return (steps - short_steps) / (t_long - t_short), ev.get("train_mse", -1.0)


def bench_federation(
    peers: int = 48,
    tasks: int = 16,
    pieces: int = 4,
    duration: float = 2.0,
    reps: int = 3,
    probe_edges: int = 32,
) -> dict:
    """Scheduler federation (ISSUE 10): two REAL scheduler subprocesses
    gossiping over the wire, measured four ways:

      swarm_rps_1sched / _2sched   aggregate dfstress-swarm rounds/s against
                                   one member vs the 2-scheduler ring,
                                   interleaved same-run median-of-N (on this
                                   2-core box both schedulers share the
                                   cores, so 2v1 reads contention, not
                                   scale-out — the share keys prove the ring
                                   splits load evenly either way)
      sync_convergence_ms          probes reported to member A visible in
                                   member B's merged view (one gossip hop)
      sync_payload_edges_*         the watermark counter-assert: a cold pull
                                   ships every edge, the steady-state pull
                                   ships ZERO, one new probe ships exactly
                                   one — payload is O(changed edges), never
                                   O(all edges)
      reshard_moved_frac_*         fraction of 10k task keys whose ring
                                   owner changes on member join/leave (the
                                   consistent-hash churn bound; ~1/N moves)

    Null-shaped on failure per the VERDICT #8 hygiene rule."""
    import asyncio

    from dragonfly2_tpu.cli.dfstress import run_swarm
    from dragonfly2_tpu.rpc.balancer import ConsistentHashRing

    out: dict = {
        "swarm_rps_1sched": None,
        "swarm_rps_2sched": None,
        "swarm_speedup_2v1": None,
        "per_scheduler_round_share": None,
        "swarm_errors": None,
        "sync_convergence_ms": None,
        "sync_payload_edges_initial": None,
        "sync_payload_edges_steady": None,
        "sync_payload_edges_after_one_probe": None,
        "reshard_moved_frac_join_1to2": None,
        "reshard_moved_frac_leave_3to2": None,
        "swarm_peers": peers,
        "swarm_leg_duration_s": duration,
    }

    # ---- ring re-shard accounting: pure in-process, no wire needed ----
    # join (1→2) and leave (3→2) are measured against DIFFERENT membership
    # pairs — a 2→1 "leave" number would just re-report the join comparison
    # with operands swapped (same two ownership maps, identical count)
    keys = [f"task-{i:05d}" for i in range(10_000)]
    one = ConsistentHashRing(["10.0.0.1:9000"])
    two = ConsistentHashRing(["10.0.0.1:9000", "10.0.0.2:9000"])
    own1 = {k: one.pick(k) for k in keys}
    own2 = {k: two.pick(k) for k in keys}
    out["reshard_moved_frac_join_1to2"] = round(
        sum(own1[k] != own2[k] for k in keys) / len(keys), 4
    )
    three = ConsistentHashRing(
        ["10.0.0.1:9000", "10.0.0.2:9000", "10.0.0.3:9000"]
    )
    own3 = {k: three.pick(k) for k in keys}
    out["reshard_moved_frac_leave_3to2"] = round(
        sum(own3[k] != own2[k] for k in keys) / len(keys), 4
    )

    # ---- two real schedulers, chained federation, short gossip tick ----
    env = dict(os.environ, PYTHONPATH=os.path.dirname(os.path.abspath(__file__)))
    procs: list[subprocess.Popen] = []

    def boot(extra: list[str]) -> str:
        p = subprocess.Popen(
            [sys.executable, "-m", "dragonfly2_tpu.scheduler.server",
             "--port", "0", "--federation-interval", "0.3", *extra],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True, env=env,
        )
        procs.append(p)
        line = p.stdout.readline()
        assert line.startswith("SCHEDULER_READY"), line
        return line.split()[1]

    try:
        addr_a = boot([])
        addr_b = boot(["--federation-peers", addr_a])

        async def drive() -> None:
            from dragonfly2_tpu.rpc.scheduler import RemoteSchedulerClient

            ca = RemoteSchedulerClient(addr_a, retries=0)
            cb = RemoteSchedulerClient(addr_b, retries=0)
            try:
                # convergence: a burst of probes into A, stopwatch until B's
                # merged view holds them (includes up to one 0.3s gossip tick)
                before = (await cb.federation_state())["remote_edges"]
                results = [
                    {"dst_host_id": f"conv-dst-{i}", "rtt_ms": 1.0 + i, "success": True}
                    for i in range(probe_edges)
                ]
                t0 = time.monotonic()
                await ca.sync_probes("conv-src", results)
                while True:
                    st = await cb.federation_state()
                    if st["remote_edges"] >= before + probe_edges:
                        break
                    if time.monotonic() - t0 > 30:
                        raise TimeoutError(f"federation never converged: {st}")
                    await asyncio.sleep(0.02)
                out["sync_convergence_ms"] = round((time.monotonic() - t0) * 1000, 1)

                # watermark counter-assert via a direct gossip exchange
                cold = await ca.federation_sync("bench-probe")
                out["sync_payload_edges_initial"] = len(cold["edges"])
                steady = await ca.federation_sync(
                    "bench-probe", topo_since=cold["topo_watermark"],
                    bw_since=cold["bw_watermark"],
                )
                out["sync_payload_edges_steady"] = len(steady["edges"]) + len(
                    steady["bandwidth"]
                )
                await ca.sync_probes(
                    "conv-src",
                    [{"dst_host_id": "conv-dst-0", "rtt_ms": 9.0, "success": True}],
                )
                after_one = await ca.federation_sync(
                    "bench-probe", topo_since=steady["topo_watermark"],
                    bw_since=steady["bw_watermark"],
                )
                out["sync_payload_edges_after_one_probe"] = len(after_one["edges"])
            finally:
                await ca.close()
                await cb.close()

        asyncio.run(drive())

        # interleaved 1-vs-2 scheduler swarm legs (same process pair, same
        # box, alternating so slow drift hits both legs equally)
        rates1, rates2, errors = [], [], 0
        share = None
        for _rep in range(reps):
            r1 = asyncio.run(
                run_swarm([addr_a], peers=peers, tasks=tasks, pieces=pieces,
                          duration=duration)
            )
            r2 = asyncio.run(
                run_swarm([addr_a, addr_b], peers=peers, tasks=tasks,
                          pieces=pieces, duration=duration)
            )
            rates1.append(r1["value"])
            rates2.append(r2["value"])
            errors += r1["extra"]["errors"] + r2["extra"]["errors"]
            share = r2["extra"]["per_scheduler_round_share"]
        out["swarm_rps_1sched"] = float(np.median(rates1))
        out["swarm_rps_2sched"] = float(np.median(rates2))
        out["swarm_speedup_2v1"] = round(
            out["swarm_rps_2sched"] / max(out["swarm_rps_1sched"], 1e-9), 3
        )
        out["per_scheduler_round_share"] = share
        out["swarm_errors"] = errors
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    return out


def bench_evaluator_serving() -> dict:
    """End-to-end serving SLO (VERDICT r4 Next #6; sharded in ISSUE 7):
    rounds/s + p50/p99 through the LIVE evaluator stack with the
    thread-scaling A/B — the dispatcher at workers=1 vs workers=2 plus the
    r05 microbatch shape, interleaved same-run median-of-3 inside
    run_scoring_stress (2-core box discipline). The headline is the
    best-measured serving config, named in evaluator_best_config (this
    2-core box typically can't feed workers=2 — see README "Concurrent
    scheduling")."""
    import shutil

    if shutil.which("g++") is None:
        return {}
    import asyncio

    from dragonfly2_tpu.cli.dfstress import run_scoring_stress

    ns = type("NS", (), {})()
    ns.rounds = 20000
    ns.concurrency = 8
    ns.candidates = 40
    ns.hosts = 256
    result = asyncio.run(run_scoring_stress(ns))
    ex = result["extra"]
    return {
        "evaluator_rounds_per_sec": result["value"],
        "evaluator_best_config": ex["eval_best_config"],
        "evaluator_p50_ms": ex["eval_p50_ms"],
        "evaluator_p99_ms": ex["eval_p99_ms"],
        # thread-scaling A/B (ISSUE 7 acceptance: workers2 >= 1.5x workers1
        # in this same interleaved run; the microbatch leg is the r05
        # serving shape for continuity)
        "evaluator_rounds_per_sec_microbatch": ex["rounds_per_sec_microbatch"],
        "evaluator_rounds_per_sec_workers1": ex["rounds_per_sec_workers1"],
        "evaluator_rounds_per_sec_workers2": ex["rounds_per_sec_workers2"],
        "evaluator_thread_scaling_speedup": ex["thread_scaling_speedup"],
        "full_round_rps": ex["full_round_rps"],
        "full_round_best_config": ex["full_round_best_config"],
        "full_round_rps_serial": ex["full_round_rps_serial"],
        "full_round_rps_dispatcher": ex["full_round_rps_dispatcher"],
        "full_round_p99_ms": ex["full_round_p99_ms"],
        # measured single-core serving ceiling: CPU cost of feature assembly
        # + the amortized native GEMMs — what bounds the end-to-end number
        # PER CORE independent of the asyncio stack; the fraction divides by
        # the cores the dispatcher used (min(workers, cpus)), so it stays
        # honest now that serving is multi-core
        "evaluator_prepare_us_per_round": ex["prepare_us_per_round"],
        "evaluator_ffi_us_per_round": ex["ffi_us_per_round_amortized"],
        "evaluator_single_core_ceiling_rps": ex["single_core_ceiling_rps"],
        "evaluator_ceiling_fraction": ex["ceiling_fraction_achieved"],
        "evaluator_ceiling_fraction_single_core": ex["ceiling_fraction_single_core"],
        "evaluator_host_cpu_count": ex["host_cpu_count"],
        "evaluator_host_cpu_count_os": ex["host_cpu_count_os"],
    }


def bench_checkpoint_fanout(
    total_mb: int = 128, files: int = 4, repeats: int = 3
) -> tuple[float, float]:
    """North-star config 4 shape at bench scale: a multi-file checkpoint
    published by one peer and fetched by fresh peers THROUGH the P2P piece
    engine (localhost). Returns (median aggregate MB/s across `repeats`
    fresh-peer fetches, raw buffered-disk-write MB/s on the default tmpdir).

    The piece stores live on tmpfs when /dev/shm has room: the metric is the
    ENGINE's distribution path (protocol, scheduling, hashing, copies), and a
    TPU-VM host staging a checkpoint streams through page cache at RAM speed
    anyway — while this container's disk throttling swings 8→4000 MB/s run to
    run, which would make the number meaningless. The separately-measured
    disk baseline says what a disk-backed store could sustain end-to-end."""
    import asyncio
    import shutil
    import tempfile
    from pathlib import Path

    from dragonfly2_tpu.daemon.engine import InProcessSchedulerClient, PeerEngine
    from dragonfly2_tpu.scheduler.service import SchedulerService
    from dragonfly2_tpu.tpuvm.checkpoint import fetch_checkpoint, publish_checkpoint

    async def run(td: str) -> tuple[float, float]:
        ckpt = Path(td) / "ckpt"
        ckpt.mkdir()
        per_file = total_mb * (1 << 20) // files
        for i in range(files):
            (ckpt / f"shard-{i}.safetensors").write_bytes(os.urandom(per_file))

        # disk baseline on the DEFAULT tmpdir (not the tmpfs store): buffered
        # piece-sized writes, no fsync — exactly the store's write pattern
        chunk = os.urandom(16 << 20)
        disk_probe = Path(tempfile.gettempdir()) / f"df-bench-disk-{os.getpid()}"
        t0 = time.perf_counter()
        with open(disk_probe, "wb") as f:
            written = 0
            while written < total_mb * (1 << 20):
                f.write(chunk)
                written += len(chunk)
        disk_mbps = total_mb / (time.perf_counter() - t0)
        os.unlink(disk_probe)

        rates = []
        for i in range(repeats):
            # fresh scheduler + publisher per repeat: a stopped fetcher from a
            # previous repeat would otherwise linger as a registered parent,
            # and the dispatcher's dead-parent retries would time the
            # RECOVERY path instead of the transfer (publisher re-announce is
            # a re-import of already-stored tasks — hash only, untimed)
            svc = SchedulerService()
            sched = InProcessSchedulerClient(svc)
            a = PeerEngine(
                storage_root=Path(td) / "a", scheduler=sched, hostname="bench-a"
            )
            await a.start()
            b = PeerEngine(
                storage_root=Path(td) / f"b{i}", scheduler=sched,
                hostname=f"bench-b{i}",
            )
            await b.start()
            try:
                manifest = await publish_checkpoint(a, ckpt, name="bench")
                t0 = time.perf_counter()
                await fetch_checkpoint(
                    b, manifest, Path(td) / f"restored{i}", concurrency=files
                )
                elapsed = time.perf_counter() - t0
                rates.append(manifest.total_bytes / elapsed / (1 << 20))
            finally:
                await b.stop()
                await a.stop()
                # keep store usage flat across repeats
                shutil.rmtree(Path(td) / f"b{i}", ignore_errors=True)
                shutil.rmtree(Path(td) / f"restored{i}", ignore_errors=True)
        return float(np.median(rates)), disk_mbps

    root = None  # default tmpdir unless tmpfs has comfortable headroom
    try:
        if Path("/dev/shm").is_dir() and (
            shutil.disk_usage("/dev/shm").free > 8 * total_mb * (1 << 20)
        ):
            root = "/dev/shm"
    except OSError:
        pass
    with tempfile.TemporaryDirectory(dir=root) as td:
        return asyncio.run(run(td))


# Upload-server parent as a SUBPROCESS: production topology for the data-
# plane A/Bs. An in-process parent shares the client's GIL, and under TLS
# both sides' per-record Python convoys on it — measured ~2x overstatement
# of the TLS cost. The child process seeds its own storage from a payload
# file, optionally arms mTLS from a cert dir (tls.crt/tls.key/ca.pem), caps
# its serving rate when asked, prints PORT, and serves until killed.
_UPLOAD_PARENT_SRC = """
import asyncio, os, sys

async def main():
    workdir, task_id, payload_file, piece_s, n_s, tls_dir, policy, rate_s = sys.argv[1:9]
    piece, n, rate = int(piece_s), int(n_s), float(rate_s)
    from dragonfly2_tpu.daemon.storage import StorageManager
    from dragonfly2_tpu.daemon.upload import UploadServer
    with open(payload_file, "rb") as f:
        payload = f.read()
    sm = StorageManager(workdir)
    ts = sm.register_task(task_id, url=f"d7y://bench/{task_id}")
    ts.set_task_info(content_length=piece * n, piece_size=piece, total_pieces=n)
    for i in range(n):
        await ts.write_piece(i, payload)
    ts.mark_done()
    tls = None
    if tls_dir:
        from dragonfly2_tpu.security.transport import DataPlaneTls
        tls = DataPlaneTls.from_paths(
            os.path.join(tls_dir, "tls.crt"), os.path.join(tls_dir, "tls.key"),
            os.path.join(tls_dir, "ca.pem"), policy=policy or None,
        )
    srv = UploadServer(sm, tls=None if tls is None else tls.server_ctx)
    await srv.start()
    if rate:
        from dragonfly2_tpu.utils.ratelimit import TokenBucket
        # small burst so the per-peer cap actually binds
        srv.bucket = TokenBucket(rate * (1 << 20), burst=2 << 20)
    print(f"PORT {srv.port}", flush=True)
    await asyncio.Event().wait()

asyncio.run(main())
"""


async def _spawn_upload_parent(
    workdir: str,
    *,
    task_id: str,
    payload_file: str,
    piece_bytes: int,
    n_pieces: int,
    tls_dir: str = "",
    policy: str = "",
    rate_mbps: float = 0.0,
):
    """(proc, port) for a seeded upload-server parent subprocess."""
    import asyncio
    import subprocess

    proc = subprocess.Popen(
        [
            sys.executable, "-c", _UPLOAD_PARENT_SRC,
            workdir, task_id, payload_file, str(piece_bytes), str(n_pieces),
            tls_dir, policy, str(rate_mbps),
        ],
        stdout=subprocess.PIPE,
        text=True,
    )
    loop = asyncio.get_running_loop()
    try:
        line = await asyncio.wait_for(
            loop.run_in_executor(None, proc.stdout.readline), 60
        )
    except asyncio.TimeoutError:
        proc.kill()
        raise
    if not line.startswith("PORT "):
        proc.kill()
        raise RuntimeError(f"upload parent failed to boot: {line!r}")
    return proc, int(line.split()[1])


async def _conductor_fetch(
    td: str,
    *,
    task_id: str,
    port: int,
    piece_bytes: int,
    n_pieces: int,
    leg_id: str,
    tls_dir: str = "",
    policy: str = "",
    extra_ports: "tuple[int, ...]" = (),
    striped: bool = True,
) -> "tuple[float, int]":
    """One real PeerTaskConductor download of the parent-held task; returns
    (MB/s, parents-that-served). Each call registers a fresh child peer
    against a fresh in-process scheduler, so legs are independent and the
    parent just serves."""
    import asyncio

    from dragonfly2_tpu.daemon.conductor import ConductorConfig as _CC
    from dragonfly2_tpu.daemon.conductor import PeerTaskConductor
    from dragonfly2_tpu.daemon.engine import InProcessSchedulerClient
    from dragonfly2_tpu.daemon.source import SourceRegistry
    from dragonfly2_tpu.daemon.storage import StorageManager
    from dragonfly2_tpu.scheduler.service import HostInfo, SchedulerService, TaskMeta

    url = f"d7y://bench/{task_id}"
    svc = SchedulerService()
    client = InProcessSchedulerClient(svc)
    for i, p in enumerate((port, *extra_ports)):
        await client.announce_task(  # dflint: disable=DF025 one announce per parent at leg SETUP (2 iterations), not a hot path
            f"bench-parent-{leg_id}-{i}",
            TaskMeta(task_id=task_id, url=url),
            HostInfo(
                id=f"bench-parent-host-{leg_id}-{i}", ip="127.0.0.1",
                hostname=f"bench-parent-{i}", download_port=p,
            ),
            content_length=piece_bytes * n_pieces, piece_size=piece_bytes,
            piece_indices=list(range(n_pieces)),
        )
    data_tls = None
    if tls_dir:
        from dragonfly2_tpu.security.transport import DataPlaneTls

        data_tls = DataPlaneTls.from_paths(
            os.path.join(tls_dir, "tls.crt"), os.path.join(tls_dir, "tls.key"),
            os.path.join(tls_dir, "ca.pem"), policy=policy or None,
        )
    cfg = _CC(
        metadata_poll_interval=0.02,
        striped_fetch=striped,
        # the A/B measures the wire+pipeline, not the per-task rate policy
        download_rate_bps=float(4 << 30),
    )
    conductor = PeerTaskConductor(
        peer_id=f"bench-child-{leg_id}",
        meta=TaskMeta(task_id=task_id, url=url),
        host=HostInfo(
            id=f"bench-child-host-{leg_id}", ip="127.0.0.1", hostname="bench-child"
        ),
        scheduler=client,
        storage=StorageManager(os.path.join(td, f"bench-child-{leg_id}")),
        sources=SourceRegistry(),
        config=cfg,
        data_tls=data_tls,
    )
    conductor.dispatcher.epsilon = 0.0  # deterministic assignment
    t0 = time.perf_counter()
    ts = await asyncio.wait_for(conductor.run(), 180)
    dt = time.perf_counter() - t0
    if not ts.is_complete():
        raise IOError(f"bench conductor leg {leg_id} incomplete")
    return (
        piece_bytes * n_pieces / (1 << 20) / dt,
        len(conductor.pieces_by_parent),
    )


def bench_piece_pipeline(total_mb: int = 192, piece_mb: int = 16) -> dict:
    """Stage decomposition of the piece-transfer hot path, measured with the
    daemon's ACTUAL pipeline primitives (daemon/pipeline.py) over a loopback
    socket and a tmpfs-backed store file:

      recv_mb_per_s    sock_recv_into a reused buffer, nothing else
      hash_mb_per_s    sha256 one full pass per piece, nothing else
      write_mb_per_s   buffered piece-sized store writes, nothing else
      serial_mb_per_s  recv pass → hash pass → write, one core (the
                       pre-pipeline shape: r05's ~2.3 ns/B serial chain)
      pipelined_mb_per_s  pooled buffers + hash-on-receive on the pipeline's
                       hash thread + writer-thread store writes with
                       immediate buffer recycle (the shipping path)

    The recv+hash overlap is the pipelined-vs-serial gap: serial pays
    recv+hash+write per byte on one core, pipelined pays ~max(recv, hash)
    plus the deferred write. Sender and hasher share the 2-core box with the
    receiver — same contention the checkpoint fan-out bench runs under."""
    import asyncio
    import hashlib
    import shutil
    import socket
    import tempfile
    import threading
    from pathlib import Path

    from dragonfly2_tpu.daemon.pipeline import BufferPool, PiecePipeline

    piece = piece_mb << 20
    pieces = max(2, (total_mb << 20) // piece)
    payload = os.urandom(piece)
    total_bytes = pieces * piece

    root = None
    try:
        if Path("/dev/shm").is_dir() and (
            shutil.disk_usage("/dev/shm").free > 4 * total_bytes
        ):
            root = "/dev/shm"
    except OSError:
        pass

    def stream(n: int):
        """(sender_thread, receiver_socket): n pieces pushed as fast as the
        kernel accepts them."""
        a, b = socket.socketpair()
        a.setblocking(True)

        def _send():
            try:
                for _ in range(n):
                    a.sendall(payload)
            except OSError:
                pass  # receiver bailed; the timing side already has its error
            finally:
                a.close()

        t = threading.Thread(target=_send, daemon=True)
        t.start()
        b.setblocking(False)
        return t, b

    async def recv_piece(loop, sock, view, on_chunk=None) -> None:
        off = 0
        while off < len(view):
            n = await loop.sock_recv_into(sock, view[off:])
            if n == 0:
                raise IOError(f"sender closed at byte {off}")
            off += n
            if on_chunk is not None:
                on_chunk(off)

    async def run_recv() -> float:
        loop = asyncio.get_running_loop()
        buf = bytearray(piece)
        view = memoryview(buf)
        t, sock = stream(pieces)
        try:
            t0 = time.perf_counter()
            for _ in range(pieces):
                await recv_piece(loop, sock, view)
            return time.perf_counter() - t0
        finally:
            sock.close()
            t.join()

    def run_hash() -> float:
        t0 = time.perf_counter()
        for _ in range(pieces):
            hashlib.sha256(payload).hexdigest()
        return time.perf_counter() - t0

    def run_write(dirpath: str) -> float:
        path = os.path.join(dirpath, "write-only")
        with open(path, "wb") as f:
            t0 = time.perf_counter()
            for i in range(pieces):
                f.seek(i * piece)
                f.write(payload)
            elapsed = time.perf_counter() - t0
        os.unlink(path)
        return elapsed

    async def run_recv_then_hash() -> float:
        """Two serial passes (the pre-pipeline shape, write excluded)."""
        loop = asyncio.get_running_loop()
        buf = bytearray(piece)
        view = memoryview(buf)
        t, sock = stream(pieces)
        try:
            t0 = time.perf_counter()
            for _ in range(pieces):
                await recv_piece(loop, sock, view)
                hashlib.sha256(view).hexdigest()
            return time.perf_counter() - t0
        finally:
            sock.close()
            t.join()

    async def run_recv_hash_overlapped() -> float:
        """recv with hash-on-receive (write excluded): the hash runs in the
        recv loop's shadow on the pipeline's shard thread."""
        loop = asyncio.get_running_loop()
        pipeline = PiecePipeline()
        t, sock = stream(pieces)
        try:
            t0 = time.perf_counter()
            for _ in range(pieces):
                pooled = await pipeline.pool.acquire(piece)
                try:
                    pump = pipeline.hash_pump(pooled.view)
                    await recv_piece(loop, sock, pooled.view, pump.feed)
                    await pump.finish()
                finally:
                    pooled.release()
            return time.perf_counter() - t0
        finally:
            sock.close()
            t.join()
            pipeline.close()

    async def run_serial(dirpath: str) -> float:
        """The r05 per-piece chain: a FRESH bytearray per piece (what
        get_range allocated — its first-touch page faults were part of the
        replaced cost), then recv, then a full hash pass, then the write."""
        loop = asyncio.get_running_loop()
        t, sock = stream(pieces)
        path = os.path.join(dirpath, "serial")
        try:
            with open(path, "wb") as f:
                t0 = time.perf_counter()
                for i in range(pieces):
                    view = memoryview(bytearray(piece))
                    await recv_piece(loop, sock, view)
                    hashlib.sha256(view).hexdigest()
                    f.seek(i * piece)
                    f.write(view)
                return time.perf_counter() - t0
        finally:
            sock.close()
            t.join()
            os.unlink(path)

    async def run_pipelined(dirpath: str, workers: int = 2) -> tuple[float, int]:
        """The shipping conductor shape: N piece workers share the pipeline;
        each recv's into a pooled buffer with hash-on-receive and lands the
        piece through a worker-thread write. recv/hash overlap within a
        piece; recv/write overlap across workers (the measured-fastest
        arrangement on this 2-core image — see
        ConductorConfig.defer_piece_writes). Returns (seconds, bytes moved)
        — with an odd piece count the remainder piece is not transferred,
        and rating it against the full total would inflate this stage."""
        loop = asyncio.get_running_loop()
        pipeline = PiecePipeline(pool=BufferPool(max_outstanding_per_bucket=4))
        path = os.path.join(dirpath, "pipelined")
        per_worker = pieces // workers
        streams = [stream(per_worker) for _ in range(workers)]
        try:
            with open(path, "w+b") as f:

                def _store(view, offset) -> None:
                    f.seek(offset)
                    f.write(view)

                async def run_worker(w: int) -> None:
                    sock = streams[w][1]
                    for i in range(per_worker):
                        pooled = await pipeline.pool.acquire(piece)
                        try:
                            pump = pipeline.hash_pump(pooled.view)
                            await recv_piece(loop, sock, pooled.view, pump.feed)
                            await pump.finish()
                            await asyncio.to_thread(
                                _store, pooled.view, (w * per_worker + i) * piece
                            )
                        finally:
                            pooled.release()

                t0 = time.perf_counter()
                await asyncio.gather(*(run_worker(w) for w in range(workers)))
                return time.perf_counter() - t0, per_worker * workers * piece
        finally:
            for t, sock in streams:
                sock.close()
                t.join()
            pipeline.close()
            if os.path.exists(path):
                os.unlink(path)

    _TLS_NULLS = {
        "plain_transport_mb_per_s": None,
        "mtls_transport_mb_per_s": None,
        "mtls_stream_mb_per_s": None,
        "tls_cipher_policy": None,
        "tls_aes_accel": None,
        "aesgcm_transport_mb_per_s": None,
        "chacha20_transport_mb_per_s": None,
        "cipher_autoselect_gain_pct": None,
        "tls_handshake_full_ms": None,
        "tls_handshake_resumed_ms": None,
        "tls_resumption_hit_rate": None,
        "pipelined_tls_mb_per_s": None,
        "pipelined_plain_e2e_mb_per_s": None,
        "tls_overhead_pct": None,
        "ktls": None,
    }

    def _tls_send_thread(srv_ctx, port_box: list, n_pieces: int):
        """Upload-side TLS sender with the parent's crypto taken OFF the
        timed window: after the live handshake the whole stream (a 1-byte
        ready marker, then the pieces) is encrypted into memory FIRST —
        record-aligned 256 KiB batches through a MemoryBIO, the
        daemon/upload.py streaming shape — and only then pushed with big
        raw sendalls. In production the encrypting parent is ANOTHER host;
        on this 2-core loopback bench a live-encrypting sender would charge
        the child's A/B for the parent's cores, roughly doubling the
        apparent cost of TLS. The receiver (the side these legs measure)
        decrypts live. Receivers must consume the marker before starting
        their clock — it fences out the pre-encryption time."""
        import socket as socketlib
        import ssl
        import threading

        from dragonfly2_tpu.security.transport import TLS_RECORD_BYTES

        s = socketlib.socket()
        s.bind(("127.0.0.1", 0))
        s.listen(1)
        port_box.append(s.getsockname()[1])
        pv = memoryview(payload)
        step = 16 * TLS_RECORD_BYTES

        def run():
            conn, _ = s.accept()
            conn.setsockopt(socketlib.IPPROTO_TCP, socketlib.TCP_NODELAY, 1)
            inc, out = ssl.MemoryBIO(), ssl.MemoryBIO()
            obj = srv_ctx.wrap_bio(inc, out, server_side=True)
            try:
                while True:
                    try:
                        obj.do_handshake()
                        break
                    except ssl.SSLWantReadError:
                        d = out.read()
                        if d:
                            conn.sendall(d)
                        r = conn.recv(65536)
                        if not r:
                            raise IOError("peer gone in handshake")
                        inc.write(r)
                d = out.read()
                if d:
                    conn.sendall(d)
                # pre-encrypt the full stream (marker + pieces, in order —
                # GCM sequence numbers make the records replay-safe only in
                # this exact order on this exact connection)
                chunks: list[bytes] = [b""]
                obj.write(b"R")
                chunks[0] = out.read()
                for _ in range(n_pieces):
                    off = 0
                    while off < piece:
                        end = min(off + step, piece)
                        obj.write(pv[off:end])
                        off = end
                        chunks.append(out.read())
                for c in chunks:
                    conn.sendall(c)
            except (OSError, ssl.SSLError):
                pass  # receiver bailed; its timing side already has the error
            finally:
                conn.close()
                s.close()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        return t

    async def _tls_connect(port: int, cli_ctx, session=None):
        import socket as socketlib

        from dragonfly2_tpu.security.transport import AsyncTlsTransport

        loop = asyncio.get_running_loop()
        sock = socketlib.socket()
        sock.setblocking(False)
        await loop.sock_connect(sock, ("127.0.0.1", port))
        return await AsyncTlsTransport.connect(sock, cli_ctx, session=session)

    async def _tls_recv_leg(cli_ctx, srv_ctx, n_pieces: int) -> float:
        """One timed fast-path transport leg: n pieces decrypted straight
        into a reused buffer (the rawrange recv_into shape)."""
        pb: list = []
        t = _tls_send_thread(srv_ctx, pb, n_pieces)
        await asyncio.sleep(0.05)
        tr = await _tls_connect(pb[0], cli_ctx)
        buf = bytearray(piece)
        view = memoryview(buf)
        try:
            assert await tr.recv(1) == b"R"  # sender pre-encryption fence
            t0 = time.perf_counter()
            for _ in range(n_pieces):
                # the shipping big-body shape: worker-thread drain
                await tr.recv_body_into(view, 0)
            return time.perf_counter() - t0
        finally:
            tr.close()
            t.join()

    async def run_tls_suite(td: str) -> dict:
        """The TLS fast-path measurements (ISSUE 13): cipher autoselect A/B,
        handshake full-vs-resumed + reconnect-storm hit rate, the fast-path
        transport vs plain AND vs the old asyncio-SSL stream shape, the
        full-pipeline overhead headline, and the kTLS probe. Emits nulls
        when no CA backend exists on the host (cryptography wheel AND
        openssl CLI both absent): skipped ≠ measured-zero (VERDICT #8).
        kTLS itself is ALWAYS a probe result, never a number — on this
        image it reports unavailable and nothing here fakes otherwise."""
        import ssl

        from dragonfly2_tpu.security import transport as tport

        try:
            from dragonfly2_tpu.security.ca import CertificateAuthority, write_issued

            ca = CertificateAuthority(os.path.join(td, "ca"))
            leaf = ca.issue("bench-pipeline", sans=["127.0.0.1"])
            paths = write_issued(leaf, os.path.join(td, "leaf"))
        except Exception as e:
            print(f"bench: tls suite skipped (no CA backend): {e}", file=sys.stderr, flush=True)
            return dict(_TLS_NULLS)

        out: dict = dict(_TLS_NULLS)
        out["ktls"] = tport.probe_ktls()
        out["tls_aes_accel"] = tport.detect_aes_accel()

        def ctxs(policy: str):
            srv = tport.data_server_ssl_context(
                paths["cert"], paths["key"], paths["ca"], policy=policy
            )
            cli = tport.data_client_ssl_context(
                paths["ca"], paths["cert"], paths["key"], policy=policy
            )
            return srv, cli

        # --- cipher A/B over the fast path (interleaved, median of 3) ---
        tls_pieces = max(2, pieces // 2)
        cipher_t: dict[str, list] = {"aes-gcm": [], "chacha20": []}
        pairs = {p: ctxs(p) for p in cipher_t}
        for _ in range(3):
            for policy, (srv_ctx, cli_ctx) in pairs.items():
                cipher_t[policy].append(
                    await _tls_recv_leg(cli_ctx, srv_ctx, tls_pieces)
                )
        mb_leg = tls_pieces * piece / (1 << 20)
        aes_rate = mb_leg / float(np.median(cipher_t["aes-gcm"]))
        cha_rate = mb_leg / float(np.median(cipher_t["chacha20"]))
        out["aesgcm_transport_mb_per_s"] = round(aes_rate, 1)
        out["chacha20_transport_mb_per_s"] = round(cha_rate, 1)
        policy = "aes-gcm" if aes_rate >= cha_rate else "chacha20"
        # what the autoselect buys over blindly shipping the OTHER cipher on
        # this host (the 55%-overhead lever on software-AES boxes)
        out["cipher_autoselect_gain_pct"] = round(
            (max(aes_rate, cha_rate) / min(aes_rate, cha_rate) - 1) * 100, 1
        )
        out["tls_cipher_policy"] = policy
        srv_ctx, cli_ctx = pairs[policy]

        # --- transport A/B: plain vs fast path vs the old stream shape ---
        async def plain_leg() -> float:
            import socket as socketlib
            import threading

            s = socketlib.socket()
            s.bind(("127.0.0.1", 0))
            s.listen(1)
            port = s.getsockname()[1]

            def send():
                conn, _ = s.accept()
                conn.setsockopt(socketlib.IPPROTO_TCP, socketlib.TCP_NODELAY, 1)
                try:
                    for _ in range(tls_pieces):
                        conn.sendall(payload)
                except OSError:
                    pass
                finally:
                    conn.close()
                    s.close()

            th = threading.Thread(target=send, daemon=True)  # dflint: disable=DF026 each bench leg IS a fresh measured transfer: one sender thread per leg by design
            th.start()
            loop = asyncio.get_running_loop()
            sock = socketlib.socket()
            sock.setblocking(False)
            await loop.sock_connect(sock, ("127.0.0.1", port))
            buf = bytearray(piece)
            view = memoryview(buf)
            try:
                t0 = time.perf_counter()
                for _ in range(tls_pieces):
                    off = 0
                    while off < piece:
                        n = await loop.sock_recv_into(sock, view[off:])
                        if n == 0:
                            raise IOError("closed")
                        off += n
                return time.perf_counter() - t0
            finally:
                sock.close()
                th.join()

        async def stream_leg() -> float:
            """The PR 7 shape: asyncio SSL streams (what the 55% was
            measured through) — kept as the A/B showing the fast path's
            transport-level gain."""
            async def handle(reader, writer):
                try:
                    for _ in range(tls_pieces):
                        writer.write(payload)
                        await writer.drain()
                except (ConnectionError, ssl.SSLError):
                    pass
                finally:
                    writer.close()

            server = await asyncio.start_server(handle, "127.0.0.1", 0, ssl=srv_ctx)
            port = server.sockets[0].getsockname()[1]
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port, ssl=cli_ctx
                )
                t0 = time.perf_counter()
                for _ in range(tls_pieces):
                    await reader.readexactly(piece)
                elapsed = time.perf_counter() - t0
                writer.close()
                return elapsed
            finally:
                server.close()
                await server.wait_closed()

        plain_t, fast_t, stream_t = [], [], []
        for _ in range(3):
            plain_t.append(await plain_leg())  # dflint: disable=DF026 each interleaved A/B rep IS a fresh measured transfer with its own sender thread
            fast_t.append(await _tls_recv_leg(cli_ctx, srv_ctx, tls_pieces))
            stream_t.append(await stream_leg())
        plain_rate = mb_leg / float(np.median(plain_t))
        out["plain_transport_mb_per_s"] = round(plain_rate, 1)
        out["mtls_transport_mb_per_s"] = round(mb_leg / float(np.median(fast_t)), 1)
        out["mtls_stream_mb_per_s"] = round(mb_leg / float(np.median(stream_t)), 1)

        # --- handshake storm: full vs resumed + hit rate ---
        import socket as socketlib
        import threading

        storms = 20
        ls = socketlib.socket()
        ls.bind(("127.0.0.1", 0))
        ls.listen(8)
        port = ls.getsockname()[1]
        stop = threading.Event()

        def storm_server():
            while not stop.is_set():
                try:
                    conn, _ = ls.accept()
                except OSError:
                    return
                try:
                    sconn = srv_ctx.wrap_socket(conn, server_side=True)
                    sconn.recv(1)
                    sconn.close()
                except (OSError, ssl.SSLError):
                    conn.close()

        th = threading.Thread(target=storm_server, daemon=True)  # dflint: disable=DF026 one accept-loop thread for the whole handshake storm, not per item
        th.start()
        sessions = tport.TlsSessionCache()
        full_ms, resumed_ms, resumed_n = [], [], 0
        try:
            for i in range(storms):
                t0 = time.perf_counter()
                tr = await _tls_connect(port, cli_ctx, session=sessions.get(("s", port)))
                dt_ms = (time.perf_counter() - t0) * 1e3
                if tr.session_reused:
                    resumed_n += 1
                    resumed_ms.append(dt_ms)
                else:
                    full_ms.append(dt_ms)
                sessions.put(("s", port), tr.session)
                await tr.sendall(b"x")
                tr.close()
        finally:
            stop.set()
            ls.close()
            th.join(timeout=2)
        if full_ms:
            out["tls_handshake_full_ms"] = round(float(np.median(full_ms)), 2)
        if resumed_ms:
            out["tls_handshake_resumed_ms"] = round(float(np.median(resumed_ms)), 2)
        out["tls_resumption_hit_rate"] = round(resumed_n / max(1, storms - 1), 3)

        # --- the headline: TLS overhead on the REAL data plane ---
        # Plain vs mTLS through the SHIPPING components end to end: a real
        # UploadServer in its OWN SUBPROCESS (production topology — parent
        # crypto on the parent's interpreter; in-process parents convoy
        # both sides' per-record Python on one GIL and overstate TLS ~2x)
        # serving a real task, and a real PeerTaskConductor fetching it
        # (rawrange fast path, hash-on-receive, store writes, the works).
        # Interleaved median-of-3; the ONLY difference between legs is the
        # wire posture.
        e2e_pieces = max(4, tls_pieces)
        payload_file = os.path.join(td, "bench-piece-payload.bin")
        if not os.path.exists(payload_file):
            with open(payload_file, "wb") as f:
                f.write(payload)
        tls_dir = os.path.dirname(paths["cert"])
        procs = []
        try:
            # rate_mbps far above the wire: the per-peer serving cap is a
            # POLICY (the striped A/B models it); the TLS A/B wants the
            # unthrottled transport+pipeline signal in both legs
            p_plain, port_plain = await _spawn_upload_parent(
                os.path.join(td, "e2e-parent-plain"),
                task_id="benchtlse2eplain", payload_file=payload_file,
                piece_bytes=piece, n_pieces=e2e_pieces, rate_mbps=8192,
            )
            procs.append(p_plain)
            p_tls, port_tls = await _spawn_upload_parent(
                os.path.join(td, "e2e-parent-tls"),
                task_id="benchtlse2etls", payload_file=payload_file,
                piece_bytes=piece, n_pieces=e2e_pieces,
                tls_dir=tls_dir, policy=policy, rate_mbps=8192,
            )
            procs.append(p_tls)
            plain_rates, tls_rates = [], []
            for rep in range(3):
                r, _w = await _conductor_fetch(
                    td, task_id="benchtlse2eplain", port=port_plain,
                    piece_bytes=piece, n_pieces=e2e_pieces,
                    leg_id=f"plain{rep}",
                )
                plain_rates.append(r)
                r, _w = await _conductor_fetch(
                    td, task_id="benchtlse2etls", port=port_tls,
                    piece_bytes=piece, n_pieces=e2e_pieces,
                    leg_id=f"tls{rep}", tls_dir=tls_dir, policy=policy,
                )
                tls_rates.append(r)
            plain_e2e = float(np.median(plain_rates))
            tls_e2e = float(np.median(tls_rates))
            out["pipelined_plain_e2e_mb_per_s"] = round(plain_e2e, 1)
            out["pipelined_tls_mb_per_s"] = round(tls_e2e, 1)
            out["tls_overhead_pct"] = round((1 - tls_e2e / plain_e2e) * 100, 1)
        except Exception as e:
            print(f"bench: conductor TLS A/B failed: {e!r}", file=sys.stderr, flush=True)
            out["pipelined_plain_e2e_mb_per_s"] = None
        finally:
            for p in procs:
                p.kill()
                p.wait()
        return out

    async def run_pipelined_deferred(dirpath: str, workers: int = 2) -> tuple[float, int]:
        """run_pipelined with WRITE-BEHIND: the store write rides its own
        task and the worker recycles a fresh buffer into recv immediately
        (the conductor's defer_piece_writes=True leg; the buffer pool's
        outstanding bound is the backpressure)."""
        from dragonfly2_tpu.daemon.pipeline import BufferPool as _BP
        from dragonfly2_tpu.daemon.pipeline import PiecePipeline as _PP

        loop = asyncio.get_running_loop()
        pipeline = _PP(pool=_BP(max_outstanding_per_bucket=4))
        path = os.path.join(dirpath, "pipelined-deferred")
        per_worker = pieces // workers
        streams = [stream(per_worker) for _ in range(workers)]
        writes: set = set()
        try:
            with open(path, "w+b") as f:

                def _store(view, offset) -> None:
                    f.seek(offset)
                    f.write(view)

                async def write_behind(pooled, offset) -> None:
                    try:
                        await asyncio.to_thread(_store, pooled.view, offset)
                    finally:
                        pooled.release()

                async def run_worker(w: int) -> None:
                    sock = streams[w][1]
                    for i in range(per_worker):
                        pooled = await pipeline.pool.acquire(piece)
                        try:
                            pump = pipeline.hash_pump(pooled.view)
                            await recv_piece(loop, sock, pooled.view, pump.feed)
                            await pump.finish()
                        except BaseException:
                            pooled.release()
                            raise
                        t = asyncio.ensure_future(
                            write_behind(pooled, (w * per_worker + i) * piece)
                        )
                        writes.add(t)
                        t.add_done_callback(writes.discard)

                t0 = time.perf_counter()
                await asyncio.gather(*(run_worker(w) for w in range(workers)))
                while writes:
                    await asyncio.gather(*list(writes))
                return time.perf_counter() - t0, per_worker * workers * piece
        finally:
            for t, sock in streams:
                sock.close()
                t.join()
            pipeline.close()
            if os.path.exists(path):
                os.unlink(path)

    async def run_striped_ab(td: str) -> dict:
        """Striped-vs-single-parent fetch over the REAL wire: two upload-
        server parents in their OWN SUBPROCESSES, each capped at a per-peer
        serving rate (the reference's 512 MB/s per-peer ceiling story,
        scaled to this box), one conductor child per leg. Striped mode
        aggregates both parents' ceilings; the single-parent leg funnels
        through one. Interleaved median-of-3; nulls on failure rather than
        fabricated numbers."""
        stripe_pieces = min(8, pieces)
        parent_cap_mbps = 150.0
        content = piece * stripe_pieces
        task_id = "benchstripetask0"
        payload_file = os.path.join(td, "bench-piece-payload.bin")
        if not os.path.exists(payload_file):
            with open(payload_file, "wb") as f:
                f.write(payload)
        procs = []
        try:
            ports = []
            for i in range(2):
                p, port = await _spawn_upload_parent(
                    os.path.join(td, f"stripe-parent{i}"),
                    task_id=task_id, payload_file=payload_file,
                    piece_bytes=piece, n_pieces=stripe_pieces,
                    rate_mbps=parent_cap_mbps,
                )
                procs.append(p)
                ports.append(port)

            single_r, striped_r, widths = [], [], []
            for rep in range(3):
                r, _w = await _conductor_fetch(
                    td, task_id=task_id, port=ports[0],
                    piece_bytes=piece, n_pieces=stripe_pieces,
                    leg_id=f"stripe-0-{rep}",
                    extra_ports=(ports[1],), striped=False,
                )
                single_r.append(r)
                r, w = await _conductor_fetch(
                    td, task_id=task_id, port=ports[0],
                    piece_bytes=piece, n_pieces=stripe_pieces,
                    leg_id=f"stripe-1-{rep}",
                    extra_ports=(ports[1],), striped=True,
                )
                striped_r.append(r)
                widths.append(w)
            single_rate = float(np.median(single_r))
            striped_rate = float(np.median(striped_r))
            return {
                "single_parent_mb_per_s": round(single_rate, 1),
                "striped_mb_per_s": round(striped_rate, 1),
                "striped_speedup": round(striped_rate / single_rate, 3),
                "stripe_parents_used": int(max(widths)),
                "stripe_parent_cap_mb_per_s": parent_cap_mbps,
            }
        except Exception as e:
            print(f"bench: striped A/B failed: {e!r}", file=sys.stderr, flush=True)
            return {
                "single_parent_mb_per_s": None,
                "striped_mb_per_s": None,
                "striped_speedup": None,
                "stripe_parents_used": None,
                "stripe_parent_cap_mb_per_s": None,
            }
        finally:
            for p in procs:
                p.kill()
                p.wait()

    async def run_all() -> dict:
        with tempfile.TemporaryDirectory(dir=root) as td:
            mb = total_bytes / (1 << 20)
            recv_s = await run_recv()
            hash_s = run_hash()
            write_s = run_write(td)
            tls = await run_tls_suite(td)
            striped = await run_striped_ab(td)
            # A/B pairs INTERLEAVED, median of 3: this shared box drifts
            # ±30% run-to-run, which would otherwise swamp the overlap
            # signal the comparisons exist to show
            rth, rho, serial_runs, pipelined_rates, deferred_rates = [], [], [], [], []
            for _ in range(3):
                rth.append(await run_recv_then_hash())
                rho.append(await run_recv_hash_overlapped())
                serial_runs.append(await run_serial(td))
                p_s, p_bytes = await run_pipelined(td)
                pipelined_rates.append(p_bytes / (1 << 20) / p_s)
                d_s, d_bytes = await run_pipelined_deferred(td)
                deferred_rates.append(d_bytes / (1 << 20) / d_s)
            rth_s = float(np.median(rth))
            rho_s = float(np.median(rho))
            serial_s = float(np.median(serial_runs))
            pipelined_rate = float(np.median(pipelined_rates))
            deferred_rate = float(np.median(deferred_rates))
            # the adaptive write-behind decision, fed the SAME stage
            # measurements a first dispatch round would collect on this box
            # (per-piece recv and write durations, inline mode)
            from dragonfly2_tpu.daemon.conductor import WriteBehindGovernor

            governor = WriteBehindGovernor(None)
            for _ in range(pieces):
                governor.note(recv_s / pieces, write_s / pieces)
            governor.decide()
            wb = governor.snapshot()
            return {
                "recv_mb_per_s": round(mb / recv_s, 1),
                "hash_mb_per_s": round(mb / hash_s, 1),
                "write_mb_per_s": round(mb / write_s, 1),
                # the recv+hash overlap isolated (write and its thread
                # excluded): hash-on-receive runs the sha256 in the recv
                # loop's shadow, so overlapped > serial == overlap working
                "recv_then_hash_mb_per_s": round(mb / rth_s, 1),
                "recv_hash_overlapped_mb_per_s": round(mb / rho_s, 1),
                "recv_hash_overlap_speedup": round(rth_s / rho_s, 3),
                "serial_mb_per_s": round(mb / serial_s, 1),
                "pipelined_mb_per_s": round(pipelined_rate, 1),
                "overlap_speedup_vs_serial": round(pipelined_rate / (mb / serial_s), 3),
                **tls,
                **striped,
                # adaptive write-behind: both legs measured + what the
                # governor decides from this box's stage profile
                "write_behind_mb_per_s_inline": round(pipelined_rate, 1),
                "write_behind_mb_per_s_deferred": round(deferred_rate, 1),
                "write_behind_decision": wb["mode"],
                "write_behind_recv_ms": wb["recv_ms"],
                "write_behind_write_ms": wb["write_ms"],
                "piece_mb": piece_mb,
                "pieces": pieces,
                "store_dir": root or "tmp",
            }

    return asyncio.run(run_all())


def bench_dataset_build(
    n_downloads: int = 100_000, n_probes: int = 20_000, n_hosts: int = 2048
) -> dict:
    """Telemetry→dataset ingest (the trainer's record plane):

      dataset_build_rows_per_sec   vectorized build_dataset on ≥100k rows
      rowloop_rows_per_sec         the per-row reference walk
                                   (_build_dataset_rowloop) on the same data
      speedup_vs_rowloop           A/B pairs INTERLEAVED, median of 3 — this
                                   shared box drifts ±30% run-to-run
      chunk_fold_rows_per_sec      DatasetAccumulator folding announcer-sized
                                   chunks (the incremental train_chunk path)
      ingest_to_train_start_ms     finalize() on the folded state — the
                                   latency between train_close and the first
                                   trainable Dataset
    """
    from dragonfly2_tpu.scheduler.announcer import CHUNK_ROWS
    from dragonfly2_tpu.trainer import dataset as datasetlib
    from dragonfly2_tpu.trainer.synthetic import synth_telemetry_records

    # generated vectorized (appending 100k rows through ColumnarStore would
    # time the generator, not the builder)
    downloads, probes = synth_telemetry_records(n_downloads, n_probes, n_hosts, seed=7)
    total = len(downloads) + len(probes)

    row_t, vec_t = [], []
    ds = None
    for _ in range(3):
        t0 = time.perf_counter()
        ref = datasetlib._build_dataset_rowloop(downloads, probes)
        row_t.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        ds = datasetlib.build_dataset(downloads, probes)
        vec_t.append(time.perf_counter() - t0)
    assert ds.num_pairs == ref.num_pairs and ds.num_nodes == ref.num_nodes

    acc = datasetlib.DatasetAccumulator()
    t0 = time.perf_counter()
    for start in range(0, len(downloads), CHUNK_ROWS):
        acc.add_downloads(downloads[start : start + CHUNK_ROWS])
    for start in range(0, len(probes), CHUNK_ROWS):
        acc.add_probes(probes[start : start + CHUNK_ROWS])
    fold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    acc.finalize()
    finalize_s = time.perf_counter() - t0

    row_s = float(np.median(row_t))
    vec_s = float(np.median(vec_t))
    return {
        "rows": total,
        "hosts": n_hosts,
        "dataset_build_rows_per_sec": round(total / vec_s, 1),
        "rowloop_rows_per_sec": round(total / row_s, 1),
        "speedup_vs_rowloop": round(row_s / vec_s, 2),
        "chunk_fold_rows_per_sec": round(total / fold_s, 1),
        "chunk_rows": CHUNK_ROWS,
        "ingest_to_train_start_ms": round(finalize_s * 1000, 2),
        "num_nodes": ds.num_nodes,
        "num_pairs": ds.num_pairs,
        "num_edges": acc.num_edges,
    }


def bench_control_plane(
    rounds: int = 2000, candidates: int = 40, hosts: int = 192,
    pieces_per_round: int = 32,
) -> dict:
    """Scheduler control-plane fast path (PR 5): the scheduling round
    decomposed into its prepare / score / report legs, each with an
    interleaved SAME-RUN A/B against the r05 shape (2-core box discipline:
    this container drifts ±30% run-to-run, stored cross-day numbers are not
    a baseline).

      full_round_rps                    find_candidate_parents rounds/s on
                                        the shipping cached-feature path
      full_round_rps_rowwise_baseline   identical rounds (same rng seed,
                                        same pool) through the r05 rowwise
                                        feature assembly
      full_round_speedup                median of 3 interleaved A/B pairs
      evaluator_prepare_us_per_round    cached build_pair_features
      evaluator_prepare_us_rowwise      r05 _build_pair_features_rowwise
      prepare_speedup                   must hold >= 2x (ISSUE 5 acceptance)
      score_us_per_round                the base-weights matmul leg
      piece_report_rpcs_per_round       measured: report_pieces calls for
                                        one buffered dispatch round (1 when
                                        batching holds) vs one unary RPC
                                        per piece on the r05 path
      report_wire_us_per_piece_batched  measured over the real msgpack
      report_wire_us_per_piece_unary    transport (localhost round trips)
    """
    import asyncio
    import random as _random

    from dragonfly2_tpu.scheduler.evaluator import (
        _build_pair_features_rowwise,
        build_pair_features,
    )
    from dragonfly2_tpu.scheduler.resource import HostType
    from dragonfly2_tpu.scheduler.scheduling import Scheduling
    from dragonfly2_tpu.scheduler.service import SchedulerService, TaskMeta

    svc = SchedulerService()  # base evaluator: no toolchain dependency
    meta = TaskMeta("cp-task", "http://origin/cp.bin")
    task = svc.pool.load_or_create_task(meta.task_id, meta.url)
    task.set_metadata(1 << 30, 4 << 20)
    all_hosts = []
    for i in range(hosts):
        h = svc.pool.load_or_create_host(
            f"h{i}", f"10.0.{i // 256}.{i % 256}", f"host{i}", download_port=8000,
            host_type=HostType.NORMAL, idc=f"idc-{i % 3}", location=f"r{i % 2}|z{i % 5}",
        )
        h.upload_limit = 10_000
        all_hosts.append(h)
    children, parents = [], []
    for i, h in enumerate(all_hosts):
        p = svc.pool.create_peer(f"peer{i}", task, h)
        for evname in ("register", "download"):
            if p.fsm.can(evname):
                p.fsm.fire(evname)
        if i < 8:
            children.append(p)
        else:
            for idx in range(8):
                p.finished_pieces.set(idx)
            p.bump_feat()
            parents.append(p)
    # live rtt + bandwidth feature sources for every (child, parent) pair the
    # round touches — the r05 prepare cost is dominated by the per-query
    # statistics over these (see networktopology.EdgeProbes)
    rng = _random.Random(7)
    for c in children:
        for p in parents:
            for _ in range(4):
                svc.topology.enqueue(c.host.id, p.host.id, rng.uniform(0.2, 30.0))
            svc.bandwidth.observe(p.host.id, c.host.id, rng.uniform(1e8, 1e9))

    cand = parents[:candidates]
    ev = svc.evaluator
    topo, bw = ev.topology, ev.bandwidth

    # ---- prepare leg: cached row-gather vs rowwise reference, interleaved
    probe_n = 512
    child = children[0]
    for fn in (build_pair_features, _build_pair_features_rowwise):
        fn(child, cand, topo, bw)  # warm caches / allocators
    cached_t, rowwise_t = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(probe_n):
            feats = build_pair_features(child, cand, topo, bw)
        cached_t.append((time.perf_counter() - t0) / probe_n * 1e6)
        t0 = time.perf_counter()
        for _ in range(probe_n):
            _build_pair_features_rowwise(child, cand, topo, bw)
        rowwise_t.append((time.perf_counter() - t0) / probe_n * 1e6)
    prepare_us = float(np.median(cached_t))
    prepare_row_us = float(np.median(rowwise_t))

    # ---- score leg (shared by both paths): the base-weights matmul
    from dragonfly2_tpu.models.features import BASE_WEIGHTS

    t0 = time.perf_counter()
    for _ in range(probe_n):
        feats @ BASE_WEIGHTS
    score_us = (time.perf_counter() - t0) / probe_n * 1e6

    # ---- full round: sample + flattened filters + evaluate + top-4.
    # Two Scheduling instances with the SAME rng seed walk identical
    # candidate-draw sequences over the same pool; only the feature assembly
    # differs (the cached shipping path vs an evaluator pinned to rowwise).
    from dragonfly2_tpu.scheduler.evaluator import new_evaluator

    ev_row = new_evaluator("base")
    ev_row.topology, ev_row.bandwidth = topo, bw
    ev_row.feature_builder = _build_pair_features_rowwise
    full_cached_t, full_row_t = [], []
    for _ in range(3):
        for ev_leg, sink in ((ev, full_cached_t), (ev_row, full_row_t)):
            sched = Scheduling(ev_leg)  # fresh seeded rng per leg: same draws
            t0 = time.perf_counter()
            for r in range(rounds):
                sched.find_candidate_parents(children[r % len(children)])
            sink.append(rounds / (time.perf_counter() - t0))
    full_rps = float(np.median(full_cached_t))
    full_row_rps = float(np.median(full_row_t))

    # ---- report leg over the real wire: one batched flush vs per-piece
    # unary RPCs (each a full localhost round trip on the msgpack transport)
    async def report_leg() -> tuple[float, float, int]:
        from dragonfly2_tpu.rpc.scheduler import RemoteSchedulerClient, serve_scheduler

        rsvc = SchedulerService()
        rtask = rsvc.pool.load_or_create_task("rt", "http://o/r")
        rtask.set_metadata(1 << 30, 4 << 20)
        rh = rsvc.pool.load_or_create_host("rh", "10.1.0.1", "rhost", download_port=8001)
        rp = rsvc.pool.create_peer("rpeer", rtask, rh)
        rp.fsm.fire("register")
        rp.fsm.fire("download")
        server = serve_scheduler(rsvc)
        await server.start()
        client = RemoteSchedulerClient(f"127.0.0.1:{server.port}", timeout=10.0)
        try:
            await client.report_piece_result("rpeer", 0, success=True)  # warm conn
            unary_t, batch_t = [], []
            for rep in range(1, 4):
                base = rep * 100_000  # fresh indices: dedupe never skews a leg
                t0 = time.perf_counter()
                for i in range(pieces_per_round):
                    await client.report_piece_result(  # dflint: disable=DF025 this IS the r05 unary baseline leg being measured
                        "rpeer", base + i, success=True, cost_ms=5.0
                    )
                unary_t.append((time.perf_counter() - t0) / pieces_per_round * 1e6)
                t0 = time.perf_counter()
                await client.report_pieces(  # dflint: disable=DF025 the batched leg under measurement: one flush per A/B repetition by design
                    "rpeer",
                    [(base + 50_000 + i, 5.0, "") for i in range(pieces_per_round)],
                )
                batch_t.append((time.perf_counter() - t0) / pieces_per_round * 1e6)
            # measured (not asserted-by-construction): one dispatch round
            # through a real PieceReportBuffer — adds + round-end flush —
            # counting actual report_pieces calls on the wire. A buffer that
            # regresses to per-piece RPCs shows up here (and fails the
            # check.sh control-plane smoke), instead of hiding behind a
            # structural constant.
            from dragonfly2_tpu.daemon.conductor import PieceReportBuffer

            buf = PieceReportBuffer(
                client, "rpeer",
                max_batch=max(64, pieces_per_round + 1), flush_interval=60.0,
            )
            for i in range(pieces_per_round):
                buf.add(900_000 + i, 5.0, "")
            await buf.flush()  # the dispatch-round-end trigger
            rpcs_per_round = buf.rpcs
            await buf.aclose()
            return float(np.median(batch_t)), float(np.median(unary_t)), rpcs_per_round
        finally:
            await client.close()
            await server.stop()

    report_batched_us, report_unary_us, report_rpcs_per_round = asyncio.run(report_leg())

    return {
        "full_round_rps": round(full_rps, 1),
        "full_round_rps_rowwise_baseline": round(full_row_rps, 1),
        "full_round_speedup": round(full_rps / max(full_row_rps, 1e-9), 2),
        "evaluator_prepare_us_per_round": round(prepare_us, 1),
        "evaluator_prepare_us_rowwise": round(prepare_row_us, 1),
        "prepare_speedup": round(prepare_row_us / max(prepare_us, 1e-9), 2),
        "score_us_per_round": round(score_us, 1),
        "candidates_per_round": len(cand),
        "rounds_per_leg": rounds,
        # measured: report_pieces calls for one dispatch round driven
        # through a real PieceReportBuffer (adds + round-end flush) — 1 when
        # batching holds; the r05 path paid one unary round trip per piece
        "piece_report_rpcs_per_round": report_rpcs_per_round,
        "piece_report_rpcs_per_round_unary": pieces_per_round,
        "report_wire_us_per_piece_batched": round(report_batched_us, 1),
        "report_wire_us_per_piece_unary": round(report_unary_us, 1),
        "report_leg_speedup": round(report_unary_us / max(report_batched_us, 1e-9), 2),
    }


def bench_observability(
    rounds: int = 1500, span_loops: int = 200_000, pipeline_mb: int = 32,
) -> dict:
    """Tracing cost, proven cheap enough to leave on (ISSUE 9 acceptance):
    interleaved SAME-RUN A/B of the default tracer at sample_rate 0.0
    (tracing "off": every span site still runs, records nothing) vs the
    shipped service default (DEFAULT_SERVICE_SAMPLE_RATE) vs 1.0, on the
    two hot paths the PR instruments — the scheduling round and the piece
    recv/hash/write pipeline. Plus the raw span primitive in ns.

      trace_span_unsampled_ns        with tracer.span(): pass at rate 0
      trace_span_sampled_ns          same at rate 1 (ring export only)
      sched_round_rps_off/deflt/full find_candidate_parents_async rounds/s
      sched_round_default_overhead_pct   (off - default)/off, median of 3
      piece_pipeline_default_overhead_pct same A/B on the pooled-buffer
                                     hash-on-receive pipeline with the
                                     conductor-shaped per-piece span
      trace_sample_rate_default      the constant the pct keys are measured at

    Nulls (never 0.0) on a skipped/failed leg per the PR 6 hygiene rule."""
    import asyncio
    import random as _random

    from dragonfly2_tpu.observability import tracing
    from dragonfly2_tpu.scheduler.resource import HostType
    from dragonfly2_tpu.scheduler.service import SchedulerService

    out: dict = {
        "trace_span_unsampled_ns": None,
        "trace_span_sampled_ns": None,
        "sched_round_rps_off": None,
        "sched_round_rps_default": None,
        "sched_round_rps_full": None,
        "sched_round_default_overhead_pct": None,
        "piece_pipeline_mb_per_s_off": None,
        "piece_pipeline_mb_per_s_default": None,
        "piece_pipeline_default_overhead_pct": None,
        "trace_sample_rate_default": tracing.DEFAULT_SERVICE_SAMPLE_RATE,
    }

    # ---- span primitive: ns per with-span at rate 0 and rate 1
    # (each leg fails independently to null keys — PR 6 hygiene)
    try:
        tr_off = tracing.Tracer(service="bench", sample_rate=0.0)
        tr_on = tracing.Tracer(service="bench", sample_rate=1.0, ring_size=64)
        for tr, key in ((tr_off, "trace_span_unsampled_ns"), (tr_on, "trace_span_sampled_ns")):
            t0 = time.perf_counter()
            for _ in range(span_loops):
                with tr.span("x"):
                    pass
            out[key] = round((time.perf_counter() - t0) / span_loops * 1e9, 1)
    except Exception as e:  # noqa: BLE001 — leg skipped, keys stay null
        print(f"bench: observability span leg failed: {e!r}", file=sys.stderr)

    saved = tracing._default
    rates = (
        ("sched_round_rps_off", 0.0),
        ("sched_round_rps_default", tracing.DEFAULT_SERVICE_SAMPLE_RATE),
        ("sched_round_rps_full", 1.0),
    )
    legs: dict[str, list[float]] = {k: [] for k, _r in rates}

    try:
        # ---- scheduling round leg: the REAL serial round path (the span
        # sites land in find_candidate_parents_async + the service), same
        # pool, same rng seeds per leg, interleaved median-of-3. The default
        # tracer is swapped per leg because that is exactly what the span
        # sites consult. Setup lives INSIDE the leg's try so a pool/
        # evaluator failure nulls only these keys, not the section.
        try:
            svc = SchedulerService()
            task = svc.pool.load_or_create_task("obs-task", "http://origin/obs.bin")
            task.set_metadata(1 << 30, 4 << 20)
            children, parents_ = [], []
            for i in range(96):
                h = svc.pool.load_or_create_host(
                    f"oh{i}", f"10.9.{i // 256}.{i % 256}", f"ohost{i}",
                    download_port=8000, host_type=HostType.NORMAL,
                )
                h.upload_limit = 10_000
                p = svc.pool.create_peer(f"opeer{i}", task, h)
                for evname in ("register", "download"):
                    if p.fsm.can(evname):
                        p.fsm.fire(evname)
                if i < 8:
                    children.append(p)
                else:
                    for idx in range(8):
                        p.finished_pieces.set(idx)
                    p.bump_feat()
                    parents_.append(p)
            rng = _random.Random(7)
            for c in children:
                for p in parents_[:40]:
                    svc.topology.enqueue(c.host.id, p.host.id, rng.uniform(0.2, 30.0))
                    svc.bandwidth.observe(p.host.id, c.host.id, rng.uniform(1e8, 1e9))

            async def sched_leg(rate: float) -> float:
                from dragonfly2_tpu.scheduler.scheduling import Scheduling

                tracing._default = tracing.Tracer(
                    service="bench", sample_rate=rate, ring_size=64,
                    rng=_random.Random(11).random,
                )
                sched = Scheduling(svc.evaluator)  # fresh seeded rng: same draws per leg
                t0 = time.perf_counter()
                for r in range(rounds):
                    await sched.find_candidate_parents_async(children[r % len(children)])
                return rounds / (time.perf_counter() - t0)

            for _rep in range(3):
                for key, rate in rates:
                    legs[key].append(asyncio.run(sched_leg(rate)))
            for key, _rate in rates:
                out[key] = round(float(np.median(legs[key])), 1)
            off, deflt = out["sched_round_rps_off"], out["sched_round_rps_default"]
            out["sched_round_default_overhead_pct"] = round(
                (off - deflt) / off * 100.0, 2
            )
        except Exception as e:  # noqa: BLE001 — leg skipped, keys stay null
            print(f"bench: observability sched leg failed: {e!r}", file=sys.stderr)

        # ---- piece pipeline leg: pooled-buffer feed + hash-on-receive with
        # the conductor-shaped per-piece span around each piece, rate 0 vs
        # default, interleaved. Chunks mimic recv granularity (256 KiB).
        from dragonfly2_tpu.daemon.pipeline import PiecePipeline

        piece = 4 << 20
        npieces = max(1, (pipeline_mb << 20) // piece)
        payload = bytes(piece)
        chunk = 256 << 10

        async def pipe_leg(rate: float) -> float:
            tracing._default = tracing.Tracer(
                service="bench", sample_rate=rate, ring_size=64,
                rng=_random.Random(13).random,
            )
            tracer = tracing._default
            pipeline = PiecePipeline()
            try:
                t0 = time.perf_counter()
                for idx in range(npieces):
                    with tracer.span(
                        "conductor.piece", piece=idx, bytes=piece, path="raw"
                    ) as sp:
                        pooled = await pipeline.pool.acquire(piece)
                        pump = pipeline.hash_pump(pooled.view)
                        try:
                            t_recv = time.monotonic() if sp.sampled else 0.0
                            off_b = 0
                            while off_b < piece:
                                pooled.view[off_b : off_b + chunk] = payload[
                                    off_b : off_b + chunk
                                ]
                                off_b += chunk
                                pump.feed(off_b)
                            if sp.sampled:
                                sp.set_attr(
                                    "recv_ms",
                                    round((time.monotonic() - t_recv) * 1e3, 3),
                                )
                            await pump.finish()
                        except BaseException:
                            pump.abort()
                            raise
                        finally:
                            pooled.release()
                return (npieces * piece) / (time.perf_counter() - t0) / (1 << 20)
            finally:
                pipeline.close()

        try:
            pipe_off, pipe_deflt = [], []
            for _rep in range(3):
                pipe_off.append(asyncio.run(pipe_leg(0.0)))
                pipe_deflt.append(
                    asyncio.run(pipe_leg(tracing.DEFAULT_SERVICE_SAMPLE_RATE))
                )
            po, pd = float(np.median(pipe_off)), float(np.median(pipe_deflt))
            out["piece_pipeline_mb_per_s_off"] = round(po, 1)
            out["piece_pipeline_mb_per_s_default"] = round(pd, 1)
            out["piece_pipeline_default_overhead_pct"] = round(
                (po - pd) / po * 100.0, 2
            )
        except Exception as e:  # noqa: BLE001 — leg skipped, keys stay null
            print(f"bench: observability pipeline leg failed: {e!r}", file=sys.stderr)
    finally:
        tracing._default = saved
    return out


def bench_metrics_plane(rounds: int = 1200, sample_probes: int = 50) -> dict:
    """Cluster metrics plane cost (ISSUE 12 acceptance: recorder ≤1% of the
    round budget): interleaved SAME-RUN A/B of the REAL serial scheduling
    round with the timeseries recorder stopped vs sampling at the shipped
    2 s default, plus the deterministic decomposition — the measured cost of
    one registry walk (sample_once on the process's real default registry)
    and the overhead that IMPLIES at the default interval (cost/interval;
    the A/B pct on a 2-core CI box carries scheduler-noise of the same
    magnitude as the effect, the implied figure does not). Also pins the
    stats-frame wire cost: build time and encoded size in bytes.

      metrics_plane_round_rps_off/on     rounds/s, recorder stopped vs live
      recorder_overhead_pct              (off-on)/off from the A/B (noisy);
                                         the live leg samples at a stress
                                         cadence calibrated to fire ~8x per
                                         leg (recorder_ab_interval_s /
                                         recorder_ab_samples), an UPPER
                                         bound on the 2 s default
      recorder_sample_cost_us            median registry walk, real registry
      recorder_implied_overhead_pct      sample cost / default interval
      recorder_series                    series the walk covers
      alert_eval_cost_us                 one default-rule evaluation pass
      stats_frame_bytes / stats_frame_build_us

    Nulls (never 0.0) on a skipped/failed leg per the PR 6 hygiene rule."""
    import asyncio
    import json as _json
    import random as _random

    from dragonfly2_tpu.observability.alerts import AlertEngine
    from dragonfly2_tpu.observability.timeseries import (
        DEFAULT_INTERVAL_S,
        MetricsRecorder,
        build_stats_frame,
        default_registry,
    )
    from dragonfly2_tpu.scheduler.resource import HostType
    from dragonfly2_tpu.scheduler.service import SchedulerService

    out: dict = {
        "metrics_plane_round_rps_off": None,
        "metrics_plane_round_rps_on": None,
        "recorder_ab_interval_s": None,
        "recorder_ab_samples": None,
        "recorder_overhead_pct": None,
        "recorder_sample_cost_us": None,
        "recorder_implied_overhead_pct": None,
        "recorder_series": None,
        "recorder_interval_s": DEFAULT_INTERVAL_S,
        "alert_eval_cost_us": None,
        "stats_frame_bytes": None,
        "stats_frame_build_us": None,
    }

    # ---- A/B leg: the real serial round, recorder stopped vs live at the
    # shipped default interval, interleaved median-of-3. Runs FIRST so the
    # rounds populate the default registry's children — the deterministic
    # walk probe below then measures a REPRESENTATIVE registry, not the
    # empty one an import-only process carries.
    try:
        svc = SchedulerService()
        task = svc.pool.load_or_create_task("mp-task", "http://origin/mp.bin")
        task.set_metadata(1 << 30, 4 << 20)
        children, parents_ = [], []
        for i in range(96):
            h = svc.pool.load_or_create_host(
                f"mph{i}", f"10.8.{i // 256}.{i % 256}", f"mphost{i}",
                download_port=8000, host_type=HostType.NORMAL,
            )
            h.upload_limit = 10_000
            p = svc.pool.create_peer(f"mpp{i}", task, h)
            for evname in ("register", "download"):
                if p.fsm.can(evname):
                    p.fsm.fire(evname)
            if i < 8:
                children.append(p)
            else:
                for idx in range(8):
                    p.finished_pieces.set(idx)
                p.bump_feat()
                parents_.append(p)
        rng = _random.Random(7)
        for c in children:
            for p in parents_[:40]:
                svc.topology.enqueue(c.host.id, p.host.id, rng.uniform(0.2, 30.0))
                svc.bandwidth.observe(p.host.id, c.host.id, rng.uniform(1e8, 1e9))

        async def round_leg(interval: float | None) -> tuple[float, int]:
            """One timed leg; interval=None keeps the recorder STOPPED."""
            from dragonfly2_tpu.scheduler.scheduling import Scheduling

            leg_rec = MetricsRecorder(
                default_registry(), interval=interval or DEFAULT_INTERVAL_S
            )
            if interval is not None:
                leg_rec.start()
            try:
                sched = Scheduling(svc.evaluator)  # fresh seeded rng per leg
                t0 = time.perf_counter()
                for r in range(rounds):
                    await sched.find_candidate_parents_async(children[r % len(children)])
                    if r % 16 == 15:
                        # the serial round never suspends, so without an
                        # explicit yield the loop's call_later timers (the
                        # recorder!) starve until the leg ends — BOTH legs
                        # yield identically so the A/B stays fair
                        await asyncio.sleep(0)
                return rounds / (time.perf_counter() - t0), leg_rec.samples
            finally:
                leg_rec.stop()

        # the leg lasts well under the shipped 2 s interval at these shapes,
        # so an "on" leg at the default cadence would never actually sample
        # — a recorder-off run dressed up as an A/B. Calibrate the leg
        # recorder to fire several times per leg instead: the measured pct
        # is the overhead at a STRESS cadence, an upper bound on the 2 s
        # default (the implied figure above is the default-cadence number).
        est_rps, _ = asyncio.run(round_leg(None))
        ab_interval = max(rounds / est_rps / 8.0, 0.002)
        out["recorder_ab_interval_s"] = round(ab_interval, 4)
        offs, ons, on_samples = [], [], []
        for _rep in range(3):
            offs.append(asyncio.run(round_leg(None))[0])
            rps_on, n_samples = asyncio.run(round_leg(ab_interval))
            ons.append(rps_on)
            on_samples.append(n_samples)
        off, on = float(np.median(offs)), float(np.median(ons))
        out["metrics_plane_round_rps_off"] = round(off, 1)
        out["metrics_plane_round_rps_on"] = round(on, 1)
        out["recorder_ab_samples"] = int(np.median(on_samples))
        out["recorder_overhead_pct"] = round((off - on) / off * 100.0, 2)
    except Exception as e:  # noqa: BLE001 — leg skipped, keys stay null
        print(f"bench: metrics_plane round leg failed: {e!r}", file=sys.stderr)

    # ---- deterministic leg: one registry walk over a POPULATED registry
    # shaped like a serving scheduler's /metrics (the bench round path
    # scores through Scheduling directly, so the process's default registry
    # has no children to walk — probing it would measure an empty loop).
    # Synthetic and private: the probe must not move the process-global
    # families other tier-1 tests window.
    try:
        from dragonfly2_tpu.observability.metrics import MetricsRegistry

        sreg = MetricsRegistry(namespace="bench")
        for fi in range(8):
            fam = sreg.counter(f"c{fi}_total", labels=("k",))
            for ci in range(8):
                fam.inc(float(ci), k=f"v{ci}")
        for fi in range(6):
            h = sreg.histogram(f"h{fi}_seconds")
            for v in (0.001, 0.01, 0.1):
                h.observe(v)
        for fi in range(6):
            sreg.gauge(f"g{fi}").set(float(fi))
        rec = MetricsRecorder(sreg, interval=DEFAULT_INTERVAL_S)
        costs = []
        for _ in range(sample_probes):
            costs.append(rec.sample_once())
        cost_us = float(np.median(costs)) * 1e6
        out["recorder_sample_cost_us"] = round(cost_us, 1)
        out["recorder_implied_overhead_pct"] = round(
            cost_us / (DEFAULT_INTERVAL_S * 1e6) * 100.0, 4
        )
        out["recorder_series"] = rec.stats()["series"]
        # export=False: this ad-hoc engine must not stomp the process's
        # serving engine in the shared dragonfly_alert_active gauge
        eng = AlertEngine(rec, export=False)
        t0 = time.perf_counter()
        for _ in range(sample_probes):
            eng.evaluate_once()
        out["alert_eval_cost_us"] = round(
            (time.perf_counter() - t0) / sample_probes * 1e6, 1
        )
        t0 = time.perf_counter()
        for _ in range(sample_probes):
            frame = build_stats_frame(rec, service="bench", hostname="bench", alerts=eng)
        out["stats_frame_build_us"] = round(
            (time.perf_counter() - t0) / sample_probes * 1e6, 1
        )
        out["stats_frame_bytes"] = len(_json.dumps(frame).encode())
    except Exception as e:  # noqa: BLE001 — leg skipped, keys stay null
        print(f"bench: metrics_plane sample leg failed: {e!r}", file=sys.stderr)
    return out


def bench_ml_observability(rounds: int = 1200, probes: int = 400) -> dict:
    """ML-plane observability cost (ISSUE 15 acceptance: decision recorder +
    live drift sketch ≤1% on the real serial round at the default sample
    rate): interleaved SAME-RUN A/B of the REAL serial scheduling round with
    both instruments OFF vs ON at shipping defaults, plus the deterministic
    decomposition — the measured per-op cost of one forced decision record
    and one sketch fold, and the overhead those IMPLY at the default
    sampling strides (the A/B pct on a 2-core CI box carries scheduler noise
    of the same magnitude as the effect; the implied figure does not).

      ml_obs_round_rps_off/on           rounds/s, instruments off vs on
      ml_obs_overhead_pct               (off-on)/off from the A/B (noisy)
      ml_obs_implied_overhead_pct       (record_us*rate + sketch_us/stride)
                                        / round_us — the ≤1% acceptance
      decision_record_us                one forced (sampled-in) record
      ml_obs_decision_sample_rate       the shipped default stride
      sketch_update_ns_per_row          FeatureSketch.update per feature row
      drift_score_us                    one full per-feature PSI compute
      decision_ring_records             ring occupancy after the on legs

    Nulls (never 0.0) on a skipped/failed leg per the PR 6 hygiene rule."""
    import asyncio
    import random as _random

    from dragonfly2_tpu.models.features import FEATURE_DIM, FEATURE_NAMES
    from dragonfly2_tpu.observability.sketches import DriftDetector, FeatureSketch, psi
    from dragonfly2_tpu.scheduler.evaluator import (
        DECISION_SAMPLE_DEFAULT,
        DecisionRecorder,
        new_evaluator,
    )
    from dragonfly2_tpu.scheduler.resource import HostType
    from dragonfly2_tpu.scheduler.service import SchedulerService

    out: dict = {
        "ml_obs_round_rps_off": None,
        "ml_obs_round_rps_on": None,
        "ml_obs_overhead_pct": None,
        "ml_obs_implied_overhead_pct": None,
        "ml_obs_decision_sample_rate": DECISION_SAMPLE_DEFAULT,
        "decision_record_us": None,
        "sketch_update_ns_per_row": None,
        "drift_score_us": None,
        "decision_ring_records": None,
    }
    try:
        # the production serving shape: an ml evaluator (base fallback — no
        # model in a bench worker) whose _prepare/fallback path carries both
        # instruments; the pool mirrors the metrics_plane section's
        svc = SchedulerService(
            evaluator=new_evaluator("ml"),
            decision_sample_rate=DECISION_SAMPLE_DEFAULT,
        )
        task = svc.pool.load_or_create_task("mlo-task", "http://origin/mlo.bin")
        task.set_metadata(1 << 30, 4 << 20)
        children = []
        for i in range(96):
            h = svc.pool.load_or_create_host(
                f"mlh{i}", f"10.9.{i // 256}.{i % 256}", f"mlhost{i}",
                download_port=8000, host_type=HostType.NORMAL,
            )
            h.upload_limit = 10_000
            p = svc.pool.create_peer(f"mlp{i}", task, h)
            for evname in ("register", "download"):
                if p.fsm.can(evname):
                    p.fsm.fire(evname)
            if i < 8:
                children.append(p)
            else:
                for idx in range(8):
                    p.finished_pieces.set(idx)
                p.bump_feat()
        rng = _random.Random(11)
        for c in children:
            for h in list(svc.pool.hosts.values())[:40]:
                svc.topology.enqueue(c.host.id, h.id, rng.uniform(0.2, 30.0))
                svc.bandwidth.observe(h.id, c.host.id, rng.uniform(1e8, 1e9))

        nprng = np.random.default_rng(11)
        ref = FeatureSketch(FEATURE_DIM, names=FEATURE_NAMES)
        ref.update(nprng.random((5000, FEATURE_DIM)).astype(np.float32))

        drift_on = svc.drift
        decisions_on = svc.decisions

        async def round_leg(on: bool) -> float:
            from dragonfly2_tpu.scheduler.scheduling import Scheduling

            if on:
                svc.evaluator.decisions = decisions_on
                svc.evaluator.drift = drift_on
                drift_on.set_reference(ref, version="bench")
            else:
                svc.evaluator.decisions = None
                svc.evaluator.drift = None
            sched = Scheduling(svc.evaluator)  # fresh seeded rng per leg
            t0 = time.perf_counter()
            for r in range(rounds):
                await sched.find_candidate_parents_async(children[r % len(children)])
            return rounds / (time.perf_counter() - t0)

        offs, ons = [], []
        for _rep in range(3):
            offs.append(asyncio.run(round_leg(False)))
            ons.append(asyncio.run(round_leg(True)))
        off, on = float(np.median(offs)), float(np.median(ons))
        out["ml_obs_round_rps_off"] = round(off, 1)
        out["ml_obs_round_rps_on"] = round(on, 1)
        out["ml_obs_overhead_pct"] = round((off - on) / off * 100.0, 2)
        out["decision_ring_records"] = decisions_on.stats()["records"]

        # ---- deterministic decomposition ----
        feats = nprng.random((40, FEATURE_DIM)).astype(np.float32)
        scores = nprng.random(40).astype(np.float32)
        child = children[0]
        cands = [p for p in task.peers() if p is not child][:40]
        rec = DecisionRecorder(sample_rate=1.0, clock=svc.clock)
        t0 = time.perf_counter()
        for _ in range(probes):
            rec.maybe_record(child, cands, feats, scores)
        record_us = (time.perf_counter() - t0) / probes * 1e6
        out["decision_record_us"] = round(record_us, 2)

        sk = FeatureSketch(FEATURE_DIM, names=FEATURE_NAMES)
        t0 = time.perf_counter()
        for _ in range(probes):
            sk.update(feats)
        sketch_us = (time.perf_counter() - t0) / probes * 1e6
        out["sketch_update_ns_per_row"] = round(sketch_us / len(feats) * 1e3, 1)

        live = FeatureSketch(FEATURE_DIM, names=FEATURE_NAMES)
        live.update(nprng.random((2000, FEATURE_DIM)).astype(np.float32))
        t0 = time.perf_counter()
        for _ in range(probes):
            psi(ref, live)
        out["drift_score_us"] = round((time.perf_counter() - t0) / probes * 1e6, 2)

        # the acceptance figure: per-round cost at the DEFAULT strides over
        # the measured uninstrumented round (A/B-noise-free by construction)
        round_us = 1e6 / off
        stride = DriftDetector().sample_stride
        implied = (
            record_us * DECISION_SAMPLE_DEFAULT + sketch_us / stride
        ) / round_us * 100.0
        out["ml_obs_implied_overhead_pct"] = round(implied, 3)

        # ---- batched shadow scoring (ISSUE 18 satellite): the candidate
        # model's per-round cost at sample rate 1.0, sync per-round leg vs
        # the multi-round batched FFI entry the native round driver feeds
        # (_shadow_score_batch). Needs the native toolchain; nulls otherwise.
        out["shadow_round_us_serial"] = None
        out["shadow_round_us_batched"] = None
        out["shadow_batched_recovery_pct"] = None
        try:
            import tempfile as _tempfile

            from dragonfly2_tpu.native import NativeScorer
            from dragonfly2_tpu.sim.engine import _synthetic_scorer_artifact

            with _tempfile.TemporaryDirectory() as td:
                art = _synthetic_scorer_artifact(
                    os.path.join(td, "shadow.dfsc"), n_nodes=256, seed=3
                )
                shadow_scorer = NativeScorer(art)
                try:
                    node_index = {
                        h.id: j % 256
                        for j, h in enumerate(svc.pool.hosts.values())
                    }
                    svc.evaluator.attach_candidate(
                        shadow_scorer, node_index,
                        version="bench-shadow", sample_rate=1.0,
                    )
                    batch = 8
                    items = [
                        (children[r % len(children)], cands, feats, scores)
                        for r in range(batch)
                    ]
                    svc.evaluator._shadow_score_batch(items)  # warm
                    for it in items:
                        svc.evaluator._shadow_score(*it)
                    reps = max(probes // batch, 8)
                    ser_t, bat_t = [], []
                    for _rep in range(3):  # interleaved, same rounds
                        t0 = time.perf_counter()
                        for _ in range(reps):
                            for it in items:
                                svc.evaluator._shadow_score(*it)
                        ser_t.append(
                            (time.perf_counter() - t0) / (reps * batch) * 1e6
                        )
                        t0 = time.perf_counter()
                        for _ in range(reps):
                            svc.evaluator._shadow_score_batch(items)
                        bat_t.append(
                            (time.perf_counter() - t0) / (reps * batch) * 1e6
                        )
                    ser_us = float(np.median(ser_t))
                    bat_us = float(np.median(bat_t))
                    out["shadow_round_us_serial"] = round(ser_us, 2)
                    out["shadow_round_us_batched"] = round(bat_us, 2)
                    out["shadow_batched_recovery_pct"] = round(
                        (ser_us - bat_us) / ser_us * 100.0, 1
                    )
                finally:
                    svc.evaluator.detach_candidate()
                    shadow_scorer.close()
        except Exception as e:  # noqa: BLE001 — shadow keys stay null
            print(f"bench: shadow batch leg skipped: {e!r}", file=sys.stderr)
        svc.close()
    except Exception as e:  # noqa: BLE001 — leg skipped, keys stay null
        print(f"bench: ml_observability leg failed: {e!r}", file=sys.stderr)
    return out


def bench_round_loop(
    rounds: int = 1200, batch: int = 8, candidates: int = 40, hosts: int = 256,
) -> dict:
    """Native round driver vs the serial Python round loop (ISSUE 18): the
    SAME batches of full scheduling rounds (sample + filter + score + stable
    top-k) through `find_candidate_parents_batch` (Python: evaluate_many +
    argsort) and `find_candidate_parents_batch_native` (snapshot under the
    lock → ONE GIL-released df_round_drive FFI → commit), interleaved
    same-run median-of-3 with identical rng draws per leg.

      native_rounds_per_s / serial_rounds_per_s   the A/B medians
      speedup                                     native / serial
      ffi_calls_per_round                         drive FFI calls / native
                                                  rounds (1/batch when the
                                                  driver carries every round)
      commit_ms                                   Python tail per ROUND after
                                                  the FFI returns (outs +
                                                  records + shadow), in ms
      native_coverage                             natively-scored fraction —
                                                  a silent fallback would
                                                  void the A/B
      equivalent                                  parent lists byte-identical
                                                  across the legs
      mirror_rounds_per_s / mirror_speedup        ISSUE 19 third leg: the
                                                  delta-fed peer-table mirror
                                                  (no Python snapshot leg);
                                                  speedup vs the SERIAL loop
      mirror_coverage                             fraction of mirror-leg
                                                  rounds the mirror drove
                                                  (native + stale-revalidated)
      mirror_full_syncs                           MUST stay 1 — the attach
                                                  export is the only full
                                                  export; steady state is
                                                  deltas or the A/B is void
      mirror_equivalent                           mirror parents byte-equal
                                                  to the serial leg's

    Needs the C++ toolchain + a synthetic scorer artifact (no jax). Nulls
    (never 0.0) when unavailable — VERDICT #8 bench hygiene."""
    import random as _random
    import tempfile

    out: dict = {
        "native_rounds_per_s": None,
        "serial_rounds_per_s": None,
        "speedup": None,
        "ffi_calls_per_round": None,
        "commit_ms": None,
        "native_coverage": None,
        "equivalent": None,
        "mirror_rounds_per_s": None,
        "mirror_speedup": None,
        "mirror_coverage": None,
        "mirror_full_syncs": None,
        "mirror_equivalent": None,
    }
    try:
        from dragonfly2_tpu.native import NativeScorer
        from dragonfly2_tpu.scheduler.evaluator import new_evaluator
        from dragonfly2_tpu.scheduler.resource import HostType
        from dragonfly2_tpu.scheduler.scheduling import Scheduling
        from dragonfly2_tpu.scheduler.service import SchedulerService
        from dragonfly2_tpu.sim.engine import _synthetic_scorer_artifact

        with tempfile.TemporaryDirectory() as td:
            scorer = NativeScorer(
                _synthetic_scorer_artifact(
                    os.path.join(td, "rl.dfsc"), n_nodes=1024, seed=5
                )
            )
            ev = new_evaluator("ml")
            svc = SchedulerService(evaluator=ev)
            task = svc.pool.load_or_create_task("rl-task", "http://origin/rl.bin")
            task.set_metadata(1 << 30, 4 << 20)
            children, all_hosts = [], []
            for i in range(hosts):
                h = svc.pool.load_or_create_host(
                    f"rlh{i}", f"10.7.{i // 256}.{i % 256}", f"rlhost{i}",
                    download_port=8000, host_type=HostType.NORMAL,
                    idc=f"idc-{i % 3}", location=f"r{i % 2}|z{i % 5}",
                )
                h.upload_limit = 10_000
                all_hosts.append(h)
                p = svc.pool.create_peer(f"rlp{i}", task, h)
                for evname in ("register", "download"):
                    if p.fsm.can(evname):
                        p.fsm.fire(evname)
                if i < batch:
                    children.append(p)
                else:
                    for idx in range(8):
                        p.finished_pieces.set(idx)
                    p.bump_feat()
            rng = _random.Random(13)
            for c in children:
                for h in all_hosts[:64]:
                    svc.topology.enqueue(c.host.id, h.id, rng.uniform(0.2, 30.0))
                    svc.bandwidth.observe(h.id, c.host.id, rng.uniform(1e8, 1e9))
            node_index = {h.id: i % 1024 for i, h in enumerate(all_hosts)}
            ev.attach_scorer(scorer, node_index, version="bench-round-loop")

            reqs = [(c, set()) for c in children]
            n_batches = max(rounds // batch, 1)

            # equivalence spot-check: same seed, same pool → byte-identical
            # parent lists (the tests pin this exhaustively; the bench only
            # guards against a silently-voided A/B)
            s_ser, s_nat = Scheduling(ev), Scheduling(ev)
            a = s_ser.find_candidate_parents_batch(list(reqs))
            b = s_nat.find_candidate_parents_batch_native(list(reqs))
            out["equivalent"] = (
                [[p.id for p in r] for r in a] == [[p.id for p in r] for r in b]
            )

            # ISSUE 19 third leg: attach the delta-fed peer-table mirror (one
            # full export now; everything after rides the mutation hooks) and
            # spot-check IT against the serial leg too
            client = svc.enable_native_mirror()
            if client is not None and client.ready:
                s_mir = Scheduling(ev)
                s_mir._mirror = client  # dflint: disable=DF036 bench A/B rig: fresh leg opts into the one attached client
                m = s_mir.find_candidate_parents_batch_native(list(reqs))
                out["mirror_equivalent"] = (
                    [[p.id for p in r] for r in a]
                    == [[p.id for p in r] for r in m]
                )

            # count drive FFI calls + time the post-FFI commit tail via a
            # class-level probe (bench-only; restored in finally)
            drive_stats = {"calls": 0, "t_ret": 0.0}
            orig_bound = NativeScorer.drive_rounds_bound

            def _probed(self, binding, **kw):
                drive_stats["calls"] += 1
                try:
                    return orig_bound(self, binding, **kw)
                finally:
                    drive_stats["t_ret"] = time.perf_counter()

            NativeScorer.drive_rounds_bound = _probed
            try:
                ser_rates, nat_rates, mir_rates = [], [], []
                commit_s = 0.0
                served0 = mirror_served = 0
                for _rep in range(3):
                    sched = Scheduling(ev)  # fresh seeded rng: same draws
                    t0 = time.perf_counter()
                    for _ in range(n_batches):
                        sched.find_candidate_parents_batch(reqs)
                    ser_rates.append(
                        n_batches * batch / (time.perf_counter() - t0)
                    )
                    sched = Scheduling(ev)
                    served0 -= sched.native_rounds_served
                    t0 = time.perf_counter()
                    for _ in range(n_batches):
                        drive_stats["t_ret"] = 0.0
                        sched.find_candidate_parents_batch_native(reqs)
                        if drive_stats["t_ret"]:
                            commit_s += time.perf_counter() - drive_stats["t_ret"]
                    nat_rates.append(
                        n_batches * batch / (time.perf_counter() - t0)
                    )
                    served0 += sched.native_rounds_served
                    if client is not None and client.ready:
                        sched = Scheduling(ev)
                        sched._mirror = client  # dflint: disable=DF036 bench A/B rig: fresh leg opts into the one attached client
                        t0 = time.perf_counter()
                        for _ in range(n_batches):
                            sched.find_candidate_parents_batch_native(reqs)
                        mir_rates.append(
                            n_batches * batch / (time.perf_counter() - t0)
                        )
                        mirror_served += (
                            sched.mirror_rounds_served
                            + sched.mirror_stale_rounds
                        )
            finally:
                NativeScorer.drive_rounds_bound = orig_bound
            nat = float(np.median(nat_rates))
            ser = float(np.median(ser_rates))
            total_native_rounds = 3 * n_batches * batch
            out["native_rounds_per_s"] = round(nat, 1)
            out["serial_rounds_per_s"] = round(ser, 1)
            out["speedup"] = round(nat / ser, 3)
            out["native_coverage"] = round(served0 / total_native_rounds, 3)
            out["ffi_calls_per_round"] = round(
                drive_stats["calls"] / max(served0, 1), 3
            )
            out["commit_ms"] = round(commit_s / total_native_rounds * 1e3, 4)
            if mir_rates:
                mir = float(np.median(mir_rates))
                out["mirror_rounds_per_s"] = round(mir, 1)
                out["mirror_speedup"] = round(mir / ser, 3)
                out["mirror_coverage"] = round(
                    mirror_served / total_native_rounds, 3
                )
                out["mirror_full_syncs"] = int(client.stats()["full_syncs"])
            svc.close()
            scorer.close()
    except Exception as e:  # noqa: BLE001 — section skipped, keys stay null
        print(f"bench: round_loop leg failed: {e!r}", file=sys.stderr)
    return out


def bench_swarm_sim(
    wall_budget_s: float = 25.0,
    start_peers: int = 4_000,
    max_peers: int = 64_000,
) -> dict:
    """Swarm-simulator throughput + the scenario-level properties (ISSUE 14
    14th section): how many peers the discrete-event engine can simulate
    against the REAL scheduler/evaluator/federation objects inside a wall
    budget, at what events/s, with the flash-crowd cluster properties
    reported alongside.

      swarm_sim_events_per_sec        engine throughput (real control-plane
                                      work per event: scheduling rounds,
                                      batched piece reports, gossip)
      swarm_sim_peers                 peers simulated in the largest rung
                                      that fit the wall budget (ladder:
                                      doubles from start_peers)
      swarm_sim_time_compression      virtual seconds per wall second
      swarm_sim_flash_origin_egress_ratio
                                      max over regions of origin bytes /
                                      task size — the O(1)-egress property
                                      (a number NEAR 1.0 means the crowd hit
                                      the origin ~once per region)
      swarm_sim_same_region_frac      placement locality at scheduling time
      swarm_sim_completed_frac        peers that finished their download
      swarm_sim_fed_convergence_virtual_s
                                      virtual time until EVERY ring member
                                      held federation-merged remote edges

    Nulls (never 0.0) when a rung/leg fails, per the PR 6 hygiene rule."""
    out: dict = {
        "swarm_sim_events_per_sec": None,
        "swarm_sim_peers": None,
        "swarm_sim_events": None,
        "swarm_sim_wall_s": None,
        "swarm_sim_virtual_s": None,
        "swarm_sim_time_compression": None,
        "swarm_sim_flash_origin_egress_ratio": None,
        "swarm_sim_same_region_frac": None,
        "swarm_sim_completed_frac": None,
        "swarm_sim_fed_convergence_virtual_s": None,
        "swarm_sim_wall_budget_s": wall_budget_s,
    }
    try:
        from dragonfly2_tpu.sim.scenarios import flash_crowd

        best = None
        peers = start_peers
        spent = 0.0
        while True:
            sc = flash_crowd(peers=peers, telemetry_dir=None)
            try:
                rep = sc.sim.run()
                sc.check(rep)
            finally:
                sc.sim.close()
            best = (peers, rep, sc.content_length)
            spent += rep.wall_s
            # double while the NEXT rung (≈2x wall) still fits the budget
            if peers >= max_peers or spent + 2.0 * rep.wall_s > wall_budget_s:
                break
            peers *= 2
        peers, rep, content = best
        out["swarm_sim_events_per_sec"] = rep.events_per_sec
        out["swarm_sim_peers"] = peers
        out["swarm_sim_events"] = rep.events
        out["swarm_sim_wall_s"] = rep.wall_s
        out["swarm_sim_virtual_s"] = rep.virtual_s
        out["swarm_sim_time_compression"] = rep.time_compression
        out["swarm_sim_flash_origin_egress_ratio"] = round(
            max(rep.origin_egress_bytes.values(), default=0) / content, 3
        )
        out["swarm_sim_same_region_frac"] = rep.same_region_frac
        out["swarm_sim_completed_frac"] = round(rep.completed / max(rep.peers, 1), 4)
        fed = rep.federation or {}
        out["swarm_sim_fed_convergence_virtual_s"] = fed.get("first_remote_edge_s")
    except Exception as e:  # noqa: BLE001 — section skipped, keys stay null
        print(f"bench: swarm_sim section failed: {e!r}", file=sys.stderr)
    return out


def bench_overload(peers: int = 2_000, overload_factor: float = 4.0) -> dict:
    """Goodput under overload, shedding ON vs OFF (ISSUE 17 brownout A/B):
    the same flash crowd at `overload_factor` x the scheduler's modeled
    register capacity, run twice against the REAL scheduler — once with the
    brownout ladder attached (typed overloaded answers + retry_after spread
    the comeback) and once without (modeled client timeouts amplify into a
    retry storm). The scenario is scale-invariant in time (fixed burst
    window, per-register cost derived from peers), so this reduced-peers
    bench arm exercises the same dynamics as the 10^4-peer acceptance run.

      overload_goodput_ratio          ON/OFF completions — the headline;
                                      >= 2.0 at 4x overload is acceptance
      overload_goodput_on_frac        completed/peers with the ladder
      overload_goodput_off_frac       completed/peers without (the storm)
      overload_admitted_p99_ms_on     admitted-round p99 with shedding —
                                      bounded comeback, not infinite queueing
      overload_max_level_on           highest rung reached (4 = admission)
      overload_refused_on             typed overloaded answers sent
      overload_retry_storm_off        retries the unshedded arm burned

    Nulls (never 0.0) when an arm fails, per the PR 6 hygiene rule."""
    out: dict = {
        "overload_peers": None,
        "overload_factor": None,
        "overload_goodput_ratio": None,
        "overload_goodput_on_frac": None,
        "overload_goodput_off_frac": None,
        "overload_admitted_p99_ms_on": None,
        "overload_max_level_on": None,
        "overload_refused_on": None,
        "overload_retry_storm_off": None,
    }
    try:
        from dragonfly2_tpu.sim.scenarios import overload_flash

        reps: dict = {}
        for arm, shed in (("on", True), ("off", False)):
            sc = overload_flash(
                peers=peers, overload_factor=overload_factor,
                shedding=shed, telemetry_dir=None,
            )
            try:
                rep = sc.sim.run()
                sc.check(rep)  # the ON arm's scenario invariants must hold
            finally:
                sc.sim.close()
            reps[arm] = rep
        on, off = reps["on"], reps["off"]
        out["overload_peers"] = peers
        out["overload_factor"] = overload_factor
        out["overload_goodput_ratio"] = round(on.completed / max(off.completed, 1), 2)
        out["overload_goodput_on_frac"] = round(on.completed / max(peers, 1), 4)
        out["overload_goodput_off_frac"] = round(off.completed / max(peers, 1), 4)
        out["overload_admitted_p99_ms_on"] = on.admitted_p99_ms
        out["overload_max_level_on"] = (on.degradation or {}).get("max_level")
        out["overload_refused_on"] = on.overload_refused
        out["overload_retry_storm_off"] = off.overload_retries
    except Exception as e:  # noqa: BLE001 — section skipped, keys stay null
        print(f"bench: overload section failed: {e!r}", file=sys.stderr)
    return out


def main() -> None:
    from dragonfly2_tpu.utils import jaxenv

    if os.environ.get("DF_BENCH_FORCE_CPU"):
        jaxenv.pin_host_cpu()
    jaxenv.enable_compile_cache()
    device = jaxenv.device_report()
    errors: dict[str, str] = {}

    def run_section(name: str, fn, default):
        """Each section is independently timed out and error-trapped: one
        broken path must not cost the round its entire perf evidence.
        `default` is None-shaped (never zeros): a section that failed or
        skipped emits null in the JSON, so a broken path can never read as a
        measured 0.0 regression (VERDICT #8 bench hygiene)."""
        try:
            with _deadline(_SECTION_TIMEOUT_S):
                return fn()
        except BaseException as e:  # noqa: BLE001 — even SystemExit must not kill the JSON
            errors[name] = f"{type(e).__name__}: {str(e)[:200]}"
            print(f"bench: section {name} failed: {errors[name]}", file=sys.stderr, flush=True)
            return default

    def _r(x, nd=1):
        """null-safe round: skipped sections carry None through to the JSON."""
        return None if x is None else round(x, nd)

    jax_calls_per_sec, jax_p50_ms, jax_multi_rps = run_section(
        "jax_scoring", bench_scoring, (None, None, None)
    )
    (
        native_calls_per_sec,
        native_p50_ms,
        native_single_rps,
        native_multi_call_p50_ms,
    ) = run_section("native_scoring", bench_native_scoring, (None, None, None, None))
    fanout_mbps, disk_mbps = run_section("checkpoint_fanout", bench_checkpoint_fanout, (None, None))
    piece_pipeline = run_section("piece_pipeline", bench_piece_pipeline, {})
    dataset_build = run_section("dataset_build", bench_dataset_build, {})
    control_plane = run_section("control_plane", bench_control_plane, {})
    observability = run_section("observability", bench_observability, {})
    metrics_plane = run_section("metrics_plane", bench_metrics_plane, {})
    ml_observability = run_section("ml_observability", bench_ml_observability, {})
    round_loop = run_section("round_loop", bench_round_loop, {})
    federation = run_section("federation", bench_federation, {})
    swarm_sim = run_section("swarm_sim", bench_swarm_sim, {})
    overload = run_section("overload", bench_overload, {})
    mlp_sps, mlp_mse = run_section("mlp_train", bench_mlp_train, (None, None))
    serving = run_section("evaluator_serving", bench_evaluator_serving, {})
    # headline = the production serving path: native C++ scorer when the
    # toolchain exists (config 5 "no GPU"), else the jitted JAX fallback
    # (the headline `value` stays numeric — the driver parses it — but the
    # per-section keys below are null when their section skipped)
    calls_per_sec = max(jax_calls_per_sec or 0.0, native_calls_per_sec or 0.0)
    skipped = ["native_scoring"] if native_calls_per_sec is None and "native_scoring" not in errors else []
    extra = {
        "native_scoring_calls_per_sec": _r(native_calls_per_sec, 1),
        "native_scoring_p50_ms": _r(native_p50_ms, 4),
        "native_single_round_calls_per_sec": _r(native_single_rps, 1),
        "native_rounds_per_ffi_call": _ROUNDS_PER_FFI_CALL,
        "native_multi_call_p50_ms": _r(native_multi_call_p50_ms, 4),
        "jax_scoring_calls_per_sec": _r(jax_calls_per_sec, 1),
        "jax_scoring_p50_ms": _r(jax_p50_ms, 3),
        "jax_scoring_multi_calls_per_sec": _r(jax_multi_rps, 1),
        # north-star config 1: MLP bandwidth predictor on the scheduler host
        # CPU (its own deployment hardware)
        "mlp_train_steps_per_sec_cpu": _r(mlp_sps, 2),
        "mlp_train_mse": _r(mlp_mse, 5),
        "checkpoint_fanout_mb_per_s": _r(fanout_mbps, 1),
        # the fetch side writes every byte to its piece store, so raw disk
        # write throughput on the same filesystem is its hard ceiling — when
        # the two are close, the remaining fan-out bottleneck is the disk
        "checkpoint_fanout_disk_write_ceiling_mb_per_s": _r(disk_mbps, 1),
        "checkpoint_fanout_note": (
            "store on tmpfs (container disk throttling is 8-4000 MB/s "
            "run-to-run noise); big pieces ride the zero-copy pipeline "
            "(daemon/pipeline.py): pooled recv_into buffers, sha256 "
            "hash-on-receive on a second core, writer-thread store writes "
            "— the piece_pipeline_* keys decompose the per-stage budget"
        ),
        "piece_pipeline_mb_per_s": piece_pipeline.get("pipelined_mb_per_s"),
        # TLS cost of secure-by-default measured on the FULL piece pipeline
        # (recv+hash+write, fast-path transport, autoselected cipher,
        # interleaved A/B) — null when the section skipped or no CA backend
        "piece_pipeline_tls_overhead_pct": piece_pipeline.get("tls_overhead_pct"),
        "piece_tls_cipher": piece_pipeline.get("tls_cipher_policy"),
        "piece_tls_resumption_hit_rate": piece_pipeline.get("tls_resumption_hit_rate"),
        # multi-parent striped fetch over the real wire (rate-capped
        # parents = the per-peer serving-ceiling story)
        "piece_striped_speedup": piece_pipeline.get("striped_speedup"),
        "piece_write_behind_decision": piece_pipeline.get("write_behind_decision"),
        "piece_pipeline_stages": piece_pipeline or "skipped",
        # the trainer's record plane: vectorized telemetry→dataset ingest vs
        # the rowloop reference (interleaved median-of-3), plus the
        # incremental chunk-fold rate and the train_close→Dataset latency
        "dataset_build_rows_per_sec": dataset_build.get("dataset_build_rows_per_sec"),
        "dataset_build": dataset_build or "skipped",
        # the scheduler control plane decomposed (prepare/score/report legs,
        # interleaved same-run A/B vs the r05 shapes) — distinct from the
        # native-FFI serving section below, which needs the C++ toolchain
        "control_plane_full_round_rps": control_plane.get("full_round_rps"),
        "control_plane": control_plane or "skipped",
        # tracing cost A/B (ISSUE 9): default-sample-rate overhead on the
        # scheduling round and the piece pipeline, interleaved same-run;
        # acceptance is ≤5% at the shipped default and ≈0 disabled
        "observability_sched_round_overhead_pct": observability.get(
            "sched_round_default_overhead_pct"
        ),
        "observability_piece_pipeline_overhead_pct": observability.get(
            "piece_pipeline_default_overhead_pct"
        ),
        "observability": observability or "skipped",
        # cluster metrics plane (ISSUE 12): recorder A/B on the real round
        # (acceptance ≤1% — the deterministic implied figure; the A/B pct
        # carries 2-core scheduler noise), walk cost, stats-frame size
        "metrics_plane_recorder_overhead_pct": metrics_plane.get(
            "recorder_implied_overhead_pct"
        ),
        "metrics_plane_stats_frame_bytes": metrics_plane.get("stats_frame_bytes"),
        "metrics_plane": metrics_plane or "skipped",
        # ML-plane observability (ISSUE 15): decision recorder + live drift
        # sketch cost on the real serial round (acceptance ≤1% implied at
        # the default sample rate; the A/B pct carries 2-core noise)
        "ml_observability_overhead_pct": ml_observability.get(
            "ml_obs_implied_overhead_pct"
        ),
        "ml_observability_decision_record_us": ml_observability.get(
            "decision_record_us"
        ),
        "ml_observability": ml_observability or "skipped",
        # native round loop (ISSUE 18): whole scheduling rounds through ONE
        # df_round_drive FFI vs the Python batch leg, same draws, interleaved
        # same-run; nulls (never 0.0) when the C++ toolchain is absent
        "round_loop_native_rounds_per_s": round_loop.get("native_rounds_per_s"),
        "round_loop_speedup": round_loop.get("speedup"),
        "round_loop": round_loop or "skipped",
        # scheduler federation (ISSUE 10): swarm rounds/s through the
        # 2-scheduler ring, one-hop topology-sync convergence, watermarked
        # payload counter-assert, and ring re-shard churn bounds
        "federation_swarm_rounds_per_sec": federation.get("swarm_rps_2sched"),
        "federation_sync_convergence_ms": federation.get("sync_convergence_ms"),
        "federation": federation or "skipped",
        # discrete-event swarm simulator (ISSUE 14): peers simulated against
        # the real control plane inside the wall budget, events/s, and the
        # flash-crowd origin-egress / federation-convergence properties
        "swarm_sim_events_per_sec": swarm_sim.get("swarm_sim_events_per_sec"),
        "swarm_sim_peers": swarm_sim.get("swarm_sim_peers"),
        "swarm_sim": swarm_sim or "skipped",
        # graceful degradation under overload (ISSUE 17): brownout-ladder
        # A/B at 4x register overload — goodput with shedding over goodput
        # without (the retry storm); >= 2.0 is the acceptance bar
        "overload_goodput_ratio": overload.get("overload_goodput_ratio"),
        "overload_admitted_p99_ms_on": overload.get("overload_admitted_p99_ms_on"),
        "overload": overload or "skipped",
        **device,
        **serving,
    }
    if skipped:
        extra["skipped"] = skipped
    if errors:
        extra["errors"] = errors
    print(_payload(calls_per_sec, extra), flush=True)
    sys.exit(1 if any(name in errors for name in DEVICE_SECTIONS) else 0)


if __name__ == "__main__":
    if os.environ.get("DF_BENCH_STAGE") == "worker":
        main()
    else:
        _supervise()
