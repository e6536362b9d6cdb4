"""Load generator for a peer daemon / cluster.

Reference equivalent: test/tools/stress (Makefile:303-309) — a concurrency
driver that hammers a target and reports latency percentiles. Here it drives
the daemon's download RPC with N concurrent workers for a duration (or a
fixed request count) and prints one JSON line: throughput, latency
p50/p90/p99, error count — the shape CI perf gates consume.

    python -m dragonfly2_tpu.cli.dfstress http://origin/file \\
        --sock /tmp/df.sock --concurrency 16 --duration 10

Each request downloads the SAME task (reuse fast path after the first), so
the tool measures control-plane + storage round-trip throughput, not origin
bandwidth; pass --unique to append a counter query param and force distinct
tasks (piece engine + scheduler path per request).

Two further modes:

    --scoring   drive the ml evaluator serving stack (rounds/s, latency,
                thread-scaling legs — see run_scoring_stress)
    --swarm     hundreds of simulated lightweight peers running the full
                control-plane round over the real wire against a scheduler
                FEDERATION (--schedulers a:1,b:2): aggregate rounds/s plus
                per-scheduler load share — the ring + gossip scale scenario

    python -m dragonfly2_tpu.cli.dfstress --swarm \\
        --schedulers 127.0.0.1:9000,127.0.0.1:9001 --peers 200 --duration 10
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

import numpy as np

from dragonfly2_tpu.cli.dfget import DEFAULT_SOCK
from dragonfly2_tpu.rpc.core import RpcClient


async def run_stress(args: argparse.Namespace) -> dict:
    client = RpcClient(args.sock, timeout=args.timeout)
    latencies: list[float] = []
    errors = 0
    counter = 0
    stop_at = time.monotonic() + args.duration if args.count is None else None

    def next_url() -> str | None:
        # no await points: atomic on the single-threaded event loop
        nonlocal counter
        if args.count is not None and counter >= args.count:
            return None
        if stop_at is not None and time.monotonic() >= stop_at:
            return None
        counter += 1
        if args.unique:
            sep = "&" if "?" in args.url else "?"
            return f"{args.url}{sep}stress={counter}"
        return args.url

    async def worker(priority: float) -> None:
        nonlocal errors
        while True:
            url = next_url()
            if url is None:
                return
            t0 = time.monotonic()
            try:
                await client.call(  # dflint: disable=DF025 load generator: one RPC per iteration IS the workload being measured
                    "download",
                    {"url": url, "output": None, "priority": priority},
                    timeout=args.timeout,
                )
                latencies.append(time.monotonic() - t0)
            except Exception:
                errors += 1

    # mixed tenant load: --priority-split N gives the first N workers the
    # high priority (--priority, default 3.0) and the rest weight 1.0, so the
    # traffic shaper's weighted fairness is drivable from the CLI (getattr:
    # programmatic callers predating the flags keep working)
    split = min(getattr(args, "priority_split", 0), args.concurrency)
    weights = [getattr(args, "priority", 1.0)] * split + [1.0] * (args.concurrency - split)
    t0 = time.monotonic()
    await asyncio.gather(*(worker(w) for w in weights))
    elapsed = time.monotonic() - t0
    await client.close()

    lat = np.asarray(latencies) * 1000.0
    return {
        "metric": "daemon_download_rps",
        "value": round(len(latencies) / max(elapsed, 1e-9), 1),
        "unit": "requests/s",
        "extra": {
            "requests": len(latencies),
            "errors": errors,
            "elapsed_s": round(elapsed, 2),
            "concurrency": args.concurrency,
            "priority_split": split,
            "unique_tasks": bool(args.unique),
            "p50_ms": round(float(np.percentile(lat, 50)), 2) if len(lat) else None,
            "p90_ms": round(float(np.percentile(lat, 90)), 2) if len(lat) else None,
            "p99_ms": round(float(np.percentile(lat, 99)), 2) if len(lat) else None,
        },
    }


async def run_scoring_stress(args: argparse.Namespace) -> dict:
    """Serving-SLO stress (VERDICT r4 Next #6, sharded in ISSUE 7): drive
    scheduling rounds through the LIVE evaluator stack on a real
    SchedulerService resource pool and report rounds/s + p50/p99 for THREE
    serving shapes, interleaved same-run median-of-3 (2-core box
    discipline — this container drifts ±30% run-to-run):

      microbatch    the r05 single-loop path: concurrent rounds coalesce in
                    MicroBatchScorer into one multi-round FFI call
      workers=1/2   the round dispatcher: each round's assembly+FFI runs
                    whole on a worker thread with its OWN native handle
                    (ScorerHandlePool; scorer.cc serializes a shared handle)

    The headline (`value`) is the BEST-measured serving config on this
    host, named in `eval_best_config` (on wide hosts that should be the
    dispatcher; on this 2-core box the loop's own glue + one worker already
    saturate the GIL, so workers1 or microbatch typically wins); the
    workers=1 leg isolates the thread-scaling factor from the executor-hop
    overhead both dispatcher legs pay. full_round_rps covers the complete
    round (sample + filters + score + top-4), again best-of named in
    `full_round_best_config` with both legs reported."""
    import tempfile
    from pathlib import Path

    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.native import (
        MicroBatchScorer,
        NativeScorer,
        ScorerHandlePool,
        export_scorer_artifact,
    )
    from dragonfly2_tpu.scheduler.evaluator import new_evaluator
    from dragonfly2_tpu.scheduler.resource import HostType
    from dragonfly2_tpu.scheduler.scheduling import RoundDispatcher, usable_cpu_count
    from dragonfly2_tpu.scheduler.service import SchedulerService, TaskMeta
    from dragonfly2_tpu.trainer import synthetic, train_gnn

    n_nodes = 1024
    cluster = synthetic.make_cluster(
        num_nodes=n_nodes, num_neighbors=16, num_pairs=4096, seed=7
    )
    cfg = train_gnn.GNNTrainConfig()
    model = train_gnn.make_model(cfg)
    state = train_gnn.init_state(cfg, cluster.graph, rng_seed=7)
    g = jax.tree.map(jnp.asarray, cluster.graph)
    z = np.asarray(
        jax.jit(lambda p, gg: model.apply(p, gg, method=model.embed))(state.params, g)
    )
    with tempfile.TemporaryDirectory() as td:
        scorer = NativeScorer(export_scorer_artifact(state.params, z, Path(td) / "s.dfsc"))
        ev = new_evaluator("ml")
        svc = SchedulerService(evaluator=ev)

        # a live pool: one task, candidate parents with pieces, child peers
        meta = TaskMeta("stress-task", "http://origin/stress.bin")
        n_hosts = args.hosts
        hosts = []
        for i in range(n_hosts):
            h = svc.pool.load_or_create_host(
                f"h{i}", f"10.0.{i // 256}.{i % 256}", f"host{i}",
                download_port=8000,
                host_type=HostType.NORMAL, idc=f"idc-{i % 3}",
                location=f"r{i % 2}|z{i % 5}",
            )
            h.upload_limit = 10_000  # saturating the slots is not the point here
            hosts.append(h)
        task = svc.pool.load_or_create_task(meta.task_id, meta.url)
        task.set_metadata(1 << 30, 4 << 20)
        children = []
        parents = []
        for i, h in enumerate(hosts):
            p = svc.pool.create_peer(f"peer{i}", task, h)
            for evname in ("register", "download"):
                if p.fsm.can(evname):
                    p.fsm.fire(evname)
            if i < args.concurrency:
                children.append(p)
            else:
                for idx in range(8):
                    p.finished_pieces.set(idx)
                p.bump_feat()
                parents.append(p)
        node_index = {h.id: i % n_nodes for i, h in enumerate(hosts)}
        mb = MicroBatchScorer(scorer)
        handle_pool = ScorerHandlePool(scorer)
        ev.attach_scorer(scorer, node_index, microbatch=mb, handle_pool=handle_pool)
        # two dispatchers over the same Scheduling: the workers=1 vs 2 A/B
        # must differ ONLY in worker count (same lock, same rng, same pool)
        disp1 = RoundDispatcher(svc.scheduling, workers=1)
        disp2 = RoundDispatcher(svc.scheduling, workers=2)

        cand = parents[: args.candidates]
        # warm every path (first calls build caches, fork per-thread
        # handles, start the micro-batch flusher)
        for _ in range(3):
            await asyncio.gather(*(ev.evaluate_async(c, cand) for c in children))
            await asyncio.gather(*(disp1.evaluate(c, cand) for c in children))
            await asyncio.gather(*(disp2.evaluate(c, cand) for c in children))

        async def measure(fn) -> tuple[float, np.ndarray]:
            done = 0
            lat: list[float] = []

            async def driver(c):
                nonlocal done
                while done < args.rounds:
                    done += 1
                    t1 = time.monotonic()
                    await fn(c)
                    lat.append(time.monotonic() - t1)

            t0 = time.monotonic()
            await asyncio.gather(*(driver(c) for c in children))
            return args.rounds / (time.monotonic() - t0), np.asarray(lat) * 1000

        # ---- eval leg (prepare+score only), three shapes interleaved ----
        eval_legs = {
            "microbatch": lambda c: ev.evaluate_async(c, cand),
            "workers1": lambda c: disp1.evaluate(c, cand),
            "workers2": lambda c: disp2.evaluate(c, cand),
        }
        eval_rates: dict[str, list[float]] = {k: [] for k in eval_legs}
        # latency samples POOLED across all three reps (keeping only the
        # last rep's array paired a median-of-3 throughput with a single
        # noise sample of latency on a ±30%-drift box)
        eval_lats: dict[str, list[np.ndarray]] = {k: [] for k in eval_legs}
        flushes0, rounds0 = mb.flushes, mb.rounds
        for _rep in range(3):
            for name, fn in eval_legs.items():
                rps, lat = await measure(fn)
                eval_rates[name].append(rps)
                eval_lats[name].append(lat)
        # coalescing stats cover exactly the microbatch legs (the dispatcher
        # legs never touch the micro-batcher)
        eval_flushes, eval_rounds = mb.flushes - flushes0, mb.rounds - rounds0
        mb_rps = float(np.median(eval_rates["microbatch"]))
        w1_rps = float(np.median(eval_rates["workers1"]))
        w2_rps = float(np.median(eval_rates["workers2"]))
        # Headline = the best-measured serving config ON THIS HOST, named in
        # eval_best_config: on the 2-core CI box the event loop's own
        # per-round glue plus one worker already saturate the GIL, so
        # workers=1 (round CPU off-loop, loop glue on the freed core) is
        # typically the winner and workers=2 adds nothing the box can give —
        # the full scaling curve needs wider hosts (ROADMAP #1's caveat;
        # tests/test_dispatch.py proves the 1→2 growth property with a
        # GIL-releasing scorer stub).
        best = max(eval_legs, key=lambda k: float(np.median(eval_rates[k])))
        eval_rps = float(np.median(eval_rates[best]))
        eval_lat = np.concatenate(eval_lats[best])

        # ---- full round (sample + filters + score + top-4) ----
        # Six legs interleaved same-run (ISSUE 18 + 19): the shipping Python
        # serial loop, the dispatcher batching rounds through the PYTHON
        # batch leg (PR 7's best shape), the native round driver
        # (df_round_drive: snapshot under the lock → ONE GIL-released FFI
        # for filter-revalidate + feature columns + score + stable top-k)
        # on 1 and 2 dispatcher workers, and the MIRROR-backed driver
        # (df_mirror_drive: no snapshot at all — sample/filter/gather/score
        # against the C-side mirrored peer table) on the same two shapes.
        # round_driver and the mirror attachment are flipped per measurement
        # on the SAME Scheduling (same pool, same rng, same lock), so each
        # A/B isolates exactly one mechanism.
        sched = svc.scheduling
        mirror_client = svc.enable_native_mirror()
        sched._mirror = None  # dflint: disable=DF036 A/B rig: legs opt into the attached client explicitly below
        full_legs = {
            "serial": ("serial", False, lambda c: sched.find_candidate_parents_async(c)),
            "dispatcher": ("serial", False, lambda c: disp2.find(c)),
            "native_workers1": ("auto", False, lambda c: disp1.find(c)),
            "native_workers2": ("auto", False, lambda c: disp2.find(c)),
        }
        if mirror_client is not None:
            full_legs["mirror_workers1"] = ("auto", True, lambda c: disp1.find(c))
            full_legs["mirror_workers2"] = ("auto", True, lambda c: disp2.find(c))
        for driver, use_mirror, fn in full_legs.values():  # warm every leg
            sched.config.round_driver = driver
            sched._mirror = mirror_client if use_mirror else None  # dflint: disable=DF036 A/B rig: per-leg toggle of the one attached client (deltas keep flowing while detached)
            await asyncio.gather(*(fn(c) for c in children))
        full_rates: dict[str, list[float]] = {k: [] for k in full_legs}
        full_lats: dict[str, list[np.ndarray]] = {k: [] for k in full_legs}
        # per-leg stage decomposition (ISSUE 19 satellite): Scheduling keeps
        # cumulative ns per stage — snapshot/delta-apply (Python descriptor
        # or snapshot build + result demux), drive (the FFI call), commit
        # (the DAG apply, which find-only legs never run) — sliced per leg
        # by delta around each measurement
        full_stages: dict[str, list[int]] = {k: [0, 0, 0] for k in full_legs}
        native_driven0 = sched.native_rounds_served
        mirror_driven0 = sched.mirror_rounds_served
        for _rep in range(3):
            for name, (driver, use_mirror, fn) in full_legs.items():
                sched.config.round_driver = driver
                sched._mirror = mirror_client if use_mirror else None  # dflint: disable=DF036 A/B rig: per-leg toggle of the one attached client
                s0, d0, c0 = (sched.stage_snapshot_ns, sched.stage_drive_ns,
                              sched.stage_commit_ns)
                rps, lat = await measure(fn)
                st = full_stages[name]
                st[0] += sched.stage_snapshot_ns - s0
                st[1] += sched.stage_drive_ns - d0
                st[2] += sched.stage_commit_ns - c0
                full_rates[name].append(rps)
                full_lats[name].append(lat)
        sched.config.round_driver = "auto"
        sched._mirror = mirror_client  # dflint: disable=DF036 A/B rig: restore the attached client after the leg sweep
        # coverage proof for the A/B: rounds the driver actually scored
        # natively across the native legs (0 would void the comparison —
        # every round silently riding the serial fallback)
        native_rounds_driven = sched.native_rounds_served - native_driven0
        mirror_rounds_driven = sched.mirror_rounds_served - mirror_driven0
        med = {k: float(np.median(v)) for k, v in full_rates.items()}
        full_serial_rps = med["serial"]
        full_disp_rps = med["dispatcher"]
        # same best-config honesty as the eval leg: the serial loop is the
        # shipping default (dispatch_workers=0) and must never be made to
        # LOOK slower by pinning the headline to a config this host can't
        # feed — best-of within each family, named explicitly
        py_best = "dispatcher" if full_disp_rps >= full_serial_rps else "serial"
        nat_best = max(("native_workers1", "native_workers2"), key=lambda k: med[k])
        round_driver_rps = med[nat_best]
        native_speedup = round_driver_rps / max(med[py_best], 1e-9)
        if mirror_client is not None:
            mirror_best = max(("mirror_workers1", "mirror_workers2"),
                              key=lambda k: med[k])
            mirror_rps = med[mirror_best]
            mirror_speedup = mirror_rps / max(med[py_best], 1e-9)
            mirror_stats = mirror_client.stats()
        else:
            mirror_best = mirror_rps = mirror_speedup = mirror_stats = None
        full_best = max(full_legs, key=lambda k: med[k])
        full_rps = med[full_best]
        full_lat = np.concatenate(full_lats[full_best])

        def stage_us(leg: str | None) -> dict:
            """Per-round stage split for one leg across its 3 reps. Null
            hygiene: a stage the leg never ran (commit on find-only legs,
            drive on pure-Python legs) reports None, not a fake 0.0."""
            if leg is None:
                return {"snapshot": None, "drive": None, "commit": None}
            snap, drv, com = full_stages[leg]
            n = 3 * args.rounds
            return {
                "snapshot": round(snap / n / 1e3, 2) if snap else None,
                "drive": round(drv / n / 1e3, 2) if drv else None,
                "commit": round(com / n / 1e3, 2) if com else None,
            }

        disp1.shutdown()
        disp2.shutdown()

        # Cost decomposition → the host's serving ceiling. Everything on this
        # path is CPU work on the scheduler's event-loop core: feature
        # assembly (Python/numpy) and the native GEMMs (which sit near the
        # core's SIMD peak — see scorer.cc). 1/(prepare+ffi) is therefore the
        # best ANY single-core deployment can serve end-to-end; the gap
        # between achieved and ceiling is asyncio + micro-batch overhead. On
        # multi-core hosts the micro-batcher offloads the native call (GIL
        # released) so assembly and GEMMs pipeline, raising the ceiling
        # toward 1/max(prepare, ffi).
        probe_n = 512
        t0 = time.monotonic()
        for _ in range(probe_n):
            ev._prepare(children[0], cand)
        prepare_us = (time.monotonic() - t0) / probe_n * 1e6
        feats, cc, pp, _known = ev._prepare(children[0], cand)
        if cc is None:
            # hosts unknown to the serving graph: the per-stage ceiling
            # cannot be probed — degrade the report to null ceiling fields
            # instead of crashing after the measurements completed
            # (ADVICE r05 #2)
            ffi_us = None
            ceiling_rps = None
        else:
            M = 8
            mf = np.tile(feats, (M, 1, 1))
            mc = np.tile(cc, (M, 1))
            mp = np.tile(pp, (M, 1))
            for _ in range(5):
                scorer.score_rounds(mf, child=mc, parent=mp)
            t0 = time.monotonic()
            for _ in range(probe_n // M):
                scorer.score_rounds(mf, child=mc, parent=mp)
            ffi_us = (time.monotonic() - t0) / probe_n * 1e6
            ceiling_rps = 1e6 / (prepare_us + ffi_us)
        if mirror_client is not None:
            sched._mirror = None  # dflint: disable=DF036 A/B rig: deliberate unwiring before closing the client
            mirror_client.close()
        handle_pool.close()
        scorer.close()

    def pct(lat: np.ndarray, q: float) -> float:
        return round(float(np.percentile(lat, q)), 3) if len(lat) else None

    # Honest ceiling accounting (ISSUE 7 satellite): the r05 capture reported
    # host_cpu_count 1 on a 2-core box (os.cpu_count semantics under the
    # container) — cores now come from the scheduling-affinity mask with
    # os.cpu_count alongside, the ceiling stays PER-CORE by definition
    # (1/(prepare+ffi) on one core), and the fraction divides by the cores
    # the dispatcher could actually use, so "1.05 of ceiling" can no longer
    # read as "done" when a second core sits idle.
    cpus = usable_cpu_count()
    cores_usable = min(disp2.workers, cpus)
    return {
        "metric": "evaluator_scoring_rounds_per_sec",
        "value": round(eval_rps, 1),
        "unit": (
            f"rounds/s (MLEvaluator end-to-end, feature build included; "
            f"best config = {best}, see eval_best_config)"
        ),
        "extra": {
            "candidates_per_round": len(cand),
            "concurrency": args.concurrency,
            "rounds": args.rounds,
            "eval_p50_ms": pct(eval_lat, 50),
            "eval_p99_ms": pct(eval_lat, 99),
            "eval_best_config": best,
            "rounds_per_sec_microbatch": round(mb_rps, 1),
            "rounds_per_sec_workers1": round(w1_rps, 1),
            "rounds_per_sec_workers2": round(w2_rps, 1),
            "thread_scaling_speedup": round(w2_rps / max(w1_rps, 1e-9), 3),
            "dispatch_workers": disp2.workers,
            "full_round_rps": round(full_rps, 1),
            "full_round_best_config": full_best,
            "full_round_rps_serial": round(full_serial_rps, 1),
            "full_round_rps_dispatcher": round(full_disp_rps, 1),
            "full_round_p50_ms": pct(full_lat, 50),
            "full_round_p99_ms": pct(full_lat, 99),
            # ISSUE 18 headline: the native round driver vs the best PYTHON
            # round loop this host can serve (py_best named so the speedup
            # is never against a strawman)
            "round_driver_best_config": nat_best,
            "round_driver_rounds_per_s": round(round_driver_rps, 1),
            "round_driver_rps_workers1": round(med["native_workers1"], 1),
            "round_driver_rps_workers2": round(med["native_workers2"], 1),
            "native_speedup_vs_best_py": round(native_speedup, 3),
            "best_py_config": py_best,
            "native_rounds_driven": int(native_rounds_driven),
            # ISSUE 19 headline: the mirror-backed driver (no Python
            # snapshot leg at all) vs the same best Python loop, plus the
            # per-round stage split for the snapshot-native and mirror legs
            # (None = that leg never ran the stage — find-only legs never
            # commit, pure-Python legs never drive)
            "round_driver_mirror_best_config": mirror_best,
            "round_driver_mirror_rounds_per_s": (
                round(mirror_rps, 1) if mirror_rps is not None else None
            ),
            "round_driver_mirror_rps_workers1": (
                round(med["mirror_workers1"], 1) if mirror_client is not None else None
            ),
            "round_driver_mirror_rps_workers2": (
                round(med["mirror_workers2"], 1) if mirror_client is not None else None
            ),
            "mirror_speedup_vs_best_py": (
                round(mirror_speedup, 3) if mirror_speedup is not None else None
            ),
            "mirror_rounds_driven": int(mirror_rounds_driven),
            "round_driver_stage_us": stage_us(nat_best),
            "round_driver_mirror_stage_us": stage_us(mirror_best),
            "mirror_full_syncs": (
                int(mirror_stats["full_syncs"]) if mirror_stats else None
            ),
            "mirror_stale_rounds": (
                int(mirror_stats["stale_rounds"]) if mirror_stats else None
            ),
            "native_flushes": eval_flushes,
            "native_rounds": eval_rounds,
            "prepare_us_per_round": round(prepare_us, 1),
            "ffi_us_per_round_amortized": round(ffi_us, 1) if ffi_us is not None else None,
            "single_core_ceiling_rps": round(ceiling_rps, 1) if ceiling_rps else None,
            "ceiling_fraction_achieved": (
                round(eval_rps / (ceiling_rps * cores_usable), 3) if ceiling_rps else None
            ),
            "ceiling_fraction_single_core": (
                round(eval_rps / ceiling_rps, 3) if ceiling_rps else None
            ),
            "host_cpu_count": cpus,
            "host_cpu_count_os": os.cpu_count(),
        },
    }


_SWARM_RPC_VERBS = frozenset({
    "register_peer", "report_task_metadata", "report_pieces",
    "report_piece_result", "report_peer_result", "announce_task",
    "announce_host", "sync_probes", "reschedule", "leave_peer", "leave_host",
    "stat_task",
})


class _CountingSchedulerClient:
    """RemoteSchedulerClient proxy counting RPCs per scheduler address — the
    swarm's per-scheduler load-share measurement (`register_peer` counts
    separately: one per round, so its share IS the ring's task placement)."""

    def __init__(self, addr: str, counts: dict, round_counts: dict):
        from dragonfly2_tpu.rpc.scheduler import RemoteSchedulerClient

        self._inner = RemoteSchedulerClient(addr)
        self._addr = addr
        self._counts = counts
        self._round_counts = round_counts

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name not in _SWARM_RPC_VERBS:
            return attr

        async def wrapped(*a, **k):
            self._counts[self._addr] = self._counts.get(self._addr, 0) + 1
            if name == "register_peer":
                self._round_counts[self._addr] = self._round_counts.get(self._addr, 0) + 1
            return await attr(*a, **k)

        return wrapped


async def run_swarm(
    scheduler_addrs: list[str],
    *,
    peers: int = 200,
    tasks: int = 32,
    pieces: int = 8,
    duration: float = 10.0,
    probe_every: int = 5,
    piece_size: int = 4 << 20,
) -> dict:
    """Swarm mode: N simulated lightweight peers driving the full
    control-plane round over the REAL wire against a scheduler federation —
    register → (seed: metadata + batched piece reports + result) or
    (child: scheduled parents + batched piece reports + result) — plus
    periodic probe syncs feeding the topology the federation gossips.

    No data plane: the swarm measures what the ring + federation can
    SCHEDULE, which is the control-plane scale story ("hundreds of peers per
    scheduler pair"). Peer ids are stable per (peer, task) so the resource
    pools stay bounded (re-registering a finished peer restarts it, the
    same reuse shape `run_stress` relies on)."""
    from dragonfly2_tpu.rpc.balancer import BalancedSchedulerClient
    from dragonfly2_tpu.scheduler.service import HostInfo, TaskMeta

    rpc_counts: dict[str, int] = {}
    round_counts: dict[str, int] = {}
    client = BalancedSchedulerClient(
        scheduler_addrs,
        client_factory=lambda a: _CountingSchedulerClient(a, rpc_counts, round_counts),
    )
    metas = [
        TaskMeta(f"swarm-task-{j:04d}", f"http://origin/swarm-{j}.bin")
        for j in range(tasks)
    ]
    content_length = pieces * piece_size
    rounds = 0
    errors = 0
    latencies: list[float] = []
    stop_at = time.monotonic() + duration

    async def peer_loop(i: int) -> None:
        nonlocal rounds, errors
        host = HostInfo(
            id=f"swarm-host-{i:04d}", ip=f"10.42.{i // 256}.{i % 256}",
            hostname=f"swarm-{i}", download_port=18000 + (i % 40000),
        )
        try:
            await client.announce_host(host)
        except Exception:
            errors += 1
        cycle = 0
        while time.monotonic() < stop_at:
            meta = metas[(i + cycle) % len(metas)]
            peer_id = f"swarm-p{i:04d}-{(i + cycle) % len(metas):04d}"
            t0 = time.monotonic()
            try:
                reg = await client.register_peer(peer_id, meta, host)  # dflint: disable=DF025 load generator: one round per iteration IS the workload being measured
                if reg.error:
                    # a refused registration did no reporting work — it must
                    # not count as a completed round (that would inflate
                    # rounds/s exactly when the federation is overloaded)
                    errors += 1
                    cycle += 1
                    continue
                if reg.back_to_source:
                    # first holder: publish metadata, then report the whole
                    # task as one batched flush — the seed leg of the round
                    await client.report_task_metadata(  # dflint: disable=DF025 load generator workload
                        meta.task_id, content_length=content_length,
                        piece_size=piece_size,
                    )
                    await client.report_pieces(  # dflint: disable=DF025 already the batched verb; one flush per round is the workload
                        peer_id, [(k, 8.0, "") for k in range(pieces)]
                    )
                    await client.report_peer_result(  # dflint: disable=DF025 load generator workload
                        peer_id, success=True, bandwidth_bps=2e8
                    )
                else:
                    parent = reg.parents[0].peer_id if reg.parents else ""
                    await client.report_pieces(  # dflint: disable=DF025 already the batched verb; one flush per round is the workload
                        peer_id, [(k, 5.0, parent) for k in range(pieces)]
                    )
                    await client.report_peer_result(  # dflint: disable=DF025 load generator workload
                        peer_id, success=True, bandwidth_bps=3e8
                    )
                if probe_every and cycle % probe_every == probe_every - 1:
                    dst = f"swarm-host-{(i + 1) % peers:04d}"
                    await client.sync_probes(  # dflint: disable=DF025 load generator workload: periodic probe round per peer
                        host.id,
                        [{"dst_host_id": dst, "rtt_ms": 1.0 + (i % 7), "success": True}],
                    )
                rounds += 1
                latencies.append(time.monotonic() - t0)
            except Exception:
                errors += 1
            cycle += 1

    t0 = time.monotonic()
    await asyncio.gather(*(peer_loop(i) for i in range(peers)))
    elapsed = time.monotonic() - t0
    await client.close()

    total_rpcs = sum(rpc_counts.values()) or 1
    total_rounds = sum(round_counts.values()) or 1
    lat = np.asarray(latencies) * 1000.0
    return {
        "metric": "swarm_rounds_per_sec",
        "value": round(rounds / max(elapsed, 1e-9), 1),
        "unit": "rounds/s (full control-plane cycle per simulated peer)",
        "extra": {
            "schedulers": list(scheduler_addrs),
            "peers": peers,
            "tasks": tasks,
            "pieces_per_round": pieces,
            "rounds": rounds,
            "errors": errors,
            "elapsed_s": round(elapsed, 2),
            "p50_ms": round(float(np.percentile(lat, 50)), 2) if len(lat) else None,
            "p99_ms": round(float(np.percentile(lat, 99)), 2) if len(lat) else None,
            # share of scheduling rounds (register_peer) per ring member —
            # the consistent-hash placement balance — plus the all-RPC share
            "per_scheduler_round_share": {
                a: round(round_counts.get(a, 0) / total_rounds, 3)
                for a in scheduler_addrs
            },
            "per_scheduler_rpc_share": {
                a: round(rpc_counts.get(a, 0) / total_rpcs, 3)
                for a in scheduler_addrs
            },
        },
    }


async def run_swarm_stress(args: argparse.Namespace) -> dict:
    addrs = [a.strip() for a in args.schedulers.split(",") if a.strip()]
    if not addrs:
        raise SystemExit("--swarm requires --schedulers host:port[,host:port...]")
    return await run_swarm(
        addrs,
        peers=args.peers,
        tasks=args.tasks,
        pieces=args.pieces,
        duration=args.duration,
        probe_every=args.probe_every,
    )


def main(argv: list[str] | None = None) -> int:
    from dragonfly2_tpu.utils import jaxenv

    jaxenv.pin_host_cpu()  # host-side process: never opens the accelerator
    ap = argparse.ArgumentParser(description="dragonfly2_tpu daemon load generator")
    ap.add_argument("url", nargs="?", default=None,
                    help="source URL to download repeatedly (download mode)")
    ap.add_argument("--sock", default=DEFAULT_SOCK)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--duration", type=float, default=10.0,
                    help="seconds to run (ignored with --count)")
    ap.add_argument("--count", type=int, default=None, help="fixed request count")
    ap.add_argument("--timeout", type=float, default=60.0)
    ap.add_argument("--unique", action="store_true",
                    help="unique task per request (full scheduler+piece path)")
    ap.add_argument("--priority", type=float, default=3.0,
                    help="tenant weight for the high-priority worker class")
    ap.add_argument("--priority-split", type=int, default=0,
                    help="first N workers request at --priority (rest at 1.0): "
                         "drives the traffic shaper's weighted fairness")
    ap.add_argument("--scoring", action="store_true",
                    help="stress the ml scoring serving path instead of downloads")
    ap.add_argument("--swarm", action="store_true",
                    help="simulated-peer swarm against a scheduler federation "
                         "over the real wire (control plane only, no data plane)")
    ap.add_argument("--schedulers", default="",
                    help="scheduler addresses host:port[,host:port...] (--swarm)")
    ap.add_argument("--peers", type=int, default=200,
                    help="simulated peers in the swarm (--swarm)")
    ap.add_argument("--tasks", type=int, default=32,
                    help="distinct tasks the swarm cycles through (--swarm)")
    ap.add_argument("--pieces", type=int, default=8,
                    help="pieces reported per swarm round (--swarm)")
    ap.add_argument("--probe-every", type=int, default=5,
                    help="sync a probe round every N cycles per peer (--swarm)")
    ap.add_argument("--rounds", type=int, default=20000,
                    help="scoring rounds to drive (--scoring)")
    ap.add_argument("--candidates", type=int, default=40,
                    help="candidate parents per round (--scoring)")
    ap.add_argument("--hosts", type=int, default=256,
                    help="hosts in the stress pool (--scoring)")
    args = ap.parse_args(argv)
    if args.scoring:
        result = asyncio.run(run_scoring_stress(args))
        print(json.dumps(result), flush=True)
        return 0
    if args.swarm:
        result = asyncio.run(run_swarm_stress(args))
        print(json.dumps(result), flush=True)
        return 0 if result["extra"]["errors"] == 0 else 1
    if not args.url:
        ap.error("url is required unless --scoring")
    result = asyncio.run(run_stress(args))
    print(json.dumps(result), flush=True)
    return 0 if result["extra"]["errors"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
