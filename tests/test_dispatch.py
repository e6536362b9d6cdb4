"""Sharded round dispatcher (ISSUE 7): thread scaling, serial equivalence,
and reader-safety of the version-keyed caches under concurrent mutation.

Covers the tentpole's three contracts:

1. Thread scaling — `evaluator_rounds_per_sec` grows monotonically 1→2
   workers. Proven with a scorer whose per-round cost is a GIL-RELEASING
   leg (time.sleep standing in for the native FFI call): the 2-core CI box
   is GIL/bandwidth-saturated for the real GEMM workload (the bench reports
   whatever the box gives honestly), so the dispatcher's scaling PROPERTY is
   pinned where it is deterministic — when rounds are dominated by work that
   drops the GIL, two workers overlap it and one cannot (ROADMAP #1: "a
   thread-scaling test that proves rounds/s grows with worker count even
   though the 2-core box can't show the full curve live").

2. Equivalence — sharded rounds are bit-identical to the serial path: same
   rng draws, same filters, same scores, same committed edges, on
   randomized pools and after a concurrent hammer of rounds + probes +
   piece reports (the mutating apply stays serialized under the state lock).

3. Cache safety — the evaluator's pair-row cache keyed on topology/
   bandwidth version counters yields the OLD or the NEW row under racing
   mutation, never a torn mix, and converges to the latest values once the
   mutator quiesces (barrier-driven reader threads).
"""

from __future__ import annotations

import asyncio
import random
import threading
import time

import numpy as np
import pytest

from dragonfly2_tpu.scheduler.evaluator import Evaluator, build_pair_features, new_evaluator
from dragonfly2_tpu.scheduler.resource import HostType
from dragonfly2_tpu.scheduler.scheduling import (
    RoundDispatcher,
    SchedulingConfig,
    usable_cpu_count,
)
from dragonfly2_tpu.scheduler.service import SchedulerService
from dragonfly2_tpu.telemetry.bandwidth import BANDWIDTH_NORM_BPS, BandwidthHistory

pytestmark = pytest.mark.concurrency


def build_pool(svc: SchedulerService, *, n_hosts: int = 48, n_children: int = 4,
               seed: int = 0):
    """A live pool with scored feature sources: children downloading, parents
    holding pieces, probe RTTs and bandwidth history on every pair."""
    rng = random.Random(seed)
    task = svc.pool.load_or_create_task(f"task-{seed}", "http://origin/t.bin")
    task.set_metadata(1 << 30, 4 << 20)
    children, parents = [], []
    for i in range(n_hosts):
        h = svc.pool.load_or_create_host(
            f"h{seed}-{i}", f"10.{seed % 256}.{i // 256}.{i % 256}", f"host{i}",
            download_port=8000, host_type=HostType.NORMAL,
            idc=f"idc-{i % 3}", location=f"r{i % 2}|z{i % 5}",
        )
        h.upload_limit = 1000
        p = svc.pool.create_peer(f"peer{seed}-{i}", task, h)
        for ev in ("register", "download"):
            if p.fsm.can(ev):
                p.fsm.fire(ev)
        if i < n_children:
            children.append(p)
        else:
            for idx in range(rng.randrange(1, 12)):
                p.finished_pieces.set(idx)
            p.add_piece_cost(rng.uniform(1.0, 50.0))
            p.bump_feat()
            parents.append(p)
    for c in children:
        for p in parents:
            svc.topology.enqueue(c.host.id, p.host.id, rng.uniform(0.2, 30.0))
            svc.bandwidth.observe(p.host.id, c.host.id, rng.uniform(1e8, 1e9))
    return task, children, parents


class SleepyEvaluator(Evaluator):
    """Base scoring behind a 2 ms GIL-RELEASING leg per round — the
    controllable stand-in for the native FFI call (ctypes drops the GIL the
    same way time.sleep does), making the scaling measurement deterministic
    on a loaded box."""

    def evaluate(self, child, parents):
        time.sleep(0.002)
        return super().evaluate(child, parents)


class TestThreadScaling:
    def test_rounds_per_sec_grows_1_to_2_workers(self):
        """THE thread-scaling proof: with rounds dominated by a GIL-releasing
        scoring leg, workers=2 must beat workers=1 by ≥1.4x (perfect overlap
        would be 2.0x; the margin absorbs dispatch overhead + box noise).

        Runs on a NON-debug loop (not the `run` fixture): asyncio debug mode
        captures a creation traceback per callback, ~ms-scale overhead that
        swamps the 2 ms scoring leg and flattens the very ratio under test.
        """

        async def body():
            svc = SchedulerService(evaluator=SleepyEvaluator())
            _task, children, _parents = build_pool(svc)

            async def measure(workers: int, rounds: int = 40) -> float:
                disp = RoundDispatcher(svc.scheduling, workers=workers)
                # warm the worker threads so thread spawn is off the clock
                await asyncio.gather(*(disp.find(c) for c in children))
                t0 = time.perf_counter()
                done = 0
                while done < rounds:
                    chunk = [disp.find(children[(done + i) % len(children)])
                             for i in range(8)]
                    await asyncio.gather(*chunk)
                    done += len(chunk)
                rate = done / (time.perf_counter() - t0)
                disp.shutdown()
                return rate

            w1 = await measure(1)
            w2 = await measure(2)
            assert w2 >= 1.4 * w1, (w1, w2)

        asyncio.run(body())

    def test_dispatched_find_matches_serial_find(self, run):
        """Same pool, same rng state: one dispatched round returns exactly
        the serial round's candidates (the dispatcher adds transport, not
        semantics)."""

        async def body():
            svc = SchedulerService()
            _task, children, _parents = build_pool(svc, seed=3)
            sched = svc.scheduling
            disp = RoundDispatcher(sched, workers=2)
            for c in children:
                state = sched._rng.getstate()
                serial = [p.id for p in sched.find_candidate_parents(c)]
                sched._rng.setstate(state)
                sharded = [p.id for p in await disp.find(c)]
                assert serial == sharded
            disp.shutdown()

        run(body())


class TestEquivalence:
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_sharded_schedule_bit_identical_to_serial(self, run, seed):
        """Two identical randomized pools, one scheduled serially and one
        through the dispatcher (rounds awaited in the same order): committed
        parent sets and the resulting DAGs must match exactly — the
        dispatcher path shares the rng, filters, scorer, and commit code."""

        async def body():
            svc_a = SchedulerService()  # serial reference
            svc_b = SchedulerService(
                scheduling_config=SchedulingConfig(dispatch_workers=2)
            )
            assert svc_b.scheduling.dispatcher is not None
            _ta, ch_a, _pa = build_pool(svc_a, seed=seed)
            _tb, ch_b, _pb = build_pool(svc_b, seed=seed)
            for ca, cb in zip(ch_a, ch_b):
                out_a = await svc_a.scheduling.schedule_candidate_parents(ca)
                out_b = await svc_b.scheduling.schedule_candidate_parents(cb)
                ids_a = [p.id for p in out_a.parents]
                ids_b = [p.id for p in out_b.parents]
                assert ids_a == ids_b and out_a.rounds == out_b.rounds
                # committed DAG edges match too (same slots consumed)
                assert sorted(p.id for p in ca.task.parents_of(ca.id)) == \
                    sorted(p.id for p in cb.task.parents_of(cb.id))
            svc_b.close()

        run(body())

    def test_chaos_hammer_preserves_serial_semantics(self, run):
        """Hammer the dispatcher with interleaved rounds, probe syncs, and
        batched piece reports (the mutating probe pipeline of the ISSUE);
        then quiesce and check every child's next round is bit-identical
        between the dispatcher and the serial path on the SAME pool state —
        concurrency must not have corrupted any cache, counter, or DAG
        invariant the filters read."""

        async def body():
            svc = SchedulerService(
                scheduling_config=SchedulingConfig(dispatch_workers=2)
            )
            task, children, parents = build_pool(svc, n_hosts=40, n_children=6)
            sched = svc.scheduling
            rng = random.Random(7)
            stop = asyncio.Event()

            async def round_driver(child):
                while not stop.is_set():
                    out = await sched.schedule_candidate_parents(child)
                    for p in out.parents:
                        # structural invariants on every commit
                        assert p.id != child.id and p.host.id != child.host.id
                    await asyncio.sleep(0)

            async def mutator():
                for i in range(120):
                    kind = i % 3
                    if kind == 0:
                        svc.sync_probes(
                            rng.choice(children).host.id,
                            [{"dst_host_id": rng.choice(parents).host.id,
                              "rtt_ms": rng.uniform(0.2, 40.0)}],
                        )
                    elif kind == 1:
                        peer = rng.choice(children)
                        svc.report_pieces(
                            peer.id,
                            [(rng.randrange(0, 256), rng.uniform(1, 30), rng.choice(parents).id)],
                        )
                    else:
                        svc.report_piece_result(
                            rng.choice(children).id, rng.randrange(0, 256),
                            success=False, parent_id=rng.choice(parents).id,
                        )
                    await asyncio.sleep(0)
                stop.set()

            await asyncio.gather(mutator(), *(round_driver(c) for c in children))

            # quiesced: dispatcher and serial must agree exactly per child
            for c in children:
                state = sched._rng.getstate()
                serial = [p.id for p in
                          sched.find_candidate_parents(c, c.block_parents)]
                sched._rng.setstate(state)
                sharded = [p.id for p in await sched.dispatcher.find(c, c.block_parents)]
                assert serial == sharded
            svc.close()

        run(body())


class TestCacheUnderConcurrentMutation:
    def test_pair_row_is_old_or_new_never_torn(self):
        """Satellite: probe/bandwidth version bumps racing feature assembly
        yield either the old or the new row value, never a torn mix, and the
        cache converges once mutation stops. queue_length=1 and alpha=1.0
        make the legal value sets exactly two-valued."""
        from dragonfly2_tpu.scheduler.networktopology import NetworkTopology

        svc = SchedulerService()
        topo = NetworkTopology(queue_length=1)
        bw = BandwidthHistory(alpha=1.0)
        _task, children, parents = build_pool(svc, n_hosts=3, n_children=1)
        child, parent = children[0], parents[0]
        rtts = (100.0, 500.0)           # -> row[6] in {0.1, 0.5}
        bws = (BANDWIDTH_NORM_BPS / 2, BANDWIDTH_NORM_BPS)  # -> row[8] in {0.5, 1.0}
        legal_rtt = {0.1, 0.5}
        legal_bw = {0.5, 1.0}
        topo.enqueue(child.host.id, parent.host.id, rtts[0])
        bw.observe(parent.host.id, child.host.id, bws[0])

        n_readers = 2
        barrier = threading.Barrier(n_readers + 1)
        stop = threading.Event()
        bad: list = []

        def reader():
            barrier.wait()
            while not stop.is_set():
                row = build_pair_features(child, [parent], topo, bw)[0]
                if round(float(row[6]), 6) not in legal_rtt:
                    bad.append(("rtt", float(row[6])))
                if round(float(row[8]), 6) not in legal_bw:
                    bad.append(("bw", float(row[8])))

        threads = [threading.Thread(target=reader) for _ in range(n_readers)]
        for t in threads:
            t.start()
        barrier.wait()
        for i in range(400):  # the mutating probe pipeline
            topo.enqueue(child.host.id, parent.host.id, rtts[i % 2])
            bw.observe(parent.host.id, child.host.id, bws[i % 2])
        stop.set()
        for t in threads:
            t.join()
        assert not bad, bad[:5]
        # convergence: the final assembled row reads the LAST published
        # values (a bump-before-write ordering bug would pin a stale row
        # under the current version key)
        final = build_pair_features(child, [parent], topo, bw)[0]
        assert round(float(final[6]), 6) == 0.5 and round(float(final[8]), 6) == 1.0

    def test_static_row_version_consistent_under_feat_bumps(self):
        """The (version, row) tuple publish: racing host mutations can only
        ever produce a row consistent with SOME published version — slots
        ratio flips between two exact values, never an in-between mix."""
        from dragonfly2_tpu.scheduler.evaluator import _parent_static_row

        svc = SchedulerService()
        _task, _children, parents = build_pool(svc, n_hosts=3, n_children=1)
        parent = parents[0]
        host = parent.host
        host.upload_limit = 10
        legal = {1.0, 0.5}  # 10/10 free vs 5/10 free
        stop = threading.Event()
        bad: list = []
        barrier = threading.Barrier(2)

        def reader():
            barrier.wait()
            while not stop.is_set():
                row = _parent_static_row(parent, host)
                if round(float(row[2]), 6) not in legal:
                    bad.append(float(row[2]))

        t = threading.Thread(target=reader)
        t.start()
        barrier.wait()
        for i in range(2000):
            host.concurrent_uploads = 0 if i % 2 else 5
            host.bump_feat()
        stop.set()
        t.join()
        assert not bad, bad[:5]


class TestDispatcherLifecycle:
    def test_worker_exception_propagates_to_round(self, run):
        class Exploding(Evaluator):
            def evaluate(self, child, parents):
                raise RuntimeError("boom")

        async def body():
            svc = SchedulerService(evaluator=Exploding())
            _task, children, _parents = build_pool(svc)
            disp = RoundDispatcher(svc.scheduling, workers=1)
            with pytest.raises(RuntimeError, match="boom"):
                await disp.find(children[0])
            disp.shutdown()

        run(body())

    def test_shutdown_fails_new_rounds_and_cancels_pending(self, run):
        async def body():
            svc = SchedulerService()
            _task, children, _parents = build_pool(svc)
            disp = RoundDispatcher(svc.scheduling, workers=1)
            await disp.find(children[0])
            disp.shutdown()
            with pytest.raises(RuntimeError, match="shut down"):
                await disp.find(children[0])

        run(body())

    def test_config_zero_workers_stays_serial(self):
        svc = SchedulerService(scheduling_config=SchedulingConfig())
        assert svc.scheduling.dispatcher is None
        svc.close()  # no-op, must not raise

    def test_usable_cpu_count_positive(self):
        assert usable_cpu_count() >= 1


needs_gxx = pytest.mark.skipif(
    __import__("shutil").which("g++") is None, reason="g++ not available"
)


@needs_gxx
class TestNativeHandlePool:
    @pytest.fixture(scope="class")
    def native(self, tmp_path_factory):
        import jax
        import jax.numpy as jnp

        from dragonfly2_tpu.native import NativeScorer, export_scorer_artifact
        from dragonfly2_tpu.trainer import synthetic, train_gnn

        cluster = synthetic.make_cluster(num_nodes=64, num_neighbors=8, num_pairs=256, seed=3)
        cfg = train_gnn.GNNTrainConfig(hidden=64, embed_dim=32, num_layers=2)
        model = train_gnn.make_model(cfg)
        state = train_gnn.init_state(cfg, cluster.graph, rng_seed=3)
        g = jax.tree.map(jnp.asarray, cluster.graph)
        z = np.asarray(
            jax.jit(lambda p, gg: model.apply(p, gg, method=model.embed))(state.params, g)
        )
        path = tmp_path_factory.mktemp("scorer") / "s.dfsc"
        scorer = NativeScorer(export_scorer_artifact(state.params, z, path))
        yield scorer, cluster
        scorer.close()

    def test_fork_scores_match_and_share_model(self, native):
        scorer, cluster = native
        rng = np.random.default_rng(3)
        child = rng.integers(0, 64, 16).astype(np.int32)
        parent = rng.integers(0, 64, 16).astype(np.int32)
        feats = cluster.pairs.feats[:16].astype(np.float32)
        fork = scorer.fork()
        try:
            np.testing.assert_array_equal(
                scorer.score(feats, child=child, parent=parent),
                fork.score(feats, child=child, parent=parent),
            )
        finally:
            fork.close()
        # primary survives a fork's close (refcounted shared model)
        assert np.isfinite(scorer.score(feats, child=child, parent=parent)).all()

    def test_handle_pool_one_handle_per_thread(self, native):
        from dragonfly2_tpu.native import ScorerHandlePool

        scorer, _cluster = native
        pool = ScorerHandlePool(scorer)
        assert pool.get() is scorer  # creating thread rides the primary
        seen = {}

        def grab(key):
            seen[key] = pool.get()

        t1 = threading.Thread(target=grab, args=(1,))
        t2 = threading.Thread(target=grab, args=(2,))
        for t in (t1, t2):
            t.start()
        for t in (t1, t2):
            t.join()
        assert seen[1] is not scorer and seen[2] is not scorer
        assert seen[1] is not seen[2]
        assert pool.handles() == 3
        pool.close()
        assert pool.get() is scorer  # closed pool degrades to the primary

    def test_evaluate_many_matches_per_round_evaluate(self, native):
        scorer, cluster = native
        ev = new_evaluator("ml")
        svc = SchedulerService(evaluator=ev)
        _task, children, parents = build_pool(svc, n_hosts=24, n_children=4)
        node_index = {p.host.id: i % 64 for i, p in enumerate(parents + children)}
        ev.attach_scorer(scorer, node_index)
        cand = parents[:12]
        rounds = [(c, cand) for c in children]
        batched = ev.evaluate_many(rounds)
        for (c, ps), got in zip(rounds, batched):
            np.testing.assert_allclose(got, ev.evaluate(c, ps), rtol=1e-5, atol=1e-6)
