#!/usr/bin/env bash
# One-shot correctness gate: dflint → ruff → mypy → tier-1 pytest.
# Stops at the first failing stage (after printing the summary table).
# ruff/mypy are optional in this image and count as SKIP when absent.
#
#   bash tools/check.sh

set -u
cd "$(dirname "$0")/.."

NAMES=()
RESULTS=()
SECS=()

summarize() {
    echo
    echo "── check.sh summary ─────────────────────────"
    printf '%-28s %-6s %8s\n' "stage" "result" "seconds"
    for i in "${!NAMES[@]}"; do
        printf '%-28s %-6s %8s\n' "${NAMES[$i]}" "${RESULTS[$i]}" "${SECS[$i]}"
    done
    echo "─────────────────────────────────────────────"
}

run_stage() {
    local name="$1"; shift
    local t0 t1 rc
    echo
    echo "━━ ${name}: $*"
    t0=$(date +%s)
    "$@"
    rc=$?
    t1=$(date +%s)
    NAMES+=("$name")
    SECS+=($((t1 - t0)))
    if [ $rc -eq 0 ]; then
        RESULTS+=("ok")
    else
        RESULTS+=("FAIL")
        summarize
        echo "check.sh: stage '${name}' failed (rc=$rc)" >&2
        exit $rc
    fi
}

skip_stage() {
    NAMES+=("$1")
    RESULTS+=("skip")
    SECS+=("-")
    echo
    echo "━━ $1: skipped ($2)"
}

run_stage "dflint" python tools/dflint.py dragonfly2_tpu/ tools/ tests/ bench.py __graft_entry__.py chip_smoke.py

if command -v ruff >/dev/null 2>&1; then
    run_stage "ruff" ruff check dragonfly2_tpu tools bench.py
else
    skip_stage "ruff" "not installed"
fi

if command -v mypy >/dev/null 2>&1; then
    run_stage "mypy" mypy dragonfly2_tpu/rpc dragonfly2_tpu/utils dragonfly2_tpu/telemetry
else
    skip_stage "mypy" "not installed"
fi

# chaos, restart, and concurrency are excluded here and run as their own
# legs below: a resilience/recovery/dispatcher regression is then named by
# the stage that caught it, and the suites are not paid for twice. (The
# ROADMAP tier-1 command still runs `-m 'not slow'`, all three included —
# the stages together cover exactly that set.)
run_stage "pytest-tier1" env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow and not chaos and not restart and not concurrency' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

run_stage "chaos-smoke" env JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py -q \
    -m 'chaos and not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly

# restart-smoke: the fast in-process crash/recover/resume path (daemon kill
# at ~50%, seed crash, scheduler crash, torn-piece debounce window, mTLS-on
# data plane). The real-SIGKILL subprocess variants are marked slow.
run_stage "restart-smoke" env JAX_PLATFORMS=cpu python -m pytest tests/test_restart.py -q \
    -m 'restart and not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly

# stripe-smoke: cluster-in-a-box with mTLS ON — a hot multi-piece task
# fetched striped across 2 parents' TLS upload servers over the real wire,
# sha256 bit-exact, per-parent byte counters proving both parents served
# stripes (ISSUE 13 data plane v2).
run_stage "stripe-smoke" env JAX_PLATFORMS=cpu python tools/stripe_smoke.py

# control-plane smoke: the bench section at tiny shapes — catches a broken
# batched-report / cached-feature / coalesced-write path without paying for
# a full bench run (the real numbers come from bench.py's control_plane key)
run_stage "control-plane-smoke" env JAX_PLATFORMS=cpu python -c "
import bench
out = bench.bench_control_plane(rounds=50, candidates=8, hosts=24, pieces_per_round=4)
assert out['full_round_rps'] > 0 and out['evaluator_prepare_us_per_round'] > 0, out
assert out['piece_report_rpcs_per_round'] == 1, out
print('control_plane smoke ok:', {k: out[k] for k in ('full_round_rps', 'evaluator_prepare_us_per_round', 'report_wire_us_per_piece_batched')})
"

# concurrency-smoke: the sharded round dispatcher — thread-scaling proof
# (GIL-releasing scorer stub, deterministic on a loaded box), serial-vs-
# sharded bit-identical equivalence, chaos hammer, and the pair-row cache
# torn-read guards (tests/test_dispatch.py).
run_stage "concurrency-smoke" env JAX_PLATFORMS=cpu python -m pytest tests/test_dispatch.py -q \
    -m 'concurrency and not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly

# roundloop-smoke: the native round driver (ISSUE 18) — serial-vs-native
# bit-exact equivalence on randomized pools (parent lists, committed DAG
# edges, chaos hammer), the fallback taxonomy (base evaluator, partial node
# index, injected driver error), arena growth + pointer-binding reuse, and
# mode-honest decision records (`dfml explain` replays a native round
# bit-exact; a scorer-error round records mode=base). Then the bench's
# round_loop section at a tiny shape: a broken drive path or a silent
# serial fallback (coverage != 1.0) fails the leg without a full bench run.
run_stage "roundloop-smoke" env JAX_PLATFORMS=cpu python -m pytest tests/test_round_driver.py -q \
    -m 'concurrency and not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly
run_stage "roundloop-bench-smoke" env JAX_PLATFORMS=cpu python -c "
import bench
out = bench.bench_round_loop(rounds=64, batch=8, candidates=8, hosts=48)
assert out, 'round_loop section returned nothing'
if out.get('native_rounds_per_s') is not None:
    assert out['equivalent'] is True, out
    assert out['native_coverage'] == 1.0, out
print('round_loop smoke ok:', {k: out[k] for k in ('native_rounds_per_s', 'speedup', 'ffi_calls_per_round', 'native_coverage')})
"

# mirror-smoke: the native mirrored peer table (ISSUE 19) — serial-vs-mirror
# bit-exact equivalence with live deltas (create/mutate/delete), the MT19937
# sample-draw reproduction contract, the chaos hammer with a mid-round
# hot-swap, and the poison discipline (tests/test_mirror.py). Then a REAL
# scheduler service boots with the mirror enabled and drives rounds while
# deltas flow: steady state must show EXACTLY ONE full sync (the attach) —
# zero per-round re-exports — and quiesced drives must go fully native.
run_stage "mirror-smoke" env JAX_PLATFORMS=cpu python -m pytest tests/test_mirror.py -q \
    -m 'concurrency and not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly
run_stage "mirror-sync-smoke" env JAX_PLATFORMS=cpu python -c "
import logging; logging.disable(logging.WARNING)
import pathlib, random, sys, tempfile
sys.path.insert(0, 'tests')
from dragonfly2_tpu.scheduler import resource
resource.Peer._DEPTH_MEMO_TTL_S = 0.0
from test_round_driver import build_pool, _artifact
from dragonfly2_tpu.native import NativeScorer
from dragonfly2_tpu.scheduler.evaluator import new_evaluator
from dragonfly2_tpu.scheduler.service import SchedulerService
with tempfile.TemporaryDirectory() as td:
    ev = new_evaluator('ml')
    svc = SchedulerService(evaluator=ev)
    task, children, parents = build_pool(svc, seed=3)
    sc = NativeScorer(_artifact(pathlib.Path(td), seed=3))
    ni = {p.host.id: i % 64 for i, p in enumerate(parents + children)}
    ev.attach_scorer(sc, ni, version='mirror-smoke')
    client = svc.enable_native_mirror()
    assert client is not None and client.ready, 'mirror failed to attach'
    sched = svc.scheduling
    r = random.Random(5)
    pool_peers = sorted(task.dag.values(), key=lambda p: p.id)
    for _ in range(8):  # deltas flow between batches (hook-fed feat bumps)
        for p in r.sample(pool_peers, 4):
            p.add_piece_cost(r.uniform(1.0, 20.0)); p.bump_feat()
        sched.find_candidate_parents_batch_native([(c, set()) for c in children])
    for _ in range(2):  # quiesced: cache converges, drives go fully native
        sched.find_candidate_parents_batch_native([(c, set()) for c in children])
    st = client.stats()
    assert client.ready, client.poison_reason
    assert st['full_syncs'] == 1, st  # ZERO steady-state re-exports
    assert st['drives'] >= 10, st
    assert sched.mirror_rounds_served > 0, (st, sched.mirror_stale_rounds)
    svc.close(); sc.close()
print('mirror smoke ok:', {k: st[k] for k in ('full_syncs', 'drives', 'native_rounds', 'stale_rounds', 'deltas')})
"

# federation-smoke: the cluster-in-a-box boots manager + 2 federated
# schedulers + 2 daemons + origin as REAL subprocesses, runs a real dfget
# through the federation (seed + P2P, bit-exact), then asserts from the
# collected trace files that the task's scheduling rounds rode EXACTLY ONE
# scheduler (ring ownership) while federation sync spans appear on BOTH
# (the gossip is live).
run_stage "federation-smoke" env JAX_PLATFORMS=cpu python -m dragonfly2_tpu.cli.dfcluster \
    demo --payload-kb 6144 --verify-trace

# sim-smoke: the discrete-event swarm simulator at 10^4 peers — the
# flash-crowd scenario against the REAL scheduler+evaluator+federation
# objects (virtual clock, zero sockets), in-process through the dfsim JSON
# contract: placement quality, O(1)-per-region origin egress, the
# no-departed-peer invariant, and the telemetry→DatasetAccumulator bridge.
# The 10^5 acceptance shape is the slow-marked test in tests/test_sim.py.
run_stage "sim-smoke" env JAX_PLATFORMS=cpu python -c "
import logging; logging.disable(logging.WARNING)
from dragonfly2_tpu.cli.dfsim import run_scenario
out = run_scenario('flash-crowd', peers=10_000, seed=0)
assert out['peers'] == 10_000, out['peers']
assert out['outcomes']['completed'] >= 9_500, out['outcomes']
assert out['events_per_sec'] > 0 and out['time_compression'] > 1.0
pl = out['placement']
assert pl['rounds'] > 9_000 and pl['same_region_frac'] >= 0.5, pl
assert 0 < out['origin_egress']['max_region_fetches'] <= 8.0, out['origin_egress']
assert out['violations']['departed_parent_rounds'] == 0, out['violations']
assert out['telemetry']['nodes'] > 0 and out['telemetry']['edges'] > 0, out['telemetry']
assert out['assertions']['passed'], out['assertions']
print('sim smoke ok:', {'peers': out['peers'], 'events_per_sec': out['events_per_sec'],
      'same_region_frac': pl['same_region_frac'],
      'origin_fetches': out['origin_egress']['max_region_fetches'],
      'dataset_nodes': out['telemetry']['nodes']})
"

# metrics-smoke: the cluster metrics plane against the live box — boots
# manager + 2 ml schedulers + 2 daemons, real dfget traffic, asserts
# `dftop --once --json` shows every member with live windowed rates, then
# that the induced base-fallback burst (ml evaluator, no model) raises its
# SLO alert through recorder → rule engine → stats frame → manager → dftop.
run_stage "metrics-smoke" env JAX_PLATFORMS=cpu python tools/metrics_smoke.py

# degradation-smoke: graceful degradation under overload (ISSUE 17) — the
# brownout ladder climbs 0->4->0 on the wall clock with the stock
# scheduler_degraded alert firing and resolving, register_peer answers
# typed overloaded + retry_after for the shed class, the cluster retry
# budget fails fast / absorbs server hints, and the overload-flash +
# manager-blackout chaos packs re-prove their invariants at reduced scale.
run_stage "degradation-smoke" env JAX_PLATFORMS=cpu python tools/degradation_smoke.py

# rollout-smoke: the live-model safe-rollout loop against real seams —
# publish a digest-verified candidate into the manager registry, shadow N
# live scheduling rounds on an ml scheduler (divergence window reported +
# aggregated), promote via the dfmodel CLI, and assert the serving-mode
# metric flips with ZERO base-fallback growth after the zero-drop swap.
run_stage "rollout-smoke" env JAX_PLATFORMS=cpu python tools/rollout_smoke.py

# mlobs-smoke: the ML-plane observability loop (ISSUE 15) — in-process
# cluster runs a real train → publish → attach cycle (artifact ships the
# digest-covered training-reference sketch), serves live rounds through the
# model, injects a shifted feature distribution, and asserts the
# feature_drift alert propagates recorder → rules → stats frame → manager →
# `dftop --once --json`, while `dfml explain` replays a real round's chosen
# parents bit-exact from the decision record.
run_stage "mlobs-smoke" env JAX_PLATFORMS=cpu python tools/mlobs_smoke.py

# observability-smoke: one trace over the REAL rpc wire into two per-process
# span files, reassembled by dftrace — propagation, all-or-nothing sampling,
# and the critical-path identity (exclusive times sum to the root's wall)
# in one shot, without paying for the full tier-1 tracing suite again.
run_stage "observability-smoke" env JAX_PLATFORMS=cpu python -c "
import asyncio, json, os, tempfile
from dragonfly2_tpu.observability import tracing
from dragonfly2_tpu.rpc.core import RpcClient, RpcServer

d = tempfile.mkdtemp(prefix='df-obs-smoke-')
fa, fb = os.path.join(d, 'client.jsonl'), os.path.join(d, 'server.jsonl')

async def run():
    server_tr = tracing.Tracer(service='smoke-server', path=fb)
    client_tr = tracing.Tracer(service='smoke-client', path=fa)
    tracing._default = server_tr  # rpc.server spans land in the server file
    srv = RpcServer(port=0)
    async def echo(p):
        with server_tr.span('smoke.work'):
            await asyncio.sleep(0.01)
        return p
    srv.register('echo', echo)
    await srv.start()
    client = RpcClient(f'127.0.0.1:{srv.port}')
    with client_tr.span('smoke.root') as root:
        assert root.sampled
        await client.call('echo', {'x': 1})
    await client.close(); await srv.stop()
    client_tr.close(); server_tr.close()
    return root.trace_id

tid = asyncio.run(run())
from dragonfly2_tpu.cli import dftrace
spans = dftrace.load_spans([fa, fb])
traces = dftrace.assemble_traces(spans)
assert list(traces) == [tid], (list(traces), tid)
path = dftrace.critical_path(traces[tid])
names = [s['name'] for s, _ in path]
assert names[:3] == ['smoke.root', 'rpc.client', 'rpc.server'], names
wall = path[0][0]['duration_ms']
excl = sum(e for _s, e in path)
assert abs(excl - wall) < 0.01, (excl, wall)
print('observability smoke ok:', {'trace': tid[:8], 'path': names, 'wall_ms': round(wall, 2)})
"

summarize
echo "check.sh: all stages passed"
