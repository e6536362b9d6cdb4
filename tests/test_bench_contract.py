"""Contract tests for bench.py's measurement helpers.

bench.py holds the host microbenchmarks (the training step is measured by
benchmarks/run.py); these pin the parts a refactor could silently break: each
section's key set at a tiny size, the device sections whose failure fails the
run, and the one-line JSON payload schema.
"""

import json
import os
import subprocess
import sys

import bench


def test_jax_scoring_contract():
    """The device section left beside `mlp_train`: single-round rate, its p50
    and the multi-round rate of the JAX scorer, all measured."""
    single_rps, p50_ms, multi_rps = bench.bench_scoring(rounds=40, candidates=8)
    assert single_rps > 0 and p50_ms > 0 and multi_rps > 0


def test_device_sections_are_sections_the_worker_runs():
    """A failure of a section named in DEVICE_SECTIONS exits the worker
    non-zero: each name must be one `main()` hands to `run_section`, or the
    refusal would never fire."""
    import ast
    import inspect

    run = {
        call.args[0].value
        for call in ast.walk(ast.parse(inspect.getsource(bench.main)))
        if isinstance(call, ast.Call) and getattr(call.func, "id", None) == "run_section"
    }
    assert bench.DEVICE_SECTIONS == ("jax_scoring", "mlp_train")
    assert set(bench.DEVICE_SECTIONS) <= run, run


def test_dataset_build_contract():
    # tiny shapes: the contract is the key set and the A/B wiring, not the
    # (tier-1-hostile) 100k-row default the real bench runs
    out = bench.bench_dataset_build(n_downloads=2000, n_probes=500, n_hosts=64)
    for key in (
        "dataset_build_rows_per_sec", "rowloop_rows_per_sec",
        "speedup_vs_rowloop", "chunk_fold_rows_per_sec",
        "ingest_to_train_start_ms", "num_nodes", "num_pairs",
    ):
        assert key in out, key
    assert out["rows"] == 2500
    assert out["dataset_build_rows_per_sec"] > 0
    assert out["rowloop_rows_per_sec"] > 0
    assert out["speedup_vs_rowloop"] > 0
    assert out["num_nodes"] >= 64


def test_control_plane_contract():
    # tiny shapes again: pins the key set and the A/B + wire-leg wiring the
    # driver's control_plane JSON consumers depend on, not the real rates
    out = bench.bench_control_plane(
        rounds=50, candidates=8, hosts=24, pieces_per_round=4
    )
    for key in (
        "full_round_rps", "full_round_rps_rowwise_baseline", "full_round_speedup",
        "evaluator_prepare_us_per_round", "evaluator_prepare_us_rowwise",
        "prepare_speedup", "score_us_per_round", "piece_report_rpcs_per_round",
        "report_wire_us_per_piece_batched", "report_wire_us_per_piece_unary",
    ):
        assert key in out, key
    assert out["full_round_rps"] > 0
    assert out["full_round_rps_rowwise_baseline"] > 0
    assert out["evaluator_prepare_us_per_round"] > 0
    # the batched path's structural contract: ONE flush per dispatch round
    assert out["piece_report_rpcs_per_round"] == 1
    assert out["report_wire_us_per_piece_batched"] > 0


def test_observability_contract():
    # tiny shapes: pins the key set and the interleaved A/B wiring (rate 0
    # vs the shipped default vs 1.0) the driver's observability JSON
    # consumers read, not the real overhead numbers
    out = bench.bench_observability(rounds=30, span_loops=2_000, pipeline_mb=8)
    for key in (
        "trace_span_unsampled_ns", "trace_span_sampled_ns",
        "sched_round_rps_off", "sched_round_rps_default", "sched_round_rps_full",
        "sched_round_default_overhead_pct",
        "piece_pipeline_mb_per_s_off", "piece_pipeline_mb_per_s_default",
        "piece_pipeline_default_overhead_pct", "trace_sample_rate_default",
    ):
        assert key in out, key
    assert out["trace_span_unsampled_ns"] > 0
    assert out["trace_span_sampled_ns"] > 0
    assert out["sched_round_rps_off"] > 0
    assert out["piece_pipeline_mb_per_s_off"] > 0
    # the default tracer must be restored: later sections (and the rest of
    # this test process) depend on it
    from dragonfly2_tpu.observability.tracing import default_tracer

    assert default_tracer().service != "bench"


def test_metrics_plane_contract():
    # tiny shapes: pins the key set and the ISSUE 12 acceptance — the
    # recorder's registry walk costs ≤1% of its sample interval (the
    # deterministic implied figure; the A/B pct carries 2-core scheduler
    # noise of the same magnitude as the effect and is pinned loosely)
    out = bench.bench_metrics_plane(rounds=60, sample_probes=10)
    for key in (
        "metrics_plane_round_rps_off", "metrics_plane_round_rps_on",
        "recorder_ab_interval_s", "recorder_ab_samples",
        "recorder_overhead_pct", "recorder_sample_cost_us",
        "recorder_implied_overhead_pct", "recorder_series",
        "recorder_interval_s", "alert_eval_cost_us",
        "stats_frame_bytes", "stats_frame_build_us",
    ):
        assert key in out, key
    assert out["metrics_plane_round_rps_off"] > 0
    assert out["metrics_plane_round_rps_on"] > 0
    # the 'on' leg must have actually SAMPLED during the timed region (the
    # leg recorder's interval is calibrated to the leg duration) — without
    # this the A/B silently compares two recorder-off runs
    assert out["recorder_ab_samples"] >= 1
    # the acceptance bound: one walk of a serving-scheduler-shaped registry
    # at the shipped 2 s cadence costs ≤1% of the interval
    assert out["recorder_series"] >= 50
    assert out["recorder_sample_cost_us"] > 0
    assert out["recorder_implied_overhead_pct"] <= 1.0
    # the A/B on a noisy 2-core box: gross-regression canary only — at the
    # tiny contract shape under a loaded tier-1 suite the scheduler-noise
    # floor alone reads ±20%, so this bound exists to catch "sampling moved
    # onto the round path" (which reads >100%), not to measure overhead
    # (bench's full-shape A/B and the deterministic implied figure do that)
    assert abs(out["recorder_overhead_pct"]) < 75.0
    # frames ride every keepalive: they must stay compact
    assert 0 < out["stats_frame_bytes"] < 4096
    assert out["alert_eval_cost_us"] > 0


def test_ml_observability_contract():
    # tiny shapes: pins the key set and the ISSUE 15 acceptance — decision
    # recorder + live drift sketch imply ≤1% of the real serial round at
    # the shipped default strides (the deterministic figure; the A/B pct
    # carries 2-core scheduler noise of the same magnitude as the effect,
    # exactly like the metrics_plane section, and is pinned loosely as a
    # gross-regression canary only)
    out = bench.bench_ml_observability(rounds=150, probes=40)
    for key in (
        "ml_obs_round_rps_off", "ml_obs_round_rps_on", "ml_obs_overhead_pct",
        "ml_obs_implied_overhead_pct", "ml_obs_decision_sample_rate",
        "decision_record_us", "sketch_update_ns_per_row", "drift_score_us",
        "decision_ring_records",
    ):
        assert key in out, key
    assert out["ml_obs_round_rps_off"] > 0
    assert out["ml_obs_round_rps_on"] > 0
    assert out["decision_record_us"] > 0
    assert out["sketch_update_ns_per_row"] > 0
    assert out["drift_score_us"] > 0
    # rounds actually recorded at the default stride during the on legs
    assert out["decision_ring_records"] >= 1
    # the acceptance bound (deterministic, noise-free by construction)
    assert out["ml_obs_implied_overhead_pct"] <= 1.0
    # gross-regression canary: "recording moved onto every round" reads
    # far above this; honest overhead reads inside the noise floor
    assert abs(out["ml_obs_overhead_pct"]) < 75.0
    # the shipped default must stay sampled (a 1.0 default would make the
    # implied figure meaningless and the ring a per-round tax)
    assert 0 < out["ml_obs_decision_sample_rate"] <= 0.1


def test_round_loop_contract():
    # tiny shapes: pins the ISSUE 18 round_loop key set and the A/B wiring
    # (same draws per leg, drive-call accounting, commit-tail probe). On a
    # toolchain-less host every key must be present AND null (never 0.0 —
    # VERDICT #8); with the native scorer the legs must have run for real.
    out = bench.bench_round_loop(rounds=64, batch=8, candidates=8, hosts=48)
    for key in (
        "native_rounds_per_s", "serial_rounds_per_s", "speedup",
        "ffi_calls_per_round", "commit_ms", "native_coverage", "equivalent",
        "mirror_rounds_per_s", "mirror_speedup", "mirror_coverage",
        "mirror_full_syncs", "mirror_equivalent",
    ):
        assert key in out, key
    if out["native_rounds_per_s"] is None:
        # skipped section: NO key may carry a measured-looking zero
        assert all(v is None for v in out.values())
        return
    assert out["native_rounds_per_s"] > 0
    assert out["serial_rounds_per_s"] > 0
    assert out["speedup"] > 0
    # one drive FFI per batch when the driver carries every round
    assert 0 < out["ffi_calls_per_round"] <= 1
    assert out["commit_ms"] >= 0
    assert out["native_coverage"] == 1.0
    # the A/B is void unless the legs pick byte-identical parents
    assert out["equivalent"] is True
    # ISSUE 19: the mirror leg ran, matched the serial leg byte-for-byte,
    # drove every round off the mirror (native or stale-revalidated), and
    # paid exactly ONE full export — the attach; a second would mean the
    # delta hooks leaked a re-sync
    assert out["mirror_rounds_per_s"] > 0
    assert out["mirror_speedup"] > 0
    assert out["mirror_equivalent"] is True
    assert out["mirror_coverage"] == 1.0
    assert out["mirror_full_syncs"] == 1


def test_ml_observability_shadow_keys():
    # the batched-shadow satellite keys (sample rate 1.0 serial-vs-batched
    # A/B): present always; null together when the toolchain is absent
    out = bench.bench_ml_observability(rounds=60, probes=24)
    for key in (
        "shadow_round_us_serial", "shadow_round_us_batched",
        "shadow_batched_recovery_pct",
    ):
        assert key in out, key
    vals = [
        out["shadow_round_us_serial"], out["shadow_round_us_batched"],
        out["shadow_batched_recovery_pct"],
    ]
    assert all(v is None for v in vals) or all(v is not None for v in vals)
    if vals[0] is not None:
        assert vals[0] > 0 and vals[1] > 0


def test_federation_contract():
    # tiny shapes: pins the key set, the interleaved 1-vs-2 swarm wiring,
    # and the WATERMARK property (steady-state sync payload is O(changed
    # edges): zero at steady state, exactly one after one probe) — the
    # ISSUE 10 counter-assert. Two real scheduler subprocesses ride this.
    # 16 tasks, not fewer: scheduler ports are random per run, so ring
    # placement of the fixed task ids re-randomizes — with 4 tasks all of
    # them land on ONE member ~1 run in 8 and the share assertion below
    # would flake; P(16 on one side) ~ 3e-5
    out = bench.bench_federation(
        peers=8, tasks=16, pieces=2, duration=0.6, reps=1, probe_edges=8
    )
    for key in (
        "swarm_rps_1sched", "swarm_rps_2sched", "swarm_speedup_2v1",
        "per_scheduler_round_share", "swarm_errors", "sync_convergence_ms",
        "sync_payload_edges_initial", "sync_payload_edges_steady",
        "sync_payload_edges_after_one_probe", "reshard_moved_frac_join_1to2",
        "reshard_moved_frac_leave_3to2",
    ):
        assert key in out, key
    assert out["swarm_rps_1sched"] > 0
    assert out["swarm_rps_2sched"] > 0
    assert out["swarm_errors"] == 0
    # both ring members actually served rounds
    share = out["per_scheduler_round_share"]
    assert len(share) == 2 and all(v > 0 for v in share.values()), share
    # the watermark contract: cold pull ships the probes, steady pull ships
    # NOTHING, one new probe ships exactly one edge
    assert out["sync_payload_edges_initial"] >= 8
    assert out["sync_payload_edges_steady"] == 0
    assert out["sync_payload_edges_after_one_probe"] == 1
    assert out["sync_convergence_ms"] is not None and out["sync_convergence_ms"] > 0
    # consistent hashing: a join moves a bounded fraction of keys, not all
    assert 0.2 < out["reshard_moved_frac_join_1to2"] < 0.75


def test_swarm_sim_contract():
    # tiny shapes: one ladder rung at 600 peers pins the key set, the
    # null-hygiene shape, and the scenario-level properties the driver's
    # swarm_sim JSON consumers read — the real scale number comes from the
    # full bench run's ladder
    out = bench.bench_swarm_sim(wall_budget_s=4.0, start_peers=600, max_peers=600)
    for key in (
        "swarm_sim_events_per_sec", "swarm_sim_peers", "swarm_sim_events",
        "swarm_sim_wall_s", "swarm_sim_virtual_s", "swarm_sim_time_compression",
        "swarm_sim_flash_origin_egress_ratio", "swarm_sim_same_region_frac",
        "swarm_sim_completed_frac", "swarm_sim_fed_convergence_virtual_s",
        "swarm_sim_wall_budget_s",
    ):
        assert key in out, key
    assert out["swarm_sim_peers"] == 600
    assert out["swarm_sim_events_per_sec"] > 0
    assert out["swarm_sim_events"] > 600  # more events than peers: real rounds ran
    # virtual time outruns the wall by construction (the whole point)
    assert out["swarm_sim_time_compression"] > 1.0
    # the O(1)-egress property at tiny scale: a bounded number of task-sized
    # origin fetches, not one per peer
    assert 0 < out["swarm_sim_flash_origin_egress_ratio"] <= 8.0
    assert out["swarm_sim_completed_frac"] >= 0.95
    # 2 ring members gossip in the scenario: convergence must be measured
    assert out["swarm_sim_fed_convergence_virtual_s"] is not None
    assert out["swarm_sim_fed_convergence_virtual_s"] > 0


def test_overload_contract():
    # tiny shape: the ISSUE 17 brownout A/B at 600 peers pins the key set
    # and the acceptance direction — the scenario is scale-invariant in
    # time (fixed burst window, cost derived from peers), so the reduced
    # arm exercises the same ladder/storm dynamics as the 10^4 run
    out = bench.bench_overload(peers=600)
    for key in (
        "overload_peers", "overload_factor", "overload_goodput_ratio",
        "overload_goodput_on_frac", "overload_goodput_off_frac",
        "overload_admitted_p99_ms_on", "overload_max_level_on",
        "overload_refused_on", "overload_retry_storm_off",
    ):
        assert key in out, key
    assert out["overload_peers"] == 600
    assert out["overload_factor"] == 4.0
    # the headline: shedding ON sustains >= 2x the goodput of OFF at 4x
    # overload (the ISSUE 17 acceptance bar)
    assert out["overload_goodput_ratio"] >= 2.0, out
    assert out["overload_goodput_on_frac"] >= 0.9
    # the ladder reached admission control and typed refusals went out
    assert out["overload_max_level_on"] == 4
    assert out["overload_refused_on"] > 0
    # the unshedded arm burned a storm of retries — that's what ON avoids
    assert out["overload_retry_storm_off"] > out["overload_refused_on"] * 0.1
    assert 0 < out["overload_admitted_p99_ms_on"] <= 150_000.0


def test_piece_pipeline_contract():
    # tiny shape: pins the ISSUE 13 key set — TLS fast path (cipher A/B,
    # handshake storm, kTLS null-probe), striped-vs-single A/B over real
    # subprocess parents, adaptive write-behind decision + both legs — and
    # the null/"skipped" hygiene (VERDICT #8): TLS keys may be None as a
    # SET (no CA backend), never fabricated zeros.
    out = bench.bench_piece_pipeline(total_mb=16, piece_mb=4)
    for key in (
        "recv_mb_per_s", "hash_mb_per_s", "write_mb_per_s",
        "serial_mb_per_s", "pipelined_mb_per_s",
        "plain_transport_mb_per_s", "mtls_transport_mb_per_s",
        "mtls_stream_mb_per_s", "tls_cipher_policy", "tls_aes_accel",
        "aesgcm_transport_mb_per_s", "chacha20_transport_mb_per_s",
        "cipher_autoselect_gain_pct", "tls_handshake_full_ms",
        "tls_handshake_resumed_ms", "tls_resumption_hit_rate",
        "pipelined_tls_mb_per_s", "pipelined_plain_e2e_mb_per_s",
        "tls_overhead_pct", "ktls",
        "single_parent_mb_per_s", "striped_mb_per_s", "striped_speedup",
        "stripe_parents_used", "stripe_parent_cap_mb_per_s",
        "write_behind_mb_per_s_inline", "write_behind_mb_per_s_deferred",
        "write_behind_decision", "write_behind_recv_ms", "write_behind_write_ms",
    ):
        assert key in out, key
    assert out["pipelined_mb_per_s"] > 0
    tls_ran = out["mtls_transport_mb_per_s"] is not None
    if tls_ran:
        # this image has the openssl CLI backend, so the suite must RUN
        assert out["tls_cipher_policy"] in ("aes-gcm", "chacha20")
        assert out["aesgcm_transport_mb_per_s"] > 0
        assert out["chacha20_transport_mb_per_s"] > 0
        # the reconnect-storm acceptance: ≥ 0.9 of post-first connects resume
        assert out["tls_resumption_hit_rate"] >= 0.9
        assert out["tls_handshake_full_ms"] > 0
        # kTLS is a PROBE RESULT, never a number: structured null-report
        assert set(out["ktls"]) == {"available", "reason"}
        assert isinstance(out["ktls"]["available"], bool)
    else:
        # skipped => the whole TLS key set is null, no fabricated zeros
        assert out["tls_overhead_pct"] is None
        assert out["tls_resumption_hit_rate"] is None
    if out["striped_speedup"] is not None:
        # two rate-capped parents: striping must beat one parent's ceiling
        # (the real acceptance bar of 1.3x is pinned by the full-shape
        # bench; the tiny shape asserts direction, not magnitude)
        assert out["stripe_parents_used"] == 2
        if out["striped_mb_per_s"] > 1.1 * out["stripe_parent_cap_mb_per_s"]:
            # the child consumed past ONE parent's cap: striping genuinely
            # aggregated both ceilings, so the direction signal is real
            assert out["striped_speedup"] > 1.1, out["striped_speedup"]
        else:
            # consumer-bound run: on a loaded 2-core box the child's
            # recv+hash ceiling sits below one parent's 150 MB/s cap, BOTH
            # legs read the child's ceiling, and the A/B cannot resolve
            # striping either way (observed bimodal 0.98-1.0 loaded vs
            # 1.5-1.6 quiet). The mechanism proof above (width 2) stands;
            # only refute if striping actively HURT.
            assert out["striped_speedup"] > 0.85, out["striped_speedup"]
    assert out["write_behind_decision"] in ("inline", "deferred", "measuring")
    assert out["write_behind_mb_per_s_inline"] > 0
    assert out["write_behind_mb_per_s_deferred"] > 0


def test_payload_schema():
    line = bench._payload(1234.5, {"platform": "cpu"})
    d = json.loads(line)
    assert set(d) == {"metric", "value", "unit", "vs_baseline", "extra"}
    assert d["metric"] == "scheduler_scoring_calls_per_sec"
    assert d["value"] == 1234.5
    assert d["vs_baseline"] == round(1234.5 / 10_000, 3)
    assert d["extra"]["platform"] == "cpu"


def test_supervisor_refuses_cpu_unless_the_caller_forced_it():
    """JAX carries on on the CPU when it finds no accelerator; bench.py must
    not: one JSON line naming the platform, and a non-zero exit."""
    env = {k: v for k, v in os.environ.items() if k != "DF_BENCH_FORCE_CPU"}
    out = subprocess.run(
        [sys.executable, bench.__file__], env={**env, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 1
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["extra"]["platform"] == "cpu" and "no accelerator" in d["extra"]["errors"]["init"]
