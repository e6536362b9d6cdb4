"""Multi-chip evidence tests (north-star configs 2-4).

Convergence UNDER sharding on the 1k-node synthetic, mesh-shape invariance
and a 16-device run, on the mesh a run gets (`mesh_for_run`: every device on
`data`) — the CPU-simulated versions of the v5e-16 / v5p-64 topologies
(SURVEY.md §4 "cluster-in-a-box" strategy). All of them drive the served
program: one call of `multi_step`, batches sampled inside the scan from one key.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from dragonfly2_tpu.parallel import mesh as meshlib
from dragonfly2_tpu.trainer import synthetic, train_gnn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scan_losses(cfg, cluster, mesh, steps: int, seed: int) -> list[float]:
    """`steps` optimizer steps in one call of the served scan on `mesh`."""
    state, g, pool, multi_step = train_gnn.shard_for_training_scan(
        train_gnn.init_state(cfg, cluster.graph, rng_seed=seed), cluster.graph, cluster.pairs, mesh,
        batch_size=cfg.batch_size, steps_per_call=steps,
    )
    _, (losses, _) = multi_step(state, g, pool, jax.random.PRNGKey(seed))
    return np.asarray(losses).tolist()


def test_sharded_convergence_1k_nodes():
    """~50 sharded steps on the 1k-node synthetic: loss must collapse from
    the start and STAY collapsed (the dryrun's one-step 'it executes' is not
    convergence evidence; this is).

    Root cause of the F carried since PR 6: the original assertion demanded
    strictly-decreasing 10-step window means across all 50 steps, but this
    config converges by ~step 15 (window means 0.080 → 0.018) and then sits
    at the batch-sampling noise floor, where adjacent windows differ only by
    noise (measured 0.0168 vs 0.0181 — a 7% wiggle failing a strict `>`).
    Post-convergence monotonicity is not a property SGD has; the honest
    convergence evidence is (a) the initial descent, (b) every later window
    staying far below the start, (c) the final window at <50% of the first —
    which still fails loudly on divergence, non-learning, or a loss blow-up."""
    cluster = synthetic.make_cluster(num_nodes=1024, num_neighbors=16, num_pairs=8192, seed=7)
    mesh = meshlib.mesh_for_run()[0]  # 8 virtual devices: the program the service builds
    assert dict(mesh.shape) == {"data": 8}
    cfg = train_gnn.GNNTrainConfig(
        hidden=64, embed_dim=32, num_layers=2, batch_size=512, warmup_steps=5
    )
    losses = _scan_losses(cfg, cluster, mesh, 50, seed=7)
    assert all(np.isfinite(v) for v in losses)
    windows = [float(np.mean(losses[i : i + 10])) for i in range(0, 50, 10)]
    assert windows[1] < windows[0], f"no initial descent: {windows}"
    # converged-and-stayed: every post-descent window well below the start
    assert all(w < windows[0] * 0.6 for w in windows[1:]), f"regressed: {windows}"
    assert windows[-1] < windows[0] * 0.5, f"weak convergence: {windows}"


def test_mesh_shape_invariance_small():
    """The same seed must give (numerically close) trajectories on `{data:
    8}` and on one device — sharding is an execution layout, not a model
    change."""
    cluster = synthetic.make_cluster(num_nodes=64, num_neighbors=4, num_pairs=1024, seed=0)
    cfg = train_gnn.GNNTrainConfig(
        hidden=32, embed_dim=16, num_layers=2, batch_size=128, warmup_steps=2
    )
    trajectories = [
        _scan_losses(cfg, cluster, meshlib.mesh_for_run(jax.devices()[:n])[0], 8, seed=0) for n in (8, 1)
    ]
    np.testing.assert_allclose(trajectories[0], trajectories[1], rtol=2e-2)
    assert trajectories[0][-1] < trajectories[0][0]


@pytest.mark.parametrize("devices", [1, 4])
def test_a_placement_with_the_sorted_table_is_served_the_kept_program(monkeypatch, devices):
    """With the table TPU chips get (the test's word, `PLATFORM`; the kernel
    interpreted): one `EdgesByDst` on one device, an `EdgesByShard` of a table
    a row shard on a `data` mesh made anew by `mesh_for_run`. The table's
    shapes follow from N and K and its data is the run's, so a second graph
    of the same shapes is placed to the program the first one built, with no
    trace, and the first graph again gets its first losses back bit for bit."""
    from jax.experimental.pallas import tpu as pltpu

    from dragonfly2_tpu.ops import neighbor_agg_pallas as pk

    monkeypatch.setattr(pk, "PLATFORM", "cpu")
    monkeypatch.setattr(train_gnn, "_kept", {})
    cfg = train_gnn.GNNTrainConfig(hidden=128, embed_dim=16, num_layers=2, batch_size=64)
    programs, losses, traces = [], [], []
    for seed in (1, 2, 1):
        cluster = synthetic.make_cluster(num_nodes=1024, num_neighbors=16, num_pairs=512, seed=seed)
        mesh, _ = meshlib.mesh_for_run(jax.devices()[:devices])
        before = train_gnn._traces
        state, g, pool, multi_step = train_gnn.shard_for_training_scan(
            train_gnn.init_state(cfg, cluster.graph, 0), cluster.graph, cluster.pairs, mesh,
            batch_size=cfg.batch_size, steps_per_call=2)
        assert isinstance(g.by_dst, pk.EdgesByDst if devices == 1 else pk.EdgesByShard)
        # under a mesh Pallas's HLO interpreter: the TPU interpreter's callbacks hang there
        with pltpu.force_tpu_interpret_mode(True) if devices > 1 else pltpu.force_tpu_interpret_mode():
            _, (ls, _) = multi_step(state, g, pool, jax.random.PRNGKey(0))
            losses.append(np.asarray(ls).tolist())
        programs.append(multi_step)
        traces.append(train_gnn._traces - before)
    assert traces == [1, 0, 0] and programs[0] is programs[1] is programs[2] and programs[0]._cache_size() == 1
    assert losses[2] == losses[0] != losses[1] and np.isfinite(losses).all()


@pytest.mark.slow
def test_dryrun_16_devices_subprocess():
    """16-device variant in a fresh process (device count is frozen at
    backend init, so the in-process 8-device mesh can't be widened here).

    Marked slow (wall-clock buy-back): XLA compiling the 2-layer GNN step
    across 16 virtual CPU devices took minutes on a 2-core CI box (twice,
    while the dry run also built a mesh with a `model` axis) — a large share
    of the tier-1 budget for a pure 'it executes at 16 devices' smoke. The properties it
    guards stay tier-1-covered in-process: sharded convergence at 8 devices
    (test_sharded_convergence_1k_nodes) and mesh-shape invariance
    (test_mesh_shape_invariance_small). The full (`slow`) suite still runs
    it on capable hardware."""
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    out = subprocess.run(
        [sys.executable, "-c", "import __graft_entry__; __graft_entry__.dryrun_multichip(16, steps=10, virtual_cpu=True)"],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-1000:]
    lines = [l for l in out.stdout.splitlines() if l.startswith("dryrun_multichip ok")]
    assert len(lines) == 1
    assert "platform=cpu mesh={'data': 16} devices=16" in lines[0]

