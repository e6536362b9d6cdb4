"""One process of a multi-process sharded GNN training job (config 3 shape).

Launched per-host by `distributed.launch_localhost` (tests / dry runs) or by
the real pod launcher: initializes jax.distributed from DF_DIST_* env, builds
the global ("data", "model") mesh over ALL processes' devices, and runs
DF_MP_STEPS training steps in one call of the served scan: the pair pool
replicated on the global mesh, batches sampled on the device. Process 0 prints
the loss trajectory as `MP_LOSSES <json>`.

This is the code path the reference never had (its trainer dropped dataset
chunks on the floor, pkg/rpc/trainer/server/server.go:59): data parallelism
across hosts over DCN/Gloo, tensor parallelism inside a host — the same jit
and shardings as single-process training; only initialization differs.
"""

from __future__ import annotations

import json
import os


def main() -> None:
    from dragonfly2_tpu.parallel import distributed as dist

    cfg = dist.DistributedConfig.from_env()
    dist.initialize(cfg)

    import jax

    from dragonfly2_tpu.utils import jaxenv

    jaxenv.enable_compile_cache()
    import numpy as np

    from dragonfly2_tpu.parallel import mesh as meshlib
    from dragonfly2_tpu.trainer import synthetic, train_gnn

    steps = int(os.environ.get("DF_MP_STEPS", "12"))
    num_nodes = int(os.environ.get("DF_MP_NODES", "128"))
    mesh = meshlib.make_mesh()  # all processes' devices → global mesh
    cluster = synthetic.make_cluster(
        num_nodes=num_nodes, num_neighbors=8, num_pairs=4096, seed=3
    )
    tcfg = train_gnn.GNNTrainConfig(
        hidden=32,
        embed_dim=16,
        num_layers=2,
        batch_size=meshlib.pad_to_multiple(256, mesh.shape[meshlib.DATA_AXIS]),
        warmup_steps=2,
    )
    state = train_gnn.init_state(tcfg, cluster.graph, rng_seed=0)
    state, g, pool, multi_step = train_gnn.shard_for_training_scan(
        state, cluster.graph, cluster.pairs, mesh, batch_size=tcfg.batch_size, steps_per_call=steps
    )
    # same key everywhere → same global batches
    state, (ls, _) = multi_step(state, g, pool, jax.random.PRNGKey(0))
    losses = [float(v) for v in np.asarray(ls)]
    if jax.process_index() == 0:
        print(
            f"mp_train ok: platform={jax.devices()[0].platform} "
            f"procs={jax.process_count()} devices={len(jax.devices())} "
            f"mesh={dict(mesh.shape)} steps={steps}",
            flush=True,
        )
        print("MP_LOSSES " + json.dumps([round(v, 6) for v in losses]), flush=True)


if __name__ == "__main__":
    main()
