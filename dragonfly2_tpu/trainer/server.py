"""Trainer process entry (the service the reference left as config+metrics).

`python -m dragonfly2_tpu.trainer.server --port 9300 --manager 127.0.0.1:9200
--model-dir /var/lib/df/models`

This is the one process on a TPU-VM host that opens the accelerator (every
other service pins the host CPU, utils/jaxenv.py). It does so before
TRAINER_READY — a chip that cannot be opened fails the start, not the first
training run — and `status` names the platform it got.
"""

from __future__ import annotations

import argparse
import asyncio
import logging

from dragonfly2_tpu.rpc.core import RpcServer
from dragonfly2_tpu.rpc.trainer import register_trainer
from dragonfly2_tpu.trainer.service import TrainerConfig, TrainerService
from dragonfly2_tpu.utils import jaxenv
from dragonfly2_tpu.utils.proc import run_until_signalled

logger = logging.getLogger("trainer")


async def run_trainer(
    *,
    host: str = "127.0.0.1",
    port: int = 9300,
    model_dir: str = "/tmp/dragonfly2_tpu_models",
    manager_addr: str | None = None,
    gnn_steps: int = 300,
    gnn_hidden: int | None = None,
    mlp_steps: int | None = None,
    min_pairs: int | None = None,
    min_probe_rows: int | None = None,
    stats_interval: float = 20.0,
    ready_event: asyncio.Event | None = None,
) -> None:
    import dataclasses

    manager = None
    if manager_addr:
        from dragonfly2_tpu.rpc.manager import RemoteManagerClient

        manager = RemoteManagerClient(manager_addr)
    cfg = TrainerConfig(model_dir=model_dir, gnn_steps=gnn_steps)
    # overrides replace ONLY the named hyperparameter — every other field
    # keeps its production default
    if gnn_hidden is not None:
        cfg.gnn = dataclasses.replace(
            cfg.gnn, hidden=gnn_hidden, embed_dim=max(16, gnn_hidden // 2),
            batch_size=min(cfg.gnn.batch_size, gnn_hidden * 4),
        )
    if mlp_steps is not None:
        cfg.mlp = dataclasses.replace(cfg.mlp, steps=mlp_steps)
    if min_pairs is not None:
        cfg.min_pairs = min_pairs
    if min_probe_rows is not None:
        cfg.min_probe_rows = min_probe_rows
    service = TrainerService(cfg, manager=manager)
    server = RpcServer(host=host, port=port)
    register_trainer(server, service)
    await server.start()
    logger.info("trainer listening on %s, device %s", server.address, service.device)
    # cluster metrics plane (ISSUE 12): the trainer is a member of the
    # cluster view too — its frame (loop lag + whatever trainer families
    # exist) rides a keepalive tick like every other service
    from dragonfly2_tpu.observability.timeseries import (
        build_stats_frame,
        default_recorder,
    )

    recorder = default_recorder()
    recorder.start()
    stats_task = None
    if manager is not None:
        import socket as _socket

        trainer_host = _socket.gethostname()

        async def stats_loop() -> None:
            while True:
                await asyncio.sleep(stats_interval)
                try:
                    frame = build_stats_frame(
                        recorder, service="trainer", hostname=trainer_host
                    )
                    await manager.keepalive("trainer", trainer_host, stats=frame)
                except Exception:
                    logger.debug("stats frame push failed", exc_info=True)

        stats_task = asyncio.ensure_future(stats_loop())
    print(f"TRAINER_READY {server.address}", flush=True)
    try:
        await run_until_signalled(ready_event)
    finally:
        recorder.stop()
        if stats_task is not None:
            stats_task.cancel()
        await server.stop()
        if manager is not None:
            await manager.close()


def main() -> None:
    ap = argparse.ArgumentParser(description="dragonfly2_tpu trainer")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=9300)
    ap.add_argument("--model-dir", default="/tmp/dragonfly2_tpu_models")
    ap.add_argument("--manager", default=None)
    ap.add_argument("--gnn-steps", type=int, default=300)
    ap.add_argument("--gnn-hidden", type=int, default=None,
                    help="override GNN width (small clusters / tests)")
    ap.add_argument("--mlp-steps", type=int, default=None,
                    help="override MLP training steps")
    ap.add_argument("--min-pairs", type=int, default=None,
                    help="minimum (parent,child) rows before training")
    ap.add_argument("--min-probe-rows", type=int, default=None,
                    help="minimum probe rows before GNN training")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args()
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    logger.info("compile cache at %s", jaxenv.enable_compile_cache())
    # every sampled span of this process is also a TraceMe event of the
    # profiler that can trace the chip, under its own name and on the device
    # trace's clock (an atomic load while no profiler session runs)
    import jax.profiler

    from dragonfly2_tpu.observability.tracing import configure_default_tracer

    configure_default_tracer(service="trainer").annotate = jax.profiler.TraceAnnotation
    asyncio.run(
        run_trainer(
            host=args.host, port=args.port, model_dir=args.model_dir,
            manager_addr=args.manager, gnn_steps=args.gnn_steps,
            gnn_hidden=args.gnn_hidden, mlp_steps=args.mlp_steps,
            min_pairs=args.min_pairs, min_probe_rows=args.min_probe_rows,
        )
    )


if __name__ == "__main__":
    main()
