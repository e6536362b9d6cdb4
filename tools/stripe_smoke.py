#!/usr/bin/env python
"""Stripe-smoke: cluster-in-a-box with mTLS ON — one hot multi-piece task
fetched STRIPED across two parents' TLS upload servers (real TCP wire),
sha256 bit-exact, per-parent byte counters proving both parents actually
served stripes. The check.sh leg for ISSUE 13's data plane v2.

    python tools/stripe_smoke.py
"""

import asyncio
import hashlib
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PIECE = 4 << 20
PIECES = 6


async def main() -> int:
    from dragonfly2_tpu.daemon import metrics
    from dragonfly2_tpu.daemon.conductor import ConductorConfig, PeerTaskConductor
    from dragonfly2_tpu.daemon.engine import InProcessSchedulerClient
    from dragonfly2_tpu.daemon.source import SourceRegistry
    from dragonfly2_tpu.daemon.storage import StorageManager
    from dragonfly2_tpu.daemon.upload import UploadServer
    from dragonfly2_tpu.scheduler.service import HostInfo, SchedulerService, TaskMeta
    from dragonfly2_tpu.security.ca import CertificateAuthority, write_issued
    from dragonfly2_tpu.security.transport import DataPlaneTls
    from dragonfly2_tpu.utils.pieces import Range

    payload = os.urandom(PIECE) * PIECES
    want_sha = hashlib.sha256(payload).hexdigest()
    with tempfile.TemporaryDirectory(prefix="df-stripe-smoke-") as td:
        # manager-CA posture: one cluster CA, one leaf per the PR 6 plane
        ca = CertificateAuthority(os.path.join(td, "ca"))
        leaf = ca.issue("stripe-smoke", sans=["127.0.0.1"])
        paths = write_issued(leaf, os.path.join(td, "leaf"))
        tls = DataPlaneTls.from_paths(
            paths["cert"], paths["key"], paths["ca"], microbench=False
        )
        print(f"stripe-smoke: mTLS on, cipher={tls.policy}, ktls={tls.ktls['reason']}")

        svc = SchedulerService()
        client = InProcessSchedulerClient(svc)
        task_id = "stripesmoketask0"
        url = f"d7y://stripe-smoke/{task_id}"
        servers = []
        for i in range(2):
            sm = StorageManager(os.path.join(td, f"parent{i}"))
            ts = sm.register_task(task_id, url=url)
            ts.set_task_info(
                content_length=len(payload), piece_size=PIECE, total_pieces=PIECES
            )
            for idx in range(PIECES):
                await ts.write_piece(idx, payload[idx * PIECE : (idx + 1) * PIECE])
            ts.mark_done()
            srv = UploadServer(sm, tls=tls.server_ctx)
            await srv.start()
            servers.append(srv)
            await client.announce_task(  # dflint: disable=DF025 one announce per parent at smoke setup (2 iterations), not a hot path
                f"stripe-parent{i}",
                TaskMeta(task_id=task_id, url=url),
                HostInfo(
                    id=f"stripe-host{i}", ip="127.0.0.1",
                    hostname=f"stripe-parent-{i}", download_port=srv.port,
                ),
                content_length=len(payload), piece_size=PIECE,
                piece_indices=list(range(PIECES)),
            )

        hs0 = metrics.PIECE_TLS_HANDSHAKES_TOTAL.value
        conductor = PeerTaskConductor(
            peer_id="stripe-smoke-child",
            meta=TaskMeta(task_id=task_id, url=url),
            host=HostInfo(id="stripe-child-host", ip="127.0.0.1", hostname="stripe-child"),
            scheduler=client,
            storage=StorageManager(os.path.join(td, "child")),
            sources=SourceRegistry(),
            # tail_steal off: a steal DELIBERATELY double-fetches a slow tail
            # piece, which would trip the exact-served-bytes gate below on a
            # loaded box even though the system behaved as designed
            config=ConductorConfig(metadata_poll_interval=0.02, tail_steal=False),
            data_tls=tls,
        )
        conductor.dispatcher.epsilon = 0.0  # deterministic stripes for the gate
        try:
            ts = await asyncio.wait_for(conductor.run(), 120)
            data = await ts.read_range(Range(0, ts.meta.content_length))
        finally:
            for srv in servers:
                await srv.stop()

        got_sha = hashlib.sha256(bytes(data)).hexdigest()
        served = [srv.bytes_served for srv in servers]
        handshakes = metrics.PIECE_TLS_HANDSHAKES_TOTAL.value - hs0
        print(
            f"stripe-smoke: sha256 {'OK' if got_sha == want_sha else 'MISMATCH'}; "
            f"per-parent bytes served={served}; stripes by parent="
            f"{conductor.pieces_by_parent}; TLS handshakes={handshakes:.0f}"
        )
        assert got_sha == want_sha, "striped mTLS fetch not bit-exact"
        assert len(conductor.pieces_by_parent) == 2, (
            f"striping did not engage both parents: {conductor.pieces_by_parent}"
        )
        assert all(b > 0 for b in served), f"a parent served nothing: {served}"
        assert sum(served) == len(payload), (
            f"served bytes {sum(served)} != payload {len(payload)} "
            "(double-fetch or short serve)"
        )
        assert handshakes >= 2, "both parents must have TLS-handshaked"
        print("stripe-smoke ok")
        return 0


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
