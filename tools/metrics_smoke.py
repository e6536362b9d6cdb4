"""check.sh metrics-smoke leg (ISSUE 12): the cluster metrics plane against
the REAL cluster-in-a-box.

Boots manager + 2 federated schedulers + 2 daemons + origin as subprocesses
(cli/dfcluster) with fast keepalive/recorder/alert cadences, pushes real
dfget traffic through the federation, then asserts the whole plane:

  1. `dftop --once --json` shows EVERY member (2 schedulers + 2 daemons)
     reporting a fresh stats frame with windowed rates, and the daemons'
     byte rates are LIVE (non-zero after the transfers).
  2. An induced serving regression raises its SLO alert within one rule
     interval: the schedulers run `--evaluator ml` with NO model published,
     so every scheduling round is a base fallback — the base_fallback_rate
     ratio rule (same ratio shape as scorer_error_rate, whose flip timing
     is unit-tested in-process in tests/test_metrics_plane.py) must flip on
     the first evaluation that sees the windowed burst, travel inside the
     scheduler's stats frame, and surface in dftop's cluster alert union.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

ALERT_INTERVAL_S = 1.0
TS_INTERVAL_S = 0.5
KEEPALIVE_S = 1.0


def dftop_once(manager_addr: str) -> tuple[int, dict]:
    r = subprocess.run(
        [sys.executable, "-m", "dragonfly2_tpu.cli.dftop",
         "--manager", manager_addr, "--once", "--json"],
        capture_output=True, text=True, timeout=30,
        env=dict(os.environ, PYTHONPATH=str(REPO)),
    )
    doc = json.loads(r.stdout) if r.stdout.strip() else {}
    return r.returncode, doc


def main() -> int:
    from dragonfly2_tpu.cli.dfcluster import Cluster, ClusterError

    # fast plane cadences for the subprocesses (inherited via the
    # environment): recorder 0.5 s, alert evaluation 1 s, keepalive 1 s
    os.environ["DRAGONFLY_TS_INTERVAL"] = str(TS_INTERVAL_S)
    os.environ["DRAGONFLY_ALERT_INTERVAL"] = str(ALERT_INTERVAL_S)

    root = tempfile.mkdtemp(prefix="df-metrics-smoke-")
    cluster = Cluster(root)
    rc = 0
    try:
        cluster.up(
            schedulers=2, daemons=2, federation_interval=1.0,
            extra_scheduler_args=[
                "--keepalive-interval", str(KEEPALIVE_S),
                "--evaluator", "ml",  # no model ever publishes → 100% fallback
            ],
            extra_daemon_args=["--announce-interval", str(KEEPALIVE_S)],
        )

        # real traffic: multi-piece payloads so the P2P legs run NORMAL
        # scheduling rounds (the fallback-burst source) and the daemons'
        # byte counters move
        for i in range(3):
            payload = os.urandom(5 * 1024 * 1024 + i * 4096)
            want = hashlib.sha256(payload).hexdigest()
            url = cluster.write_origin_file(f"smoke-{i}.bin", payload)
            for d in (0, 1):
                out = os.path.join(root, f"out-{i}-{d}.bin")
                r = cluster.dfget(d, url, out, timeout=120)
                if r.returncode != 0:
                    raise ClusterError(f"dfget {i}/{d} failed: {r.stderr}")
                with open(out, "rb") as f:
                    got = hashlib.sha256(f.read()).hexdigest()
                if got != want:
                    raise ClusterError(f"out-{i}-{d}.bin corrupt")
        # fallback-burst amplifier: the dfgets alone leave the fallback/round
        # ratio near 0.4 (seed legs are back-to-source rounds that never
        # reach the evaluator) — a short swarm drives scheduled-parents
        # rounds, every one of which the model-less ml evaluator serves via
        # base fallback, pushing the windowed ratio decisively past the 0.5
        # rule bound
        r = subprocess.run(
            [sys.executable, "-m", "dragonfly2_tpu.cli.dfstress", "--swarm",
             "--schedulers", ",".join(cluster.scheduler_addrs),
             "--peers", "30", "--duration", "4"],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, PYTHONPATH=str(REPO)),
        )
        if r.returncode != 0:
            raise ClusterError(f"swarm phase failed: {r.stderr or r.stdout}")
        traffic_done = time.monotonic()
        print("metrics-smoke: traffic done (3 payloads x 2 daemons + swarm)",
              flush=True)

        # ---- 1. every member reports a live frame ----------------------
        deadline = time.monotonic() + 30
        doc: dict = {}
        while time.monotonic() < deadline:
            code, doc = dftop_once(cluster.manager_addr)
            members = {
                (m["source_type"], m["hostname"])
                for m in doc.get("members", ())
                if not m.get("stale")
            }
            if code == 0 and len(members) >= 4:
                break
            time.sleep(1.0)
        else:
            raise ClusterError(
                f"not every member reported a frame: {json.dumps(doc)[:800]}"
            )
        kinds = [m["source_type"] for m in doc["members"]]
        assert kinds.count("scheduler") == 2, kinds
        assert kinds.count("daemon") == 2, kinds
        daemon_bytes = sum(
            (m["frame"].get("rates") or {}).get("piece_down_mb_per_s", 0.0)
            + (m["frame"].get("rates") or {}).get("piece_up_mb_per_s", 0.0)
            for m in doc["members"] if m["source_type"] == "daemon"
        )
        if daemon_bytes <= 0:
            raise ClusterError(
                f"daemon byte rates are not live: {json.dumps(doc['members'])[:800]}"
            )
        sched_rounds = sum(
            (m["frame"].get("rates") or {}).get("rounds_per_s", 0.0)
            for m in doc["members"] if m["source_type"] == "scheduler"
        )
        if sched_rounds <= 0:
            raise ClusterError("no scheduler reported a live round rate")
        print(
            f"metrics-smoke: all 4 members live — cluster rates "
            f"{json.dumps(doc['cluster']['rates'])}", flush=True,
        )

        # ---- 2. the induced fallback burst raises its alert ------------
        # every round above was a base fallback (ml evaluator, no model);
        # the rule has for_s=0, so the first evaluation that sees the
        # windowed ratio must flip it — bound the observed latency by the
        # full pipeline cadence (recorder tick + alert tick + keepalive +
        # one dftop poll), NOT by a generous grab-bag timeout
        budget = TS_INTERVAL_S + ALERT_INTERVAL_S + KEEPALIVE_S + 2.0
        deadline = time.monotonic() + max(budget * 3, 15.0)
        alert_seen = None
        while time.monotonic() < deadline:
            _code, doc = dftop_once(cluster.manager_addr)
            names = {a["name"] for a in doc.get("cluster", {}).get("alerts", ())}
            if "base_fallback_rate" in names:
                alert_seen = time.monotonic()
                break
            time.sleep(0.5)
        if alert_seen is None:
            raise ClusterError(
                f"base_fallback_rate never fired: {json.dumps(doc)[:800]}"
            )
        latency = alert_seen - traffic_done
        print(
            f"metrics-smoke: base_fallback_rate alert live {latency:.1f}s after "
            f"traffic (pipeline cadence budget {budget:.1f}s/poll)", flush=True,
        )
        members_with_alert = {
            a["member"] for a in doc["cluster"]["alerts"]
            if a["name"] == "base_fallback_rate"
        }
        print(
            f"metrics-smoke: ok — alert attributed to {sorted(members_with_alert)}",
            flush=True,
        )
    except ClusterError as e:
        print(f"metrics-smoke: FAIL — {e}", file=sys.stderr, flush=True)
        rc = 1
    except Exception as e:
        import traceback

        traceback.print_exc()
        print(f"metrics-smoke: FAIL — unexpected {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        rc = 1
    finally:
        cluster.down()
        if rc == 0:
            import shutil

            shutil.rmtree(root, ignore_errors=True)
        else:
            print(f"metrics-smoke: state kept at {root}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
