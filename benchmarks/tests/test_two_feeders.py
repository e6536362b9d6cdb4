"""A deployment that differs from `tiny` in more than numbers, added as files
found by name and nothing else (data/two_feeders: configuration, generator,
mix, window, limits, its own BENCHMARK.json): two schedulers' sessions open
together, the second commit lands while the first run trains, and the
reference rebuilds the checked run's pool from the two commits in their order.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_two_feeders.py -q
"""

import json
import os
import subprocess
import sys
from pathlib import Path

TESTS = Path(__file__).resolve().parent
REPO = TESTS.parents[1]
DEPLOYMENT = TESTS / "data" / "two_feeders"


def test_two_feeders_side_by_side_come_out_correct():
    p = subprocess.run(
        [sys.executable, str(TESTS.parent / "run.py"), "--workload", "two_feeders.side_by_side", "--seed", "2147483659",
         "--seconds", "1", "--trace", "0", "--cpu-rehearsal", "--benchmark-json", str(DEPLOYMENT / "BENCHMARK.json")],
        cwd=REPO, capture_output=True, text=True, timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stderr[-3000:]
    (line,) = p.stdout.splitlines()
    result = json.loads(line)
    assert result["correct"] is True and (result["attempted"], result["failed"]) == (2, 0)
    assert result["metrics"] == {} and set(result["rehearsal"]["read"]) == {"setup_s"}
    # the pool rebuilt from feeder 0's commit then feeder 1's is the program's graph, entry for entry;
    # the first scan call and the MLP loop of the run that trained on it inside tiny's limits
    limits = json.loads((TESTS / "data" / "tiny" / "limits" / "tiny.retrain.json").read_text())["numbers"]
    assert {k: v[1] for k, v in result["compared"].items()} == {k: v["limit"] for k, v in limits.items()}
    assert result["compared"]["dataset_mismatch"] == [0, 0]
    # what the window expects of the trainer: both sessions open at once, one run a close, nothing
    # coalesced (one close landed in the run), no rotation (64 hosts, 3,072 probes)
    assert result["detail"]["status"] == {"open_together": 2, "trains_started": 2, "trains_succeeded": 2,
                                          "trains_coalesced": 0, "pool_rotations": 0, "open_sessions": 0}
    first, second = result["detail"]["run_stages"]
    assert first["gnn_train_s"] and second["gnn_train_s"]
