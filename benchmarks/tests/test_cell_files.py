"""A cell is files found by name: the records a configuration's generator
makes are the parent's byte for byte, the reference rebuilds a pool from
commits in their order as it did from `uploads=n`, and a name with no file is
an error that names the file (exit 1, no result line).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_cell_files.py -q
"""

import hashlib
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
import traffic_driver  # noqa: E402

TINY = TESTS / "data" / "tiny" / "tiny.json"
# sha256 over downloads.tobytes() then probes.tobytes() of
# telemetry_gen.generate_for(config["cluster"], seed) at commit ad47720 (PR 34),
# taken before the generator became a file found by name
PARENT_RECORDS = {
    ("configs/gnn-32k-512.json", 7): "dc6e409dbb2a21fd3e1ab1225ec8d16734f84280177c9d41a92ffd19b6a444d3",
    ("configs/gnn-32k-512.json", 2_147_483_659): "9634bf52b58f18337b4884976edf25a6a254695085227f1c0888342385604864",
    ("configs/gnn-64k-256.json", 7): "b6b71e7d7baa12eedb78e80299f05f26247fdee680f7e1ce18decd6fde3a9f97",
    ("configs/gnn-64k-256.json", 2_147_483_659): "2131574a559bfb664a88b3912c0bd3ea86c4aab2e67c04783c0bb444f40ca65d",
    ("configs/gnn-64k-512.json", 7): "b6b71e7d7baa12eedb78e80299f05f26247fdee680f7e1ce18decd6fde3a9f97",
    ("configs/gnn-64k-512.json", 2_147_483_659): "2131574a559bfb664a88b3912c0bd3ea86c4aab2e67c04783c0bb444f40ca65d",
    ("tests/data/tiny/tiny.json", 7): "a70aab98de38c84a5d39f8f46e6d07aef44a173083aba038ddf80c3b98d3b038",
    ("tests/data/tiny/tiny.json", 2_147_483_659): "d5ba8c955cc5c56701d98f28943c79255269e7c0d590e57eac71cbb5a00d7923",
}


def feeders_of(config: dict, seed: int) -> list[dict]:
    return traffic_driver.load_file(BENCH / "generators" / f"{config['generator']}.py").generate(config["cluster"], seed)


@pytest.mark.parametrize("file,seed", sorted(PARENT_RECORDS))
def test_every_configurations_records_are_the_parents_byte_for_byte(file, seed):
    (feeder,) = feeders_of(json.loads((BENCH / file).read_text()), seed)
    assert (feeder["hostname"], feeder["scheduler_id"]) == ("benchmark-feeder", 0)
    digest = hashlib.sha256(feeder["downloads"].tobytes())
    digest.update(feeder["probes"].tobytes())
    assert digest.hexdigest() == PARENT_RECORDS[file, seed]


@pytest.mark.parametrize("n,cap,pairs", [(1, 500_000, 460), (3, 500_000, 1380), (1, 300, 460), (3, 300, 460), (3, 700, 920)])
def test_n_commits_of_one_upload_are_uploads_n_array_for_array(n, cap, pairs):
    config = json.loads(TINY.read_text())
    (feeder,) = feeders_of(config, 2_147_483_777)
    records = (feeder["downloads"], feeder["probes"])
    kw = dict(num_neighbors=config["model"]["num_neighbors"], chunk_rows=config["cluster"]["chunk_rows"], pool_rows_cap=cap)
    by_count = reference.build_dataset(*records, uploads=n, **kw)
    by_commits = reference.build_dataset(commits=[records] * n, **kw)
    once = reference.build_dataset(*records, uploads=1, **kw)
    assert len(by_count["pairs"]["label"]) == pairs
    for key in ("hosts", "node_feats", "neighbors", "mask", "edge_feats"):
        assert by_commits[key].tobytes() == by_count[key].tobytes() == once[key].tobytes(), key
    for key, column in by_count["pairs"].items():
        assert by_commits["pairs"][key].dtype == column.dtype and np.array_equal(by_commits["pairs"][key], column), key
        # the rolling pool: the newest whole chunks of the same rows, again and again
        assert np.array_equal(column[-460:], once["pairs"][key]), key


def test_commits_that_differ_are_read_in_their_order():
    """Two schedulers' commits: hosts numbered as the first commit streamed
    them, edge means and parents' rates over both commits' rows, the pair pool
    the first's chunks then the second's."""
    config = json.loads((TESTS / "data" / "two_feeders" / "two_feeders.json").read_text())
    by_task = traffic_driver.load_file(TESTS / "data" / "two_feeders" / "generators" / "by_task.py")
    a, b = by_task.generate(config["cluster"], 2_147_483_777)
    assert len(a["downloads"]) + len(b["downloads"]) == config["cluster"]["downloads"] and len(a["downloads"]) > 64 < len(b["downloads"])
    a, b = (a["downloads"], a["probes"]), (b["downloads"], b["probes"])
    kw = dict(num_neighbors=16, chunk_rows=config["cluster"]["chunk_rows"], pool_rows_cap=0)
    ab, ba, only_a = (reference.build_dataset(commits=c, **kw) for c in ([a, b], [b, a], [a]))
    assert ab["hosts"][:8].tolist() == only_a["hosts"][:8].tolist() != ba["hosts"][:8].tolist()
    n_a = len(only_a["pairs"]["label"])
    assert np.array_equal(ab["pairs"]["feats"][:n_a], only_a["pairs"]["feats"])
    assert np.array_equal(ab["pairs"]["feats"][n_a:], ba["pairs"]["feats"][: len(ab["pairs"]["label"]) - n_a])
    # the same pool whatever the order, but for the numbering: every host's features, by its id
    order = np.argsort(ab["hosts"]), np.argsort(ba["hosts"])
    assert np.array_equal(ab["node_feats"][order[0]], ba["node_feats"][order[1]])
    assert not np.array_equal(ab["node_feats"], only_a["node_feats"])
    # a commit that came twice weighs twice: [a, a, b] is not [a, b], and is [a, b, a] but for the pool's order
    aab, aba = (reference.build_dataset(commits=c, **kw) for c in ([a, a, b], [a, b, a]))
    assert np.array_equal(aab["edge_feats"], aba["edge_feats"]) and not np.array_equal(aab["edge_feats"], ab["edge_feats"])
    assert len(aab["pairs"]["label"]) == 2 * n_a + len(ab["pairs"]["label"]) - n_a


def _bench_with(tmp_path: Path, config: dict, traffic: dict) -> Path:
    """A BENCHMARK.json of one tiny steady cell whose configuration and mix are the given ones."""
    (tmp_path / "config.json").write_text(json.dumps(config))
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "mix.json").write_text(json.dumps(traffic))
    (tmp_path / "limits").symlink_to(TESTS / "data" / "tiny" / "limits")
    bench = {"paths": ["benchmarks"], "end_to_end": [], "per_layer": [],
             "configs": [{"name": "c", "file": str(tmp_path / "config.json")}],
             "workloads": [{"name": "tiny.steady", "config": "c", "traffic": "mix", "chips": 1}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path / "BENCHMARK.json"


@pytest.mark.parametrize("key,value,named", [
    ("generator", None, 'config.json names no "generator"'),
    ("generator", "nowhere", "no generators/nowhere.py under"),
    ("window", "nowhere", "no windows/nowhere.py under"),
])
def test_a_name_with_no_file_is_an_error_that_names_the_file(tmp_path, key, value, named):
    config = json.loads(TINY.read_text())
    traffic = json.loads((BENCH / "traffic" / "steady.json").read_text())
    for part in (config, traffic):
        if key in part:
            part[key] = value
            if value is None:
                del part[key]
    bench = _bench_with(tmp_path, config, traffic)
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload", "tiny.steady", "--seed", "1", "--seconds", "1",
                        "--trace", "0", "--cpu-rehearsal", "--benchmark-json", str(bench)],
                       cwd=REPO, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1 and p.stdout == ""
    assert named in p.stderr and str(tmp_path) in p.stderr
