"""The scope vocabulary of the GNN step (models/graphsage.STEP_SCOPES): every
name reaches the compiled program's `op_name` metadata, the ops that carry
the step's cost all have one, and naming them renamed no flax module (the
parameter tree and the initial weights are the parent commit's)."""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from dragonfly2_tpu.models import graphsage
from dragonfly2_tpu.models.features import FEATURE_DIM
from dragonfly2_tpu.models.graphsage import TopoGraph
from dragonfly2_tpu.parallel import mesh as meshlib
from dragonfly2_tpu.trainer import train_gnn
from dragonfly2_tpu.trainer.synthetic import PairBatch

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
sys.path.insert(0, str(BENCH))

import scope_reduce  # noqa: E402  (the benchmark's reader of the names: plain Python)

N, K = 64, 16
CFG = train_gnn.GNNTrainConfig(hidden=32, embed_dim=16, num_layers=2, batch_size=64)


def _graph() -> TopoGraph:
    return TopoGraph(
        np.zeros((N, 12), np.float32), np.zeros((N, K), np.int32),
        np.ones((N, K), np.float32), np.zeros((N, K, 4), np.float32),
    )


@pytest.fixture(scope="module")
def op_names() -> set[str]:
    """`op_name` of every instruction of the compiled scan step (with the
    gradient norms, as the trainer builds it)."""
    pairs = PairBatch(
        np.zeros(256, np.int32), np.zeros(256, np.int32),
        np.zeros((256, FEATURE_DIM), np.float32), np.zeros(256, np.float32),
    )
    state, g, pool, multi_step = train_gnn.shard_for_training_scan(
        train_gnn.init_state(CFG, _graph(), 0), _graph(), pairs, meshlib.mesh_for_run()[0],
        batch_size=CFG.batch_size, steps_per_call=3,
    )
    text = multi_step.lower(state, g, pool, jax.random.PRNGKey(0)).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("scope", graphsage.STEP_SCOPES)
def test_every_scope_reaches_the_compiled_step(op_names, scope):
    found = {n for n in op_names if scope_reduce.classify(n)[0] == scope}
    assert found, f"no op of the compiled step is under {scope!r}"
    if scope == graphsage.GATHER:
        # JAX marks the backward pass itself: the gather's VJP is found there
        assert any(scope_reduce.classify(n) == (scope, True) and n.endswith("scatter-add")
                   for n in found), sorted(found)
    if scope in (graphsage.OPTIMIZER, graphsage.SAMPLE):
        assert not any(scope_reduce.classify(n)[1] for n in found)


def test_no_costly_op_of_the_scan_body_lacks_a_scope(op_names):
    costly = {n for n in op_names if "while/body" in n
              and n.rsplit("/", 1)[-1] in ("dot_general", "gather", "scatter-add", "scatter_add")}
    assert len(costly) > 20
    assert [n for n in costly if scope_reduce.classify(n)[0] is None] == []
    # a primitive's own name is not a scope: the pool's gathers are `sample`
    assert {scope_reduce.classify(n)[0] for n in costly if "/sample/" in n} == {graphsage.SAMPLE}


def test_the_benchmark_reads_the_programs_vocabulary():
    copy = json.loads((BENCH / "scopes.json").read_text())
    assert tuple(copy["names"]) == graphsage.STEP_SCOPES
    assert scope_reduce.classify("jit(f)/jit(main)/while/body/closed_call/jvp(loss)/sub") == ("loss", False)
    assert scope_reduce.classify("jit(f)/while/body/transpose(jvp(loss))/mul") == ("loss", True)
    assert scope_reduce.classify("jit(f)/while/body/dynamic_slice") == (None, False)
    assert scope_reduce.classify("") == (None, False)


# (key path, shape, sum, sum of squares) of every leaf of
# init_state(CFG, graph, 0).params at the parent commit (14b2555): a renamed
# flax module changes a key path and reseeds that module's initial weights
PARENT_PARAMS = [
    ("['params']['encoder']['Dense_0']['bias']", (32,), 0.000000, 0.000000),
    ("['params']['encoder']['Dense_0']['kernel']", (12, 32), -6.566503, 31.327871),
    ("['params']['encoder']['Dense_1']['bias']", (16,), 0.000000, 0.000000),
    ("['params']['encoder']['Dense_1']['kernel']", (32, 16), 3.172556, 16.164827),
    ("['params']['encoder']['SAGELayer_0']['Dense_0']['bias']", (32,), 0.000000, 0.000000),
    ("['params']['encoder']['SAGELayer_0']['Dense_0']['kernel']", (32, 32), -1.109763, 31.480261),
    ("['params']['encoder']['SAGELayer_0']['LayerNorm_0']['bias']", (32,), 0.000000, 0.000000),
    ("['params']['encoder']['SAGELayer_0']['LayerNorm_0']['scale']", (32,), 32.000000, 32.000000),
    ("['params']['encoder']['SAGELayer_0']['msg_edge']['kernel']", (4, 32), -3.427432, 31.495851),
    ("['params']['encoder']['SAGELayer_0']['msg_nbr']['kernel']", (32, 32), -5.491420, 30.600561),
    ("['params']['encoder']['SAGELayer_0']['msg_self']['bias']", (32,), 0.000000, 0.000000),
    ("['params']['encoder']['SAGELayer_0']['msg_self']['kernel']", (32, 32), -3.763726, 31.320788),
    ("['params']['encoder']['SAGELayer_1']['Dense_0']['bias']", (32,), 0.000000, 0.000000),
    ("['params']['encoder']['SAGELayer_1']['Dense_0']['kernel']", (32, 32), -1.433441, 31.532512),
    ("['params']['encoder']['SAGELayer_1']['LayerNorm_0']['bias']", (32,), 0.000000, 0.000000),
    ("['params']['encoder']['SAGELayer_1']['LayerNorm_0']['scale']", (32,), 32.000000, 32.000000),
    ("['params']['encoder']['SAGELayer_1']['msg_edge']['kernel']", (4, 32), -2.619857, 36.904159),
    ("['params']['encoder']['SAGELayer_1']['msg_nbr']['kernel']", (32, 32), -3.287359, 31.121481),
    ("['params']['encoder']['SAGELayer_1']['msg_self']['bias']", (32,), 0.000000, 0.000000),
    ("['params']['encoder']['SAGELayer_1']['msg_self']['kernel']", (32, 32), -10.651685, 31.760258),
    ("['params']['head']['layers_0']['bias']", (256,), 0.000000, 0.000000),
    ("['params']['head']['layers_0']['kernel']", (64, 256), 17.846913, 256.638068),
    ("['params']['head']['layers_2']['bias']", (128,), 0.000000, 0.000000),
    ("['params']['head']['layers_2']['kernel']", (256, 128), -2.738121, 127.151607),
    ("['params']['head']['layers_4']['bias']", (1,), 0.000000, 0.000000),
    ("['params']['head']['layers_4']['kernel']", (128, 1), -2.284663, 1.110578),
]


def test_no_module_was_renamed():
    params = jax.tree.map(lambda x: np.asarray(x, np.float64), train_gnn.init_state(CFG, _graph(), 0).params)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert [jax.tree_util.keystr(path) for path, _ in leaves] == [p[0] for p in PARENT_PARAMS]
    for (_, a), (key, shape, total, squares) in zip(leaves, PARENT_PARAMS):
        assert a.shape == shape, key
        assert (a.sum(), (a * a).sum()) == pytest.approx((total, squares), abs=2e-5), key
