"""Model artifact (de)serialization for the registry.

One artifact = one directory: params.msgpack (flax serialized pytree) +
config.json (model hyperparameters + type + version) + sketch.json (the
training-reference feature sketch drift detection compares live scoring
features against, ISSUE 15) + the GNN's graph.npz/hosts.json/scorer.dfsc.
The manager's model registry rows point at these via artifact_path
(manager/models/model.go:28-45 kept evaluation metrics in the DB and the
artifact elsewhere; same split). The scheduler's ml evaluator loads an
artifact straight into a scorer; `artifact_digest` covers EVERY file, so
any of them tampering fails verify_artifact before attach.
"""

from __future__ import annotations

import json
from functools import partial
from pathlib import Path
from typing import Any

import flax.serialization
import jax
import jax.numpy as jnp
import numpy as np

from dragonfly2_tpu.models.graphsage import TopoScorer
from dragonfly2_tpu.models.mlp import BandwidthMLP


# Bumped whenever the flax param-tree structure changes (renamed/reshaped
# modules make from_bytes fail); loaders refuse mismatched artifacts with a
# clear error instead of a pytree exception deep in deserialization.
# 2: SAGELayer pre-projection decomposition (msg_nbr/msg_self/msg_edge).
ARTIFACT_FORMAT = 2


class IncompatibleArtifact(Exception):
    pass


class ArtifactIntegrityError(IOError):
    """The on-disk artifact does not match the registry row's digest —
    truncated/corrupt/partially-written files must never attach to a live
    evaluator (ISSUE 11)."""


def artifact_digest(directory: str | Path) -> str:
    """Content digest of a whole artifact directory: sha256 over every
    regular file (sorted relative path + contents, length-framed so file
    boundaries can't alias). Computed by the trainer at publish time and
    stored on the registry row; the scheduler recomputes it before attaching
    a version. Injectable: each file's bytes pass the faultline
    `model.load` mutate point, so chaos tests corrupt artifacts the same
    seeded way they corrupt pieces."""
    import hashlib

    from dragonfly2_tpu.resilience import faultline

    d = Path(directory)
    h = hashlib.sha256()
    for f in sorted(p for p in d.rglob("*") if p.is_file()):
        data = f.read_bytes()
        if faultline.ACTIVE is not None:
            data = faultline.ACTIVE.mutate("model.load", data)
        rel = f.relative_to(d).as_posix().encode()
        h.update(len(rel).to_bytes(4, "big"))
        h.update(rel)
        h.update(len(data).to_bytes(8, "big"))
        h.update(data)
    return h.hexdigest()


def verify_artifact(directory: str | Path, expected_digest: str) -> None:
    """Raise ArtifactIntegrityError unless the directory's recomputed digest
    matches the registry row's. Empty expected digest = unverified row
    (pre-rollout registry) — allowed through, the caller decides policy."""
    if not expected_digest:
        return
    d = Path(directory)
    if not d.is_dir():
        raise FileNotFoundError(f"artifact directory {d} missing")
    got = artifact_digest(d)
    if got != expected_digest:
        raise ArtifactIntegrityError(
            f"artifact {d} digest mismatch: registry {expected_digest[:16]}…, "
            f"disk {got[:16]}… (truncated/corrupt artifact must not attach)"
        )


def save_artifact(
    directory: str | Path, *, model_type: str, version: str, params: Any, config: dict
) -> Path:
    d = Path(directory)
    d.mkdir(parents=True, exist_ok=True)
    (d / "params.msgpack").write_bytes(flax.serialization.to_bytes(params))
    (d / "config.json").write_text(
        json.dumps({"type": model_type, "version": version, "format": ARTIFACT_FORMAT, **config})
    )
    return d


def _check_format(cfg: dict, directory: Any) -> None:
    fmt = cfg.get("format", 1)
    if fmt != ARTIFACT_FORMAT:
        raise IncompatibleArtifact(
            f"artifact {directory} has format {fmt}, this build expects "
            f"{ARTIFACT_FORMAT}; retrain to republish"
        )


def load_config(directory: str | Path) -> dict:
    return json.loads((Path(directory) / "config.json").read_text())


def load_gnn(directory: str | Path) -> tuple[TopoScorer, Any]:
    cfg = load_config(directory)
    assert cfg["type"] == "gnn", cfg
    _check_format(cfg, directory)
    model = TopoScorer(
        hidden=cfg["hidden"], embed_dim=cfg["embed_dim"], num_layers=cfg["num_layers"]
    )
    from dragonfly2_tpu.models.features import FEATURE_DIM, NODE_FEATURE_DIM
    from dragonfly2_tpu.models.graphsage import TopoGraph
    from dragonfly2_tpu.trainer.synthetic import EDGE_FEATURE_DIM

    # template pytree with the right structure for from_bytes
    g = TopoGraph(
        jnp.zeros((8, NODE_FEATURE_DIM)), jnp.zeros((8, 4), jnp.int32),
        jnp.zeros((8, 4)), jnp.zeros((8, 4, EDGE_FEATURE_DIM)),
    )
    template = model.init(
        jax.random.PRNGKey(0), g, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, FEATURE_DIM)),
    )
    params = flax.serialization.from_bytes(
        template, (Path(directory) / "params.msgpack").read_bytes()
    )
    return model, params


def save_graph(directory: str | Path, graph: Any, host_index: dict[bytes, int]) -> None:
    """Snapshot the topology graph + host→row mapping beside the GNN params —
    the scheduler's ml evaluator needs both to refresh scorer embeddings and
    translate live host ids into graph rows."""
    d = Path(directory)
    np.savez_compressed(
        d / "graph.npz",
        node_feats=np.asarray(graph.node_feats),
        neighbors=np.asarray(graph.neighbors),
        mask=np.asarray(graph.mask),
        edge_feats=np.asarray(graph.edge_feats),
    )
    (d / "hosts.json").write_text(
        json.dumps({k.decode("utf-8", "replace"): v for k, v in host_index.items()})
    )


def load_graph(directory: str | Path) -> tuple[Any, dict[str, int]]:
    from dragonfly2_tpu.models.graphsage import TopoGraph

    d = Path(directory)
    z = np.load(d / "graph.npz")
    graph = TopoGraph(z["node_feats"], z["neighbors"], z["mask"], z["edge_feats"])
    host_index = json.loads((d / "hosts.json").read_text())
    return graph, {k: int(v) for k, v in host_index.items()}


def save_native(directory: str | Path, model: TopoScorer, params: Any, graph: Any) -> Path:
    """Export the native serving artifact beside the flax one: compute the
    cached node embeddings once in JAX, then flatten head weights + embeddings
    into the C++ scorer's binary format (native/scorer.cc; replaces the
    reference's TF-Serving hop, tfserving/client_v1.go:82-102). The forward
    runs over the rows a training run places (`placed_rows`: what it compiles
    is a rung's, not this host count's) and the cluster's own rows are
    written: a padding row is a copy of node 0 that no neighbour slot names."""
    from dragonfly2_tpu.native import export_scorer_artifact
    from dragonfly2_tpu.ops.neighbor_agg_pallas import placed_rows
    from dragonfly2_tpu.trainer.train_gnn import pad_graph

    hosts = graph.node_feats.shape[0]
    z = np.asarray(_embed(model, params, pad_graph(graph, placed_rows(hosts))))[:hosts]
    return export_scorer_artifact(params, z, Path(directory) / "scorer.dfsc")


@partial(jax.jit, static_argnums=0)  # a model is its few numbers: one program a model and a rung
def _embed(model: TopoScorer, params: Any, graph: Any) -> jnp.ndarray:
    return model.apply(params, graph, method=model.embed)


def load_native(directory: str | Path):
    """Load the native scorer if its artifact exists, else None."""
    from dragonfly2_tpu.native import NativeScorer

    path = Path(directory) / "scorer.dfsc"
    if not path.exists():
        return None
    return NativeScorer(path)


def save_sketch(directory: str | Path, sketch: Any) -> Path:
    """Write the training-reference feature sketch beside the params
    (ISSUE 15). Called BEFORE artifact_digest, so the digest covers it like
    every other file — a tampered/truncated sketch fails verify_artifact the
    same way tampered weights do."""
    p = Path(directory) / "sketch.json"
    p.write_text(json.dumps(sketch.to_dict()))
    return p


def load_sketch(directory: str | Path):
    """The artifact's training-reference FeatureSketch, or None for
    pre-sketch artifacts (every pre-ISSUE-15 artifact; drift detection just
    stays dormant for them)."""
    from dragonfly2_tpu.observability.sketches import FeatureSketch

    p = Path(directory) / "sketch.json"
    if not p.exists():
        return None
    return FeatureSketch.from_dict(json.loads(p.read_text()))


def load_mlp(directory: str | Path) -> tuple[BandwidthMLP, Any]:
    cfg = load_config(directory)
    assert cfg["type"] == "mlp", cfg
    _check_format(cfg, directory)
    model = BandwidthMLP(hidden=tuple(cfg["hidden"]))
    from dragonfly2_tpu.models.features import FEATURE_DIM

    template = model.init(jax.random.PRNGKey(0), jnp.zeros((2, FEATURE_DIM)))
    params = flax.serialization.from_bytes(
        template, (Path(directory) / "params.msgpack").read_bytes()
    )
    return model, params
