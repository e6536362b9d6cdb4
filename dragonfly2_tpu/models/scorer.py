"""Batched parent scorer serving the scheduler's hot loop.

Serving design (vs reference): the reference called per-pair Evaluate inside a
sort comparator (~2·40·log 40 calls per round, evaluator_base.go:79) and its
intended ML path was a TF-Serving RPC per round (tfserving/client_v1.go:82).
Here scoring is one batched call per round: node embeddings are *cached*
(recomputed only when telemetry refreshes, `refresh()`), and a round scores
all ~40 candidates through the pairwise head in a single jitted call — the
batch API SURVEY.md §7 says must be designed in from day one.

Two engines:
  LinearScorer  — the reference's default evaluator weights (base fallback).
  GNNScorer     — TopoScorer embeddings + head (the `ml` slot, no RPC hop).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from dragonfly2_tpu.models.features import BASE_WEIGHTS
from dragonfly2_tpu.models.graphsage import TopoGraph, TopoScorer


def _to_device(tree: Any, device: Any) -> Any:
    """Move a pytree to a device, staging through host memory: params may
    arrive as numpy (a loaded artifact) or as arrays on another backend (a
    TPU-trained state handed to the CPU scorer in the same process), and
    np.asarray + device_put is the one form that covers both."""
    return jax.tree.map(lambda a: jax.device_put(np.asarray(a), device), tree)


class LinearScorer:
    """Reference-default linear blend (evaluator_base.go:31-49 weights)."""

    def score(self, pair_feats: np.ndarray, **_: Any) -> np.ndarray:
        return np.asarray(pair_feats[:, : len(BASE_WEIGHTS)] @ BASE_WEIGHTS[: pair_feats.shape[1]])


class GNNScorer:
    """Cached-embedding GNN scorer; one jitted head call per scheduling round.

    Serving is pinned to the host CPU backend by default: training runs on the
    TPU mesh, but per-round scoring must not pay a device-dispatch round trip
    (the north-star contract is 10k calls/s "with no GPU" — the reference's
    equivalent hop was a TF-Serving RPC). Params/embeddings transfer once per
    refresh; each round is a committed-CPU jit call.
    """

    engine = "jax"  # serving-mode metric label (native C++ scorer: "native")

    def __init__(self, model: TopoScorer, params: Any, device: Any = None):
        if device is None:
            # no CPU backend is an error, never a reason to serve from the
            # accelerator: host-side processes pin CPU (utils/jaxenv.py)
            device = jax.devices("cpu")[0]
        self._device = device
        self._model = model
        self._params = _to_device(params, device)
        self._z: jax.Array | None = None
        self._uc: jax.Array | None = None
        self._up: jax.Array | None = None
        dt = model.dtype

        def _embed_and_proj(params: Any, g: TopoGraph):
            """Embeddings + LOAD-TIME head-layer-1 partials (the same
            precompute scorer.cc does natively): the head's first Dense sees
            x = [zc, zp, zc*zp, feats], so its kernel splits row-wise into
            per-term blocks — the zc and zp blocks depend only on the node,
            and projecting the whole table once per refresh removes ~half the
            per-round head FLOPs (only the pairwise zc*zp block and the tiny
            feats block remain per candidate). Partials are kept in float32
            (f32-accumulated bf16 dots), so the per-round partial SUM loses
            nothing vs the original single fused matmul."""
            z = model.apply(params, g, method=model.embed)
            w1 = params["params"]["head"]["layers_0"]["kernel"]
            e = z.shape[1]
            zb = z.astype(dt)
            uc = jnp.dot(zb, w1[:e].astype(dt), preferred_element_type=jnp.float32)
            up = jnp.dot(zb, w1[e : 2 * e].astype(dt), preferred_element_type=jnp.float32)
            return z, uc, up

        def _head_tail(m: TopoScorer, v: jax.Array) -> jax.Array:
            # the rest of the head THROUGH THE MODEL (no hand-copied layer
            # names/activations to drift when TopoScorer.head changes; only
            # the first Dense is split for the precompute, and the shape
            # assert below catches a changed layer-1 signature)
            for layer in m.head.layers[1:]:
                v = layer(v)
            return v

        def _score(params: Any, z: jax.Array, uc: jax.Array, up: jax.Array,
                   child: jax.Array, parent: jax.Array, feats: jax.Array) -> jax.Array:
            head = params["params"]["head"]
            w1 = head["layers_0"]["kernel"]
            e = z.shape[1]
            assert w1.shape[0] == 3 * e + feats.shape[-1], (
                f"head layer-1 kernel {w1.shape} no longer matches the "
                f"[zc, zp, zc*zp, feats] split (e={e}, Fp={feats.shape[-1]}) — "
                "update GNNScorer's precompute decomposition"
            )
            zc = jnp.take(z, child, axis=0)
            zp = jnp.take(z, parent, axis=0)
            # f32 partial sum; bf16 rounding happens once, at the gelu input,
            # exactly where the original fused Dense rounded its output
            h = (
                jnp.take(uc, child, axis=0)
                + jnp.take(up, parent, axis=0)
                + jnp.dot((zc * zp).astype(dt), w1[2 * e : 3 * e].astype(dt),
                          preferred_element_type=jnp.float32)
                + feats @ w1[3 * e :]
                + head["layers_0"]["bias"]
            )
            out = model.apply(params, h.astype(dt), method=_head_tail)
            return jax.nn.sigmoid(out.astype(jnp.float32).squeeze(-1))

        self._embed_and_proj = jax.jit(_embed_and_proj)
        self._score_fn = jax.jit(_score)

    def refresh(self, graph: TopoGraph) -> None:
        """Recompute cached node embeddings + head partials (call when
        telemetry updates)."""
        g = jax.tree.map(lambda a: jax.device_put(np.asarray(a), self._device), graph)
        self._z, self._uc, self._up = self._embed_and_proj(self._params, g)
        self._z.block_until_ready()

    @property
    def num_nodes(self) -> int:
        """Rows in the cached embedding table (micro-batcher bounds checks)."""
        return 0 if self._z is None else int(self._z.shape[0])

    @property
    def feature_dim(self) -> int:
        from dragonfly2_tpu.models.features import FEATURE_DIM

        return FEATURE_DIM

    def update_params(self, params: Any) -> None:
        self._params = _to_device(params, self._device)
        self._z = self._uc = self._up = None

    @property
    def ready(self) -> bool:
        return self._z is not None

    def score(
        self, pair_feats: np.ndarray, *, child: np.ndarray, parent: np.ndarray
    ) -> np.ndarray:
        if self._z is None:
            raise RuntimeError("GNNScorer.refresh(graph) must run before score()")
        dev = self._device
        out = self._score_fn(
            self._params,
            self._z,
            self._uc,
            self._up,
            jax.device_put(np.asarray(child, np.int32), dev),
            jax.device_put(np.asarray(parent, np.int32), dev),
            jax.device_put(np.asarray(pair_feats, np.float32), dev),
        )
        return np.asarray(out)

    def score_rounds(
        self, pair_feats: np.ndarray, *, child: np.ndarray, parent: np.ndarray
    ) -> np.ndarray:
        """Multi-round entry: [M, B, F] feats + [M, B] indices → [M, B].
        Rounds are independent, so the flattened [M*B] batch rides the SAME
        jitted head call as a single round — one dispatch per flush lets the
        micro-batcher amortize the jax fallback the way it does the native
        FFI (the no-g++ serving path was a 7.5x SLO gap otherwise)."""
        f = np.asarray(pair_feats, np.float32)
        m, b = f.shape[0], f.shape[1]
        flat = self.score(
            f.reshape(m * b, -1),
            child=np.asarray(child, np.int32).reshape(-1),
            parent=np.asarray(parent, np.int32).reshape(-1),
        )
        return flat.reshape(m, b)
