"""GraphSAGE over the network-topology probe graph (north-star configs 2-3).

The flagship model. The reference collects (src, dst, RTT) probes into Redis
queues (scheduler/networktopology/network_topology.go:38-122) and streams them
to a trainer that was never implemented. Here the probe graph becomes a dense
padded-neighbor-table `TopoGraph` (see dragonfly2_tpu.ops.neighbor_agg for the
TPU-first rationale) and a GraphSAGE encoder produces per-host embeddings; a
pairwise head scores (child, parent) candidates by predicted bandwidth — the
`ml` evaluator slot the reference stubbed (evaluator.go:48).

All shapes static; compute in bfloat16 on the MXU; params float32.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dragonfly2_tpu.ops.neighbor_agg import masked_mean, neighbor_gather

if TYPE_CHECKING:
    from dragonfly2_tpu.ops.neighbor_agg_pallas import EdgesByDst, EdgesByShard


class TopoGraph(NamedTuple):
    """Dense padded topology graph.

    node_feats: [N, F] float32 host features (models.features.NODE_FEATURE_NAMES)
    neighbors:  [N, K] int32 neighbor indices (padded slots point at 0)
    mask:       [N, K] float32 1.0 for real edges
    edge_feats: [N, K, E] float32 probe stats (rtt mean/std/min, probe count)
    by_dst:     `neighbors`' slots sorted by destination, for the gather's VJP:
                derived data of a training run placed on TPU chips (one table,
                or one a row shard of the mesh's `data` axis), which only
                trainer.train_gnn's placement fills. The published graph is
                the four arrays; without the table the VJP is `jnp.take`'s
    """

    node_feats: jnp.ndarray
    neighbors: jnp.ndarray
    mask: jnp.ndarray
    edge_feats: jnp.ndarray
    by_dst: EdgesByDst | EdgesByShard | None = None


# The scope vocabulary of the training step: every device op of the step
# carries exactly one of these as a component of its `op_name` (set with
# `jax.named_scope`, metadata only: the compiled program does not change),
# and the benchmark's per-layer metrics find a part's device time by it
# (benchmarks/scopes.json is their copy). JAX itself adds `transpose(jvp(..))`
# for the backward pass and flax the module's name (`SAGELayer_1`), so a name
# says what an op is FOR, not how it is done: a kernel or `custom_vjp` that
# replaces a part keeps its forward and its backward under that part's name.
# The scopes do not nest. Never rename a flax module for the trace's sake:
# module names are parameter-tree keys and seed the initial weights.
GATHER = "gather"        # neighbor_gather in SAGELayer, and its VJP: a custom_vjp (sorted rows
                         # summed by run, a kernel) with TopoGraph.by_dst, else XLA's scatter-add;
                         # on a `data` mesh also the all-gather of the states and the
                         # reduce-scatter of the cotangent's sums, written or the partitioner's
MESSAGE = "message"      # edge projection, the sum of the three terms, gelu
REDUCE = "reduce"        # masked_mean over the K neighbor slots
DENSE = "dense"          # every other Dense / LayerNorm of the encoder, the L2 norm
HEAD = "head"            # the pair rows' take and the pairwise head
LOSS = "loss"            # trainer.train_gnn.loss_fn's mean square
OPTIMIZER = "optimizer"  # global_norm, apply_gradients
SAMPLE = "sample"        # the scan's key split, randint and pool gathers
STEP_SCOPES = (GATHER, MESSAGE, REDUCE, DENSE, HEAD, LOSS, OPTIMIZER, SAMPLE)


class SAGELayer(nn.Module):
    features: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h: jnp.ndarray, g: TopoGraph) -> jnp.ndarray:
        # Pre-projection decomposition: the naive form projects the
        # [N, K, 2H+E] concat of (neighbor state, self state, edge feats)
        # through one Dense — K times the FLOPs per node state. Algebraically
        # W·[hn; hs; e] = Wn·hn + Ws·hs + We·e, so project each term at its
        # natural rank instead: node projections are [N, H]·[H, F] (no K),
        # only the tiny edge term stays per-edge. ~(2H+E)/(2H/K+E) ≈ 7x fewer
        # MACs at K=16, and every matmul is a clean MXU shape.
        with jax.named_scope(DENSE):
            h = h.astype(self.dtype)
            u = nn.Dense(
                self.features, use_bias=False, dtype=self.dtype, param_dtype=jnp.float32,
                name="msg_nbr",
            )(h)
            s = nn.Dense(
                self.features, dtype=self.dtype, param_dtype=jnp.float32, name="msg_self"
            )(h)
        with jax.named_scope(MESSAGE):
            v = nn.Dense(
                self.features, use_bias=False, dtype=self.dtype, param_dtype=jnp.float32,
                name="msg_edge",
            )(g.edge_feats.astype(self.dtype))
        with jax.named_scope(GATHER):
            nbr = neighbor_gather(u, g.neighbors, g.by_dst)  # [N, K, F]
        with jax.named_scope(MESSAGE):
            msg = nn.gelu(nbr + s[:, None, :] + v)  # [N, K, F]
        with jax.named_scope(REDUCE):
            agg = masked_mean(msg, g.mask.astype(self.dtype))  # [N, features]
        with jax.named_scope(DENSE):
            self_h = nn.Dense(self.features, dtype=self.dtype, param_dtype=jnp.float32)(h)
            out = nn.gelu(self_h + agg)
            return nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32)(out)


class GraphSAGE(nn.Module):
    """Encoder: TopoGraph -> per-node embeddings [N, embed_dim]."""

    hidden: int = 256
    embed_dim: int = 128
    num_layers: int = 3
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, g: TopoGraph) -> jnp.ndarray:
        with jax.named_scope(DENSE):
            h = nn.Dense(self.hidden, dtype=self.dtype, param_dtype=jnp.float32)(
                g.node_feats.astype(self.dtype)
            )
        for _ in range(self.num_layers):
            h = SAGELayer(self.hidden, dtype=self.dtype)(h, g)
        with jax.named_scope(DENSE):
            z = nn.Dense(self.embed_dim, dtype=self.dtype, param_dtype=jnp.float32)(h)
            # L2-normalized embeddings (standard GraphSAGE) keep the pairwise head
            # scale-stable across training rounds.
            z = z.astype(jnp.float32)
            return z / (jnp.linalg.norm(z, axis=-1, keepdims=True) + 1e-6)


class TopoScorer(nn.Module):
    """GraphSAGE encoder + pairwise (child, parent) bandwidth head.

    score(g, child_idx[B], parent_idx[B], pair_feats[B, Fp]) -> [B] in (0, 1):
    predicted normalized bandwidth, used directly as the parent score for one
    batched call per scheduling round (all ~40 candidates at once).
    """

    hidden: int = 256
    embed_dim: int = 128
    num_layers: int = 3
    head_hidden: int = 256
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self) -> None:
        self.encoder = GraphSAGE(self.hidden, self.embed_dim, self.num_layers, self.dtype)
        self.head = nn.Sequential(
            [
                nn.Dense(self.head_hidden, dtype=self.dtype, param_dtype=jnp.float32),
                nn.gelu,
                nn.Dense(self.head_hidden // 2, dtype=self.dtype, param_dtype=jnp.float32),
                nn.gelu,
                nn.Dense(1, dtype=self.dtype, param_dtype=jnp.float32),
            ]
        )

    def __call__(
        self,
        g: TopoGraph,
        child_idx: jnp.ndarray,
        parent_idx: jnp.ndarray,
        pair_feats: jnp.ndarray,
    ) -> jnp.ndarray:
        z = self.encoder(g)  # [N, D] float32
        with jax.named_scope(HEAD):
            zc = jnp.take(z, child_idx, axis=0)
            zp = jnp.take(z, parent_idx, axis=0)
            x = jnp.concatenate(
                [zc, zp, zc * zp, pair_feats.astype(jnp.float32)], axis=-1
            ).astype(self.dtype)
            out = self.head(x).astype(jnp.float32).squeeze(-1)
            return nn.sigmoid(out)

    def embed(self, g: TopoGraph) -> jnp.ndarray:
        return self.encoder(g)
