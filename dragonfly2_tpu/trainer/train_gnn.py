"""Sharded GNN training (north-star configs 2-3).

One jitted program over the run's mesh (`parallel.mesh.mesh_for_run`: every
device on "data"), `multi_step`: a `lax.scan` of optimizer steps that samples
its minibatches on the device (`shard_for_training_scan` places a run and
builds it, `train_async` drives it). Graph node rows and the sampled pair
batch are sharded over "data", the state is replicated; the gradient psum, and
off the TPU the neighbor gather's all-gather, are XLA's, from the sharding
annotations. On TPU chips the gather's VJP is a `custom_vjp` over a table of
the slots sorted by destination that placement builds once a run
(`ops/neighbor_agg_pallas`): one Pallas segmented sum on one chip; on a `data`
mesh under `shard_map`, a table per row shard, with the gather's own
`all_gather` of the states.

The compiled step is kept across runs of the same shapes: model and optimizer
transform are made once per configuration, so two runs' states have one tree
structure, and the `jax.jit` of `multi_step` outlives the run that built it
(two programs at most: `shard_for_training_scan`). A warm trainer's retrain
starts with a call like any other; the run manifest's `calls.traced` says
whether it did. The node rows are placed at a rung of a ladder of row counts
(`ops/neighbor_agg_pallas.placed_rows`), padded with copies of node 0, so a
cluster of any size takes the sorted VJP and a host count that moves inside a
rung keeps the program; `placement.decision` says `hosts`, `rows`, `pad_pct`.

Replaces the reference's never-implemented trainer loop (trainer/ is
config+metrics only; the Train RPC at pkg/rpc/trainer/server/server.go:59
received CSV chunks and dropped them on the floor).
"""

from __future__ import annotations

import asyncio
import contextlib
import time
from dataclasses import dataclass
from functools import cache, partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state
from jax.sharding import Mesh

from dragonfly2_tpu.models.graphsage import LOSS, OPTIMIZER, SAMPLE, TopoGraph, TopoScorer
from dragonfly2_tpu.observability.tracing import default_tracer
from dragonfly2_tpu.parallel import mesh as meshlib
from dragonfly2_tpu.trainer.synthetic import PairBatch
from dragonfly2_tpu.utils import jaxenv


@dataclass
class GNNTrainConfig:
    hidden: int = 256
    embed_dim: int = 128
    num_layers: int = 3
    batch_size: int = 4096
    learning_rate: float = 3e-3
    weight_decay: float = 1e-4
    warmup_steps: int = 100


# Model and transform are functions of the configuration's values alone, and a
# `TrainState` carries both as static fields of its tree (a module's bound
# `apply` and a transform's closures compare by identity): made once per
# distinct values, so that the states of two runs of one configuration have
# equal tree structures and a kept `multi_step` does not trace again. A few
# numbers in, a few small objects out: nothing here grows with a run.
@cache
def _model(hidden: int, embed_dim: int, num_layers: int) -> TopoScorer:
    return TopoScorer(hidden=hidden, embed_dim=embed_dim, num_layers=num_layers)


@cache
def _transform(learning_rate: float, weight_decay: float, warmup_steps: int) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(
            optax.warmup_cosine_decay_schedule(
                0.0, learning_rate, warmup_steps, 20_000, learning_rate * 0.05
            ),
            weight_decay=weight_decay,
        ),
    )


def make_model(cfg: GNNTrainConfig) -> TopoScorer:
    return _model(cfg.hidden, cfg.embed_dim, cfg.num_layers)


def init_state(
    cfg: GNNTrainConfig, graph: TopoGraph, rng_seed: int = 0
) -> train_state.TrainState:
    from dragonfly2_tpu.models.features import FEATURE_DIM

    model = make_model(cfg)
    dummy_idx = jnp.zeros((8,), jnp.int32)
    dummy_feats = jnp.zeros((8, FEATURE_DIM), jnp.float32)
    params = model.init(
        jax.random.PRNGKey(rng_seed), _as_jnp_graph(graph), dummy_idx, dummy_idx, dummy_feats
    )
    tx = _transform(cfg.learning_rate, cfg.weight_decay, cfg.warmup_steps)
    return train_state.TrainState.create(apply_fn=model.apply, params=params, tx=tx)


def _as_jnp_graph(g: TopoGraph) -> TopoGraph:
    return jax.tree.map(jnp.asarray, g)


def loss_fn(apply_fn: Callable, params: Any, g: TopoGraph, batch: PairBatch) -> jnp.ndarray:
    pred = apply_fn(params, g, batch.child, batch.parent, batch.feats)
    with jax.named_scope(LOSS):
        return jnp.mean((pred - batch.label) ** 2)


def make_train_step() -> Callable:
    """One optimizer step, `(state, g, batch) -> (state, (loss, grad_norm))`:
    the body `multi_step` scans. The norm is the global one of the gradients
    before clipping, which the training-run telemetry exports per step."""

    def step(
        state: train_state.TrainState, g: TopoGraph, batch: PairBatch
    ):
        loss, grads = jax.value_and_grad(partial(loss_fn, state.apply_fn))(state.params, g, batch)
        with jax.named_scope(OPTIMIZER):
            gnorm = optax.global_norm(grads)
            return state.apply_gradients(grads=grads), (loss, gnorm)

    return step


def _place_sharded(
    state: train_state.TrainState, g: TopoGraph, mesh: Mesh
) -> tuple[train_state.TrainState, Any, TopoGraph, TopoGraph]:
    """Shared placement: pad node rows to their rung (whole tiles and whole
    row shards: `neighbor_agg_pallas.placed_rows`), the state replicated,
    node rows over "data". The neighbor table is fixed from here to the run's
    end, so its transpose is fixed here too, once, on the host, where the
    kernel that sums the gather's VJP over it will run
    (`neighbor_agg_pallas.gather_vjp_tables` decides): one table on one
    device, a table per row shard on a `data` mesh, each beside its shard.
    Elsewhere None, and the VJP stays `jnp.take`'s.
    Returns (state, state_sharding, g, g_sharding)."""
    from dragonfly2_tpu.ops.neighbor_agg_pallas import gather_vjp_tables, placed_rows

    dp = mesh.shape[meshlib.DATA_AXIS]
    hosts = g.node_feats.shape[0]
    rows = placed_rows(hosts, dp)
    state_sh = jax.tree.map(lambda _: meshlib.replicated(mesh), state)
    # the host's part of placement, timed: padding, the sorted table(s), and
    # the copies onto the devices (a row shard each on a `data` mesh)
    with default_tracer().span("trainer.gnn.place", data=dp, hosts=hosts, rows=rows):
        g = pad_graph(g, rows)
        by_dst, _ = gather_vjp_tables(np.asarray(g.neighbors), *_gathered_states(state), mesh)
        g = g._replace(by_dst=by_dst)
        state = jax.device_put(state, state_sh)
        # per-shard tables are stacked by shard; one device's table lies as it always has
        table_sh = meshlib.replicated(mesh) if dp == 1 else meshlib.batch_sharding(mesh)
        g_sh = TopoGraph(*meshlib.graph_shardings(mesh), by_dst=jax.tree.map(lambda _: table_sh, g.by_dst))
        g = jax.block_until_ready(jax.device_put(_as_jnp_graph(g), g_sh))
    return state, state_sh, g, g_sh


def _gathered_states(state: train_state.TrainState) -> tuple[int, Any]:
    """Width and dtype of the states a SAGE layer gathers, read off the model
    whose `apply` the state holds (0, None: not a model of ours)."""
    model = getattr(state.apply_fn, "__self__", None)
    return getattr(model, "hidden", 0), getattr(model, "dtype", None)


def pad_graph(g: TopoGraph, n_padded: int) -> TopoGraph:
    """Pad node dim to n_padded (the rung a host count is placed at) with copies
    of node 0. No neighbour slot and no pair names a padding row, so it moves
    no loss and no gradient; a copy, and not a row of zeros, because a node
    whose state is all zero has an all-zero embedding, and the embedding's L2
    norm has no gradient there (0 x NaN in every parameter's sum)."""
    pad = n_padded - g.node_feats.shape[0]
    if pad == 0:
        return g
    return TopoGraph(*(np.concatenate([a, np.repeat(np.asarray(a[:1]), pad, axis=0)]) for a in g[:4]))


# The scan programs this process keeps: what each was built from -> its jitted
# `multi_step`, the most recently used last; and how often `multi_step`'s body
# has run. Two: a pool that rotates every second upload alternates between two
# placements. Runs come one after the other (the drainer); no array hangs on it.
KEPT_PROGRAMS = 2
_kept: dict[tuple, Callable] = {}
_traces = 0


def shard_for_training_scan(
    state: train_state.TrainState,
    g: TopoGraph,
    pairs: PairBatch,
    mesh: Mesh,
    *,
    batch_size: int = 4096,
    steps_per_call: int = 10,
) -> tuple[train_state.TrainState, TopoGraph, PairBatch, Callable]:
    """Device-resident training: the pair POOL lives on device and each
    jitted call runs `steps_per_call` optimizer steps via lax.scan, sampling
    minibatches with the JAX PRNG inside the scan body.

    This removes the per-step host round trip (numpy sampling + H2D transfer
    + dispatch) that dominates wall clock for a model this size — the
    scaling-book rule: don't bounce to the host between steps. Returns
    (state, g, pairs, multi_step) where ``multi_step(state, g, pairs, key) ->
    (state, (losses[steps_per_call], grad_norms[steps_per_call]))``.

    The jitted `multi_step` outlives the run, kept under everything it was
    built from or closes over (`_kept`): a run placed to the same gets it back,
    its first call a hit of `jax.jit`'s own cache; any other builds its own.
    """
    batch_size = meshlib.pad_to_multiple(batch_size, mesh.shape[meshlib.DATA_AXIS])
    state, state_sh, g, g_sh = _place_sharded(state, g, mesh)
    # the full pool is small (MBs) and replicated; sampled rows get
    # constrained onto the data axis inside the step
    pool_sh = PairBatch(*([meshlib.replicated(mesh)] * 4))
    pairs = jax.device_put(PairBatch(*(jnp.asarray(a) for a in pairs)), pool_sh)
    # what the program is built from: model and transform are static fields
    # of the state's tree (so the configuration's values are in `structure`),
    # a graph with and without the table are different trees, and every shape
    # of the table follows from N, K and the block count
    shardings, structure = jax.tree.flatten((state_sh, g_sh, pool_sh))
    shapes = [(a.shape, a.dtype) for a in jax.tree.leaves((state, g, pairs))]
    built_from = (mesh, batch_size, steps_per_call, structure, tuple(shardings), tuple(shapes))
    if built_from in _kept:
        _kept[built_from] = _kept.pop(built_from)  # the most recently used goes last
        return state, g, pairs, _kept[built_from]
    for old in list(_kept)[: len(_kept) + 1 - KEPT_PROGRAMS]:  # the least recently used go first
        del _kept[old]
    batch_sh = meshlib.batch_sharding(mesh)
    step = make_train_step()

    def multi_step(st, gg, pool, key):
        global _traces
        _traces += 1  # Python runs this body only when jax traces it
        n_pool = pool.child.shape[0]

        def one(carry, k):
            with jax.named_scope(SAMPLE):
                idx = jax.random.randint(k, (batch_size,), 0, n_pool)
                batch = PairBatch(
                    *(jax.lax.with_sharding_constraint(a[idx], batch_sh) for a in pool)
                )
            return step(carry, gg, batch)

        with jax.named_scope(SAMPLE):
            keys = jax.random.split(key, steps_per_call)
        return jax.lax.scan(one, st, keys)

    # the replicated out-sharding is a pytree prefix: it covers both ys
    jitted = jax.jit(
        multi_step,
        in_shardings=(state_sh, g_sh, pool_sh, meshlib.replicated(mesh)),
        out_shardings=(state_sh, meshlib.replicated(mesh)),
        donate_argnums=(0,),
    )
    _kept[built_from] = jitted
    return state, g, pairs, jitted


def _placement(mesh: Mesh, decision: dict, hosts: int, state: Any, g: TopoGraph, batch_size: int) -> dict:
    """What the placed run occupies, for the run manifest: the mesh and why
    (`parallel.mesh.mesh_for_run`'s record), the hosts the run was given and
    the rows they were placed at (`pad_pct`: the rows above the cluster's own,
    of its own, which every step pays for), the graph's node rows read back
    from the placed arrays, the rows of one pair batch each device is
    constrained to inside the step, and which VJP the gather takes: the
    table(s) placement hung on the graph, or the rule's reason there is none
    (`neighbor_agg_pallas.gather_vjp_report`)."""
    from dragonfly2_tpu.ops.neighbor_agg_pallas import gather_vjp_report

    batch_size = meshlib.pad_to_multiple(batch_size, mesh.shape[meshlib.DATA_AXIS])
    rows = g.node_feats.shape[0]
    return {
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "decision": {**decision, "hosts": hosts, "rows": rows, "pad_pct": round(100.0 * (rows - hosts) / hosts, 2)},
        "graph": meshlib.placement_report(g._replace(by_dst=None)),
        "batch_rows_per_device": meshlib.batch_sharding(mesh).shard_shape((batch_size,))[0],
        "gather_vjp": {
            **gather_vjp_report(g.by_dst, g.neighbors.shape, *_gathered_states(state), mesh),
            "slots": int(g.neighbors.size),
            "max_in_degree": int(np.bincount(np.asarray(g.neighbors)[:hosts].ravel()).max()),
        },
    }


# `train_async` logs a line every this many steps
LOG_EVERY = 100


async def train_async(
    cfg: GNNTrainConfig,
    graph: TopoGraph,
    pairs: PairBatch,
    *,
    steps: int,
    seed: int = 0,
    steps_per_call: int = 10,
    log: Callable[[str], None] = lambda s: None,
    telemetry=None,
) -> tuple[train_state.TrainState, list[float]]:
    """Cooperative training driver for asyncio hosts (the trainer service).

    Uses the device-resident scan path: each jitted `steps_per_call`-step
    call runs in a worker thread (asyncio.to_thread) and the event loop
    regains control between calls, so the host keeps answering RPCs
    mid-train instead of stalling for the whole run. Setup (init + placement
    + the compile triggered by the first call) runs in the worker too — the
    loop never blocks on XLA. Returns (state, per-step losses); loss length
    is steps rounded up to a whole number of calls.

    telemetry: optional trainer.metrics.TrainRunTelemetry — per-step loss +
    grad-norm land in the dragonfly_train_* families after every call. Both
    ride the scan's ys and are pulled every call, so the compiled program is
    the same with and without it. It also gets every call's start and end
    once, at the run's end, with how often this run traced `multi_step` (1
    where it built the program, 0 where the kept one served it), and once
    placed, whether a kept program served the run and how many are kept.

    The mesh is `parallel.mesh.mesh_for_run`'s (every device on `data`); the
    run manifest's `placement.decision` says so, with the hosts given and the
    rows placed. `log` gets a line every `LOG_EVERY` steps and at the end.

    Spans (children of the caller's current span; to_thread copies the
    context): `trainer.gnn.setup` around init + placement + building the
    jit, inside it `trainer.gnn.place` around the host's padding and the
    copies onto the devices, `trainer.gnn.call` around each call in the
    worker, and inside it `trainer.gnn.dispatch` (key split + enqueue) and `trainer.gnn.pull` (the
    D2H pulls). The loop's turn between two calls is the gap between two
    `trainer.gnn.call` spans.
    """
    mesh, decision = meshlib.mesh_for_run()
    steps_per_call = max(1, min(steps_per_call, steps))
    calls = -(-steps // steps_per_call)
    traces_before = _traces
    hosts = graph.node_feats.shape[0]

    tracer = default_tracer()

    def _setup():
        from dragonfly2_tpu.ops.neighbor_agg_pallas import placed_rows

        with tracer.span("trainer.gnn.setup"):
            # the eager init runs the model over the rows placement will place:
            # what it compiles is a rung's, like the step, not this host count's
            rows = placed_rows(hosts, mesh.shape[meshlib.DATA_AXIS])
            state = init_state(cfg, pad_graph(graph, rows), seed)
            return shard_for_training_scan(
                state, graph, pairs, mesh,
                batch_size=cfg.batch_size, steps_per_call=steps_per_call,
            )

    kept_before = list(_kept.values())
    state, g, pool, multi_step = await asyncio.to_thread(_setup)
    if telemetry is not None:
        telemetry.on_placed(_placement(mesh, decision, hosts, state, g, cfg.batch_size))
        # whether a program kept from an earlier run served this one, and how many are kept now
        telemetry.on_kept(programs=len(_kept), served=any(f is multi_step for f in kept_before))
    key = jax.random.PRNGKey(seed)

    # each call's (start, enqueued, end) in the worker, always on: three
    # clock reads a call, summarized once at the run's end (telemetry.on_calls)
    call_times: list[tuple[float, float, float]] = []

    def _one_call(st, k, index):
        t_start = time.perf_counter()
        with tracer.span("trainer.gnn.call", index=index, steps=steps_per_call):
            with tracer.span("trainer.gnn.dispatch"):
                k, sub = jax.random.split(k)
                # the first call compiles the step or loads it from the
                # persistent cache: under a key that holds its scope names
                with jaxenv.op_names_in_cache_key() if index == 0 else contextlib.nullcontext():
                    st, (ls, gn) = multi_step(st, g, pool, sub)
            t_enqueued = time.perf_counter()
            # D2H pull materializes the whole call's chain before returning to
            # the loop
            with tracer.span("trainer.gnn.pull"):
                ls, gn = np.asarray(ls), np.asarray(gn)
        call_times.append((t_start, t_enqueued, time.perf_counter()))
        return st, k, ls, gn

    # imported here, not at the top: a line added above `multi_step` moves
    # the source lines that key its compiled program, and every cell would
    # compile its step once more
    from dragonfly2_tpu.observability.gcwatch import default_watch as gc_watch

    # what the collector takes from the first call's start to the last one's return
    gc_window = gc_watch().open()
    losses: list[float] = []
    t0 = time.perf_counter()
    for i in range(calls):
        state, key, ls, gn = await asyncio.to_thread(_one_call, state, key, i)
        if telemetry is not None:
            for lv, gv in zip(ls, gn):
                telemetry.on_step(
                    float(lv), float(gv), examples=cfg.batch_size
                )
        losses.extend(float(x) for x in ls)
        done = len(losses)
        if done % LOG_EVERY < steps_per_call or i == calls - 1:
            log(
                f"step {done}/{calls * steps_per_call} loss={losses[-1]:.5f} "
                f"({done / (time.perf_counter() - t0):.2f} steps/s)"
            )
    gc = gc_watch().close(gc_window)
    if telemetry is not None:
        telemetry.on_calls(
            call_times, traced=_traces - traces_before, first_steps=steps_per_call,
            gc_ms=None if gc is None else gc["ms"],
        )
    return state, losses
