"""Component-level time/byte breakdown of the GNN training step (VERDICT r4
weak #1: "nobody has yet run a profile on the step to say where the other 98%
goes"). Times each stage of the step in isolation on the device JAX finds
(named in the output) and prints one JSON object naming the sinks, with XLA
cost-analysis bytes/FLOPs per stage so the bandwidth-bound argument is
checkable per component:

  python tools/gnn_profile.py            # config-2 shape (1k nodes)
  python tools/gnn_profile.py --scaled   # config-3 scale (16k nodes)

Stages (cumulative nesting, so sink = difference of adjacent stages):
  encode       GraphSAGE encoder alone (3 SAGE layers: gathers + GEMMs)
  gather_agg   just the neighbor gather + masked-mean of one layer width
  forward      full scoring forward (encoder + pairwise head)
  grad         loss + backward
  step         grad + optimizer update (the trained unit, excl. scan wrapper)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sync(out) -> None:
    """End a timed window with a D2H fetch of ONE element that depends on
    the whole chain: the host cannot have the value before the device has
    computed it. Works for any output pytree (grad dicts, TrainState,
    tuples); slices on DEVICE first so a single element is copied, not a
    whole activation.

    dflint DF013 recognizes this helper (and any np.asarray/float() pull) as
    a valid sync inside a perf_counter window — do not drop the _sync() calls
    from timed regions or the numbers time dispatch, not compute."""
    import jax

    leaf = jax.tree.leaves(out)[0]
    float(np.asarray(leaf.ravel()[0] if hasattr(leaf, "ravel") else leaf))


def _timed(fn, *args, repeats: int | None = None) -> float:
    import jax

    if repeats is None:
        # a --cpu run is ~1000x slower; full TPU-sized windows would blow
        # any reasonable wall clock there
        repeats = 30 if jax.devices()[0].platform != "cpu" else 2
    out = fn(*args)
    _sync(out)
    best = float("inf")
    for _ in range(3):  # best-of-3 windows, same rationale as bench.py
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(*args)
        _sync(out)
        best = min(best, (time.perf_counter() - t0) / repeats)
    return best


def _cost(lowered) -> tuple[float, float]:
    ca = lowered.compile().cost_analysis()
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scaled", action="store_true", help="config-3 scale (16k nodes)")
    ap.add_argument("--cpu", action="store_true", help="run on the host CPU")
    args = ap.parse_args()

    from dragonfly2_tpu.utils import jaxenv

    if args.cpu or os.environ.get("DF_BENCH_FORCE_CPU"):
        jaxenv.pin_host_cpu()
    jaxenv.enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.ops.neighbor_agg import masked_mean, neighbor_gather
    from dragonfly2_tpu.trainer import synthetic, train_gnn

    if args.scaled:
        num_nodes, hidden, batch = 16384, 512, 16384
    else:
        num_nodes, hidden, batch = 1024, 256, 4096
    cluster = synthetic.make_cluster(
        num_nodes=num_nodes, num_neighbors=16, num_pairs=65536, seed=7
    )
    cfg = train_gnn.GNNTrainConfig(hidden=hidden, batch_size=batch)
    model = train_gnn.make_model(cfg)
    state = train_gnn.init_state(cfg, cluster.graph, rng_seed=7)
    g = jax.tree.map(jnp.asarray, cluster.graph)
    rng = np.random.default_rng(7)
    sel = rng.integers(0, len(cluster.pairs.child), size=batch)
    pb = type(cluster.pairs)(
        *(jnp.asarray(np.asarray(a)[sel]) for a in cluster.pairs)
    )

    results: dict[str, dict] = {}

    def record(name, fn, *fargs):
        t = _timed(fn, *fargs)
        flops, nbytes = _cost(jax.jit(fn).lower(*fargs))
        results[name] = {
            "ms": round(t * 1e3, 4),
            "gflops": round(flops / 1e9, 3),
            "bytes_mb": round(nbytes / 1e6, 2),
            # per-stage achieved bandwidth: is THIS stage near the HBM roof?
            "achieved_gb_per_s": round(nbytes / t / 1e9, 1) if t > 0 else 0.0,
            "achieved_tflops": round(flops / t / 1e12, 3) if t > 0 else 0.0,
        }

    encode = jax.jit(lambda p, gg: model.apply(p, gg, method=model.embed))
    record("encode", encode, state.params, g)

    H = cfg.hidden
    u = jnp.ones((num_nodes, 16, H), jnp.bfloat16)  # post-gather message tensor

    @jax.jit
    def gather_agg(gg, uu):
        m = neighbor_gather(uu[:, 0, :], gg.neighbors)
        return masked_mean(m, gg.mask.astype(jnp.bfloat16))

    record("gather_agg_1layer", gather_agg, g, u)

    fwd = jax.jit(
        lambda p, gg, b: train_gnn.loss_fn(model.apply, p, gg, b)
    )
    record("forward_loss", fwd, state.params, g, pb)

    grad = jax.jit(
        lambda p, gg, b: jax.grad(
            lambda pp: train_gnn.loss_fn(model.apply, pp, gg, b)
        )(p)
    )
    record("grad", grad, state.params, g, pb)

    @jax.jit
    def full_step(st, gg, b):
        loss, grads = jax.value_and_grad(
            lambda pp: train_gnn.loss_fn(model.apply, pp, gg, b)
        )(st.params)
        return st.apply_gradients(grads=grads), loss

    record("train_step", full_step, state, g, pb)

    step = results["train_step"]["ms"]
    sinks = sorted(
        ((k, v["ms"]) for k, v in results.items() if k != "train_step"),
        key=lambda kv: -kv[1],
    )
    print(
        json.dumps(
            {
                **jaxenv.device_report(),
                "shape": {"num_nodes": num_nodes, "hidden": hidden, "batch": batch},
                "stages": results,
                "top_sinks": [
                    {"stage": k, "ms": v, "frac_of_step": round(v / step, 3)}
                    for k, v in sinks
                ],
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
