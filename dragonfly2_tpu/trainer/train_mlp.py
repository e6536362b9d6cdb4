"""MLP bandwidth-predictor training (north-star config 1).

Trains models.mlp.BandwidthMLP on (child, parent) pair features from the
scheduler's download records — the path the reference sketched as
TrainMLPRequest CSV chunks (scheduler/announcer/announcer.go:193) into a
trainer that was never written. Single-host JAX (CPU or one chip): the model
is tiny; data parallelism buys nothing here, so no mesh.

The loop runs on the device: a run's batch rows are drawn first, on the host,
the pairs are put on the device once, and one jitted function scans
`_train_step` over a chunk of index rows (`_scan_steps`; a chunk is the log's
cadence, one D2H pull a call). Model and transform are made once per
configuration's values, so a second run of the same shapes is served by
`jax.jit`'s own cache and traces nothing; the run manifest's `calls.traced`
says whether it did.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import cache, partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dragonfly2_tpu.models.mlp import BandwidthMLP
from dragonfly2_tpu.trainer.synthetic import PairBatch


@dataclass
class MLPTrainConfig:
    hidden: tuple[int, ...] = (256, 256, 128)
    batch_size: int = 4096
    learning_rate: float = 1e-3
    steps: int = 500


# Model and transform are functions of the configuration's values alone and
# static arguments of every jitted function here (a transform's closures
# compare by identity): made once per distinct values, so that a second run
# of one configuration hits `jax.jit`'s cache (as `train_gnn._model` does).
@cache
def _model(hidden: tuple[int, ...]) -> BandwidthMLP:
    return BandwidthMLP(hidden=hidden)


@cache
def _transform(learning_rate: float) -> optax.GradientTransformation:
    return optax.adam(learning_rate)


def make_model(cfg: MLPTrainConfig) -> BandwidthMLP:
    return _model(tuple(cfg.hidden))


@partial(jax.jit, static_argnums=(0, 1))
def _train_step(model: BandwidthMLP, tx: Any, params: Any, opt_state: Any, x: jnp.ndarray, y: jnp.ndarray):
    def loss_fn(p):
        pred = model.apply(p, x)
        return jnp.mean((pred - y) ** 2)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    # global grad norm rides every step's outputs: a diverging run shows in
    # dragonfly_train_grad_norm steps before the loss moves (ISSUE 15); the
    # reduction is a handful of FLOPs next to the matmuls
    gnorm = optax.global_norm(grads)
    updates, opt_state = tx.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss, gnorm


# the hook's cadence: every Nth step's loss and gradient norm are reported,
# which keeps the curve dense at a tenth of the calls into the registry
_TELEMETRY_EVERY = 10
# steps a scan call: the log's cadence, and one D2H pull a call
STEPS_PER_CALL = 100

# how often `_scan_steps`' Python body has run: jax runs it only to trace
_traces = 0


@partial(jax.jit, static_argnums=(0, 1, 2))
def _init(model: BandwidthMLP, tx: Any, feature_dim: int, seed: jnp.ndarray):
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((8, feature_dim)))
    return params, tx.init(params)


@partial(jax.jit, static_argnums=(0, 1))
def _scan_steps(model: BandwidthMLP, tx: Any, params: Any, opt_state: Any,
                feats: jnp.ndarray, label: jnp.ndarray, rows: jnp.ndarray):
    """`_train_step` over `rows[steps, batch]`, a step a row, the batches
    gathered on the device; returns every step's loss and gradient norm."""
    global _traces
    _traces += 1

    def one(carry, idx):
        # the module's `_train_step` as it stands when this is traced
        p, o, loss, gnorm = _train_step(model, tx, *carry, feats[idx], label[idx])
        return (p, o), (loss, gnorm)

    (params, opt_state), (losses, gnorms) = jax.lax.scan(one, (params, opt_state), rows)
    return params, opt_state, losses, gnorms


@partial(jax.jit, static_argnums=(0,))
def _eval_mse(model: BandwidthMLP, params: Any, feats: jnp.ndarray, label: jnp.ndarray):
    return jnp.mean((model.apply(params, feats) - label) ** 2)


def train(
    cfg: MLPTrainConfig,
    pairs: PairBatch,
    *,
    eval_pairs: PairBatch | None = None,
    seed: int = 0,
    log: Callable[[str], None] = lambda s: None,
    telemetry=None,
) -> tuple[Any, dict[str, float]]:
    """Returns (params, evaluation dict with train/eval mse).

    telemetry: optional trainer.metrics.TrainRunTelemetry — receives sampled
    per-step loss/grad-norm/examples (the dragonfly_train_* families), and at
    the run's end every scan call's start and end with how often the run
    traced `_scan_steps` (0 where a run of the same shapes came before)."""
    model = make_model(cfg)
    tx = _transform(cfg.learning_rate)
    n = len(pairs.child)
    batch = min(cfg.batch_size, n)
    # every step's rows first, from the stream the loop has always drawn from
    rng = np.random.default_rng(seed)
    rows = np.empty((cfg.steps, batch), np.int32)
    for i in range(cfg.steps):
        rows[i] = rng.integers(0, n, size=batch)
    params, opt_state = _init(model, tx, pairs.feats.shape[1], np.uint32(seed))
    feats, label = jax.device_put((pairs.feats, pairs.label))
    traces_before = _traces
    call_times: list[tuple[float, float]] = []
    last_loss = 0.0
    pending = 0
    for start in range(0, cfg.steps, STEPS_PER_CALL):
        t_start = time.perf_counter()
        params, opt_state, losses, gnorms = _scan_steps(
            model, tx, params, opt_state, feats, label, rows[start:start + STEPS_PER_CALL]
        )
        # the D2H pull materializes the whole call's chain
        losses, gnorms = np.asarray(losses), np.asarray(gnorms)
        call_times.append((t_start, time.perf_counter()))
        if telemetry is not None:
            for i, (loss, gnorm) in enumerate(zip(losses, gnorms), start + 1):
                pending += 1
                if pending >= _TELEMETRY_EVERY or i == cfg.steps:
                    telemetry.on_step(float(loss), float(gnorm), steps=pending, examples=pending * batch)
                    pending = 0
        last_loss = float(losses[-1])
        done = start + len(losses)
        if done % STEPS_PER_CALL == 0:
            log(f"mlp step {done}/{cfg.steps} loss={last_loss:.5f}")
    if telemetry is not None:
        telemetry.on_calls(call_times, traced=_traces - traces_before)
    evaluation = {"train_mse": last_loss}
    if eval_pairs is not None and len(eval_pairs.child):
        evaluation["eval_mse"] = float(_eval_mse(model, params, eval_pairs.feats, eval_pairs.label))
    return params, evaluation
