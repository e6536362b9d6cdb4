"""The MLP loop on the device (trainer.train_mlp.train): the scan over the
pre-drawn batch rows against a plain loop, a step a dispatch, over the same
`_train_step` and the same numpy stream (every step's loss and gradient norm
and the final weights, bit for bit on the CPU); what the telemetry hook is
told; that a second run of the same shapes compiles nothing and says so
(`calls.traced`); that the scan's body is the module's `_train_step` as it
stands when the scan is traced (the benchmark's planted faults replace it);
and that no jitted name here reads as the GNN's scan program."""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dragonfly2_tpu.models.features import FEATURE_DIM
from dragonfly2_tpu.trainer import train_mlp
from dragonfly2_tpu.trainer.metrics import TrainRunTelemetry
from dragonfly2_tpu.trainer.synthetic import PairBatch

CHUNK = train_mlp.STEPS_PER_CALL


def _pairs(n: int, seed: int = 0) -> PairBatch:
    rng = np.random.default_rng(seed)
    return PairBatch(
        np.zeros(n, np.int32), np.ones(n, np.int32),
        rng.random((n, FEATURE_DIM)).astype(np.float32), rng.random(n).astype(np.float32),
    )


class _Reports(TrainRunTelemetry):
    """The hook, keeping what it was told."""

    def __init__(self):
        super().__init__("mlp")
        self.reports: list[tuple[float, float, int, int]] = []

    def on_step(self, loss, grad_norm=None, *, steps=1, examples=None):
        self.reports.append((loss, grad_norm, steps, examples))
        super().on_step(loss, grad_norm, steps=steps, examples=examples)


def _plain_loop(cfg: train_mlp.MLPTrainConfig, pairs: PairBatch, seed: int):
    """The loop as it was before the scan: a fresh model and transform, an
    eager init, and every step drawn, gathered on the host and dispatched."""
    model = train_mlp.BandwidthMLP(hidden=tuple(cfg.hidden))
    tx = optax.adam(cfg.learning_rate)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((8, pairs.feats.shape[1])))
    opt_state = tx.init(params)
    rng = np.random.default_rng(seed)
    n = len(pairs.child)
    losses, gnorms = [], []
    for _ in range(cfg.steps):
        idx = rng.integers(0, n, size=min(cfg.batch_size, n))
        params, opt_state, loss, gnorm = train_mlp._train_step(
            model, tx, params, opt_state, jnp.asarray(pairs.feats[idx]), jnp.asarray(pairs.label[idx]))
        losses.append(float(loss))
        gnorms.append(float(gnorm))
    return params, losses, gnorms


@pytest.mark.parametrize("steps,n,batch", [
    (2 * CHUNK, 1000, 64),    # whole chunks
    (CHUNK + 30, 1000, 64),   # a last chunk of another length
    (7, 1000, 64),            # less than one chunk
    (CHUNK, 40, 64),          # fewer pairs than a batch: the batch is the pairs' count
], ids=["whole_chunks", "ragged_last_chunk", "short_run", "n_under_batch"])
def test_the_scan_is_the_plain_loop_step_for_step(monkeypatch, steps, n, batch):
    monkeypatch.setattr(train_mlp, "_TELEMETRY_EVERY", 1)  # the hook hears every step
    cfg = train_mlp.MLPTrainConfig(hidden=(16, 8), steps=steps, batch_size=batch)
    pairs, sink = _pairs(n), _Reports()
    params, evaluation = train_mlp.train(cfg, pairs, eval_pairs=_pairs(50, seed=1), seed=3, telemetry=sink)
    want_params, want_losses, want_gnorms = _plain_loop(cfg, pairs, seed=3)
    assert [r[0] for r in sink.reports] == want_losses
    assert [r[1] for r in sink.reports] == want_gnorms
    assert all(r[2:] == (1, min(batch, n)) for r in sink.reports)
    jax.tree.map(np.testing.assert_array_equal, params, want_params)
    assert evaluation["train_mse"] == want_losses[-1]
    assert 0 < evaluation["eval_mse"] < 1
    assert sink.summary()["calls"]["count"] == -(-steps // CHUNK)


@pytest.mark.parametrize("steps", [5, 10, 37, CHUNK, 2 * CHUNK + 11])
def test_the_hook_hears_every_tenth_step_and_the_last(steps):
    batch = 32
    cfg = train_mlp.MLPTrainConfig(hidden=(8,), steps=steps, batch_size=batch)
    sink, lines = _Reports(), []
    train_mlp.train(cfg, _pairs(256), telemetry=sink, log=lines.append)
    due = sorted({*range(10, steps + 1, 10), steps})
    assert list(np.cumsum([r[2] for r in sink.reports])) == due
    assert all(examples == told * batch for _, _, told, examples in sink.reports)
    assert (sink.steps, sink.examples) == (steps, steps * batch)
    assert all(np.isfinite(loss) and gnorm > 0 for loss, gnorm, _, _ in sink.reports)
    # the log line, every hundredth step
    assert [line.split()[2] for line in lines] == [f"{done}/{steps}" for done in range(CHUNK, steps + 1, CHUNK)]


@contextlib.contextmanager
def _backend_compiles():
    """The backend compiles jax reports while the block runs."""
    from jax import monitoring

    seen: list[str] = []

    def listener(event, seconds, **kw):
        if "backend_compile" in event:
            seen.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(listener)


def _calls(cfg, pairs, **kw) -> dict:
    sink = TrainRunTelemetry("mlp")
    train_mlp.train(cfg, pairs, telemetry=sink, **kw)
    return sink.summary()["calls"]


def test_a_second_run_of_the_same_shapes_compiles_nothing():
    # a configuration no other test of this process trains, so the first run builds
    cfg = train_mlp.MLPTrainConfig(hidden=(24, 12), steps=CHUNK + 20, batch_size=48, learning_rate=2e-3)
    pairs, held_out = _pairs(300), _pairs(60, seed=1)
    with _backend_compiles() as first:
        cold = _calls(cfg, pairs, eval_pairs=held_out)
    # init, a scan a chunk length, the eval: the transfers and pulls compile nothing
    assert len(first) == 4 and cold["traced"] == 2 and cold["count"] == 2
    with _backend_compiles() as second:
        warm = _calls(cfg, _pairs(300, seed=5), eval_pairs=held_out, seed=9)  # other records, other rows: the same shapes
    assert second == [] and warm["traced"] == 0 and warm["count"] == 2
    assert warm["first_ms"] < cold["first_ms"]
    # another pair count is another program, once
    with _backend_compiles() as third:
        grown = _calls(cfg, _pairs(301), eval_pairs=held_out)
    assert len(third) == 2 and grown["traced"] == 2
    assert _calls(cfg, _pairs(301))["traced"] == 0
    # a run is served by chunk lengths, not by its step count: 100 and 20 were met, 15 was not
    for steps, traced in ((CHUNK, 0), (20, 0), (2 * CHUNK + 20, 0), (15, 1)):
        assert _calls(train_mlp.MLPTrainConfig(**{**vars(cfg), "steps": steps}), pairs)["traced"] == traced


def test_model_and_transform_are_made_once_per_configuration():
    a = train_mlp.MLPTrainConfig(hidden=[32, 16])
    b = train_mlp.MLPTrainConfig(hidden=(32, 16))
    assert train_mlp.make_model(a) is train_mlp.make_model(b)
    assert train_mlp._transform(a.learning_rate) is train_mlp._transform(b.learning_rate)
    assert train_mlp.make_model(a) is not train_mlp.make_model(train_mlp.MLPTrainConfig())
    assert train_mlp._transform(1e-3) is not train_mlp._transform(2e-3)


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_the_scan_body_is_the_modules_train_step_when_it_is_traced(monkeypatch, fault):
    """`benchmarks/tests/faulty_child.py` plants its MLP faults by replacing
    `train_mlp._train_step` before the server trains: the scan must reach the
    step through the module's global."""
    original = train_mlp._train_step

    def _train_step(model, tx, params, opt_state, x, y):
        if fault == "half_batch":
            half = x.shape[0] // 2
            return original(model, tx, params, opt_state, x[:half], y[:half])
        _, _, loss, gnorm = original(model, tx, params, opt_state, x, y)
        return params, opt_state, loss, gnorm

    # a configuration of this test's own: nothing traced it with the sound step
    hidden = {"half_batch": (20, 10), "state_unchanged": (10, 20)}[fault]
    cfg = train_mlp.MLPTrainConfig(hidden=hidden, steps=30, batch_size=64)
    pairs = _pairs(500)
    _, sound_losses, _ = _plain_loop(cfg, pairs, seed=0)
    monkeypatch.setattr(train_mlp, "_train_step", _train_step)
    try:
        sink = _Reports()
        params, _ = train_mlp.train(cfg, pairs, telemetry=sink)
    finally:
        train_mlp._scan_steps.clear_cache()  # the faulty program goes with the fault
    got = [r[0] for r in sink.reports]
    assert got[0] != sound_losses[9] and got[-1] != sound_losses[-1]
    if fault == "state_unchanged":
        init = train_mlp.make_model(cfg).init(jax.random.PRNGKey(0), jnp.zeros((8, FEATURE_DIM)))
        jax.tree.map(np.testing.assert_array_equal, params, init)


def test_no_jitted_name_reads_as_the_gnns_scan_program():
    """Every configuration of the benchmark names the GNN's scan program to
    the trace readers by the substring `multi_step` of its module's name."""
    jitted = {name: f for name, f in vars(train_mlp).items() if hasattr(f, "lower") and hasattr(f, "clear_cache")}
    assert set(jitted) == {"_train_step", "_init", "_scan_steps", "_eval_mse"}
    assert not [name for name, f in jitted.items() if "multi_step" in name or "multi_step" in f.__name__]
    cfg = train_mlp.MLPTrainConfig(hidden=(8,))
    model, tx = train_mlp.make_model(cfg), train_mlp._transform(cfg.learning_rate)
    params, opt_state = train_mlp._init(model, tx, FEATURE_DIM, np.uint32(0))
    pairs = _pairs(64)
    lowered = train_mlp._scan_steps.lower(
        model, tx, params, opt_state, pairs.feats, pairs.label, np.zeros((3, 16), np.int32))
    assert "multi_step" not in lowered.as_text() and "jit__scan_steps" in lowered.as_text()
