"""A launcher for the fault tests: breaks the trainer's timed path in one
named way (BENCH_FAULT), then hands over to trainer_child.py unchanged. The
harness must see `correct` come out false for every one of them."""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def plant(fault: str) -> None:
    if fault == "state_unchanged":
        # the optimizer step returns its state as it got it
        from flax.training import train_state

        train_state.TrainState.apply_gradients = lambda self, *, grads, **kw: self
    elif fault == "half_batch":
        # half of the batch left out, the mean taken over the rest
        import jax.numpy as jnp

        from dragonfly2_tpu.trainer import train_gnn

        def loss_fn(apply_fn, params, g, batch):
            pred = apply_fn(params, g, batch.child, batch.parent, batch.feats)
            half = pred.shape[0] // 2
            return jnp.mean(((pred - batch.label) ** 2)[:half])

        train_gnn.loss_fn = loss_fn
    elif fault in ("altered_publish", "mlp_altered_publish"):
        # what is published is not what was trained
        import jax

        from dragonfly2_tpu.trainer import artifacts

        original = artifacts.save_artifact
        which = "mlp" if fault.startswith("mlp_") else "gnn"

        def save_artifact(directory, *, model_type, version, params, config):
            if model_type == which:
                params = jax.tree.map(lambda a: a * 0.5, params)
            return original(directory, model_type=model_type, version=version, params=params, config=config)

        artifacts.save_artifact = save_artifact
    elif fault in ("mlp_state_unchanged", "mlp_half_batch"):
        # the same two faults in the host-dispatched MLP loop
        from dragonfly2_tpu.trainer import train_mlp

        original_step = train_mlp._train_step

        def _train_step(model, tx, params, opt_state, x, y):
            if fault == "mlp_half_batch":
                half = x.shape[0] // 2
                return original_step(model, tx, params, opt_state, x[:half], y[:half])
            _, _, loss, gnorm = original_step(model, tx, params, opt_state, x, y)
            return params, opt_state, loss, gnorm

        train_mlp._train_step = _train_step
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    sys.path.insert(0, sys.argv[1])
    plant(os.environ["BENCH_FAULT"])
    import trainer_child

    trainer_child.main(sys.argv[1:])
