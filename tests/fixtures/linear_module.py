"""Minimal fixture model: linear regression on y = 2x + 1 records.

Mirrors the reference's in-repo test model
(elasticdl/python/tests/test_module.py) so unit tests don't depend on
model_zoo/.
"""

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax


class Linear(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(1)(x)


def custom_model():
    return Linear()


def dataset_fn(records, mode):
    arr = np.stack([np.frombuffer(r, dtype=np.float32) for r in records])
    return arr[:, :1], arr[:, 1:]


def loss(outputs, labels):
    return jnp.mean((outputs - labels) ** 2)


def optimizer():
    return optax.sgd(0.5)


def optimizer_delayed():
    """For jobs whose pushes apply one version late (two async
    workers): x ~ U(-1, 1) gives the bias curvature 2, and an update
    delayed by one step is stable only below step * curvature = 1 —
    sgd(0.5) sits on that edge, 0.3 is inside it and still lands the
    kernel within 0.2 of 2.0 in 16 steps."""
    return optax.sgd(0.3)


def eval_metrics_fn(predictions, labels):
    return {"mse": jnp.mean((predictions - labels) ** 2)}


class PredictionOutputsProcessor:
    """Sinks predictions to EDL_TEST_PRED_OUT-<worker_id>.npy — lets
    process-mode e2e tests observe the prediction path (reference ABC:
    worker/prediction_outputs_processor.py:4-22)."""

    def process(self, predictions, worker_id):
        base = __import__("os").environ.get("EDL_TEST_PRED_OUT")
        if base:
            path = f"{base}-{worker_id}.npy"
            existing = (
                np.load(path) if __import__("os").path.exists(path) else
                np.zeros((0, predictions.shape[-1]), predictions.dtype)
            )
            np.save(path, np.concatenate([existing, predictions]))
