"""Each configuration's zoo module against its plain reference, on
seeded random weights at a small size (the published depth, small
widths or images), float32 on the CPU. Tolerances: both sides are
float32 with the same mathematics in a different order (scan vs loop,
flax vs lax), so they agree to accumulated rounding — a relative 2e-4
of the largest logit, far tighter than bf16's 4e-3, so a lower
precision or a left-out term on either side fails."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)
TOLERANCE = 2e-4


from benchmark.harness.manifest import load_module  # noqa: E402


def load(config, name):
    return load_module(
        os.path.join(ROOT, "benchmark", "configs", config, name + ".py")
    )


def close(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= TOLERANCE * max(np.max(np.abs(b)), 1e-6)


SLOW = pytest.mark.slow  # gradients compile for a minute on the CPU


@pytest.mark.parametrize(
    "layers,gradients", [(2, False), pytest.param(3, True, marks=SLOW)]
)
def test_dense_lm_matches_the_reference(layers, gradients):
    import jax
    import jax.numpy as jnp

    zoo, ref = load("lm-dense-160m", "zoo"), load("lm-dense-160m", "reference")
    model = zoo.TransformerLM(
        vocab=97, d_model=48, n_heads=4, d_ff=96, n_layers=layers,
        dtype=jnp.float32,
    )
    params = model.init(jax.random.PRNGKey(3), None)["params"]
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, 97, size=(2, 33)), jnp.int32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    logits = jax.jit(lambda p: model.apply({"params": p}, inputs))(params)
    assert close(logits, jax.jit(lambda p: ref.forward(p, inputs, 4))(params))
    assert float(zoo.loss(logits, targets)) == pytest.approx(
        float(jax.jit(lambda p: ref.loss(p, inputs, targets, 4))(params)),
        rel=TOLERANCE,
    )
    if not gradients:
        return

    def zoo_loss(p):
        return zoo.loss(model.apply({"params": p}, inputs), targets)

    value, grads = jax.value_and_grad(zoo_loss)(
        jax.tree_util.tree_map(jnp.asarray, params)
    )
    ref_value, ref_grads = ref.loss_and_grads(params, inputs, targets, 4)
    assert float(value) == pytest.approx(float(ref_value), rel=TOLERANCE)
    for ours, theirs in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(ref_grads)
    ):
        assert close(ours, theirs)


@pytest.mark.parametrize(
    "stages,gradients",
    [((1, 1, 1, 1), False), pytest.param((3, 4, 6, 3), True, marks=SLOW)],
)
def test_resnet_matches_the_reference(stages, gradients):
    import jax
    import jax.numpy as jnp

    zoo, ref = load("resnet50-224", "zoo"), load("resnet50-224", "reference")
    model = zoo.ResNet50(
        num_classes=10, stage_sizes=stages, compute_dtype=jnp.float32
    )
    rng = np.random.default_rng(7)
    images = jnp.asarray(rng.integers(0, 256, size=(4, 32, 32, 3)), jnp.uint8)
    labels = jnp.asarray(rng.integers(0, 10, size=(4,)), jnp.int32)
    variables = jax.jit(lambda: model.init(jax.random.PRNGKey(1), images, train=False))()
    # the last scale of each block starts at zero: fill every leaf, so
    # that no branch of the reference is multiplied away
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    params = jax.tree_util.tree_unflatten(
        tree, [jnp.asarray(rng.normal(0, 0.1, l.shape) + (l.ndim == 1), jnp.float32)
               for l in leaves],
    )
    stats = {"batch_stats": variables["batch_stats"]}

    def zoo_loss(p):
        logits, _ = model.apply(
            {"params": p, **stats}, images, train=True, mutable=["batch_stats"]
        )
        return zoo.loss(logits, labels), logits

    value, logits = jax.jit(zoo_loss)(params)
    assert close(logits, jax.jit(lambda p: ref.forward(p, images, stages))(params))
    assert float(value) == pytest.approx(
        float(jax.jit(lambda p: ref.loss(p, images, labels, stages))(params)),
        rel=TOLERANCE,
    )
    if not gradients:
        return
    (value, logits), grads = jax.value_and_grad(zoo_loss, has_aux=True)(params)
    ref_value, ref_grads = ref.loss_and_grads(params, images, labels, stages)
    assert float(value) == pytest.approx(float(ref_value), rel=TOLERANCE)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    worst = max(
        float(jnp.max(jnp.abs(g - r))) / max(float(jnp.max(jnp.abs(r))), 1e-6)
        for (_, g), r in zip(flat, jax.tree_util.tree_leaves(ref_grads))
    )
    assert worst <= 5e-3  # 53 convolutions and norms of float32 back to back
