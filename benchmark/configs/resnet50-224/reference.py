"""Plain reference of the `resnet50-224` model: the training-mode
forward pass, the loss and its gradients in straightforward
`jax.numpy`/`lax` and float32, reading the parameter tree the zoo's
flax module makes (`Conv_i`, `BatchNorm_i`, `Bottleneck_i`, `Dense_0`)
— no flax, no cast. He et al. 2015 Table 1 as
`models/resnet50_subclass` arranges it: 7x7/2 stem, 3x3/2 max pool,
bottlenecks 1x1 -> 3x3 (carrying the stride) -> 1x1 with a projection
shortcut where the shape changes, batch norm on the batch's own
statistics (epsilon 1e-5), global average pool, dense head. The tests
compare the zoo module with this at a small image size.
"""

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5


def _conv(x, kernel, stride):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _norm(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) / jnp.sqrt(var + EPS) * p["scale"] + p["bias"]


def _bottleneck(p, x, stride):
    y = jax.nn.relu(_norm(_conv(x, p["Conv_0"]["kernel"], 1), p["BatchNorm_0"]))
    y = jax.nn.relu(
        _norm(_conv(y, p["Conv_1"]["kernel"], stride), p["BatchNorm_1"])
    )
    y = _norm(_conv(y, p["Conv_2"]["kernel"], 1), p["BatchNorm_2"])
    if "Conv_3" in p:  # projection shortcut
        x = _norm(_conv(x, p["Conv_3"]["kernel"], stride), p["BatchNorm_3"])
    return jax.nn.relu(y + x)


def forward(params, images, stage_sizes=(3, 4, 6, 3)):
    """images: uint8 or float [B, H, W, 3] -> logits [B, classes]."""
    x = jnp.asarray(images, jnp.float32)
    if jnp.issubdtype(jnp.asarray(images).dtype, jnp.integer):
        x = x / 255.0
    x = jax.nn.relu(
        _norm(_conv(x, params["Conv_0"]["kernel"], 2), params["BatchNorm_0"])
    )
    x = lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    block = 0
    for stage, count in enumerate(stage_sizes):
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            x = _bottleneck(params[f"Bottleneck_{block}"], x, stride)
            block += 1
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]


def loss(params, images, labels, stage_sizes=(3, 4, 6, 3)):
    """Mean softmax cross-entropy with integer labels."""
    logp = jax.nn.log_softmax(forward(params, images, stage_sizes), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


loss_and_grads = jax.value_and_grad(loss)
