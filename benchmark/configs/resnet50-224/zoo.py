"""ResNet-50 at 224 px, 1000 classes, bf16 compute — the model-zoo
module of the `resnet50-224` configuration (sizes in `config.json`
beside this file; nothing reduced from the paper's Table 1).

Reuses the program's model, loss and optimizer
(`models/resnet50_subclass`); only `dataset_fn` is its own, because the
package module decodes at its 64 px test default.
"""

import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(_HERE)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import jax.numpy as jnp  # noqa: E402

from benchmark.harness import probe  # noqa: E402
from elasticdl_tpu.models.record_codec import decode_image_records  # noqa: E402
from elasticdl_tpu.models.resnet50_subclass import (  # noqa: E402,F401
    ResNet50,
    eval_metrics_fn,
    loss,
    optimizer,
)

with open(os.path.join(_HERE, "config.json")) as _f:
    SIZES = json.load(_f)
IMAGE_SHAPE = tuple(SIZES["image_shape"])

probe.start_if_worker()  # inert outside a benchmarked worker


def custom_model():
    return ResNet50(
        num_classes=SIZES["num_classes"],
        stage_sizes=tuple(SIZES["stage_sizes"]),
        compute_dtype=jnp.dtype(SIZES["compute_dtype"]),
    )


def dataset_fn(records, mode):
    return decode_image_records(records, IMAGE_SHAPE, scale=False)
