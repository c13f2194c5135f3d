"""The repo's dense transformer LM at the published sizes of
EleutherAI/pythia-160m, bf16 compute, sequences of 2048 — the
model-zoo module of the `lm-dense-160m` configuration (sizes and the
block's departures from the source in `config.json` beside this file).

Reuses the program's adapter, loss and optimizer
(`models/transformer_lm_zoo`); only the sizes are its own.
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
from elasticdl_tpu.models.transformer_lm_zoo import (  # noqa: E402,F401
    TransformerLM,
    dataset_fn,
    eval_metrics_fn,
    loss,
    optimizer,
)

with open(os.path.join(_HERE, "config.json")) as _f:
    SIZES = json.load(_f)

probe.start_if_worker()  # inert outside a benchmarked worker


def custom_model():
    return TransformerLM(
        vocab=SIZES["vocab_size"],
        d_model=SIZES["hidden_size"],
        n_heads=SIZES["num_attention_heads"],
        d_ff=SIZES["intermediate_size"],
        n_layers=SIZES["num_hidden_layers"],
        dtype=jnp.dtype(SIZES["compute_dtype"]),
    )
