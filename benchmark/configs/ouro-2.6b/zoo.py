"""Ouro-2.6B (ByteDance, a looped language model) at its published
widths, bf16 compute, sequences of 2048 — the model-zoo module of the
`ouro-2.6b` configuration. The sizes, the two cuts (depth, and the
vocabulary as one chip's share of a four-chip vocabulary-parallel
head), what was assumed beyond the published `config.json` and the
optimizer are in `config.json` beside this file.

The block is the program's own (`models/transformer_lm.plain_forward`
with `mlp="swiglu"`, `sandwich_norm`, `rope_base` and `n_loops` set):
this file holds sizes and the optimizer's learning rate only.
"""

import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(_HERE)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from benchmark.harness import probe  # noqa: E402
from elasticdl_tpu.models.transformer_lm_zoo import (  # noqa: E402,F401
    TransformerLM,
    dataset_fn,
    eval_metrics_fn,
    loss,
)

with open(os.path.join(_HERE, "config.json")) as _f:
    SIZES = json.load(_f)

probe.start_if_worker()  # inert outside a benchmarked worker
if float(os.environ.get(probe.ENV_TRACE_SECS) or 0) > 0:
    # a traced run: the worker writes the window program's op_names,
    # which `layer_metrics/_scopes.py` joins the device trace to (an
    # untraced run asks for nothing, and the worker writes nothing)
    os.environ.setdefault("EDL_HLO_SCOPES", "1")


def custom_model(dtype=None, **overrides):
    """The configuration's model; `compare.py` asks for float32."""
    if not (
        SIZES["hidden_act"] == "silu"
        and not SIZES["tie_word_embeddings"]
        and SIZES["hidden_size"]
        == SIZES["num_attention_heads"] * SIZES["head_dim"]
        and SIZES["num_key_value_heads"] == SIZES["num_attention_heads"]
    ):
        raise ValueError(
            "config.json states a block this module does not build: SiLU "
            "gate, untied head, plain multi-head attention, heads x head_dim "
            "= hidden"
        )
    sizes = dict(
        vocab=SIZES["vocab_size"],
        d_model=SIZES["hidden_size"],
        n_heads=SIZES["num_attention_heads"],
        d_ff=SIZES["intermediate_size"],
        n_layers=SIZES["num_hidden_layers"],
        n_loops=SIZES["total_ut_steps"],
        mlp="swiglu",
        sandwich_norm=True,
        rope_base=float(SIZES["rope_theta"]),
        norm_eps=SIZES["rms_norm_eps"],
        exit_entropy_weight=SIZES["exit_entropy_weight"],
        dtype=jnp.dtype(dtype or SIZES["compute_dtype"]),
    )
    sizes.update(overrides)
    return TransformerLM(**sizes)


def optimizer():
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adam(SIZES["learning_rate"]),
    )
