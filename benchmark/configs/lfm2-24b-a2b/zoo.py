"""LFM2-24B-A2B (Liquid AI; the double-gated short convolution three
layers in four, grouped-query attention with normed queries and keys in
the fourth, two leading dense layers, then layers of 64 routed experts,
top-4 by sigmoid scores, no shared expert, the head tied to the
embedding) at its published widths, bf16 compute, sequences of 2048 —
the model-zoo module of the `lfm2-24b-a2b` configuration. The sizes,
the cuts (depth, the 8 of 64 experts this chip holds of an 8-chip
expert-parallel layer, the vocabulary as this chip's eighth), what was
assumed beyond the published `config.json` and the optimizer are in
`config.json` beside this file.

The block is the program's own (`models/transformer_lm.plain_forward`
with `layer_types` of "conv" and "mha", `n_kv_heads`, `qk_norm`,
`tie_embeddings` and `moe_score="sigmoid"` set; the expert layer is
`parallel/moe.moe_topk_held` with no shared expert): this file holds
sizes and the optimizer's learning rate only.
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

MIXERS = {"conv": "conv", "full_attention": "mha"}


def custom_model(dtype=None, **overrides):
    """The configuration's model; `compare.py` asks for float32."""
    first, count = SIZES["held_layers"]
    if not (
        SIZES["model_type"] == "lfm2_moe"
        and not SIZES["conv_bias"]
        and SIZES["rope_parameters"]["rope_type"] == "default"
        and (SIZES["norm_topk_prob"], SIZES["use_expert_bias"]) == (True, True)
        and count == SIZES["num_hidden_layers"]
        and first + SIZES["num_dense_layers"]
        == SIZES["published"]["num_dense_layers"]
        and set(SIZES["layer_types"]) <= set(MIXERS)
    ):
        raise ValueError(
            "config.json states a block this module does not build: a "
            "short convolution without bias, plain rotary angles, sigmoid "
            "scores with a selection bias and renormalised gates, the "
            "layers held here ending the published dense ones, a mixer "
            "named 'conv' or 'full_attention' for every layer"
        )
    sizes = dict(
        vocab=SIZES["vocab_size"],
        d_model=SIZES["hidden_size"],
        n_heads=SIZES["num_attention_heads"],
        n_kv_heads=SIZES["num_key_value_heads"],
        qk_norm=True,
        d_ff=SIZES["intermediate_size"],
        n_layers=SIZES["num_hidden_layers"],
        n_dense_layers=SIZES["num_dense_layers"],
        mlp="swiglu",
        # the published pattern is kept whole; the layers held here
        # are `held_layers` = (first, count) of it
        layer_types=tuple(
            MIXERS[kind] for kind in SIZES["layer_types"][first:first + count]
        ),
        conv_taps=SIZES["conv_L_cache"],
        rope_base=float(SIZES["rope_parameters"]["rope_theta"]),
        norm_eps=SIZES["norm_eps"],
        tie_embeddings=True,
        # the router's width is the published count; the experts whose
        # weights exist here are `held_experts`
        n_experts=SIZES["published"]["num_experts"],
        held_experts=tuple(SIZES["held_experts"]),
        d_expert=SIZES["moe_intermediate_size"],
        moe_top_k=SIZES["num_experts_per_tok"],
        n_shared_experts=0,
        routed_scaling=float(SIZES["routed_scaling_factor"]),
        moe_score="sigmoid",
        moe_renormalize=SIZES["norm_topk_prob"],
        aux_weight=0.0,
        remat=True,
        dtype=jnp.dtype(dtype or SIZES["compute_dtype"]),
    )
    sizes.update(overrides)
    return TransformerLM(**sizes)


def optimizer():
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adam(SIZES["learning_rate"]),
    )
