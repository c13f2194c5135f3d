"""Laguna-XS.2 (poolside; window-512 and full attention three layers to
one, 64 and 48 query heads of 128 over 8 key-value heads, each kind
with its own rotation, a per-head gate on the attention's output, one
dense layer, then layers of 256 routed experts of 512, top-8 by softmax
scores, renormalised, x 2.5, and one shared expert; an untied head) at
its published widths, bf16 compute, sequences of 8192 — the model-zoo
module of the `laguna-xs2` configuration. The sizes, the cuts (depth,
the 16 of 256 experts this chip holds of a 16-chip expert-parallel
layer, the vocabulary as this chip's eighth), what was assumed beyond
the published `config.json` and the optimizer are in `config.json`
beside this file.

The block is the program's own (`models/transformer_lm.plain_forward`
with `layer_types` of "mha" and "swa", `head_width`, `attn_gate`,
`rope_dim`, `rope_factor`, `rope_yarn` and the `swa_*` settings; the
expert layer is `parallel/moe.moe_topk_held`): this file holds sizes
and the optimizer's learning rate only.
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
from elasticdl_tpu.models.transformer_lm import YarnScaling  # noqa: E402
from elasticdl_tpu.models.transformer_lm_zoo import (  # noqa: E402,F401
    TransformerLM,
    dataset_fn,
    eval_metrics_fn,
    loss,
)

with open(os.path.join(_HERE, "config.json")) as _f:
    SIZES = json.load(_f)

probe.start_if_worker()  # inert outside a benchmarked worker

MIXERS = {"full_attention": "mha", "sliding_attention": "swa"}


def custom_model(dtype=None, **overrides):
    """The configuration's model; `compare.py` asks for float32."""
    first, count = SIZES["held_layers"]
    kinds = SIZES["layer_types"][first:first + count]
    heads = dict(zip(
        kinds, SIZES["num_attention_heads_per_layer"][first:first + count]
    ))
    dense = SIZES["mlp_layer_types"][first:first + count].count("dense")
    rope = SIZES["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if not (
        SIZES["model_type"] == "laguna"
        and SIZES["gating"] is True
        and not SIZES["attention_bias"]
        and not SIZES["tie_word_embeddings"]
        and not SIZES["moe_apply_router_weight_on_input"]
        and count == SIZES["num_hidden_layers"]
        and set(kinds) == set(MIXERS)
        and SIZES["mlp_layer_types"][first:first + count]
        == ["dense"] * dense + ["sparse"] * (count - dense)
        and all(
            n == heads[kind] for kind, n in zip(
                SIZES["layer_types"], SIZES["num_attention_heads_per_layer"]
            )
        )
        and (full["rope_type"], sliding["rope_type"]) == ("yarn", "default")
        and sliding["partial_rotary_factor"] == 1
    ):
        raise ValueError(
            "config.json states a block this module does not build: a "
            "per-head output gate, no bias, an untied head, the gate on "
            "the experts' output, layers of 'full_attention' and "
            "'sliding_attention' with one head count a kind, dense MLPs "
            "first, YaRN on the full layers and the plain rotation over "
            "the whole head on the sliding ones"
        )
    sizes = dict(
        vocab=SIZES["vocab_size"],
        d_model=SIZES["hidden_size"],
        head_width=SIZES["head_dim"],
        n_kv_heads=SIZES["num_key_value_heads"],
        attn_gate=True,
        d_ff=SIZES["intermediate_size"],
        n_layers=count,
        n_dense_layers=dense,
        mlp="swiglu",
        # the published patterns are kept whole; the layers held here
        # are `held_layers` = (first, count) of them
        layer_types=tuple(MIXERS[kind] for kind in kinds),
        n_heads=heads["full_attention"],
        rope_base=float(full["rope_theta"]),
        rope_dim=int(SIZES["head_dim"] * full["partial_rotary_factor"]),
        rope_factor=float(full["attention_factor"]),
        rope_yarn=YarnScaling(
            float(full["factor"]), float(full["beta_fast"]),
            float(full["beta_slow"]),
            full["original_max_position_embeddings"], 1.0, 0.0,
        ),
        swa_heads=heads["sliding_attention"],
        swa_window=SIZES["sliding_window"],
        swa_rope_base=float(sliding["rope_theta"]),
        norm_eps=SIZES["rms_norm_eps"],
        # the router's width is the published count; the experts whose
        # weights exist here are `held_experts`
        n_experts=SIZES["published"]["num_experts"],
        held_experts=tuple(SIZES["held_experts"]),
        d_expert=SIZES["moe_intermediate_size"],
        moe_top_k=SIZES["num_experts_per_tok"],
        n_shared_experts=(
            SIZES["shared_expert_intermediate_size"]
            // SIZES["moe_intermediate_size"]
        ),
        routed_scaling=float(SIZES["moe_routed_scaling_factor"]),
        moe_score="softmax",
        moe_renormalize=True,
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
