"""SmallThinker-21BA3B-Instruct (PowerInfer; every layer an expert layer
of 64 gated-ReLU experts of 768, top-6 by a softmax over the chosen
logits, whose ROUTER READS THE LAYER'S INPUT ahead of the attention;
attention of 28 query heads on 4 key-value heads of 128, three layers
in four under a window of 4096 and turned at theta 1.5e6, the fourth
full and not turned at all; an untied head) at its published widths,
bf16 compute, one sequence of 16,384 tokens — the model-zoo module of
the `smallthinker-21b-a3b` configuration. The sizes, the cuts (depth,
the 8 of 64 experts this chip holds of an 8-chip expert-parallel layer,
the vocabulary as this chip's eighth), what was assumed beyond the
published `config.json` and the optimizer are in `config.json` beside
this file.

The block is the program's own (`models/transformer_lm.plain_forward`
with `layer_types` of "mha" and "swa", `early_router`, `rope_mixers`,
`mlp="reglu"` and the `swa_*` settings; the expert layer is
`parallel/moe.moe_topk_held`): this file holds sizes and the
optimizer's learning rate only.
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

MIXERS = {0: "mha", 1: "swa"}  # by a layer's `sliding_window_layout`


def custom_model(dtype=None, **overrides):
    """The configuration's model; `compare.py` asks for float32."""
    first, count = SIZES["held_layers"]
    windowed = SIZES["sliding_window_layout"][first:first + count]
    turned = SIZES["rope_layout"][first:first + count]
    if not (
        SIZES["moe_primary_router_apply_softmax"]
        and SIZES["norm_topk_prob"]
        and SIZES["rope_scaling"] is None
        and not SIZES["tie_word_embeddings"]
        and count == SIZES["num_hidden_layers"]
        and set(windowed) == set(MIXERS)
        # one answer a kind of layer: a kind turns, or it does not
        and len(set(zip(windowed, turned))) == len(set(windowed))
    ):
        raise ValueError(
            "config.json states a block this module does not build: "
            "gates a softmax over the chosen logits, no rope scaling, an "
            "untied head, layers full and windowed, each kind either "
            "turned or not"
        )
    sizes = dict(
        vocab=SIZES["vocab_size"],
        d_model=SIZES["hidden_size"],
        head_width=SIZES["head_dim"],
        n_heads=SIZES["num_attention_heads"],
        n_kv_heads=SIZES["num_key_value_heads"],
        n_layers=count,
        mlp="reglu",
        # the published layouts are kept whole; the layers held here
        # are `held_layers` = (first, count) of them
        layer_types=tuple(MIXERS[w] for w in windowed),
        rope_mixers=tuple(sorted(
            {MIXERS[w] for w, t in zip(windowed, turned) if t}
        )),
        rope_base=float(SIZES["rope_theta"]),
        swa_heads=SIZES["num_attention_heads"],
        swa_window=SIZES["sliding_window_size"],
        swa_rope_base=float(SIZES["rope_theta"]),
        norm_eps=SIZES["rms_norm_eps"],
        # the router's width is the published count; the experts whose
        # weights exist here are `held_experts`
        n_experts=SIZES["published"]["moe_num_primary_experts"],
        held_experts=tuple(SIZES["held_experts"]),
        d_expert=SIZES["moe_ffn_hidden_size"],
        moe_top_k=SIZES["moe_num_active_primary_experts"],
        n_shared_experts=0,
        early_router=True,
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
