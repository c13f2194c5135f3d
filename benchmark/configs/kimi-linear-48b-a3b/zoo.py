"""Kimi-Linear-48B-A3B (Moonshot AI; Kimi Delta Attention three layers
in four, latent attention without rotary in the fourth, one dense
layer, then layers of 256 routed and 1 shared experts, top-8 by
sigmoid scores) at its published widths, bf16 compute, sequences of
2048 — the model-zoo module of the `kimi-linear-48b-a3b`
configuration. The sizes, the three cuts (depth, the 8 of 256 experts
this chip holds of a 32-chip expert-parallel layer, the vocabulary as
this chip's eighth), what was assumed beyond the published
`config.json` and the optimizer are in `config.json` beside this file.

The block is the program's own (`models/transformer_lm.plain_forward`
with `layer_types`, `moe_score="sigmoid"` and `mla_rope=False` set;
the recurrence is `ops/kda.kda_chunked`, the expert layer
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


def custom_model(dtype=None, **overrides):
    """The configuration's model; `compare.py` asks for float32."""
    linear = SIZES["linear_attn_config"]
    if not (
        SIZES["hidden_act"] == "silu"
        and not SIZES["tie_word_embeddings"]
        and SIZES["q_lora_rank"] is None
        and SIZES["rope_scaling"] is None
        and SIZES["mla_use_nope"]
        and SIZES["num_key_value_heads"] == SIZES["num_attention_heads"]
        and (SIZES["moe_router_activation_func"], SIZES["moe_renormalize"],
             SIZES["num_expert_group"], SIZES["topk_group"],
             SIZES["moe_layer_freq"], SIZES["num_nextn_predict_layers"])
        == ("sigmoid", True, 1, 1, 1, 0)
        and len(SIZES["layer_types"]) == SIZES["num_hidden_layers"]
    ):
        raise ValueError(
            "config.json states a block this module does not build: SiLU "
            "gates, untied head, latent attention without a query latent "
            "and without rotation, sigmoid scores with ungrouped top-k and "
            "renormalised gates, an expert layer at every layer after the "
            "dense ones, no prediction modules, a mixer named for every layer"
        )
    sizes = dict(
        vocab=SIZES["vocab_size"],
        d_model=SIZES["hidden_size"],
        n_heads=SIZES["num_attention_heads"],
        d_ff=SIZES["intermediate_size"],
        n_layers=SIZES["num_hidden_layers"],
        n_dense_layers=SIZES["first_k_dense_replace"],
        mlp="swiglu",
        layer_types=tuple(SIZES["layer_types"]),
        kda_heads=linear["num_heads"],
        kda_head_dim=linear["head_dim"],
        kda_conv=linear["short_conv_kernel_size"],
        kda_chunk=SIZES["kda_chunk"],
        kv_lora_rank=SIZES["kv_lora_rank"],
        qk_nope_dim=SIZES["qk_nope_head_dim"],
        qk_rope_dim=SIZES["qk_rope_head_dim"],
        v_head_dim=SIZES["v_head_dim"],
        mla_rope=not SIZES["mla_use_nope"],
        norm_eps=SIZES["rms_norm_eps"],
        # the router's width is the published count; the experts whose
        # weights exist here are `held_experts`
        n_experts=SIZES["published"]["num_experts"],
        held_experts=tuple(SIZES["held_experts"]),
        d_expert=SIZES["moe_intermediate_size"],
        moe_top_k=SIZES["num_experts_per_token"],
        n_shared_experts=SIZES["num_shared_experts"],
        routed_scaling=float(SIZES["routed_scaling_factor"]),
        moe_score=SIZES["moe_router_activation_func"],
        moe_renormalize=SIZES["moe_renormalize"],
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
