"""DeepSeek-V2-Lite (DeepSeek-AI; latent attention, one dense layer,
then layers of 64 routed and 2 shared experts, top-6) at its published
widths, bf16 compute, sequences of 2048 — the model-zoo module of the
`deepseek-v2-lite` configuration. The sizes, the three cuts (depth, the
8 of 64 experts this chip holds of an eight-chip expert-parallel
layer, the vocabulary as this chip's eighth), what was assumed beyond
the published `config.json` and the optimizer are in `config.json`
beside this file.

The block is the program's own (`models/transformer_lm.plain_forward`
with `attention="mla"`, `n_dense_layers`, `moe_top_k` and
`held_experts` set; the expert layer is
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
if float(os.environ.get(probe.ENV_TRACE_SECS) or 0) > 0:
    # a traced run: the worker writes the window program's op_names,
    # which the scope readers join the device trace to (an untraced run
    # asks for nothing, and the worker writes nothing)
    os.environ.setdefault("EDL_HLO_SCOPES", "1")


def custom_model(dtype=None, **overrides):
    """The configuration's model; `compare.py` asks for float32."""
    scaling = SIZES["rope_scaling"]
    if not (
        SIZES["hidden_act"] == "silu"
        and not SIZES["tie_word_embeddings"]
        and not SIZES["attention_bias"]
        and SIZES["q_lora_rank"] is None
        and SIZES["num_key_value_heads"] == SIZES["num_attention_heads"]
        and (SIZES["scoring_func"], SIZES["topk_method"], SIZES["n_group"],
             SIZES["norm_topk_prob"], SIZES["seq_aux"], SIZES["moe_layer_freq"])
        == ("softmax", "greedy", 1, False, True, 1)
        and scaling["type"] == "yarn"
    ):
        raise ValueError(
            "config.json states a block this module does not build: SiLU "
            "gates, untied head, no bias, latent attention without a query "
            "latent, softmax scores with greedy ungrouped top-k and no "
            "renormalisation, the sequence-wise balance term, an expert "
            "layer at every layer after the dense ones, YaRN"
        )
    sizes = dict(
        vocab=SIZES["vocab_size"],
        d_model=SIZES["hidden_size"],
        n_heads=SIZES["num_attention_heads"],
        d_ff=SIZES["intermediate_size"],
        n_layers=SIZES["num_hidden_layers"],
        n_dense_layers=SIZES["first_k_dense_replace"],
        mlp="swiglu",
        attention="mla",
        kv_lora_rank=SIZES["kv_lora_rank"],
        qk_nope_dim=SIZES["qk_nope_head_dim"],
        qk_rope_dim=SIZES["qk_rope_head_dim"],
        v_head_dim=SIZES["v_head_dim"],
        rope_base=float(SIZES["rope_theta"]),
        rope_yarn=YarnScaling(
            factor=scaling["factor"],
            beta_fast=scaling["beta_fast"],
            beta_slow=scaling["beta_slow"],
            original_length=scaling["original_max_position_embeddings"],
            mscale=scaling["mscale"],
            mscale_all_dim=scaling["mscale_all_dim"],
        ),
        norm_eps=SIZES["rms_norm_eps"],
        # the router's width is the published count; the experts whose
        # weights exist here are `held_experts`
        n_experts=SIZES["published"]["n_routed_experts"],
        held_experts=tuple(SIZES["held_experts"]),
        d_expert=SIZES["moe_intermediate_size"],
        moe_top_k=SIZES["num_experts_per_tok"],
        n_shared_experts=SIZES["n_shared_experts"],
        routed_scaling=float(SIZES["routed_scaling_factor"]),
        aux_weight=SIZES["aux_loss_alpha"],
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
