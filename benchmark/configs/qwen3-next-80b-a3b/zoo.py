"""Qwen3-Next-80B-A3B (Qwen; Gated DeltaNet three layers in four: 16
key heads under 32 value heads of 128, one decay a head, a convolution
of 4 taps, the output normed and gated by SiLU(z); gated softmax
attention in the fourth: 16 query heads of 256 over 2 key-value heads,
normed queries and keys, 64 columns turned, a sigmoid gate per output
channel; every layer 512 routed experts of 512, top-10 by softmax
scores, renormalised, and one shared expert behind a sigmoid gate; an
untied head) at its published widths, bf16 compute, sequences of 8192 —
the model-zoo module of the `qwen3-next-80b-a3b` configuration. The
sizes, the cuts (depth, the 16 of 512 experts this chip holds of a
32-chip expert-parallel layer, the vocabulary as this chip's eighth),
what was assumed beyond the published `config.json` (a balance term
among it: `balance_term`) and the optimizer are in `config.json` beside
this file.

The block is the program's own (`models/transformer_lm.plain_forward`
with `layer_types` of "gdn" and "mha", the `gdn_*` settings,
`head_width`, `n_kv_heads`, `qk_norm`, `rope_dim`, `attn_channel_gate`
and `shared_expert_gate`; the scan is `ops/kda.kda_chunked` under one
decay a head, the expert layer `parallel/moe.moe_topk_held`): this file
holds sizes and the optimizer's learning rate only.
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

MIXERS = {"linear_attention": "gdn", "full_attention": "mha"}


def custom_model(dtype=None, **overrides):
    """The configuration's model; `compare.py` asks for float32."""
    first, count = SIZES["held_layers"]
    kinds = SIZES["layer_types"][first:first + count]
    interval = SIZES["full_attention_interval"]
    if not (
        SIZES["model_type"] == "qwen3_next"
        and SIZES["hidden_act"] == "silu"
        and not SIZES["tie_word_embeddings"]
        and not SIZES["use_sliding_window"]
        and SIZES["rope_scaling"] is None
        and SIZES["norm_topk_prob"] is True
        and SIZES["decoder_sparse_step"] == 1
        and SIZES["mlp_only_layers"] == []
        and SIZES["linear_key_head_dim"] == SIZES["linear_value_head_dim"]
        and count == SIZES["num_hidden_layers"]
        and set(kinds) == set(MIXERS)
        and all(
            kind == ("full_attention" if (i + 1) % interval == 0
                     else "linear_attention")
            for i, kind in enumerate(SIZES["layer_types"])
        )
    ):
        raise ValueError(
            "config.json states a block this module does not build: "
            "layers of 'linear_attention' (Gated DeltaNet, key and value "
            "heads of one width) and 'full_attention' every "
            "full_attention_interval-th, every layer an expert layer "
            "(decoder_sparse_step 1, no mlp_only_layers) with renormalised "
            "top-k gates, SiLU, the plain rotation, no window, an untied "
            "head"
        )
    sizes = dict(
        vocab=SIZES["vocab_size"],
        d_model=SIZES["hidden_size"],
        n_layers=count,
        mlp="swiglu",
        # the published pattern is kept whole; the layers held here are
        # `held_layers` = (first, count) of it
        layer_types=tuple(MIXERS[kind] for kind in kinds),
        gdn_key_heads=SIZES["linear_num_key_heads"],
        gdn_value_heads=SIZES["linear_num_value_heads"],
        gdn_head_dim=SIZES["linear_key_head_dim"],
        gdn_conv=SIZES["linear_conv_kernel_dim"],
        kda_chunk=SIZES["kda_chunk"],
        n_heads=SIZES["num_attention_heads"],
        n_kv_heads=SIZES["num_key_value_heads"],
        head_width=SIZES["head_dim"],
        qk_norm=True,
        attn_channel_gate=True,
        rope_base=float(SIZES["rope_theta"]),
        rope_dim=int(SIZES["head_dim"] * SIZES["partial_rotary_factor"]),
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
        shared_expert_gate=True,
        moe_score="softmax",
        moe_renormalize=True,
        # assumed, no key of the published row (`config.json`: assumed)
        aux_weight=SIZES["balance_term"]["weight_a_layer"],
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
