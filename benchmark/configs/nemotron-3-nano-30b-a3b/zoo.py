"""Nemotron-3-Nano-30B-A3B (NVIDIA; `model_type` `nemotron_h`: 52
blocks that are EACH a Mamba-2 mixer `M`, an expert feed-forward part
`E` or an attention mixer `*`, never two; Mamba-2 of 64 heads of 64
over a state of 128 in 8 groups, a convolution of 4 taps with a bias,
the output gated by SiLU(z) and normed over groups of 512; attention of
32 heads of 128 over 2 key-value heads that turns nothing; 128 routed
squared-ReLU experts of 1856, top-6 by sigmoid scores, renormalised,
x 2.5, and one shared expert of 3712; an untied head) at its published
widths, bf16 compute, sequences of 8192 — the model-zoo module of the
`nemotron-3-nano-30b-a3b` configuration. The sizes, the cuts (published
blocks 0-6, the 8 of 128 experts this chip holds of a 16-chip
expert-parallel layer, the vocabulary as this chip's eighth), what was
assumed beyond the published `config.json` and the optimizer are in
`config.json` beside this file.

The block is the program's own (`models/transformer_lm.plain_forward`
with `layer_types` of "mamba2" and "mha", `bare_layers`, the `ssm_*`
settings, `mlp` "relu2", `rope` False; the scan is
`ops/ssd.ssd_chunked`, the expert layer `parallel/moe.moe_topk_held`):
this file holds sizes, the walk from the published pattern string to
the program's layers and the optimizer's learning rate only.
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

MIXERS = {"M": "mamba2", "*": "mha"}


def layers_of(blocks):
    """The program's layers from published blocks: a mixer block and
    the `E` block behind it are one layer (h + mixer(norm h), then h +
    experts(norm h), which is what the two blocks compute), a mixer
    block that no `E` follows is a bare layer -> (mixers, the bare
    layers' indexes)."""
    mixers, bare, at = [], [], 0
    while at < len(blocks):
        if blocks[at] not in MIXERS:
            raise ValueError(
                f"block {at} of {blocks!r} is {blocks[at]!r} behind no "
                "mixer block: this module builds mixer blocks, each alone "
                "or with ONE expert block behind it"
            )
        mixers.append(MIXERS[blocks[at]])
        if blocks[at + 1:at + 2] == "E":
            at += 2
        else:
            bare.append(len(mixers) - 1)
            at += 1
    return tuple(mixers), tuple(bare)


def custom_model(dtype=None, **overrides):
    """The configuration's model; `compare.py` asks for float32."""
    first, count = SIZES["held_layers"]
    blocks = SIZES["hybrid_override_pattern"][first:first + count]
    inner = SIZES["mamba_num_heads"] * SIZES["mamba_head_dim"]
    if not (
        SIZES["model_type"] == "nemotron_h"
        and SIZES["mlp_hidden_act"] == "relu2"
        and SIZES["mamba_hidden_act"] == "silu"
        and not SIZES["tie_word_embeddings"]
        and SIZES["norm_topk_prob"] is True
        and SIZES["use_conv_bias"] is True
        and not (SIZES["use_bias"] or SIZES["mlp_bias"]
                 or SIZES["attention_bias"] or SIZES["mamba_proj_bias"])
        and SIZES["n_group"] == SIZES["topk_group"] == 1
        and SIZES["sliding_window"] is None
        and count == SIZES["num_hidden_layers"]
        and len(SIZES["hybrid_override_pattern"])
        == SIZES["published"]["num_hidden_layers"]
        and inner % SIZES["n_groups"] == 0
        and SIZES["moe_shared_expert_intermediate_size"]
        % SIZES["moe_intermediate_size"] == 0
    ):
        raise ValueError(
            "config.json states a block this module does not build: "
            "blocks of 'M' (Mamba-2, a convolution with a bias, SiLU), "
            "'*' (attention, no bias) and 'E' (squared-ReLU experts, "
            "renormalised top-k in ONE group, shared experts of a "
            "multiple of the routed width), no bias elsewhere, no window, "
            "an untied head"
        )
    mixers, bare = layers_of(blocks)
    sizes = dict(
        vocab=SIZES["vocab_size"],
        d_model=SIZES["hidden_size"],
        n_layers=len(mixers),
        mlp="relu2",
        # the published pattern is kept whole; the blocks held here are
        # `held_layers` = (first, count) of it
        layer_types=mixers,
        bare_layers=bare,
        ssm_heads=SIZES["mamba_num_heads"],
        ssm_head_dim=SIZES["mamba_head_dim"],
        ssm_state=SIZES["ssm_state_size"],
        ssm_groups=SIZES["n_groups"],
        ssm_conv=SIZES["conv_kernel"],
        ssm_chunk=SIZES["chunk_size"],
        # `rescale_prenorm_residual`, by the published depth
        ssm_residual_blocks=(
            SIZES["published"]["num_hidden_layers"]
            if SIZES["rescale_prenorm_residual"] else 0
        ),
        n_heads=SIZES["num_attention_heads"],
        n_kv_heads=SIZES["num_key_value_heads"],
        head_width=SIZES["head_dim"],
        # assumed: the family's attention block turns nothing
        # (`config.json`: assumed); `rope_theta` is read by no layer
        rope=False,
        norm_eps=SIZES["norm_eps"],
        # the router's width is the published count; the experts whose
        # weights exist here are `held_experts`
        n_experts=SIZES["published"]["n_routed_experts"],
        held_experts=tuple(SIZES["held_experts"]),
        d_expert=SIZES["moe_intermediate_size"],
        moe_top_k=SIZES["num_experts_per_tok"],
        n_shared_experts=SIZES["n_shared_experts"] * (
            SIZES["moe_shared_expert_intermediate_size"]
            // SIZES["moe_intermediate_size"]
        ),
        moe_score="sigmoid",
        moe_renormalize=True,
        routed_scaling=SIZES["routed_scaling_factor"],
        aux_weight=0.0,  # assumed: no balance term (`config.json`)
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
