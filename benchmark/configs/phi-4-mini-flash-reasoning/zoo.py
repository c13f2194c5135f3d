"""Phi-4-mini-flash-reasoning (Microsoft; `model_type` `phi4flash`, the
SambaY decoder-hybrid-decoder: 32 layers, the first half Mamba-1 and
differential attention under a window of 512 in turn, layer 16 a
Mamba-1 whose scan output is the MEMORY, layer 17 full differential
attention whose keys and values are SHARED, and behind them gated
memory units and differential cross attention that read the two; 40
heads of 64 in 20 pairs over 20 key-value heads, a gated MLP of 10,240
in every layer, LayerNorms with a bias, tied embeddings, no position
encoding) at its published widths, bf16 compute — the model-zoo module
of the `phi-4-mini-flash-reasoning` configuration. The sizes, the cuts
(published layers 15-19, the vocabulary as this chip's eighth), what
was assumed beyond the published `config.json` and the optimizer are in
`config.json` beside this file.

The layers are the program's own (`models/transformer_lm.plain_forward`
with `layer_types` of "swa", "mamba1", "mha", "gmu" and "cross",
`diff_attention`, `norm` "layer", `rope` False; the scan is
`ops/selective_scan.selective_scan`), and which published layer is
which is `transformer_lm_zoo.sambay_layers`' walk: this file holds
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
    sambay_layers,
)

with open(os.path.join(_HERE, "config.json")) as _f:
    SIZES = json.load(_f)

probe.start_if_worker()  # inert outside a benchmarked worker


def custom_model(dtype=None, **overrides):
    """The configuration's model; `compare.py` asks for float32."""
    first, count = SIZES["held_layers"]
    assumed = SIZES["assumed_sizes"]
    if not (
        SIZES["model_type"] == "phi4flash"
        and SIZES["hidden_act"] == "silu"
        and SIZES["tie_word_embeddings"] is True
        and SIZES["mb_per_layer"] == 2
        and not (SIZES["mlp_bias"] or SIZES["lm_head_bias"])
        and not (SIZES["embd_pdrop"] or SIZES["resid_pdrop"])
        and count == SIZES["num_hidden_layers"]
        and SIZES["hidden_size"] % SIZES["num_attention_heads"] == 0
    ):
        raise ValueError(
            "config.json states a layer this module does not build: a "
            "state-space layer every second layer of the first half, "
            "SiLU-gated MLPs with no bias, tied embeddings with no bias "
            "on the head, no dropout"
        )
    hidden = SIZES["hidden_size"]
    sizes = dict(
        vocab=SIZES["vocab_size"],
        d_model=hidden,
        d_ff=SIZES["intermediate_size"],
        mlp="swiglu",
        norm="layer",
        norm_eps=SIZES["layer_norm_eps"],
        tie_embeddings=True,
        rope=False,  # no position encoding anywhere
        # which published layer is which; the layers held here are
        # `held_layers` = (first, count) of the published 32
        **sambay_layers(SIZES["published"]["num_hidden_layers"], (first, count)),
        n_layers=count,
        n_heads=SIZES["num_attention_heads"],
        n_kv_heads=SIZES["num_key_value_heads"],
        swa_heads=SIZES["num_attention_heads"],
        swa_window=SIZES["sliding_window"],
        attn_bias=True,
        diff_attention=True,
        ssm1_inner=assumed["mamba_expand"] * hidden,
        ssm1_state=assumed["mamba_d_state"],
        ssm1_conv=assumed["mamba_d_conv"],
        ssm1_dt_rank=hidden // assumed["mamba_dt_rank_divisor"],
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
