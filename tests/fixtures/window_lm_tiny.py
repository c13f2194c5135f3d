"""The Laguna-XS.2 block at a tiny size, as a model-zoo module: a
full-attention layer (6 query heads of 16 over 2 key-value heads, the
first 8 columns of a head turned by YaRN's blended frequencies with the
attention factor on cosine and sine) with the dense SwiGLU, three
window-8 layers (8 query heads, the whole head turned at base 10000)
and one more full layer, each of the four with 8 routed experts of
which this program holds four (2..5), top-2 by softmax scores,
renormalised, x 2.5, and one shared expert; a per-head sigmoid gate on
every attention output; an untied head."""

import math

from elasticdl_tpu.models.transformer_lm import YarnScaling
from elasticdl_tpu.models.transformer_lm_zoo import (  # noqa: F401
    TransformerLM,
    dataset_fn,
    eval_metrics_fn,
    loss,
    optimizer,
)

YARN = dict(factor=4.0, beta_fast=8.0, beta_slow=1.0, original_length=16)
ATTENTION_FACTOR = 0.1 * math.log(YARN["factor"]) + 1.0
SIZES = dict(
    vocab=64, d_model=64, n_heads=6, n_kv_heads=2, head_width=16,
    attn_gate=True, d_ff=96, n_layers=5, n_dense_layers=1, mlp="swiglu",
    layer_types=("mha", "swa", "swa", "swa", "mha"),
    rope_base=500.0, rope_dim=8, rope_factor=ATTENTION_FACTOR,
    rope_yarn=YarnScaling(
        YARN["factor"], YARN["beta_fast"], YARN["beta_slow"],
        YARN["original_length"], 1.0, 0.0,
    ),
    swa_heads=8, swa_window=8, swa_rope_base=10000.0, norm_eps=1e-6,
    n_experts=8, held_experts=(2, 4), d_expert=24, moe_top_k=2,
    n_shared_experts=1, routed_scaling=2.5, moe_score="softmax",
    moe_renormalize=True, aux_weight=0.0, remat=True,
)
# what `benchmark/configs/laguna-xs2/reference.py` is given
REFERENCE_SIZES = dict(
    kv_heads=2, head_dim=16, eps=1e-6, top_k=2, held=(2, 4),
    routed_scaling=2.5,
    full=dict(
        heads=6, window=None, rope_base=500.0, rope_dim=8,
        attention_factor=ATTENTION_FACTOR, yarn=YARN,
    ),
    sliding=dict(
        heads=8, window=8, rope_base=10000.0, rope_dim=16,
        attention_factor=1.0, yarn=None,
    ),
)


def custom_model(**overrides):
    return TransformerLM(**{**SIZES, **overrides})
