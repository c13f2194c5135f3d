"""The LFM2 block at a tiny size, as a model-zoo module: one dense
layer with the double-gated short convolution, then grouped-query
attention (4 query heads over 2 key-value heads of 12, queries and keys
normed) and three more convolution layers, each with 16 routed experts
of which this program holds four (4..7), top-3 by sigmoid scores with
renormalised gates, NO shared expert; the head is the embedding
transposed."""

from elasticdl_tpu.models.transformer_lm_zoo import (  # noqa: F401
    TransformerLM,
    dataset_fn,
    eval_metrics_fn,
    loss,
    optimizer,
)

SIZES = dict(
    vocab=64, d_model=48, n_heads=4, n_kv_heads=2, qk_norm=True, d_ff=80,
    n_layers=5, n_dense_layers=1, mlp="swiglu",
    layer_types=("conv", "mha", "conv", "conv", "conv"), conv_taps=3,
    rope_base=1e6, norm_eps=1e-5, tie_embeddings=True,
    n_experts=16, held_experts=(4, 4), d_expert=20, moe_top_k=3,
    n_shared_experts=0, routed_scaling=1.0, moe_score="sigmoid",
    moe_renormalize=True, aux_weight=0.0, remat=True,
)
# what `benchmark/configs/lfm2-24b-a2b/reference.py` is given
REFERENCE_SIZES = dict(
    heads=4, kv_heads=2, head_dim=12, eps=1e-5, rope_base=1e6, top_k=3,
    held=(4, 4), routed_scaling=1.0,
)


def custom_model(**overrides):
    return TransformerLM(**{**SIZES, **overrides})
