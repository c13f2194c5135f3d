"""The Kimi-Linear block at a tiny size, as a model-zoo module: Kimi
Delta Attention in layers 1, 2, 3 and 5, latent attention without
rotation in layer 4, one dense layer, then four layers of 16 routed
experts of which this program holds four (4..7), top-3 by sigmoid
scores with renormalised gates x 2.446, one shared expert. Chunks of 16
tokens, so a sequence of 24 is one chunk and a half."""

from elasticdl_tpu.models.transformer_lm_zoo import (  # noqa: F401
    TransformerLM,
    dataset_fn,
    eval_metrics_fn,
    loss,
    optimizer,
)

SIZES = dict(
    vocab=64, d_model=48, n_heads=4, d_ff=80, n_layers=5, n_dense_layers=1,
    mlp="swiglu", layer_types=("kda", "kda", "kda", "mla", "kda"),
    kda_heads=4, kda_head_dim=16, kda_conv=4, kda_chunk=16,
    kv_lora_rank=24, qk_nope_dim=8, qk_rope_dim=8, v_head_dim=12,
    mla_rope=False, norm_eps=1e-5,
    n_experts=16, held_experts=(4, 4), d_expert=20, moe_top_k=3,
    n_shared_experts=1, routed_scaling=2.446, moe_score="sigmoid",
    moe_renormalize=True, aux_weight=0.0, remat=True,
)
# what `benchmark/configs/kimi-linear-48b-a3b/reference.py` is given
REFERENCE_SIZES = dict(
    heads=4, kv_lora_rank=24, qk_nope=8, qk_rope=8, v_head=12, kda_heads=4,
    kda_head_dim=16, eps=1e-5, top_k=3, held=(4, 4), routed_scaling=2.446,
)


def custom_model(**overrides):
    return TransformerLM(**{**SIZES, **overrides})
