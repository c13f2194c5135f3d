"""The DeepSeek-V2 block at a tiny size, as a model-zoo module: latent
attention, one dense layer, then two layers of 16 routed experts of
which this program holds four (4..7), top-3, two shared experts."""

from elasticdl_tpu.models.transformer_lm import YarnScaling
from elasticdl_tpu.models.transformer_lm_zoo import (  # noqa: F401
    TransformerLM,
    dataset_fn,
    eval_metrics_fn,
    loss,
    optimizer,
)

SIZES = dict(
    vocab=64, d_model=48, n_heads=4, d_ff=80, n_layers=3, n_dense_layers=1,
    mlp="swiglu", attention="mla", kv_lora_rank=24, qk_nope_dim=8,
    qk_rope_dim=8, v_head_dim=12,
    rope_yarn=YarnScaling(40, 32, 1, 4096, 0.707, 0.707),
    n_experts=16, held_experts=(4, 4), d_expert=20, moe_top_k=3,
    n_shared_experts=2, aux_weight=0.001, remat=True,
)


def custom_model(**overrides):
    return TransformerLM(**{**SIZES, **overrides})
