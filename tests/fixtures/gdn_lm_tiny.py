"""The Qwen3-Next block at a tiny size, as a model-zoo module: three
Gated DeltaNet layers (2 key heads under 4 value heads of 16, one decay
a head, 4 taps, chunks of 16) and one gated attention layer (4 query
heads of 32 over 1 key-value head, normed queries and keys, 8 columns
turned at base 1e7, a sigmoid gate per output channel), each of the
four with 16 routed experts of which this program holds four (4..7),
top-3 by softmax scores, renormalised, under a balance term of weight
0.01, and one shared expert behind a sigmoid gate; an untied head."""

from elasticdl_tpu.models.transformer_lm_zoo import (  # noqa: F401
    TransformerLM,
    dataset_fn,
    eval_metrics_fn,
    loss,
    optimizer,
)

SIZES = dict(
    vocab=64, d_model=64, n_layers=4, mlp="swiglu",
    layer_types=("gdn", "gdn", "gdn", "mha"),
    gdn_key_heads=2, gdn_value_heads=4, gdn_head_dim=16, gdn_conv=4,
    kda_chunk=16,
    n_heads=4, n_kv_heads=1, head_width=32, qk_norm=True,
    attn_channel_gate=True, rope_base=1e7, rope_dim=8, norm_eps=1e-6,
    n_experts=16, held_experts=(4, 4), d_expert=24, moe_top_k=3,
    n_shared_experts=1, shared_expert_gate=True, moe_score="softmax",
    moe_renormalize=True, aux_weight=0.01, remat=True,
)
# what `benchmark/configs/qwen3-next-80b-a3b/reference.py` is given
REFERENCE_SIZES = dict(
    gdn_key_heads=2, gdn_value_heads=4, gdn_head_dim=16,
    heads=4, kv_heads=1, head_dim=32, rope_base=1e7, rope_dim=8,
    eps=1e-6, top_k=3, aux_weight=0.01, held=(4, 4),
    kinds=("linear", "linear", "linear", "full"),
)


def custom_model(**overrides):
    return TransformerLM(**{**SIZES, **overrides})
