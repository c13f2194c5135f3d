"""The SmallThinker block at a tiny size, as a model-zoo module: one
full-attention layer whose queries and keys do not turn, then three
window-8 layers whose whole head turns (7 query heads of 16 over 1
key-value head: a group of 7, as the model's 28 over 4), every layer
an expert layer of 16 gated-ReLU experts of which this program holds
four (4..7), top-3 by a softmax over the chosen logits, THE ROUTER ON
THE LAYER'S INPUT ahead of the attention; no shared expert, no dense
layer; an untied head."""

from elasticdl_tpu.models.transformer_lm_zoo import (  # noqa: F401
    TransformerLM,
    dataset_fn,
    eval_metrics_fn,
    loss,
    optimizer,
)

SIZES = dict(
    vocab=64, d_model=64, n_heads=7, n_kv_heads=1, head_width=16,
    n_layers=4, mlp="reglu", layer_types=("mha", "swa", "swa", "swa"),
    rope_mixers=("swa",), rope_base=1500000.0, swa_heads=7, swa_window=8,
    swa_rope_base=1500000.0, norm_eps=1e-6, n_experts=16,
    held_experts=(4, 4), d_expert=24, moe_top_k=3, n_shared_experts=0,
    early_router=True, moe_score="softmax", moe_renormalize=True,
    aux_weight=0.0, remat=True,
)
# what `benchmark/configs/smallthinker-21b-a3b/reference.py` is given
REFERENCE_SIZES = dict(
    heads=7, kv_heads=1, head_dim=16, rope_base=1500000.0, eps=1e-6,
    top_k=3, held=(4, 4), router_reads="input",
    kinds=("full", "sliding", "sliding", "sliding"),
    full=dict(window=None, turns=False),
    sliding=dict(window=8, turns=True),
)


def custom_model(**overrides):
    return TransformerLM(**{**SIZES, **overrides})
