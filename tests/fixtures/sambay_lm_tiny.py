"""A tiny decoder-hybrid-decoder LM for the CPU tests: the layers of
the `phi-4-mini-flash-reasoning` configuration (Mamba-1, differential
attention under a window and in full, gated memory units and cross
attention that read ONE layer's memory and keys and values, LayerNorms
with a bias, a dense gated MLP in every layer, tied embeddings, no
position encoding) at sizes a test can differentiate, UNCUT: all 12
published layers, so two "gmu" and two "cross" layers read the memory
and the keys and values."""

from elasticdl_tpu.models.transformer_lm_zoo import (  # noqa: F401
    TransformerLM,
    dataset_fn,
    eval_metrics_fn,
    loss,
    optimizer,
    sambay_layers,
)

PUBLISHED_LAYERS = 12

SIZES = dict(
    vocab=61, d_model=32, d_ff=48, mlp="swiglu", norm="layer", norm_eps=1e-5,
    tie_embeddings=True, rope=False,
    n_heads=4, n_kv_heads=2, head_width=8, attn_bias=True,
    diff_attention=True, swa_heads=4, swa_window=8,
    ssm1_inner=64, ssm1_state=4, ssm1_conv=4, ssm1_dt_rank=2,
    remat=True,
)


def custom_model(held=None, **overrides):
    layers = sambay_layers(PUBLISHED_LAYERS, held)
    return TransformerLM(**{
        **SIZES, **layers, "n_layers": len(layers["layer_types"]), **overrides
    })
