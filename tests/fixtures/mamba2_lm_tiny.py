"""A tiny state-space expert LM for the CPU tests: the block of the
`nemotron-3-nano-30b-a3b` configuration (`layer_types` of "mamba2" and
"mha", one bare layer, squared-ReLU experts, sigmoid routing with a
selection bias, attention that turns nothing) at sizes a test can
differentiate. The published blocks it stands for are `PATTERN`, which
the configuration's `zoo.py` would read as these four layers."""

from elasticdl_tpu.models.transformer_lm_zoo import (  # noqa: F401
    TransformerLM,
    dataset_fn,
    eval_metrics_fn,
    loss,
    optimizer,
)

PATTERN = "MEMEM*E"  # the cell's own seven blocks

SIZES = dict(
    vocab=61, d_model=32, n_layers=4,
    layer_types=("mamba2", "mamba2", "mamba2", "mha"), bare_layers=(2,),
    mlp="relu2",
    ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_conv=4,
    ssm_chunk=8, ssm_residual_blocks=52,
    n_heads=4, n_kv_heads=2, head_width=8, rope=False, norm_eps=1e-5,
    n_experts=16, held_experts=(4, 4), d_expert=12, moe_top_k=3,
    n_shared_experts=2, moe_score="sigmoid", moe_renormalize=True,
    routed_scaling=2.5, aux_weight=0.0, remat=True,
)


def custom_model(**overrides):
    return TransformerLM(**{**SIZES, **overrides})
