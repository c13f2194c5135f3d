"""FLOPs one training sample of the `deepseek-v2-lite` configuration
requires, from shapes alone (`harness/flops.py`'s rules: 3 x forward,
2 FLOPs a multiply-accumulate, elementwise work, norms and the router's
softmax not counted, recomputation not credited) — and the operations
and bytes of one grouped expert matmul, for `experts_roofline_pct`.

Routing is counted UNIFORM: a token takes 6 of 64 experts and 8 are
held here, so on average 6 x 8 / 64 = 0.75 routed experts a token work
on this chip. What the router really sent is in `expert_tokens` of the
`worker.window_stats` span; `mfu_pct` does not follow it."""


def attention_macs(sizes):
    """Latent attention's matrices, a token: queries projected whole,
    the joint key-value latent with the shared rotary key, the latent's
    up-projection to keys and values, the output."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rot = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rank, value = sizes["kv_lora_rank"], sizes["v_head_dim"]
    return (
        d * heads * (nope + rot)
        + d * (rank + rot)
        + rank * heads * (nope + value)
        + heads * value * d
    )


def flops_per_sample(sizes):
    d, s = sizes["hidden_size"], sizes["seq_len"]
    layers, dense = sizes["num_hidden_layers"], sizes["first_k_dense_replace"]
    expert = 3 * d * sizes["moe_intermediate_size"]  # one SwiGLU
    routed_here = (
        sizes["num_experts_per_tok"]
        * sizes["n_routed_experts"]  # held here
        / sizes["published"]["n_routed_experts"]
    )
    dense_layer = attention_macs(sizes) + 3 * d * sizes["intermediate_size"]
    expert_layer = (
        attention_macs(sizes)
        + d * sizes["published"]["n_routed_experts"]  # the router, all 64
        + sizes["n_shared_experts"] * expert
        + routed_here * expert
    )
    head = d * sizes["vocab_size"]
    # causal: a token attends to (s + 1) / 2 positions on average; a
    # head's scores are (nope + rot) wide, its values v_head_dim
    attended = layers * sizes["num_attention_heads"] * (
        sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
        + sizes["v_head_dim"]
    ) * (s + 1) / 2
    macs = dense * dense_layer + (layers - dense) * expert_layer + head + attended
    return 3 * 2 * macs * s


def expert_matmul_flops(rows, sizes):
    """One grouped matmul of the expert layer (any of a layer's twelve:
    gate, up and down, forward, recomputed, and each one's two
    backward products) over `rows` routed rows: rows x 2048 x 1408
    multiply-accumulates whichever way it is laid."""
    return 2.0 * rows * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def expert_matmul_bytes(rows, sizes, itemsize=2):
    """The least such a matmul moves: its rows in, its rows out, and
    every held expert's matrix once (bfloat16)."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    return itemsize * (rows * d + rows * f + sizes["n_routed_experts"] * d * f)
