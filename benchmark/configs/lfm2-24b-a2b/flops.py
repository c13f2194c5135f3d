"""FLOPs one training sample of the `lfm2-24b-a2b` configuration
requires, from shapes alone (`harness/flops.py`'s rules: 3 x forward,
2 FLOPs a multiply-accumulate, elementwise work, norms and the router's
sigmoid not counted, recomputation not credited) — and the operations
and bytes of one grouped matmul of the expert layer, for
`sparse_experts_roofline_pct`.

Routing is counted UNIFORM: a token takes 4 of 64 experts and 8 are
held here, so on average 4 x 8 / 64 = 0.5 routed experts a token work
on this chip. What the router really sent is in `expert_tokens` of the
`worker.window_stats` span; `mfu_pct` does not follow it.

The short convolution's taps (3 multiply-accumulates a channel, a
token) and its two gatings are elementwise work and are not counted:
they are 6,144 of a conv mixer's 16.8 M a token."""


def conv_mixer_macs(sizes):
    """The short convolution's two matrices, a token: in (d x 3d) and
    out (d x d)."""
    d = sizes["hidden_size"]
    return d * 3 * d + d * d


def attention_macs(sizes):
    """Grouped-query attention's matrices, a token."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    hd = d // heads
    return 2 * d * heads * hd + 2 * d * sizes["num_key_value_heads"] * hd


def attention_score_macs(sizes):
    """Causal scores and their product with the values, a token: it
    attends to (s + 1) / 2 positions on average, in every query head."""
    d = sizes["hidden_size"]
    return 2 * d * (sizes["seq_len"] + 1) / 2


def flops_per_sample(sizes):
    d, s = sizes["hidden_size"], sizes["seq_len"]
    expert = 3 * d * sizes["moe_intermediate_size"]  # one SwiGLU
    routed_here = (
        sizes["num_experts_per_tok"]
        * sizes["num_experts"]  # held here
        / sizes["published"]["num_experts"]
    )
    first, count = sizes["held_layers"]
    macs = d * sizes["vocab_size"]  # the tied head
    for i, kind in enumerate(sizes["layer_types"][first:first + count]):
        if kind == "conv":
            macs += conv_mixer_macs(sizes)
        else:
            macs += attention_macs(sizes) + attention_score_macs(sizes)
        if i < sizes["num_dense_layers"]:
            macs += 3 * d * sizes["intermediate_size"]
        else:
            macs += (
                d * sizes["published"]["num_experts"]  # the router, all 64
                + routed_here * expert
            )
    return 3 * 2 * macs * s


def expert_matmul_flops(rows, sizes):
    """One grouped matmul of the expert layer (any of a layer's twelve:
    gate, up and down, forward, recomputed, and each one's two
    backward products) over `rows` routed rows: rows x 2048 x 1536
    multiply-accumulates whichever way it is laid."""
    return 2.0 * rows * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def expert_matmul_bytes(rows, sizes, itemsize=2):
    """The least such a matmul moves: its rows in, its rows out, and
    every held expert's matrix once (bfloat16)."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    return itemsize * (rows * d + rows * f + sizes["num_experts"] * d * f)
