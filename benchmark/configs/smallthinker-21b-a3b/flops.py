"""FLOPs one training sample of the `smallthinker-21b-a3b` configuration
requires, from shapes alone (`harness/flops.py`'s rules: 3 x forward, 2
FLOPs a multiply-accumulate, elementwise work, norms, the rotation and
the router's softmax not counted, recomputation not credited) — and the
operations and bytes of the banded attention kernels, for
`band4k_roofline_pct`, and of the held experts' grouped matmuls, for
`reglu_experts_roofline_pct`.

Attention's scores and their product with the values are counted over
the pairs the mask leaves VISIBLE: the causal triangle's s (s + 1) / 2
on the full layer, the band's w (w + 1) / 2 + (s - w) w on a sliding one
(58,722,304 of 134,225,920 at 16,384 tokens and a window of 4096:
43.8 %), whatever tiles a kernel runs to cover them.

Routing is counted UNIFORM: a token takes 6 of 64 experts and 8 are
held here, so on average 6 x 8 / 64 = 0.75 routed experts a token work
on this chip. What the router really sent is in `expert_tokens` of the
`worker.window_stats` span; `mfu_pct` does not follow it."""


def visible_pairs(length, window=None):
    """(query, key) pairs a sequence of `length` sees: the triangle's,
    or under `window` the band's."""
    w = length if window is None else min(window, length)
    return w * (w + 1) // 2 + (length - w) * w


def attention_macs(sizes):
    """One attention layer's matrices, a token: q and o of the query
    heads, k and v of the key-value heads."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    return (
        2 * d * sizes["num_attention_heads"] * hd
        + 2 * d * sizes["num_key_value_heads"] * hd
    )


def score_macs(sizes, window=None):
    """Scores and their product with the values, a SEQUENCE: two
    products of head_dim a visible pair and query head."""
    return 2 * sizes["num_attention_heads"] * sizes["head_dim"] * (
        visible_pairs(sizes["seq_len"], window)
    )


def flops_per_sample(sizes):
    d, s = sizes["hidden_size"], sizes["seq_len"]
    published = sizes["published"]["moe_num_primary_experts"]
    expert = 3 * d * sizes["moe_ffn_hidden_size"]  # one gated-ReLU expert
    routed_here = (
        sizes["moe_num_active_primary_experts"]
        * sizes["moe_num_primary_experts"]  # held here
        / published
    )
    first, count = sizes["held_layers"]
    macs = s * d * sizes["vocab_size"]  # the head; the embedding is a lookup
    for windowed in sizes["sliding_window_layout"][first:first + count]:
        macs += s * attention_macs(sizes)
        macs += score_macs(
            sizes, sizes["sliding_window_size"] if windowed else None
        )
        # the router, all 64 outputs, and the held experts' share
        macs += s * (d * published + routed_here * expert)
    return 3 * 2 * macs


# ------------------------------------------- the banded kernels' roofline
#
# What a banded call is credited, whatever tiles it runs: the products
# of head_dim over the band's VISIBLE pairs. The forward kernel forms
# two of them a pair (q k^T, p v); the dq kernel three (q k^T, do v^T,
# ds k); the dk+dv kernel four (k q^T, p^T do, v do^T, ds^T q).
FORWARD_PRODUCTS = 2
BACKWARD_PRODUCTS = 3 + 4


def swa_call_flops(sizes, products, sequences=1):
    """One banded kernel call that forms `products` products a visible
    pair: 2 x products x heads x head_dim x the band's pairs (a forward
    call at 16,384 tokens: 4 x 28 x 128 x 58,722,304 = 841.8 GFLOP)."""
    return (
        2.0 * products * sequences * sizes["num_attention_heads"]
        * sizes["head_dim"]
        * visible_pairs(sizes["seq_len"], sizes["sliding_window_size"])
    )


def swa_call_bytes(sizes, tensors, sequences=1, itemsize=2):
    """The least such a call moves: `tensors` arrays of [tokens, heads,
    head_dim] in bfloat16, each once (forward: q, k, v in and o out = 4;
    dq: q, k, v, do in and dq out = 5; dk+dv: q, k, v, do in and dk, dv
    out = 6), k and v counted as widened to the 28 query heads, which
    is how they reach the kernels; the float32 rows (logsumexp, delta)
    are a 128th of one and left out."""
    return (
        itemsize * tensors * sequences * sizes["seq_len"]
        * sizes["num_attention_heads"] * sizes["head_dim"]
    )


# ------------------------------------------ the gated-ReLU experts' roofline


def expert_matmul_flops(rows, sizes):
    """One grouped matmul of the expert layer (any of a layer's twelve:
    gate, up and down, forward, recomputed, and each one's two backward
    products) over `rows` routed rows: rows x 2560 x 768
    multiply-accumulates whichever way it is laid. 768 is three tiles of
    256: `moe.run_width` leaves it as it is, so the stated width is the
    width that runs."""
    return 2.0 * rows * sizes["hidden_size"] * sizes["moe_ffn_hidden_size"]


def expert_matmul_bytes(rows, sizes, itemsize=2):
    """The least such a matmul moves: its rows in, its rows out, and
    every held expert's matrix once (bfloat16)."""
    d, f = sizes["hidden_size"], sizes["moe_ffn_hidden_size"]
    return itemsize * (
        rows * d + rows * f + sizes["moe_num_primary_experts"] * d * f
    )
