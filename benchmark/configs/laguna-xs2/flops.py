"""FLOPs one training sample of the `laguna-xs2` configuration requires,
from shapes alone (`harness/flops.py`'s rules: 3 x forward, 2 FLOPs a
multiply-accumulate, elementwise work, norms, the rotation, the gate's
sigmoid and the router's softmax not counted, recomputation not
credited) — and the operations and bytes of the banded attention
kernels, for `swa_roofline_pct`.

Attention's scores and their product with the values are counted over
the pairs the mask leaves VISIBLE: the causal triangle's s (s + 1) / 2
on a full layer, the band's w (w + 1) / 2 + (s - w) w on a sliding one
(4,063,488 of 33,558,528 at 8192 tokens and a window of 512), whatever
tiles a kernel runs to cover them.

Routing is counted UNIFORM: a token takes 8 of 256 experts and 16 are
held here, so on average 8 x 16 / 256 = 0.5 routed experts a token work
on this chip. What the router really sent is in `expert_tokens` of the
`worker.window_stats` span; `mfu_pct` does not follow it."""


def visible_pairs(length, window=None):
    """(query, key) pairs a sequence of `length` sees: the triangle's,
    or under `window` the band's."""
    w = length if window is None else min(window, length)
    return w * (w + 1) // 2 + (length - w) * w


def attention_macs(sizes, heads):
    """One attention layer's matrices, a token: q and o of `heads`
    heads, k and v of the key-value heads, the gate."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    return (
        2 * d * heads * hd + 2 * d * sizes["num_key_value_heads"] * hd
        + d * heads
    )


def score_macs(sizes, heads, window=None):
    """Scores and their product with the values, a SEQUENCE: two
    products of head_dim a visible pair and head."""
    return 2 * heads * sizes["head_dim"] * visible_pairs(
        sizes["seq_len"], window
    )


def flops_per_sample(sizes):
    d, s = sizes["hidden_size"], sizes["seq_len"]
    expert = 3 * d * sizes["moe_intermediate_size"]  # one SwiGLU
    shared = 3 * d * sizes["shared_expert_intermediate_size"]
    routed_here = (
        sizes["num_experts_per_tok"]
        * sizes["num_experts"]  # held here
        / sizes["published"]["num_experts"]
    )
    first, count = sizes["held_layers"]
    macs = s * d * sizes["vocab_size"]  # the head; the embedding is a lookup
    for i in range(first, first + count):
        heads = sizes["num_attention_heads_per_layer"][i]
        window = (
            sizes["sliding_window"]
            if sizes["layer_types"][i] == "sliding_attention" else None
        )
        macs += s * attention_macs(sizes, heads)
        macs += score_macs(sizes, heads, window)
        if sizes["mlp_layer_types"][i] == "dense":
            macs += s * 3 * d * sizes["intermediate_size"]
        else:
            macs += s * (
                d * sizes["published"]["num_experts"]  # the router, all 256
                + shared + routed_here * expert
            )
    return 3 * 2 * macs


# ------------------------------------------- the banded kernels' roofline
#
# What a banded call is credited, whatever tiles it runs: the products
# of head_dim over the band's VISIBLE pairs. The forward kernel forms
# two of them a pair (q k^T, p v); the dq kernel three (q k^T, do v^T,
# ds k); the dk+dv kernel four (k q^T, p^T do, v do^T, ds^T q).
FORWARD_PRODUCTS = 2
BACKWARD_PRODUCTS = 3 + 4


def swa_heads(sizes):
    first, count = sizes["held_layers"]
    return next(
        sizes["num_attention_heads_per_layer"][i]
        for i in range(first, first + count)
        if sizes["layer_types"][i] == "sliding_attention"
    )


def swa_call_flops(sizes, products, sequences=1):
    """One banded kernel call that forms `products` products a visible
    pair: 2 x products x heads x head_dim x the band's pairs (a forward
    call at 8192 tokens: 4 x 64 x 128 x 4,063,488 = 133.1 GFLOP)."""
    return (
        2.0 * products * sequences * swa_heads(sizes) * sizes["head_dim"]
        * visible_pairs(sizes["seq_len"], sizes["sliding_window"])
    )


def swa_call_bytes(sizes, tensors, sequences=1, itemsize=2):
    """The least such a call moves: `tensors` arrays of [tokens, heads,
    head_dim] in bfloat16, each once (forward: q, k, v in and o out = 4;
    dq: q, k, v, do in and dq out = 5; dk+dv: q, k, v, do in and dk, dv
    out = 6), k and v counted as widened to the query heads, which is
    how they reach the kernels; the float32 rows (logsumexp, delta) are
    a 128th of one and left out."""
    return (
        itemsize * tensors * sequences * sizes["seq_len"] * swa_heads(sizes)
        * sizes["head_dim"]
    )
