"""FLOPs one training sample of the `qwen3-next-80b-a3b` configuration
requires, from shapes alone (`harness/flops.py`'s rules: 3 x forward,
2 FLOPs a multiply-accumulate, elementwise work, norms, the rotation,
exponentials, the gates' sigmoids and the router's softmax not counted,
recomputation not credited) — and the operations and bytes of the
scalar-decay scan and of a causal attention call at heads of 256, for
`gdn_scan_roofline_pct` and `attn256_roofline_pct`.

Attention's scores and their product with the values are counted over
the causal triangle's s (s + 1) / 2 VISIBLE pairs, whatever tiles a
kernel runs to cover them.

Routing is counted UNIFORM: a token takes 10 of 512 experts and 16 are
held here, so on average 10 x 16 / 512 = 0.3125 routed experts a token
work on this chip. What the router really sent is in `expert_tokens` of
the `worker.window_stats` span; `mfu_pct` does not follow it.

The recurrence is counted as the chunked form under ONE decay a head at
chunks of 64 tokens (`kda_chunk`), WHATEVER implements it: a value
head's chunk of C tokens with keys of dk and values of dv costs, in
multiply-accumulates,
- the two triangles, k against k below the diagonal and q against k on
  and below it, as products on the multiplier: 2 x C^2 / 2 x dk, formed
  ONCE A KEY HEAD and shared by the value heads that read it (so half
  of that a value head, 16 under 32);
- the unit lower-triangular system of the WY form, solved by
  substitution against its dk + dv right-hand columns: C^2 / 2 x
  (dk + dv);
- the carried state read twice ((K exp G) S_0 and (Q exp G) S_0) and
  written once (K^T W): 3 x C x dk x dv;
- the triangle against the solved rows, B W: C^2 / 2 x dv.
The decay is a [C, C] matrix of exponentials a head and no product. The
program's own route (the system's inverse formed by substitution, then
multiplied; whole squares where a triangle would do; the recomputation
of a chunk in the backward pass) does more work than this and none of
it is credited; a route that did less could read above its due, so the
form is fixed here."""


def visible_pairs(length):
    """(query, key) pairs a causal sequence of `length` sees."""
    return length * (length + 1) // 2


def gdn_mixer_macs(sizes):
    """Gated DeltaNet's matrices, a token: q | k | v | z, the taps over
    q | k | v, the write strength and the decay, the output."""
    d, hd = sizes["hidden_size"], sizes["linear_key_head_dim"]
    kh, vh = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    qkv = (2 * kh + vh) * hd
    return (
        d * (qkv + vh * hd)
        + sizes["linear_conv_kernel_dim"] * qkv
        + d * 2 * vh
        + vh * hd * d
    )


def gdn_scan_macs(sizes):
    """The recurrence, a token (all value heads), as the scalar chunked
    form above."""
    kh, vh = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    chunk = sizes["kda_chunk"]
    a_key_head = 2 * chunk * chunk / 2 * dk
    a_value_head = (
        chunk * chunk / 2 * (dk + dv)
        + 3 * chunk * dk * dv
        + chunk * chunk / 2 * dv
    )
    return (kh * a_key_head + vh * a_value_head) / chunk


def attention_macs(sizes):
    """The gated attention layer's matrices, a token: the queries and
    their gates, k and v of the key-value heads, the output."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    heads = sizes["num_attention_heads"]
    return (
        d * 2 * heads * hd + 2 * d * sizes["num_key_value_heads"] * hd
        + heads * hd * d
    )


def score_macs(sizes):
    """Scores and their product with the values, a SEQUENCE: two
    products of head_dim a visible pair and head."""
    return (
        2 * sizes["num_attention_heads"] * sizes["head_dim"]
        * visible_pairs(sizes["seq_len"])
    )


def flops_per_sample(sizes):
    d, s = sizes["hidden_size"], sizes["seq_len"]
    expert = 3 * d * sizes["moe_intermediate_size"]  # one SwiGLU
    shared = 3 * d * sizes["shared_expert_intermediate_size"] + d  # its gate
    routed_here = (
        sizes["num_experts_per_tok"]
        * sizes["num_experts"]  # held here
        / sizes["published"]["num_experts"]
    )
    first, count = sizes["held_layers"]
    macs = s * d * sizes["vocab_size"]  # the head; the embedding is a lookup
    for kind in sizes["layer_types"][first:first + count]:
        if kind == "linear_attention":
            macs += s * (gdn_mixer_macs(sizes) + gdn_scan_macs(sizes))
        else:
            macs += s * attention_macs(sizes) + score_macs(sizes)
        macs += s * (
            d * sizes["published"]["num_experts"]  # the router, all 512
            + shared + routed_here * expert
        )
    return 3 * 2 * macs


# ------------------------------------------------- the scan's roofline


def gdn_scan_flops(tokens, sizes):
    """One forward pass of one layer's recurrence over `tokens` tokens
    (all value heads)."""
    return 2.0 * tokens * gdn_scan_macs(sizes)


def gdn_scan_bytes(tokens, sizes):
    """The least such a pass moves: q and k of the key heads, v in and
    o out of the value heads in bfloat16, the log-decay and the write
    strength (float32, one a value head each) in; the state stays on
    the chip."""
    kh, vh = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    return tokens * (2 * (2 * kh * dk + 2 * vh * dv) + 2 * 4 * vh)


# ------------------------------------- the causal kernels' roofline at 256
#
# What a causal call is credited, whatever tiles it runs: the products
# of head_dim over the triangle's VISIBLE pairs. The forward kernel
# forms two of them a pair (q k^T, p v); the dq kernel three (q k^T,
# do v^T, ds k); the dk+dv kernel four (k q^T, p^T do, v do^T, ds^T q).
FORWARD_PRODUCTS = 2
BACKWARD_PRODUCTS = 3 + 4


def attention_call_flops(sizes, products, sequences=1):
    """One causal kernel call that forms `products` products a visible
    pair: 2 x products x heads x head_dim x the triangle's pairs (a
    forward call at 8192 tokens: 4 x 16 x 256 x 33,558,528 = 549.8
    GFLOP)."""
    return (
        2.0 * products * sequences * sizes["num_attention_heads"]
        * sizes["head_dim"] * visible_pairs(sizes["seq_len"])
    )


def attention_call_bytes(sizes, tensors, sequences=1, itemsize=2):
    """The least such a call moves: `tensors` arrays of [tokens, heads,
    head_dim] in bfloat16, each once (forward: q, k, v in and o out = 4;
    dq: q, k, v, do in and dq out = 5; dk+dv: q, k, v, do in and dk, dv
    out = 6), k and v counted as widened to the query heads, which is
    how they reach the kernels; the float32 rows (logsumexp, delta) are
    a 256th of one and left out."""
    return (
        itemsize * tensors * sequences * sizes["seq_len"]
        * sizes["num_attention_heads"] * sizes["head_dim"]
    )
