"""FLOPs one training sample of the `nemotron-3-nano-30b-a3b`
configuration requires, from shapes alone (`harness/flops.py`'s rules:
3 x forward, 2 FLOPs a multiply-accumulate, elementwise work, norms,
exponentials, the gates and the router's sigmoid not counted,
recomputation not credited) — and the operations and bytes of the
state-space scan and of one of the squared-ReLU experts' grouped
matmuls, for `ssm_scan_roofline_pct` and `relu2_experts_roofline_pct`.

The published pattern string is walked a block at a time over the
blocks held here (`held_layers`): `M` a Mamba-2 block, `*` an attention
block, `E` an expert block.

Attention's scores and their product with the values are counted over
the causal triangle's s (s + 1) / 2 VISIBLE pairs, whatever tiles a
kernel runs to cover them.

Routing is counted UNIFORM: a token takes 6 of 128 experts and 8 are
held here, so on average 6 x 8 / 128 = 0.375 routed experts a token
work on this chip. What the router really sent is in `expert_tokens` of
the `worker.window_stats` span; `mfu_pct` does not follow it.

The recurrence is counted as the chunked form at chunks of
`chunk_size` tokens, WHATEVER implements it. A chunk of C tokens costs,
in multiply-accumulates,
- the scores C B^T on and below the diagonal, ONCE A GROUP (its 8 heads
  share them): C^2 / 2 x N;
- a head's weights against its inputs on and below the diagonal:
  C^2 / 2 x P;
- what the chunk adds to the head's state, x^T B, and what the state it
  meets adds to its outputs, C S: 2 x C x P x N;
- the pass between chunks as the recurrence's own step, once a chunk:
  P x N a head (a product with a [chunks, chunks] matrix does chunks / 2
  times that and is not credited for it).
The decay is a [C, C] matrix of exponentials a head and no product. A
route that did less could read above its due, so the form is fixed
here."""


def visible_pairs(length):
    """(query, key) pairs a causal sequence of `length` sees."""
    return length * (length + 1) // 2


def mamba2_macs(sizes):
    """A Mamba-2 block's matrices, a token: z | x | B | C | dt, the taps
    over x | B | C, the output."""
    d = sizes["hidden_size"]
    inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    bc = 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    return (
        d * (2 * inner + bc + sizes["mamba_num_heads"])
        + sizes["conv_kernel"] * (inner + bc)
        + inner * d
    )


def ssm_scan_macs(sizes):
    """The recurrence, a token (all heads), as the chunked form above."""
    heads, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, n = sizes["n_groups"], sizes["ssm_state_size"]
    chunk = sizes["chunk_size"]
    a_group = chunk * chunk / 2 * n
    a_head = chunk * chunk / 2 * p + 2 * chunk * p * n + p * n
    return (groups * a_group + heads * a_head) / chunk


def attention_macs(sizes):
    """An attention block's matrices, a token: q, k and v of the
    key-value heads, the output."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    heads = sizes["num_attention_heads"]
    return (
        d * heads * hd + 2 * d * sizes["num_key_value_heads"] * hd
        + heads * hd * d
    )


def score_macs(sizes):
    """Scores and their product with the values, a SEQUENCE: two
    products of head_dim a visible pair and head."""
    return (
        2 * sizes["num_attention_heads"] * sizes["head_dim"]
        * visible_pairs(sizes["seq_len"])
    )


def expert_block_macs(sizes):
    """An expert block, a token: the router over all 128 outputs, the
    shared expert, the held experts' share at uniform routing; an
    expert is two matrices."""
    d = sizes["hidden_size"]
    routed_here = (
        sizes["num_experts_per_tok"] * sizes["n_routed_experts"]  # held here
        / sizes["published"]["n_routed_experts"]
    )
    return (
        d * sizes["published"]["n_routed_experts"]
        + sizes["n_shared_experts"] * 2 * d
        * sizes["moe_shared_expert_intermediate_size"]
        + routed_here * 2 * d * sizes["moe_intermediate_size"]
    )


def flops_per_sample(sizes):
    d, s = sizes["hidden_size"], sizes["seq_len"]
    first, count = sizes["held_layers"]
    macs = s * d * sizes["vocab_size"]  # the head; the embedding is a lookup
    for letter in sizes["hybrid_override_pattern"][first:first + count]:
        if letter == "M":
            macs += s * (mamba2_macs(sizes) + ssm_scan_macs(sizes))
        elif letter == "*":
            macs += s * attention_macs(sizes) + score_macs(sizes)
        elif letter == "E":
            macs += s * expert_block_macs(sizes)
        else:
            raise ValueError(f"no count for a block {letter!r}")
    return 3 * 2 * macs


# ------------------------------------------------- the scan's roofline


def ssm_scan_flops(tokens, sizes):
    """One forward pass of one block's recurrence over `tokens` tokens
    (all heads)."""
    return 2.0 * tokens * ssm_scan_macs(sizes)


def ssm_scan_bytes(tokens, sizes):
    """The least such a pass moves: x in and y out of the heads and B
    and C of the groups in bfloat16, the step dt (float32, one a head)
    in; the states stay on the chip."""
    heads, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, n = sizes["n_groups"], sizes["ssm_state_size"]
    return tokens * (2 * (2 * heads * p + 2 * groups * n) + 4 * heads)


# ------------------------------------- the squared-ReLU experts' roofline


def expert_matmul_flops(rows, sizes):
    """One grouped matmul of the expert block (any of a block's eight:
    up and down, forward, recomputed, and each one's two backward
    products) over `rows` routed rows: rows x 2688 x 1856
    multiply-accumulates whichever way it is laid."""
    return 2.0 * rows * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def expert_matmul_bytes(rows, sizes, itemsize=2):
    """The least such a matmul moves: its rows in, its rows out, and
    every held expert's matrix once (bfloat16)."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    return itemsize * (
        rows * d + rows * f + sizes["n_routed_experts"] * d * f
    )
