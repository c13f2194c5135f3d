"""FLOPs one training sample of the `phi-4-mini-flash-reasoning`
configuration requires, from shapes alone (`harness/flops.py`'s rules:
3 x forward, 2 FLOPs a multiply-accumulate, elementwise work, norms,
exponentials, softmax and the gates not counted, recomputation not
credited) — and the operations and bytes of the selective scan, for
`selscan_roofline_pct`.

The published table of layers (`kind_of`: by the published index, as
`reference.py` reads it) is walked over the layers held here
(`held_layers`).

Attention's scores and their product with the values are counted over
the VISIBLE (query, key) pairs, the causal triangle's s (s + 1) / 2 or
the band's, whatever tiles a kernel runs to cover them; a pair of heads
computes TWO softmax maps, each a product of 64 for the scores and one
of 128 with the pair's values.

The recurrence is counted AS WRITTEN, whatever implements it: a token,
channel and state column cost dt A, exp(.) h, (dt x) B, their sum, h C
and its sum: three multiply-accumulates (the exponential itself is not
counted). A form that did less could read above its due and one that
does more (an associative scan's levels) is not credited for it.
"""


def kind_of(i, n_layers):
    """The published table: the mixer of layer i of n_layers."""
    half = n_layers // 2
    if i <= half:
        return "mamba" if i % 2 == 0 else "sliding"
    if i == half + 1:
        return "full"
    return "gmu" if i % 2 == 0 else "cross"


def visible_pairs(length, window=None):
    """(query, key) pairs a causal sequence of `length` sees, under a
    window the band's: the query at t sees min(t + 1, window) keys."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def _mamba_sizes(sizes):
    hidden, assumed = sizes["hidden_size"], sizes["assumed_sizes"]
    return (
        assumed["mamba_expand"] * hidden, assumed["mamba_d_state"],
        assumed["mamba_d_conv"], hidden // assumed["mamba_dt_rank_divisor"],
    )


def mlp_macs(sizes):
    """The gated MLP, a token: gate, up and down."""
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def attention_macs(sizes, cross=False):
    """A differential attention layer's matrices, a token: q and the
    output, and (not in a cross layer) k and v of the key-value heads."""
    d = sizes["hidden_size"]
    hd = d // sizes["num_attention_heads"]
    kv = 0 if cross else 2 * d * sizes["num_key_value_heads"] * hd
    return 2 * d * sizes["num_attention_heads"] * hd + kv


def score_macs(sizes, window=None):
    """Both softmax maps of every pair of heads, a SEQUENCE: scores of
    head_dim and a product with values of 2 x head_dim, a visible
    pair, map and pair of heads."""
    hd = sizes["hidden_size"] // sizes["num_attention_heads"]
    pairs = sizes["num_attention_heads"] // 2
    return 2 * pairs * 3 * hd * visible_pairs(sizes["seq_len"], window)


def mamba_macs(sizes):
    """A Mamba-1 layer's matrices, a token: x | z, the taps, delta | B |
    C, the step's projection, the output."""
    d = sizes["hidden_size"]
    inner, n, taps, rank = _mamba_sizes(sizes)
    return (
        d * 2 * inner + taps * inner + inner * (rank + 2 * n)
        + rank * inner + inner * d
    )


def selscan_macs(sizes):
    """The recurrence as written, a token (all channels and columns)."""
    inner, n, _taps, _rank = _mamba_sizes(sizes)
    return 3 * inner * n


def gmu_macs(sizes):
    inner = _mamba_sizes(sizes)[0]
    return 2 * sizes["hidden_size"] * inner


def flops_per_sample(sizes):
    d, s = sizes["hidden_size"], sizes["seq_len"]
    first, count = sizes["held_layers"]
    macs = s * d * sizes["vocab_size"]  # the tied head; the embedding a lookup
    for i in range(first, first + count):
        kind = kind_of(i, sizes["published"]["num_hidden_layers"])
        macs += s * mlp_macs(sizes)
        if kind == "mamba":
            macs += s * (mamba_macs(sizes) + selscan_macs(sizes))
        elif kind == "gmu":
            macs += s * gmu_macs(sizes)
        elif kind == "sliding":
            macs += s * attention_macs(sizes) + score_macs(
                sizes, sizes["sliding_window"]
            )
        else:
            macs += s * attention_macs(sizes, kind == "cross") + score_macs(sizes)
    return 3 * 2 * macs


# ---------------------------------------------- the selective scan's roofline


def selscan_flops(tokens, sizes):
    """One forward pass of one layer's recurrence over `tokens`
    tokens."""
    return 2.0 * tokens * selscan_macs(sizes)


def selscan_bytes(tokens, sizes):
    """The least such a pass moves: x (bfloat16) and dt (float32) read
    once, y (float32) written once, a channel and token; B and C
    (bfloat16) a column and token. Never the [T, inner, state] states."""
    inner, n, _taps, _rank = _mamba_sizes(sizes)
    return tokens * (inner * (2 + 4 + 4) + 2 * n * 2)
