"""FLOPs one training sample of the `kimi-linear-48b-a3b` configuration
requires, from shapes alone (`harness/flops.py`'s rules: 3 x forward,
2 FLOPs a multiply-accumulate, elementwise work, norms, exponentials
and the router's sigmoid not counted, recomputation not credited) — and
the operations and bytes of the KDA recurrence, for
`kda_scan_roofline_pct`.

Routing is counted UNIFORM: a token takes 8 of 256 experts and 8 are
held here, so on average 8 x 8 / 256 = 0.25 routed experts a token work
on this chip. What the router really sent is in `expert_tokens` of the
`worker.window_stats` span; `mfu_pct` does not follow it.

The recurrence is counted as the chunked form at chunks of 64 tokens
(`kda_chunk`), WHATEVER implements it: a head's chunk of C tokens with
keys of dk and values of dv costs, in multiply-accumulates,
- the two decayed triangles, k against k below the diagonal and q
  against k on and below it: 2 x C^2 / 2 x dk;
- the unit lower-triangular system of the WY form, solved by
  substitution against its dk + dv right-hand columns: C^2 / 2 x
  (dk + dv);
- the carried state read twice ((K exp G) S_0 and (Q exp G) S_0) and
  written once (K^T W): 3 x C x dk x dv;
- the triangle against the solved rows, B W: C^2 / 2 x dv.
The program's own route (the system's inverse formed once by
substitution in sub-blocks of 16, then multiplied; the recomputation of
a chunk in the backward pass) does more work than this and none of it
is credited; a kernel that did less could read above its due, so the
form is fixed here."""


def kda_mixer_macs(sizes):
    """KDA's matrices, a token: q, k, v, their convolutions' taps, the
    decay's and the gate's low-rank pairs, the write strength, the
    output."""
    d = sizes["hidden_size"]
    linear = sizes["linear_attn_config"]
    heads, hd = linear["num_heads"], linear["head_dim"]
    wide = heads * hd
    return (
        3 * d * wide
        + 3 * linear["short_conv_kernel_size"] * wide
        + 2 * (d * hd + hd * wide)
        + d * heads
        + wide * d
    )


def kda_scan_macs(sizes):
    """The recurrence, a token (all heads), as the chunked form above."""
    linear = sizes["linear_attn_config"]
    heads, dk = linear["num_heads"], linear["head_dim"]
    dv, chunk = dk, sizes["kda_chunk"]
    a_chunk = (
        2 * chunk * chunk / 2 * dk
        + chunk * chunk / 2 * (dk + dv)
        + 3 * chunk * dk * dv
        + chunk * chunk / 2 * dv
    )
    return heads * a_chunk / chunk


def mla_macs(sizes):
    """Latent attention's matrices, a token (no query latent)."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, shared = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rank, value = sizes["kv_lora_rank"], sizes["v_head_dim"]
    return (
        d * heads * (nope + shared)
        + d * (rank + shared)
        + rank * heads * (nope + value)
        + heads * value * d
    )


def mla_score_macs(sizes):
    """Causal scores and their product with the values, a token: it
    attends to (s + 1) / 2 positions on average."""
    return sizes["num_attention_heads"] * (
        sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
        + sizes["v_head_dim"]
    ) * (sizes["seq_len"] + 1) / 2


def flops_per_sample(sizes):
    d, s = sizes["hidden_size"], sizes["seq_len"]
    dense = sizes["first_k_dense_replace"]
    expert = 3 * d * sizes["moe_intermediate_size"]  # one SwiGLU
    routed_here = (
        sizes["num_experts_per_token"]
        * sizes["num_experts"]  # held here
        / sizes["published"]["num_experts"]
    )
    macs = d * sizes["vocab_size"]  # the head
    for i, mixer in enumerate(sizes["layer_types"]):
        if mixer == "kda":
            macs += kda_mixer_macs(sizes) + kda_scan_macs(sizes)
        else:
            macs += mla_macs(sizes) + mla_score_macs(sizes)
        if i < dense:
            macs += 3 * d * sizes["intermediate_size"]
        else:
            macs += (
                d * sizes["published"]["num_experts"]  # the router, all 256
                + sizes["num_shared_experts"] * expert
                + routed_here * expert
            )
    return 3 * 2 * macs * s


def kda_scan_flops(tokens, sizes):
    """One forward pass of one KDA layer's recurrence over `tokens`
    tokens (all heads)."""
    return 2.0 * tokens * kda_scan_macs(sizes)


def kda_scan_bytes(tokens, sizes):
    """The least such a pass moves: q, k, v in and o out in bfloat16,
    the log-decay (float32, a key channel each) and the write strength
    (float32, one a head) in; the state stays on the chip."""
    linear = sizes["linear_attn_config"]
    heads, hd = linear["num_heads"], linear["head_dim"]
    return tokens * heads * (2 * 4 * hd + 4 * hd + 4)
