"""Plain reference of the `phi-4-mini-flash-reasoning` layers as the
configuration cuts them: the forward pass, the loss (cross-entropy over
the vocabulary's slice) and its gradients in straightforward
`jax.numpy` and float32 — the PUBLISHED table of layers walked one
layer at a time by its published index (`kind_of`; this file knows
nothing of runs or scans over layers), the state-space recurrence as
the recurrence itself, ONE TOKEN AT A TIME (`lax.scan` over time: no
chunk, no kernel), differential attention ONE PAIR OF HEADS AT A TIME
with both softmax maps written out a block of queries at a time, no
recomputation, no cast. It takes the zoo module's parameter tree
(`layers_of` is the one place that knows how that tree lays its leaves
out) and imports nothing of the program. On a TPU set
`jax.default_matmul_precision("highest")` around it.

It follows the published `config.json` (microsoft/Phi-4-mini-flash-
reasoning, `model_type` `phi4flash`; SambaY, arXiv:2507.06607, with
differential attention, arXiv:2410.05258) and, for what that leaves
open, the conventions `config.json`'s `assumed` lists. Every layer:
h <- h + mixer(LN(h)); h <- h + MLP(LN(h)); LN a LayerNorm with weight
and bias; MLP(x) = W_down(SiLU(W_gate x) * W_up x); a final LayerNorm;
logits by the embedding transposed; no position encoding. With N
published layers, layer i is (`kind_of`):
- even, i < N/2, and i = N/2: Mamba-1. (x | z) = u W_in; x <-
  SiLU(conv4(x) + b); (delta | B | C) = x W_x; dt = softplus(delta W_dt
  + b_dt); A = -exp(A_log) [inner, state]; from h = 0: h_t = exp(dt_t
  A) h_{t-1} + (dt_t x_t) B_t^T, y_t = h_t C_t + D x_t; out = (y *
  SiLU(z)) W_out. Layer N/2's y is the MEMORY M.
- odd, i < N/2: differential attention under the window (the query at
  t sees the keys u with 0 <= t - u < window); i = N/2 + 1: the same,
  full causal, and its K and V are THE SHARED KEYS AND VALUES.
- even, i >= N/2 + 2: the gated memory unit, W_2 (M * SiLU(W_1 u)).
- odd, i >= N/2 + 3: differential CROSS attention, queries of its own
  over layer N/2 + 1's K and V, full causal.
Differential attention: query heads in pairs (2p, 2p + 1), key-value
heads too, a pair's two value heads read as one of twice the width;
query pair p reads key-value pair p // (pairs / kv pairs); o = softmax(
q1 k1^T / sqrt(hd)) v - lambda softmax(q2 k2^T / sqrt(hd)) v, lambda =
exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, lambda_init = 0.8 - 0.6
exp(-0.3 i) with i the published index; o <- RMSnorm over its 2 hd
columns (a weight of 2 hd) x (1 - lambda_init); heads joined, W_o; a
bias on q, k, v and o.
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def kind_of(i, n_layers):
    """The published table: the mixer of layer i of n_layers."""
    half = n_layers // 2
    if i <= half:
        if i % 2 == 0:
            return "memory_mamba" if i == half else "mamba"
        return "sliding"
    if i == half + 1:
        return "full"
    return "gmu" if i % 2 == 0 else "cross"


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def _float32(params):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def gated_mlp(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def short_conv(x, taps, bias):
    """x [B, L, C], taps [n, C], bias [C]: y_t = silu(sum_i taps[i]
    x_{t-(n-1)+i} + bias), zeros before the start."""
    n, length = taps.shape[0], x.shape[1]
    out = jnp.zeros_like(x) + bias
    for i in range(n):
        shift = n - 1 - i  # tap i reads the token `shift` back
        moved = jnp.concatenate(
            [jnp.zeros_like(x[:, :shift]), x[:, :length - shift]], axis=1
        )
        out = out + moved * taps[i]
    return jax.nn.silu(out)


def scan_step(state, xs, A):
    """One token of the recurrence: state [B, D, N], xs = (x_t, dt_t
    [B, D], b_t, c_t [B, N]), A [D, N] -> (the next state, y_t [B, D])."""
    x_t, dt_t, b_t, c_t = xs
    state = jnp.exp(dt_t[..., None] * A) * state + (
        (dt_t * x_t)[..., None] * b_t[:, None, :]
    )
    return state, jnp.einsum("bdn,bn->bd", state, c_t)


def selective_scan(x, dt, A, b, c):
    """The recurrence, a token at a time. x and dt [B, L, D], A [D, N],
    b and c [B, L, N] -> y [B, L, D]."""
    start = jnp.zeros((x.shape[0],) + A.shape, jnp.float32)
    _, out = jax.lax.scan(
        lambda state, xs: scan_step(state, xs, A), start,
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)),
    )
    return jnp.moveaxis(out, 0, 1)


def mamba(lp, u, sizes):
    """u [B, L, d] normed -> (out [B, L, d], y [B, L, inner]: the scan's
    output plus D x, before the gate). `lp["a_log"]` is [inner, state]
    here (`layers_of` turns it)."""
    inner, n, rank = sizes["inner"], sizes["state"], sizes["dt_rank"]
    projected = u @ lp["in_proj"]  # x | z
    x = short_conv(projected[..., :inner], lp["conv"], lp["conv_bias"])
    z = projected[..., inner:]
    dbc = x @ lp["x_proj"]  # delta | B | C
    dt = jax.nn.softplus(dbc[..., :rank] @ lp["dt_proj"] + lp["dt_bias"])
    b, c = dbc[..., rank:rank + n], dbc[..., rank + n:]
    y = selective_scan(x, dt, -jnp.exp(lp["a_log"]), b, c) + lp["D"] * x
    return (y * jax.nn.silu(z)) @ lp["out_proj"], y


def gmu(lp, u, memory):
    return (memory * jax.nn.silu(u @ lp["w1"])) @ lp["w2"]


def block_softmax(q, k, first_query, window):
    """One block of queries of ONE head against ONE key head: q [B, Q,
    D] at positions first_query.., k [B, U, D] at 0.. -> the softmax
    map [B, Q, U] under the causal mask (and the window's)."""
    t = first_query + jnp.arange(q.shape[1])[:, None]
    u = jnp.arange(k.shape[1])[None, :]
    seen = u <= t
    if window is not None:
        seen = seen & (t - u < window)
    scores = jnp.einsum("bqd,bud->bqu", q, k) * q.shape[-1] ** -0.5
    return jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)


def pair_attention(q1, q2, k1, k2, v, lam, window):
    """One pair of heads: q1, q2, k1, k2 [B, L, hd], v [B, L, 2 hd] ->
    softmax(q1 k1) v - lam softmax(q2 k2) v [B, L, 2 hd], a block of
    queries at a time."""
    length, blocks = q1.shape[1], []
    for start in range(0, length, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, length)
        first = block_softmax(q1[:, start:end], k1[:, :end], start, window)
        second = block_softmax(q2[:, start:end], k2[:, :end], start, window)
        blocks.append((first - lam * second) @ v[:, :end])
    return jnp.concatenate(blocks, axis=1)


def keys_values(lp, u, sizes):
    """A layer's own keys and values: k [B, L, kv heads x hd] and v
    [B, L, kv pairs x 2 hd] as `heads_of` reads them."""
    return u @ lp["wk"] + lp["bk"], u @ lp["wv"] + lp["bv"]


def heads_of(q, k, v, sizes):
    """Where the tree lays a pair's members: `wq`'s and `wk`'s columns
    hold every pair's FIRST member, then every pair's second
    (config.json: departures); a pair's value is 2 hd columns of `wv`
    -> lists over the query pairs of (q1, q2, k1, k2, v)."""
    hd, pairs, kv_pairs = sizes["head_dim"], sizes["heads"] // 2, sizes["kv_heads"] // 2

    def head(y, i, width=hd):
        return y[..., i * width:(i + 1) * width]

    out = []
    for p in range(pairs):
        reads = p // (pairs // kv_pairs)
        out.append((
            head(q, p), head(q, pairs + p),
            head(k, reads), head(k, kv_pairs + reads), head(v, reads, 2 * hd),
        ))
    return out


def diff_attention(lp, u, sizes, depth, window=None, shared=None):
    """u [B, L, d] normed -> (out, (k, v)): differential attention of
    the layer at published index `depth`; `shared` (k, v) in place of
    the layer's own (cross attention)."""
    hd = sizes["head_dim"]
    q = u @ lp["wq"] + lp["bq"]
    k, v = shared if shared is not None else keys_values(lp, u, sizes)
    lq1, lk1, lq2, lk2 = (lp["diff"][i * hd:(i + 1) * hd] for i in range(4))
    weight = lp["diff"][4 * hd:]
    init = lambda_init(depth)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + init
    joined = []
    for q1, q2, k1, k2, v_pair in heads_of(q, k, v, sizes):
        o = pair_attention(q1, q2, k1, k2, v_pair, lam, window)
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + sizes["eps"])
        joined.append(o * weight * (1.0 - init))
    return jnp.concatenate(joined, axis=-1) @ lp["wo"] + lp["bo"], (k, v)


def layer(kind, depth, lp, h, shared, sizes):
    """One published layer on the residual stream h [B, L, d] -> (h,
    shared): `shared` holds the memory and the shared keys and values
    once their layers have run."""
    u = layer_norm(h, lp["ln1"], lp["ln1_bias"], sizes["eps"])
    if kind in ("mamba", "memory_mamba"):
        out, y = mamba(lp, u, sizes)
        if kind == "memory_mamba":
            shared = {**shared, "memory": y}
    elif kind == "gmu":
        out = gmu(lp, u, shared["memory"])
    elif kind == "cross":
        out, _ = diff_attention(lp, u, sizes, depth, shared=shared["kv"])
    else:
        window = sizes["window"] if kind == "sliding" else None
        out, kv = diff_attention(lp, u, sizes, depth, window)
        if kind == "full":
            shared = {**shared, "kv": kv}
    h = h + out
    u = layer_norm(h, lp["ln2"], lp["ln2_bias"], sizes["eps"])
    return h + gated_mlp(u, lp["wg"], lp["wu"], lp["wd"]), shared


def layers_of(params):
    """The zoo's tree as layers in order: the tree stacks the program's
    layers run by run under `stack`; a Mamba layer's `a_log` lies
    [state, inner] there and is turned to [inner, state]."""
    for run in params["stack"]:
        for i in range(run["ln1"].shape[0]):
            lp = {name: leaf[i] for name, leaf in run.items()}
            if "a_log" in lp:
                lp["a_log"] = lp["a_log"].T
            yield lp


def head_loss(params, h, targets, sizes):
    """-> (mean next-token cross-entropy over the vocabulary's slice,
    the logits): the final LayerNorm, the embedding transposed."""
    h = layer_norm(h, params["ln_f"], params["ln_f_bias"], sizes["eps"])
    logits = h @ params["embed"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return ce, logits


def forward(params, tokens, sizes):
    """params: the zoo's tree; tokens [B, L] -> the last held layer's
    output [B, L, d]. The held layers are published layers first ..
    first + count - 1 of `sizes["published_layers"]`."""
    params = _float32(params)
    h = params["embed"][tokens]
    first, count = sizes["held"]
    held = list(layers_of(params))
    assert len(held) == count, (len(held), count)
    shared = {}
    for at, lp in enumerate(held):
        depth = first + at
        kind = kind_of(depth, sizes["published_layers"])
        h, shared = layer(kind, depth, lp, h, shared, sizes)
    return h


def logits_of(params, tokens, sizes):
    params = _float32(params)
    return head_loss(params, forward(params, tokens, sizes), tokens, sizes)[1]


def loss(params, tokens, targets, sizes):
    params = _float32(params)
    return head_loss(params, forward(params, tokens, sizes), targets, sizes)[0]


def sizes_of(config, **overrides):
    """The reference's settings from a `config.json` of the released
    model's keys (`benchmark/configs/phi-4-mini-flash-reasoning/
    config.json`); the Mamba sizes are the family's defaults it lists
    under `assumed_sizes`."""
    hidden = config["hidden_size"]
    sizes = {
        "published_layers": config["published"]["num_hidden_layers"],
        "held": tuple(config["held_layers"]),
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": hidden // config["num_attention_heads"],
        "window": config["sliding_window"],
        "eps": config["layer_norm_eps"],
        "inner": config["assumed_sizes"]["mamba_expand"] * hidden,
        "state": config["assumed_sizes"]["mamba_d_state"],
        "dt_rank": hidden // config["assumed_sizes"]["mamba_dt_rank_divisor"],
    }
    sizes.update(overrides)
    return sizes
