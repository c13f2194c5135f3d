"""Plain reference of the `lfm2-24b-a2b` block as the configuration cuts
it: the forward pass, the loss (cross-entropy over the vocabulary's
slice; no balance term) and its gradients in straightforward
`jax.numpy` and float32 — Python loops over the layers (the stacked
weights indexed, not scanned), the short convolution ONE TOKEN AT A
TIME from a cache of `conv_L_cache` rows (`lax.scan` over time: no
padding, no shifted copies), attention HEAD BY HEAD with its scores
written out and each query head naming its key-value head, the experts
as a masked dense sum over the experts held here, the head as the
embedding transposed: no sort, no grouped matmul, no recomputation, no
kernel, no cast. It takes the zoo module's parameter tree and imports
nothing of the program. On a TPU set
`jax.default_matmul_precision("highest")` around it.

It follows the published `config.json` (LiquidAI/LFM2-24B-A2B,
`model_type` `lfm2_moe`) and, for what that leaves open, the released
modelling code's conventions; each is also in `config.json`'s
`assumed`:
- pre-norm residual block, RMS norm with a weight, no bias anywhere,
  the head tied to the embedding;
- `conv` mixer: (b, c, u) = split3(x W_in); z = b * u; y_t = sum_i
  w_i * z_{t-2+i} over the last three rows (zeros before the start),
  no bias and no activation; out = (c * y) W_out;
- `full_attention` mixer: 32 query heads and 8 key-value heads of 64;
  queries and keys RMS-normed per head (one weight of 64 each a
  layer), then rotated over the whole 64 at base 1e6, pair i =
  (x[i], x[i + 32]); query head i reads key-value head i // 4; causal
  softmax of (q . k) x 64^-1/2;
- sigmoid scores over all 64 router outputs, the 4 largest of score +
  bias chosen (equal ones to the lower expert first), gates the chosen
  scores over their sum (all four, held or not) x 1.0; the bias is not
  in the gate; NO shared expert;
- the cuts: only the experts `held` = (first, count) add to a layer's
  output (what the 56 others would add is left out, and that partial
  result goes on to the next layer); the vocabulary is one chip's slice
  of the rows of the embedding.
"""

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def gated_mlp(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def conv_step(cache, xs, taps):
    """One token of the short convolution: cache [B, n, C] holds the
    last n rows of z, the oldest first; z_t [B, C] -> (the next cache,
    y_t = sum_i taps[i] * row i)."""
    cache = jnp.concatenate([cache[:, 1:], xs[:, None]], axis=1)
    return cache, jnp.sum(cache * taps, axis=1)


def cached_conv(z, taps):
    """z [B, L, C], taps [n, C] -> y [B, L, C], a token at a time from
    a cache of n rows that starts as zeros."""
    start = jnp.zeros((z.shape[0], taps.shape[0], z.shape[2]), z.dtype)
    _, y = jax.lax.scan(
        lambda cache, z_t: conv_step(cache, z_t, taps),
        start, jnp.moveaxis(z, 1, 0),
    )
    return jnp.moveaxis(y, 0, 1)


def conv_mixer(lp, x):
    """x [B, L, d] normed -> [B, L, d]."""
    d = x.shape[-1]
    bcu = x @ lp["in_proj"]
    b, c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    return (c * cached_conv(b * u, lp["conv"])) @ lp["out_proj"]


def rotate(x, base):
    """x [B, L, D] -> the same turned by its position: pair i is
    (x[i], x[i + D/2]), its angle position x base^(-2i/D)."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def one_head(q, k, v):
    """q, k, v [B, L, D] of one query head and the key-value head it
    reads -> [B, L, D]."""
    length, width = q.shape[1], q.shape[2]
    scores = jnp.einsum("bqd,bkd->bqk", q, k) * width**-0.5
    causal = jnp.tril(jnp.ones((length, length), dtype=bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1), v)


def grouped_attention(lp, x, sizes):
    """x [B, L, d] normed -> [B, L, d]."""
    heads, kv_heads, hd = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
    group = heads // kv_heads
    q, k, v = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]

    def head(y, i):
        return y[..., i * hd:(i + 1) * hd]

    keys = [
        rotate(_rms_norm(head(k, j), lp["k_norm"], sizes["eps"]), sizes["rope_base"])
        for j in range(kv_heads)
    ]
    out = []
    for i in range(heads):
        q_i = rotate(
            _rms_norm(head(q, i), lp["q_norm"], sizes["eps"]), sizes["rope_base"]
        )
        j = i // group  # the key-value head query head i reads
        out.append(one_head(q_i, keys[j], head(v, j)))
    return jnp.concatenate(out, axis=-1) @ lp["wo"]


def top_k_by(scores, k):
    """[T, E] -> one-hot choices [T, E] of the k largest of each row,
    taken one at a time; among equals the lowest expert first."""
    chosen = jnp.zeros_like(scores)
    left = scores
    for _ in range(k):
        pick = jax.nn.one_hot(jnp.argmax(left, axis=-1), scores.shape[-1])
        chosen = chosen + pick
        left = jnp.where(pick > 0, -jnp.inf, left)
    return jax.lax.stop_gradient(chosen)


def expert_layer(lp, x, sizes, held=None):
    """x [B, L, d] normed -> (y, tokens of each expert [E]). `held` =
    (first, count): the experts whose weights `lp` holds (`eg`, `eu`,
    `ed` stacked [count, ...]) and whose part is added. No shared
    expert: a token none of whose experts is held gets zero."""
    experts, k = lp["router"].shape[-1], sizes["top_k"]
    first, count = held if held else sizes["held"]
    scores = jax.nn.sigmoid(x @ lp["router"])  # [B, L, E]
    biased = scores + jax.lax.stop_gradient(lp["router_bias"])
    chosen = top_k_by(biased.reshape(-1, experts), k).reshape(scores.shape)
    gates = scores * chosen
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * sizes["routed_scaling"]
    y = jnp.zeros_like(x)
    for j in range(count):
        y = y + gates[..., first + j, None] * gated_mlp(
            x, lp["eg"][j], lp["eu"][j], lp["ed"][j]
        )
    return y, jnp.sum(chosen, axis=(0, 1))


def forward(params, tokens, sizes):
    """params: the zoo's tree (`stack`: the runs of layers in order; no
    `head`); tokens [B, L] -> (logits [B, L, vocab], tokens per expert
    [expert layers, E])."""
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    h = params["embed"][tokens]
    eps = sizes["eps"]
    loads = []
    for run in params["stack"]:
        for i in range(run["ln1"].shape[0]):
            lp = {name: leaf[i] for name, leaf in run.items()}
            x = _rms_norm(h, lp["ln1"], eps)
            if "in_proj" in lp:
                h = h + conv_mixer(lp, x)
            else:
                h = h + grouped_attention(lp, x, sizes)
            x = _rms_norm(h, lp["ln2"], eps)
            if "router" in lp:
                y, load = expert_layer(lp, x, sizes)
                h = h + y
                loads.append(load)
            else:
                h = h + gated_mlp(x, lp["wg"], lp["wu"], lp["wd"])
    logits = _rms_norm(h, params["ln_f"], eps) @ params["embed"].T
    return logits, jnp.stack(loads)


def parts(params, tokens, targets, sizes):
    """-> (loss, loads): the loss is the cross-entropy alone."""
    logits, loads = forward(params, tokens, sizes)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return ce, loads


def loss(params, tokens, targets, sizes):
    return parts(params, tokens, targets, sizes)[0]


def sizes_of(config, **overrides):
    """The reference's settings from a `config.json` of the released
    model's keys (`benchmark/configs/lfm2-24b-a2b/config.json`)."""
    sizes = {
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "eps": config["norm_eps"],
        "rope_base": float(config["rope_parameters"]["rope_theta"]),
        "top_k": config["num_experts_per_tok"],
        "held": tuple(config["held_experts"]),
        "routed_scaling": float(config["routed_scaling_factor"]),
    }
    sizes.update(overrides)
    return sizes
