"""Plain reference of the `laguna-xs2` block as the configuration cuts
it: the forward pass, the loss (cross-entropy over the vocabulary's
slice; no balance term) and its gradients in straightforward
`jax.numpy` and float32 — Python loops over the layers (the stacked
weights indexed, not scanned), attention ONE KEY-VALUE HEAD AT A TIME
with the query heads that read it named by their index, its scores
written out one block of queries at a time (so that 8192 tokens fit:
a block's keys are those its queries can see and no others), the band
and the diagonal as a comparison of positions, the experts as a masked
dense sum over the experts held here: no sort, no grouped matmul, no
recomputation, no kernel, no cast. It takes the zoo module's parameter
tree and imports nothing of the program. On a TPU set
`jax.default_matmul_precision("highest")` around it.

It follows the published `config.json` (poolside/Laguna-XS.2,
`model_type` `laguna`) and, for what that leaves open, the conventions
`config.json`'s `assumed` lists:
- pre-norm residual block, RMS norm with a weight, no bias anywhere,
  an untied head;
- attention, a layer of kind `full` or `sliding`: H query heads (48 |
  64) and 8 key-value heads of 128; query head i reads key-value head
  i // (H / 8); queries and keys rotated, pair i = (x[i], x[i + r/2])
  inside the first r columns and the rest as projected: `sliding` r =
  128 at base 10000; `full` r = 64 by YaRN's blended frequencies (base
  500000, factor 64, original length 4096, beta_fast 64, beta_slow 1)
  with cosine and sine times the attention factor; softmax of
  q . k x 128^-1/2 over the keys u <= t and, on a sliding layer,
  t - u < 512; each head's output times sigmoid(x . w_h), its gate;
  out = concat(heads) W_o;
- softmax over all 256 router outputs, the 8 largest chosen (equal
  ones to the lower expert first), gates the chosen probabilities over
  their sum (all eight, held or not) x 2.5; one shared expert added
  ungated;
- the cuts: only the experts `held` = (first, count) add to a layer's
  output (what the 240 others would add is left out, and that partial
  result goes on to the next layer); the vocabulary is one chip's slice
  of the rows of the embedding and the head.
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _float32(params):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)


def gated_mlp(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def rotary_frequencies(kind):
    """The rope_dim / 2 frequencies of a layer kind, float32: base^(-2i
    / rope_dim), and under YaRN (Peng et al. 2023) that blended with
    the same over `factor`: a pair that turns more than beta_fast times
    over the original length keeps its frequency, one that turns less
    than beta_slow times takes the divided one, a linear ramp between."""
    dim, base = kind["rope_dim"], kind["rope_base"]
    plain = [base ** (-2.0 * i / dim) for i in range(dim // 2)]
    yarn = kind["yarn"]
    if yarn is None:
        return jnp.asarray(plain, jnp.float32)

    def pair_that_turns(times):
        return dim * math.log(
            yarn["original_length"] / (times * 2 * math.pi)
        ) / (2 * math.log(base))

    low = max(math.floor(pair_that_turns(yarn["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(yarn["beta_slow"])), dim - 1)
    blended = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        blended.append(f / yarn["factor"] * ramp + f * (1.0 - ramp))
    return jnp.asarray(blended, jnp.float32)


def rotate(x, kind):
    """x [B, L, D] -> its first rope_dim columns turned by position
    (cosine and sine times the attention factor), the rest as they
    are."""
    r = kind["rope_dim"]
    half = r // 2
    angle = (
        jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
        * rotary_frequencies(kind)[None, :]
    )
    cos = jnp.cos(angle) * kind["attention_factor"]
    sin = jnp.sin(angle) * kind["attention_factor"]
    x1, x2 = x[..., :half], x[..., half:r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., r:]], axis=-1
    )


def block_attention(q, k, v, first_query, first_key, window):
    """One block of queries of the heads that read one key-value head:
    q [B, Q, G, D] at positions first_query.., k and v [B, U, D] at
    positions first_key.. -> [B, Q, G, D]."""
    t = first_query + jnp.arange(q.shape[1])[:, None]
    u = first_key + jnp.arange(k.shape[1])[None, :]
    seen = u <= t
    if window is not None:
        seen = seen & (t - u < window)
    scores = jnp.einsum("bqgd,bud->bgqu", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("bgqu,bud->bqgd", jax.nn.softmax(scores, axis=-1), v)


def attention_mixer(lp, x, kind, sizes):
    """x [B, L, d] normed -> [B, L, d]; `kind` the layer kind's
    settings (`sizes["full"]` or `sizes["sliding"]`)."""
    heads, kv_heads, hd = kind["heads"], sizes["kv_heads"], sizes["head_dim"]
    group, window, length = heads // kv_heads, kind["window"], x.shape[1]
    q, k, v = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
    gate = jax.nn.sigmoid(x @ lp["wgate"].T)  # [B, L, heads]

    def head(y, i):
        return y[..., i * hd:(i + 1) * hd]

    out = []
    for j in range(kv_heads):
        # the query heads that read key-value head j: i // group == j
        mine = range(j * group, (j + 1) * group)
        k_j, v_j = rotate(head(k, j), kind), head(v, j)
        q_j = jnp.stack([rotate(head(q, i), kind) for i in mine], axis=2)
        blocks = []
        for start in range(0, length, QUERY_BLOCK):
            end = min(start + QUERY_BLOCK, length)
            first = 0 if window is None else max(0, start - window + 1)
            blocks.append(block_attention(
                q_j[:, start:end], k_j[:, first:end], v_j[:, first:end],
                start, first, window,
            ))
        o_j = jnp.concatenate(blocks, axis=1)  # [B, L, G, D]
        out += [gate[..., i, None] * o_j[:, :, n] for n, i in enumerate(mine)]
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


def expert_layer(lp, x, sizes, held=None, shared=True):
    """x [B, L, d] normed -> (y, tokens of each expert [E]). `held` =
    (first, count): the experts whose weights `lp` holds (`eg`, `eu`,
    `ed` stacked [count, ...]) and whose part is added; `shared` False
    leaves the shared expert out (the share test counts it once)."""
    experts, k = lp["router"].shape[-1], sizes["top_k"]
    first, count = held if held else sizes["held"]
    probs = jax.nn.softmax(x @ lp["router"], axis=-1)  # [B, L, E]
    chosen = top_k_by(probs.reshape(-1, experts), k).reshape(probs.shape)
    gates = probs * chosen
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * sizes["routed_scaling"]
    y = gated_mlp(x, lp["sg"], lp["su"], lp["sd"]) if shared else (
        jnp.zeros_like(x)
    )
    for j in range(count):
        y = y + gates[..., first + j, None] * gated_mlp(
            x, lp["eg"][j], lp["eu"][j], lp["ed"][j]
        )
    return y, jnp.sum(chosen, axis=(0, 1))


def layer(lp, h, kind, sizes):
    """One block on the residual stream h [B, L, d] -> (h, tokens of
    each expert [E], or None for the dense layer): h + mixer(norm(h)),
    then h + ffn(norm(h)); `lp` the layer's own leaves, `kind` its
    kind's settings."""
    eps = sizes["eps"]
    h = h + attention_mixer(lp, _rms_norm(h, lp["ln1"], eps), kind, sizes)
    x = _rms_norm(h, lp["ln2"], eps)
    if "router" in lp:
        y, load = expert_layer(lp, x, sizes)
        return h + y, load
    return h + gated_mlp(x, lp["wg"], lp["wu"], lp["wd"]), None


def layers_of(params):
    """The stack's layers in order, each as its own leaves."""
    for run in params["stack"]:
        for i in range(run["ln1"].shape[0]):
            yield {name: leaf[i] for name, leaf in run.items()}


def head_loss(ln_f, head, h, targets, sizes):
    """-> (mean next-token cross-entropy over the vocabulary's slice,
    the logits)."""
    logits = _rms_norm(h, ln_f, sizes["eps"]) @ head
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return ce, logits


def forward(params, tokens, sizes):
    """params: the zoo's tree (`stack`: the runs of layers in order);
    tokens [B, L] -> (the last layer's output [B, L, d], tokens per
    expert [expert layers, E]). `sizes["kinds"]` names each layer's
    kind in order."""
    params = _float32(params)
    h = params["embed"][tokens]
    loads = []
    for lp, kind in zip(layers_of(params), sizes["kinds"]):
        h, load = layer(lp, h, sizes[kind], sizes)
        if load is not None:
            loads.append(load)
    return h, jnp.stack(loads)


def logits_of(params, tokens, sizes):
    params = _float32(params)
    h, _loads = forward(params, tokens, sizes)
    return head_loss(params["ln_f"], params["head"], h, tokens, sizes)[1]


def parts(params, tokens, targets, sizes):
    """-> (loss, loads): the loss is the cross-entropy alone."""
    params = _float32(params)
    h, loads = forward(params, tokens, sizes)
    return head_loss(params["ln_f"], params["head"], h, targets, sizes)[0], loads


def loss(params, tokens, targets, sizes):
    return parts(params, tokens, targets, sizes)[0]


def sizes_of(config, **overrides):
    """The reference's settings from a `config.json` of the released
    model's keys (`benchmark/configs/laguna-xs2/config.json`)."""
    first, count = config["held_layers"]
    per_layer = config["num_attention_heads_per_layer"][first:first + count]
    names = config["layer_types"][first:first + count]
    hd = config["head_dim"]

    def kind(name, window):
        rope = config["rope_parameters"][name]
        yarn = rope["rope_type"] == "yarn"
        return {
            "heads": per_layer[names.index(name)],
            "window": window,
            "rope_base": float(rope["rope_theta"]),
            "rope_dim": int(hd * rope["partial_rotary_factor"]),
            "attention_factor": float(rope["attention_factor"]) if yarn else 1.0,
            "yarn": {
                "factor": float(rope["factor"]),
                "beta_fast": float(rope["beta_fast"]),
                "beta_slow": float(rope["beta_slow"]),
                "original_length": rope["original_max_position_embeddings"],
            } if yarn else None,
        }

    sizes = {
        "kv_heads": config["num_key_value_heads"],
        "head_dim": hd,
        "eps": config["rms_norm_eps"],
        "top_k": config["num_experts_per_tok"],
        "held": tuple(config["held_experts"]),
        "routed_scaling": float(config["moe_routed_scaling_factor"]),
        "kinds": tuple(
            {"full_attention": "full", "sliding_attention": "sliding"}[n]
            for n in names
        ),
        "full": kind("full_attention", None),
        "sliding": kind("sliding_attention", config["sliding_window"]),
    }
    sizes.update(overrides)
    return sizes
