"""Plain reference of the `smallthinker-21b-a3b` block as the
configuration cuts it: the forward pass, the loss (cross-entropy over
the vocabulary's slice; no balance term) and its gradients in
straightforward `jax.numpy` and float32 — Python loops over the layers
(the stacked weights indexed, not scanned), attention ONE KEY-VALUE
HEAD AT A TIME with the query heads that read it named by their index,
its scores written out one block of queries at a time (so that 16,384
tokens fit: a block's keys are those its queries can see and no
others), the band and the diagonal as a comparison of positions, the
experts as a masked dense sum over the experts held here: no sort, no
grouped matmul, no recomputation, no kernel, no cast. It takes the zoo
module's parameter tree and imports nothing of the program. On a TPU
set `jax.default_matmul_precision("highest")` around it.

It follows the published `config.json` (PowerInfer/
SmallThinker-21BA3B-Instruct, `model_name` `smallthinker_21b_instruct`)
and, for what that leaves open, the conventions `config.json`'s
`assumed` lists. A layer with input x [T, 2560]:
- THE ROUTER FIRST, on x itself, before any norm: s = x W_r [T, 64];
  the 6 largest logits chosen (equal ones to the lower expert first);
  gates the softmax over those six logits
  (`moe_primary_router_apply_softmax`; `norm_topk_prob` is then the
  identity), on the expert's output;
- a = ln1(x), an RMS norm with a weight, eps 1e-6; 28 query heads and
  4 key-value heads of 128, no bias, no norm on queries or keys; query
  head i reads key-value head i // 7; on a `sliding` layer
  (`sliding_window_layout` 1, `rope_layout` 1) queries and keys turn
  whole, pair i = (x[i], x[i + 64]), theta 1,500,000, and the query at t
  sees the keys u with 0 <= t - u < 4096; on a `full` layer (both 0)
  NOTHING turns and the mask is the causal triangle; softmax of
  q . k x 128^-1/2; h = x + concat(heads) W_o;
- u = ln2(h); expert e is W_d (relu(W_g u) * W_u u), inner width 768;
  y = h + sum over the chosen experts of gate_e E_e(u). No shared
  expert, no dense layer;
- a final RMS norm and an untied head.

Departures from the published description, each a cut the
configuration states: only the experts `held` = (first, count) add to a
layer's output (what the 56 others would add is left out, and that
partial result goes on to the next layer); the vocabulary is one chip's
slice of the rows of the embedding and the head; four layers of 52.
`router_reads` names the tensor the router reads, "input" as assumed;
"ln1" (the other reading of "before attention") and "ln2" (the usual
placement) are there for the comparison's controls and the tests.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _float32(params):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)


def gated_relu_mlp(x, wg, wu, wd):
    return (jax.nn.relu(x @ wg) * (x @ wu)) @ wd


def rotate(x, base):
    """x [B, L, D] -> the whole head turned by position: pair i is
    (x[i], x[i + D/2]), its angle position x base^(-2i/D)."""
    half = x.shape[-1] // 2
    freqs = jnp.asarray(
        [base ** (-i / half) for i in range(half)], jnp.float32
    )
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


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
    settings (`sizes["full"]` or `sizes["sliding"]`: `window`, None for
    the whole triangle, and `turns`)."""
    heads, kv_heads, hd = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
    group, window, length = heads // kv_heads, kind["window"], x.shape[1]
    q, k, v = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]

    def head(y, i):
        y = y[..., i * hd:(i + 1) * hd]
        return rotate(y, sizes["rope_base"]) if kind["turns"] else y

    out = []
    for j in range(kv_heads):
        # the query heads that read key-value head j: i // group == j
        mine = range(j * group, (j + 1) * group)
        k_j, v_j = head(k, j), v[..., j * hd:(j + 1) * hd]
        q_j = jnp.stack([head(q, i) for i in mine], axis=2)
        blocks = []
        for start in range(0, length, QUERY_BLOCK):
            end = min(start + QUERY_BLOCK, length)
            first = 0 if window is None else max(0, start - window + 1)
            blocks.append(block_attention(
                q_j[:, start:end], k_j[:, first:end], v_j[:, first:end],
                start, first, window,
            ))
        o_j = jnp.concatenate(blocks, axis=1)  # [B, L, G, D]
        out += [o_j[:, :, n] for n in range(group)]
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


def route(lp, x, sizes):
    """The router on x [B, L, d], whatever tensor that is -> (gates
    [B, L, E], zero off the chosen; the one-hot choices [B, L, E])."""
    logits = x @ lp["router"]
    experts = logits.shape[-1]
    chosen = top_k_by(logits.reshape(-1, experts), sizes["top_k"]).reshape(
        logits.shape
    )
    # the softmax over the chosen logits alone
    gates = jax.nn.softmax(jnp.where(chosen > 0, logits, -jnp.inf), axis=-1)
    return gates, chosen


def expert_layer(lp, u, gates, sizes, held=None):
    """u [B, L, d] normed, gates [B, L, E] -> y [B, L, d]. `held` =
    (first, count): the experts whose weights `lp` holds (`eg`, `eu`,
    `ed` stacked [count, ...]) and whose part is added."""
    first, count = held if held else sizes["held"]
    y = jnp.zeros_like(u)
    for j in range(count):
        y = y + gates[..., first + j, None] * gated_relu_mlp(
            u, lp["eg"][j], lp["eu"][j], lp["ed"][j]
        )
    return y


def layer(lp, x, kind, sizes, held=None):
    """One block on the residual stream x [B, L, d] -> (y, tokens of
    each expert [E]); `lp` the layer's own leaves, `kind` its kind's
    settings."""
    eps, reads = sizes["eps"], sizes.get("router_reads", "input")
    a = _rms_norm(x, lp["ln1"], eps)
    if reads != "ln2":  # ahead of the attention: nothing of it is seen
        gates, chosen = route(lp, x if reads == "input" else a, sizes)
    h = x + attention_mixer(lp, a, kind, sizes)
    u = _rms_norm(h, lp["ln2"], eps)
    if reads == "ln2":
        gates, chosen = route(lp, u, sizes)
    return h + expert_layer(lp, u, gates, sizes, held), jnp.sum(
        chosen, axis=(0, 1)
    )


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
    expert [layers, E]). `sizes["kinds"]` names each layer's kind in
    order."""
    params = _float32(params)
    h = params["embed"][tokens]
    loads = []
    for lp, kind in zip(layers_of(params), sizes["kinds"]):
        h, load = layer(lp, h, sizes[kind], sizes)
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
    model's keys (`benchmark/configs/smallthinker-21b-a3b/config.json`)."""
    first, count = config["held_layers"]
    windowed = config["sliding_window_layout"][first:first + count]
    turned = config["rope_layout"][first:first + count]
    kinds = tuple("sliding" if w else "full" for w in windowed)

    def kind(name):
        at = kinds.index(name)
        return {
            "window": config["sliding_window_size"] if windowed[at] else None,
            "turns": bool(turned[at]),
        }

    sizes = {
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rope_base": float(config["rope_theta"]),
        "eps": config["rms_norm_eps"],
        "top_k": config["moe_num_active_primary_experts"],
        "held": tuple(config["held_experts"]),
        "router_reads": "input",
        "kinds": kinds,
        **{name: kind(name) for name in sorted(set(kinds))},
    }
    sizes.update(overrides)
    return sizes
