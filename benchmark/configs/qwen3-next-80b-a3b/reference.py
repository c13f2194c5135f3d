"""Plain reference of the `qwen3-next-80b-a3b` block as the
configuration cuts it: the forward pass, the loss (cross-entropy over
the vocabulary's slice plus the weighted balance term) and its gradients in
straightforward `jax.numpy` and float32 — Python loops over the layers
(the stacked weights indexed, not scanned), the delta rule as the
recurrence itself, ONE TOKEN AT A TIME (`lax.scan` over time: no chunk,
no WY form, no triangular solve), every value head naming the key head
it reads by its index, attention ONE KEY-VALUE HEAD AT A TIME with the
query heads that read it named by their index and its scores written
out one block of queries at a time (so that 8192 tokens fit: a block's
keys are those its queries can see and no others), the experts as a
masked dense sum over the experts held here: no sort, no grouped
matmul, no recomputation, no kernel, no cast. It takes the zoo module's
parameter tree and imports nothing of the program. On a TPU set
`jax.default_matmul_precision("highest")` around it.

It follows the published `config.json` (Qwen/Qwen3-Next-80B-A3B-
Instruct, `model_type` `qwen3_next`) and, for what that leaves open,
the conventions `config.json`'s `assumed` lists:
- pre-norm residual block, RMS norm with a weight, no bias anywhere,
  an untied head;
- Gated DeltaNet (three layers in four): (q, k, v, z) = x W_qkvz, the
  columns q | k | v | z, q and k of 16 heads and v and z of 32, all of
  128; (b, a) = x W_ba (stored [64, d]: the write strength's rows, then
  the decay's); q | k | v through one causal depthwise convolution of 4
  taps, then SiLU; q and k over their lengths per head (1e-6 under the
  root), q x 128^-1/2 more; value head j reads key head j // 2; beta =
  sigmoid(b), g = -exp(A_log) softplus(a + dt_bias), one number a value
  head and token; per value head S' = exp(g_t) S_{t-1}, S_t = S' +
  beta_t k_t (v_t - S'^T k_t)^T, o_t = S_t^T q_t from S = 0; o normed
  over each head's 128 (one weight of 128) x SiLU(z); out = concat(o)
  W_o;
- gated attention (the fourth): (q, gate) = x W_q, the columns the
  queries then the gates; 16 query heads and 2 key-value heads of 256;
  query head i reads key-value head i // 8; q and k RMS-normed over
  their 256 (one weight each), then their first 64 columns turned, pair
  i = (x[i], x[i + 32]), base 1e7; softmax of q . k x 256^-1/2 over the
  keys u <= t; o x sigmoid(gate), a gate per channel; out = concat(o)
  W_o;
- softmax over all 512 router outputs, the 10 largest chosen (equal
  ones to the lower expert first), gates the chosen probabilities over
  their sum (all ten, held or not); the shared expert's output times
  sigmoid(x . w_s), one number a token; the balance term a layer is
  per sequence sum_e f_e P_e over all 512 experts, f_e = 512 / (10 s) x
  the tokens of the sequence that chose e (no gradient), P_e the
  sequence's mean probability of e, weight `aux_weight`;
- the cuts: only the experts `held` = (first, count) add to a layer's
  output (what the 504 others would add is left out, and that partial
  result goes on to the next layer); the vocabulary is one chip's slice
  of the rows of the embedding and the head.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _float32(params):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)


def gated_mlp(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def short_conv(x, taps):
    """x [B, L, C], taps [n, C]: y_t = silu(sum_i taps[i] x_{t-(n-1)+i}),
    zeros before the start."""
    n, length = taps.shape[0], x.shape[1]
    out = jnp.zeros_like(x)
    for i in range(n):
        shift = n - 1 - i  # tap i reads the token `shift` back
        moved = jnp.concatenate(
            [jnp.zeros_like(x[:, :shift]), x[:, :length - shift]], axis=1
        )
        out = out + moved * taps[i]
    return jax.nn.silu(out)


def delta_step(state, xs):
    """One token of the recurrence: state [B, H, dk, dv], xs = (q_t,
    k_t [B, H, dk], v_t [B, H, dv], g_t, beta_t [B, H]) -> (the next
    state, o_t). The decay is one number a head."""
    q_t, k_t, v_t, g_t, beta_t = xs
    decayed = jnp.exp(g_t)[..., None, None] * state
    read = jnp.einsum("bhkv,bhk->bhv", decayed, k_t)
    write = beta_t[..., None] * (v_t - read)
    state = decayed + k_t[..., :, None] * write[..., None, :]
    return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)


def delta_rule(q, k, v, g, beta):
    """The recurrence, a token at a time. q, k [B, L, H, dk], v
    [B, L, H, dv], g and beta [B, L, H] -> o [B, L, H, dv]."""
    batch, _, heads, dk = q.shape
    start = jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(
        delta_step, start,
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(out, 0, 1)


def delta_attention(lp, x, sizes):
    """x [B, L, d] normed -> [B, L, d]. lp: one Gated DeltaNet layer's
    leaves, `a_log` and `dt_bias` [value heads] among them."""
    batch, length, _ = x.shape
    kh, vh, hd = sizes["gdn_key_heads"], sizes["gdn_value_heads"], sizes["gdn_head_dim"]
    group = vh // kh
    projected = x @ lp["wqkvz"]
    qkv = short_conv(projected[..., :(2 * kh + vh) * hd], lp["conv"])
    z = projected[..., (2 * kh + vh) * hd:].reshape(batch, length, vh, hd)
    q = qkv[..., :kh * hd].reshape(batch, length, kh, hd)
    k = qkv[..., kh * hd:2 * kh * hd].reshape(batch, length, kh, hd)
    v = qkv[..., 2 * kh * hd:].reshape(batch, length, vh, hd)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * hd**-0.5
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    # value head j reads key head j // group
    reads = [j // group for j in range(vh)]
    q, k = q[:, :, reads], k[:, :, reads]
    ba = x @ lp["wba"].T  # stored [2 x value heads, d]
    beta = jax.nn.sigmoid(ba[..., :vh])
    g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(ba[..., vh:] + lp["dt_bias"])
    o = _rms_norm(delta_rule(q, k, v, g, beta), lp["out_norm"], sizes["eps"])
    o = o * jax.nn.silu(z)
    return o.reshape(batch, length, vh * hd) @ lp["wo"]


def rotate(x, sizes):
    """x [B, L, D] -> its first rope_dim columns turned by position, the
    rest as they are."""
    r = sizes["rope_dim"]
    half = r // 2
    frequencies = jnp.asarray(
        [sizes["rope_base"] ** (-2.0 * i / r) for i in range(half)], jnp.float32
    )
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * frequencies[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., r:]], axis=-1
    )


def block_attention(q, k, v, first_query):
    """One block of queries of the heads that read one key-value head:
    q [B, Q, G, D] at positions first_query.., k and v [B, U, D] at
    positions 0.. -> [B, Q, G, D]."""
    t = first_query + jnp.arange(q.shape[1])[:, None]
    u = jnp.arange(k.shape[1])[None, :]
    scores = jnp.einsum("bqgd,bud->bgqu", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where((u <= t)[None, None], scores, -jnp.inf)
    return jnp.einsum("bgqu,bud->bqgd", jax.nn.softmax(scores, axis=-1), v)


def gated_attention(lp, x, sizes):
    """x [B, L, d] normed -> [B, L, d]."""
    heads, kv_heads, hd = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
    group, length, eps = heads // kv_heads, x.shape[1], sizes["eps"]
    projected = x @ lp["wq"]  # the queries' columns, then the gates'
    q, gate = projected[..., :heads * hd], projected[..., heads * hd:]
    gate = jax.nn.sigmoid(gate)
    k, v = x @ lp["wk"], x @ lp["wv"]

    def head(y, i):
        return y[..., i * hd:(i + 1) * hd]

    out = []
    for j in range(kv_heads):
        # the query heads that read key-value head j: i // group == j
        mine = range(j * group, (j + 1) * group)
        k_j = rotate(_rms_norm(head(k, j), lp["k_norm"], eps), sizes)
        v_j = head(v, j)
        q_j = jnp.stack([
            rotate(_rms_norm(head(q, i), lp["q_norm"], eps), sizes)
            for i in mine
        ], axis=2)
        blocks = []
        for start in range(0, length, QUERY_BLOCK):
            end = min(start + QUERY_BLOCK, length)
            blocks.append(block_attention(
                q_j[:, start:end], k_j[:, :end], v_j[:, :end], start
            ))
        o_j = jnp.concatenate(blocks, axis=1)  # [B, L, G, D]
        out += [head(gate, i) * o_j[:, :, n] for n, i in enumerate(mine)]
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
    """x [B, L, d] normed -> (y, the balance term before its weight,
    tokens of each expert [E]). `held` =
    (first, count): the experts whose weights `lp` holds (`eg`, `eu`,
    `ed` stacked [count, ...]) and whose part is added; `shared` False
    leaves the gated shared expert out (the share test counts it
    once)."""
    experts, k = lp["router"].shape[-1], sizes["top_k"]
    first, count = held if held else sizes["held"]
    probs = jax.nn.softmax(x @ lp["router"], axis=-1)  # [B, L, E]
    chosen = top_k_by(probs.reshape(-1, experts), k).reshape(probs.shape)
    gates = probs * chosen
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    fraction = jnp.mean(chosen, axis=1) * experts / k  # [B, E], no gradient
    balance = jnp.mean(jnp.sum(fraction * jnp.mean(probs, axis=1), axis=-1))
    if shared:
        y = jax.nn.sigmoid(x @ lp["sgate"].T) * gated_mlp(
            x, lp["sg"], lp["su"], lp["sd"]
        )
    else:
        y = jnp.zeros_like(x)
    for j in range(count):
        y = y + gates[..., first + j, None] * gated_mlp(
            x, lp["eg"][j], lp["eu"][j], lp["ed"][j]
        )
    return y, balance, jnp.sum(chosen, axis=(0, 1))


def layer(lp, h, kind, sizes):
    """One block on the residual stream h [B, L, d] -> (h, the layer's
    balance term, tokens of each expert [E]): h + mixer(norm(h)), then
    h + moe(norm(h)); `lp` the layer's own leaves, `kind` "linear" or
    "full"."""
    eps = sizes["eps"]
    mixer = delta_attention if kind == "linear" else gated_attention
    h = h + mixer(lp, _rms_norm(h, lp["ln1"], eps), sizes)
    y, balance, load = expert_layer(lp, _rms_norm(h, lp["ln2"], eps), sizes)
    return h + y, balance, load


def layers_of(params, sizes):
    """The stack's layers in order, each as its own leaves; a Gated
    DeltaNet layer's `a_log` and `dt_bias` cut from the one flat leaf
    that holds them for all such layers: [a_log | dt_bias], each in
    stack order."""
    vh, seen = sizes["gdn_value_heads"], 0
    half = params["gdn_decay"].shape[0] // 2
    for run in params["stack"]:
        for i in range(run["ln1"].shape[0]):
            lp = {name: leaf[i] for name, leaf in run.items()}
            if "wqkvz" in lp:
                at = slice(seen * vh, (seen + 1) * vh)
                lp["a_log"] = params["gdn_decay"][:half][at]
                lp["dt_bias"] = params["gdn_decay"][half:][at]
                seen += 1
            yield lp


def head_loss(ln_f, head, h, targets, sizes):
    """-> (mean next-token cross-entropy over the vocabulary's slice,
    the logits)."""
    logits = _rms_norm(h, ln_f, sizes["eps"]) @ head
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return ce, logits


def forward(params, tokens, sizes):
    """params: the zoo's tree (`stack`: the runs of layers in order);
    tokens [B, L] -> (the last layer's output [B, L, d], the layers'
    summed balance term, tokens per expert [layers, E]).
    `sizes["kinds"]` names each layer's kind in order."""
    params = _float32(params)
    h = params["embed"][tokens]
    balance, loads = 0.0, []
    for lp, kind in zip(layers_of(params, sizes), sizes["kinds"]):
        h, term, load = layer(lp, h, kind, sizes)
        balance = balance + term
        loads.append(load)
    return h, balance, jnp.stack(loads)


def logits_of(params, tokens, sizes):
    params = _float32(params)
    h, _balance, _loads = forward(params, tokens, sizes)
    return head_loss(params["ln_f"], params["head"], h, tokens, sizes)[1]


def parts(params, tokens, targets, sizes):
    """-> (loss, loads): the cross-entropy plus `aux_weight` x the
    layers' summed balance term."""
    params = _float32(params)
    h, balance, loads = forward(params, tokens, sizes)
    ce = head_loss(params["ln_f"], params["head"], h, targets, sizes)[0]
    return ce + sizes["aux_weight"] * balance, loads


def loss(params, tokens, targets, sizes):
    return parts(params, tokens, targets, sizes)[0]


def sizes_of(config, **overrides):
    """The reference's settings from a `config.json` of the released
    model's keys (`benchmark/configs/qwen3-next-80b-a3b/config.json`)."""
    first, count = config["held_layers"]
    sizes = {
        "gdn_key_heads": config["linear_num_key_heads"],
        "gdn_value_heads": config["linear_num_value_heads"],
        "gdn_head_dim": config["linear_key_head_dim"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rope_base": float(config["rope_theta"]),
        "rope_dim": int(config["head_dim"] * config["partial_rotary_factor"]),
        "eps": config["rms_norm_eps"],
        "top_k": config["num_experts_per_tok"],
        "aux_weight": config["balance_term"]["weight_a_layer"],
        "held": tuple(config["held_experts"]),
        "kinds": tuple(
            {"linear_attention": "linear", "full_attention": "full"}[n]
            for n in config["layer_types"][first:first + count]
        ),
    }
    sizes.update(overrides)
    return sizes
