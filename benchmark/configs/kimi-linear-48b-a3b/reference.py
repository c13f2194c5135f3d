"""Plain reference of the `kimi-linear-48b-a3b` block as the
configuration cuts it: the forward pass, the loss (cross-entropy over
the vocabulary's slice; no balance term) and its gradients in
straightforward `jax.numpy` and float32 — Python loops over the layers
(the stacked weights indexed, not scanned), Kimi Delta Attention as the
recurrence itself, ONE TOKEN AT A TIME (`lax.scan` over time: no
chunk, no triangular system, no cumulative decay), latent attention
with its scores written out, the experts as a masked dense sum over the
experts held here: no sort, no grouped matmul, no recomputation, no
kernel, no cast. It takes the zoo module's parameter tree and imports
nothing of the program. On a TPU set
`jax.default_matmul_precision("highest")` around it.

It follows the published `config.json` (moonshotai/Kimi-Linear-48B-A3B-
Instruct) and, for what that leaves open, the family's conventions;
each is also in `config.json`'s `assumed`:
- pre-norm residual block, RMS norm, no bias anywhere, untied head;
- KDA: q, k, v projected, a causal depthwise convolution of 4 taps and
  SiLU on each, per head q / |q| x 128^-1/2 and k / |k|; log-decay per
  key channel g = -exp(A_log_h) softplus(W_f_up W_f_down x + dt_bias);
  beta = sigmoid(W_beta x); S' = Diag(exp g) S; S = S' + beta k (v -
  S'^T k)^T; o = S^T q; y = W_o [RMSNorm_128(o) * sigmoid(W_g_up
  W_g_down x)];
- latent attention without a query latent and WITHOUT rotation
  (`mla_use_nope`): scores (q . k) x 192^-1/2 over the 128 columns a
  head's key has of its own and the 64 every head shares;
- sigmoid scores over all 256 router outputs, the 8 largest of score +
  bias chosen (equal ones to the lower expert first), gates the chosen
  scores over their sum (all eight, held or not) x 2.446; the bias is
  not in the gate;
- the cuts: only the experts `held` = (first, count) add to a layer's
  output (what the 248 others would add is left out, and that partial
  result goes on to the next layer); the vocabulary is one chip's slice
  of the rows of the embedding and of the head.
"""

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


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
    k_t, v_t, g_t [B, H, d], beta_t [B, H]) -> (the next state, o_t)."""
    q_t, k_t, v_t, g_t, beta_t = xs
    decayed = jnp.exp(g_t)[..., None] * state
    read = jnp.einsum("bhkv,bhk->bhv", decayed, k_t)
    write = beta_t[..., None] * (v_t - read)
    state = decayed + k_t[..., :, None] * write[..., None, :]
    return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)


def delta_rule(q, k, v, g, beta):
    """The recurrence, a token at a time. q, k, g [B, L, H, dk], v
    [B, L, H, dv], beta [B, L, H] -> o [B, L, H, dv]."""
    batch, _, heads, dk = q.shape
    start = jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(
        delta_step, start,
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(out, 0, 1)


def delta_attention(lp, a_log, x, sizes):
    """x [B, L, d] normed -> [B, L, d]. lp: one KDA layer's leaves;
    a_log [heads]."""
    batch, length, _ = x.shape
    heads, hd = sizes["kda_heads"], sizes["kda_head_dim"]

    def per_head(y):
        return y.reshape(batch, length, heads, hd)

    q = per_head(short_conv(x @ lp["wq"], lp["conv_q"]))
    k = per_head(short_conv(x @ lp["wk"], lp["conv_k"]))
    v = per_head(short_conv(x @ lp["wv"], lp["conv_v"]))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-12) * hd**-0.5
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-12)
    step = jax.nn.softplus((x @ lp["f_down"]) @ lp["f_up"] + lp["dt_bias"])
    g = -jnp.exp(a_log)[:, None] * per_head(step)
    beta = jax.nn.sigmoid(x @ lp["wbeta"].T)  # stored [heads, d]
    o = _rms_norm(delta_rule(q, k, v, g, beta), lp["o_norm"], sizes["eps"])
    gate = jax.nn.sigmoid(per_head((x @ lp["g_down"]) @ lp["g_up"]))
    return (o * gate).reshape(batch, length, heads * hd) @ lp["wo"]


def latent_attention(lp, x, sizes):
    """x [B, L, d] normed -> [B, L, d]; nothing is rotated."""
    batch, length, _ = x.shape
    heads, rank = sizes["heads"], sizes["kv_lora_rank"]
    nope, shared, vdim = sizes["qk_nope"], sizes["qk_rope"], sizes["v_head"]
    q = (x @ lp["wq"]).reshape(batch, length, heads, nope + shared)
    kva = x @ lp["wkva"]
    latent = _rms_norm(kva[..., :rank], lp["kv_norm"], sizes["eps"])
    kv = (latent @ lp["wkvb"]).reshape(batch, length, heads, nope + vdim)
    k_shared = kva[..., rank:].reshape(batch, length, 1, shared)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.tile(k_shared, (1, 1, heads, 1))], -1
    )
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (nope + shared) ** -0.5
    causal = jnp.tril(jnp.ones((length, length), dtype=bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attended = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), kv[..., nope:]
    )
    return attended.reshape(batch, length, heads * vdim) @ lp["wo"]


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
    `ed` stacked [count, ...]) and whose part is added."""
    experts, k = lp["router"].shape[-1], sizes["top_k"]
    first, count = held if held else sizes["held"]
    scores = jax.nn.sigmoid(x @ lp["router"])  # [B, L, E]
    biased = scores + jax.lax.stop_gradient(lp["router_bias"])
    chosen = top_k_by(biased.reshape(-1, experts), k).reshape(scores.shape)
    gates = scores * chosen
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * sizes["routed_scaling"]
    y = gated_mlp(x, lp["sg"], lp["su"], lp["sd"])
    for j in range(count):
        y = y + gates[..., first + j, None] * gated_mlp(
            x, lp["eg"][j], lp["eu"][j], lp["ed"][j]
        )
    return y, jnp.sum(chosen, axis=(0, 1))


def forward(params, tokens, sizes):
    """params: the zoo's tree (`stack`: the runs of layers in order,
    `kda_a_log`: [KDA layers x heads], flat); tokens [B, L] -> (logits
    [B, L, vocab], tokens per expert [expert layers, E])."""
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    h = params["embed"][tokens]
    eps = sizes["eps"]
    loads, kda_seen = [], 0
    a_log = params.get("kda_a_log", jnp.zeros(0)).reshape(-1, sizes["kda_heads"])
    for run in params["stack"]:
        for i in range(run["ln1"].shape[0]):
            lp = {name: leaf[i] for name, leaf in run.items()}
            x = _rms_norm(h, lp["ln1"], eps)
            if "wkva" in lp:
                h = h + latent_attention(lp, x, sizes)
            else:
                h = h + delta_attention(lp, a_log[kda_seen], x, sizes)
                kda_seen += 1
            x = _rms_norm(h, lp["ln2"], eps)
            if "router" in lp:
                y, load = expert_layer(lp, x, sizes)
                h = h + y
                loads.append(load)
            else:
                h = h + gated_mlp(x, lp["wg"], lp["wu"], lp["wd"])
    logits = _rms_norm(h, params["ln_f"], eps) @ params["head"]
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
    model's keys (`benchmark/configs/kimi-linear-48b-a3b/config.json`)."""
    linear = config["linear_attn_config"]
    sizes = {
        "heads": config["num_attention_heads"],
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope": config["qk_nope_head_dim"],
        "qk_rope": config["qk_rope_head_dim"],
        "v_head": config["v_head_dim"],
        "kda_heads": linear["num_heads"],
        "kda_head_dim": linear["head_dim"],
        "eps": config["rms_norm_eps"],
        "top_k": config["num_experts_per_token"],
        "held": tuple(config["held_experts"]),
        "routed_scaling": float(config["routed_scaling_factor"]),
    }
    sizes.update(overrides)
    return sizes
