"""Plain reference of the `deepseek-v2-lite` block as the configuration
cuts it: the forward pass, the loss (cross-entropy plus the router's
sequence-wise balance term) and its gradients in straightforward
`jax.numpy` and float32 — Python loops over the layers (the stacked
weights indexed, not scanned), attention written out, the experts as a
masked dense sum over the experts held here: no sort, no grouped or
ragged matmul, no scan, no recomputation, no kernel, no cast. It takes
the zoo module's parameter tree and imports nothing of the program. On
a TPU set `jax.default_matmul_precision("highest")` around it.

It follows the published description (DeepSeek-V2-Lite `config.json`;
DeepSeek-AI 2024, "DeepSeek-V2", sections 2.1 and 2.2; the released
modelling file for what the paper leaves open). Each departure and
assumption is also in `config.json`'s `assumed`:
- pre-norm residual block, RMS norm, no bias anywhere, untied head;
- latent attention without a query latent (`q_lora_rank` null);
- the turning parts of query and key are laid out as halves, pair i =
  (x[i], x[i + 32]); the released file stores them interleaved and
  permutes to halves before it turns them: a fixed permutation of 64
  columns of seeded weights;
- YaRN: frequencies blended as the released file does, the cos/sin
  multiplier mscale / mscale_all_dim = 1, the softmax scale
  192^-0.5 x (0.1 x 0.707 x ln 40 + 1)^2;
- greedy top-6 of the softmax over all 64 router outputs, weights not
  renormalised, equal probabilities to the lower expert first;
- the balance term is `seq_aux`'s, over all 64 experts, weight 0.001;
- the cuts: only the experts `held` = (first, count) add to a layer's
  output (what the 56 others would add is left out, and that partial
  result goes on to the next layer); the vocabulary is one chip's slice
  of the rows of the embedding and of the head.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def yarn_frequencies(dim, base, yarn):
    """[dim / 2]: base^(-2i/dim), blended under YaRN with the same over
    `factor`. `yarn`: {factor, beta_fast, beta_slow,
    original_max_position_embeddings} or None."""
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not yarn:
        return jnp.asarray(plain, jnp.float32)
    length = yarn["original_max_position_embeddings"]

    def pair_that_turns(times):
        return dim * math.log(length / (times * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair_that_turns(yarn["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(yarn["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0, 1)
    return jnp.asarray(plain / yarn["factor"] * ramp + plain * (1 - ramp), jnp.float32)


def softmax_scale(width, yarn):
    if not yarn:
        return width**-0.5
    mscale = 0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1.0
    return width**-0.5 * mscale * mscale


def _rotary(x, freqs):
    """x: [B, L, H, D]; pair (i, i + D/2) turns by position x freqs[i]."""
    length, half = x.shape[1], x.shape[-1] // 2
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def latent_attention(lp, x, sizes):
    """x [B, L, d] normed -> [B, L, d]. lp: one layer's wq, wkva,
    kv_norm, wkvb, wo."""
    batch, length, _ = x.shape
    heads, rank = sizes["heads"], sizes["kv_lora_rank"]
    nope, rot, vdim = sizes["qk_nope"], sizes["qk_rope"], sizes["v_head"]
    freqs = yarn_frequencies(rot, sizes["rope_base"], sizes["yarn"])
    q = (x @ lp["wq"]).reshape(batch, length, heads, nope + rot)
    kva = x @ lp["wkva"]
    latent = _rms_norm(kva[..., :rank], lp["kv_norm"], sizes["eps"])
    k_pe = _rotary(kva[..., rank:].reshape(batch, length, 1, rot), freqs)
    kv = (latent @ lp["wkvb"]).reshape(batch, length, heads, nope + vdim)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], freqs)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.tile(k_pe, (1, 1, heads, 1))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * softmax_scale(
        nope + rot, sizes["yarn"]
    )
    causal = jnp.tril(jnp.ones((length, length), dtype=bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attended = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), kv[..., nope:]
    )
    return attended.reshape(batch, length, heads * vdim) @ lp["wo"]


def gated_mlp(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def greedy_top_k(probs, k):
    """[T, E] -> one-hot choices [T, E] of the k largest of each row,
    taken one at a time; among equals the lowest expert first."""
    chosen = jnp.zeros_like(probs)
    left = probs
    for _ in range(k):
        pick = jax.nn.one_hot(jnp.argmax(left, axis=-1), probs.shape[-1])
        chosen = chosen + pick
        left = jnp.where(pick > 0, -jnp.inf, left)
    return jax.lax.stop_gradient(chosen)


def expert_layer(lp, x, sizes, held=None):
    """x [B, L, d] normed -> (y, balance term, tokens of each expert
    [E]). `held` = (first, count): the experts whose weights `lp` holds
    (`eg`, `eu`, `ed` stacked [count, ...]) and whose part is added."""
    batch, length, width = x.shape
    experts, k = lp["router"].shape[-1], sizes["top_k"]
    first, count = held if held else sizes["held"]
    probs = jax.nn.softmax(x @ lp["router"], axis=-1)  # [B, L, E]
    chosen = greedy_top_k(probs.reshape(-1, experts), k).reshape(probs.shape)
    y = gated_mlp(x, lp["sg"], lp["su"], lp["sd"])
    for j in range(count):
        weight = (probs * chosen)[..., first + j] * sizes["routed_scaling"]
        y = y + weight[..., None] * gated_mlp(
            x, lp["eg"][j], lp["eu"][j], lp["ed"][j]
        )
    fraction = jnp.sum(chosen, axis=1) * experts / (k * length)  # [B, E]
    balance = jnp.mean(jnp.sum(fraction * jnp.mean(probs, axis=1), axis=-1))
    return y, balance, jnp.sum(chosen, axis=(0, 1))


def forward(params, tokens, sizes):
    """params: the zoo's tree; tokens [B, L] -> (logits [B, L, vocab],
    the layers' summed balance term, tokens per expert [layers, E])."""
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    h = params["embed"][tokens]
    eps = sizes["eps"]
    dense, layers = params["dense"], params["layers"]
    for i in range(dense["wq"].shape[0]):
        lp = {name: leaf[i] for name, leaf in dense.items()}
        h = h + latent_attention(lp, _rms_norm(h, lp["ln1"], eps), sizes)
        h = h + gated_mlp(_rms_norm(h, lp["ln2"], eps), lp["wg"], lp["wu"], lp["wd"])
    balance, loads = 0.0, []
    for i in range(layers["wq"].shape[0]):
        lp = {name: leaf[i] for name, leaf in layers.items()}
        h = h + latent_attention(lp, _rms_norm(h, lp["ln1"], eps), sizes)
        y, term, load = expert_layer(lp, _rms_norm(h, lp["ln2"], eps), sizes)
        h, balance = h + y, balance + term
        loads.append(load)
    logits = _rms_norm(h, params["ln_f"], eps) @ params["head"]
    return logits, balance, jnp.stack(loads)


def parts(params, tokens, targets, sizes):
    """-> (loss, cross-entropy, balance term unweighted, loads)."""
    logits, balance, loads = forward(params, tokens, sizes)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return ce + sizes["aux_weight"] * balance, ce, balance, loads


def loss(params, tokens, targets, sizes):
    return parts(params, tokens, targets, sizes)[0]


def sizes_of(config, **overrides):
    """The reference's settings from a `config.json` of the released
    model's keys (`benchmark/configs/deepseek-v2-lite/config.json`)."""
    scaling = config.get("rope_scaling")
    sizes = {
        "heads": config["num_attention_heads"],
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope": config["qk_nope_head_dim"],
        "qk_rope": config["qk_rope_head_dim"],
        "v_head": config["v_head_dim"],
        "rope_base": float(config["rope_theta"]),
        "yarn": scaling,
        "eps": config["rms_norm_eps"],
        "top_k": config["num_experts_per_tok"],
        "held": tuple(config["held_experts"]),
        "routed_scaling": float(config["routed_scaling_factor"]),
        "aux_weight": config["aux_loss_alpha"],
    }
    sizes.update(overrides)
    return sizes
