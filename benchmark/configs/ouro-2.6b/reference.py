"""Plain reference of the `ouro-2.6b` block: the forward pass of all T
exits and gates, the stage-I exit objective and its gradients in
straightforward `jax.numpy` and float32 — a Python loop over the passes
and over the layers (the layer weights indexed, not scanned), attention
written out, no scan, no recomputation, no kernel, no cast. It takes
the zoo module's parameter tree. On a TPU set
`jax.default_matmul_precision("highest")` around it.

It follows the published description (Ouro-2.6B `config.json`; Zhu et
al. 2025, "Scaling Latent Reasoning via Looped Language Models", the
stage-I objective). Departures and assumptions, each also in
`config.json`'s `assumed`:
- no biases anywhere but the gate's (the published parameter count of a
  layer leaves room for none);
- four RMS norms a layer (sandwich), as in the released modelling file:
  h + norm_1b(attn(norm_1a(h))), h + norm_2b(mlp(norm_2a(h)));
- the final norm closes EVERY pass and its output is what the next pass
  reads and what that pass's exit projects;
- the last exit takes the probability the gates before it left over
  (its own gate is computed and not read), beta = 0.1, every exit
  trained (`early_exit_threshold` 1), no stage-II gate training;
- depth and vocabulary are the configuration's cuts: the vocabulary
  here is one chip's slice of the rows of the embedding and of the
  head, and the softmax, the loss and the token ids are over the slice.
"""

import math

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, base):
    """x: [B, L, H, D]; pairs (i, i + D/2) turn by position / base^(2i/D)."""
    length, half = x.shape[1], x.shape[-1] // 2
    freqs = 1.0 / base ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def one_pass(params, layers, h, n_heads, rope_base=1e6, eps=1e-6):
    """The stack `layers` (stacked [n_layers, ...] leaves) applied once
    to h [B, L, d], then the final norm."""
    batch, length, _ = h.shape
    causal = jnp.tril(jnp.ones((length, length), dtype=bool))
    split = lambda y: y.reshape(batch, length, n_heads, -1)  # noqa: E731
    for i in range(layers["wq"].shape[0]):
        x = _rms_norm(h, layers["ln1"][i], eps)
        q = _rotary(split(x @ layers["wq"][i]), rope_base)
        k = _rotary(split(x @ layers["wk"][i]), rope_base)
        v = split(x @ layers["wv"][i])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        attended = jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v
        ).reshape(batch, length, -1)
        h = h + _rms_norm(attended @ layers["wo"][i], layers["ln1b"][i], eps)
        x = _rms_norm(h, layers["ln2"][i], eps)
        gated = jax.nn.silu(x @ layers["wg"][i]) * (x @ layers["wu"][i])
        h = h + _rms_norm(gated @ layers["wd"][i], layers["ln2b"][i], eps)
    return _rms_norm(h, params["ln_f"], eps)


def exit_of(params, h):
    """One exit: (logits [B, L, vocab], gate probability lambda [B, L])."""
    gate = h @ params["exit_gate"]["w"] + params["exit_gate"]["b"]
    return h @ params["head"], jax.nn.sigmoid(gate[..., 0])


def forward(params, tokens, n_heads, passes, **kw):
    """params: the zoo's tree; tokens [B, L] -> (logits [T, B, L,
    vocab], lambda [T, B, L]), float32 throughout."""
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    h = params["embed"][tokens]
    exits = []
    for _ in range(passes):  # the same weights every pass
        h = one_pass(params, params["layers"], h, n_heads, **kw)
        exits.append(exit_of(params, h))
    return jnp.stack([e[0] for e in exits]), jnp.stack([e[1] for e in exits])


def exit_distribution(gates):
    """q [T, ...] from lambda [T, ...]: q_1 = lambda_1, q_t = lambda_t
    prod_{j<t} (1 - lambda_j), q_T = prod_{j<T} (1 - lambda_j)."""
    q, left = [], jnp.ones_like(gates[0])
    for t in range(gates.shape[0] - 1):
        q.append(gates[t] * left)
        left = left * (1.0 - gates[t])
    return jnp.stack(q + [left])


def objective(logits, gates, targets, beta=0.1):
    """-> (loss, per-exit mean cross-entropy [T], mean exit
    distribution [T]): loss = mean over tokens of
    sum_t q_t CE_t - beta H(q)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    index = jnp.broadcast_to(targets[None, ..., None], logp.shape[:-1] + (1,))
    ce = -jnp.take_along_axis(logp, index, axis=-1)[..., 0]  # [T, B, L]
    q = exit_distribution(gates)
    entropy = -jnp.sum(q * jnp.log(q), axis=0)
    value = jnp.mean(jnp.sum(q * ce, axis=0) - beta * entropy)
    return value, jnp.mean(ce, axis=(1, 2)), jnp.mean(q, axis=(1, 2))


def parts(params, tokens, targets, n_heads, passes, beta=0.1, **kw):
    logits, gates = forward(params, tokens, n_heads, passes, **kw)
    return objective(logits, gates, targets, beta)


def loss(params, tokens, targets, n_heads, passes, beta=0.1, **kw):
    return parts(params, tokens, targets, n_heads, passes, beta, **kw)[0]


loss_and_grads = jax.value_and_grad(loss)
