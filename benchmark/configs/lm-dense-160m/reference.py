"""Plain reference of the `lm-dense-160m` block: the forward pass, the
loss and its gradients in straightforward `jax.numpy` and float32 — a
Python loop over layers, attention written out, no scan, no kernel, no
cast. It follows `models/transformer_lm.plain_forward` as the program
defines the block (pre-norm RMS with a scale, rotary over the whole
head, causal softmax attention, tanh-approximated GELU MLP, final norm,
untied head); where that departs from GPT-NeoX is listed in
`config.json`. The tests compare the zoo module with this at a small
size; on a TPU set `jax.default_matmul_precision("highest")` around it.
"""

import math

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, base=10000.0):
    """x: [B, L, H, D]; pairs (i, i + D/2) turn by position / base^(2i/D)."""
    length, half = x.shape[1], x.shape[-1] // 2
    freqs = 1.0 / base ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(params, tokens, n_heads):
    """params: the zoo's tree (stacked [n_layers, ...] leaves); tokens
    [B, L] -> logits [B, L, vocab], float32 throughout."""
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    batch, length = tokens.shape
    h = params["embed"][tokens]
    layers = params["layers"]
    causal = jnp.tril(jnp.ones((length, length), dtype=bool))
    for i in range(layers["wq"].shape[0]):
        x = _rms_norm(h, layers["ln1"][i])
        split = lambda y: y.reshape(batch, length, n_heads, -1)  # noqa: E731
        q = _rotary(split(x @ layers["wq"][i]))
        k = _rotary(split(x @ layers["wk"][i]))
        v = split(x @ layers["wv"][i])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        attended = jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v
        )
        h = h + attended.reshape(batch, length, -1) @ layers["wo"][i]
        x = _rms_norm(h, layers["ln2"][i])
        h = h + jax.nn.gelu(x @ layers["w1"][i], approximate=True) @ layers["w2"][i]
    return _rms_norm(h, params["ln_f"]) @ params["head"]


def loss(params, tokens, targets, n_heads):
    """Mean next-token cross-entropy."""
    logits = forward(params, tokens, n_heads)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


loss_and_grads = jax.value_and_grad(loss)
