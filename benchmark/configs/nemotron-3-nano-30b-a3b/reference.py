"""Plain reference of the `nemotron-3-nano-30b-a3b` blocks as the
configuration cuts them: the forward pass, the loss (cross-entropy over
the vocabulary's slice) and its gradients in straightforward
`jax.numpy` and float32 — the published pattern string walked ONE
BLOCK AT A TIME (a block is a Mamba-2 mixer `M`, an attention mixer `*`
or an expert feed-forward part `E`, and this file knows nothing of
pairs of them), the state-space recurrence as the recurrence itself,
ONE TOKEN AT A TIME (`lax.scan` over time: no chunk, no decay matrix),
every head naming the group it reads by its index, attention ONE
KEY-VALUE HEAD AT A TIME with the query heads that read it named by
their index and its scores written out one block of queries at a time
(so that 8192 tokens fit), the experts as a masked dense sum over the
experts held here: no sort, no grouped matmul, no recomputation, no
kernel, no cast. It takes the zoo module's parameter tree (`blocks_of`
is the one place that knows how that tree lays its leaves out) and
imports nothing of the program. On a TPU set
`jax.default_matmul_precision("highest")` around it.

It follows the published `config.json` (nvidia/NVIDIA-Nemotron-3-Nano-
30B-A3B-BF16, `model_type` `nemotron_h`) and, for what that leaves
open, the conventions `config.json`'s `assumed` lists. Every block:
h <- h + f(norm(h)), norm(h) = h / sqrt(mean(h^2) + eps) x w; a final
norm; an untied head; no bias but the convolution's.
- `M`: (z, xBC, dt) = x W_in, the columns z 4096 | x 4096 | B 1024 | C
  1024 | dt 64; xBC <- SiLU(causal depthwise convolution of 4 taps +
  bias); x [64 heads, 64], B and C [8 groups, 128], head j reads group
  j // 8; dt = softplus(dt + dt_bias), unclamped; A = -exp(A_log); per
  head from S = 0 [64, 128]: S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  B_t^T, y_t = S_t C_t + D x_t; y <- y x SiLU(z), then an RMS norm over
  EACH GROUP's 512 channels with one weight of 4096; out = y W_out.
- `*`: q [32 heads, 128], k and v [2 heads, 128]; nothing turns; query
  head i reads key-value head i // 16; softmax of q . k x 128^-1/2 over
  the keys u <= t; out = concat(o) W_o.
- `E`: s = sigmoid(x W_r) over all 128 outputs; the 6 largest of s + b
  chosen (b the selection bias, no gradient; equal ones to the lower
  expert first); gates s_e / (sum of the six + 1e-20) x 2.5;
  expert_e(x) = W_down,e relu(W_up,e x)^2; y = sum over chosen AND held
  e of gate_e expert_e(x) + shared(x), shared the same form at 3712.
- the cuts: only the experts `held` = (first, count) add to a block's
  output (what the 120 others would add is left out, and that partial
  result goes on to the next block); the vocabulary is one chip's slice
  of the rows of the embedding and the head.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _float32(params):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)


def relu2_mlp(x, wu, wd):
    return jnp.square(jax.nn.relu(x @ wu)) @ wd


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


def ssm_step(state, xs):
    """One token of the recurrence: state [B, H, P, N], xs = (x_t
    [B, H, P], dt_t [B, H], decay_t [B, H] = exp(dt_t A), b_t and c_t
    [B, H, N]) -> (the next state, y_t [B, H, P])."""
    x_t, dt_t, decay_t, b_t, c_t = xs
    write = (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
    state = decay_t[..., None, None] * state + write
    return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)


def selective_scan(x, dt, decay, b, c):
    """The recurrence, a token at a time. x [B, L, H, P], dt and decay
    [B, L, H], b and c [B, L, H, N] -> y [B, L, H, P]."""
    batch, _, heads, width = x.shape
    start = jnp.zeros((batch, heads, width, b.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(
        ssm_step, start,
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, decay, b, c)),
    )
    return jnp.moveaxis(out, 0, 1)


def mamba2(lp, x, sizes):
    """x [B, L, d] normed -> [B, L, d]. lp: one Mamba-2 block's leaves,
    `a_log`, `dt_bias` and `D` [heads] among them."""
    batch, length, _ = x.shape
    heads, width = sizes["ssm_heads"], sizes["ssm_head_dim"]
    groups, state = sizes["ssm_groups"], sizes["ssm_state"]
    inner, gn = heads * width, groups * state
    projected = x @ lp["in_proj"]  # z | x | B | C | dt
    z = projected[..., :inner]
    xbc = short_conv(
        projected[..., inner:2 * inner + 2 * gn], lp["conv"], lp["conv_bias"]
    )
    dt = jax.nn.softplus(projected[..., 2 * inner + 2 * gn:] + lp["dt_bias"])
    xs = xbc[..., :inner].reshape(batch, length, heads, width)
    b = xbc[..., inner:inner + gn].reshape(batch, length, groups, state)
    c = xbc[..., inner + gn:].reshape(batch, length, groups, state)
    # head j reads group j // (heads / groups)
    reads = [j // (heads // groups) for j in range(heads)]
    decay = jnp.exp(dt * -jnp.exp(lp["a_log"]))
    y = selective_scan(xs, dt, decay, b[:, :, reads], c[:, :, reads])
    y = y + lp["D"][:, None] * xs
    y = y.reshape(batch, length, inner) * jax.nn.silu(z)
    # the norm over each group's inner / groups channels, one weight
    grouped = y.reshape(batch, length, groups, inner // groups)
    grouped = grouped / jnp.sqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + sizes["eps"]
    )
    return (grouped.reshape(batch, length, inner) * lp["ssm_norm"]) @ lp["out_proj"]


def block_attention(q, k, v, first_query):
    """One block of queries of the heads that read one key-value head:
    q [B, Q, G, D] at positions first_query.., k and v [B, U, D] at
    positions 0.. -> [B, Q, G, D]."""
    t = first_query + jnp.arange(q.shape[1])[:, None]
    u = jnp.arange(k.shape[1])[None, :]
    scores = jnp.einsum("bqgd,bud->bgqu", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where((u <= t)[None, None], scores, -jnp.inf)
    return jnp.einsum("bgqu,bud->bqgd", jax.nn.softmax(scores, axis=-1), v)


def attention(lp, x, sizes):
    """x [B, L, d] normed -> [B, L, d]; nothing turns."""
    heads, kv_heads, hd = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
    group, length = heads // kv_heads, x.shape[1]
    q, k, v = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]

    def head(y, i):
        return y[..., i * hd:(i + 1) * hd]

    out = []
    for j in range(kv_heads):
        # the query heads that read key-value head j: i // group == j
        mine = range(j * group, (j + 1) * group)
        k_j, v_j = head(k, j), head(v, j)
        q_j = jnp.stack([head(q, i) for i in mine], axis=2)
        blocks = []
        for start in range(0, length, QUERY_BLOCK):
            end = min(start + QUERY_BLOCK, length)
            blocks.append(block_attention(
                q_j[:, start:end], k_j[:, :end], v_j[:, :end], start
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


def experts(lp, x, sizes, held=None, shared=True):
    """x [B, L, d] normed -> (y, tokens of each expert [E]). `held` =
    (first, count): the experts whose weights `lp` holds (`eu`, `ed`
    stacked [count, ...]) and whose part is added; `shared` False
    leaves the shared expert out (the share test counts it once)."""
    outputs, k = lp["router"].shape[-1], sizes["top_k"]
    first, count = held if held else sizes["held"]
    scores = jax.nn.sigmoid(x @ lp["router"])  # [B, L, E]
    biased = scores + jax.lax.stop_gradient(lp["router_bias"])
    chosen = top_k_by(biased.reshape(-1, outputs), k).reshape(scores.shape)
    gates = scores * chosen
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    gates = gates * sizes["scaling"]
    y = relu2_mlp(x, lp["su"], lp["sd"]) if shared else jnp.zeros_like(x)
    for j in range(count):
        y = y + gates[..., first + j, None] * relu2_mlp(
            x, lp["eu"][j], lp["ed"][j]
        )
    return y, jnp.sum(chosen, axis=(0, 1))


MIXERS = {"M": mamba2, "*": attention}


def block(letter, lp, h, sizes):
    """One published block on the residual stream h [B, L, d]: h +
    f(norm(h)), f by the block's letter -> (h, the tokens of each
    expert [E] of an `E` block, else None). `lp`: the block's own
    leaves, its norm's weight under `norm`."""
    x = _rms_norm(h, lp["norm"], sizes["eps"])
    if letter == "E":
        y, load = experts(lp, x, sizes)
        return h + y, load
    return h + MIXERS[letter](lp, x, sizes), None


_FEED_FORWARD = ("router", "router_bias", "eu", "ed", "su", "sd")


def blocks_of(params, sizes):
    """The zoo's tree as published blocks in order: (letter, the
    block's own leaves). The tree stacks the program's layers run by
    run, a layer holding a mixer block's leaves (its norm `ln1`) and,
    where it has `ln2`, the leaves of the `E` block behind it; a
    Mamba-2 block's `a_log`, `dt_bias` and `D` are cut from the one
    flat leaf that holds them for all such blocks: [a_log | dt_bias |
    D], each in stack order."""
    heads, seen = sizes["ssm_heads"], 0
    third = params["ssm_decay"].shape[0] // 3
    for run in params["stack"]:
        for i in range(run["ln1"].shape[0]):
            lp = {name: leaf[i] for name, leaf in run.items()}
            mixer = {
                name: leaf for name, leaf in lp.items()
                if name not in _FEED_FORWARD and name not in ("ln1", "ln2")
            }
            mixer["norm"] = lp["ln1"]
            if "in_proj" in lp:
                at = slice(seen * heads, (seen + 1) * heads)
                for n, name in enumerate(("a_log", "dt_bias", "D")):
                    mixer[name] = params["ssm_decay"][n * third:(n + 1) * third][at]
                seen += 1
            yield ("M" if "in_proj" in lp else "*"), mixer
            if "ln2" in lp:
                yield "E", {
                    "norm": lp["ln2"], **{n: lp[n] for n in _FEED_FORWARD}
                }


def head_loss(ln_f, head, h, targets, sizes):
    """-> (mean next-token cross-entropy over the vocabulary's slice,
    the logits)."""
    logits = _rms_norm(h, ln_f, sizes["eps"]) @ head
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return ce, logits


def forward(params, tokens, sizes):
    """params: the zoo's tree; tokens [B, L] -> (the last block's
    output [B, L, d], tokens per expert [E blocks, E]). `sizes["blocks"]`
    is the held part of the published pattern string, walked a letter
    at a time."""
    params = _float32(params)
    h = params["embed"][tokens]
    loads = []
    held = list(blocks_of(params, sizes))
    assert "".join(letter for letter, _lp in held) == sizes["blocks"], (
        held, sizes["blocks"]
    )
    for letter, (_letter, lp) in zip(sizes["blocks"], held):
        h, load = block(letter, lp, h, sizes)
        if load is not None:
            loads.append(load)
    return h, jnp.stack(loads)


def logits_of(params, tokens, sizes):
    params = _float32(params)
    h, _loads = forward(params, tokens, sizes)
    return head_loss(params["ln_f"], params["head"], h, tokens, sizes)[1]


def parts(params, tokens, targets, sizes):
    """-> (loss, loads): the cross-entropy alone (no balance term)."""
    params = _float32(params)
    h, loads = forward(params, tokens, sizes)
    return head_loss(params["ln_f"], params["head"], h, targets, sizes)[0], loads


def loss(params, tokens, targets, sizes):
    return parts(params, tokens, targets, sizes)[0]


def sizes_of(config, **overrides):
    """The reference's settings from a `config.json` of the released
    model's keys (`benchmark/configs/nemotron-3-nano-30b-a3b/
    config.json`)."""
    first, count = config["held_layers"]
    sizes = {
        "ssm_heads": config["mamba_num_heads"],
        "ssm_head_dim": config["mamba_head_dim"],
        "ssm_groups": config["n_groups"],
        "ssm_state": config["ssm_state_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "eps": config["norm_eps"],
        "top_k": config["num_experts_per_tok"],
        "scaling": config["routed_scaling_factor"],
        "held": tuple(config["held_experts"]),
        "blocks": config["hybrid_override_pattern"][first:first + count],
    }
    sizes.update(overrides)
    return sizes
