"""The gated delta rule's recurrence, computed in chunks, under either
decay: one number a KEY CHANNEL (Kimi Delta Attention) or one number a
HEAD (Gated DeltaNet).

Per head, with a state S [dk, dv] that starts at zero, a log-decay
g_t <= 0 per key channel (a head's one number is the same on every
channel: Diag(alpha) = exp(g) I), alpha_t = exp(g_t), and a write
strength beta_t:

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

`kda_chunked` gives the same o_t from chunks of `chunk` tokens. Inside
a chunk that starts from the state S_0, with G_r the log-decay summed
from the chunk's first token through r and w_r = beta_r (v_r - S'_r^T
k_r) the row that token r writes,

    S_r = Diag(exp G_r) S_0 + sum_{i<=r} Diag(exp(G_r - G_i)) k_i w_i^T

so the rows solve a unit lower-triangular system (the WY form),

    (I + Diag(beta) A) W = Diag(beta) (V - (K * exp G) S_0),
    A_ri = sum_c k_rc k_ic exp(G_rc - G_ic)  for i < r,

    O = (Q * exp G) S_0 + B W,  B_ri = sum_c q_rc k_ic exp(G_rc - G_ic), i <= r,
    S_C = Diag(exp G_C) S_0 + (K * exp(G_C - G))^T W.

Everything that does not read S_0 (`intra`: A, B, T = the inverse of
I + Diag(beta) A, and the two products T beta V and T beta (K * exp G))
is computed for all chunks at once. The pass over the chunks (`state`)
carries S alone: a chunk forms its rows W = T beta V - T beta (K * exp
G) S_0, its outputs and the state it ends with, four small products,
and nothing of the size of a state is written but the outputs (formed
for all chunks at once, the chunk's map S_C = move S_0 + add costs two
[dk, dk] matrices a chunk written and read again: measured slower on
the v5e, where this function is bound by what it moves).
Exact: no decay is clamped and no term is dropped. Every exponent is a
DIFFERENCE of cumulative log-decays that is <= 0 (G_r - G_i for i <= r,
G_C - G_r, G_r itself), formed before it is exponentiated, so nothing
overflows at any g; a product smaller than float32's smallest normal
number underflows to zero, as the recurrence's own alpha products do.
A and B are formed in sub-blocks of `sub` tokens (`decay_pairs`): the
diagonal blocks from the pairwise differences themselves, a block
below the diagonal from two factors that both refer to the block's
first boundary, each <= 1. T comes from substitution in the same
sub-blocks (`solve_unit_lower` against the identity).

Decay, cumulative sums, exponentials and the state are float32
whatever the inputs are, and so is every product of the form's
matrices and of the state: each is asked at `lax.Precision.HIGHEST`
(`_mm`, `_einsum`), because the TPU's default rounds a float32 operand
to bfloat16 on its way into the multiplier (at 2 x 2048 tokens the
outputs then differ from the recurrence's by 5.5e-3 of the largest,
against 3.3e-6, for 17 % more time: my chip run, PR 38) and the ambient
`jax.default_matmul_precision` is the caller's, not this function's.
Two backward passes are written out, because
jax's own through the forward pass costs several times it: the
diagonal blocks' (`block_pairs`: one more exponential, no third-order
intermediate of its own) and the inverse's (`unit_lower_inverse`: two
products, no second substitution). The rest is jax's; the pass over
the chunks recomputes a chunk's four products from the state the chunk
started from, which is all it keeps.

On a TPU, at head widths the lanes divide, the per-channel `intra`
runs as the Pallas kernels of `ops/kda_kernels.py` (`takes_kernels`:
the same mathematics, a chunk's intermediates never leaving the chip);
`intra_stage` below is the path of every other per-channel call and the
kernels' oracle.

Which stage serves which decay is read from the shape of `g` alone
(`kda_chunked`): [B, L, H, dk] is the per-channel decay and takes the
stages above; [B, L, H] (or a trailing 1) is one number a head. On a
TPU at those widths that is the same kernels under `scalar=True`
(`kda_kernels.scalar_intra_stage`): there the factor exp(G_r - G_i)
does not depend on the channel, so it leaves the sums, A = tril(K K^T *
E, -1) and B = tril(Q K^T * E) with E_ri = exp(G_r - G_i), two
[chunk, dk] x [dk, chunk] products on the multiplier and one
[chunk, chunk] exponential a head, masked before it is taken; no
[sub, sub, d] intermediate and no sub-blocks of pairs, and a key head's
rows are read where they lie by every value head that shares them
(value head j reads q and k of key head j // group). Everywhere else
one decay a head is `intra_stage` told that decay on every channel,
q and k widened to the value heads: right, slower (on the chip the
scalar form in plain jax was slower still: PERF.md, PR 52), and the
scalar kernels' oracle. The pass over the chunks is this file's
`lax.scan` (`chunk_step`) under either decay.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
_einsum = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)


def _block_decay(Gb):
    """(i < r [sub, sub], exp(G_r - G_i) for i <= r else 0
    [..., r, i, c]): above the diagonal a difference is positive and is
    masked BEFORE the exp; one exponential serves both triangles."""
    rows = jnp.arange(Gb.shape[-2])
    keep = rows[:, None] >= rows[None, :]
    E = jnp.exp(jnp.where(
        keep[:, :, None], Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf
    ))
    return rows[:, None] > rows[None, :], E


@jax.custom_vjp
def block_pairs(qb, kb, Gb):
    """The diagonal blocks, from the pairwise differences themselves:
    qb, kb, Gb [..., sub, d] -> (A [..., sub, sub] with A[r, i] =
    sum_c k[r, c] k[i, c] exp(G[r, c] - G[i, c]) for i < r, B the same
    with q[r, c] for i <= r). Its backward pass is written out: the
    log-decay's gradient is k dk + q dq of the row less k dk of the
    column, so it costs one more exponential and no third-order
    intermediate of its own (jax's through the forward pass makes
    three)."""
    strict, E = _block_decay(Gb)
    KE = kb[..., None, :, :] * E
    A = jnp.where(strict, jnp.sum(kb[..., :, None, :] * KE, axis=-1), 0.0)
    return A, jnp.sum(qb[..., :, None, :] * KE, axis=-1)


def _block_pairs_fwd(qb, kb, Gb):
    return block_pairs(qb, kb, Gb), (qb, kb, Gb)


def _block_pairs_bwd(saved, cotangents):
    qb, kb, Gb = saved
    dA, dB = cotangents
    strict, E = _block_decay(Gb)
    dA = jnp.where(strict, dA, 0.0)[..., None]
    dB = dB[..., None]
    KE = kb[..., None, :, :] * E
    dk_row = jnp.sum(dA * KE, axis=-2)  # over i -> [..., r, c]
    dq = jnp.sum(dB * KE, axis=-2)
    dk_col = jnp.sum(
        (dA * kb[..., :, None, :] + dB * qb[..., :, None, :]) * E, axis=-3
    )  # over r -> [..., i, c]
    return dq, dk_row + dk_col, kb * (dk_row - dk_col) + qb * dq


block_pairs.defvjp(_block_pairs_fwd, _block_pairs_bwd)


def decay_pairs(q, k, G, sub: int):
    """(A, B) [..., C, C]: A[r, i] = sum_c k[r, c] k[i, c] exp(G[r, c]
    - G[i, c]) for i < r, B[r, i] the same with q[r, c] for i <= r,
    zero above; q, k, G [..., C, d] float32, G non-increasing along C.
    `sub` divides C. The diagonal blocks of `sub` tokens come from
    `block_pairs`; a block's rows against every earlier token from one
    product of two factors that both refer to R, the cumulative
    log-decay at the last token before the block: exp(G_r - R) and
    exp(R - G_i), each <= 1. (A block against the earlier tokens it
    has, not every block against all C: a third of the exponentials at
    four blocks, and on the v5e the fastest of three ways measured.)"""
    C, d = k.shape[-2:]
    lead = k.shape[:-2]
    n = C // sub
    qb, kb, Gb = (x.reshape(lead + (n, sub, d)) for x in (q, k, G))
    on_a, on_b = block_pairs(qb, kb, Gb)  # 2 x [..., n, sub, sub]
    rows_a, rows_b = [], []
    for m in range(n):
        lo = m * sub
        parts_a, parts_b = [on_a[..., m, :, :]], [on_b[..., m, :, :]]
        if m:
            R = G[..., lo - 1:lo, :]
            within = jnp.exp(Gb[..., m, :, :] - R)
            below = _einsum(
                "...rc,...ic->...ri",
                jnp.concatenate(
                    [kb[..., m, :, :] * within, qb[..., m, :, :] * within],
                    axis=-2,
                ),
                k[..., :lo, :] * jnp.exp(R - G[..., :lo, :]),
            )  # [..., 2 sub, lo]
            parts_a.insert(0, below[..., :sub, :])
            parts_b.insert(0, below[..., sub:, :])
        if lo + sub < C:
            above = jnp.zeros(lead + (sub, C - lo - sub), k.dtype)
            parts_a.append(above)
            parts_b.append(above)
        rows_a.append(jnp.concatenate(parts_a, axis=-1))
        rows_b.append(jnp.concatenate(parts_b, axis=-1))
    return jnp.concatenate(rows_a, axis=-2), jnp.concatenate(rows_b, axis=-2)


def solve_unit_lower(N, rhs, sub: int):
    """X with (I + N) X = rhs, for N [..., C, C] strictly lower
    triangular and rhs [..., C, m], by substitution: inside a diagonal
    block of `sub` rows a row at a time (that block's inverse, `sub` - 1
    small steps for all blocks at once), from block to block by
    matmuls. Backward stable, as substitution is: the entries of
    (I + N)^-1 stay small where the powers of N, which a product form
    of the inverse would pass through, do not (keys that resemble each
    other under a slow decay give N near a constant below the
    diagonal, whose 32nd power holds entries of 1e17)."""
    C = N.shape[-1]
    n = C // sub
    lead = N.shape[:-2]
    Nb = N.reshape(lead + (n, sub, n, sub))
    diag = jnp.stack([Nb[..., a, :, a, :] for a in range(n)], axis=-3)
    eye = jnp.eye(sub, dtype=N.dtype)
    rows = [jnp.broadcast_to(eye[0], lead + (n, sub))]
    for r in range(1, sub):  # row r of every diagonal block's inverse
        known = jnp.stack(rows, axis=-2)  # [..., n, r, sub]
        rows.append(
            eye[r] - _einsum("...i,...ij->...j", diag[..., r, :r], known)
        )
    inverse = jnp.stack(rows, axis=-2)  # [..., n, sub, sub]
    rb = rhs.reshape(lead + (n, sub, rhs.shape[-1]))
    solved = []
    for a in range(n):
        left = rb[..., a, :, :]
        for b in range(a):
            left = left - _mm(Nb[..., a, :, b, :], solved[b])
        solved.append(_mm(inverse[..., a, :, :], left))
    return jnp.concatenate(solved, axis=-2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def unit_lower_inverse(N, sub: int):
    """T = (I + N)^-1 for N [..., C, C] strictly lower triangular:
    `solve_unit_lower` against the identity. The backward pass is the
    inverse's own, dN = -T^T dT T^T below the diagonal: two products,
    where jax's through the substitution walks its rows again."""
    C = N.shape[-1]
    return solve_unit_lower(
        N, jnp.broadcast_to(jnp.eye(C, dtype=N.dtype), N.shape), sub
    )


def _unit_lower_inverse_fwd(N, sub):
    T = unit_lower_inverse(N, sub)
    return T, T


def _unit_lower_inverse_bwd(_sub, T, dT):
    Tt = jnp.swapaxes(T, -1, -2)
    return (jnp.tril(-_mm(_mm(Tt, dT), Tt), k=-1),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def chunk_step(S, xs):
    """A chunk of the pass over the chunks: from the float32 state S
    [B, H, dk, dv] it started from and its six `intra` matrices, its
    rows W = T beta V - T beta (K * exp G) S, -> (the state it ends
    with, its outputs [B, H, chunk, dv])."""
    U, Wt, q_in, Bqk, k_out, keep = xs
    W = U - _mm(Wt, S)
    return keep * S + _mm(k_out, W), _mm(q_in, S) + _mm(Bqk, W)


def intra_stage(q, k, v, g, beta, sub: int):
    """Everything of a chunk that does not read the carried state, for
    all chunks at once, in plain jax: q, k, g [..., C, dk], v
    [..., C, dv], beta [..., C, 1] float32 -> (U, Wt, q_in, Bqk, k_out,
    total [..., 1, dk]). The path of every call the kernels do not take
    (`takes_kernels`), and their oracle."""
    dv = v.shape[-1]
    G = jnp.cumsum(g, axis=-2)
    total = G[..., -1:, :]
    A, Bqk = decay_pairs(q, k, G, sub)
    decay = jnp.exp(G)
    solved = _mm(
        unit_lower_inverse(beta * A, sub),
        beta * jnp.concatenate([v, k * decay], axis=-1),
    )
    U, Wt = solved[..., :dv], solved[..., dv:]  # T beta V, T beta K exp G
    k_out = jnp.swapaxes(k * jnp.exp(total - G), -1, -2)  # [.., dk, chunk]
    return U, Wt, q * decay, Bqk, k_out, total


# The stage runs as the Pallas kernels of `ops/kda_kernels.py` where
# Mosaic's tiling takes its blocks: a head read where it lies is a
# block column of [B, L, H * d], so both head widths are multiples of
# the 128 lanes; a chunk's rows and the diagonal blocks' are multiples
# of the 8 sublanes, and the blocks divide the chunk. Timed on one TPU
# v5e ("TPU v5 lite", jax 0.9.0, libtpu 0.0.34) on 2026-09-30 at the
# one shape a cell runs, (2, 2048, 32, 128) in chunks of 64 and blocks
# of 16, a layer's whole scan, ms a call, jax | kernels: forward 10.41
# | 3.91, forward + backward 28.29 | 11.58 (`docs/performance.md`). The
# rule's other shapes (chunks of 16 to 128, blocks of 8 to 64, a width
# of 256) compile for the v5e and hold to the recurrence in the
# interpreter, and are not timed: a narrower head and the tests'
# chunks of 32 in blocks of 8 on the CPU stay with `intra_stage`.
KERNEL_LANES = 128
KERNEL_SUBLANES = 8


def takes_kernels(dk: int, dv: int, chunk: int, sub: int, backend=None) -> bool:
    """Whether `kda_chunked` hands a call's `intra` stage to the
    kernels: read from the call's own shapes and the backend alone."""
    backend = jax.default_backend() if backend is None else backend
    return (
        backend == "tpu"
        and dk % KERNEL_LANES == 0
        and dv % KERNEL_LANES == 0
        and sub % KERNEL_SUBLANES == 0
        and chunk % sub == 0
    )


def kda_chunked(q, k, v, g, beta, chunk: int = 64, sub: int = 16,
                interpret: bool = False):
    """q, k [B, L, Hk, dk], v [B, L, H, dv], g (log-decay, <= 0),
    beta [B, L, H] -> (o [B, L, H, dv] float32, the most negative
    cumulative log-decay inside a chunk, a float32 scalar). The decay's
    kind is read from `g`'s shape: [B, L, H, dk] is one a key channel
    (Hk = H); [B, L, H] or [B, L, H, 1] is one a head, and then H may be
    a multiple of Hk (value head j reads key head j // (H / Hk)). L need
    not be a multiple of `chunk`: the tail is padded with tokens that
    write nothing (k = 0, beta = 0) and do not decay (g = 0).
    `interpret=True` runs the `intra` stage as the kernels in the Pallas
    interpreter and is for tests only (no model path passes it)."""
    B, L, Hk, dk = q.shape
    H, dv = v.shape[2:]
    pad = -L % chunk
    f32 = jnp.float32
    sub = min(sub, chunk)
    scalar = g.ndim == 3 or g.shape[-1] == 1
    if scalar:
        g = g.reshape(B, L, H, 1)
    elif Hk != H:
        raise ValueError(
            f"{Hk} key heads under {H} value heads need one decay "
            f"a head, g [B, L, H]; got g {g.shape}"
        )

    def padded(x):  # [B, L, H, ...] -> [B, L + pad, H, ...] float32
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x

    def chunks(x):  # [B, L + pad, H, ...] -> [n, B, H, chunk, ...]
        x = x.reshape((B, (L + pad) // chunk, chunk) + x.shape[2:])
        return jnp.moveaxis(x, (1, 3), (0, 2))

    q, k, v, g, beta = (padded(x) for x in (q, k, v, g, beta))
    with jax.named_scope("intra"):
        kernels = interpret or takes_kernels(dk, dv, chunk, sub)
        if scalar and kernels:
            from elasticdl_tpu.ops import kda_kernels

            U, Wt, q_in, Bqk, k_out, total = kda_kernels.scalar_intra_stage(
                q, k, v, g[..., 0], beta, chunk, sub, interpret
            )
            k_out = jnp.swapaxes(k_out, -1, -2)
        elif kernels:
            from elasticdl_tpu.ops import kda_kernels

            U, Wt, q_in, Bqk, k_out, total = kda_kernels.intra_stage(
                q, k, v, g, beta, chunk, sub, interpret
            )
            k_out = jnp.swapaxes(k_out, -1, -2)  # a layout, not a copy
        else:
            if scalar:  # the per-channel stage, told the one decay
                q, k = (jnp.repeat(x, H // Hk, axis=2) for x in (q, k))
                g = jnp.broadcast_to(g, g.shape[:-1] + (dk,))
            U, Wt, q_in, Bqk, k_out, total = intra_stage(
                chunks(q), chunks(k), chunks(v), chunks(g),
                chunks(beta)[..., None], sub,
            )
        keep = jnp.exp(jnp.swapaxes(total, -1, -2))  # [n, B, H, dk, 1]
    with jax.named_scope("state"):
        # `chunk_step` is looked up here, at the call: a control of the
        # benchmark's comparison wraps it (a state rounded on its way
        # from chunk to chunk)
        _, o = lax.scan(
            jax.checkpoint(chunk_step),
            jnp.zeros((B, H, dk, dv), f32),
            (U, Wt, q_in, Bqk, k_out, keep),
        )  # [n, B, H, chunk, dv]
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, L + pad, H, dv)[:, :L]
    return o, lax.stop_gradient(jnp.min(total))


def kda_recurrent(q, k, v, g, beta):
    """The recurrence itself, a token at a time (tests hold
    `kda_chunked` to it): same arguments, -> o [B, L, H, dv] float32.
    g [B, L, H] is one decay a head; fewer key heads than value heads
    are widened in front (value head j reads key head j // group)."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if g.ndim == 3:
        g = g[..., None]
    group = v.shape[2] // q.shape[2]
    if group > 1:
        q, k = (jnp.repeat(x, group, axis=2) for x in (q, k))

    def step(S, xs):  # S [B, H, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None] * S
        read = _einsum("bhkv,bhk->bhv", S, k_t)
        S = S + _einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - read))
        return S, _einsum("bhkv,bhk->bhv", S, q_t)

    B, _, H, dk = q.shape
    _, o = lax.scan(
        step, jnp.zeros((B, H, dk, v.shape[-1]), f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(o, 0, 1)


def _layer_inputs(shape, seed: int):
    """q, k, v, g, beta at [B, L, H, d] as a layer makes them: SiLU of
    normals, q and k scaled to unit length (q by d^-1/2 more), a
    log-decay of -a x softplus(normal - 3) with a in (1, 16), a
    sigmoid's write strength (`compare.py`'s `scan_errors` draws the
    same)."""
    d = shape[-1]
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.nn.silu(jax.random.normal(key, shape)) for key in keys[:3])
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-12) * d**-0.5
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-12)
    rate = jax.random.uniform(keys[3], (shape[2], 1), minval=1.0, maxval=16.0)
    g = -rate * jax.nn.softplus(jax.random.normal(keys[4], shape) - 3.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], shape[:3]))
    return q, k, v, g, beta


def check_against_recurrence(shape, interpret: bool = False, seed: int = 0,
                             chunk: int = 64):
    """`kda_chunked` as this backend dispatches it (on a TPU at a head
    width of 128: the kernels; `interpret=True`: the kernels in the
    interpreter) at one [B, L, H, d] shape against `kda_recurrent`, the
    outputs and the five input gradients of a fixed random cotangent:
    {"o", "dq", "dk", "dv", "dg", "dbeta": max|chunked - recurrent| /
    max|recurrent|, "kernels": whether the kernels ran}. The gated chip
    check beside `flash_attention.check_against_reference`."""
    args = _layer_inputs(shape, seed)
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), shape)

    def through(f):
        def loss(w, *a):
            o = f(*a)
            return jnp.sum(o * w), o

        return jax.jit(
            jax.value_and_grad(loss, argnums=(1, 2, 3, 4, 5), has_aux=True)
        )

    (_, o), grads = through(
        lambda *a: kda_chunked(*a, chunk=chunk, interpret=interpret)[0]
    )(w, *args)
    # the recurrence four heads at a time: its backward pass keeps a
    # state a token, 8.6 GB for all 32 heads at 2 x 2048
    recurrent, wants = through(kda_recurrent), []
    for h in range(0, shape[2], 4):
        (_, oh), gh = recurrent(*(x[:, :, h:h + 4] for x in (w, *args)))
        wants.append((oh, *gh))
    errors = {
        name: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        for name, a, b in zip(
            ("o", "dq", "dk", "dv", "dg", "dbeta"), (o, *grads),
            (jnp.concatenate(parts, axis=2) for parts in zip(*wants)),
        )
    }
    errors["kernels"] = bool(interpret or takes_kernels(
        shape[-1], shape[-1], chunk, min(16, chunk)
    ))
    return errors
