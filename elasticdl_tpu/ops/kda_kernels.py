"""The `intra` stage of `ops/kda.py` as Pallas TPU kernels (fwd + bwd).

`kda.intra_stage` builds, for every chunk of a head at once, the six
matrices the pass over the chunks reads. In plain jax each of its small
products is an XLA operation of its own, and the third-order
intermediates between them (the [sub, sub, d] pairwise decays of the
diagonal blocks, the substitution's rows, jax's saved copies for the
backward pass) go out to HBM and come back. Here a grid step holds
`TILES` chunks of one head in VMEM from its four inputs to its six
outputs: what a pass moves is the inputs and the outputs once.

The mathematics is `kda.py`'s: chunks of `chunk` tokens; the diagonal
blocks of `sub` tokens from the pairwise differences themselves (one
column of every diagonal block of the chunk at a time, [n, sub, d],
never [sub, sub, d]); a block's rows against the earlier tokens from
two factors that refer to the block's first boundary; every exponent a
difference of cumulative log-decays that is <= 0, formed and masked
before it is exponentiated; the inverse of I + Diag(beta) A by
substitution: inside a diagonal block a column at a time on the vector
unit, all blocks at once, and from block to block by products
(`_tile`). Every product is float32 at `Precision.HIGHEST` (`_dot`).
The cumulative sum runs on the vector unit (`_cumsum`): as a product
with a triangle of ones it was a sixth of the forward kernel's time.

What the chip's clock decided (one TPU v5e, (2, 2048, 32, 128), the
stage alone, ms a pass; `PERF.md` section 6 has the calls): a chunk's
products wait on each other, so the chain from block to block is the
shortest that is still a substitution (four products, not seven), and
the chunks of a grid step are traced a stage at a time each (`_weave`),
so that one chunk's products fill another's waits. beta goes in and
dbeta comes out as rows along the lanes, [B, H, n, 1, chunk] (a column
[L, 1] is padded 128-fold in memory), and k_out leaves as [chunk, dk]:
the compiler lays the scan's [dk, chunk] operand out that way, and a
transpose in the kernel was copied back by XLA.

The backward kernel takes the six cotangents and the five inputs,
recomputes the chunk and writes the five input gradients: the diagonal
blocks' transpose is `kda._block_pairs_bwd`'s, the inverse's
`kda._unit_lower_inverse_bwd`'s (dN = -T^T dT T^T below the diagonal),
the cumulative sum's the reversed sum. Nothing but the inputs is kept
between the passes.

Layout: q, k, g [B, L, H, dk], v [B, L, H, dv] float32 are read where
they lie, a head as a block column of [B, L, H * d] (so dk and dv are
multiples of 128: `flash_attention._Layout`'s rule). The outputs are
written as the `lax.scan` over the chunks reads them, [n, B, H, ...].
`interpret=True` runs the kernels in the Pallas interpreter: the tests'
entry, on the CPU.

Under ONE decay a head (Gated DeltaNet; `scalar_intra_stage`, at the
end of the file) the same two kernels run with `scalar=True`: the
factor exp(G_r - G_i) leaves the sums over the channels, so a chunk's A
and B are two products on the multiplier times one [C, C] matrix of
exponentials (`_scalar_pairs`) where `_channel_pairs` walks the diagonal
blocks a column at a time on the vector unit, and their transposes are
products too; the inverse, the weave and the rest of a tile are shared.
g goes in and dg comes out as rows along the lanes, as beta does, and
fewer key heads than value heads are read where they lie.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# chunks of one head a grid step holds: the first that divides the
# sequence's chunks. Independent chunks fill each other's waits
# (`_weave`): the stage alone, forward | forward + backward, at 2 | 4 |
# 8 chunks a step read 2.54 | 2.34 | 2.27 and 5.20 | 4.97 | 4.84 ms, and
# 3.13 | 6.26 a chunk at a time; eight double what Mosaic compiles
TILES = (4, 2, 1)

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
_F32 = jnp.float32


def _dot(a, b, dims):
    return lax.dot_general(
        a, b, dims, precision=lax.Precision.HIGHEST,
        preferred_element_type=_F32,
    )


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _cumsum(x, reverse=False):
    """The cumulative sum down the rows of [C, d] (up, for `reverse`)
    on the vector unit: inside each tile of 8 rows by three shifted
    adds, then the tiles' totals carried from tile to tile."""
    C, d = x.shape
    y = x.reshape(C // 8, 8, d)
    rows = _iota((1, 8, 1), 1)
    for s in (1, 2, 4):
        if reverse:
            y = y + jnp.where(rows < 8 - s, pltpu.roll(y, 8 - s, axis=1), 0.0)
        else:
            y = y + jnp.where(rows >= s, pltpu.roll(y, s, axis=1), 0.0)
    tiles, carry = [None] * (C // 8), None
    for i in (range(C // 8 - 1, -1, -1) if reverse else range(C // 8)):
        tiles[i] = y[i] if carry is None else y[i] + carry
        carry = tiles[i][0:1, :] if reverse else tiles[i][7:8, :]
    return jnp.concatenate(tiles, axis=0)


def _blocks(x, n, sub):
    return x.reshape(n, sub, x.shape[-1])


def _block_decay(Gb, j):
    """exp(G_r - G_j) for the rows r >= j of every diagonal block, 0
    above: [n, sub, d]. The difference is masked BEFORE the exp."""
    rows = _iota((1, Gb.shape[1], 1), 1)
    return jnp.exp(jnp.where(rows >= j, Gb - Gb[:, j:j + 1, :], -jnp.inf))


def _channel_pairs(q, k, G, sub):
    """A, B [C, C] under a decay a key channel (a generator: `_weave`),
    with the columns of A's diagonal blocks, its blocks below them and
    the factors the backward pass transposes (`within`, `right` of each
    block of rows below the first)."""
    C, d = k.shape
    n = C // sub
    qb, kb, Gb = (_blocks(x, n, sub) for x in (q, k, G))
    rows3 = _iota((1, sub, 1), 1)
    lanes = _iota((n, sub, C), 2)
    own = _iota((n, sub, C), 0) * sub  # a block's first column
    on_a = jnp.zeros((n, sub, C), _F32)
    on_b = jnp.zeros((n, sub, C), _F32)
    columns = []  # column j of every diagonal block of A: [n, sub, 1]
    for j in range(sub):
        KE = kb[:, j:j + 1, :] * _block_decay(Gb, j)
        col_a = jnp.where(
            rows3 > j, jnp.sum(kb * KE, axis=-1, keepdims=True), 0.0
        )
        col_b = jnp.sum(qb * KE, axis=-1, keepdims=True)
        on_a = jnp.where(lanes == own + j, col_a, on_a)
        on_b = jnp.where(lanes == own + j, col_b, on_b)
        columns.append(col_a)
        if j % 4 == 3:
            yield
    # a block's rows against every earlier token: two factors that both
    # refer to R, the cumulative log-decay at the last token before the
    # block, each <= 1 (columns from the block on are masked to zero)
    tokens = _iota((C, 1), 0)
    rows_a, rows_b, below_a, factors = [on_a[0]], [on_b[0]], [None], [None]
    for m in range(1, n):
        lo = m * sub
        R = G[lo - 1:lo, :]
        within = jnp.exp(Gb[m] - R)
        right = k * jnp.exp(jnp.where(tokens < lo, R - G, -jnp.inf))
        below = _dot(
            jnp.concatenate([kb[m] * within, qb[m] * within], axis=0),
            right, _NT,
        )  # [2 sub, C]
        below_a.append(below[:sub])
        rows_a.append(on_a[m] + below[:sub])
        rows_b.append(on_b[m] + below[sub:])
        factors.append((within, right))
        yield
    A = jnp.concatenate(rows_a, axis=0)
    B = jnp.concatenate(rows_b, axis=0)
    return A, B, columns, below_a, factors


def _scalar_pairs(q, k, G, sub):
    """`_channel_pairs` under ONE decay a head (G's lanes all alike):
    exp(G_r - G_i) leaves the sums over the channels, so A and B are two
    products on the multiplier times one [C, C] matrix of exponentials,
    every exponent a difference <= 0 masked before it is taken. The
    factors the backward pass transposes are that matrix and the
    products."""
    C = k.shape[0]
    n = C // sub
    Gc = jnp.max(G, axis=-1, keepdims=True)  # [C, 1]: any lane's
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    E = jnp.exp(jnp.where(rows >= cols, Gc - _row(Gc), -jnp.inf))
    pairs = _dot(jnp.concatenate([k, q], axis=0), k, _NT)  # [2 C, C]
    yield
    A = jnp.where(rows > cols, pairs[:C] * E, 0.0)
    B = pairs[C:] * E
    A3 = _blocks(A, n, sub)
    lanes = _iota((n, sub, C), 2)
    own = _iota((n, sub, C), 0) * sub
    columns = [
        jnp.sum(jnp.where(lanes == own + j, A3, 0.0), axis=-1, keepdims=True)
        for j in range(sub)
    ]
    before = _iota((sub, C), 1)
    below_a = [None] + [
        jnp.where(before < m * sub, A[m * sub:(m + 1) * sub], 0.0)
        for m in range(1, n)
    ]
    yield
    return A, B, columns, below_a, (E, pairs)


def _tile(q, k, g, beta, sub, scalar=False):
    """What both passes form of a chunk (a generator: `_weave`): q, k,
    g [C, d], beta [C, 1] -> G [C, d], A, B [C, C], Y and D [C, C] with
    (I + beta A)^-1 = Y D, and the factors the backward pass transposes.
    `scalar`: g holds one decay a head on every lane, and the pairs
    come from `_scalar_pairs`."""
    C, d = k.shape
    n = C // sub
    G = _cumsum(g)  # the cumulative log-decay
    rows3 = _iota((1, sub, 1), 1)
    lanes = _iota((n, sub, C), 2)
    own = _iota((n, sub, C), 0) * sub  # a block's first column
    A, B, columns, below_a, factors = yield from (
        _scalar_pairs if scalar else _channel_pairs
    )(q, k, G, sub)
    # (I + N)^-1, N = beta A, by substitution. D = every diagonal
    # block's inverse on the diagonal, a column at a time (D[r] -= N[r,
    # j] D[j] for r > j, all blocks at once, formed where they lie);
    # with L the blocks below, (I + N)^-1 = (I + D L)^-1 D, and Y =
    # (I + D L)^-1 follows from block to block (its first two blocks of
    # rows need no product): four products in a row where solving block
    # after block against the identity takes seven
    bb = _blocks(beta, n, sub)
    yield
    D = jnp.where(lanes == own + rows3, 1.0, 0.0)
    for j in range(sub - 1):
        D = D - (bb * columns[j]) * D[:, j:j + 1, :]
        if j % 4 == 3:
            yield
    D = D.reshape(C, C)
    DL = _dot(D, beta * jnp.concatenate(
        [jnp.zeros((sub, C), _F32)] + below_a[1:], axis=0
    ), _NN)
    yield
    eye = jnp.where(_iota((C, C), 0) == _iota((C, C), 1), 1.0, 0.0)
    Y = [eye[:sub]]
    for a in range(1, n):
        rows = slice(a * sub, (a + 1) * sub)
        if a == 1:  # (D L)'s second block of rows meets Y's first: I
            Y.append(eye[rows] - DL[rows])
            continue
        known = jnp.concatenate(
            Y + [jnp.zeros((C - a * sub, C), _F32)], axis=0
        )
        Y.append(eye[rows] - _dot(DL[rows], known, _NN))
        yield
    return G, A, B, jnp.concatenate(Y, axis=0), D, factors


def _weave(tiles):
    """Run the tiles' generators a stage at a time each, so that the
    operations of independent chunks lie side by side in the program
    (a chunk's products wait on each other; another chunk's fill the
    wait) -> their results."""
    results, live = [None] * len(tiles), dict(enumerate(tiles))
    while live:
        for i, tile in list(live.items()):
            try:
                next(tile)
            except StopIteration as done:
                results[i] = done.value
                del live[i]
    return results


def _forward_tile(q, k, v, g, beta, sub, scalar=False):
    """-> U, Wt, q_in, Bqk, k_out^T [C, dk], total [1, dk]."""
    C = k.shape[0]
    G, _A, B, Y, D, _ = yield from _tile(q, k, g, beta, sub, scalar)
    decay = jnp.exp(G)
    total = G[C - 1:C, :]
    dv = v.shape[-1]
    rhs = jnp.concatenate([beta * v, beta * (k * decay)], axis=-1)
    solved = _dot(Y, _dot(D, rhs, _NN), _NN)  # T rhs; D rhs waits for nothing
    return (
        solved[:, :dv], solved[:, dv:], q * decay, B,
        k * jnp.exp(total - G), total,
    )


def _backward_tile(q, k, v, g, beta, dU, dWt, dq_in, dB, dko, dtotal, sub,
                   scalar=False):
    """The transposes of `_forward_tile`, the tile recomputed: dko is
    the cotangent of k_out^T [C, dk] -> dq, dk, dv, dg, dbeta [C, 1];
    under `scalar` dg is [C, 1] too, the lanes' sum."""
    C, d = k.shape
    n = C // sub
    dv_width = v.shape[-1]
    G, A, _B, Y, D, factors = yield from _tile(q, k, g, beta, sub, scalar)
    T = _dot(Y, D, _NN)
    yield
    decay = jnp.exp(G)
    total = G[C - 1:C, :]
    kd = k * decay
    # solved = T [beta v | beta kd]
    rhs = jnp.concatenate([beta * v, beta * kd], axis=-1)
    dsolved = jnp.concatenate([dU, dWt], axis=-1)
    dT = _dot(dsolved, rhs, _NT)
    drhs = _dot(T, dsolved, _TN)
    yield
    dX, dY = drhs[:, :dv_width], drhs[:, dv_width:]
    # the inverse's own transpose: dN = -T^T dT T^T below the diagonal
    strict = _iota((C, C), 0) > _iota((C, C), 1)
    TtdT = _dot(T, dT, _TN)
    yield
    dN = jnp.where(strict, -_dot(TtdT, T, _NT), 0.0)
    yield
    dbeta = (
        jnp.sum(dN * A, axis=-1, keepdims=True)
        + jnp.sum(dX * v, axis=-1, keepdims=True)
        + jnp.sum(dY * kd, axis=-1, keepdims=True)
    )
    dA = beta * dN
    dkd = beta * dY
    dq = dq_in * decay
    dk = dkd * decay
    dG = (dkd * k + dq_in * q) * decay
    ex = jnp.exp(total - G)
    dk = dk + dko * ex
    through_ex = dko * k * ex
    dG = dG - through_ex
    dtotal = dtotal + jnp.sum(through_ex, axis=0, keepdims=True)
    tokens = _iota((C, 1), 0)
    dG = dG + jnp.where(tokens == C - 1, dtotal, 0.0)
    if scalar:
        # A = strict (K K^T) E, B = (Q K^T) E: the products' transposes
        # are products, and the exponent's is the rows' sums less the
        # columns' of dE E. That part of the decay's gradient is one
        # number a token: it is laid on lane 0, and the lanes are summed
        E, pairs = factors
        Ma = jnp.where(_iota((C, C), 0) > _iota((C, C), 1), dA * E, 0.0)
        Mb = dB * E
        dk = dk + _dot(Ma, k, _NN) + _dot(Ma, k, _TN) + _dot(Mb, q, _TN)
        dq = dq + _dot(Mb, k, _NN)
        yield
        P = Ma * pairs[:C] + Mb * pairs[C:]
        dGc = jnp.sum(P, axis=-1, keepdims=True) - _column(
            jnp.sum(P, axis=0, keepdims=True)
        )
        dG = dG + jnp.where(_iota((1, d), 1) == 0, dGc, 0.0)
        dg = jnp.sum(_cumsum(dG, reverse=True), axis=-1, keepdims=True)
        return dq, dk, beta * dX, dg, dbeta
    # the blocks below the diagonal: below = [k within; q within] right^T
    qb, kb, Gb = (_blocks(x, n, sub) for x in (q, k, G))
    none = jnp.zeros((sub, d), _F32)  # the first block has no rows below
    dq_rows, dk_rows, dG_rows = [none], [none], [none]
    for m in range(1, n):
        lo = m * sub
        within, right = factors[m]
        R = G[lo - 1:lo, :]
        before = _iota((1, C), 1) < lo
        dbelow = jnp.concatenate([
            jnp.where(before, dA[lo:lo + sub], 0.0),
            jnp.where(before, dB[lo:lo + sub], 0.0),
        ], axis=0)  # [2 sub, C]
        dleft = _dot(dbelow, right, _NN)  # [2 sub, d]
        dright = _dot(
            dbelow,
            jnp.concatenate([kb[m] * within, qb[m] * within], axis=0),
            _TN,
        )  # [C, d]
        dk_rows.append(dleft[:sub] * within)
        dq_rows.append(dleft[sub:] * within)
        through_within = (dleft[:sub] * kb[m] + dleft[sub:] * qb[m]) * within
        dG_rows.append(through_within)
        er = jnp.exp(jnp.where(tokens < lo, R - G, -jnp.inf))
        dk = dk + dright * er
        through_er = dright * right  # dright k er
        dG = dG - through_er
        dR = (
            jnp.sum(through_er, axis=0, keepdims=True)
            - jnp.sum(through_within, axis=0, keepdims=True)
        )
        dG = dG + jnp.where(tokens == lo - 1, dR, 0.0)
        yield
    # the diagonal blocks (`kda._block_pairs_bwd`), a column at a time
    rows3 = _iota((1, sub, 1), 1)
    lanes = _iota((n, sub, C), 2)
    own = _iota((n, sub, C), 0) * sub
    dA3, dB3 = _blocks(dA, n, sub), _blocks(dB, n, sub)
    dk_row = jnp.zeros((n, sub, d), _F32)
    dq_on = jnp.zeros((n, sub, d), _F32)
    dk_col = jnp.zeros((n, sub, d), _F32)
    for j in range(sub):
        E = _block_decay(Gb, j)
        KE = kb[:, j:j + 1, :] * E
        here = lanes == own + j
        a_col = jnp.where(
            rows3 > j,
            jnp.sum(jnp.where(here, dA3, 0.0), axis=-1, keepdims=True), 0.0,
        )
        b_col = jnp.sum(jnp.where(here, dB3, 0.0), axis=-1, keepdims=True)
        dk_row = dk_row + a_col * KE
        dq_on = dq_on + b_col * KE
        column = jnp.sum((a_col * kb + b_col * qb) * E, axis=1, keepdims=True)
        dk_col = jnp.where(rows3 == j, column, dk_col)
        if j % 4 == 3:
            yield
    dq = dq + dq_on.reshape(C, d) + jnp.concatenate(dq_rows, axis=0)
    dk = dk + (dk_row + dk_col).reshape(C, d) + jnp.concatenate(dk_rows, axis=0)
    dG = dG + (kb * (dk_row - dk_col) + qb * dq_on).reshape(C, d) + (
        jnp.concatenate(dG_rows, axis=0)
    )
    dg = _cumsum(dG, reverse=True)
    return dq, dk, beta * dX, dg, dbeta


# ----------------------------------------------------------------- kernels


def _decay_of(g_ref, t, at, width, scalar):
    """A chunk's log-decay [C, width]: read where it lies, or under
    `scalar` its one number a token, a row along the lanes in memory as
    beta is, laid on every lane."""
    if not scalar:
        return g_ref[0, at[t], :]
    column = _column(g_ref[0, 0, t])
    return jnp.broadcast_to(column, (column.shape[0], width))


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *out_refs, chunk,
                    sub, tiles, scalar=False):
    at = [slice(t * chunk, (t + 1) * chunk) for t in range(tiles)]
    results = _weave([
        _forward_tile(
            q_ref[0, at[t], :], k_ref[0, at[t], :], v_ref[0, at[t], :],
            _decay_of(g_ref, t, at, k_ref.shape[-1], scalar),
            _column(beta_ref[0, 0, t]), sub, scalar,
        ) for t in range(tiles)
    ])
    for t, result in enumerate(results):
        for ref, value in zip(out_refs, result):
            ref[t, 0, 0] = value


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, dU_ref, dWt_ref,
                     dq_in_ref, dB_ref, dk_out_ref, dtotal_ref, dq_ref, dk_ref,
                     dv_ref, dg_ref, dbeta_ref, *, chunk, sub, tiles,
                     scalar=False):
    at = [slice(t * chunk, (t + 1) * chunk) for t in range(tiles)]
    results = _weave([
        _backward_tile(
            q_ref[0, at[t], :], k_ref[0, at[t], :], v_ref[0, at[t], :],
            _decay_of(g_ref, t, at, k_ref.shape[-1], scalar),
            _column(beta_ref[0, 0, t]), dU_ref[t, 0, 0],
            dWt_ref[t, 0, 0], dq_in_ref[t, 0, 0], dB_ref[t, 0, 0],
            dk_out_ref[t, 0, 0], dtotal_ref[t, 0, 0], sub, scalar,
        ) for t in range(tiles)
    ])
    for t, (dq, dk, dv, dg, dbeta) in enumerate(results):
        dq_ref[0, at[t], :] = dq
        dk_ref[0, at[t], :] = dk
        dv_ref[0, at[t], :] = dv
        if scalar:
            dg_ref[0, 0, t] = _row(dg)
        else:
            dg_ref[0, at[t], :] = dg
        dbeta_ref[0, 0, t] = _row(dbeta)


def _column(row):
    """[1, C] -> [C, 1]: a chunk's beta lies along the lanes in memory
    (a column there would be padded 128-fold)."""
    C = row.shape[-1]
    on = _iota((C, C), 0) == _iota((C, C), 1)
    return jnp.sum(jnp.where(on, row, 0.0), axis=-1, keepdims=True)


def _row(column):
    """[C, 1] -> [1, C]."""
    C = column.shape[0]
    on = _iota((C, C), 0) == _iota((C, C), 1)
    return jnp.sum(jnp.where(on, column, 0.0), axis=0, keepdims=True)


def _params(interpret: bool):
    if interpret:
        return {"interpret": True}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        )
    }


class _Specs:
    """The blocks of one grid step (batch, head, `tiles` chunks)."""

    def __init__(self, shape, dv, chunk, tiles):
        self.B, self.L, self.H, self.dk = shape
        self.dv, self.chunk, self.tiles = dv, chunk, tiles
        self.n = self.L // chunk
        self.grid = (self.B, self.H, self.n // tiles)

    def where_it_lies(self, d, group=1):
        """A head's rows of [B, L, H * d]; with `group` the rows of the
        key head that value head h reads, h // group of H / group."""
        return pl.BlockSpec(
            (1, self.tiles * self.chunk, d),
            lambda b, h, c: (b, c, h // group),
        )

    def rows(self):
        """[B, H, n, 1, C]: a chunk's beta as one row (the singleton
        keeps Mosaic's tiling rule: the last two edges the array's)."""
        return pl.BlockSpec(
            (1, 1, self.tiles, 1, self.chunk), lambda b, h, c: (b, h, c, 0, 0)
        )

    def scanned(self, rows, width):
        """[n, B, H, rows, width], as the pass over the chunks reads."""
        return pl.BlockSpec(
            (self.tiles, 1, 1, rows, width), lambda b, h, c: (c, b, h, 0, 0)
        )

    def scanned_shape(self, rows, width):
        return jax.ShapeDtypeStruct(
            (self.n, self.B, self.H, rows, width), _F32
        )

    def outputs(self):
        C, dk, dv = self.chunk, self.dk, self.dv
        return [(C, dv), (C, dk), (C, dk), (C, C), (C, dk), (1, dk)]


def pick_tiles(n: int) -> int:
    return next(t for t in TILES if n % t == 0)


def _flat(x):
    B, L, H, d = x.shape
    return x.reshape(B, L, H * d)


def _beta_rows(beta, chunk):
    B, L, H = beta.shape
    return jnp.swapaxes(beta, 1, 2).reshape(B, H, L // chunk, 1, chunk)


# The two entries are `jax.jit`s, so that the layer bodies of a program
# and their recomputation share one trace and one lowering to Mosaic
# each (nine traced apart cost a worker 10 s of every boot, compile
# cache or not); what a trace reads, the chunks a step among it, is an
# argument
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _forward(q, k, v, g, beta, chunk, sub, tiles, interpret):
    sp = _Specs(q.shape, v.shape[-1], chunk, tiles)
    wide, value = sp.where_it_lies(sp.dk), sp.where_it_lies(sp.dv)
    return pl.pallas_call(
        functools.partial(
            _forward_kernel, chunk=chunk, sub=sub, tiles=sp.tiles
        ),
        out_shape=[sp.scanned_shape(*o) for o in sp.outputs()],
        grid=sp.grid,
        in_specs=[wide, wide, value, wide, sp.rows()],
        out_specs=[sp.scanned(*o) for o in sp.outputs()],
        **_params(interpret),
    )(_flat(q), _flat(k), _flat(v), _flat(g), _beta_rows(beta, chunk))


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _backward(q, k, v, g, beta, cotangents, chunk, sub, tiles, interpret):
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    sp = _Specs(q.shape, dv, chunk, tiles)
    wide, value = sp.where_it_lies(dk), sp.where_it_lies(dv)
    flat = lambda d: jax.ShapeDtypeStruct((B, L, H * d), _F32)  # noqa: E731
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(
            _backward_kernel, chunk=chunk, sub=sub, tiles=sp.tiles
        ),
        out_shape=[
            flat(dk), flat(dk), flat(dv), flat(dk),
            jax.ShapeDtypeStruct((B, H, L // chunk, 1, chunk), _F32),
        ],
        grid=sp.grid,
        in_specs=[wide, wide, value, wide, sp.rows()]
        + [sp.scanned(*o) for o in sp.outputs()],
        out_specs=[wide, wide, value, wide, sp.rows()],
        **_params(interpret),
    )(_flat(q), _flat(k), _flat(v), _flat(g), _beta_rows(beta, chunk),
      *cotangents)
    return (
        dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
        dg.reshape(g.shape), jnp.swapaxes(dbeta.reshape(B, H, L), 1, 2),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def intra_stage(q, k, v, g, beta, chunk: int, sub: int, interpret: bool):
    """q, k, g [B, L, H, dk], v [B, L, H, dv], beta [B, L, H], float32,
    L a multiple of `chunk` -> (U, Wt, q_in, Bqk, k_out^T, total), each
    [n, B, H, ...]: `kda.intra_stage`'s, but k_out as [chunk, dk] (the
    compiler lays the pass over the chunks' [dk, chunk] operand out that
    way in memory: a transpose in the kernel was copied back by XLA),
    and `keep` left to the caller (exp of `total`, transposed)."""
    tiles = pick_tiles(q.shape[1] // chunk)
    return tuple(_forward(q, k, v, g, beta, chunk, sub, tiles, interpret))


def _intra_fwd(q, k, v, g, beta, chunk, sub, interpret):
    out = intra_stage(q, k, v, g, beta, chunk, sub, interpret)
    return out, (q, k, v, g, beta)


def _intra_bwd(chunk, sub, interpret, saved, cotangents):
    tiles = pick_tiles(saved[0].shape[1] // chunk)
    return _backward(*saved, cotangents, chunk, sub, tiles, interpret)


intra_stage.defvjp(_intra_fwd, _intra_bwd)


# ------------------------------------------- the stage under one decay a head
#
# The same two kernels with `scalar=True` (`_scalar_pairs` for the
# column loops of `_channel_pairs`; the rest of a tile as it is): g
# [B, L, H], one number a value head and token, goes in and its
# gradient comes out as rows along the lanes, as beta does; q and k may
# have fewer heads than v, and the grid step of value head h then reads
# the rows of key head h // group where they lie. dq and dk are written
# a value head each and summed over a group here.


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _scalar_forward(q, k, v, g, beta, chunk, sub, tiles, interpret):
    B, L, H, dv = v.shape
    dk = q.shape[-1]
    sp = _Specs((B, L, H, dk), dv, chunk, tiles)
    key = sp.where_it_lies(dk, H // q.shape[2])
    return pl.pallas_call(
        functools.partial(
            _forward_kernel, chunk=chunk, sub=sub, tiles=sp.tiles, scalar=True
        ),
        out_shape=[sp.scanned_shape(*o) for o in sp.outputs()],
        grid=sp.grid,
        in_specs=[key, key, sp.where_it_lies(dv), sp.rows(), sp.rows()],
        out_specs=[sp.scanned(*o) for o in sp.outputs()],
        **_params(interpret),
    )(_flat(q), _flat(k), _flat(v), _beta_rows(g, chunk),
      _beta_rows(beta, chunk))


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _scalar_backward(q, k, v, g, beta, cotangents, chunk, sub, tiles,
                     interpret):
    B, L, H, dv = v.shape
    heads, dk = q.shape[2:]
    group = H // heads
    sp = _Specs((B, L, H, dk), dv, chunk, tiles)
    key, wide, value = (
        sp.where_it_lies(dk, group), sp.where_it_lies(dk), sp.where_it_lies(dv)
    )
    flat = lambda d: jax.ShapeDtypeStruct((B, L, H * d), _F32)  # noqa: E731
    rows = jax.ShapeDtypeStruct((B, H, L // chunk, 1, chunk), _F32)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(
            _backward_kernel, chunk=chunk, sub=sub, tiles=sp.tiles, scalar=True
        ),
        out_shape=[flat(dk), flat(dk), flat(dv), rows, rows],
        grid=sp.grid,
        in_specs=[key, key, value, sp.rows(), sp.rows()]
        + [sp.scanned(*o) for o in sp.outputs()],
        out_specs=[wide, wide, value, sp.rows(), sp.rows()],
        **_params(interpret),
    )(_flat(q), _flat(k), _flat(v), _beta_rows(g, chunk),
      _beta_rows(beta, chunk), *cotangents)

    def a_key_head(x):  # the value heads that read it, summed
        return x.reshape(B, L, heads, group, dk).sum(axis=3)

    def a_token(rows):
        return jnp.swapaxes(rows.reshape(B, H, L), 1, 2)

    return (
        a_key_head(dq), a_key_head(dk_), dv_.reshape(v.shape),
        a_token(dg), a_token(dbeta),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def scalar_intra_stage(q, k, v, g, beta, chunk: int, sub: int,
                       interpret: bool):
    """`intra_stage` under one decay a head: q, k [B, L, Hk, dk], v
    [B, L, H, dv], g and beta [B, L, H], float32, H a multiple of Hk
    (value head j reads key head j // (H / Hk)), L a multiple of
    `chunk` -> the same six, each [n, B, H, ...]; `total` [.., 1, dk]
    holds the head's one sum on every lane."""
    tiles = pick_tiles(q.shape[1] // chunk)
    return tuple(
        _scalar_forward(q, k, v, g, beta, chunk, sub, tiles, interpret)
    )


def _scalar_fwd(q, k, v, g, beta, chunk, sub, interpret):
    out = scalar_intra_stage(q, k, v, g, beta, chunk, sub, interpret)
    return out, (q, k, v, g, beta)


def _scalar_bwd(chunk, sub, interpret, saved, cotangents):
    tiles = pick_tiles(saved[0].shape[1] // chunk)
    return _scalar_backward(*saved, cotangents, chunk, sub, tiles, interpret)


scalar_intra_stage.defvjp(_scalar_fwd, _scalar_bwd)
