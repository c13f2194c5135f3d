"""Fused attention, causal and optionally banded, as Pallas TPU kernels
(fwd + bwd).

The hot op of the transformer (models/transformer_lm.py) and of a
one-device ring (parallel/ring_attention.py) is softmax(QK^T)V. From
2048 tokens on, XLA's lowering writes the [L, L] scores to HBM, reads
them back and keeps them for the backward pass; these kernels keep a
tile of them in VMEM with the online-softmax accumulator (m/l running
max/denominator), so HBM traffic is O(L*D) instead of O(L^2) and the
MXU sees back-to-back [BQ,D]x[D,BK] and [BQ,BK]x[BK,D] matmuls with
f32 accumulation.

All three kernels (fwd, dq, dk+dv) are STREAMING: the non-owned
sequence dimension rides the innermost grid axis, one tile in flight
per input, accumulators in VMEM scratch across grid steps, output
blocks revisited until their row/column is done. The tile is as large
as the ladder allows (`pick_tiles`: 1024 x 1024 where L divides by
it), because a grid step costs more than a 128^3 matmul: at 128 x 128
the kernels ran at half XLA's speed at 2048 tokens, at 1024 x 1024 at
2.1 to 2.2 times it (FLASH_MIN_LENGTH has the numbers). Under the
causal mask a step above the diagonal runs nothing and fetches
nothing (its block index is held at the nearest visible tile's), and
only a tile the diagonal crosses is masked. Under a `window` (the
query at t sees the keys u with 0 <= t - u < window) the inner grid axis
is only as long as the tiles a band crosses and starts at the band's
first tile (`_Band`), so a tile wholly left of the band costs no step
at all, and a tile the band's left edge crosses is masked on that edge.
A head width of a multiple
of 128 is read where it lies in [B, L, H, D]; any other is folded
through memory (`_Layout`). Values, the output and its cotangent may be
of another width than queries and keys (latent attention: 192 | 128)
and are laid out by their own; the scores' scale is 1/sqrt(D) or the
caller's, a Python number compiled in. VMEM use is O(tile) whatever L
is. The
forward also emits the per-row logsumexp; the backward is the standard
two-kernel flash scheme re-forming p = exp(s - lse) from O(L*D)
residuals: nothing quadratic is ever saved, and no atomics, each
kernel owns its output block (FlashAttention-2 layout). Numerics are
validated tile pair by tile pair against the reference math in
tests/test_flash_attention.py in Pallas interpret mode on CPU, and
compiled on the chip by chip_smoke.py and under EDL_TPU_TESTS=1
(`check_against_reference`).

Layout contract: q [B, L, H, D], k [B, L, Hkv, D], v [B, L, Hkv, Dv]
("blhd", matching transformer_lm), any float dtype; scores, softmax and
accumulators are f32. H is a multiple of Hkv (grouped-query attention;
equal in most callers): query head i reads key-value head i // group
through the k and v index maps, nothing is widened for the kernels, and
the dk + dv kernel, whose grid walks the key-value heads, sums a
group's query heads in its float32 accumulators. L must divide
by the 128 block; callers with ragged L use the jnp fallback
(`reference_attention`).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128  # the smallest tile edge: L must divide by it
# Tile edges, largest first: a kernel call cuts queries and keys alike
# into the first that divides L. 1024 is where the sweep on the chip
# stopped paying (FLASH_MIN_LENGTH, docs/performance.md): a wider k edge
# steps through the whole square again, a wider q edge was slower.
TILE_LADDER = (1024, 512, 256, 128)
# What a kernel may take of the v5e's 128 MiB of VMEM: its tiles twice
# (the pipeline's two buffers), its accumulators and the float32 score
# tile with its copies (s, p, dp, ds: 4 MiB each at 1024 x 1024).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# The names (`jax.ad_checkpoint.checkpoint_name`) of the forward
# kernel's output and log-sum-exp among its backward rule's residuals:
# O(L*Dv) a head. A `jax.checkpoint` whose policy saves these names
# (`transformer_lm._remat`) keeps the two and its backward pass does not
# run the forward kernel again; q, k and v are recomputed as the rest of
# the layer is. Under no `jax.checkpoint` a name is an identity.
RESIDUAL_NAMES = ("flash_attention_out", "flash_attention_lse")
_NEG_INF = -1e30


def reference_attention(q, k, v, causal: bool = True, scale=None,
                        window=None):
    """Plain-XLA causal attention, [B, L, H, D] -> [B, L, H, Dv]: `v`
    may be of another width than `q` and `k` (latent attention: 192-wide
    queries and keys, 128-wide values). `scale` multiplies the scores
    where 1/sqrt(D) is not the model's (YaRN's softmax scale). Under
    `window` the causal mask is a band: the query at t sees the keys u
    with 0 <= t - u < window."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    s = s / math.sqrt(d) if scale is None else s * scale
    if causal:
        L = q.shape[1]
        mask = jnp.tril(jnp.ones((L, L), dtype=bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((L, L), dtype=bool), -window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def pick_tiles(L: int, window=None):
    """(q edge, k edge) of the tiles the kernels cut a sequence of `L`
    into, or None where not even BLOCK divides it (the caller's
    fallback is `reference_attention`). Under a `window` the edge is no
    longer than the window (and no shorter than BLOCK): a band runs the
    tiles it crosses whole, so a longer tile multiplies pairs nobody
    sees. On the chip (2026-10-01, `scripts/swa_kernel_sweep.py`) at
    (1, 8192, 64, 128), window 512, forward and backward, ms a call by
    q x k edge: 128 x 128 41.9, 256 x 256 23.4, 512 x 512 16.4,
    1024 x 1024 20.8, 256 x 512 19.8, 512 x 256 22.2, 1024 x 512 21.0;
    the causal call of those shapes 42.2 (and at 48 heads 31.7 at
    1024 x 1024, 41.9 at 512 x 512). At (1, 16384, 28, 128), window
    4096 (2026-10-04, `--cases band4k`; forward alone in brackets):
    1024 x 1024 34.0 (9.6), 512 x 1024 36.5 (10.5), 2048 x 2048 38.4
    (11.0), 2048 x 1024 39.1 (11.1), 512 x 512 41.3 (14.9), 1024 x 512
    42.8 (16.9), 256 x 256 79.6 (31.6); the causal call of those shapes
    62.7 (17.7) at 1024 x 1024 and 87.9 (31.9) at 512 x 512. A band of
    four tiles runs four to five a row, so the ladder's first edge wins
    under a window that holds it as it does without one, and the rule
    stays. Windows between 512 and 4096 are not measured."""
    most = max(window or TILE_LADDER[0], BLOCK)
    edge = next(
        (t for t in TILE_LADDER if t <= most and L > 0 and L % t == 0), None
    )
    return edge and (edge, edge)


def _on_visible_tiles(qi, kj, bq: int, bk: int, causal: bool, body,
                      window=None, n_q=None):
    """Run `body(masked)` for the (qi, kj) tile pair: nothing for a
    tile wholly above the diagonal or, under `window`, wholly left of
    the band (or below the sequence's end: `n_q` q tiles, where the
    caller's steps can pass it); `masked` only where the diagonal or
    the band's left edge crosses the tile."""
    if not causal:
        body(False)
        return
    visible = kj * bk <= qi * bq + bq - 1  # its first key, its last query
    crossed = kj * bk + bk - 1 > qi * bq  # its last key, its first query
    if window is not None:
        # its last key, the first key its first query sees
        visible &= kj * bk + bk - 1 >= qi * bq - (window - 1)
        # its first key, the first key its last query sees
        crossed |= kj * bk < qi * bq + bq - window
    if n_q is not None:
        visible &= qi < n_q
    pl.when(visible & crossed)(lambda: body(True))
    pl.when(visible & jnp.logical_not(crossed))(lambda: body(False))


def _mask(s, qi, kj, bq: int, bk: int, q_axis: int, window=None):
    """Mask a score tile by global position; queries run along
    `q_axis` of `s`, keys along the other."""
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    seen = q_pos >= k_pos
    if window is not None:
        seen &= q_pos - k_pos < window
    return jnp.where(seen, s, _NEG_INF)


def _tile_picks(bq: int, bk: int, causal: bool):
    """(own, seen_k, seen_q): which tile along L a BlockSpec fetches at
    grid step (head, j, t). `own` is the outer axis's tile; `seen_k` the
    k tile of step t under q tile j, `seen_q` the q tile of step t over
    k tile j. Under the causal mask a step off the triangle is held at
    the nearest tile on it (the row's last visible k tile, the column's
    first visible q tile), so it fetches nothing new."""
    own = lambda j, t: j  # noqa: E731
    if not causal:
        return own, (lambda j, t: t), (lambda j, t: t)
    seen_k = lambda j, t: jnp.minimum(t, ((j + 1) * bq - 1) // bk)  # noqa: E731
    seen_q = lambda j, t: jnp.maximum(t, (j * bk) // bq)  # noqa: E731
    return own, seen_k, seen_q


class _Band:
    """The tiles a banded call (`window` < L) walks. A q tile's band
    crosses a few k tiles however long the sequence is, so the inner
    grid axis is as long as the most tiles a band can cross (`k_steps`
    under a q tile, `q_steps` over a k tile) and step t of q tile j
    reads k tile `first_k(j) + t`: no step is spent left of the band.
    A step past the row's last visible tile (the sequence's first rows,
    whose band the start cuts; the last columns, whose band the end
    cuts) is held there and runs nothing. Measured against the causal
    call's whole row held at the band's nearest tile on both sides, on
    the chip at (1, 8192, 64, 128), window 512, 512 x 512 tiles,
    forward and backward: 16.4 ms against 27.7 (`pick_tiles`)."""

    def __init__(self, L: int, bq: int, bk: int, window: int):
        self._key = (L, bq, bk, window)
        self.bq, self.bk, self.window = bq, bk, window
        self.nq = L // bq
        self.k_steps = max(
            self.last_k(j) - self.first_k(j, max) + 1 for j in range(self.nq)
        )
        self.q_steps = max(
            self.last_q(j, min) - self.first_q(j) + 1 for j in range(L // bk)
        )

    def __eq__(self, other):
        return isinstance(other, _Band) and self._key == other._key

    def __hash__(self):
        return hash(self._key)

    # `least` / `most` are min / max for whole numbers and jnp.minimum /
    # jnp.maximum for a grid step's index
    def first_k(self, j, most=jnp.maximum):
        return most(j * self.bq - (self.window - 1), 0) // self.bk

    def last_k(self, j):
        return ((j + 1) * self.bq - 1) // self.bk

    def first_q(self, j):
        return (j * self.bk) // self.bq

    def last_q(self, j, least=jnp.minimum):
        return least(
            ((j + 1) * self.bk - 1 + self.window - 1) // self.bq, self.nq - 1
        )

    def k_tile(self, j, t):
        """The k tile step t of q tile j computes on (past the row's
        last visible one: none)."""
        return self.first_k(j) + t

    def q_tile(self, j, t):
        return self.first_q(j) + t

    def picks(self):
        """`_tile_picks` for the band: a step past it is held at its
        last tile."""
        return (
            lambda j, t: j,
            lambda j, t: jnp.minimum(self.k_tile(j, t), self.last_k(j)),
            lambda j, t: jnp.minimum(self.q_tile(j, t), self.last_q(j)),
        )


class _Layout:
    """How the kernels see [B, L, H, D]: one (head, rows) tile of width
    D at a time. A head width the lanes divide is read where it lies,
    as columns h*D.. of [B, L, H*D]; any other (64, or latent
    attention's 192) cannot be a block of that array (its last edge
    must be a multiple of 128 or the whole of it), so it is folded to
    [B*H, L, D] through memory. The other way with 192, zeros padded to
    256 and read in place, measured the same (FLASH_MIN_LENGTH's
    table) and is not built.

    H is the array's own head count: k and v with fewer heads than q
    (grouped-query attention) are laid out, and folded, at theirs, and
    a grid that walks the query heads reads head i // group of them
    through `spec`'s `head`. Nothing is widened for the kernels."""

    def __init__(self, b, L, h, d):
        self.b, self.L, self.h, self.d = b, L, h, d
        self.in_place = d % 128 == 0
        self.shape = (b, L, h * d) if self.in_place else (b * h, L, d)

    def view(self, x):
        if self.in_place:
            return x.reshape(self.shape)
        return x.transpose(0, 2, 1, 3).reshape(self.shape)

    def unview(self, x):
        b, L, h, d = self.b, self.L, self.h, self.d
        if self.in_place:
            return x.reshape(b, L, h, d)
        return x.reshape(b, h, L, d).transpose(0, 2, 1, 3)

    def spec(self, rows: int, pick, head=None):
        """Tiles of `rows` rows; `pick(j, t)` is the tile's index along
        L at grid step (i, j, t). The step reads head i of this array's
        B*H, or `head(i, t)` where the grid's first axis counts another
        array's heads (a key-value head under a grid over the query
        heads, a group's query heads under a grid over the key-value
        heads)."""
        h = self.h
        if head is None:  # the equal-heads maps, to the instruction
            head = lambda i, t: i  # noqa: E731
        if self.in_place:
            index = lambda i, j, t: (  # noqa: E731
                head(i, t) // h, pick(j, t), head(i, t) % h
            )
        else:
            index = lambda i, j, t: (head(i, t), pick(j, t), 0)  # noqa: E731
        return pl.BlockSpec((1, rows, self.d), index)


def _params(interpret: bool):
    if interpret:
        return {"interpret": True}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        )
    }


_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ----------------------------------------------------------------- forward


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
               *, bq: int, bk: int, causal: bool, scale: float, band=None):
    """Streaming forward: grid (head, q tile, k tile), k innermost. One
    tile per input is resident; the online-softmax state (acc/m/l)
    lives in VMEM scratch across the k sweep; o/lse write once at the
    sweep's end (their block index is constant over kj, so Mosaic keeps
    them in VMEM until then). A step above the diagonal runs nothing
    and, its index clamped to the row's last visible tile, fetches
    nothing. Under a `band` the sweep is its steps (`_Band`). A row
    whose keys in a tile are all left of the band adds exp(0) there;
    the tile on the diagonal, which every row sees and which comes
    last, wipes that with exp(-1e30 - m) = 0."""
    qi, step = pl.program_id(1), pl.program_id(2)
    kj = step if band is None else band.k_tile(qi, step)
    window = band and band.window

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body(masked):
        vb = v_ref[0]
        # operands in the input dtype: bf16 MXU passes, f32 accumulation
        s = _dot(q_ref[0], k_ref[0], _NT) * scale  # [BQ, BK]
        if masked:
            s = _mask(s, qi, kj, bq, bk, 0, window)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _dot(p.astype(vb.dtype), vb, _NN)

    _on_visible_tiles(qi, kj, bq, bk, causal, body, window)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        l = l_ref[...]  # [BQ, 1]
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # log(l), then one Newton step on exp(-y) l = 1: Mosaic's log
        # read up to 1.1e-4 off on the chip where its exp is good to
        # 5e-6, and the backward kernels' p = exp(s - lse) carries lse's
        # error into a whole row (float32 dq, dk, dv against the
        # reference 2.7e-5 before the step, 2e-6 after: PERF.md, PR 49)
        y = jnp.log(l)
        lse_ref[0] = m_ref[...] + (y - 1.0 + l * jnp.exp(-y))


def _layouts(q, k, v):
    """(queries', keys', values', output's layout, group): each array
    by its own head count and width; the output and its cotangent have
    the queries' heads at the values' width. `group` query heads read
    one key-value head."""
    b, L, h, d = q.shape
    h_kv, dv = k.shape[2], v.shape[-1]
    return (
        _Layout(b, L, h, d), _Layout(b, L, h_kv, d), _Layout(b, L, h_kv, dv),
        _Layout(b, L, h, dv), h // h_kv,
    )


def _kv_head(group: int):
    """`_Layout.spec`'s `head` for k and v under a grid over B*H query
    heads: with H = group * H_kv, query head i of the B*H reads head
    i // group of the B*H_kv. None with equal heads, whose index maps
    hold no trace of a group."""
    return None if group == 1 else (lambda i, t: i // group)


def _flash_forward(q, k, v, causal: bool, interpret: bool, tiles, band,
                   scale: float):
    """Returns (o [B,L,H,Dv], lse [B*H, L, 1]). k and v may have fewer
    heads than q: the grid walks the query heads and each reads its
    key-value head where it lies."""
    b, L, h, d = q.shape
    bq, bk = tiles
    lay, klay, vlay, olay, group = _layouts(q, k, v)
    kv_head = _kv_head(group)
    own, seen_k, _ = band.picks() if band else _tile_picks(bq, bk, causal)
    # rows ([B*H, L, 1]) carry a trailing singleton so Mosaic's tiling
    # rule holds: block (1, BQ, 1) -> last two dims (BQ, 1) are
    # (div-by-8, equal-to-array)
    lse_spec = pl.BlockSpec((1, bq, 1), lambda i, j, t: (i, j, 0))
    out, lse = pl.pallas_call(
        functools.partial(
            _fa_kernel, bq=bq, bk=bk, causal=causal,
            scale=scale, band=band,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(olay.shape, q.dtype),
            jax.ShapeDtypeStruct((b * h, L, 1), jnp.float32),
        ],
        grid=(b * h, L // bq, band.k_steps if band else L // bk),
        in_specs=[
            lay.spec(bq, own), klay.spec(bk, seen_k, kv_head),
            vlay.spec(bk, seen_k, kv_head),
        ],
        out_specs=[olay.spec(bq, own), lse_spec],
        scratch_shapes=[
            pltpu.VMEM((bq, olay.d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        **_params(interpret),
    )(lay.view(q), klay.view(k), vlay.view(v))
    return olay.unview(out), lse


# ---------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, bq: int, bk: int, causal: bool, scale: float,
               band=None):
    """Streaming dq: grid (head, q tile, k tile), k innermost. Re-forms
    p = exp(s - lse), ds = p * (do v^T - delta) * scale, accumulates
    dq += ds k in VMEM scratch across the k sweep."""
    qi, step = pl.program_id(1), pl.program_id(2)
    kj = step if band is None else band.k_tile(qi, step)
    window = band and band.window

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked):
        kb = k_ref[0]
        s = _dot(q_ref[0], kb, _NT) * scale  # [BQ, BK]
        if masked:
            s = _mask(s, qi, kj, bq, bk, 0, window)
        p = jnp.exp(s - lse_ref[0])  # lse, delta: [BQ, 1]
        dp = _dot(do_ref[0], v_ref[0], _NT)
        ds = (p * (dp - delta_ref[0]) * scale).astype(kb.dtype)
        acc_ref[...] = acc_ref[...] + _dot(ds, kb, _NN)

    _on_visible_tiles(qi, kj, bq, bk, causal, body, window)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, bq: int, bk: int, causal: bool,
                scale: float, band=None, q_steps=None):
    """Streaming dk/dv: grid (key-value head, k tile, q tile), q
    innermost. The owned k/v tiles stay resident (their index is
    constant over the inner axis); q/do/lse/delta tiles stream past;
    dk/dv accumulate in VMEM scratch. No atomics: this kernel owns its
    k tile's outputs. The scores are formed transposed, keys down and
    queries across ([BK, BQ]), so p^T do and ds^T q are plain products
    and lse/delta come as rows ([1, BQ], one dense line each) that
    broadcast down the tile. Under a group (`q_steps`: the q steps of
    one member; None with equal heads) the inner axis is the group's
    query heads one after another, step t member t // q_steps at q step
    t % q_steps, and the group's sum is formed here, in float32, and
    rounded once."""
    kj, t = pl.program_id(1), pl.program_id(2)
    step = t if q_steps is None else t % q_steps
    qi = step if band is None else band.q_tile(kj, step)
    window = band and band.window

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked):
        qb = q_ref[0]  # [BQ, D]
        do = do_ref[0]
        st = _dot(k_ref[0], qb, _NT) * scale  # [BK, BQ]
        if masked:
            st = _mask(st, qi, kj, bq, bk, 1, window)
        pt = jnp.exp(st - lse_ref[0])  # lse, delta: [1, BQ]
        dv_acc[...] = dv_acc[...] + _dot(pt.astype(do.dtype), do, _NN)
        dpt = _dot(v_ref[0], do, _NT)
        dst = (pt * (dpt - delta_ref[0]) * scale).astype(qb.dtype)
        dk_acc[...] = dk_acc[...] + _dot(dst, qb, _NN)

    _on_visible_tiles(
        qi, kj, bq, bk, causal, body, window, band and band.nq
    )

    @pl.when(t == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, causal: bool, interpret: bool,
                    tiles, band, scale: float):
    b, L, h, d = q.shape
    bq, bk = tiles
    lay, klay, vlay, olay, group = _layouts(q, k, v)
    kv_head = _kv_head(group)
    qf, kf, vf, gf = lay.view(q), klay.view(k), vlay.view(v), olay.view(g)
    # delta_i = rowsum(do_i * o_i): tiny elementwise+reduce, XLA fuses
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1).reshape(b * h, L, 1)
    own, seen_k, seen_q = (
        band.picks() if band else _tile_picks(bq, bk, causal)
    )
    column = pl.BlockSpec((1, bq, 1), lambda i, j, t: (i, j, 0))
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, bq=bq, bk=bk, causal=causal, scale=scale, band=band
        ),
        out_shape=jax.ShapeDtypeStruct(lay.shape, q.dtype),
        grid=(b * h, L // bq, band.k_steps if band else L // bk),
        in_specs=[
            lay.spec(bq, own), klay.spec(bk, seen_k, kv_head),
            vlay.spec(bk, seen_k, kv_head), olay.spec(bq, own), column,
            column,
        ],
        out_specs=lay.spec(bq, own),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        **_params(interpret),
    )(qf, kf, vf, gf, lse, delta)
    # dk + dv: the grid walks the key-value heads. With a group its
    # inner axis runs the group's query heads one after another, each
    # over the q steps a lone head takes, into one pair of accumulators
    q_steps = band.q_steps if band else L // bq
    q_head = lambda i, t: i  # noqa: E731
    if group > 1:  # equal heads keep their maps to the instruction
        q_head = lambda i, t: i * group + t // q_steps  # noqa: E731
        of_member = seen_q
        seen_q = lambda j, t: of_member(j, t % q_steps)  # noqa: E731
    row = pl.BlockSpec(
        (1, 1, bq), lambda i, j, t: (q_head(i, t), 0, seen_q(j, t))
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, bq=bq, bk=bk, causal=causal, scale=scale, band=band,
            q_steps=q_steps if group > 1 else None,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(klay.shape, k.dtype),
            jax.ShapeDtypeStruct(vlay.shape, v.dtype),
        ],
        grid=(b * klay.h, L // bk, group * q_steps),
        in_specs=[
            lay.spec(bq, seen_q, q_head), klay.spec(bk, own),
            vlay.spec(bk, own), olay.spec(bq, seen_q, q_head), row, row,
        ],
        out_specs=[klay.spec(bk, own), vlay.spec(bk, own)],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, vlay.d), jnp.float32),
        ],
        **_params(interpret),
    )(qf, kf, vf, gf, lse.reshape(b * h, 1, L), delta.reshape(b * h, 1, L))
    return lay.unview(dq), klay.unview(dk), vlay.unview(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal: bool, interpret: bool, tiles, band,
                     scale: float):
    return _flash_forward(q, k, v, causal, interpret, tiles, band, scale)[0]


def _fa_fwd(q, k, v, causal, interpret, tiles, band, scale):
    o, lse = map(
        checkpoint_name,
        _flash_forward(q, k, v, causal, interpret, tiles, band, scale),
        RESIDUAL_NAMES,
    )
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, interpret, tiles, band, scale, residuals, g):
    # two-kernel flash backward (dq; dk+dv) from O(L*D) residuals —
    # the [L, L] score matrix is re-formed tile by tile in VMEM, never
    # materialized in HBM
    q, k, v, o, lse = residuals
    return _flash_backward(
        q, k, v, o, lse, g, causal, interpret, tiles, band, scale
    )


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


def _group(q, k, v) -> int:
    """The query heads that read one key-value head; heads that are not
    whole groups are refused."""
    group, rest = divmod(q.shape[2], k.shape[2])
    if rest or k.shape[2] != v.shape[2]:
        raise ValueError(
            f"{q.shape[2]} query heads over {k.shape[2]} key and "
            f"{v.shape[2]} value heads: not whole groups"
        )
    return group


_GROUPS_TRACED = contextvars.ContextVar("groups_traced", default=None)


@contextlib.contextmanager
def groups_traced():
    """-> a set that gains (query heads, key-value heads) for every
    call with fewer key-value heads that this thread hands to the
    kernels inside the block: k and v read where they lie."""
    seen = set()
    token = _GROUPS_TRACED.set(seen)
    try:
        yield seen
    finally:
        _GROUPS_TRACED.reset(token)


def flash_attention(q, k, v, causal: bool = True, interpret: bool = False,
                    tiles=None, window=None, scale=None):
    """Differentiable fused attention, [B, L, H, D] -> [B, L, H, Dv]:
    `v` may be of another width than `q` and `k` (latent attention),
    each laid out by its own width (`_Layout`). `k` and `v` may have
    fewer heads than `q`, the query heads a multiple (grouped-query
    attention): query head i reads key-value head i // group through
    the kernels' index maps, out of k and v as they came, and dk and dv
    come back at their heads, a group's sum formed in the dk + dv
    kernel's float32 accumulators.
    `interpret=True` runs the kernel in the Pallas interpreter and is
    for tests only (no model path passes it); compiled, the kernels
    are Mosaic programs and exist on the TPU alone. `tiles` = (q edge,
    k edge) in place of `pick_tiles(L, window)`: the tests' and the
    sweep's. `window`: the causal mask as a band, the query at t seeing
    the keys u with 0 <= t - u < window; a window that holds the whole
    sequence is the causal call itself. `scale` multiplies the scores
    in place of 1/sqrt(D): a Python number, compiled into the kernels
    (a traced value is refused). A band rides the same index maps as
    the causal call, so `window` goes with either."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            "flash_attention compiles for the TPU only (default backend "
            f"{jax.default_backend()!r}); model code calls attention(), "
            "tests pass interpret=True"
        )
    if isinstance(scale, jax.core.Tracer):
        raise TypeError(
            "scale is compiled into the kernels: a Python number, not a "
            f"traced value ({scale})"
        )
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else float(scale)
    L = q.shape[1]
    seen = _GROUPS_TRACED.get()
    if _group(q, k, v) > 1 and seen is not None:
        seen.add((q.shape[2], k.shape[2]))
    if window is not None and not (causal and window >= 1):
        raise ValueError(f"window={window} needs causal=True and a key to see")
    if window is not None and window >= L:
        window = None
    tiles = tuple(tiles or pick_tiles(L, window) or ())
    if len(tiles) != 2 or L % tiles[0] or L % tiles[1]:
        raise ValueError(f"no tile of {tiles or TILE_LADDER} divides L={L}")
    band = window and _Band(L, *tiles, window)
    return _flash_attention(q, k, v, causal, interpret, tiles, band, scale)


# check_against_reference's bound on max|kernel - ref| / max|ref|: bf16
# rounds to 2^-8 of a value; the kernels round p, ds and each output to
# bf16 where the f32 reference rounds nothing, and the comparison is
# against the tensor's largest magnitude — four such roundings
# stacked. Interpret mode on the CPU measures 0.2-0.6%.
REFERENCE_TOLERANCE = 2.0**-6


def check_against_reference(shape, interpret: bool = False, seed: int = 0,
                            window=None, v_width=None, scale=None,
                            kv_heads=None):
    """Forward and all three backward kernels at one [B, L, H, D] bf16
    shape against `reference_attention` in true f32 — chip_smoke.py's
    kernel phase and the gated chip tests. Returns, for o/dq/dk/dv,
    max|kernel - ref| / max|ref|. The cotangent is a fixed random
    tensor, so each gradient is checked against a generic direction.
    The reference runs one head at a time: its [L, L] scores and their
    backward copies would not fit beside each other at L=8192.
    `v_width`: values (and the cotangent) of another width than D;
    `scale`: in place of 1/sqrt(D), for both sides; `kv_heads`: k and v
    with that many heads under the H query heads, read in place by the
    kernels; the reference's dk and dv are the float32 sums over a
    group's query heads."""
    import numpy as np

    rng = np.random.default_rng(seed)
    b, L, h, d = shape
    h_kv, dv = kv_heads or h, v_width or d
    group = h // h_kv
    q, k, v, w = (
        jnp.asarray(rng.standard_normal(s), dtype=jnp.bfloat16)
        for s in (shape, (b, L, h_kv, d), (b, L, h_kv, dv), (b, L, h, dv))
    )

    def through(attn):
        def loss(q, k, v, w):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))

    (_, o), grads = through(
        lambda q, k, v: flash_attention(
            q, k, v, interpret=interpret, window=window, scale=scale
        )
    )(q, k, v, w)
    ref_fn = through(
        functools.partial(reference_attention, window=window, scale=scale)
    )
    refs = []
    with jax.default_matmul_precision("highest"):
        for at in range(h):
            qh, wh = (x[:, :, at : at + 1].astype(jnp.float32) for x in (q, w))
            kh, vh = (
                x[:, :, at // group : at // group + 1].astype(jnp.float32)
                for x in (k, v)
            )
            (_, oh), gh = ref_fn(qh, kh, vh, wh)
            refs.append((oh, *gh))
    errors = {}
    for name, got, ref in zip(
        ("o", "dq", "dk", "dv"),
        (o, *grads),
        (jnp.concatenate(parts, axis=2) for parts in zip(*refs)),
    ):
        got = got.astype(jnp.float32)
        if ref.shape != got.shape:  # dk, dv: the float32 sum over a group
            ref = ref.reshape(b, L, h_kv, group, -1).sum(axis=3)
        errors[name] = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    return errors


# The shortest sequence the dispatcher hands to the kernels: where
# they first beat XLA's path, forward and backward together on the same
# bf16 inputs inside one program. Measured on one TPU v5e chip ("TPU v5
# lite", jax 0.9.0, libtpu 0.0.34) on 2026-09-29, ms a call, XLA |
# kernels at the ladder's tiles:
#   (2, 1024, 12, 64) 0.258 | 0.398    (2, 1024, 16, 128) 0.446 | 0.542
#   (2, 2048, 12, 64) 2.713 | 1.231    (2, 2048, 16, 128) 3.677 | 1.738
#   (2, 4096, 12, 64) 10.24 | 4.161    (2, 4096, 16, 128) 13.86 | 5.821
# At 1024 and under XLA keeps a head's scores on the chip and wins (512:
# 0.079 | 0.166, 0.104 | 0.234); from 2048 it writes them out, ten times
# the time for four times the work. Lengths between the two are not
# measured and stay with XLA.
# Latent attention, queries and keys 192 wide over values of 128 under
# deepseek-v2-lite's scale 0.11472 (2026-10-01, the same chip kind and
# versions, `scripts/swa_kernel_sweep.py --cases latent`), ms a call,
# forward + backward (forward alone): XLA | the kernels with q and k
# folded to [B*H, L, 192] | with q and k padded to 256 and read in place:
#   (4, 2048, 16, 192 | 128) at 1024 x 1024
#       7.534 (2.587) | 6.156 (1.677) | 6.220 (1.813)
#   (2, 2048, 32, 192 | 128) at 1024 x 1024
#       7.426 (2.589) | 6.255 (1.676) | 6.148 (1.822)
#   at 512 x 512        - | 6.874 (2.258), 6.978 (2.234) | 6.983, 6.858
#   (2, 2048, 16, 128) that day: 3.788 (1.276) | 2.071 (0.604)
# Folded and padded tie forward + backward (the multiplier is 128 x 128:
# 192 costs the two passes 256 does, and a pad moves what a transpose
# moves); folded is 8 % ahead in the forward pass, which a step that
# recomputes runs twice, and is what `_Layout` does with any width the
# lanes do not divide: kept. 192 | 128 costs 1.49 x the equal-width
# call a head (14 passes of the multiplier a tile pair for 9).
# Heads of 256, read where they lie (2026-10-02, the same chip kind and
# versions, `scripts/swa_kernel_sweep.py --cases wide`), causal at
# (1, 8192, 16, 256), ms a call, forward + backward (forward alone), by
# q x k edge: 1024 x 1024 19.24 (5.06), 1024 x 512 19.90 (5.35),
# 2048 x 1024 20.17 (5.26), 512 x 1024 20.47 (5.53), 1024 x 2048 20.80
# (5.60), 512 x 512 21.15 (5.88), 256 x 256 33.31 (11.51); the same
# columns as 32 heads of 128 at 1024 x 1024: 21.15 (5.80). A contraction
# of 256 is two passes of the 128-wide multiplier and runs 9 % ahead of
# twice the heads at 128; the ladder's first edge wins, so `pick_tiles`
# reads no width. Against the float32 reference there: o 0.21 %, dq
# 0.24 %, dk 0.30 %, dv 0.28 % of the largest entry.
# Differential attention's call, both members of 20 pairs of heads:
# queries and keys of 64 folded to [B*H, L, 64], the pairs' values of
# 128 read where they lie (2026-10-03, the same chip kind and versions,
# `scripts/swa_kernel_sweep.py --cases diff`), (1, 4096, 40, 64 | 128),
# ms a call, forward + backward (forward alone), XLA | the kernels at
# 1024 x 1024 | at 512 x 512:
#   under a window of 512   17.91 (5.88) | 6.16 (1.69) | 4.93 (1.68)
#   the whole triangle      17.92 (5.87) | 8.02 (2.28) | 10.03 (3.53)
# The kernels win both (3.6 x under the band at the 512 x 512 that
# `pick_tiles` gives a window of 512, 2.2 x in full at the ladder's
# first edge), so the rule stays as it is: it reads the call's length
# and nothing of its widths. Against the float32 reference at (1, 2048,
# 8, 64 | 128), band and full: o 0.24 %, dq 0.53 %, dk 0.37-0.38 %, dv
# 0.25-0.28 % of the largest entry.
# 16,384 tokens, 28 query heads of 128 on 4 key-value heads widened in
# front of the call (as they were until PR 63), a group of 7
# (2026-10-04, the same chip kind and versions,
# `scripts/swa_kernel_sweep.py --cases band4k`; `pick_tiles`
# has the table): 34.0 ms under a window of 4096 and 62.7 ms in full at
# 1024 x 1024, forward + backward; XLA's path would write 30 GB of
# scores and is not run. Against the float32 reference at (1, 8192, 7,
# 128), band and full: o 0.27 %, dq 0.40 %, dk 0.36-0.44 %, dv
# 0.42-0.48 % of the largest entry.
# Fewer key-value heads than query heads (2026-10-05, the same chip kind
# and versions, `scripts/swa_kernel_sweep.py --cases gqa`), at the
# ladder's tiles, ms a call, forward + backward (forward alone): k and v
# widened to the query heads in front of the kernels and dk, dv summed
# over a group behind them | read where they lie through the index maps,
# the group summed in the dk + dv kernel:
#   (1, 8192, 64 on 8, 128), window 512    17.30 (6.17) | 14.84 (5.01)
#   (1, 8192, 48 on 8, 128)                31.23 (8.93) | 30.07 (8.18)
#   (1, 16384, 28 on 4, 128), window 4096  33.51 (9.41) | 32.74 (8.97)
#   (1, 16384, 28 on 4, 128)               62.25 (17.54) | 61.25 (17.05)
#   (1, 8192, 16 on 2, 256)                19.23 (4.96) | 18.37 (4.74)
#   (4, 2048, 32 on 8, 64)                  8.24 (2.20) |  7.79 (2.04)
#   (1, 4096, 32 on 2, 128)                 6.45 (1.72) |  6.00 (1.59)
#   (1, 4096, 40 on 20, 64 | 128), w. 512   5.81 (1.97) |  4.48 (1.59)
#   (1, 4096, 40 on 20, 64 | 128)           8.92 (2.49) |  7.55 (2.09)
# In place is ahead at every shape, by what the copies cost (0.45 to
# 2.5 ms a call; the kernels' own reads and products are what they
# were), so the dispatcher hands k and v over as they come at any group
# and no rule reads one. Against the float32 reference at (1, 2048, 16
# on 2, 128) under the band, (1, 2048, 14 on 2, 128), (1, 4096, 12 on 2,
# 128), (1, 2048, 8 on 1, 256), (2, 2048, 8 on 2, 64) and (1, 2048, 8 on
# 4, 64 | 128): o and dq to the bit of the widened call's (0.19-0.43 %
# of the largest entry), dk 0.26-0.39 % (widened 0.28-0.62 %) and dv
# 0.23-0.41 % (widened 0.30-0.42 %): the group's sum rounded once, and
# nearer the reference at five of the six; at 8 on 1 of 256 the largest
# entry's error reads a little further (dk 0.36 | 0.34, dv 0.41 | 0.35).
FLASH_MIN_LENGTH = 2048


def attention(q, k, v, causal: bool = True, scale=None, window=None):
    """Dispatcher, the single entry point for model code: causal,
    optionally banded (`window`: the query at t sees the keys u with
    0 <= t - u < window; None, or a window that holds the sequence, is
    the causal call and traces as it did).

    On a TPU the Pallas kernels take a call whose sequence the tile
    ladder divides and that is at least FLASH_MIN_LENGTH long, at
    every head width measured (64, 128, 192 | 128, since 2026-10-02 256
    at 8192 tokens, since 2026-10-03 64 | 128 at 4096, band and full,
    and since 2026-10-04 128 at 16,384 under a window of 4096 and in full:
    FLASH_MIN_LENGTH's table; the rule reads nothing
    but the call's own shapes); XLA's attention takes the rest.
    EDL_TPU_FLASH=1 forces the kernels on for any block-divisible L,
    EDL_TPU_FLASH=0 forces them off. The kernels hold their scores in
    float32 where XLA's path rounds them to the inputs' dtype
    (tests/test_flash_attention.py holds both to the float32 math).
    Values of another width than the queries and keys (latent
    attention: 192 | 128) and a `scale` of the caller's in place of
    1/sqrt(D) go where an equal-width call of that length goes;
    `scale` is a Python number, never a traced value.

    `k` and `v` may have fewer heads than `q` (grouped-query attention,
    the query heads a multiple): query head i reads key-value head
    i // group. The kernels read that head where it lies, through their
    index maps, and sum dk and dv over a group on the chip
    (`flash_attention`): k and v go to them as they came. XLA's path
    knows equal heads only, so in front of it, and of it alone, k and v
    are widened to the query heads and the gradient sums over a group by
    itself; a call with equal heads is traced as it was on either."""
    import os

    from elasticdl_tpu.common.constants import ENV_TPU_FLASH

    group = _group(q, k, v)
    L = q.shape[1]
    if window is not None and window >= L:
        window = None
    flag = os.environ.get(ENV_TPU_FLASH)
    if (
        jax.default_backend() == "tpu"
        and pick_tiles(L) is not None
        and flag != "0"
        and (flag == "1" or L >= FLASH_MIN_LENGTH)
    ):
        return flash_attention(q, k, v, causal, window=window, scale=scale)
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    return reference_attention(q, k, v, causal, scale, window)
