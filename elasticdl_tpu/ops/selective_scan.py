"""Mamba-1's selective scan: a state of [channels, N] a sequence whose
decay is one number a channel AND state column,

    h[t, d, n] = exp(dt[t, d] A[d, n]) h[t-1, d, n] + dt[t, d] B[t, n] x[t, d]
    y[t, d]    = sum_n h[t, d, n] C[t, n]

(Gu & Dao 2023, section 3; the skip D x and the gate are the caller's).
No product on [chunk, chunk] tiles computes it: the decay between two
tokens depends on the channel and the column together, so `ops/ssd.py`
(one decay a head) and `ops/kda.py` (one a key channel) do not apply.
What it costs is elementwise: T x D x N exponentials and a handful of
multiply-adds each, on a state that should never leave the chip.

Three forms of the same mathematics, chosen by the backend and by
nothing else (`selective_scan`; no flag):

- `selective_scan_kernels`: two Pallas kernels, on a TPU. The grid is
  (batch, channel tiles, chunks of `CHUNK` tokens), the chunks in
  order; a tile's state [N, lanes] lives in VMEM scratch from the first
  chunk to the last, the channels along the lanes and the N columns
  along the sublanes, and a chunk's tokens are stepped through one at a
  time: a row of x and dt, a column of B and C broadcast over the lanes
  (laid out once a chunk), two vector exponentials per 128 channels.
  HBM sees x, dt, B and C once and y once; the [T, D, N] states are
  never written. The forward kernel leaves the state at each chunk's
  START ([T / CHUNK, N, D] float32, 10 MB at 4096 x 5120 x 16), from
  which the backward kernel, walking the chunks from the last to the
  first, recomputes a chunk's states into VMEM and then runs the
  adjoint recurrence g[t-1] = exp(dt[t] A) g[t] + C[t] dy[t] over them.
  A ragged last chunk and a channel count the tile does not divide are
  padded with zeros (dt = 0 leaves the state as it is).
- `selective_scan_chunked`: plain jax, everywhere else and the
  kernels' oracle: a `lax.scan` over chunks that carries the state, a
  chunk's tokens by `lax.associative_scan` over [chunk, N, D] pairs
  (decay, write), the chunk recomputed in the backward pass
  (`jax.checkpoint`). Its working set is a chunk's, not the
  sequence's, but every level of the scan goes through HBM.
- `selective_scan_tokens`: the recurrence as written, a token at a
  time; the tests' and the benchmark reference's yardstick.

Timed on one TPU v5e at the one shape a cell runs, (1, 4096, 5120) x
16: `docs/performance.md`, "The selective scan".

Layout contract: x and dt [B, T, D] (dt float32, after its softplus),
A [N, D] float32 (= -exp(A_log): the state's columns lead, so that no
leaf of a parameter tree ends in a dim of 16), Bm and Cm [B, T, N],
h0 [B, N, D] float32 or None -> (y [B, T, D] float32, the last state
[B, N, D] float32). Everything inside is float32 whatever x, Bm and Cm
come as.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128  # tokens a grid step: the lanes of a chunk's B and C columns
LANES = 128
LANE_TILE = 512  # channels a grid step: four independent chains a token
JAX_CHUNK = 64  # `selective_scan_chunked`'s
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_UNROLL = 8

_f32 = jnp.float32


def _decay(dt, A):
    """exp(dt A), float32: the plain-jax forms' (the kernels take the
    exponential themselves)."""
    return jnp.exp(dt * A)


# ------------------------------------------------------- a token at a time


def selective_scan_tokens(x, dt, A, Bm, Cm, h0=None):
    """The recurrence as written, `lax.scan` over the tokens."""
    b, _, d = x.shape
    n = A.shape[0]
    h = jnp.zeros((b, n, d), _f32) if h0 is None else h0.astype(_f32)

    def step(h, xs):
        x_t, dt_t, b_t, c_t = (a.astype(_f32) for a in xs)
        h = _decay(dt_t[:, None, :], A) * h + (
            (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        )
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    h, y = lax.scan(
        step, h, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm))
    )
    return jnp.moveaxis(y, 0, 1), h


# ------------------------------------------------------------ plain jax


def _combine(left, right):
    """(decay, write) of two stretches of tokens that follow each
    other: the later one's decay falls on the earlier one's write."""
    return left[0] * right[0], right[0] * left[1] + right[1]


def selective_scan_chunked(x, dt, A, Bm, Cm, h0=None, chunk: int = JAX_CHUNK):
    """Chunks of `chunk` tokens in order, the state carried between
    them; inside a chunk an associative scan over the tokens' (decay,
    write) pairs [chunk, N, D]. The chunk is recomputed in the backward
    pass. A ragged tail is padded with dt = 0."""
    b, t, d = x.shape
    n = A.shape[0]
    pad = -t % chunk
    if pad:
        x, dt, Bm, Cm = (
            jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (x, dt, Bm, Cm)
        )
    A = A.astype(_f32)

    def cut(a):  # [B, T, ...] -> [chunks, B, chunk, ...]
        a = a.reshape((b, (t + pad) // chunk, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 1, 0)

    @jax.checkpoint
    def a_chunk(h, xs):
        x_c, dt_c, b_c, c_c = (a.astype(_f32) for a in xs)
        decay = _decay(dt_c[:, :, None, :], A)  # [B, chunk, N, D]
        write = (dt_c * x_c)[:, :, None, :] * b_c[..., None]
        through, states = lax.associative_scan(
            _combine, (decay, write), axis=1
        )
        states = states + through * h[:, None]
        return states[:, -1], jnp.sum(states * c_c[..., None], axis=2)

    h = jnp.zeros((b, n, d), _f32) if h0 is None else h0.astype(_f32)
    h, y = lax.scan(a_chunk, h, tuple(cut(a) for a in (x, dt, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, t + pad, d)
    return y[:, :t], h


# -------------------------------------------------------------- kernels


def _rows(block):
    """The `_UNROLL` tokens of `block`, a whole tile of sublanes: a
    Mosaic `for` unrolls all of its steps or none, so the token loop
    runs over such blocks and is written out inside one."""
    return pl.ds(pl.multiple_of(block * _UNROLL, _UNROLL), _UNROLL)


def _columns(ref_block, scratch, width):
    """A chunk's B or C, [N, CHUNK] with the tokens along the lanes,
    laid out for the token loop: `scratch[j]` = column j over `width`
    lanes."""
    n = ref_block.shape[0]
    for j in range(CHUNK):
        scratch[j] = jnp.broadcast_to(ref_block[:, j:j + 1], (n, width))


def _forward_kernel(x_ref, dt_ref, a_ref, bt_ref, ct_ref, h0_ref,
                    y_ref, starts_ref, last_ref, h_scr, b_scr, c_scr):
    chunk_at = pl.program_id(2)
    width = a_ref.shape[1]

    @pl.when(chunk_at == 0)
    def _():
        h_scr[...] = h0_ref[0]

    starts_ref[0, 0] = h_scr[...]
    _columns(bt_ref[0].astype(_f32), b_scr, width)
    _columns(ct_ref[0].astype(_f32), c_scr, width)
    A = a_ref[...]

    def tokens(block, h):
        rows = _rows(block)
        dt_rows = dt_ref[0, rows, :]  # [_UNROLL, width]
        x_rows = x_ref[0, rows, :].astype(_f32)
        y_rows = []
        for j in range(_UNROLL):
            t = block * _UNROLL + j
            dt_t, x_t = dt_rows[j:j + 1], x_rows[j:j + 1]
            h = jnp.exp(dt_t * A) * h + (dt_t * x_t) * b_scr[t]
            y_rows.append(jnp.sum(h * c_scr[t], axis=0, keepdims=True))
        y_ref[0, rows, :] = jnp.concatenate(y_rows, axis=0)
        return h

    h = lax.fori_loop(0, CHUNK // _UNROLL, tokens, h_scr[...])
    h_scr[...] = h

    @pl.when(chunk_at == pl.num_programs(2) - 1)
    def _():
        last_ref[0] = h


def _backward_kernel(x_ref, dt_ref, dy_ref, a_ref, bt_ref, ct_ref,
                     starts_ref, dlast_ref,
                     dx_ref, ddt_ref, da_ref, dbt_ref, dct_ref, dh0_ref,
                     g_scr, da_scr, h_scr, b_scr, c_scr):
    step = pl.program_id(2)  # the chunks from the last to the first
    width = a_ref.shape[1]
    n = a_ref.shape[0]

    @pl.when(step == 0)
    def _():
        g_scr[...] = dlast_ref[0]
        da_scr[...] = jnp.zeros_like(da_scr)

    _columns(bt_ref[0].astype(_f32), b_scr, width)
    _columns(ct_ref[0].astype(_f32), c_scr, width)
    A = a_ref[...]

    # the chunk's states again, from the state at its start
    h_scr[0] = starts_ref[0, 0]

    def again(block, h):
        rows = _rows(block)
        dt_rows = dt_ref[0, rows, :]
        x_rows = x_ref[0, rows, :].astype(_f32)
        for j in range(_UNROLL):
            t = block * _UNROLL + j
            dt_t, x_t = dt_rows[j:j + 1], x_rows[j:j + 1]
            h = jnp.exp(dt_t * A) * h + (dt_t * x_t) * b_scr[t]
            h_scr[t + 1] = h
        return h

    lax.fori_loop(0, CHUNK // _UNROLL, again, h_scr[0])

    lane = lax.broadcasted_iota(jnp.int32, (n, CHUNK), 1)

    def tokens(i, carry):
        g, da, db, dc = carry
        block = CHUNK // _UNROLL - 1 - i
        rows = _rows(block)
        dt_rows = dt_ref[0, rows, :]
        x_rows = x_ref[0, rows, :].astype(_f32)
        dy_rows = dy_ref[0, rows, :].astype(_f32)
        ddt_rows, dx_rows = [], []
        for j in reversed(range(_UNROLL)):
            t = block * _UNROLL + j
            dt_t, x_t, dy_t = (a[j:j + 1] for a in (dt_rows, x_rows, dy_rows))
            decay = jnp.exp(dt_t * A)
            g = g + dy_t * c_scr[t]  # the whole cotangent of h[t]
            dc_t = jnp.sum(h_scr[t + 1] * dy_t, axis=1, keepdims=True)
            db_t = jnp.sum(g * (dt_t * x_t), axis=1, keepdims=True)  # [N, 1]
            dwrite = jnp.sum(g * b_scr[t], axis=0, keepdims=True)  # of dt x
            g = g * decay  # what reaches h[t-1]
            dexp = g * h_scr[t]  # the cotangent of dt[t] A
            ddt_rows.append(
                jnp.sum(dexp * A, axis=0, keepdims=True) + dwrite * x_t
            )
            dx_rows.append(dwrite * dt_t)
            da = da + dexp * dt_t
            at = lane == t
            db, dc = jnp.where(at, db_t, db), jnp.where(at, dc_t, dc)
        ddt_ref[0, rows, :] = jnp.concatenate(ddt_rows[::-1], axis=0)
        dx_ref[0, rows, :] = jnp.concatenate(dx_rows[::-1], axis=0).astype(
            dx_ref.dtype
        )
        return g, da, db, dc

    zeros = jnp.zeros((n, CHUNK), _f32)
    g, da, db, dc = lax.fori_loop(
        0, CHUNK // _UNROLL, tokens, (g_scr[...], da_scr[...], zeros, zeros)
    )
    g_scr[...] = g
    da_scr[...] = da
    dbt_ref[0, 0] = db
    dct_ref[0, 0] = dc

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        da_ref[0] = da
        dh0_ref[0] = g


def _params(interpret: bool):
    if interpret:
        return {"interpret": True}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        )
    }


def _tile(d: int) -> int:
    """The channels a grid step takes of `d` padded to the lanes."""
    return LANE_TILE if d >= LANE_TILE else -(-d // LANES) * LANES


def _forward_call(x, dt, A, Bt, Ct, h0, interpret):
    b, t, d = x.shape
    n, width = A.shape[0], _tile(d)
    chunks, tiles = t // CHUNK, d // width
    rows = pl.BlockSpec((1, CHUNK, width), lambda i, j, c: (i, c, j))
    cols = pl.BlockSpec((1, n, CHUNK), lambda i, j, c: (i, 0, c))
    state = pl.BlockSpec((1, n, width), lambda i, j, c: (i, 0, j))
    columns = pltpu.VMEM((CHUNK, n, width), _f32)
    return pl.pallas_call(
        _forward_kernel,
        grid=(b, tiles, chunks),
        in_specs=[
            rows, rows, pl.BlockSpec((n, width), lambda i, j, c: (0, j)),
            cols, cols, state,
        ],
        out_specs=[
            rows,
            pl.BlockSpec((1, 1, n, width), lambda i, j, c: (i, c, 0, j)),
            state,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), _f32),
            jax.ShapeDtypeStruct((b, chunks, n, d), _f32),
            jax.ShapeDtypeStruct((b, n, d), _f32),
        ],
        scratch_shapes=[pltpu.VMEM((n, width), _f32), columns, columns],
        **_params(interpret),
    )(x, dt, A, Bt, Ct, h0)


def _backward_call(x, dt, dy, A, Bt, Ct, starts, dlast, interpret):
    b, t, d = x.shape
    n, width = A.shape[0], _tile(d)
    chunks, tiles = t // CHUNK, d // width
    last = chunks - 1
    rows = pl.BlockSpec((1, CHUNK, width), lambda i, j, c: (i, last - c, j))
    cols = pl.BlockSpec((1, n, CHUNK), lambda i, j, c: (i, 0, last - c))
    state = pl.BlockSpec((1, n, width), lambda i, j, c: (i, 0, j))
    partial = pl.BlockSpec(
        (1, 1, n, CHUNK), lambda i, j, c: (i, j, 0, last - c)
    )
    tile_state = pltpu.VMEM((n, width), _f32)
    columns = pltpu.VMEM((CHUNK, n, width), _f32)
    return pl.pallas_call(
        _backward_kernel,
        grid=(b, tiles, chunks),
        in_specs=[
            rows, rows, rows,
            pl.BlockSpec((n, width), lambda i, j, c: (0, j)),
            cols, cols,
            pl.BlockSpec((1, 1, n, width), lambda i, j, c: (i, last - c, 0, j)),
            state,
        ],
        out_specs=[rows, rows, state, partial, partial, state],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), x.dtype),
            jax.ShapeDtypeStruct((b, t, d), _f32),
            jax.ShapeDtypeStruct((b, n, d), _f32),
            jax.ShapeDtypeStruct((b, tiles, n, t), _f32),
            jax.ShapeDtypeStruct((b, tiles, n, t), _f32),
            jax.ShapeDtypeStruct((b, n, d), _f32),
        ],
        scratch_shapes=[
            tile_state, tile_state,
            pltpu.VMEM((CHUNK + 1, n, width), _f32), columns, columns,
        ],
        **_params(interpret),
    )(x, dt, dy, A, Bt, Ct, starts, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_kernels(x, dt, A, Bt, Ct, h0, interpret):
    y, _starts, last = _forward_call(x, dt, A, Bt, Ct, h0, interpret)
    return y, last


def _scan_kernels_fwd(x, dt, A, Bt, Ct, h0, interpret):
    y, starts, last = _forward_call(x, dt, A, Bt, Ct, h0, interpret)
    return (y, last), (x, dt, A, Bt, Ct, starts)


def _scan_kernels_bwd(interpret, kept, cotangents):
    x, dt, A, Bt, Ct, starts = kept
    dy, dlast = cotangents
    dx, ddt, da, dbt, dct, dh0 = _backward_call(
        x, dt, dy, A, Bt, Ct, starts, dlast, interpret
    )
    return (
        dx, ddt, jnp.sum(da, axis=0),
        jnp.sum(dbt, axis=1).astype(Bt.dtype),
        jnp.sum(dct, axis=1).astype(Ct.dtype), dh0,
    )


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def selective_scan_kernels(x, dt, A, Bm, Cm, h0=None, interpret: bool = False):
    """The Pallas kernels (module docstring); `interpret` runs them on
    the CPU, for the tests."""
    b, t, d = x.shape
    n = A.shape[0]
    pad_t, pad_d = -t % CHUNK, -d % _tile(d)
    h0 = jnp.zeros((b, n, d), _f32) if h0 is None else h0.astype(_f32)
    dt, A = dt.astype(_f32), A.astype(_f32)
    if pad_t or pad_d:
        x, dt = (jnp.pad(a, ((0, 0), (0, pad_t), (0, pad_d))) for a in (x, dt))
        Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad_t), (0, 0))) for a in (Bm, Cm))
        A = jnp.pad(A, ((0, 0), (0, pad_d)))
        h0 = jnp.pad(h0, ((0, 0), (0, 0), (0, pad_d)))
    y, last = _scan_kernels(
        x, dt, A, jnp.swapaxes(Bm, 1, 2), jnp.swapaxes(Cm, 1, 2), h0,
        interpret,
    )
    return y[:, :t, :d], last[:, :, :d]


def takes_kernels(backend=None) -> bool:
    """Whether `selective_scan` runs the kernels: on a TPU, at every
    shape (what the tiles do not divide is padded)."""
    backend = jax.default_backend() if backend is None else backend
    return backend == "tpu"


def selective_scan(x, dt, A, Bm, Cm, h0=None):
    """-> (y [B, T, D] float32, the last state [B, N, D]): the kernels
    on a TPU, the chunked plain-jax form elsewhere."""
    if takes_kernels():
        return selective_scan_kernels(x, dt, A, Bm, Cm, h0)
    return selective_scan_chunked(x, dt, A, Bm, Cm, h0)
