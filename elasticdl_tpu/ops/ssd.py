"""Mamba-2's selective state-space recurrence (the state-space dual
form), computed in chunks.

Per head, with a state S [P, N] that starts at zero, a step dt_t > 0
and a rate A < 0 (one number a head), so a log-decay dt_t A <= 0 and
a_t = exp(dt_t A) in (0, 1), one number a head and token:

    S_t = a_t S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t

x_t [P] is the head's input, B_t and C_t [N] are shared by the heads of
a group (head j reads group j // (H / G)). The recurrence is LINEAR in
the state and its decay is a scalar, so nothing has to be solved: with
G_r the log-decay summed from a chunk's first token through r,

    y_r = exp(G_r) S_0 C_r + sum_{i<=r} exp(G_r - G_i) dt_i (C_r . B_i) x_i
    S_C = exp(G_C) S_0 + sum_i exp(G_C - G_i) dt_i x_i B_i^T

`ssd_chunked` forms, for all chunks at once, a chunk's own outputs
(`intra`: the [chunk, chunk] scores C B^T a group, the decay matrix a
head, masked BEFORE it is exponentiated, their product against x) and
what the chunk adds to the state (`state`), then the state every chunk
meets (`state_pass`: a sum of the earlier chunks' additions weighted by
products of whole-chunk decays, ONE product with a [chunks, chunks]
lower-triangular matrix a head, no `while`), then what that state adds
to the chunk's outputs (`out`). Every exponent is a difference <= 0 of
cumulative log-decays, formed before it is exponentiated.

The log-decay, its cumulative sums, every exponential and the states
are float32 whatever the inputs are. The four products on the
multiplier (C B^T, weights x, x^T B, C S) take their operands in x's
dtype (bfloat16 in a timed model: the weights and the entering state
are rounded on their way in, as attention's probabilities are) and
accumulate in float32; the pass between chunks is float32 at
`Precision.HIGHEST`.

Measured on one TPU v5e at the cell's shape (1, 8192, 64 heads of 64 x
128, 8 groups, chunks of 128), the pass between chunks three ways and
the rest: `docs/performance.md` ("The state-space scan"); the matrix
product stayed, the associative scan and the `lax.scan` over chunks
are in `scripts/ssd_scan_probe.py` alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_F32 = jnp.float32


def _cumsum(a, axis):
    """The cumulative log-decay, float32. Looked up at the call: a
    control of the benchmark's comparison sums in bfloat16."""
    return jnp.cumsum(a.astype(_F32), axis=axis)


def _masked_exp(diff, keep):
    """exp(diff) where `keep`, else 0: the mask is laid BEFORE the
    exponential (above the diagonal a difference is positive)."""
    return jnp.exp(jnp.where(keep, diff, -jnp.inf))


def state_pass(added, total):
    """The state each chunk meets: added [B, n, H, P, N] float32 (what
    each chunk adds), total [B, n, H] (each chunk's whole log-decay,
    <= 0) -> [B, n, H, P, N], entry c = sum_{j<c} exp(total_{j+1} +
    ... + total_{c-1}) added_j, by ONE product with the [n, n] matrix
    of those factors a head."""
    n = total.shape[1]
    through = jnp.cumsum(total, axis=1)  # [B, n, H], through chunk c
    # through chunk c - 1, the same sums shifted (not `through - total`:
    # chunk c - 1's own factor is then exp(0) to the last bit)
    before = jnp.pad(through[:, :-1], ((0, 0), (1, 0), (0, 0)))
    chunks = jnp.arange(n)
    factor = _masked_exp(
        before[:, :, None, :] - through[:, None, :, :],  # [B, c, j, H]
        (chunks[:, None] > chunks[None, :])[None, :, :, None],
    )
    return jnp.einsum(
        "bcjh,bjhps->bchps", factor, added, precision=lax.Precision.HIGHEST
    )


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int = 128):
    """x [B, L, H, P], dt [B, L, H] (> 0), A [H] (< 0), Bm and Cm
    [B, L, G, N], G dividing H -> (y [B, L, H, P] float32, the most
    negative dt x A, a float32 scalar). L must be a multiple of
    `chunk`."""
    B, L, H, P = x.shape
    G, N = Bm.shape[2:]
    if L % chunk or H % G:
        raise ValueError(
            f"{L} tokens in chunks of {chunk}, {H} heads in {G} groups: "
            "the chunk must divide the length and the groups the heads"
        )
    n, e = L // chunk, H // G
    dtype = x.dtype
    dt = dt.astype(_F32)
    a = dt * A.astype(_F32)  # [B, L, H], <= 0
    xc = x.reshape(B, n, chunk, G, e, P)
    Bc = Bm.reshape(B, n, chunk, G, N).astype(dtype)
    Cc = Cm.reshape(B, n, chunk, G, N).astype(dtype)
    # [B, n, H, chunk]: a head's tokens along the lanes
    dtc = jnp.moveaxis(dt.reshape(B, n, chunk, H), 2, 3)
    cum = _cumsum(jnp.moveaxis(a.reshape(B, n, chunk, H), 2, 3), axis=-1)
    total = cum[..., -1]  # [B, n, H]
    rows = jnp.arange(chunk)
    with jax.named_scope("intra"):
        scores = jnp.einsum(
            "bnrgs,bnigs->bngri", Cc, Bc, preferred_element_type=_F32
        )  # [B, n, G, chunk, chunk], one a group
        decay = _masked_exp(
            cum[..., :, None] - cum[..., None, :], rows[:, None] >= rows[None, :]
        )  # [B, n, H, r, i]
        weights = (
            scores[:, :, :, None] * (decay * dtc[..., None, :]).reshape(
                B, n, G, e, chunk, chunk
            )
        ).astype(dtype)
        y = jnp.einsum(
            "bngeri,bnigep->bnrgep", weights, xc, preferred_element_type=_F32
        )
    with jax.named_scope("state"):
        # what token i leaves in the state the chunk ends with
        left = (jnp.exp(total[..., None] - cum) * dtc).reshape(
            B, n, G, e, chunk
        )
        added = jnp.einsum(
            "bnigep,bnigs->bngeps",
            (xc * jnp.moveaxis(left, 4, 2)[..., None].astype(dtype)), Bc,
            preferred_element_type=_F32,
        ).reshape(B, n, H, P, N)
        met = state_pass(added, total)
    with jax.named_scope("out"):
        read = jnp.einsum(
            "bnrgs,bngeps->bnrgep", Cc,
            met.reshape(B, n, G, e, P, N).astype(dtype),
            preferred_element_type=_F32,
        )
        carried = jnp.moveaxis(jnp.exp(cum), 3, 2).reshape(
            B, n, chunk, G, e, 1
        )
        y = y + carried * read
    return y.reshape(B, L, H, P), lax.stop_gradient(jnp.min(a))


def ssd_recurrent(x, dt, A, Bm, Cm):
    """The recurrence itself, a token at a time, float32 throughout
    (tests hold `ssd_chunked` to it): same arguments, -> y
    [B, L, H, P]."""
    B, L, H, P = x.shape
    G, N = Bm.shape[2:]
    x, dt, Bm, Cm = (t.astype(_F32) for t in (x, dt, Bm, Cm))
    reads = jnp.arange(H) // (H // G)  # head j reads group j // (H / G)
    Bm, Cm = Bm[:, :, reads], Cm[:, :, reads]  # [B, L, H, N]
    a = jnp.exp(dt * A.astype(_F32))
    highest = lax.Precision.HIGHEST

    def step(S, xs):  # S [B, H, P, N]
        x_t, dt_t, a_t, b_t, c_t = xs
        S = a_t[..., None, None] * S + jnp.einsum(
            "bhp,bhs->bhps", dt_t[..., None] * x_t, b_t, precision=highest
        )
        return S, jnp.einsum("bhps,bhs->bhp", S, c_t, precision=highest)

    _, y = lax.scan(
        step, jnp.zeros((B, H, P, N), _F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, a, Bm, Cm)),
    )
    return jnp.moveaxis(y, 0, 1)
