"""Ring attention: exact attention over a sequence-sharded mesh axis.

Sequence/context parallelism is absent from the reference (SURVEY §5.7
— it predates attention models entirely); this is a new TPU-native
capability. Design follows the ring-attention recipe (Liu et al.,
blockwise attention with K/V blocks rotating around an ICI ring):

- each `sp` rank holds a [B, L/sp, H, D] chunk of Q, K, V;
- `sp` steps: attend local Q against the currently-held K/V block with
  an online-softmax (flash-style m/l/o accumulator), then rotate K/V to
  the next rank with `lax.ppermute` — compute overlaps the permute and
  the full [L, L] score matrix never materializes;
- causal masking is applied per block from global positions, so the
  result is bit-wise the same math as full causal attention.

Must be called inside `shard_map` with `axis_name` mapped over the
sequence-parallel mesh axis. Differentiable (ppermute/while-free scan
carries transpose cleanly); the backward pass re-runs the ring.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = True,
) -> jnp.ndarray:
    """q, k, v: [B, Lc, H, D] local sequence chunks -> [B, Lc, H, D].

    With axis size 1 this degenerates to plain attention and delegates
    to `ops.flash_attention.attention`: on a TPU the Pallas O(L*D)-HBM
    kernels from 2048 tokens on, XLA's attention under that (that
    module's FLASH_MIN_LENGTH has the measured crossover;
    EDL_TPU_FLASH=1/0 force either). The ring path keeps the lax online-softmax
    (its K/V blocks already never materialize the full score matrix).
    """
    sp = lax.axis_size(axis_name)
    if sp == 1:
        from elasticdl_tpu.ops.flash_attention import attention

        return attention(q, k, v, causal=causal)
    idx = lax.axis_index(axis_name)
    b, lc, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, dtype=q.dtype))
    qs = q * scale

    q_pos = idx * lc + jnp.arange(lc)  # global positions of local queries

    def step(carry, i):
        o, l, m, kb, vb = carry
        src = (idx - i) % sp  # which global block we currently hold
        # scores: [B, H, Lq, Lk]
        s = jnp.einsum("blhd,bmhd->bhlm", qs, kb)
        if causal:
            k_pos = src * lc + jnp.arange(lc)
            mask = q_pos[:, None] >= k_pos[None, :]  # [Lq, Lk]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard: fully-masked rows keep m at -inf; exp underflows to 0
        p = jnp.exp(s - m_new[..., None])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1)
        o_new = o * correction[..., None].transpose(0, 2, 1, 3) + jnp.einsum(
            "bhlm,bmhd->blhd", p, vb
        )
        perm = [(j, (j + 1) % sp) for j in range(sp)]
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (o_new, l_new, m_new, kb, vb), None

    # fresh accumulators are replicated-typed; the scan carry becomes
    # device-varying after one step, so promote them to the q/k/v vma
    # up front (zeros_like(q) already inherits q's type)
    from elasticdl_tpu.parallel.vma_util import match_vma

    o0 = jnp.zeros_like(q)
    l0 = match_vma(jnp.zeros((b, h, lc), dtype=q.dtype), q, k, v)
    m0 = match_vma(jnp.full((b, h, lc), _NEG_INF, dtype=q.dtype), q, k, v)
    (o, l, _m, _kb, _vb), _ = lax.scan(
        step, (o0, l0, m0, k, v), jnp.arange(sp)
    )
    # l is 0 only for rows with no visible keys (cannot happen causally:
    # a query always sees its own block)
    return o / l.transpose(0, 2, 1)[..., None]
