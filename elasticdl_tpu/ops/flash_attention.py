"""Fused causal attention as Pallas TPU kernels (fwd + bwd).

The hot op of the flagship transformer (models/transformer_lm.py) and
of each ring-attention step (parallel/ring_attention.py) is blockwise
softmax(QK^T)V. XLA's stock lowering materializes the [L, L] score
matrix in HBM for the full-sequence path; these kernels keep the
working set in VMEM with the standard flash-attention online-softmax
accumulator (m/l running max/denominator), so HBM traffic is O(L*D)
instead of O(L^2) and the MXU sees back-to-back [BQ,D]x[D,BK] and
[BQ,BK]x[BK,D] matmuls with f32 accumulation.

No reference equivalent (the 2019 reference has no attention model);
this is the "pallas kernels for the hot ops" arm of the TPU-first
design. All three kernels (fwd, dq, dk+dv) are STREAMING: the
non-owned sequence dimension rides the innermost grid axis — one
[BLOCK, D] tile in flight per input, accumulators live in VMEM scratch
across grid steps, output blocks revisit until their row/column is
done. VMEM use is O(BLOCK*D) regardless of L (the earlier seq-resident
layout hit Mosaic's 16M scoped-vmem wall at L=8192), which is what
makes long-context the kernel's home regime. The forward also emits
the per-row logsumexp; the backward is the standard two-kernel flash
scheme re-forming p = exp(s - lse) from O(L*D) residuals — nothing
quadratic is ever saved, and no atomics: each kernel owns its output
block (FlashAttention-2 layout). Numerics are validated
block-for-block against the reference math in
tests/test_flash_attention.py in Pallas interpret mode on CPU, and
compiled on the chip by chip_smoke.py and under EDL_TPU_TESTS=1
(`check_against_reference`).

Layout contract: [B, L, H, D] ("blhd", matching transformer_lm), any
float dtype; compute is f32. L must divide by the 128 block; callers
with ragged L use the jnp fallback (`reference_attention`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128  # q/k block edge: MXU-native tile
_NEG_INF = -1e30


def reference_attention(q, k, v, causal: bool = True, scale=None):
    """Plain-XLA causal attention, [B, L, H, D] -> [B, L, H, Dv]: `v`
    may be of another width than `q` and `k` (latent attention: 192-wide
    queries and keys, 128-wide values). `scale` multiplies the scores
    where 1/sqrt(D) is not the model's (YaRN's softmax scale)."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    s = s / math.sqrt(d) if scale is None else s * scale
    if causal:
        L = q.shape[1]
        mask = jnp.tril(jnp.ones((L, L), dtype=bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _causal_mask(qi, kj, s):
    """Mask s [BQ, BK] by global position for the (qi, kj) block pair;
    off-diagonal visible blocks pass through unchanged."""
    rows = qi * BLOCK + jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 0)
    cols = kj * BLOCK + jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1)
    return jnp.where(rows >= cols, s, _NEG_INF)


def _fold(x, b, L, h, d):
    return x.transpose(0, 2, 1, 3).reshape(b * h, L, d)


def _unfold(x, b, L, h, d):
    return x.reshape(b, h, L, d).transpose(0, 2, 1, 3)


# ----------------------------------------------------------------- forward


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
               *, n_k: int, causal: bool, scale: float):
    """Streaming forward: grid (bh, q-block, k-block), k innermost.
    One [BLOCK, D] tile per input is resident; the online-softmax state
    (acc/m/l) lives in VMEM scratch across the k sweep; o/lse write
    once at the sweep's end (their block index is constant over kj, so
    Mosaic keeps them in VMEM until then)."""
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    visible = kj <= qi if causal else kj >= 0

    @pl.when(visible)
    def _body():
        q = q_ref[0]  # [BQ, D], input dtype: MXU-native operands
        kb = k_ref[0]
        vb = v_ref[0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BQ, BK]
        if causal:
            s = _causal_mask(qi, kj, s)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(vb.dtype), vb,  # p in operand dtype: bf16 MXU pass
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == n_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l_ref[...])  # [BLOCK, 1]


def _flash_forward(q, k, v, causal: bool, interpret: bool):
    """Returns (o [B,L,H,D], lse [B*H, L, 1])."""
    b, L, h, d = q.shape
    assert L % BLOCK == 0, f"L={L} must divide by {BLOCK}"
    n_k = L // BLOCK
    scale = 1.0 / math.sqrt(d)
    # [B, L, H, D] -> [B*H, L, D]; grid = (head, q-block, k-block)
    qf, kf, vf = (_fold(x, b, L, h, d) for x in (q, k, v))
    q_spec = pl.BlockSpec((1, BLOCK, d), lambda i, j, t: (i, j, 0))
    kv_spec = pl.BlockSpec((1, BLOCK, d), lambda i, j, t: (i, t, 0))
    # rows ([B*H, L, 1]) carry a trailing singleton so Mosaic's tiling
    # rule holds: block (1, BLOCK, 1) -> last two dims (BLOCK, 1) are
    # (div-by-8, equal-to-array)
    lse_spec = pl.BlockSpec((1, BLOCK, 1), lambda i, j, t: (i, j, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fa_kernel, n_k=n_k, causal=causal, scale=scale),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, L, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, L, 1), jnp.float32),
        ],
        grid=(b * h, n_k, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        scratch_shapes=[
            pltpu.VMEM((BLOCK, d), jnp.float32),
            pltpu.VMEM((BLOCK, 1), jnp.float32),
            pltpu.VMEM((BLOCK, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return _unfold(out, b, L, h, d), lse


# ---------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, n_k: int, causal: bool, scale: float):
    """Streaming dq: grid (bh, q-block, k-block), k innermost. Re-forms
    p = exp(s - lse), ds = p * (do v^T - delta) * scale, accumulates
    dq += ds k in VMEM scratch across the k sweep."""
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    visible = kj <= qi if causal else kj >= 0

    @pl.when(visible)
    def _body():
        q = q_ref[0]  # [BQ, D]
        do = do_ref[0]
        lse = lse_ref[0]  # [BQ, 1]
        delta = delta_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            s = _causal_mask(qi, kj, s)
        p = jnp.exp(s - lse)  # [BQ, BK]
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta) * scale).astype(kb.dtype)
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == n_k - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, n_q: int, causal: bool,
                scale: float):
    """Streaming dk/dv: grid (bh, k-block, q-block), q innermost. The
    owned k/v tiles stay resident (their index is constant over qi);
    q/do/lse/delta tiles stream past; dk/dv accumulate in VMEM scratch.
    No atomics — this kernel owns its k-block's outputs."""
    kj, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    visible = qi >= kj if causal else qi >= 0

    @pl.when(visible)
    def _body():
        kb = k_ref[0]  # [BK, D]
        vb = v_ref[0]
        qb = q_ref[0]  # [BQ, D]
        do = do_ref[0]
        lse = lse_ref[0]  # [BQ, 1]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            s = _causal_mask(qi, kj, s)
        p = jnp.exp(s - lse)  # [BQ, BK]
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta) * scale).astype(qb.dtype)
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, causal: bool, interpret: bool):
    b, L, h, d = q.shape
    n_blocks = L // BLOCK
    scale = 1.0 / math.sqrt(d)
    qf, kf, vf, gf = (_fold(x, b, L, h, d) for x in (q, k, v, g))
    of = _fold(o, b, L, h, d)
    # delta_i = rowsum(do_i * o_i): tiny elementwise+reduce, XLA fuses
    delta = jnp.sum(
        gf.astype(jnp.float32) * of.astype(jnp.float32),
        axis=-1,
        keepdims=True,
    )  # [B*H, L, 1] — trailing singleton for the tiling rule
    own = pl.BlockSpec((1, BLOCK, d), lambda i, j, t: (i, j, 0))
    stream = pl.BlockSpec((1, BLOCK, d), lambda i, j, t: (i, t, 0))
    row_own = pl.BlockSpec((1, BLOCK, 1), lambda i, j, t: (i, j, 0))
    row_stream = pl.BlockSpec((1, BLOCK, 1), lambda i, j, t: (i, t, 0))
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, n_k=n_blocks, causal=causal, scale=scale
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, L, d), q.dtype),
        grid=(b * h, n_blocks, n_blocks),
        in_specs=[own, stream, stream, own, row_own, row_own],
        out_specs=own,
        scratch_shapes=[pltpu.VMEM((BLOCK, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, gf, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, n_q=n_blocks, causal=causal, scale=scale
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, L, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, L, d), v.dtype),
        ],
        grid=(b * h, n_blocks, n_blocks),
        in_specs=[stream, own, own, stream, row_stream, row_stream],
        out_specs=[own, own],
        scratch_shapes=[
            pltpu.VMEM((BLOCK, d), jnp.float32),
            pltpu.VMEM((BLOCK, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, gf, lse, delta)
    return tuple(_unfold(x, b, L, h, d) for x in (dq, dk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention(q, k, v, causal: bool, interpret: bool):
    return _flash_forward(q, k, v, causal, interpret)[0]


def _fa_fwd(q, k, v, causal, interpret):
    o, lse = _flash_forward(q, k, v, causal, interpret)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, interpret, residuals, g):
    # two-kernel flash backward (dq; dk+dv) from O(L*D) residuals —
    # the [L, L] score matrix is re-formed blockwise in VMEM, never
    # materialized in HBM
    q, k, v, o, lse = residuals
    return _flash_backward(q, k, v, o, lse, g, causal, interpret)


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q, k, v, causal: bool = True, interpret: bool = False):
    """Differentiable fused attention, [B, L, H, D] -> [B, L, H, D].
    `interpret=True` runs the kernel in the Pallas interpreter and is
    for tests only (no model path passes it); compiled, the kernels
    are Mosaic programs and exist on the TPU alone."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            "flash_attention compiles for the TPU only (default backend "
            f"{jax.default_backend()!r}); model code calls attention(), "
            "tests pass interpret=True"
        )
    return _flash_attention(q, k, v, causal, interpret)


# check_against_reference's bound on max|kernel - ref| / max|ref|: bf16
# rounds to 2^-8 of a value; the kernels round p, ds and each output to
# bf16 where the f32 reference rounds nothing, and the comparison is
# against the tensor's largest magnitude — four such roundings
# stacked. Interpret mode on the CPU measures 0.2-0.6%.
REFERENCE_TOLERANCE = 2.0**-6


def check_against_reference(shape, interpret: bool = False, seed: int = 0):
    """Forward and all three backward kernels at one [B, L, H, D] bf16
    shape against `reference_attention` in true f32 — chip_smoke.py's
    kernel phase and the gated chip tests. Returns, for o/dq/dk/dv,
    max|kernel - ref| / max|ref|. The cotangent is a fixed random
    tensor, so each gradient is checked against a generic direction.
    The reference runs one head at a time: its [L, L] scores and their
    backward copies would not fit beside each other at L=8192."""
    import numpy as np

    rng = np.random.default_rng(seed)
    q, k, v, w = (
        jnp.asarray(rng.standard_normal(shape), dtype=jnp.bfloat16)
        for _ in range(4)
    )

    def through(attn):
        def loss(q, k, v, w):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))

    (_, o), grads = through(
        lambda q, k, v: flash_attention(q, k, v, interpret=interpret)
    )(q, k, v, w)
    ref_fn = through(reference_attention)
    refs = []
    with jax.default_matmul_precision("highest"):
        for h in range(shape[2]):
            qh, kh, vh, wh = (
                x[:, :, h : h + 1].astype(jnp.float32) for x in (q, k, v, w)
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
        errors[name] = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    return errors


# Auto-engage threshold: estimated bytes of the materialized scores
# (+backward copies) beyond which XLA's [L,L] path approaches the
# 16G HBM and the O(L*D) kernels take over — the kernels are meant as
# the long-context ENABLER, not a short-sequence speedup. Where the
# crossover in time and the out-of-memory length sit on this machine
# is not measured (ROADMAP S5).
FLASH_SCORE_BYTES = 6e9


def attention(q, k, v, causal: bool = True, scale=None):
    """Dispatcher, the single entry point for model code.

    On TPU the Pallas kernels engage automatically when the estimated
    quadratic working set of XLA's materializing path would crowd HBM
    (see FLASH_SCORE_BYTES); otherwise XLA's fused attention runs.
    EDL_TPU_FLASH=1 forces the kernels on for any block-divisible L,
    EDL_TPU_FLASH=0 forces them off. Numerics are identical either way
    (tests/test_flash_attention.py). The kernels know one head width
    and the scale 1/sqrt(D): values of another width than the queries
    (latent attention) or a `scale` of the caller's never reach them,
    whatever the flag says."""
    import os

    from elasticdl_tpu.common.constants import ENV_TPU_FLASH

    b, L, h, _d = q.shape
    flag = os.environ.get(ENV_TPU_FLASH)
    kernel_shapes = scale is None and v.shape[-1] == q.shape[-1]
    if (
        kernel_shapes
        and jax.default_backend() == "tpu"
        and L % BLOCK == 0
        and flag != "0"
    ):
        score_bytes = 2.5 * b * h * L * L * 2  # bf16 probs, fwd+bwd copies
        if flag == "1" or score_bytes > FLASH_SCORE_BYTES:
            return flash_attention(q, k, v, causal)
    return reference_attention(q, k, v, causal, scale)
