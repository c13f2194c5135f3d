"""Pallas flash-attention kernel vs the reference math.

The kernel runs in Pallas interpret mode on the CPU backend here (the
conftest pins tests to CPU); EDL_TPU_TESTS=1 adds a compiled run on
the real chip (test_cluster_gated.py covers the chip gate pattern)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.flash_attention import (
    BLOCK,
    attention,
    flash_attention,
    reference_attention,
)


def _qkv(b=2, L=2 * BLOCK, h=2, d=32, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((b, L, h, d)), dtype=dtype
    )
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_kernel_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_kernel_matches_reference_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True)
    ref = reference_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2, rtol=3e-2
    )


def test_multi_block_causality():
    """A later-block query must ignore later keys: perturbing the
    future must not change earlier outputs (3 blocks deep)."""
    q, k, v = _qkv(L=3 * BLOCK)
    out1 = flash_attention(q, k, v, interpret=True)
    k2 = k.at[:, -1].set(100.0)
    v2 = v.at[:, -1].set(-100.0)
    out2 = flash_attention(q, k2, v2, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), atol=2e-5
    )
    assert not np.allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("L", [BLOCK, 3 * BLOCK])
def test_gradients_match_reference(causal, L):
    """The Pallas backward kernels (dq; dk+dv, lse residuals) against
    grad-of-reference-math, across block counts and causality — the
    multi-block causal case exercises the triangular loop bounds of
    BOTH backward kernels."""
    q, k, v = _qkv(b=1, L=L, h=2, d=16, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_gradients_bf16_operands():
    """bf16 hot path end-to-end through the backward kernels: grads
    come back bf16 and track an f32 reference within bf16 tolerance."""
    q, k, v = _qkv(b=1, L=2 * BLOCK, h=1, d=32, dtype=jnp.bfloat16, seed=5)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, interpret=True).astype(jnp.float32) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            reference_attention(
                q.astype(jnp.float32),
                k.astype(jnp.float32),
                v.astype(jnp.float32),
            )
            ** 2
        )

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    for a, b in zip(gf, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), atol=0.15, rtol=0.1
        )


def test_dispatcher_falls_back_off_tpu():
    """On CPU (and for ragged L) `attention` must use the XLA path and
    still be exact."""
    q, k, v = _qkv(L=96)  # not a multiple of BLOCK
    out = attention(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("forced", ["1", None], ids=["flash-forced", "auto"])
@pytest.mark.parametrize("case", ["wider-keys", "own-scale"])
def test_values_of_another_width_or_a_scale_never_reach_the_kernels(
    monkeypatch, forced, case
):
    """Latent attention: 192-wide queries and keys beside 128-wide
    values, and a softmax scale of the model's own. `attention` and
    `reference_attention` take both; the Pallas kernels know one width
    and 1/sqrt(D), so such shapes stay on XLA's path even on a TPU with
    the kernels forced on. Same-width calls without a scale go where
    they went."""
    import math

    from elasticdl_tpu.ops import flash_attention as fa

    rng = np.random.default_rng(0)
    b, L, h, dk, dv = 1, 128, 2, 48, 32
    q = jnp.asarray(rng.standard_normal((b, L, h, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, L, h, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, L, h, dv if case == "wider-keys" else dk)), jnp.float32)
    scale = 0.5 * dk**-0.5 if case == "own-scale" else None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if forced:
        monkeypatch.setenv("EDL_TPU_FLASH", forced)
    called = []
    monkeypatch.setattr(
        fa, "flash_attention", lambda *a, **kw: called.append(a) or a[2]
    )
    out = fa.attention(q, k, v, causal=True, scale=scale)
    assert not called
    assert out.shape == (b, L, h, v.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (scale or 1 / math.sqrt(dk))
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None, None], s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    if forced:  # the same call with one width and no scale still takes them
        fa.attention(q, k, k, causal=True)
        assert len(called) == 1
