"""`ops/selective_scan.py`: the chunked plain-jax form and the Pallas
kernels (interpret mode: the same kernel bodies, on the CPU) against
the recurrence a token at a time — outputs, the last state and every
gradient, float32 and bfloat16 inputs, a ragged last chunk, a channel
count the lane tile does not divide, a carried state. (The kernels compiled
for a described TPU v5e at the shape the benchmark's cell runs:
`tests/test_flash_attention_v5e.py`, the one file that loads the TPU's
library.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import selective_scan as ss


def _inputs(b, t, d, n, seed=0, dtype=jnp.float32):
    """x, B and C as a block makes them, the step log-uniform on
    (0.001, 0.1) and the rate about 1..n a column (the initialiser's),
    and a state to start from."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.nn.silu(jax.random.normal(keys[0], (b, t, d))).astype(dtype)
    dt = jnp.exp(jax.random.uniform(
        keys[1], (b, t, d), minval=jnp.log(1e-3), maxval=jnp.log(0.1)
    ))
    A = -jnp.arange(1, n + 1, dtype=jnp.float32)[:, None] * jax.random.uniform(
        keys[2], (n, d), minval=0.5, maxval=1.5
    )
    Bm = jax.random.normal(keys[3], (b, t, n)).astype(dtype)
    Cm = jax.random.normal(keys[4], (b, t, n)).astype(dtype)
    return x, dt, A, Bm, Cm, jax.random.normal(keys[5], (b, n, d))


def _loss(scan):
    def loss(x, dt, A, Bm, Cm, h0):
        y, last = scan(x, dt, A, Bm, Cm, h0)
        return jnp.sum(jnp.sin(y)) + jnp.sum(last * last)

    return loss


def _kernels(*args):
    return ss.selective_scan_kernels(*args, interpret=True)


FORMS = {"chunked": ss.selective_scan_chunked, "kernels": _kernels}
SHAPES = {
    "whole": (2, 128, 128, 16),
    # 200 tokens: a ragged last chunk of either form; 160 channels: more
    # than one lane tile's worth padded
    "ragged": (1, 200, 160, 16),
    "narrow_state": (1, 64, 24, 4),
}


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_form_holds_to_the_recurrence(form, shape, dtype):
    args = _inputs(*SHAPES[shape], dtype=jnp.dtype(dtype))
    everything = tuple(range(6))
    want_value, want = jax.jit(jax.value_and_grad(
        _loss(ss.selective_scan_tokens), argnums=everything
    ))(*args)
    got_value, got = jax.jit(jax.value_and_grad(
        _loss(FORMS[form]), argnums=everything
    ))(*args)
    # the inputs are the same rounded numbers on both sides; what is
    # left is the order of float32 sums, and a bfloat16 cotangent's own
    # rounding on its way out (dx, dB, dC come back in the input's dtype)
    limit = 2e-5 if dtype == "float32" else 1e-2
    assert abs(got_value - want_value) <= 2e-5 * abs(want_value)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "h0"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.max(np.abs(g - w)) <= limit * np.max(np.abs(w)), name


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_carried_state_joins_two_halves(form):
    """The scan over a sequence is the scan over its first part, then
    over the rest from the state the first part left."""
    x, dt, A, Bm, Cm, h0 = _inputs(1, 256, 128, 16, seed=3)
    scan = FORMS[form]
    whole, last = scan(x, dt, A, Bm, Cm, h0)
    cut = 96
    first, state = scan(x[:, :cut], dt[:, :cut], A, Bm[:, :cut], Cm[:, :cut], h0)
    rest, last2 = scan(x[:, cut:], dt[:, cut:], A, Bm[:, cut:], Cm[:, cut:], state)
    np.testing.assert_allclose(
        np.concatenate([first, rest], axis=1), whole, rtol=2e-5, atol=2e-6
    )
    np.testing.assert_allclose(last2, last, rtol=2e-5, atol=2e-6)
    no_state, _ = scan(x, dt, A, Bm, Cm)
    assert float(jnp.max(jnp.abs(no_state - whole))) > 1e-3  # h0 was read


def test_the_dispatcher_reads_the_backend_alone():
    assert ss.takes_kernels("tpu") and not ss.takes_kernels("cpu")
    args = _inputs(1, 70, 24, 4)
    np.testing.assert_array_equal(
        ss.selective_scan(*args)[0], ss.selective_scan_chunked(*args)[0]
    )
