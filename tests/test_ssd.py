"""`ops/ssd.py`: Mamba-2's recurrence in chunks against the recurrence
a token at a time — values and all five gradients, at chunks of 16 and
128, at decays near 0 and near 1, the head-to-group mapping, a length
the chunk does not divide refused, every exponent formed as a
difference before it is exponentiated, and the pass between chunks
against a plain loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import ssd

NAMES = ("y", "dx", "ddt", "dA", "dB", "dC")


def inputs(seed, B=2, L=64, H=8, P=4, G=2, N=6, lo=1e-3, hi=0.1,
           dtype=jnp.float32):
    """x, dt, A, B, C as a layer makes them: dt log-uniform on
    (lo, hi), A = -uniform(1, 16)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, L, H, P)).astype(dtype)
    Bm = jax.random.normal(ks[1], (B, L, G, N)).astype(dtype)
    Cm = jax.random.normal(ks[2], (B, L, G, N)).astype(dtype)
    dt = jnp.exp(jax.random.uniform(
        ks[3], (B, L, H), minval=np.log(lo), maxval=np.log(hi)
    ))
    A = -jax.random.uniform(ks[4], (H,), minval=1.0, maxval=16.0)
    return x, dt, A, Bm, Cm


def both(f, args):
    """(y, the five gradients of sum(sin y))."""
    def loss(*a):
        y = f(*a)
        return jnp.sum(jnp.sin(y)), y

    (_, y), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True
    )(*args)
    return (y, *grads)


def errors(got, want):
    return {
        name: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        for name, a, b in zip(NAMES, got, want)
    }


# (dt's range): the configuration's untrained steps; a decay near 0
# (dt x A to -160: a state forgotten inside a token, exponents that
# underflow and must not overflow); a decay near 1 (a state that lives
# through the whole sequence)
DECAYS = {"untrained": (1e-3, 0.1), "near_0": (1.0, 10.0), "near_1": (1e-6, 1e-5)}


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("chunk,length", [(16, 64), (128, 256)])
def test_chunked_holds_to_the_recurrence_values_and_gradients(
    chunk, length, decay
):
    lo, hi = DECAYS[decay]
    args = inputs(3, L=length, lo=lo, hi=hi)
    with jax.default_matmul_precision("highest"):
        got = both(lambda *a: ssd.ssd_chunked(*a, chunk=chunk)[0], args)
        want = both(ssd.ssd_recurrent, args)
    found = errors(got, want)
    assert all(np.isfinite(v) for v in found.values()), found
    # float32 in another order; dA at a fast decay sums tiny terms
    limit = 5e-4 if decay == "near_0" else 2e-5
    assert max(found.values()) < limit, found


def test_the_most_negative_log_decay_is_reported_and_carries_no_gradient():
    args = inputs(5)
    x, dt, A, Bm, Cm = args
    _y, lowest = ssd.ssd_chunked(*args, chunk=16)
    assert float(lowest) == pytest.approx(float(jnp.min(dt * A)), rel=1e-6)
    grad = jax.grad(lambda dt: ssd.ssd_chunked(x, dt, A, Bm, Cm, chunk=16)[1])(dt)
    assert float(jnp.max(jnp.abs(grad))) == 0.0


def test_a_length_the_chunk_does_not_divide_is_refused():
    args = inputs(0, L=40)
    with pytest.raises(ValueError, match="chunk must divide"):
        ssd.ssd_chunked(*args, chunk=16)
    x, dt, A, Bm, Cm = inputs(0, H=6, G=4)
    with pytest.raises(ValueError, match="groups the heads"):
        ssd.ssd_chunked(x, dt, A, Bm, Cm, chunk=16)


def test_head_j_reads_group_j_over_heads_a_group():
    """Changing group 1's B and C moves the heads 4..7 that read it and
    no other; heads given their groups one by one agree with the
    grouped call."""
    x, dt, A, Bm, Cm = inputs(7)
    with jax.default_matmul_precision("highest"):
        base = ssd.ssd_chunked(x, dt, A, Bm, Cm, chunk=16)[0]
        moved = ssd.ssd_chunked(
            x, dt, A, Bm.at[:, :, 1].add(1.0), Cm.at[:, :, 1].multiply(2.0),
            chunk=16,
        )[0]
        assert float(jnp.max(jnp.abs(moved[:, :, :4] - base[:, :, :4]))) == 0.0
        assert float(jnp.min(jnp.max(
            jnp.abs(moved[:, :, 4:] - base[:, :, 4:]), axis=(0, 1, 3)
        ))) > 1e-3
        # a group a head, widened by hand: j // 4
        reads = jnp.arange(8) // 4
        wide = ssd.ssd_chunked(
            x, dt, A, Bm[:, :, reads], Cm[:, :, reads], chunk=16
        )[0]
    np.testing.assert_allclose(wide, base, rtol=2e-5, atol=2e-6)


def test_bfloat16_operands_keep_the_decay_in_float32():
    """x, B and C in bfloat16 (a timed model's): the outputs are
    float32 and hold to the float32 call by the operands' rounding, not
    by a rounded decay (which a sequence of slow decays would
    multiply up)."""
    args = inputs(11, L=128, lo=1e-5, hi=1e-4)
    low = tuple(
        a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
        for i, a in enumerate(args)
    )
    y, _ = ssd.ssd_chunked(*low, chunk=16)
    want = ssd.ssd_recurrent(*low)  # the same rounded inputs, float32 math
    assert y.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(y - want)) / jnp.max(jnp.abs(want))) < 2e-2


def test_the_pass_between_chunks_is_the_loop_over_chunks():
    key = jax.random.PRNGKey(2)
    added = jax.random.normal(key, (2, 9, 3, 4, 5))
    total = -jax.random.uniform(jax.random.PRNGKey(3), (2, 9, 3), maxval=60.0)
    got = ssd.state_pass(added, total)
    state, want = jnp.zeros_like(added[:, 0]), []
    for c in range(9):
        want.append(state)
        state = jnp.exp(total[:, c])[..., None, None] * state + added[:, c]
    np.testing.assert_allclose(got, jnp.stack(want, axis=1), rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(got[:, 0]))) == 0.0  # the first meets zero


def test_every_exponent_is_masked_before_it_is_taken():
    """Log-decays of -1e4 a chunk: a difference taken the wrong way
    round would be exp(+1e4) = inf and poison the products with NaN."""
    x, dt, A, Bm, Cm = inputs(13, L=64)
    args = (x, dt * 1e3, A, Bm, Cm)
    got = both(lambda *a: ssd.ssd_chunked(*a, chunk=16)[0], args)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in got)
    assert "while" not in str(jax.make_jaxpr(
        lambda *a: ssd.ssd_chunked(*a, chunk=16)[0]
    )(*args))
