"""The delta rule's chunked scan under ONE decay a head (`ops/kda.py`:
`kda_chunked` given `g` [B, L, H]; off the TPU that is `intra_stage`
told the decay on every channel, on it the scalar kernels, which
`test_kda_kernels.py` holds to that stage), float32 on the CPU: against
the recurrence a token at a time, against the per-channel call given
the same decay broadcast over its channels, forward and every gradient,
over chunk lengths, tails that are not a whole chunk, strong decays and
fewer key heads than value heads.

Tolerance: both sides are float32 with the same mathematics in another
order: a relative 2e-4 of the largest value, the other configurations'
tolerance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import kda

TOLERANCE = 2e-4


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def close(a, b, tolerance=TOLERANCE, floor=1e-6):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tolerance * max(np.max(np.abs(b)), floor)


def inputs(decay, length=150, key_heads=2, heads=4, dk=16, dv=12, seed=0):
    """q, k [B, L, key_heads, dk], v [B, L, heads, dv], g and beta
    [B, L, heads], the log-decay about -`decay` a token."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (2, length, key_heads, dk))
    k = jax.random.normal(keys[1], (2, length, key_heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (2, length, heads, dv))
    g = -decay * (0.5 + jax.nn.sigmoid(jax.random.normal(keys[3], (2, length, heads))))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (2, length, heads)))
    return q, k, v, g, beta


def per_channel(q, k, v, g, beta, **kw):
    """The per-channel call given the same decay: q and k widened to
    the value heads, g broadcast over the key channels."""
    group = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(x, group, axis=2) for x in (q, k))
    wide = jnp.broadcast_to(g[..., None], g.shape + (q.shape[-1],))
    return kda.kda_chunked(q, k, v, wide, beta, **kw)


CHUNKED = jax.jit(kda.kda_chunked, static_argnames=("chunk", "sub"))
RECURRENT = jax.jit(kda.kda_recurrent)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("key_heads", [4, 2, 1])
@pytest.mark.parametrize("decay", [1e-4, 0.3, 20.0])
def test_the_scalar_scan_is_the_recurrence(decay, key_heads, chunk):
    args = inputs(decay, key_heads=key_heads)
    got, lowest = CHUNKED(*args, chunk=chunk)
    assert got.shape == args[2].shape and got.dtype == jnp.float32
    assert close(got, RECURRENT(*args))
    # the most negative cumulative log-decay inside a chunk
    g = np.asarray(args[3])
    pad = -g.shape[1] % chunk
    sums = np.pad(g, ((0, 0), (0, pad), (0, 0))).reshape(2, -1, chunk, 4).sum(2)
    assert float(lowest) == pytest.approx(float(sums.min()), rel=1e-5)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("decay", [1e-2, 1.0, 20.0])
def test_the_scalar_scan_s_gradients_are_the_recurrence_s(decay, chunk):
    args = inputs(decay, length=100)
    w = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)

    def through(f):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4)
        ))(*args)

    got = through(lambda *a: kda.kda_chunked(*a, chunk=chunk)[0])
    want = through(kda.kda_recurrent)
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert a.shape == b.shape, name
        # at -20 a token a decay's gradient is of the order of exp(-20)
        # and lies under float32's rounding of the sums of order one it
        # is taken from: held to that order, not to its own size
        floor = 1e-3 if name == "dg" and decay >= 20 else 1e-6
        assert close(a, b, floor=floor), name


@pytest.mark.parametrize("length", [64, 65, 1, 63, 150])
def test_the_scalar_scan_is_its_own_per_channel_call_given_the_decay_broadcast(
    length,
):
    """Forward and every gradient, at lengths that are and are not a
    multiple of the chunk: the stage that fits one decay a head gives
    what the per-channel stage gives when told the same decay."""
    args = inputs(0.3, length=length)
    w = jax.random.normal(jax.random.PRNGKey(3), args[2].shape)

    def through(f):
        def loss(*a):
            o = f(*a, chunk=16)[0]
            return jnp.sum(o * w), o

        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True
        ))(*args)

    (_, got), got_grads = through(kda.kda_chunked)
    (_, want), want_grads = through(per_channel)
    assert close(got, want)
    # a lone token's decay moves nothing (the state it scales is zero):
    # the floor is the other gradients' order, not dg's own
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got_grads, want_grads):
        assert close(a, b, floor=1e-4), name


def test_a_trailing_one_is_one_decay_a_head_too():
    q, k, v, g, beta = inputs(0.3, length=40)
    a, _ = kda.kda_chunked(q, k, v, g, beta, chunk=16)
    b, _ = kda.kda_chunked(q, k, v, g[..., None], beta, chunk=16)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fewer_key_heads_need_one_decay_a_head():
    q, k, v, g, beta = inputs(0.3, length=40)
    wide = jnp.broadcast_to(g[..., None], g.shape + (q.shape[-1],))
    with pytest.raises(ValueError, match="one decay a head"):
        kda.kda_chunked(q, k, v, wide, beta, chunk=16)


def test_value_head_j_reads_key_head_j_over_the_group():
    """Not j mod the key heads: the two orders differ."""
    q, k, v, g, beta = inputs(0.3, length=40)
    got, _ = kda.kda_chunked(q, k, v, g, beta, chunk=16)
    blocked = [jnp.repeat(x, 2, axis=2) for x in (q, k)]
    tiled = [jnp.tile(x, (1, 1, 2, 1)) for x in (q, k)]
    assert close(got, kda.kda_chunked(*blocked, v, g, beta, chunk=16)[0], 1e-6)
    assert not close(got, kda.kda_chunked(*tiled, v, g, beta, chunk=16)[0], 1e-2)


def _stage_calls(monkeypatch):
    """What `kda.intra_stage` is given, call by call: (q, g) shapes."""
    calls, kept = [], kda.intra_stage

    def stage(q, k, v, g, beta, sub):
        calls.append((q.shape, g.shape))
        return kept(q, k, v, g, beta, sub)

    monkeypatch.setattr(kda, "intra_stage", stage)
    return calls


@pytest.mark.parametrize("key_heads", [4, 1])
def test_off_the_tpu_one_decay_a_head_is_the_per_channel_stage_told_it(
    monkeypatch, key_heads
):
    """No second plain stage: q and k widened to the value heads, the
    decay broadcast over the key channels, chunk by chunk
    [n, B, H, chunk, dk]."""
    calls = _stage_calls(monkeypatch)
    q, k, v, g, beta = inputs(0.3, length=40, key_heads=key_heads)
    kda.kda_chunked(q, k, v, g, beta, chunk=16)
    assert calls == [((3, 2, 4, 16, 16), (3, 2, 4, 16, 16))]


def test_the_per_channel_call_traces_as_it_did(monkeypatch):
    """A decay a key channel reaches `intra_stage` as it is given:
    nothing is widened or broadcast in front of it."""
    calls = _stage_calls(monkeypatch)
    q, k, v, g, beta = inputs(0.3, length=64, key_heads=4)
    wide = -jnp.abs(jax.random.normal(jax.random.PRNGKey(1), g.shape + (16,)))
    kda.kda_chunked(q, k, v, wide, beta, chunk=16)
    assert calls == [((4, 2, 4, 16, 16), (4, 2, 4, 16, 16))]
    # and fewer key heads cannot be told a decay a channel
    with pytest.raises(ValueError, match="one decay a head"):
        kda.kda_chunked(q[:, :, :2], k[:, :, :2], v, wide, beta, chunk=16)
    assert len(calls) == 1
