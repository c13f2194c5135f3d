"""The Pallas attention kernels given fewer key-value heads than query
heads (grouped-query attention, PR 63): query head i reads key-value
head i // group where it lies, through the index maps, and the dk + dv
kernel sums a group in its float32 accumulators. Interpret mode on the
CPU, as tests/test_flash_attention.py, whose helpers these borrow; a
file of its own so that `--dist loadfile` gives it a worker of its own."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from elasticdl_tpu.ops.flash_attention import (  # noqa: E402
    BLOCK,
    flash_attention,
    reference_attention,
)
from test_flash_attention import _qkv, _through, _widened  # noqa: E402

# (group, key-value heads, batch, D, Dv, L, tiles, window): groups of 2,
# 6, 7 and 8; keys of 64 folded, of 128 and 256 in place; the whole
# triangle, a window of 512 at its own tiles and windows of several
# tiles; unequal q and k edges; values of another width than the keys
# (differential attention's 64 | 128: k folded beside v in place)
GROUPED = [
    (2, 2, 2, 64, 64, 2 * BLOCK, (128, 128), None),
    (4, 1, 1, 64, 64, 4 * BLOCK, (128, 256), 128),
    (6, 1, 2, 128, 128, 2 * BLOCK, (128, 128), None),
    (7, 1, 1, 128, 128, 4 * BLOCK, (128, 128), 300),
    (8, 1, 1, 128, 128, 8 * BLOCK, (512, 512), 512),
    (2, 3, 1, 128, 128, 4 * BLOCK, (256, 128), 200),
    (8, 1, 1, 256, 256, 2 * BLOCK, (128, 128), None),
    (2, 2, 1, 64, 128, 4 * BLOCK, (256, 128), 200),
    (2, 1, 1, 128, 64, 2 * BLOCK, (128, 128), None),
]


@pytest.mark.parametrize(
    "group, h_kv, b, d, dv, L, tiles, window", GROUPED,
    ids=[
        f"{g * n}on{n}-b{b}-{d}over{dv}-L{L}-q{t[0]}k{t[1]}-w{w}"
        for g, n, b, d, dv, L, t, w in GROUPED
    ],
)
def test_a_group_reads_its_key_value_head_where_it_lies(
    group, h_kv, b, d, dv, L, tiles, window
):
    """The kernels given fewer key-value heads than query heads: query
    head i reads head i // group through the index maps, and the dk + dv
    kernel walks a group's query heads into one pair of float32
    accumulators. o, dq, dk and dv against the float32 math and a
    generic cotangent, and against the same kernels behind k and v
    widened: o and dq bit for bit (the same tiles through the same
    steps), dk and dv to the order of a float32 sum."""
    h = group * h_kv
    q = _qkv(b=b, L=L, h=h, d=d, seed=41)[0]
    k = _qkv(b=b, L=L, h=h_kv, d=d, seed=42)[0]
    v = _qkv(b=b, L=L, h=h_kv, d=dv, seed=43)[0]
    w = _qkv(b=b, L=L, h=h, d=dv, seed=44)[0]
    kernels = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, interpret=True, tiles=tiles, window=window
    )
    (_, o), grads = _through(kernels, w)(q, k, v)
    (_, o_wide), grads_wide = _through(_widened(kernels), w)(q, k, v)
    (_, o_ref), grads_ref = _through(_widened(
        lambda q, k, v: reference_attention(q, k, v, window=window)
    ), w)(q, k, v)
    assert o.shape == (b, L, h, dv)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
    for name, got, want in zip(("dq", "dk", "dv"), grads, grads_ref):
        assert got.shape == want.shape, name
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, err_msg=name
        )
    assert np.array_equal(np.asarray(o), np.asarray(o_wide))
    assert np.array_equal(np.asarray(grads[0]), np.asarray(grads_wide[0]))
    for name, got, want in zip(("dk", "dv"), grads[1:], grads_wide[1:]):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, err_msg=name
        )


def test_the_other_reading_of_a_group_is_another_result():
    """Head i reads head i // group, not i mod the key-value heads."""
    q = _qkv(b=1, L=2 * BLOCK, h=4, d=128, seed=45)[0]
    k, v, _ = _qkv(b=1, L=2 * BLOCK, h=2, d=128, seed=46)
    got = flash_attention(q, k, v, interpret=True)
    blocked = reference_attention(
        q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    )
    interleaved = reference_attention(
        q, jnp.tile(k, (1, 1, 2, 1)), jnp.tile(v, (1, 1, 2, 1))
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(blocked), atol=2e-5)
    assert np.max(np.abs(np.asarray(got) - np.asarray(interleaved))) > 1e-2


@pytest.mark.parametrize("kv_heads, v_heads", [(4, 4), (2, 3), (8, 8)])
def test_the_kernels_refuse_heads_that_are_not_whole_groups(kv_heads, v_heads):
    q = _qkv(b=1, L=BLOCK, h=6, d=32)[0]
    k = _qkv(b=1, L=BLOCK, h=kv_heads, d=32)[0]
    v = _qkv(b=1, L=BLOCK, h=v_heads, d=32)[0]
    with pytest.raises(ValueError, match="not whole groups"):
        flash_attention(q, k, v, interpret=True)


# `jax.value_and_grad` of a bfloat16 call with equal heads, traced on
# the parent of PR 63 (commit eb6ab00), addresses wiped: the same
# digests, so with a group of one the index maps, grids and kernels hold
# no trace of a group. (shape, Dv, window, tiles)
TRACED = {
    "folded-64": (((2, 256, 4, 64), 64, None, None), "f85c7ac58d23c039"),
    "in-place-128": (
        ((1, 512, 2, 128), 128, None, (256, 128)), "f4ac8dd4ae0a3b27"
    ),
    "banded-128": (
        ((1, 512, 2, 128), 128, 200, (128, 128)), "8b45aff631e64491"
    ),
    "latent-192-128": (((1, 256, 2, 192), 128, None, None), "aa97e366486a132b"),
    "banded-64-128": (
        ((1, 512, 4, 64), 128, 130, (128, 256)), "01c328c345d2e1b2"
    ),
}


@pytest.mark.parametrize("case", sorted(TRACED))
def test_a_call_with_equal_heads_traces_to_the_parent_s_jaxpr(case):
    import hashlib
    import re

    (shape, dv, window, tiles), digest = TRACED[case]
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct((*shape[:3], dv), jnp.bfloat16)

    def loss(q, k, v, w):
        o = flash_attention(
            q, k, v, interpret=True, window=window, tiles=tiles
        )
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

    def traced(*args):
        text = str(
            jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(*args)
        )
        return re.sub(r" at 0x[0-9a-f]+", "", text)

    text = traced(x, x, v, v)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
    # and a group does leave its trace: half the key-value heads
    kv = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        (a.shape[0], a.shape[1], a.shape[2] // 2, a.shape[3]), a.dtype
    )
    assert traced(x, kv(x), kv(v), v) != text


def test_the_chip_s_check_takes_fewer_key_value_heads():
    """`check_against_reference(..., kv_heads=)` as chip_smoke.py and
    the gated chip tests call it, cut down for the interpreter: k and v
    at their own heads, dk and dv against the float32 sum over a group;
    without `kv_heads` it draws what it drew."""
    from elasticdl_tpu.ops.flash_attention import (
        REFERENCE_TOLERANCE,
        check_against_reference,
    )

    for shape, how in [
        ((1, 2 * BLOCK, 4, 128), {"kv_heads": 2, "window": 100}),
        ((2, BLOCK, 4, 64), {"kv_heads": 1, "v_width": 128}),
    ]:
        errors = check_against_reference(shape, interpret=True, **how)
        assert set(errors) == {"o", "dq", "dk", "dv"}
        assert max(errors.values()) <= REFERENCE_TOLERANCE, (shape, errors)
    shape = (1, BLOCK, 2, 64)
    assert check_against_reference(
        shape, interpret=True, kv_heads=2
    ) == check_against_reference(shape, interpret=True)


def test_the_calls_read_in_place_are_counted_while_a_thread_traces():
    """`groups_traced`: (query heads, key-value heads) of every call
    with a group that reaches the kernels inside the block; a call with
    equal heads, and one outside the block, leave nothing."""
    from elasticdl_tpu.ops import flash_attention as fa

    x = jax.ShapeDtypeStruct((1, BLOCK, 4, 32), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, BLOCK, 2, 32), jnp.float32)
    one = jax.ShapeDtypeStruct((1, BLOCK, 1, 32), jnp.float32)
    call = lambda q, k, v: flash_attention(q, k, v, interpret=True)  # noqa: E731
    with fa.groups_traced() as groups:
        jax.eval_shape(call, x, x, x)
        assert groups == set()
        jax.eval_shape(call, x, kv, kv)
        jax.eval_shape(call, x, kv, kv)
        jax.eval_shape(call, x, one, one)
    assert sorted(groups) == [(4, 1), (4, 2)]
    jax.eval_shape(call, x, kv, kv)
    assert sorted(groups) == [(4, 1), (4, 2)]
