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
    pick_tiles,
    reference_attention,
)

# (L, (q edge, k edge)): None = what `pick_tiles` gives. At L = 512 a
# 256 x 128 pair has tiles the diagonal crosses off-centre ((1, 3)),
# skipped ones ((0, 2)) and fully visible ones ((1, 1)); 128 x 256 the
# same with the long edge along the keys.
TILE_CASES = [
    (2 * BLOCK, None),
    (4 * BLOCK, (256, 128)),
    (4 * BLOCK, (128, 256)),
    (4 * BLOCK, (256, 256)),
    (4 * BLOCK, (512, 128)),
]
TILE_IDS = [
    f"L{L}-" + ("ladder" if t is None else f"q{t[0]}k{t[1]}")
    for L, t in TILE_CASES
]


def _qkv(b=2, L=2 * BLOCK, h=2, d=32, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((b, L, h, d)), dtype=dtype
    )
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("L, tiles", TILE_CASES, ids=TILE_IDS)
def test_kernel_matches_reference(causal, L, tiles):
    q, k, v = _qkv(b=1, L=L)
    out = flash_attention(q, k, v, causal=causal, interpret=True, tiles=tiles)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("d", [32, 64, 128])
@pytest.mark.parametrize("L, tiles", TILE_CASES[1:], ids=TILE_IDS[1:])
def test_every_kind_of_tile_matches_reference(L, tiles, d):
    """Output and the three gradients, against a generic cotangent, on
    tile pairs that put a crossed, a skipped and a fully visible tile
    into each kernel's sweep; d = 128 is the width read in place (no
    fold through memory), 32 and 64 the folded ones."""
    q, k, v = _qkv(b=1, L=L, h=2, d=d, seed=7)
    w = _qkv(b=1, L=L, h=2, d=d, seed=8)[0]

    def through(attn):
        def loss(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o * w), o

        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)

    (_, o), grads = through(
        lambda q, k, v: flash_attention(q, k, v, interpret=True, tiles=tiles)
    )(q, k, v)
    (_, o_ref), grads_ref = through(reference_attention)(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
    for got, want in zip(grads, grads_ref):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=5e-5
        )


@pytest.mark.parametrize("L, want", [
    (128, (128, 128)), (384, (128, 128)), (2048, (1024, 1024)),
    (16384, (1024, 1024)), (96, None),
])
def test_the_ladder_picks_a_tile_or_leaves_the_length_to_the_fallback(L, want):
    """The largest edge of the ladder that divides L, under each
    edge's cap; a length that not even BLOCK divides has no tile and
    `attention` falls back (test_dispatcher_falls_back_off_tpu)."""
    assert pick_tiles(L) == want
    if want:
        assert L % want[0] == 0 and L % want[1] == 0


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
@pytest.mark.parametrize("L, tiles", [
    (BLOCK, None), (3 * BLOCK, None), (4 * BLOCK, (256, 128)),
    (4 * BLOCK, (128, 256)),
], ids=["L128", "L384", "L512-q256k128", "L512-q128k256"])
def test_gradients_match_reference(causal, L, tiles):
    """The Pallas backward kernels (dq; dk+dv, lse residuals) against
    grad-of-reference-math, across block counts and causality — the
    multi-block causal case exercises the triangular loop bounds of
    BOTH backward kernels."""
    q, k, v = _qkv(b=1, L=L, h=2, d=16, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=causal, interpret=True, tiles=tiles
            ) ** 2
        )

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
def test_values_of_another_width_or_a_scale_reach_the_kernels(
    monkeypatch, forced, case
):
    """Latent attention: 192-wide queries and keys beside 128-wide
    values, and a softmax scale of the model's own. On a TPU
    `attention` hands such a call to the Pallas kernels under the rule
    an equal-width call goes by: the traced program of a 2048-token
    call and its gradient holds the three `pallas_call`s, that of a
    1024-token call none unless the flag forces them. Traced only."""
    from elasticdl_tpu.ops import flash_attention as fa

    dk, dv = (192, 128) if case == "wider-keys" else (128, 128)
    scale = 0.5 * dk**-0.5 if case == "own-scale" else None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if forced:
        monkeypatch.setenv("EDL_TPU_FLASH", forced)
    else:
        monkeypatch.delenv("EDL_TPU_FLASH", raising=False)

    def kernels_of(length):
        q = jax.ShapeDtypeStruct((1, length, 2, dk), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((1, length, 2, dv), jnp.bfloat16)

        def loss(q, k, v):
            o = fa.attention(q, k, v, causal=True, scale=scale)
            assert o.shape == v.shape
            return jnp.sum(o.astype(jnp.float32))

        return str(
            jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, v)
        ).count("pallas_call")

    assert kernels_of(2048) == 3  # forward, dq, dk+dv
    assert kernels_of(1024) == (3 if forced else 0)


# (dk, dv): folded | in place (the routed cell's), both folded, both in
# place, in place | folded
LATENT_WIDTHS = [(192, 128), (48, 32), (256, 128), (128, 64)]


@pytest.mark.parametrize("L, tiles, window", [
    (2 * BLOCK, (128, 128), None), (4 * BLOCK, (256, 128), None),
    (4 * BLOCK, (128, 256), None), (4 * BLOCK, (128, 128), 200),
], ids=["L256-q128k128", "L512-q256k128", "L512-q128k256", "L512-band200"])
@pytest.mark.parametrize("dk, dv", LATENT_WIDTHS,
                         ids=[f"{a}-over-{b}" for a, b in LATENT_WIDTHS])
def test_a_value_width_and_a_scale_of_the_caller_s_match_the_float32_math(
    dk, dv, L, tiles, window
):
    """Forward, dq, dk and dv with values of another width than the
    queries and keys under `scale = 0.5 * dk**-0.5`, against the float32
    math and a generic cotangent, tile pair by tile pair; each operand
    laid out by its own width (192 folded beside 128 in place), and dq
    and dk come back as wide as q and k. A band rides the same index
    maps."""
    q, k, _ = _qkv(b=1, L=L, h=2, d=dk, seed=21)
    v, w, _ = _qkv(b=1, L=L, h=2, d=dv, seed=22)
    scale = 0.5 * dk**-0.5
    (_, o), grads = _through(
        lambda q, k, v: flash_attention(
            q, k, v, interpret=True, tiles=tiles, scale=scale, window=window
        ), w,
    )(q, k, v)
    (_, o_ref), grads_ref = _through(
        lambda q, k, v: reference_attention(
            q, k, v, scale=scale, window=window
        ), w,
    )(q, k, v)
    assert o.shape == v.shape
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
    for name, got, want in zip(("dq", "dk", "dv"), grads, grads_ref):
        assert got.shape == want.shape, name
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=5e-5, err_msg=name
        )


@pytest.mark.parametrize("dk, dv, scale", [(192, 128, 0.1147), (64, 64, None)])
def test_the_forward_s_row_residual_is_the_logsumexp(dk, dv, scale):
    """What the backward kernels re-form p from: log(l) after one
    Newton step on exp(-y) l = 1 is still the logsumexp of the scaled,
    masked scores."""
    import math

    from elasticdl_tpu.ops.flash_attention import _flash_forward

    q, k, _ = _qkv(b=1, L=2 * BLOCK, h=2, d=dk, seed=23)
    v = _qkv(b=1, L=2 * BLOCK, h=2, d=dv, seed=24)[0]
    scale = scale or 1.0 / math.sqrt(dk)
    _, lse = _flash_forward(q, k, v, True, True, (128, 128), None, scale)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    want = jax.nn.logsumexp(s, axis=-1).reshape(lse.shape)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want), atol=2e-6)


def test_a_traced_scale_is_refused():
    q, k, v = _qkv(b=1, L=BLOCK)
    with pytest.raises(TypeError, match="scale"):
        jax.jit(
            lambda s: flash_attention(q, k, v, interpret=True, scale=s)
        )(0.1)


@pytest.mark.parametrize("d", [64, 128])
def test_the_scale_one_over_sqrt_d_is_the_call_without_a_scale_bit_for_bit(d):
    import math

    q, k, v = _qkv(b=1, L=2 * BLOCK, h=2, d=d, seed=15)
    w = _qkv(b=1, L=2 * BLOCK, h=2, d=d, seed=16)[0]

    def call(scale):
        return _through(
            lambda q, k, v: flash_attention(
                q, k, v, interpret=True, scale=scale
            ), w,
        )

    (_, o), grads = call(1.0 / math.sqrt(d))(q, k, v)
    (_, o_none), grads_none = call(None)(q, k, v)
    for got, want in zip((o, *grads), (o_none, *grads_none)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert str(jax.make_jaxpr(call(1.0 / math.sqrt(d)))(q, k, v)) == str(
        jax.make_jaxpr(call(None))(q, k, v)
    )


def test_the_chip_s_check_takes_a_value_width_and_a_scale():
    """`check_against_reference` as chip_smoke.py calls it for the
    routed cell's shape, cut down for the interpreter."""
    from elasticdl_tpu.ops.flash_attention import (
        REFERENCE_TOLERANCE,
        check_against_reference,
    )

    errors = check_against_reference(
        (1, 2 * BLOCK, 2, 192), interpret=True, v_width=128,
        scale=0.5 * 192**-0.5,
    )
    assert set(errors) == {"o", "dq", "dk", "dv"}
    assert max(errors.values()) <= REFERENCE_TOLERANCE, errors


@pytest.mark.parametrize("case, q_shape, v_width, scale, flag, kernels", [
    ("dense-lm-2048", (2, 2048, 12, 64), 64, None, None, True),
    ("looped-lm-2048", (2, 2048, 16, 128), 128, None, None, True),
    ("long-4096", (1, 4096, 8, 64), 64, None, None, True),
    ("xla-wins-at-1024", (2, 1024, 12, 64), 64, None, None, False),
    ("xla-wins-at-512", (2, 512, 16, 128), 128, None, None, False),
    ("unmeasured-1536-stays", (1, 1536, 8, 64), 64, None, None, False),
    ("forced-on-at-1024", (2, 1024, 12, 64), 64, None, "1", True),
    ("forced-off-at-2048", (2, 2048, 12, 64), 64, None, "0", False),
    ("latent-192-over-128", (4, 2048, 16, 192), 128, None, None, True),
    ("own-scale", (2, 2048, 16, 128), 128, 0.05, None, True),
    ("latent-own-scale", (4, 2048, 16, 192), 128, 0.1147, None, True),
    ("hybrid-latent", (2, 2048, 32, 192), 128, None, None, True),
    ("latent-xla-wins-at-1024", (4, 1024, 16, 192), 128, 0.1147, None, False),
    ("latent-forced-off", (4, 2048, 16, 192), 128, 0.1147, "0", False),
    ("gated-attention-256", (1, 8192, 16, 256), 256, None, None, True),
    ("heads-of-256-xla-wins-at-1024", (1, 1024, 16, 256), 256, None, None, False),
    ("no-tile-divides-96", (2, 96, 12, 64), 64, None, "1", False),
    ("no-tile-divides-2080", (1, 2080, 8, 64), 64, None, None, False),
])
def test_the_rule_reads_shapes_and_engages_where_the_chip_said(
    monkeypatch, case, q_shape, v_width, scale, flag, kernels
):
    """On a TPU `attention` hands a call to the kernels from what it
    sees in its arguments: a length the ladder divides, at least
    FLASH_MIN_LENGTH (measured: XLA wins at 1024, the kernels at 2048,
    at both head widths), whatever the values' width and the scale.
    Traced only (`eval_shape`): nothing of these sizes is computed."""
    from elasticdl_tpu.ops import flash_attention as fa

    assert fa.FLASH_MIN_LENGTH == 2048
    assert not hasattr(fa, "FLASH_SCORE_BYTES")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if flag is None:
        monkeypatch.delenv("EDL_TPU_FLASH", raising=False)
    else:
        monkeypatch.setenv("EDL_TPU_FLASH", flag)
    called = []
    monkeypatch.setattr(
        fa, "flash_attention", lambda *a, **kw: called.append(a) or a[2]
    )
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct((*q_shape[:3], v_width), jnp.bfloat16)
    out = jax.eval_shape(
        lambda q, k, v: fa.attention(q, k, v, causal=True, scale=scale),
        q, q, v,
    )
    assert out.shape == v.shape
    assert bool(called) is kernels, case


# ------------------------------------------------------------ the band

# (window, what it is to tiles of 128): under a tile, a tile, not a
# multiple of the tile, two tiles, one key, the whole sequence and more
WINDOWS = [
    (40, "under-a-tile"), (128, "a-tile"), (200, "not-a-multiple"),
    (256, "two-tiles"), (1, "one-key"), (511, "all-but-one"),
]
BAND_TILES = [(128, 128), (256, 128), (128, 256), (256, 256), (512, 128)]


def _through(attn, w):
    def loss(q, k, v):
        o = attn(q, k, v)
        return jnp.sum(o * w), o

    return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)


def _widened(attend):
    """`attend` behind k and v repeated to the query heads: what the
    dispatcher did in front of the kernels until PR 63 and still does in
    front of XLA's path. Its transpose sums dk and dv over a group."""
    def run(q, k, v):
        group = q.shape[2] // k.shape[2]
        return attend(q, *(jnp.repeat(x, group, axis=2) for x in (k, v)))

    return run


@pytest.mark.parametrize("tiles", BAND_TILES, ids=lambda t: f"q{t[0]}k{t[1]}")
@pytest.mark.parametrize("window", [w for w, _ in WINDOWS],
                         ids=[name for _, name in WINDOWS])
def test_the_banded_kernels_match_the_reference_tile_pair_by_tile_pair(
    window, tiles
):
    """Forward, dq, dk and dv of the banded call at L = 512 against
    `reference_attention`'s banded mask and a generic cotangent, over
    tile pairs that put into each kernel's sweep a tile the band's left
    edge crosses, one the diagonal crosses, one both cross, one wholly
    inside the band and steps past the row's (the column's) last
    tile."""
    q, k, v = _qkv(b=1, L=4 * BLOCK, h=2, d=32, seed=11)
    w = _qkv(b=1, L=4 * BLOCK, h=2, d=32, seed=12)[0]
    (_, o), grads = _through(
        lambda q, k, v: flash_attention(
            q, k, v, interpret=True, tiles=tiles, window=window
        ), w,
    )(q, k, v)
    (_, o_ref), grads_ref = _through(
        lambda q, k, v: reference_attention(q, k, v, window=window), w
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
    for name, got, want in zip(("dq", "dk", "dv"), grads, grads_ref):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=5e-5, err_msg=name
        )


@pytest.mark.parametrize("window", [512, 513, 4096])
def test_a_window_that_holds_the_sequence_is_the_causal_call_bit_for_bit(
    window
):
    q, k, v = _qkv(b=1, L=4 * BLOCK, h=2, d=128, seed=13)
    w = _qkv(b=1, L=4 * BLOCK, h=2, d=128, seed=14)[0]
    tiles = (256, 128)

    def call(window):
        return _through(
            lambda q, k, v: flash_attention(
                q, k, v, interpret=True, tiles=tiles, window=window
            ), w,
        )

    (_, o), grads = call(window)(q, k, v)
    (_, o_causal), grads_causal = call(None)(q, k, v)
    for got, want in zip((o, *grads), (o_causal, *grads_causal)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    # and the same traced program: no band is built
    assert str(jax.make_jaxpr(call(window))(q, k, v)) == str(
        jax.make_jaxpr(call(None))(q, k, v)
    )
    assert str(jax.make_jaxpr(call(200))(q, k, v)) != str(
        jax.make_jaxpr(call(None))(q, k, v)
    )


def test_the_band_forgets_a_key_that_left_the_window():
    """Perturbing a key and value changes exactly the `window` queries
    that see it (3 tiles deep, window 100)."""
    q, k, v = _qkv(L=3 * BLOCK)
    at, window = 130, 100
    out1 = flash_attention(q, k, v, interpret=True, window=window)
    out2 = flash_attention(
        q, k.at[:, at].set(100.0), v.at[:, at].set(-100.0), interpret=True,
        window=window,
    )
    changed = np.any(
        np.abs(np.asarray(out1) - np.asarray(out2)) > 1e-4, axis=(0, 2, 3)
    )
    assert np.array_equal(np.flatnonzero(changed), np.arange(at, at + window))


@pytest.mark.parametrize("L, window, want", [
    (8192, 512, (512, 512)), (8192, 513, (512, 512)), (8192, 300, (256, 256)),
    (8192, 1024, (1024, 1024)), (8192, 4096, (1024, 1024)),
    (8192, 8, (128, 128)), (384, 200, (128, 128)), (8192, None, (1024, 1024)),
    (96, 8, None),
])
def test_a_band_s_tiles_are_no_longer_than_its_window(L, window, want):
    assert pick_tiles(L, window) == want


@pytest.mark.parametrize("L, tiles, window, k_steps, q_steps", [
    (8192, (512, 512), 512, 2, 2),  # the cell's: 2 of a row's 16 tiles
    (8192, (256, 256), 512, 3, 3),
    (8192, (1024, 1024), 512, 2, 2),
    (8192, (1024, 512), 512, 3, 2),
    (512, (128, 128), 1, 1, 1),
    (512, (128, 128), 200, 3, 3),
])
def test_a_band_s_inner_axis_is_as_long_as_the_tiles_it_crosses(
    L, tiles, window, k_steps, q_steps
):
    from elasticdl_tpu.ops.flash_attention import _Band

    band = _Band(L, *tiles, window)
    assert (band.k_steps, band.q_steps) == (k_steps, q_steps)
    bq, bk = tiles
    for j in range(L // bq):  # every visible pair lies in a walked tile
        first, last = int(band.first_k(j, max)), band.last_k(j)
        assert first * bk <= max(j * bq - window + 1, 0) < (first + 1) * bk
        assert last * bk <= j * bq + bq - 1 < (last + 1) * bk
        assert last - first + 1 <= k_steps
    assert band == _Band(L, *tiles, window) and hash(band) == hash(
        _Band(L, *tiles, window)
    )


def test_a_window_needs_the_causal_mask_and_a_key():
    q, k, v = _qkv(b=1, L=BLOCK)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, interpret=True, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, interpret=True, window=0)


@pytest.mark.parametrize("case, length, window, kernels, passed", [
    ("banded-8192", 8192, 512, True, 512),
    ("holds-the-sequence", 8192, 8192, True, None),
    ("under-min-length", 1024, 512, False, 512),
    ("none", 2048, None, True, None),
])
def test_the_dispatcher_hands_the_window_to_whichever_path_takes_the_call(
    monkeypatch, case, length, window, kernels, passed
):
    from elasticdl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("EDL_TPU_FLASH", raising=False)
    seen = {}
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, causal, window=None, scale=None:
            seen.update(kernel=window) or v,
    )
    monkeypatch.setattr(
        fa, "reference_attention",
        lambda q, k, v, causal, scale, window: seen.update(xla=window) or v,
    )
    x = jax.ShapeDtypeStruct((1, length, 8, 128), jnp.bfloat16)
    jax.eval_shape(
        lambda q, k, v: fa.attention(q, k, v, window=window), x, x, x
    )
    assert seen == {("kernel" if kernels else "xla"): passed}, case


# ------------------------------------------------------- heads of 256


@pytest.mark.parametrize("L, tiles", [
    (2 * BLOCK, (128, 128)), (4 * BLOCK, (256, 128)), (4 * BLOCK, (128, 256)),
], ids=["L256-q128k128", "L512-q256k128", "L512-q128k256"])
def test_heads_of_256_under_a_group_of_eight_match_the_float32_math(L, tiles):
    """Qwen3-Next's gated attention: 8 query heads of 256 over ONE
    key-value head (the cell's 16 over 2, halved), read where it lies
    by the kernels (what the dispatcher hands them since PR 63) and,
    beside that, widened in front of them as it was until then;
    forward, dq and, summed over the group (in the dk + dv kernel's
    accumulators | by the widening's own transpose), dk and dv, against
    the float32 math and a generic cotangent, tile pair by tile pair. A
    width of 256 is read where it lies (`_Layout`), two passes of the
    128-wide multiplier a contraction."""
    q, w, _ = _qkv(b=1, L=L, h=8, d=256, seed=31)
    k, v, _ = _qkv(b=1, L=L, h=1, d=256, seed=32)
    kernels = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, interpret=True, tiles=tiles
    )
    (_, o_ref), grads_ref = _through(_widened(reference_attention), w)(q, k, v)
    for attend in (kernels, _widened(kernels)):
        (_, o), grads = _through(attend, w)(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
        for name, got, want in zip(("dq", "dk", "dv"), grads, grads_ref):
            assert got.shape == want.shape, name
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-4, err_msg=name
            )


@pytest.mark.parametrize("flag, path, heads", [
    (None, "kernel", 2), ("0", "xla", 16),
], ids=["kernels", "xla"])
def test_the_dispatcher_hands_two_key_value_heads_to_the_kernels_as_they_lie(
    monkeypatch, flag, path, heads
):
    """On a TPU the call of the cell's shape, 16 query heads of 256 over
    2 key-value heads at 8192 tokens, reaches the kernels with k and v
    as they came, 2 heads, at the ladder's tiles; k and v are widened to
    the 16 in front of XLA's path and of it alone. Traced only."""
    from elasticdl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if flag is None:
        monkeypatch.delenv("EDL_TPU_FLASH", raising=False)
    else:
        monkeypatch.setenv("EDL_TPU_FLASH", flag)
    seen = []

    def taking(name):
        return lambda q, k, v, *a, **kw: seen.append(
            (name, q.shape, k.shape, v.shape)
        ) or q

    monkeypatch.setattr(fa, "flash_attention", taking("kernel"))
    monkeypatch.setattr(fa, "reference_attention", taking("xla"))
    q = jax.ShapeDtypeStruct((1, 8192, 16, 256), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 2, 256), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: fa.attention(q, k, v), q, kv, kv)
    assert seen == [
        (path, (1, 8192, 16, 256), *((1, 8192, heads, 256),) * 2)
    ]
    assert fa.pick_tiles(8192) == (1024, 1024)
