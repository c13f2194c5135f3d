"""The `intra` stage of the delta rule as Pallas kernels
(`ops/kda_kernels.py`), in Pallas interpret mode on the CPU: the six
matrices and the five input gradients against the jax stage
(`kda.intra_stage`, their oracle), `kda_chunked` through the kernels
against the recurrence a token at a time at the tolerances
`test_hybrid_lm.py` holds the jax form to, the dispatcher's rule, and
the kernels compiled for a described TPU v5e at the width the hybrid
cell runs (nothing executes there); and the same kernels under one
decay a head (`scalar_intra_stage`: Gated DeltaNet's) against
`kda.intra_stage` told that decay on every channel with q and k widened
(the path off the TPU), fewer key heads than value heads among them.

One shape throughout, (1, 128, 2, 128) in chunks of 64, a chunk a grid
step (`one_chunk_a_step`: the interpreter's XLA:CPU compile of the
unrolled chunk is what these tests cost, and it grows with the chunks a
step holds; one test weaves two). The functions are jitted once each.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from elasticdl_tpu.ops import kda, kda_kernels  # noqa: E402
from test_hybrid_lm import DECAYS, close, recurrence_inputs  # noqa: E402

WIDE = dict(batch=1, length=128, heads=2, dk=128, dv=128)
NAMES = "U Wt q_in Bqk k_out total".split()


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def one_chunk_a_step(monkeypatch):
    monkeypatch.setattr(kda_kernels, "TILES", (1,))


def _chunks(x, chunk=64):
    x = x.reshape((x.shape[0], x.shape[1] // chunk, chunk) + x.shape[2:])
    return jnp.moveaxis(x, (1, 3), (0, 2))


def jax_stage(q, k, v, g, beta):
    return kda.intra_stage(
        _chunks(q), _chunks(k), _chunks(v), _chunks(g),
        _chunks(beta)[..., None], 16,
    )


def kernel_stage(q, k, v, g, beta):
    U, Wt, q_in, Bqk, k_out, total = kda_kernels.intra_stage(
        q, k, v, g, beta, 64, 16, True
    )
    return U, Wt, q_in, Bqk, jnp.swapaxes(k_out, -1, -2), total


def _weights(like):
    return [
        jax.random.normal(jax.random.PRNGKey(10 + i), x.shape)
        for i, x in enumerate(like)
    ]


def _stage_gradients(stage):
    def loss(weights, *args):
        return sum(jnp.sum(o * w) for o, w in zip(stage(*args), weights))

    return jax.jit(jax.grad(loss, argnums=(1, 2, 3, 4, 5)))


def _scan_gradients(scan):
    return jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.sin(scan(*a))), argnums=(0, 1, 2, 3, 4)
    ))


JAX_STAGE = jax.jit(jax_stage)
KERNEL_STAGE = jax.jit(kernel_stage)
JAX_STAGE_GRADS = _stage_gradients(jax_stage)
KERNEL_STAGE_GRADS = _stage_gradients(kernel_stage)
THROUGH_KERNELS = jax.jit(lambda *a: kda.kda_chunked(*a, interpret=True)[0])
RECURRENT = jax.jit(kda.kda_recurrent)
THROUGH_KERNELS_GRADS = _scan_gradients(
    lambda *a: kda.kda_chunked(*a, interpret=True)[0]
)
RECURRENT_GRADS = _scan_gradients(kda.kda_recurrent)


@pytest.mark.parametrize("decay", DECAYS)
def test_the_kernel_s_six_matrices_are_the_jax_stage_s(decay):
    args = recurrence_inputs(decay, **WIDE)
    for name, got, want in zip(NAMES, KERNEL_STAGE(*args), JAX_STAGE(*args)):
        assert got.shape == want.shape, name
        assert bool(jnp.all(jnp.isfinite(got))), name
        assert close(got, want, 2e-5), name


@pytest.mark.parametrize("decay", [1e-4, 1.0, 20.0])
def test_the_kernels_gradients_are_jax_s_through_the_jax_stage(decay):
    """Every output weighted by a fixed random tensor, so each of the
    six cotangents is a generic direction."""
    args = recurrence_inputs(decay, **WIDE)
    weights = _weights(JAX_STAGE(*args))
    got = KERNEL_STAGE_GRADS(weights, *args)
    want = JAX_STAGE_GRADS(weights, *args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert close(a, b, 2e-5, floor=1e-3), name


@pytest.mark.parametrize("decay", DECAYS)
def test_the_scan_through_the_kernels_is_the_recurrence(decay):
    args = recurrence_inputs(decay, **WIDE)
    got = THROUGH_KERNELS(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert close(got, RECURRENT(*args), 2e-5)


@pytest.mark.parametrize("decay", [1e-4, 1.0, 20.0])
def test_the_scan_s_gradients_through_the_kernels_are_the_recurrence_s(decay):
    args = recurrence_inputs(decay, **WIDE)
    got, want = THROUGH_KERNELS_GRADS(*args), RECURRENT_GRADS(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert close(a, b, 1e-4, floor=1e-3), name


def test_keys_that_resemble_each_other_under_a_slow_decay_pass_the_kernels():
    """I + Diag(beta) A near a constant below the diagonal: the
    substitution's entries stay small where a product of powers holds
    1e17 (call 2 of PR 38 trained to NaN that way)."""
    q, k, v, g, beta = recurrence_inputs(1e-4, **WIDE)
    k = k * 0.05 + k[:, :1]  # one direction and a little of its own
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = 0.98 + 0.0 * beta
    for name, got, want in zip(
        NAMES, KERNEL_STAGE(q, k, v, g, beta), JAX_STAGE(q, k, v, g, beta)
    ):
        assert bool(jnp.all(jnp.isfinite(got))), name
        assert close(got, want, 1e-3), name
    assert close(
        THROUGH_KERNELS(q, k, v, g, beta), RECURRENT(q, k, v, g, beta), 1e-3
    )


def test_a_length_that_is_no_multiple_of_the_chunk_is_padded_for_the_kernels():
    """The tail writes nothing and does not decay: 100 tokens are
    padded to the two chunks the other tests run."""
    args = recurrence_inputs(0.3, **{**WIDE, "length": 100})
    got = THROUGH_KERNELS(*args)
    assert got.shape == args[2].shape
    assert close(got, RECURRENT(*args), 2e-5)
    grads = THROUGH_KERNELS_GRADS(*args)
    for name, a, b in zip("q k v g beta".split(), grads, RECURRENT_GRADS(*args)):
        assert a.shape == b.shape, name
        assert close(a, b, 1e-4, floor=1e-3), name


@pytest.mark.parametrize("dk, dv, chunk, sub, backend, taken", [
    (128, 128, 64, 16, "tpu", True),  # the hybrid cell's
    (256, 128, 64, 16, "tpu", True),
    (128, 128, 128, 8, "tpu", True),
    (128, 128, 16, 16, "tpu", True),
    (16, 12, 64, 16, "tpu", False),  # the tests' heads
    (128, 64, 64, 16, "tpu", False),  # values of half a lane row
    (192, 128, 64, 16, "tpu", False),
    (128, 128, 32, 4, "tpu", False),  # blocks of under a sublane tile
    (128, 128, 64, 24, "tpu", False),  # blocks that do not divide the chunk
    (128, 128, 64, 16, "cpu", False),  # off the TPU always the jax stage
    (128, 128, 64, 16, "gpu", False),
    (128, 128, 64, 16, None, False),  # this process: the CPU
])
def test_the_dispatcher_reads_the_shapes_and_the_backend(
    dk, dv, chunk, sub, backend, taken
):
    assert kda.takes_kernels(dk, dv, chunk, sub, backend) is taken


def test_two_chunks_of_a_grid_step_are_woven_and_each_is_its_own(monkeypatch):
    """Two chunks a grid step: their generators run a stage at a time
    each (`_weave`), and the matrices are what a chunk a step gives."""
    args = recurrence_inputs(0.3, **WIDE)
    alone = KERNEL_STAGE(*args)
    monkeypatch.setattr(kda_kernels, "TILES", (2, 1))
    assert kda_kernels.pick_tiles(2) == 2 and kda_kernels.pick_tiles(3) == 1
    for name, got, want in zip(NAMES, jax.jit(kernel_stage)(*args), alone):
        assert close(got, want, 1e-6), name


def test_off_the_tpu_the_scan_holds_no_kernel():
    args = recurrence_inputs(0.3, **WIDE)
    held = str(jax.make_jaxpr(lambda *a: kda.kda_chunked(*a)[0])(*args))
    assert "pallas_call" not in held
    through = str(jax.make_jaxpr(
        lambda *a: kda.kda_chunked(*a, interpret=True)[0]
    )(*args))
    assert "pallas_call" in through


def test_the_chip_check_reports_which_path_it_held_to_the_recurrence():
    errors = kda.check_against_recurrence((1, 64, 1, 128), interpret=True)
    assert errors.pop("kernels") is True
    assert sorted(errors) == ["dbeta", "dg", "dk", "dq", "dv", "o"]
    assert all(e < 2e-5 for e in errors.values()), errors
    assert kda.check_against_recurrence((1, 64, 1, 16))["kernels"] is False


# ------------------------------------------------- one decay a head


def scalar_inputs(decay, key_heads=1, seed=0):
    """`recurrence_inputs` at the kernels' width with one decay a head
    and `key_heads` key heads under 2 value heads."""
    q, k, v, g, beta = recurrence_inputs(decay, seed=seed, **WIDE)
    return q[:, :, :key_heads], k[:, :, :key_heads], v, g[..., 0], beta


def scalar_jax_stage(q, k, v, g, beta):
    """The per-channel jax stage told the one decay on every channel,
    q and k widened to the value heads: what `kda_chunked` runs off the
    TPU, and the scalar kernels' oracle."""
    q, k = (jnp.repeat(x, v.shape[2] // x.shape[2], axis=2) for x in (q, k))
    wide = jnp.broadcast_to(g[..., None], g.shape + (q.shape[-1],))
    return kda.intra_stage(
        _chunks(q), _chunks(k), _chunks(v), _chunks(wide),
        _chunks(beta)[..., None], 16,
    )


def scalar_kernel_stage(q, k, v, g, beta):
    U, Wt, q_in, Bqk, k_out, total = kda_kernels.scalar_intra_stage(
        q, k, v, g, beta, 64, 16, True
    )
    # the head's one sum lies on every lane, as the per-channel stage's
    assert total.shape[-1] == q.shape[-1]
    return U, Wt, q_in, Bqk, jnp.swapaxes(k_out, -1, -2), total


SCALAR_JAX = jax.jit(scalar_jax_stage)
SCALAR_KERNEL = jax.jit(scalar_kernel_stage)
SCALAR_JAX_GRADS = _stage_gradients(scalar_jax_stage)
SCALAR_KERNEL_GRADS = _stage_gradients(scalar_kernel_stage)


@pytest.mark.parametrize("key_heads", [2, 1])
@pytest.mark.parametrize("decay", [1e-4, 0.3, 20.0])
def test_the_scalar_kernel_s_six_matrices_are_the_jax_stage_s_told_one_decay(
    decay, key_heads
):
    """Under one decay a head the pairs are products times a [C, C]
    matrix of exponentials (`_scalar_pairs`); with one key head under
    two value heads both grid steps read the one key head's rows."""
    args = scalar_inputs(decay, key_heads)
    got, want = SCALAR_KERNEL(*args), SCALAR_JAX(*args)
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape, name
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert close(a, b, 2e-5), name


@pytest.mark.parametrize("key_heads", [2, 1])
@pytest.mark.parametrize("decay", [1e-4, 1.0, 20.0])
def test_the_scalar_kernels_gradients_are_jax_s_through_the_stage_told_one_decay(
    decay, key_heads
):
    args = scalar_inputs(decay, key_heads)
    weights = _weights(SCALAR_JAX(*args))
    got = SCALAR_KERNEL_GRADS(weights, *args)
    want = SCALAR_JAX_GRADS(weights, *args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.shape == b.shape, name
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert close(a, b, 2e-5, floor=1e-3), name


@pytest.mark.parametrize("decay", [1e-2, 20.0])
def test_the_scalar_scan_through_the_kernels_is_the_recurrence(decay):
    """Forward and every gradient, one key head under two value heads,
    a length that is padded to the chunk."""
    q, k, v, g, beta = scalar_inputs(decay, 1)
    args = tuple(x[:, :100] for x in (q, k, v, g, beta))
    through = lambda *a: kda.kda_chunked(*a, interpret=True)[0]  # noqa: E731
    assert "pallas_call" in str(jax.make_jaxpr(through)(*args))
    assert close(jax.jit(through)(*args), RECURRENT(*args), 2e-5)
    got = _scan_gradients(through)(*args)
    want = RECURRENT_GRADS(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.shape == b.shape, name
        assert close(a, b, 1e-4, floor=1e-3), name


def test_the_per_channel_kernels_trace_as_they_did():
    """`scalar` defaults to off and adds no operation: the per-channel
    kernel's jaxpr holds no [C, C] exponential of the scalar pairs."""
    args = recurrence_inputs(0.3, **WIDE)
    per_channel = str(jax.make_jaxpr(kernel_stage)(*args))
    scalar = str(jax.make_jaxpr(scalar_kernel_stage)(*scalar_inputs(0.3)))
    assert "reduce_max" in scalar and "reduce_max" not in per_channel


# ------------------------------------------ compiled for a described v5e


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("pass_", ["forward", "backward"])
def test_mosaic_takes_the_kernels_at_the_hybrid_cell_s_width(one_chip, pass_):
    """Both kernels lower for the v5e at (2, 512, 4, 128), chunks of 64
    in blocks of 16, every product float32 at `HIGHEST`: what interpret
    mode cannot show (tiling, VMEM, what Mosaic refuses to lower)."""
    shape = (2, 512, 4, 128)
    like = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    args = (like(*shape),) * 4 + (like(*shape[:3]),)

    def stage(*a):
        return kda_kernels.intra_stage(*a, 64, 16, False)

    def transposed(*a):
        out, vjp = jax.vjp(stage, *a)
        return vjp(out)

    traced = stage if pass_ == "forward" else transposed
    text = jax.jit(traced).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pass_", ["forward", "backward"])
def test_mosaic_takes_the_scalar_kernels_at_the_qwen3_next_cell_s_heads(
    one_chip, pass_
):
    """(1, 1024) tokens, 2 key heads under 4 value heads of 128 (the
    cell: 16 under 32 at 8192), one decay a head, four chunks a grid
    step."""
    like = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    key, value, rows = like(1, 1024, 2, 128), like(1, 1024, 4, 128), like(1, 1024, 4)
    args = (key, key, value, rows, rows)

    def stage(*a):
        return kda_kernels.scalar_intra_stage(*a, 64, 16, False)

    def transposed(*a):
        out, vjp = jax.vjp(stage, *a)
        return vjp(out)

    traced = stage if pass_ == "forward" else transposed
    text = jax.jit(traced).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
