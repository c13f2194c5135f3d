"""The three Pallas attention kernels, and the selective scan's two,
compiled for a TPU v5e that is described, not attached (docs/performance.md): what Mosaic refuses (a block that
breaks the tiling rule, more VMEM than a kernel may take) is refused
here, at the shapes the benchmark's cells run, at no chip time. Nothing
executes. The topology is described inside a fixture, in this file
alone: one process at a time may load the TPU's library."""

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.obs import hlo_scopes
from elasticdl_tpu.ops import flash_attention as fa


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache and cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("shape", [
    (2, 2048, 12, 64),  # lm-dense-160m: folded, 1024 x 1024 tiles
    (2, 2048, 16, 128),  # ouro-2.6b: read in place
    (1, 16384, 8, 64),  # the long sequence of test_cluster_gated.py
    (1, 384, 2, 64),  # a length only BLOCK divides
    (1, 2048, 4, 256),  # the widest head: the tiles' VMEM at its largest
    (1, 8192, 16, 256),  # qwen3-next-80b-a3b's gated attention layer
    (1, 8192, 48, 128),  # laguna-xs2's full layers
    (1, 8192, 64, 128, 512),  # its sliding layers: banded, 512 x 512 tiles
    (1, 2048, 4, 128, 300),  # a window no tile divides
    (1, 8192, 8, 128, 513),  # one key more than the tile
    # latent attention, values 128 wide in place beside keys of 192
    # folded, under a scale of the model's own:
    (4, 2048, 16, 192, None, 128),  # deepseek-v2-lite
    (2, 2048, 32, 192, None, 128),  # kimi-linear-48b-a3b
    (1, 2048, 4, 192, 512, 128),  # and under a band, which no cell runs
    # differential attention, both members of 20 pairs of heads: queries
    # and keys of 64 folded, the pairs' values of 128 in place
    (1, 4096, 40, 64, 512, 128),  # phi-4-mini-flash-reasoning's windowed layer
    (1, 4096, 40, 64, None, 128),  # its full and cross layers
    (1, 16384, 28, 128, 4096),  # smallthinker-21b-a3b's sliding layers
    (1, 16384, 28, 128),  # its full layer: the longest sequence a cell runs
    # fewer key-value heads, read where they lie (PR 63): the six
    # grouped cells' calls as the dispatcher hands them over
    (1, 8192, 64, 128, 512, None, 8),  # laguna-xs2's sliding layers
    (1, 8192, 48, 128, None, None, 8),  # its full layers: a group of 6
    (1, 16384, 28, 128, 4096, None, 4),  # smallthinker-21b-a3b: a group of 7
    (1, 8192, 16, 256, None, None, 2),  # qwen3-next-80b-a3b
    (4, 2048, 32, 64, None, None, 8),  # lfm2-24b-a2b: folded at 8 heads
    (1, 4096, 40, 64, 512, 128, 20),  # phi-4: k folded, the pairs' v in place
], ids=lambda s: "x".join(map(str, s[:4])) + "".join(
    f"-{n}{x}" for n, x in zip(("w", "v", "kv"), s[4:]) if x
))
def test_the_kernels_compile_for_the_v5e(one_chip, no_compile_cache, shape):
    shape, (window, v_width, kv_heads) = (
        shape[:4], (*shape[4:], None, None, None)[:3]
    )
    b, L, h, d = shape
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct(
        (b, L, kv_heads or h, d), jnp.bfloat16, sharding=one_chip
    )
    v, w = (
        jax.ShapeDtypeStruct(
            (b, L, heads, v_width or d), jnp.bfloat16, sharding=one_chip
        )
        for heads in (kv_heads or h, h)
    )
    tiles = fa.pick_tiles(shape[1], window)
    band = window and fa._Band(shape[1], *tiles, window)
    # the routed cell's where the widths differ, else 1/sqrt(D)
    scale = 0.11472 if v_width else shape[3] ** -0.5

    def loss(q, k, v, w):
        with jax.named_scope("attention"):
            o = fa._flash_attention(q, k, v, True, False, tiles, band, scale)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

    compiled = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, k, v, w).compile()
    )
    # forward, dq, dk+dv, each under the scope it was traced in
    assert hlo_scopes.kernels(compiled.as_text()) == {"attention": 3}
    # nothing quadratic in L is set aside: O(L*D) residuals and copies
    assert compiled.memory_analysis().temp_size_in_bytes < 20 * b * L * h * max(d, 128)


@pytest.mark.parametrize("remat, calls", [("_remat", 3), ("checkpoint", 4)])
def test_a_rematerialised_layer_keeps_the_forward_kernels_outputs(
    one_chip, no_compile_cache, remat, calls
):
    """Under `transformer_lm._remat` the backward pass recomputes the
    projections and holds the two backward kernels; under
    `jax.checkpoint` alone it holds the forward kernel a second time."""
    from elasticdl_tpu.models import transformer_lm as lm

    b, L, h, d = 2, 2048, 4, 128  # read in place, 1024 x 1024 tiles
    x = jax.ShapeDtypeStruct((b, L, h * d), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4, h * d, h * d), jnp.bfloat16, sharding=one_chip)
    tiles = fa.pick_tiles(L)

    def body(w, x):
        with jax.named_scope("attention"):
            q, k, v = ((x @ w[i]).reshape(b, L, h, d) for i in range(3))
            o = fa._flash_attention(q, k, v, True, False, tiles, None, d ** -0.5)
            return x + o.reshape(b, L, h * d) @ w[3]

    wrap = lm._remat if remat == "_remat" else jax.checkpoint

    def loss(w, x):
        return jnp.sum(wrap(body)(w, x).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, (0, 1))).lower(w, x).compile().as_text()
    assert hlo_scopes.kernels(text) == {"attention": calls}
    recomputed = [
        line for line in text.splitlines() if "rematted_computation" in line
    ]
    assert recomputed  # the projections, a second time
    again = [line for line in recomputed if hlo_scopes._KERNEL in line]
    assert len(again) == calls - 3


def test_the_selective_scan_s_kernels_compile_at_the_cell_s_shape(
    one_chip, no_compile_cache
):
    """Mamba-1's scan (`ops/selective_scan.py`) at (1, 4096, 5120) x 16,
    bfloat16 x, B and C as the timed program hands them: the forward
    kernel and the backward one, and nothing of [T, D, N] set aside."""
    from elasticdl_tpu.ops import selective_scan as ss

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, dt, A, Bm, Cm):
        with jax.named_scope("scan"):
            y, _last = ss.selective_scan_kernels(x, dt, A, Bm, Cm)
        return jnp.sum(y * y)

    b, t, d, n = 1, 4096, 5120, 16
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        spec((b, t, d), jnp.bfloat16), spec((b, t, d)), spec((n, d)),
        spec((b, t, n), jnp.bfloat16), spec((b, t, n), jnp.bfloat16),
    ).compile()
    assert hlo_scopes.kernels(compiled.as_text()) == {"scan": 2}
    # the chunk starts are 10 MB; one [T, D, N] tensor would be 1.3 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 400e6
