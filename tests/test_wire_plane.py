"""Zero-copy wire plane: codec v2 frames, bf16 error-feedback sync,
and wire-byte accounting.

Covers the v2 frame contract end to end: round-trips across dtypes and
tree shapes, v1<->v2 cross-decode (old payloads and checkpoints must
keep decoding), the no-copy-on-encode guarantee (measured, not
asserted by reading the code), the reduceat merge fast path against
its scatter oracle, the cached unravel plan, the bf16 payload-size
contract, error-feedback quantization math plus its end-to-end window
convergence, and the WireStats counters on both ends of a real RPC.
"""

import threading

import numpy as np
import pytest

from elasticdl_tpu.common import codec
from elasticdl_tpu.common.codec import (
    IndexedRows,
    _merge_indexed_rows_scatter,
    merge_indexed_rows,
)


def _bf16():
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


# -- v2 frame round-trips ----------------------------------------------------


@pytest.mark.parametrize(
    "arr",
    [
        np.arange(24, dtype=np.float32).reshape(2, 3, 4),
        np.asarray([[1.5, -2.25], [0.0, 3.0]]),  # float64
        np.arange(-4, 4, dtype=np.int64),
        np.asarray([[True, False], [False, True]]),
        np.asarray(np.float32(3.5)),  # 0-d scalar param
        np.empty((0, 7), dtype=np.float32),  # empty leaf
        np.arange(6, dtype=np.int32).reshape(3, 2).T,  # non-contiguous
    ],
    ids=["f32", "f64", "int64", "bool", "zero-d", "empty", "transposed"],
)
def test_v2_roundtrip_arrays(arr):
    out = codec.loads(codec.dumps({"a": arr}))["a"]
    assert out.dtype == arr.dtype
    assert out.shape == arr.shape
    np.testing.assert_array_equal(out, arr)


def test_v2_roundtrip_bfloat16():
    a = np.asarray([[1.5, -2.25], [0.0, 3.0]], dtype=_bf16())
    out = codec.loads(codec.dumps(a))
    assert out.dtype == _bf16()
    np.testing.assert_array_equal(
        a.astype(np.float32), out.astype(np.float32)
    )


def test_v2_roundtrip_nested_pytree():
    tree = {
        "layers": [
            {"w": np.random.randn(8, 4).astype(np.float32), "b": np.zeros(4)},
            {"w": np.random.randn(4, 2).astype(np.float32), "b": np.ones(2)},
        ],
        "meta": {"version": 7, "name": "m", "lr": 0.5, "flag": True},
        "tup": (np.arange(3), "s", None),
        "rows": IndexedRows(
            values=np.random.randn(3, 4).astype(np.float32),
            indices=[7, 1, 3],
        ),
    }
    out = codec.loads(codec.dumps(tree))
    np.testing.assert_array_equal(out["layers"][0]["w"], tree["layers"][0]["w"])
    np.testing.assert_array_equal(out["layers"][1]["b"], np.ones(2))
    assert out["meta"] == tree["meta"]
    assert isinstance(out["tup"], tuple)
    np.testing.assert_array_equal(out["tup"][0], np.arange(3))
    assert out["tup"][1:] == ("s", None)
    assert isinstance(out["rows"], IndexedRows)
    np.testing.assert_array_equal(out["rows"].indices, [7, 1, 3])
    np.testing.assert_array_equal(out["rows"].values, tree["rows"].values)


def test_v2_frame_magic_and_version():
    buf = codec.dumps({"a": np.ones(3, dtype=np.float32)})
    assert buf[0] == codec.FRAME_MAGIC
    assert buf[1] == codec.CODEC_VERSION
    # v1 payloads can never start with the reserved msgpack byte
    assert codec.dumps_v1({"x": 1})[0] != codec.FRAME_MAGIC


def test_v1_payloads_still_decode():
    """Mixed-version jobs and v1-era checkpoints: `loads` must accept
    both wire formats and produce identical trees."""
    tree = {
        "w": np.random.randn(5, 3).astype(np.float32),
        "i64": np.arange(4, dtype=np.int64),
        "rows": IndexedRows(values=np.ones((2, 3), np.float32), indices=[4, 9]),
        "meta": {"v": 3, "tag": "ckpt"},
        "tup": (1, 2.5),
    }
    v1 = codec.loads(codec.dumps_v1(tree))
    v2 = codec.loads(codec.dumps(tree))
    for out in (v1, v2):
        np.testing.assert_array_equal(out["w"], tree["w"])
        np.testing.assert_array_equal(out["i64"], tree["i64"])
        np.testing.assert_array_equal(out["rows"].values, tree["rows"].values)
        np.testing.assert_array_equal(out["rows"].indices, [4, 9])
        assert out["meta"] == tree["meta"]
        assert out["tup"] == (1, 2.5)


def test_v2_decode_is_views_into_the_frame():
    a = np.arange(64, dtype=np.float32)
    buf = codec.dumps({"a": a})
    out = codec.loads(buf)["a"]
    # zero-copy decode: the array is a read-only view over the frame
    assert out.base is not None
    assert not out.flags.writeable
    np.testing.assert_array_equal(out, a)


def test_v2_corrupt_descriptor_rejected():
    buf = bytearray(codec.dumps({"a": np.ones(4, dtype=np.float32)}))
    buf[1] = 99  # unknown frame version
    with pytest.raises(ValueError, match="version"):
        codec.loads(bytes(buf))


# -- no-copy-on-encode guarantee ---------------------------------------------


@pytest.mark.perf
def test_64mb_encode_makes_no_per_array_copy():
    """The v2 contract measured: encoding a 64 MB pytree of contiguous
    host arrays performs AT MOST one full-size host copy (the final
    frame join) — zero per-array copies. The counter tallies exactly
    the compaction copies the encoder takes; contiguous input must
    report none."""
    mb = 1024 * 1024
    tree = {
        "a": np.zeros(16 * mb // 4, dtype=np.float32),
        "b": {"c": np.zeros(32 * mb // 4, dtype=np.float32)},
        "d": [np.zeros(8 * mb // 4, dtype=np.float32),
              np.zeros(8 * mb // 8, dtype=np.int64)],
    }
    total = 64 * mb
    codec.reset_encode_copy_stats()
    buf = codec.dumps(tree)
    stats = codec.encode_copy_stats()
    assert stats["bytes"] == 0 and stats["arrays"] == 0, stats
    assert len(buf) > total  # all payload present (plus header/padding)


@pytest.mark.perf
def test_non_contiguous_arrays_are_counted():
    base = np.zeros((512, 512), dtype=np.float32)
    codec.reset_encode_copy_stats()
    codec.dumps({"t": base.T})  # transposed: needs compaction
    stats = codec.encode_copy_stats()
    assert stats["arrays"] == 1
    assert stats["bytes"] == base.nbytes


@pytest.mark.perf
@pytest.mark.parametrize("carrier", ["uds", "grpc"])
def test_the_socket_carrier_allocates_nothing_the_size_of_the_frame(
    carrier, monkeypatch, tmp_path
):
    """The join was the last full-size copy of a request and is the
    one-buffer carriers' alone now. Measured on the client, with the
    receiver in a process of its own (its frame buffer is its own
    affair): a 64 MB request over the Unix socket allocates less than
    a tenth of its length at the peak (over gRPC, the control: the
    whole frame and more) and compacts nothing."""
    import multiprocessing
    import tracemalloc

    from elasticdl_tpu.common.constants import ENV_TRANSPORT, ENV_UDS_DIR
    from elasticdl_tpu.rpc.client import RpcClient

    monkeypatch.setenv(ENV_TRANSPORT, carrier)
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    ctx = multiprocessing.get_context("spawn")
    port_q = ctx.Queue()
    server = ctx.Process(target=_serve_sum, args=(port_q,), daemon=True)
    server.start()
    try:
        client = RpcClient(f"localhost:{port_q.get(timeout=60)}")
        client.wait_ready(30)
        served_by = client._transport.name if client._transport else "grpc"
        assert served_by == carrier
        mb = 1 << 20
        tree = {
            "a": np.ones(16 * mb // 4, dtype=np.float32),
            "b": {"c": np.ones(40 * mb // 4 + 3, dtype=np.float32)},
            "d": [np.ones(8 * mb // 8 + 1, dtype=np.int64)],
        }
        leaves = (tree["a"], tree["b"]["c"], tree["d"][0])
        total = sum(a.nbytes for a in leaves)
        client.call("Sum", {"x": 1})  # connections and stubs are made
        codec.reset_encode_copy_stats()
        tracemalloc.start()
        try:
            resp = client.call("Sum", tree, timeout=120)
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        client.close()
    finally:
        server.terminate()
        server.join(10)
    assert resp["sum"] == sum(a.size for a in leaves)  # all ones
    assert codec.encode_copy_stats() == {"bytes": 0, "arrays": 0}
    if carrier == "uds":
        assert peak < total // 10, peak
    else:
        assert peak >= total, peak


def _serve_sum(port_q):
    import time

    from elasticdl_tpu.rpc.server import RpcServer

    def total(req):
        import jax

        return {"sum": int(sum(
            np.asarray(leaf).sum() for leaf in jax.tree_util.tree_leaves(req)
        ))}

    server = RpcServer({"Sum": total}, port=0)
    server.start()
    port_q.put(server.port)
    time.sleep(300)


# -- bf16 payload-size contract ----------------------------------------------


def test_bf16_sync_payload_at_most_55_percent_of_f32():
    """The acceptance bar for the lossy sync plane: a realistic window
    sync request with a bf16 delta must cost <= 55% of the f32 bytes
    (2x on the vector, plus the fixed header overhead)."""
    vec = np.random.randn(100_000).astype(np.float32)
    req = {
        "delta_flat": vec,
        "steps": 32,
        "base_version": 41,
        "aux_state": None,
        "worker_id": 0,
    }
    f32_bytes = len(codec.dumps(req))
    req_bf16 = dict(req, delta_flat=vec.astype(_bf16()))
    bf16_bytes = len(codec.dumps(req_bf16))
    assert bf16_bytes <= 0.55 * f32_bytes, (bf16_bytes, f32_bytes)


# -- merge_indexed_rows: reduceat fast path vs scatter oracle ----------------


def _random_slices(rng, n_slices, dim, id_space, integer_valued):
    slices = []
    for _ in range(n_slices):
        n = int(rng.integers(0, 12))
        vals = rng.standard_normal((n, dim)).astype(np.float32)
        if integer_valued:
            vals = np.round(vals * 4).astype(np.float32)
        slices.append(
            IndexedRows(
                values=vals, indices=rng.integers(0, id_space, size=n)
            )
        )
    return slices


@pytest.mark.parametrize("integer_valued", [True, False])
def test_merge_dedup_property_vs_scatter_oracle(integer_valued):
    """Property test over random shapes/duplication patterns: the
    sort+reduceat fast path must match the np.add.at scatter oracle —
    bit-exactly on integer-valued floats (no rounding involved),
    allclose on arbitrary floats (reduceat's pairwise summation order
    differs from the scatter's sequential order by ~1 ulp)."""
    rng = np.random.default_rng(1234 + integer_valued)
    for _ in range(40):
        slices = _random_slices(
            rng, int(rng.integers(1, 5)), int(rng.integers(1, 6)),
            id_space=int(rng.integers(1, 15)), integer_valued=integer_valued,
        )
        fast = merge_indexed_rows(slices, dedup=True)
        oracle = _merge_indexed_rows_scatter(slices, dedup=True)
        np.testing.assert_array_equal(fast.indices, oracle.indices)
        assert fast.values.shape == oracle.values.shape
        if integer_valued:
            np.testing.assert_array_equal(fast.values, oracle.values)
        else:
            np.testing.assert_allclose(
                fast.values, oracle.values, rtol=1e-6, atol=1e-6
            )


def test_merge_dedup_empty_and_no_dedup():
    empty = merge_indexed_rows(
        [IndexedRows(values=np.zeros((0, 3), np.float32), indices=[])],
        dedup=True,
    )
    assert empty.values.shape == (0, 3)
    assert empty.indices.size == 0
    a = IndexedRows(values=np.ones((2, 3)), indices=[0, 1])
    b = IndexedRows(values=2 * np.ones((1, 3)), indices=[0])
    m = merge_indexed_rows([a, b])  # no dedup: plain concat
    assert m.values.shape == (3, 3)
    np.testing.assert_array_equal(m.indices, [0, 1, 0])


# -- cached unravel plan -----------------------------------------------------


def test_make_unraveler_matches_unravel_np_and_validates():
    template = {
        "w": np.zeros((3, 4), dtype=np.float32),
        "b": np.zeros(4, dtype=np.float32),
        "nest": {"k": np.zeros((2,), dtype=np.float32)},
    }
    vec = np.arange(18, dtype=np.float32)
    u = codec.make_unraveler(template)
    one_shot = codec.unravel_np(vec, template)
    cached = u(vec)
    import jax

    for a, b in zip(
        jax.tree_util.tree_leaves(one_shot), jax.tree_util.tree_leaves(cached)
    ):
        np.testing.assert_array_equal(a, b)
    assert cached["w"].shape == (3, 4)
    with pytest.raises(ValueError, match="size"):
        u(np.zeros(17, dtype=np.float32))
    # bf16 wire vectors widen to f32 through the same plan
    wide = u(vec.astype(_bf16()))
    assert wide["w"].dtype == np.float32


# -- error-feedback quantization ---------------------------------------------


def _dummy_worker(**kwargs):
    from elasticdl_tpu.api.model_spec_helpers import spec_from_module
    from elasticdl_tpu.worker.worker import Worker

    from tests.fixtures import linear_module

    return Worker(
        0, None, spec_from_module(linear_module), minibatch_size=4, **kwargs
    )


def test_ef_residual_telescopes_the_quantization_error():
    """The EF invariant the sync plane rests on: after any number of
    quantized window deltas, sum(wire deltas) + residual == sum(true
    deltas) exactly (in f32 arithmetic) — the PS's accumulated state
    trails the true trajectory by at most the CURRENT residual (one
    bf16 quantum), it never drifts with the step count."""
    import jax.numpy as jnp

    w = _dummy_worker(sync_dtype="bf16")
    assert w._sync_dtype == "bfloat16"  # alias normalized
    rng = np.random.default_rng(7)
    true_sum = np.zeros(257, dtype=np.float32)
    wire_sum = np.zeros(257, dtype=np.float32)
    for _ in range(50):
        d = rng.standard_normal(257).astype(np.float32) * 1e-3
        true_sum += d
        meta, arrs = w._ef_quantize_delta(jnp.asarray(d))
        assert meta == ("dense",)
        q = arrs[0]
        assert q.dtype == jnp.bfloat16
        wire_sum += np.asarray(q).astype(np.float32)
    residual = np.asarray(w._ef_residual)
    np.testing.assert_allclose(wire_sum + residual, true_sum, atol=1e-6)


def test_ef_beats_plain_quantization_on_accumulated_drift():
    import jax.numpy as jnp

    w = _dummy_worker(sync_dtype="bf16")
    rng = np.random.default_rng(11)
    deltas = [
        rng.standard_normal(512).astype(np.float32) * 1e-3 for _ in range(200)
    ]
    true_sum = np.sum(deltas, axis=0)
    ef_sum = np.zeros(512, dtype=np.float32)
    plain_sum = np.zeros(512, dtype=np.float32)
    for d in deltas:
        ef_sum += np.asarray(
            w._ef_quantize_delta(jnp.asarray(d))[1][0]
        ).astype(np.float32)
        plain_sum += np.asarray(
            jnp.asarray(d).astype(jnp.bfloat16)
        ).astype(np.float32)
    ef_err = np.abs(ef_sum - true_sum).max()
    plain_err = np.abs(plain_sum - true_sum).max()
    assert ef_err < plain_err


def test_ef_grad_quantizer_is_thread_safe():
    """Pipelined reports quantize concurrently; the locked
    read-modify-write must preserve the telescoping identity under any
    interleaving."""
    import jax.numpy as jnp

    w = _dummy_worker(sync_dtype="bfloat16")
    rng = np.random.default_rng(3)
    grads = [rng.standard_normal(64).astype(np.float32) for _ in range(32)]
    out = [None] * len(grads)

    def quantize(i):
        out[i] = np.asarray(w._ef_quantize_grad(jnp.asarray(grads[i]))[1][0])

    threads = [
        threading.Thread(target=quantize, args=(i,))
        for i in range(len(grads))
    ]
    [t.start() for t in threads]
    [t.join(30) for t in threads]
    wire_sum = np.sum([o.astype(np.float32) for o in out], axis=0)
    true_sum = np.sum(grads, axis=0)
    residual = np.asarray(w._ef_grad_residual)
    np.testing.assert_allclose(wire_sum + residual, true_sum, atol=1e-5)


def test_int8_ef_residual_telescopes():
    """Same telescoping identity as bf16, on the int8 per-chunk path:
    sum(dequantized wire deltas) + residual == sum(true deltas)."""
    import jax.numpy as jnp

    from elasticdl_tpu.common import codec

    w = _dummy_worker(sync_dtype="int8")
    rng = np.random.default_rng(13)
    true_sum = np.zeros(300, dtype=np.float32)
    wire_sum = np.zeros(300, dtype=np.float32)
    for _ in range(30):
        d = rng.standard_normal(300).astype(np.float32) * 1e-3
        true_sum += d
        meta, arrs = w._ef_quantize_delta(jnp.asarray(d))
        assert meta == ("int8", codec.DEFAULT_INT8_CHUNK)
        delta = w._materialize_wire_delta(
            meta, [np.asarray(a) for a in arrs]
        )
        assert isinstance(delta, codec.QuantizedDelta)
        assert delta.q.dtype == np.int8
        wire_sum += delta.dequantize()
    residual = np.asarray(w._ef_residual)
    np.testing.assert_allclose(wire_sum + residual, true_sum, atol=1e-5)


def test_topk_ef_residual_telescopes():
    """Top-k sparsification with EF: the unsent coordinates ride the
    residual, so the cumulative wire stream still tracks the true
    trajectory exactly (Deep Gradient Compression's memory term)."""
    import jax.numpy as jnp

    from elasticdl_tpu.common import codec

    w = _dummy_worker(sync_compress="topk:0.1")
    assert w._lossy_sync()
    rng = np.random.default_rng(17)
    n = 500
    true_sum = np.zeros(n, dtype=np.float32)
    wire_sum = np.zeros(n, dtype=np.float32)
    for _ in range(40):
        d = rng.standard_normal(n).astype(np.float32) * 1e-3
        true_sum += d
        meta, arrs = w._ef_quantize_delta(jnp.asarray(d))
        assert meta[0] == "topk" and meta[1] == n
        delta = w._materialize_wire_delta(
            meta, [np.asarray(a) for a in arrs]
        )
        assert isinstance(delta, codec.SparseDelta)
        assert delta.indices.size == 50  # k = 0.1 * 500
        wire_sum += delta.dense()
    residual = np.asarray(w._ef_residual)
    np.testing.assert_allclose(wire_sum + residual, true_sum, atol=1e-5)


def test_topk_int8_composition_telescopes():
    """topk + int8 stacked: BOTH the dropped coordinates and the
    survivors' quantization error land in one residual."""
    import jax.numpy as jnp

    from elasticdl_tpu.common import codec

    w = _dummy_worker(sync_dtype="int8", sync_compress="topk:0.2")
    rng = np.random.default_rng(19)
    n = 400
    true_sum = np.zeros(n, dtype=np.float32)
    wire_sum = np.zeros(n, dtype=np.float32)
    for _ in range(30):
        d = rng.standard_normal(n).astype(np.float32) * 1e-3
        true_sum += d
        meta, arrs = w._ef_quantize_delta(jnp.asarray(d))
        assert meta[0] == "topk_int8" and meta[1] == n
        delta = w._materialize_wire_delta(
            meta, [np.asarray(a) for a in arrs]
        )
        assert isinstance(delta, codec.SparseDelta)
        assert isinstance(delta.values, codec.QuantizedDelta)
        wire_sum += delta.dense()
    residual = np.asarray(w._ef_residual)
    np.testing.assert_allclose(wire_sum + residual, true_sum, atol=1e-5)


def test_parse_sync_compress_validation():
    from elasticdl_tpu.worker.worker import _parse_sync_compress

    assert _parse_sync_compress(None) == 0.0
    assert _parse_sync_compress("") == 0.0
    assert _parse_sync_compress("none") == 0.0
    assert _parse_sync_compress("topk:0.05") == 0.05
    assert _parse_sync_compress("topk:1") == 1.0
    for bad in ("topk:0", "topk:1.5", "topk:", "gzip", "topk:-0.1"):
        with pytest.raises(ValueError, match="sync_compress"):
            _parse_sync_compress(bad)


def test_sync_compress_env_fallback(monkeypatch):
    from elasticdl_tpu.common.constants import ENV_SYNC_COMPRESS

    monkeypatch.setenv(ENV_SYNC_COMPRESS, "topk:0.25")
    w = _dummy_worker()
    assert w._topk_ratio == 0.25
    assert w._lossy_sync()


def test_topk_wire_bytes_cut_vs_f32():
    """The acceptance ratio at codec level: topk:0.05 + int8 shrinks a
    window-delta frame >= 4x vs the f32 frame at model scale."""
    from elasticdl_tpu.common import codec

    n = 1 << 16
    rng = np.random.default_rng(23)
    v = rng.standard_normal(n).astype(np.float32)
    k = round(0.05 * n)
    idx = np.sort(np.argsort(np.abs(v))[-k:]).astype(np.int32)
    sd = codec.SparseDelta(
        indices=idx, values=codec.quantize_int8(v[idx]), n=n
    )
    f32_bytes = len(codec.dumps({"delta_flat": v}))
    topk_bytes = len(codec.dumps({"delta_flat": sd}))
    assert topk_bytes * 4 <= f32_bytes, (f32_bytes, topk_bytes)


def test_sync_dtype_supersedes_transport_dtype():
    """EF needs full-precision input: the legacy device pre-cast is
    disabled when both lossy knobs are on, but model-down stays bf16."""
    w = _dummy_worker(sync_dtype="bf16", transport_dtype="bfloat16")
    assert w._transport_dtype == "float32"
    assert w._model_wire_dtype() == "bfloat16"
    w2 = _dummy_worker()
    assert w2._sync_dtype == "float32"
    assert w2._model_wire_dtype() is None


def test_sync_dtype_env_fallback_and_validation(monkeypatch):
    from elasticdl_tpu.common.constants import ENV_SYNC_DTYPE

    monkeypatch.setenv(ENV_SYNC_DTYPE, "bf16")
    assert _dummy_worker()._sync_dtype == "bfloat16"
    monkeypatch.delenv(ENV_SYNC_DTYPE)
    with pytest.raises(ValueError, match="sync_dtype"):
        _dummy_worker(sync_dtype="float16")


@pytest.mark.parametrize(
    "sync_dtype,sync_compress",
    [
        ("bf16", None),
        ("int8", None),
        (None, "topk:0.5"),
        ("int8", "topk:0.5"),
    ],
)
def test_reset_local_state_drops_residuals(sync_dtype, sync_compress):
    """A sync-chain break invalidates the EF residual for EVERY lossy
    mode — a stale residual re-applied against a restored model would
    inject error mass that was already (or never) shipped."""
    import jax.numpy as jnp

    w = _dummy_worker(sync_dtype=sync_dtype, sync_compress=sync_compress)
    assert w._lossy_sync()
    w._ef_quantize_delta(jnp.ones(8, dtype=jnp.float32) * 1e-3)
    assert w._ef_residual is not None
    if w._sync_dtype in ("bfloat16", "int8"):
        # the per-step grad path only quantizes for dtype modes
        # (top-k is a window-delta knob)
        w._ef_quantize_grad(jnp.ones(8, dtype=jnp.float32) * 1e-3)
        assert w._ef_grad_residual is not None
    w._reset_local_state()
    assert w._ef_residual is None and w._ef_grad_residual is None


# -- end-to-end: bf16 EF window sync converges like f32 ----------------------


def _run_window_job(tmp_path, tag, sync_dtype, sync_compress=None):
    import random

    from elasticdl_tpu.api.model_spec_helpers import spec_from_module
    from elasticdl_tpu.master.ps_optimizer import PSOptimizer
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.testing import InProcessMaster, write_linear_records
    from elasticdl_tpu.worker.worker import Worker

    from tests.fixtures import linear_module

    path = str(tmp_path / f"train-{tag}.rio")
    write_linear_records(path, 64, noise=0.05)
    random.seed(7)  # identical per-epoch task shuffle across runs
    dispatcher = TaskDispatcher({path: 64}, {}, {}, 16, 4)
    servicer = MasterServicer(
        grads_to_wait=1,
        optimizer=PSOptimizer(linear_module.optimizer()),
        task_dispatcher=dispatcher,
    )
    worker = Worker(
        0,
        InProcessMaster(servicer),
        spec_from_module(linear_module),
        minibatch_size=16,
        local_updates=4,
        sync_dtype=sync_dtype,
        sync_compress=sync_compress,
    )
    worker.run()
    assert dispatcher.finished()
    params, _aux, version = servicer.get_params_copy()
    return np.asarray(params["Dense_0"]["kernel"]), version


def test_bf16_ef_window_sync_converges_to_f32_trajectory(tmp_path):
    """The tentpole's correctness bar: a bf16 EF window job must land
    within tolerance of the f32 job, and the f32 default must stay
    bit-identical run to run (no hidden state from the lossy plane)."""
    k_f32, v_f32 = _run_window_job(tmp_path, "f32a", None)
    k_f32b, _ = _run_window_job(tmp_path, "f32b", None)
    np.testing.assert_array_equal(k_f32, k_f32b)  # default is bit-exact
    k_bf16, v_bf16 = _run_window_job(tmp_path, "bf16", "bfloat16")
    assert v_f32 == v_bf16
    # the linear fixture converges to kernel ~2.0; EF keeps the lossy
    # trajectory within a bf16-quantum-scale band of the exact one
    np.testing.assert_allclose(k_bf16, k_f32, rtol=2e-2, atol=2e-2)
    assert abs(float(k_bf16.ravel()[0]) - 2.0) < 0.3


def test_compressed_window_sync_converges_to_f32_trajectory(tmp_path):
    """Same bar for the PR 6 compressed modes: int8 window deltas and
    the stacked int8+topk pipeline run the identical job through the
    codec wire format (InProcessMaster packs/unpacks both directions,
    so QuantizedDelta/SparseDelta frames are decoded by the servicer
    exactly as they would be off the wire) and land near the f32 run."""
    k_f32, v_f32 = _run_window_job(tmp_path, "f32", None)
    k_int8, v_int8 = _run_window_job(tmp_path, "int8", "int8")
    assert v_f32 == v_int8
    np.testing.assert_allclose(k_int8, k_f32, rtol=2e-2, atol=2e-2)
    assert abs(float(k_int8.ravel()[0]) - 2.0) < 0.3
    # topk on the 2-param linear fixture: k=1 of 2 per window — the EF
    # residual carries the dropped coordinate to the next window, so
    # convergence survives even maximal sparsification (looser band:
    # each window ships half the coordinates)
    k_topk, v_topk = _run_window_job(tmp_path, "topk", "int8", "topk:0.5")
    assert v_f32 == v_topk
    assert abs(float(k_topk.ravel()[0]) - 2.0) < 0.4


# -- wire-byte accounting ----------------------------------------------------


def test_wire_stats_record_snapshot_reset():
    from elasticdl_tpu.rpc.policy import (
        WireStats,
        aggregate_wire_snapshots,
    )

    ws = WireStats("ep")
    ws.record("Push", sent=100)
    ws.record("Push", received=40)  # response half of the same call
    ws.record("Pull", sent=7, received=9)
    snap = ws.snapshot()
    assert snap["endpoint"] == "ep"
    assert snap["bytes_sent"] == 107 and snap["bytes_received"] == 49
    # calls count request sends, not response records
    assert snap["methods"]["Push"] == {
        "bytes_sent": 100, "bytes_received": 40, "calls": 1,
    }
    agg = aggregate_wire_snapshots([snap, snap])
    assert agg["bytes_sent"] == 214
    assert agg["methods"]["Pull"]["calls"] == 2
    ws.reset()
    assert ws.snapshot()["calls"] == 0


def test_wire_stats_counted_on_both_ends_of_a_real_rpc():
    from elasticdl_tpu.rpc.client import RpcClient
    from elasticdl_tpu.rpc.server import RpcServer

    payload = {"vec": np.random.randn(10_000).astype(np.float32)}

    def echo(req):
        return {"vec": req["vec"]}

    server = RpcServer({"Echo": echo}, port=0)
    server.start()
    try:
        client = RpcClient(f"localhost:{server.port}")
        client.wait_ready(10)
        client.wire.reset()
        client.call("Echo", payload)
        csnap = client.wire.snapshot()
        ssnap = server.wire_stats()
        client.close()
    finally:
        server.stop()
    row = csnap["methods"]["Echo"]
    assert row["calls"] == 1
    assert row["bytes_sent"] > 40_000  # 10k f32 + framing
    assert row["bytes_received"] > 40_000
    srow = ssnap["methods"]["Echo"]
    # what the client sent is what the server received, and vice versa
    assert srow["bytes_received"] == row["bytes_sent"]
    assert srow["bytes_sent"] == row["bytes_received"]


def test_ps_shard_stats_surface_wire_bytes():
    from elasticdl_tpu.master.ps_shard import PSShardServicer
    from elasticdl_tpu.rpc.policy import WireStats

    shard = PSShardServicer(shard_id=0, num_shards=1)
    wire = WireStats("shard0")
    wire.record("PSPushGrad", sent=0, received=128)
    wire.record("PSPushGrad", sent=64)
    shard.attach_wire_stats(wire)
    stats = shard.stats()
    assert stats["bytes_received"] == 128
    assert stats["bytes_sent"] == 64
