"""Codec round-trips (mirrors reference tests/ndarray_test.py)."""

import numpy as np
import pytest

from elasticdl_tpu.common import codec
from elasticdl_tpu.common.codec import IndexedRows, merge_indexed_rows


def test_roundtrip_dense():
    a = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    out = codec.loads(codec.dumps(a))
    np.testing.assert_array_equal(a, out)
    assert out.dtype == np.float32


@pytest.mark.parametrize("dtype", ["float32", "float64", "int32", "int64", "uint8", "bool"])
def test_roundtrip_dtypes(dtype):
    a = np.ones((3, 5), dtype=dtype)
    out = codec.loads(codec.dumps(a))
    assert out.dtype == a.dtype
    np.testing.assert_array_equal(a, out)


def test_roundtrip_bfloat16():
    import ml_dtypes

    a = np.asarray([[1.5, -2.25], [0.0, 3.0]], dtype=ml_dtypes.bfloat16)
    out = codec.loads(codec.dumps(a))
    assert out.dtype == np.dtype(ml_dtypes.bfloat16)
    np.testing.assert_array_equal(a.astype(np.float32), out.astype(np.float32))


def test_roundtrip_pytree():
    tree = {
        "dense": {"w": np.ones((2, 2), dtype=np.float32), "b": np.zeros(2)},
        "meta": {"version": 7, "name": "m"},
        "list": [np.arange(3), "s", 1.5],
    }
    out = codec.loads(codec.dumps(tree))
    np.testing.assert_array_equal(out["dense"]["w"], tree["dense"]["w"])
    assert out["meta"] == {"version": 7, "name": "m"}
    np.testing.assert_array_equal(out["list"][0], np.arange(3))


def test_roundtrip_indexed_rows():
    ir = IndexedRows(values=np.ones((3, 4), dtype=np.float32), indices=[7, 1, 3])
    out = codec.loads(codec.dumps({"g": ir}))["g"]
    assert isinstance(out, IndexedRows)
    np.testing.assert_array_equal(out.indices, [7, 1, 3])
    np.testing.assert_array_equal(out.values, ir.values)


def test_merge_indexed_rows():
    a = IndexedRows(values=np.ones((2, 3)), indices=[0, 1])
    b = IndexedRows(values=2 * np.ones((1, 3)), indices=[5])
    m = merge_indexed_rows([a, b])
    np.testing.assert_array_equal(m.indices, [0, 1, 5])
    assert m.values.shape == (3, 3)


def test_jax_array_encodes():
    import jax.numpy as jnp

    a = jnp.ones((2, 2))
    out = codec.loads(codec.dumps({"a": a}))["a"]
    np.testing.assert_array_equal(out, np.ones((2, 2)))


def test_zero_dim_arrays_round_trip():
    """Regression: np.ascontiguousarray promotes 0-d to 1-d; scalar
    params (e.g. a model's global bias) must keep shape ()."""
    import numpy as np

    from elasticdl_tpu.common import codec

    out = codec.loads(codec.dumps({"bias": np.asarray(np.float32(3.5))}))
    assert out["bias"].shape == ()
    assert float(out["bias"]) == 3.5


# -- compressed wire deltas (QuantizedDelta / SparseDelta) --------------------


def _qd(n=5003, seed=0, chunk=None):
    rng = np.random.default_rng(seed)
    v = rng.standard_normal(n).astype(np.float32)
    kw = {} if chunk is None else {"chunk": chunk}
    return v, codec.quantize_int8(v, **kw)


def test_quantize_int8_error_bound():
    """Per-chunk scaled int8: the reconstruction error is bounded by
    half a quantization step of the CHUNK's own scale — the bound the
    EF residual telescopes away."""
    v, qd = _qd()
    deq = qd.dequantize()
    assert deq.shape == v.shape and deq.dtype == np.float32
    for c in range(qd.scale.size):
        lo, hi = c * qd.chunk, min(v.size, (c + 1) * qd.chunk)
        err = np.abs(deq[lo:hi] - v[lo:hi]).max()
        assert err <= qd.scale[c] / 2 + 1e-7


def test_quantize_int8_zero_chunk_scale():
    """An all-zero chunk must not divide by zero (scale falls back to
    1.0) and must reconstruct as exact zeros."""
    v = np.zeros(4096, dtype=np.float32)
    v[2048:] = 1.0
    qd = codec.quantize_int8(v, chunk=2048)
    assert qd.scale[0] == 1.0
    np.testing.assert_array_equal(qd.dequantize()[:2048], 0.0)


@pytest.mark.parametrize(
    "s,e", [(0, 5003), (0, 1), (17, 2049), (2048, 4096), (4999, 5003), (7, 7)]
)
def test_quantized_delta_slice_matches_dense_oracle(s, e):
    """slice-then-dequantize == dequantize-then-slice, bit exact — the
    invariant that lets ShardedPS split a compressed delta per shard
    without decompressing (chunk boundaries never align with shard
    boundaries, hence the offset bookkeeping)."""
    _, qd = _qd()
    np.testing.assert_array_equal(
        qd.slice(s, e).dequantize(), qd.dequantize()[s:e]
    )


def test_quantized_delta_nested_slice():
    """A slice of a slice keeps absolute chunk coordinates straight."""
    _, qd = _qd()
    inner = qd.slice(100, 4000).slice(50, 1900)
    np.testing.assert_array_equal(
        inner.dequantize(), qd.dequantize()[150:2000]
    )


def test_sparse_delta_dense_and_slice_oracle():
    rng = np.random.default_rng(5)
    n = 4001
    idx = np.sort(rng.choice(n, 200, replace=False)).astype(np.int64)
    vals = rng.standard_normal(200).astype(np.float32)
    sd = codec.SparseDelta(indices=idx, values=vals, n=n)
    dense = sd.dense()
    assert dense.size == n
    np.testing.assert_array_equal(dense[idx], vals)
    for s, e in [(0, n), (10, 3500), (2000, 2001), (5, 5)]:
        np.testing.assert_array_equal(sd.slice(s, e).dense(), dense[s:e])


def test_sparse_delta_with_quantized_values_slices():
    """topk+int8 composition: SparseDelta carrying a QuantizedDelta
    payload slices without decompressing either layer."""
    rng = np.random.default_rng(6)
    n = 10007
    idx = np.sort(rng.choice(n, 500, replace=False)).astype(np.int32)
    sd = codec.SparseDelta(
        indices=idx,
        values=codec.quantize_int8(
            rng.standard_normal(500).astype(np.float32), chunk=128
        ),
        n=n,
    )
    dense = sd.dense()
    for s, e in [(0, n), (100, 9000), (5000, 5001)]:
        np.testing.assert_array_equal(sd.slice(s, e).dense(), dense[s:e])


def test_sparse_delta_rejects_float_indices():
    with pytest.raises((TypeError, ValueError)):
        codec.SparseDelta(
            indices=np.array([0.5, 1.5]), values=np.ones(2, np.float32), n=4
        )


@pytest.mark.parametrize("dumps", [codec.dumps, codec.dumps_v1])
def test_compressed_delta_wire_roundtrip(dumps):
    """Both codec versions carry QD/SD (including the nested topk+int8
    form) — mixed-version jobs can drain mid-upgrade."""
    v, qd = _qd(n=4097, seed=1)
    rng = np.random.default_rng(2)
    idx = np.sort(rng.choice(v.size, 100, replace=False)).astype(np.int64)
    sd = codec.SparseDelta(indices=idx, values=v[idx], n=v.size)
    sd_q = codec.SparseDelta(
        indices=idx, values=codec.quantize_int8(v[idx], chunk=64), n=v.size
    )
    m = codec.loads(dumps({"qd": qd, "sd": sd, "sd_q": sd_q, "l": [qd]}))
    np.testing.assert_array_equal(m["qd"].dequantize(), qd.dequantize())
    np.testing.assert_array_equal(m["sd"].dense(), sd.dense())
    np.testing.assert_array_equal(m["sd_q"].dense(), sd_q.dense())
    assert isinstance(m["l"][0], codec.QuantizedDelta)


def test_delta_helpers_dispatch():
    v, qd = _qd(n=1025, seed=3)
    assert codec.delta_length(qd) == 1025
    assert codec.delta_length(v) == 1025
    np.testing.assert_array_equal(codec.delta_to_f32(qd), qd.dequantize())
    np.testing.assert_array_equal(codec.delta_to_f32(v), v)
    np.testing.assert_array_equal(
        codec.slice_delta(v, 3, 9), v[3:9]
    )
    np.testing.assert_array_equal(
        codec.slice_delta(qd, 3, 9).dequantize(), qd.dequantize()[3:9]
    )
    with pytest.raises(ValueError):
        codec.delta_to_f32(qd, n=9)


def test_int8_wire_bytes_are_quarter_of_f32():
    """The point of the exercise: the dense int8 frame is ~4x smaller
    than the f32 frame (int8 payload + f32 scale per 2048-chunk)."""
    v, qd = _qd(n=1 << 16, seed=4)
    f32_bytes = len(codec.dumps({"d": v}))
    int8_bytes = len(codec.dumps({"d": qd}))
    assert int8_bytes < f32_bytes / 3.5, (f32_bytes, int8_bytes)


# -- a LeafVector piece that may still be on its way (PR 45) ------------------


class _Lander:
    """Pieces of a float32 vector that land when told to, each waited
    for through `codec.PendingPiece`; counts the waits."""

    def __init__(self, vec, cuts):
        import threading

        self.vec = vec
        self.bounds = list(zip([0] + cuts, cuts + [vec.size]))
        self.events = [threading.Event() for _ in self.bounds]
        self.waits = [0] * len(self.bounds)

    def _wait(self, i, timeout):
        self.waits[i] += 1
        if not self.events[i].wait(timeout):
            raise TimeoutError(f"piece {i}")
        lo, hi = self.bounds[i]
        return self.vec[lo:hi]

    def vector(self, plain=()):
        """The vector as pending pieces; those in `plain` as arrays."""
        return codec.LeafVector([
            self.vec[lo:hi] if i in plain
            else codec.PendingPiece(hi - lo, lambda t, i=i: self._wait(i, t))
            for i, (lo, hi) in enumerate(self.bounds)
        ])

    def land(self, *which):
        for i in which or range(len(self.events)):
            self.events[i].set()


def test_a_frame_of_pending_pieces_is_the_plain_vector_s_frame():
    """The header, the part list's layout and the length are made from
    the pieces' sizes alone, before a byte of them has landed; once
    they have, the parts' bytes are `dumps` of the vector itself."""
    vec = np.random.default_rng(45).standard_normal(10007).astype(np.float32)
    lander = _Lander(vec, [4000, 4001, 9000])
    request = {"delta_flat": None, "steps": 16, "k": np.arange(5)}
    want = codec.dumps({**request, "delta_flat": vec})
    parts, total = codec.dumps_parts(
        {**request, "delta_flat": lander.vector(plain={1})}
    )
    assert total == len(want) and lander.waits == [0, 0, 0, 0]
    pending = [p for p in parts if isinstance(p, codec.PendingPiece)]
    assert [p.size for p in pending] == [4000, 4999, 1007]
    assert all(p.peek() is None for p in pending)
    # everything before the first pending piece is the plain frame's start
    head = b"".join(
        bytes(p) for p in parts[:parts.index(pending[0])]
    )
    assert want.startswith(head) and len(head) >= 64
    with pytest.raises(TimeoutError):
        codec.part_bytes(pending[0], 0.01)
    lander.land()
    assert b"".join(bytes(codec.part_bytes(p)) for p in parts) == want
    got = codec.loads(want)["delta_flat"]
    assert got.dtype == np.float32 and np.array_equal(got, vec)
    # what landed is kept: a second read waits for nothing
    assert [p.peek() is not None for p in pending] == [True] * 3
    assert b"".join(bytes(codec.part_bytes(p)) for p in parts) == want
    assert lander.waits == [2, 0, 1, 1]  # piece 0: the timeout, then once


def test_dumps_and_asarray_wait_for_the_pieces():
    import threading

    vec = np.arange(999, dtype=np.float32)
    lander = _Lander(vec, [10, 500])
    threading.Timer(0.05, lander.land).start()
    assert codec.dumps({"v": lander.vector()}) == codec.dumps({"v": vec})
    assert np.array_equal(np.asarray(lander.vector()), vec)
    assert np.asarray(lander.vector(), dtype=np.float64).dtype == np.float64


def test_a_delta_s_size_is_counted_without_waiting_or_joining():
    lander = _Lander(np.zeros(300, np.float32), [100])
    vector = lander.vector()
    assert codec.delta_length(vector) == 300
    assert lander.waits == [0, 0]


@pytest.mark.parametrize("landed,error", [
    (np.zeros(7, np.float64), TypeError),
    (np.zeros((7, 1), np.float32), TypeError),
    (np.zeros(14, np.float32)[::2], TypeError),
    (np.zeros(6, np.float32), ValueError),
])
def test_a_piece_that_lands_as_something_else_is_refused(landed, error):
    piece = codec.PendingPiece(7, lambda timeout: landed)
    with pytest.raises(error):
        piece.landed()
    assert piece.peek() is None


def test_a_piece_s_own_failure_comes_out_as_it_was_raised():
    def wait(timeout):
        raise RuntimeError("the copy failed")

    vector = codec.LeafVector([np.zeros(3, np.float32),
                               codec.PendingPiece(4, wait)])
    assert vector.size == 7
    with pytest.raises(RuntimeError, match="the copy failed"):
        codec.dumps({"v": vector})
