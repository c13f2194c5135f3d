"""Tensor codec: numpy/JAX pytrees <-> wire bytes.

TPU-native redesign of the reference's float32-only tensor codec
(reference: elasticdl/python/common/ndarray.py:7-55 and the `Tensor`
proto message at elasticdl/proto/elasticdl.proto:43-55):

- dtype-aware: bfloat16 is the native TPU transport dtype for gradients;
  float32/int32/int64/bool etc. all round-trip.
- zero-copy decode: `np.frombuffer` views over the received buffer.
- sparse tensors: `IndexedRows` (values + int64 row indices) mirrors
  `tf.IndexedSlices` — the wire form of embedding gradients.
- arbitrary pytrees: nested dict/list/tuple structures of arrays are
  encoded with msgpack; this replaces the reference's flat
  `map<string, Tensor>` Model message (elasticdl.proto:57-60) because
  JAX parameters are naturally nested pytrees.

Wire format (codec v2, the default `dumps`): a framed layout that is
also zero-copy on ENCODE. The old v1 encoder ran every array through
`ndarray.tobytes()` (one full copy per array) and then msgpack copied
the resulting bin into its output buffer (a second full copy). v2
instead packs a small msgpack header holding dtype/shape/offset
descriptors and appends the raw array bytes out-of-band as buffer
views of the contiguous source arrays (`dumps_parts`). The only
full-size copy left is the `b"".join` of `dumps`, and it is the
one-buffer carriers' alone: gRPC, `inproc` and files need a single
buffer; the Unix-socket carrier writes a frame's parts to the socket
as they lie, request and response alike (`messages.PackedParts`,
`rpc/transport.py`; see docs/architecture.md, "Wire plane"). A model
on its way down is not even raveled first: a `LeafVector` enters the
frame as the vector its leaves would concatenate to, from where they
lie.

    offset  size  field
    0       1     0xC1 frame magic (a reserved, never-emitted msgpack
                  type byte — a v1 payload can never start with it, so
                  `loads` auto-detects both formats)
    1       1     codec version (0x02)
    2       4     u32 LE header length H
    6       2     u16 LE header pad P (zeros aligning the payload)
    8       H     msgpack header: the pytree with every array replaced
                  by a descriptor {"d": dtype, "s": shape, "o": payload
                  offset, "n": byte length}
    8+H     P     zero padding so the payload starts 64-byte aligned
    8+H+P   ...   payload: raw array bytes, each segment 64-byte
                  aligned relative to (and including) the frame start

Decode builds `np.frombuffer` views into the one received frame — the
arrays share the frame's lifetime, exactly as v1 arrays shared their
msgpack bin's. v1 payloads (and v1-era checkpoints) still decode:
`loads` dispatches on the magic byte. `dumps_v1` keeps the old encoder
reachable for cross-version tests and emergency interop.
"""

from __future__ import annotations

import dataclasses
import struct
import threading
from typing import Any

import ml_dtypes  # the bf16 numpy dtype; ships with JAX
import msgpack
import numpy as np

_BFLOAT16 = np.dtype(ml_dtypes.bfloat16)

_ND_KEY = "__nd__"
_IR_KEY = "__ir__"
_TUPLE_KEY = "__tp__"
_QD_KEY = "__qd__"
_SD_KEY = "__sd__"

#: v2 frame constants. 0xC1 is the one byte the msgpack spec reserves
#: and never emits, so it unambiguously marks a framed payload.
FRAME_MAGIC = 0xC1
CODEC_VERSION = 2
#: fixed prefix: magic, version, u32 header length, u16 header pad
_FRAME_PREFIX = struct.Struct("<BBIH")
#: payload segments start at multiples of this (relative to the frame
#: start — the header is padded so the payload base is aligned too)
_SEGMENT_ALIGN = 64

#: The full key set a v2 array descriptor may carry. The edl-lint
#: rpc-conformance rule cross-checks the encoder's emitted dict keys
#: and the decoder's reads against this declaration (frame-descriptor
#: checks in analysis/rpc_conformance.py) the same way WIRE_SCHEMAS
#: pins request dicts: d = dtype string, s = shape list, o = byte
#: offset into the payload, n = segment byte length (validation only —
#: count is derived from s and d).
FRAME_DESCRIPTOR_FIELDS = ("d", "s", "o", "n")


class _EncodeCopyCounter(threading.local):
    """Per-thread tally of host bytes COPIED while encoding (the
    contiguity fallback). The zero-copy guarantee is tested against
    this: encoding a pytree of contiguous host arrays must report 0
    (the frame join is the single allowed full-size copy, taken only
    where a carrier needs one wire buffer). Device->host transfers for
    jax arrays are not counted — they are transfers, not wire-plane
    copies."""

    def __init__(self):
        self.bytes = 0
        self.arrays = 0


_encode_copies = _EncodeCopyCounter()


def reset_encode_copy_stats() -> None:
    _encode_copies.bytes = 0
    _encode_copies.arrays = 0


def encode_copy_stats() -> dict:
    """{"bytes": copied_bytes, "arrays": arrays_copied} since the last
    reset on this thread."""
    return {"bytes": _encode_copies.bytes, "arrays": _encode_copies.arrays}


@dataclasses.dataclass
class IndexedRows:
    """A sparse (row-indexed) tensor: `values[k]` is the row for id `indices[k]`.

    Equivalent of tf.IndexedSlices on the wire (reference:
    elasticdl/proto/elasticdl.proto:43-55); produced by embedding-layer
    backward passes and consumed by the PS sparse-apply path.
    """

    values: np.ndarray  # [n, dim]
    indices: np.ndarray  # [n] int64

    def __post_init__(self):
        self.values = np.asarray(self.values)
        self.indices = np.asarray(self.indices, dtype=np.int64)


def _merge_indexed_rows_scatter(
    slices: list[IndexedRows], dedup: bool = False
) -> IndexedRows:
    """Reference implementation of `merge_indexed_rows` using the
    `np.add.at` scatter. Kept (unused in production) as the oracle for
    the property test of the reduceat fast path — scatter is an
    order-of-magnitude slower but its semantics are the spec."""
    out = IndexedRows(
        values=np.concatenate([s.values for s in slices], axis=0),
        indices=np.concatenate([s.indices for s in slices], axis=0),
    )
    if not dedup:
        return out
    uniq, inverse = np.unique(out.indices, return_inverse=True)
    summed = np.zeros((len(uniq),) + out.values.shape[1:], dtype=np.float32)
    np.add.at(summed, inverse, np.asarray(out.values, dtype=np.float32))
    return IndexedRows(values=summed, indices=uniq)


def merge_indexed_rows(
    slices: list[IndexedRows], dedup: bool = False
) -> IndexedRows:
    """Concatenate several IndexedRows (reference:
    elasticdl/python/common/tensor_helper.py:4-8). With dedup=True,
    duplicate-id rows are summed (same math the PS sparse-apply runs
    first thing) — senders use it to shrink multi-step accumulations
    before they hit the wire.

    The dedup sum is a stable-sort + `np.add.reduceat` group reduction
    rather than an `np.add.at` scatter: reduceat is vectorized where
    add.at is an element-at-a-time ufunc inner loop. The stable sort
    preserves each id's within-group operand order, so results match
    the scatter path up to reduceat's pairwise-summation rounding
    (exact for integer-valued floats; see tests/test_codec.py
    property test against `_merge_indexed_rows_scatter`)."""
    out = IndexedRows(
        values=np.concatenate([s.values for s in slices], axis=0),
        indices=np.concatenate([s.indices for s in slices], axis=0),
    )
    if not dedup:
        return out
    uniq, inverse = np.unique(out.indices, return_inverse=True)
    vals = np.asarray(out.values, dtype=np.float32)
    if len(uniq) == 0:
        return IndexedRows(
            values=np.zeros((0,) + vals.shape[1:], dtype=np.float32),
            indices=uniq,
        )
    order = np.argsort(inverse, kind="stable")
    starts = np.searchsorted(inverse[order], np.arange(len(uniq)))
    summed = np.add.reduceat(vals[order], starts, axis=0)
    return IndexedRows(values=summed, indices=uniq)


def _dtype_to_str(dt: np.dtype) -> str:
    if dt == _BFLOAT16:
        return "bfloat16"
    return dt.str


def dtype_from_str(s: str) -> np.dtype:
    if s == "bfloat16":
        return _BFLOAT16
    return np.dtype(s)


def as_f32(a: Any) -> np.ndarray:
    """Float32 VIEW of `a` when it already is f32 (the decoded wire
    view passes through untouched, read-only and all); a widening cast
    only when the dtype differs (bf16 wire payloads land here).
    `np.asarray(x, dtype=np.float32)` is a no-op for f32 inputs too,
    but spelling the intent out keeps the no-copy contract visible and
    lintable at the PS apply sites (ps_shard.push_grad/push_delta)."""
    a = np.asarray(a)
    if a.dtype == np.float32:
        return a
    return a.astype(np.float32)


# --------------------------------------------------------------------------
# Compressed delta wire forms: int8 per-chunk scaled quantization and
# top-k sparsification. Both are BIASED compressors; senders fold the
# compression error into an f32 error-feedback residual (worker-side,
# same telescoping-bound machinery as the bf16 transport) so the
# receiver can apply the decoded f32 delta exactly as if it were dense.

#: Elements per int8 scale chunk. 2048 f32 elements quantize to 2048
#: int8 bytes + one f32 scale — a fixed 0.05% scale overhead while
#: keeping the max-magnitude scale local enough that one outlier only
#: coarsens its own chunk.
DEFAULT_INT8_CHUNK = 2048


@dataclasses.dataclass
class QuantizedDelta:
    """An int8 per-chunk-scaled quantization of a dense f32 vector.

    Chunk c (elements [c*chunk, (c+1)*chunk) in ABSOLUTE coordinates)
    was quantized as q = clip(round(v / scale[c]), -127, 127) with
    scale[c] = max|v| / 127 over the chunk (0-chunks get scale 1.0 so
    dequantize is exact zeros). `offset` is the absolute position of
    q[0] in the source vector; keeping chunk boundaries absolute makes
    per-shard slicing exact without chunk alignment: a slice reuses the
    parent's scales for the chunks it overlaps.
    """

    q: np.ndarray  # [n] int8
    scale: np.ndarray  # [nchunks] f32, chunks offset//chunk ..
    chunk: int
    offset: int = 0

    def __post_init__(self):
        self.q = np.asarray(self.q)
        self.scale = np.asarray(self.scale)
        self.chunk = int(self.chunk)
        self.offset = int(self.offset)

    @property
    def n(self) -> int:
        return int(self.q.size)

    def slice(self, start: int, stop: int) -> "QuantizedDelta":
        """Sub-delta for local elements [start, stop) — the PS-shard
        split. Scales slice to the overlapped absolute chunks."""
        start, stop = int(start), int(stop)
        abs_start = self.offset + start
        first_chunk = self.offset // self.chunk
        if stop <= start:
            return QuantizedDelta(
                q=self.q[:0], scale=self.scale[:0], chunk=self.chunk, offset=abs_start
            )
        lo = abs_start // self.chunk - first_chunk
        hi = (self.offset + stop - 1) // self.chunk - first_chunk + 1
        return QuantizedDelta(
            q=self.q[start:stop],
            scale=self.scale[lo:hi],
            chunk=self.chunk,
            offset=abs_start,
        )

    def dequantize(self) -> np.ndarray:
        """Dense f32 reconstruction (q * scale-of-its-chunk)."""
        if self.q.size == 0:
            return np.zeros(0, dtype=np.float32)
        first_chunk = self.offset // self.chunk
        idx = (self.offset + np.arange(self.q.size)) // self.chunk - first_chunk
        return self.q.astype(np.float32) * np.asarray(
            self.scale, dtype=np.float32
        )[idx]


@dataclasses.dataclass
class SparseDelta:
    """A top-k sparsified dense vector: `values[j]` is the entry at
    position `indices[j]` of a length-`n` vector whose other entries
    are zero. Indices are LOCAL to this delta, sorted ascending and
    unique, so a PS-shard slice is one searchsorted range. `values` is
    either a dense array (f32/bf16) or a nested QuantizedDelta over the
    packed values — the topk+int8 composition."""

    indices: np.ndarray  # [k] int, sorted ascending, in [0, n)
    values: Any  # [k] ndarray or QuantizedDelta over the packed values
    n: int

    def __post_init__(self):
        self.indices = np.asarray(self.indices)
        if not np.issubdtype(self.indices.dtype, np.integer):
            raise TypeError(f"SparseDelta indices must be integer, got {self.indices.dtype}")
        if not isinstance(self.values, QuantizedDelta):
            self.values = np.asarray(self.values)
        self.n = int(self.n)

    @property
    def k(self) -> int:
        return int(self.indices.size)

    def slice(self, start: int, stop: int) -> "SparseDelta":
        """Sub-delta covering local elements [start, stop), indices
        rebased to the sub-range."""
        start, stop = int(start), int(stop)
        lo = int(np.searchsorted(self.indices, start, side="left"))
        hi = int(np.searchsorted(self.indices, stop, side="left"))
        values = (
            self.values.slice(lo, hi)
            if isinstance(self.values, QuantizedDelta)
            else self.values[lo:hi]
        )
        return SparseDelta(
            indices=self.indices[lo:hi] - start,
            values=values,
            n=max(0, stop - start),
        )

    def dense(self) -> np.ndarray:
        """Dense f32 reconstruction (zeros with values scattered in)."""
        out = np.zeros(self.n, dtype=np.float32)
        vals = (
            self.values.dequantize()
            if isinstance(self.values, QuantizedDelta)
            else as_f32(self.values)
        )
        out[self.indices] = vals
        return out


def quantize_int8(vec, chunk: int = DEFAULT_INT8_CHUNK) -> QuantizedDelta:
    """Host-side int8 per-chunk quantization of a dense f32 vector
    (offset 0). The worker hot path quantizes ON DEVICE with the same
    math (worker._ef_compress_delta); this is the host mirror used by
    the PS restore/test paths and as the spec the device math is tested
    against."""
    vec = np.asarray(vec, dtype=np.float32).ravel()
    n = vec.size
    chunk = int(chunk)
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    nchunks = -(-n // chunk) if n else 0
    pad = nchunks * chunk - n
    padded = np.pad(vec, (0, pad)) if pad else vec
    blocks = padded.reshape(max(nchunks, 0), chunk) if nchunks else padded.reshape(0, chunk)
    scale = np.abs(blocks).max(axis=1) / 127.0 if nchunks else np.zeros(0, dtype=np.float32)
    scale = np.where(scale > 0, scale, 1.0).astype(np.float32)
    q = np.clip(np.rint(blocks / scale[:, None]), -127, 127).astype(np.int8)
    return QuantizedDelta(q=q.reshape(-1)[:n], scale=scale, chunk=chunk)


def delta_length(obj: Any) -> int:
    """Dense length of a wire delta regardless of its compression."""
    if isinstance(obj, QuantizedDelta):
        return obj.n
    if isinstance(obj, SparseDelta):
        return obj.n
    if isinstance(obj, LeafVector):
        return obj.size
    return int(np.asarray(obj).size)


def slice_delta(obj: Any, start: int, stop: int) -> Any:
    """Elements [start, stop) of a wire delta, preserving its
    compression — the PS-shard fan-out split (ps_client.push_delta)."""
    if isinstance(obj, (QuantizedDelta, SparseDelta)):
        return obj.slice(start, stop)
    return np.asarray(obj)[start:stop]


def delta_to_f32(obj: Any, n: int | None = None) -> np.ndarray:
    """Decode any wire delta form to a dense f32 vector: dense arrays
    pass through `as_f32` (f32 stays a view), QuantizedDelta
    dequantizes, SparseDelta densifies. The single decode point for the
    PS/master apply sites — compression never leaks past it."""
    if isinstance(obj, QuantizedDelta):
        out = obj.dequantize()
    elif isinstance(obj, SparseDelta):
        out = obj.dense()
    else:
        out = as_f32(obj)
    if n is not None and out.size != n:
        raise ValueError(f"delta length {out.size} != expected {n}")
    return out


# --------------------------------------------------------------------------
# v1 payload form: arrays embedded as msgpack bins ({"d","s","b"})


def _encode_array(a: np.ndarray) -> dict:
    a = np.asarray(a)
    shape = list(a.shape)  # before ascontiguousarray: it promotes 0-d to 1-d
    a = np.ascontiguousarray(a)
    return {
        "d": _dtype_to_str(a.dtype),
        "s": shape,
        "b": a.tobytes(),
    }


def _decode_array(m: dict) -> np.ndarray:
    dt = dtype_from_str(m["d"])
    arr = np.frombuffer(m["b"], dtype=dt)
    return arr.reshape(m["s"])


def _default(obj: Any) -> Any:
    if isinstance(obj, IndexedRows):
        return {
            _IR_KEY: True,
            "v": _encode_array(obj.values),
            "i": _encode_array(obj.indices),
        }
    if isinstance(obj, QuantizedDelta):
        return {
            _QD_KEY: True,
            "q": _encode_array(obj.q),
            "sc": _encode_array(obj.scale),
            "c": obj.chunk,
            "f": obj.offset,
        }
    if isinstance(obj, SparseDelta):
        # values may be an ndarray or a nested QuantizedDelta; either
        # way packb routes it back through _default
        return {
            _SD_KEY: True,
            "i": _encode_array(obj.indices),
            "v": obj.values,
            "n": obj.n,
        }
    if isinstance(obj, np.ndarray):
        return {_ND_KEY: True, **_encode_array(obj)}
    if isinstance(obj, (np.floating, np.integer, np.bool_)):
        return obj.item()
    if isinstance(obj, tuple):
        return {_TUPLE_KEY: list(obj)}
    # jax.Array and DeviceArray duck-type via __array__
    if hasattr(obj, "__array__"):
        return {_ND_KEY: True, **_encode_array(np.asarray(obj))}
    raise TypeError(f"cannot encode {type(obj)!r}")


def _object_hook(m: dict) -> Any:
    if _ND_KEY in m:
        return _decode_array(m)
    if _IR_KEY in m:
        return IndexedRows(values=_decode_array(m["v"]), indices=_decode_array(m["i"]))
    if _QD_KEY in m:
        return QuantizedDelta(
            q=_decode_array(m["q"]),
            scale=_decode_array(m["sc"]),
            chunk=m["c"],
            offset=m["f"],
        )
    if _SD_KEY in m:
        # "v" was decoded bottom-up (ndarray via _ND_KEY or nested
        # QuantizedDelta via _QD_KEY)
        return SparseDelta(indices=_decode_array(m["i"]), values=m["v"], n=m["n"])
    if _TUPLE_KEY in m:
        return tuple(m[_TUPLE_KEY])
    return m


# --------------------------------------------------------------------------
# v2 frame: descriptor header + out-of-band aligned raw segments


class _FrameBuilder:
    """Collects payload segments during the encode walk and assigns
    64-byte-aligned offsets. Segments are buffer VIEWS of the source
    arrays — nothing is copied until the final frame join."""

    __slots__ = ("segments", "offset")

    def __init__(self):
        # [(pad_before, uint8-views)] in payload order: a segment is
        # one view, or the consecutive views a `LeafVector` lies in
        self.segments: list = []
        self.offset = 0

    def add(self, views, nbytes: int) -> int:
        pad = (-self.offset) % _SEGMENT_ALIGN
        off = self.offset + pad
        self.segments.append((pad, views))
        self.offset = off + nbytes
        return off


def _frame_descriptor(a: np.ndarray, builder: _FrameBuilder) -> dict:
    """Append `a`'s bytes to the frame payload and return its header
    descriptor. Zero-copy for contiguous arrays: `reshape(-1)` and
    `view(np.uint8)` are views. Only a non-contiguous array pays a
    compaction copy, which the encode copy counter records."""
    a = np.asarray(a)
    shape = list(a.shape)
    if not a.flags["C_CONTIGUOUS"]:
        _encode_copies.bytes += int(a.nbytes)
        _encode_copies.arrays += 1
        a = np.ascontiguousarray(a)
    seg = a.reshape(-1).view(np.uint8)
    off = builder.add((seg,), seg.nbytes)
    return {"d": _dtype_to_str(a.dtype), "s": shape, "o": off, "n": seg.nbytes}


def _build_frame_tree(obj: Any, builder: _FrameBuilder) -> Any:
    """Replace every array in the pytree with a frame descriptor,
    collecting the raw segments in `builder`. Container structure and
    scalar leaves pass through for the msgpack header."""
    if isinstance(obj, IndexedRows):
        return {
            _IR_KEY: True,
            "v": {_ND_KEY: True, **_frame_descriptor(obj.values, builder)},
            "i": {_ND_KEY: True, **_frame_descriptor(obj.indices, builder)},
        }
    if isinstance(obj, QuantizedDelta):
        return {
            _QD_KEY: True,
            "q": {_ND_KEY: True, **_frame_descriptor(obj.q, builder)},
            "sc": {_ND_KEY: True, **_frame_descriptor(obj.scale, builder)},
            "c": obj.chunk,
            "f": obj.offset,
        }
    if isinstance(obj, SparseDelta):
        return {
            _SD_KEY: True,
            "i": {_ND_KEY: True, **_frame_descriptor(obj.indices, builder)},
            "v": _build_frame_tree(obj.values, builder),
            "n": obj.n,
        }
    if isinstance(obj, np.ndarray):
        return {_ND_KEY: True, **_frame_descriptor(obj, builder)}
    if isinstance(obj, LeafVector):
        # the entry a float32 ndarray of this length gets, over one
        # segment made of the leaves where they lie
        # (a piece still on its way enters as itself: its length is
        # known, `part_bytes` waits for the rest)
        nbytes = obj.size * 4
        off = builder.add(
            [
                p if isinstance(p, PendingPiece) else p.view(np.uint8)
                for p in obj.pieces
            ],
            nbytes,
        )
        return {_ND_KEY: True, "d": "<f4", "s": [obj.size], "o": off, "n": nbytes}
    if isinstance(obj, dict):
        return {k: _build_frame_tree(v, builder) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_build_frame_tree(v, builder) for v in obj]
    if isinstance(obj, tuple):
        # stays a tuple so packb's strict_types routes it to _default's
        # {_TUPLE_KEY: ...} wrapper — round-trips as a tuple
        return tuple(_build_frame_tree(v, builder) for v in obj)
    if isinstance(obj, (str, bytes, bytearray, bool, int, float)) or obj is None:
        return obj
    if isinstance(obj, (np.floating, np.integer, np.bool_)):
        return obj.item()
    # jax.Array and DeviceArray duck-type via __array__ (device->host
    # transfer — deliberately not counted as an encode copy)
    if hasattr(obj, "__array__"):
        return {_ND_KEY: True, **_frame_descriptor(np.asarray(obj), builder)}
    return obj  # let packb/_default accept or reject it


def _read_frame_descriptor(m: dict, frame, payload_start: int) -> np.ndarray:
    """Materialize one descriptor as an `np.frombuffer` view into the
    frame (read-only, shares the frame's lifetime — v1 semantics, one
    buffer instead of one per array)."""
    dt = dtype_from_str(m["d"])
    shape = m["s"]
    count = 1
    for dim in shape:
        count *= int(dim)
    nbytes = count * dt.itemsize
    if m["n"] != nbytes:
        raise ValueError(
            f"corrupt frame descriptor: {m['n']} bytes for "
            f"dtype {m['d']} shape {shape} (expected {nbytes})"
        )
    arr = np.frombuffer(
        frame, dtype=dt, count=count, offset=payload_start + m["o"]
    )
    return arr.reshape(shape)


def _loads_frame(data) -> Any:
    magic, version, hlen, pad = _FRAME_PREFIX.unpack_from(data, 0)
    if version != CODEC_VERSION:
        raise ValueError(f"unsupported codec frame version {version}")
    header_end = _FRAME_PREFIX.size + hlen
    payload_start = header_end + pad

    def hook(m: dict) -> Any:
        if _ND_KEY in m:
            return _read_frame_descriptor(m, data, payload_start)
        if _IR_KEY in m:
            # descriptors carry _ND_KEY, so msgpack's bottom-up hooks
            # already turned v/i into arrays
            return IndexedRows(values=m["v"], indices=m["i"])
        if _QD_KEY in m:
            return QuantizedDelta(
                q=m["q"], scale=m["sc"], chunk=m["c"], offset=m["f"]
            )
        if _SD_KEY in m:
            return SparseDelta(indices=m["i"], values=m["v"], n=m["n"])
        if _TUPLE_KEY in m:
            return tuple(m[_TUPLE_KEY])
        return m

    header = bytes(data[_FRAME_PREFIX.size:header_end])
    return msgpack.unpackb(
        header, object_hook=hook, raw=False, strict_map_key=False
    )


def all_float_leaves(tree) -> bool:
    import jax

    return all(
        np.issubdtype(np.asarray(leaf).dtype, np.floating)
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def ravel_np(tree) -> np.ndarray:
    """Concatenate a float pytree into ONE contiguous float32 vector
    (tree_flatten order). TPU-first transport: the full model/gradient
    rides a single buffer — one host<->device transfer and one memcpy
    instead of one per leaf (what that saves on a local chip: not
    measured on this machine)."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    return np.concatenate(
        [np.asarray(leaf, dtype=np.float32).ravel() for leaf in leaves]
    )


class PendingPiece:
    """A `LeafVector` piece that may still be on its way: `size`
    float32 elements, known when the frame is built, and `wait`, which
    blocks until they have landed and hands them over as a flat
    C-contiguous float32 array (`wait(timeout)`: seconds or None;
    `TimeoutError` when they pass, whatever else went wrong with the
    copy as it was raised). The frame's header and length need only the
    size, so a carrier that writes to a socket sends what lies before
    the piece and waits for it there (`part_bytes`), inside the call's
    deadline. What landed is kept: a retry, or a join for a carrier
    that needs one buffer, reads the same host copy and waits for
    nothing."""

    __slots__ = ("size", "_wait", "_array")

    def __init__(self, size: int, wait):
        self.size = int(size)
        self._wait = wait
        self._array = None

    def peek(self):
        """The array if it has been waited for and landed, else None."""
        return self._array

    def landed(self, timeout=None) -> np.ndarray:
        arr = self._array
        if arr is None:
            arr = self._wait(timeout)
            _check_piece(arr)
            if arr.size != self.size:
                raise ValueError(
                    f"a piece of {self.size} elements landed as {arr.size}"
                )
            self._array, self._wait = arr, None
        return arr


def _check_piece(p) -> None:
    if p.dtype != np.float32 or p.ndim != 1 or not p.flags.c_contiguous:
        raise TypeError("a LeafVector piece is a flat float32 array")


def part_bytes(part, timeout=None):
    """A frame part as the buffer that goes on the wire: itself, or,
    for a `PendingPiece`, the bytes it has landed as (waited for, at
    most `timeout` seconds)."""
    if isinstance(part, PendingPiece):
        return part.landed(timeout).view(np.uint8)
    return part


class LeafVector:
    """One float32 vector that lies in several arrays: what `ravel_np`
    of a tree would concatenate, without concatenating. `pieces` are
    flat C-contiguous float32 arrays, in order, or `PendingPiece`s that
    will land as such; whoever builds one
    answers for their staying as they are until the frame has left (a
    read-only leaf that is replaced and never written, or a copy of its
    own). In a v2 frame it is the vector: the header entry an ndarray
    of `size` gets, over one segment whose parts are the pieces' bytes,
    so `loads` on the other side sees the `ravel_np` vector and the
    frame is the same byte for byte. A caller that is handed one
    directly, with no wire between, reads it with `np.asarray`."""

    __slots__ = ("pieces", "size")

    def __init__(self, pieces):
        self.pieces = list(pieces)
        for p in self.pieces:
            if not isinstance(p, PendingPiece):
                _check_piece(p)
        self.size = sum(p.size for p in self.pieces)

    def __array__(self, dtype=None, copy=None):
        vec = (
            np.concatenate(
                [
                    p.landed() if isinstance(p, PendingPiece) else p
                    for p in self.pieces
                ]
            )
            if self.pieces
            else np.zeros(0, np.float32)
        )
        return vec if dtype is None else vec.astype(dtype, copy=False)


def template_meta(template) -> tuple:
    """(shapes, sizes, treedef) of a pytree — the unravel plan. One
    `np.asarray` per leaf; callers on hot paths cache the result via
    `make_unraveler` instead of re-deriving it per pull."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(template)
    shapes, sizes = [], []
    for leaf in leaves:
        a = np.asarray(leaf)
        shapes.append(a.shape)
        sizes.append(int(a.size))
    return shapes, sizes, treedef


def make_unraveler(template):
    """Build a reusable `vec -> pytree` closure from `template`.

    Model-pull hot path: the template's structure is fixed for the life
    of a job, so the (shapes, sizes, treedef) plan is computed once and
    every call is just len(leaves) slice+reshape views."""
    import jax

    shapes, sizes, treedef = template_meta(template)
    total = sum(sizes)

    def unravel(vec) -> Any:
        vec = np.asarray(vec, dtype=np.float32)
        if vec.size != total:
            raise ValueError(
                f"flat vector size {vec.size} != template size {total}"
            )
        out, off = [], 0
        for shape, n in zip(shapes, sizes):
            out.append(vec[off : off + n].reshape(shape))
            off += n
        return jax.tree_util.tree_unflatten(treedef, out)

    return unravel


def unravel_np(vec: np.ndarray, template) -> Any:
    """Inverse of ravel_np given a template tree with the same
    structure/shapes (e.g. the PS's param tree). One-shot form of
    `make_unraveler(template)(vec)`."""
    return make_unraveler(template)(vec)


def dumps_parts(obj: Any):
    """Serialize a pytree as an ordered list of v2-frame parts (bytes
    for the prefix/header/pads, flat uint8 views of the source arrays,
    which keep them alive, and a `LeafVector`'s `PendingPiece`s as
    themselves) and the total frame length. The parts' bytes
    (`part_bytes`), joined, ARE the frame; a carrier that writes to a
    socket sends the parts and never joins."""
    builder = _FrameBuilder()
    tree = _build_frame_tree(obj, builder)
    header = msgpack.packb(
        tree, default=_default, use_bin_type=True, strict_types=True
    )
    head_pad = (-(_FRAME_PREFIX.size + len(header))) % _SEGMENT_ALIGN
    parts = [
        _FRAME_PREFIX.pack(FRAME_MAGIC, CODEC_VERSION, len(header), head_pad),
        header,
    ]
    if head_pad:
        parts.append(b"\x00" * head_pad)
    total = _FRAME_PREFIX.size + len(header) + head_pad
    for pad, views in builder.segments:
        if pad:
            parts.append(b"\x00" * pad)
        parts.extend(views)
    return parts, total + builder.offset


def dumps(obj: Any) -> bytes:
    """Serialize a pytree (nested dict/list/tuple of arrays, scalars,
    strings) as a v2 frame in one buffer. Contiguous array bytes enter
    the frame as buffer views; the single full-size copy is this
    join."""
    parts, _ = dumps_parts(obj)
    return b"".join(map(part_bytes, parts))


def dumps_v1(obj: Any) -> bytes:
    """The pre-frame encoder (arrays embedded as msgpack bins, one
    `tobytes()` copy per array). Kept for cross-version decode tests
    and as an escape hatch while mixed-version jobs drain."""
    return msgpack.packb(obj, default=_default, use_bin_type=True, strict_types=True)


def loads(data: bytes) -> Any:
    """Deserialize either codec version; array buffers are zero-copy
    views over `data`. v2 frames are detected by the 0xC1 magic byte
    (reserved in msgpack — no v1 payload starts with it)."""
    if len(data) >= _FRAME_PREFIX.size and data[0] == FRAME_MAGIC:
        return _loads_frame(data)
    return msgpack.unpackb(data, object_hook=_object_hook, raw=False, strict_map_key=False)
