"""A window's delta leaves the device in slices (worker/delta_stream.py,
PR 45): the copies' side alone, over fake device arrays, and a tiny
window job whose slice size is lowered so its delta takes several, on
the serial and on the overlapped chain, against the same job with the
delta in one copy. On the serial chain the slices are a snapshot's and
the host subtracts its base from each (PR 59)."""

import threading
import time

import flax.linen as nn
import numpy as np
import pytest

from elasticdl_tpu.common import codec
from elasticdl_tpu.common.constants import MASTER_UPDATE_METHODS
from elasticdl_tpu.obs import trace
from elasticdl_tpu.worker import delta_stream


@pytest.fixture(autouse=True)
def _clean_recorder():
    trace.configure(0.0)  # the timeline needs no sampling
    trace.RECORDER.clear()
    yield
    trace.RECORDER.clear()
    trace.configure(None)


# -- the copies' side ---------------------------------------------------------


class _DevicePiece:
    """What `DeltaStream` asks of a device array: a copy it can start
    and an array it can wait for."""

    def __init__(self, log, i, values, gate=None, error=None):
        self._log, self._i, self._values = log, i, values
        self._gate, self._error = gate, error

    def copy_to_host_async(self):
        self._log.append(("asked", self._i))

    def __array__(self, dtype=None, copy=None):
        if self._gate is not None:
            assert self._gate.wait(10)
        if self._error is not None:
            raise self._error
        self._log.append(("landed", self._i))
        return self._values


def test_slice_bounds_are_equal_slices_and_a_tail(monkeypatch):
    monkeypatch.setattr(delta_stream, "DELTA_SLICE_BYTES", 16)
    assert delta_stream.slice_bounds(10) == [(0, 4), (4, 8), (8, 10)]
    assert delta_stream.slice_bounds(8) == [(0, 4), (4, 8)]
    assert delta_stream.slice_bounds(3) == [(0, 3)]


def test_the_chosen_slice_is_a_whole_number_of_floats_and_fits_the_bound():
    """Two in flight stay under the 0.3 GB the sync's moment may add."""
    assert delta_stream.DELTA_SLICE_BYTES % 4 == 0
    assert (
        delta_stream.DELTA_SLICE_BYTES * delta_stream.SLICES_IN_FLIGHT
        <= 300_000_000
    )


def test_copies_are_asked_in_order_a_bounded_number_ahead():
    vec = np.arange(23, dtype=np.float32)
    bounds = [(lo, min(lo + 4, 23)) for lo in range(0, 23, 4)]
    log, gates = [], [threading.Event() for _ in bounds]

    def cut(i):
        log.append(("cut", i))
        lo, hi = bounds[i]
        return _DevicePiece(log, i, vec[lo:hi], gates[i])

    stream = delta_stream.DeltaStream(
        bounds, (cut(i) for i in range(len(bounds)))
    )
    vector = stream.vector()
    assert len(vector.pieces) == 6 and vector.size == 23 and log == []
    t0 = time.time()
    stream.start()
    for i, gate in enumerate(gates):
        # until slice i lands, nothing beyond those in flight is cut
        deadline = time.monotonic() + 5
        want = min(len(bounds), i + delta_stream.SLICES_IN_FLIGHT)
        while (
            sum(e[0] == "asked" for e in log) < want
            and time.monotonic() < deadline
        ):
            time.sleep(0.001)
        time.sleep(0.01)
        assert [e[1] for e in log if e[0] == "cut"] == list(range(want))
        with pytest.raises(TimeoutError):
            vector.pieces[i].landed(0.01)
        gate.set()
        assert np.array_equal(vector.pieces[i].landed(5), vec[slice(*bounds[i])])
    t_first, t_last = stream.settle()
    assert t0 <= t_first <= t_last <= time.time()
    assert np.array_equal(np.asarray(vector), vec)
    # each slice: cut, its copy asked at once, landed after that
    for i in range(len(bounds)):
        assert log.index(("cut", i)) + 1 == log.index(("asked", i))
        assert log.index(("asked", i)) < log.index(("landed", i))
    assert codec.dumps({"d": vector}) == codec.dumps({"d": vec})


def test_a_copy_that_fails_fails_every_piece_not_yet_landed():
    vec = np.arange(12, dtype=np.float32)
    bounds = [(0, 4), (4, 8), (8, 12)]
    boom = ValueError("device lost")
    log = []
    stream = delta_stream.DeltaStream(
        bounds,
        (
            _DevicePiece(
                log, i, vec[slice(*bounds[i])], error=boom if i == 1 else None
            )
            for i in range(len(bounds))
        ),
    )
    vector = stream.vector()
    stream.start()
    stream.settle()
    assert np.array_equal(vector.pieces[0].landed(1), vec[:4])
    for piece in vector.pieces[1:]:
        with pytest.raises(RuntimeError, match="did not land") as ei:
            piece.landed(1)
        assert ei.value.__cause__ is boom


def _read_only(values):
    landed = np.array(values, np.float32)
    landed.flags.writeable = False  # as the runtime hands a copy out
    return landed


@pytest.mark.parametrize("kept", [True, False], ids=["kept_memory", "fresh"])
def test_a_snapshots_slices_less_the_base_are_the_delta(kept):
    """Piece i is what landed less the base's slice, float32, in the
    caller's memory where it keeps some; what landed is the next base,
    untouched; the old base is let go slice by slice."""
    rng = np.random.default_rng(59)
    old = rng.standard_normal(10).astype(np.float32)
    new = (old + rng.standard_normal(10) * 1e-3).astype(np.float32)
    bounds = [(0, 4), (4, 8), (8, 10)]
    log, gate = [], threading.Event()
    base = [_read_only(old[lo:hi]) for lo, hi in bounds]
    landed = [_read_only(new[lo:hi]) for lo, hi in bounds]
    out = np.full(10, np.nan, np.float32) if kept else None
    stream = delta_stream.DeltaStream(
        bounds,
        (
            _DevicePiece(log, i, landed[i], gate if i == 2 else None)
            for i in range(3)
        ),
        base=base, out=out,
    )
    vector = stream.vector()
    stream.start()
    first = vector.pieces[0].landed(5)
    assert first.tobytes() == (new[:4] - old[:4]).tobytes()
    assert base[0] is None and stream.snapshot() is None  # not all landed yet
    gate.set()
    t_first, t_last = stream.settle()
    assert np.asarray(vector).tobytes() == (new - old).tobytes()
    if kept:
        assert out.tobytes() == (new - old).tobytes()
        assert np.shares_memory(first, out)
    assert base == [None] * 3
    snapshot = stream.snapshot()
    assert all(a is b for a, b in zip(snapshot, landed))
    assert np.concatenate(snapshot).tobytes() == new.tobytes()
    began, ended, busy = stream.subtracting()
    assert t_first <= began <= ended <= time.time() and 0 <= busy <= ended - began
    assert codec.dumps({"d": vector}) == codec.dumps({"d": new - old})


def test_the_hosts_difference_is_ieee_and_keeps_a_subnormal():
    """Where the two forms can differ: a TPU flushes a subnormal
    difference to zero, the host keeps it. Everything else is the one
    correctly rounded float32 subtraction."""
    old = np.array([1e-38, 1.0, -3.5, 0.0], np.float32)
    new = np.array([1.1e-38, 1.0 + 2**-23, 2**24, -0.0], np.float32)
    stream = delta_stream.DeltaStream(
        [(0, 4)], iter([_DevicePiece([], 0, _read_only(new))]),
        base=[_read_only(old)],
    )
    stream.start()
    stream.settle()
    (piece,) = stream.vector().pieces
    got = piece.landed(1)
    assert got.dtype == np.float32
    assert got.tobytes() == (new - old).tobytes()
    assert 0 < got[0] < np.finfo(np.float32).tiny  # subnormal, kept
    assert got[1] == np.float32(2**-23) and got[2] == np.float32(2**24 + 4)


def test_a_snapshot_whose_copy_fails_is_no_base():
    vec = np.arange(12, dtype=np.float32)
    bounds = [(0, 4), (4, 8), (8, 12)]
    boom = ValueError("device lost")
    stream = delta_stream.DeltaStream(
        bounds,
        (
            _DevicePiece(
                [], i, _read_only(vec[slice(*bounds[i])]),
                error=boom if i == 1 else None,
            )
            for i in range(3)
        ),
        base=[_read_only(np.ones(4)) for _ in bounds],
    )
    vector = stream.vector()
    stream.start()
    stream.settle()
    assert vector.pieces[0].landed(1).tobytes() == (vec[:4] - 1).tobytes()
    for piece in vector.pieces[1:]:
        with pytest.raises(RuntimeError, match="did not land") as ei:
            piece.landed(1)
        assert ei.value.__cause__ is boom
    assert stream.snapshot() is None


def test_the_thread_keeps_no_slice_when_it_has_ended():
    """The stream's thread lets go of every device slice it was handed
    (the slice asked for last, the generator that cuts them) when it
    returns: a landed slice lives on only where its owner keeps it."""
    import gc
    import weakref

    vec = np.arange(10, dtype=np.float32)
    bounds = [(0, 4), (4, 8), (8, 10)]
    alive = []

    def cut(i):
        piece = _DevicePiece([], i, vec[slice(*bounds[i])])
        alive.append(weakref.ref(piece))
        return piece

    stream = delta_stream.DeltaStream(bounds, (cut(i) for i in range(3)))
    vector = stream.vector()
    stream.start()
    stream.settle()
    gc.collect()
    assert [r() is not None for r in alive] == [False] * 3
    assert np.array_equal(np.asarray(vector), vec)


# -- a tiny window job --------------------------------------------------------


class _Mlp(nn.Module):
    """Ten parameters: with a slice of four floats, two equal slices
    and a tail of two."""

    @nn.compact
    def __call__(self, x):
        return nn.Dense(1)(nn.tanh(nn.Dense(3)(x)))


def _run_job(tmp_path, monkeypatch, slice_bytes, chain, **worker_kw):
    """A real Worker over the Unix socket against a real servicer; ->
    (spans, the master's parameters, its version, compile events as
    (name, time.time()))."""
    from jax import monitoring

    from elasticdl_tpu.api.model_spec_helpers import spec_from_module
    from elasticdl_tpu.master.ps_optimizer import PSOptimizer
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.rpc.client import RpcClient
    from elasticdl_tpu.rpc.server import RpcServer
    from elasticdl_tpu.testing import write_linear_records
    from elasticdl_tpu.worker.worker import Worker
    from tests.fixtures import linear_module

    monkeypatch.setattr(delta_stream, "DELTA_SLICE_BYTES", slice_bytes)
    trace.RECORDER.clear()
    path = str(tmp_path / f"train-{slice_bytes}-{chain}.rio")
    write_linear_records(path, 192, noise=0.05)
    dispatcher = TaskDispatcher({path: 192}, {}, {}, 64, 1, shuffle_seed=45)
    servicer = MasterServicer(
        grads_to_wait=1,
        optimizer=PSOptimizer(linear_module.optimizer()),
        task_dispatcher=dispatcher,
    )
    server = RpcServer(servicer.handlers(), port=0)
    server.start()
    client = RpcClient(
        f"localhost:{server.port}", timeline=MASTER_UPDATE_METHODS
    )
    compiles = []

    def listener(event, _seconds, **_kw):
        if "backend_compile" in event:
            compiles.append((event, time.time()))

    monitoring.register_event_duration_secs_listener(listener)
    try:
        client.wait_ready(10)
        worker = Worker(
            0, client, spec_from_module(linear_module, model=_Mlp()),
            minibatch_size=16, local_updates=2, overlap_sync=chain,
            **worker_kw,
        )
        worker.run()
        worker.close()
    finally:
        monitoring.unregister_event_duration_listener(listener)
        client.close()
        server.stop()
    assert dispatcher.finished()
    return (
        trace.RECORDER.snapshot(), servicer.get_params_copy(),
        servicer._version, compiles,
    )


def _named(spans, name):
    return sorted((s for s in spans if s["name"] == name), key=lambda s: s["ts"])


def _leaves(params):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(params)]


@pytest.mark.parametrize("chain", ["off", "on"], ids=["serial", "overlapped"])
def test_a_sliced_delta_trains_the_same_model_bit_for_bit(
    tmp_path, monkeypatch, chain
):
    whole = _run_job(tmp_path, monkeypatch, 1 << 20, chain)
    sliced = _run_job(tmp_path, monkeypatch, 16, chain)
    spans, params, version, compiles = sliced
    # the master's model and version: those of the job with one slice
    assert version == whole[2] == 192 // 16
    for got, want in zip(_leaves(params), _leaves(whole[1])):
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes()
    # one `worker.d2h` a sync, on the sync's own thread and inside it
    syncs = _named(spans, "worker.window_sync")
    copies = _named(spans, "worker.d2h")
    trips = _named(spans, "rpc.client.ReportLocalUpdate")
    assert len(syncs) == len(copies) == len(trips) == 192 // 32
    for sync, copy, trip in zip(syncs, copies, trips):
        assert copy["tid"] == trip["tid"] == sync["tid"]
        assert sync["ts"] <= copy["ts"]
        assert copy["ts"] + copy["dur"] <= sync["ts"] + sync["dur"] + 1e-6
        assert copy["args"]["slices"] == 3 and copy["args"]["bytes"] == 40
        assert trip["args"]["streamed"] is True
        assert trip["args"]["joined"] is False
        assert trip["args"]["waited_ms"] >= 0
        # the copies begin before the request leaves and end inside it
        assert copy["ts"] <= trip["ts"] + trip["dur"]
    # the master received into the frame as before and applied in place
    applies = _named(spans, "apply")
    assert len(applies) == len(syncs)
    # the serial chain forms no delta on its device: one program, the
    # snapshot in slices, where the job with one slice has the
    # subtraction and the base's copy, and the host subtracts inside
    # every sync; the overlapped chain's `jit_subtract` forms the delta
    # in its slices, so it has the one-slice job's programs. All in
    # set-up: nothing compiles once the first sync has settled
    programs = [s["args"]["program"] for s in _named(spans, "setup.program")]
    whole_programs = [
        s["args"]["program"] for s in _named(whole[0], "setup.program")
    ]
    assert whole_programs.count("jit_subtract") == 1
    if chain == "off":
        assert sorted(programs + ["jit_subtract", "jit_copy"]) == sorted(
            whole_programs + ["jit_snapshot"]
        )
        assert [s["tid"] for s in _named(spans, "worker.host_delta")] == [
            s["tid"] for s in syncs
        ]
    else:
        assert sorted(programs) == sorted(whole_programs)
        assert not _named(spans, "worker.host_delta")
    first_settled = syncs[0]["ts"] + syncs[0]["dur"]
    assert compiles and not [e for e in compiles if e[1] > first_settled]
    # and the one-slice job says so
    for copy, trip in zip(
        _named(whole[0], "worker.d2h"),
        _named(whole[0], "rpc.client.ReportLocalUpdate"),
    ):
        assert copy["args"]["slices"] == 1
        assert trip["args"]["streamed"] is False
        assert trip["args"]["waited_ms"] == 0.0


@pytest.mark.parametrize("worker_kw", [
    {"transport_dtype": "bfloat16"},
    {"sync_dtype": "int8"},
], ids=["bf16_cast", "int8_ef"])
def test_another_wire_form_takes_the_one_copy(tmp_path, monkeypatch, worker_kw):
    """A delta that is cast or quantized on the device is not the
    plain float32 vector: one `device_get`, whatever its length."""
    spans, _params, version, _ = _run_job(
        tmp_path, monkeypatch, 16, "off", **worker_kw
    )
    assert version == 192 // 16
    copies = _named(spans, "worker.d2h")
    trips = _named(spans, "rpc.client.ReportLocalUpdate")
    assert len(copies) == len(trips) == 192 // 32
    assert all(c["args"]["slices"] == 1 for c in copies)
    assert all(t["args"]["streamed"] is False for t in trips)
    assert not [
        s for s in _named(spans, "setup.program")
        if s["args"]["program"] == "jit_snapshot"
    ]
    assert not _named(spans, "worker.host_delta")


def test_a_slice_that_fails_to_land_is_a_failed_sync(tmp_path, monkeypatch):
    """The request is cut short with its connection, the master reads
    a peer that closed mid-frame and applies nothing, and the worker
    takes the "sync failed" path it always had: the window's tasks go
    back to the dispatcher and the job ends at the exact version."""
    real, calls = delta_stream.DeltaStream._less_base, []

    def less_base(self, i, new, began):
        calls.append(i)
        if len(calls) == 5:  # the second sync's second slice
            raise RuntimeError("the device lost slice 1")
        return real(self, i, new, began)

    monkeypatch.setattr(delta_stream.DeltaStream, "_less_base", less_base)
    spans, _params, version, _ = _run_job(tmp_path, monkeypatch, 16, "off")
    assert version == 192 // 16
    trips = _named(spans, "rpc.client.ReportLocalUpdate")
    failed = [t for t in trips if t["args"].get("failed")]
    assert len(failed) == 1 and failed[0]["args"]["streamed"] is True
    applied = [
        s for s in _named(spans, "apply")
        if s["args"].get("kind") == "local_update"
    ]
    # (the failed task's first window had landed: retrained, its key
    # is a duplicate, which answers and applies nothing)
    assert len(applied) == 192 // 32 <= len(trips) - 1
    # every sync, the failed one too, has its one `worker.d2h`
    assert len(_named(spans, "worker.d2h")) == len(trips)
