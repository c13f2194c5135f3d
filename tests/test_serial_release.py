"""The serial chain lets the step loop go at the sync's release point
(PR 57): the host holds all the sync reads from the device and the
delta is deleted there; the rest of the send, the master's apply, the
answer and the reports run behind the next window. A tiny window job
against an in-process master whose `ReportLocalUpdate` is held for a
moment, the delta in slices (the slice lowered to four floats, as
`test_delta_stream.py` does) and in one copy."""

import sys
import threading
import time

import flax.linen as nn
import numpy as np
import pytest

from elasticdl_tpu.api.model_spec_helpers import spec_from_module
from elasticdl_tpu.master.ps_optimizer import PSOptimizer
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.obs import trace
from elasticdl_tpu.testing import InProcessMaster, write_linear_records
from elasticdl_tpu.worker import delta_stream
from elasticdl_tpu.worker.worker import Worker

from tests.fixtures import linear_module

SYNC, REPORT = "ReportLocalUpdate", "ReportTaskResult"
FORMS = pytest.mark.parametrize("form", ["sliced", "whole"])
RECORDS, MINIBATCH, WINDOW = 192, 16, 2
STEPS = RECORDS // MINIBATCH
SYNCS = STEPS // WINDOW


@pytest.fixture(autouse=True)
def _clean_recorder():
    trace.configure(0.0)  # the timeline needs no sampling
    trace.RECORDER.clear()
    yield
    trace.RECORDER.clear()
    trace.configure(None)


class _Mlp(nn.Module):
    """Ten parameters: with a slice of four floats, two equal slices
    and a tail of two."""

    @nn.compact
    def __call__(self, x):
        return nn.Dense(1)(nn.tanh(nn.Dense(3)(x)))


class _WholeSyncs(Worker):
    """The parent's serial chain: every sync waited for whole."""

    _sync_hold = property(lambda self: "first", lambda self, value: None)


class _HeldMaster(InProcessMaster):
    """Holds every `ReportLocalUpdate` but the first until the worker
    has asked its device for another run (or half a second: the last
    sync of a job has no window after it), so that whatever follows
    the hold lies in the sync's hidden tail. `before(n, request)` runs
    after the hold of the worker's n-th report, before the servicer
    sees it. Records what reached the master."""

    def __init__(self, servicer, before=None):
        super().__init__(servicer)
        self.worker = None
        self.before = before
        self.pushes = []  # (delta bytes, steps, base_version)
        self.reports = []  # (task_id, err_message, the master's version)
        self._lock = threading.Lock()

    def call(self, method, request=None):
        if method == REPORT:
            with self._lock:
                self.reports.append((
                    request["task_id"], request["err_message"],
                    self.servicer._version,
                ))
        if method != SYNC:
            return super().call(method, request)
        with self._lock:
            self.pushes.append((
                np.asarray(request["delta_flat"], np.float32).tobytes(),
                request["steps"], request["base_version"],
            ))
            n = len(self.pushes)
        runs = self.worker._device_runs
        seq, deadline = runs.seq, time.monotonic() + 0.5
        while n > 1 and runs.seq == seq and time.monotonic() < deadline:
            time.sleep(0.001)
        if self.before is not None:
            self.before(n, request)
        return super().call(method, request)


def _job(tmp_path, monkeypatch, form, *, worker_cls=Worker, before=None,
         records_per_task=64, chain="off", **worker_kw):
    """-> (worker, master, servicer, spans) of one finished job;
    `before(servicer)` gives `_HeldMaster` its hook."""
    monkeypatch.setattr(
        delta_stream, "DELTA_SLICE_BYTES", 16 if form == "sliced" else 1 << 20
    )
    trace.RECORDER.clear()
    tmp_path.mkdir(parents=True, exist_ok=True)
    path = str(tmp_path / "train.rio")
    write_linear_records(path, RECORDS, noise=0.05)
    dispatcher = TaskDispatcher(
        {path: RECORDS}, {}, {}, records_per_task, 1, shuffle_seed=57
    )
    servicer = MasterServicer(
        grads_to_wait=1,
        optimizer=PSOptimizer(linear_module.optimizer()),
        task_dispatcher=dispatcher,
    )
    master = _HeldMaster(servicer, before and before(servicer))
    worker = master.worker = worker_cls(
        0, master, spec_from_module(linear_module, model=_Mlp()),
        minibatch_size=MINIBATCH, local_updates=WINDOW, overlap_sync=chain,
        **worker_kw,
    )
    worker.run()
    worker.close()
    assert dispatcher.finished()
    return worker, master, servicer, trace.RECORDER.snapshot()


def _named(spans, name):
    return sorted(
        (s for s in spans if s["name"] == name), key=lambda s: s["ts"]
    )


def _end(span):
    return span["ts"] + span["dur"]


def _model_bytes(servicer):
    import jax

    params, _aux, version = servicer.get_params_copy()
    return version, [
        np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(params)
    ]


def _released(spans):
    return [
        (s["args"].get("released"), s["args"].get("why"))
        for s in _named(spans, "worker.window_sync")
    ]


@FORMS
def test_the_step_loop_goes_on_when_the_delta_has_left_the_device(
    tmp_path, monkeypatch, form
):
    deltas, donated, freed = [], [], []
    real_delta, real_sync = Worker._delta_from_base, Worker._sync_local_updates

    def delta_from_base(self):
        delta = real_delta(self)
        deltas.append(delta)
        donated.append(self._base_flat is None)  # given up to the program
        return delta

    def sync_local_updates(self, blocking=True):
        formed = len(deltas)
        real_sync(self, blocking)
        if len(deltas) > formed:  # the step loop has just been let go
            freed.append(deltas[-1].is_deleted())

    monkeypatch.setattr(Worker, "_delta_from_base", delta_from_base)
    monkeypatch.setattr(Worker, "_sync_local_updates", sync_local_updates)
    worker, _master, servicer, spans = _job(tmp_path, monkeypatch, form)
    assert servicer._version == STEPS
    syncs = _named(spans, "worker.window_sync")
    runs = {s["args"]["seq"]: s for s in _named(spans, "worker.device_run")}
    assert len(syncs) == SYNCS and len(runs) == SYNCS
    # a worker's first sync is waited for whole; every later one lets
    # the step loop go at its release point
    assert _released(spans) == [("settled", "first")] + [("copied", None)] * (
        SYNCS - 1
    )
    assert worker.sync_releases == {"copied": SYNCS - 1, "settled": 1}
    assert [
        (s["args"].get("released"), s["args"].get("why"))
        for s in _named(spans, "worker.sync_spawn")
    ] == _released(spans)
    first, later = syncs[0], syncs[1:-1]  # the last has no window after it
    assert runs[first["args"]["seq"] + 1]["args"]["asked"] >= _end(first)
    for sync in later:
        after = runs[sync["args"]["seq"] + 1]
        # the next window was asked for while the master still held
        # this sync's request
        assert after["args"]["asked"] < _end(sync)
        trip = [
            s for s in _named(spans, "worker.d2h")
            if s["args"]["seq"] == sync["args"]["seq"]
        ]
        assert len(trip) == 1 and _end(trip[0]) <= after["args"]["asked"]
        assert trip[0]["args"]["slices"] == (3 if form == "sliced" else 1)
    # nothing of a delta is on the device when the step loop goes on,
    # and no snapshot holds the base: every subtraction donates it
    assert freed == [True] * SYNCS and donated == [True] * SYNCS
    # the wait before the next delta found the sync settled or waited
    # for it under a reason of its own; the wait after the spawn kept its
    reasons = [s["args"]["reason"] for s in _named(spans, "worker.sync_exposed")]
    assert reasons.count("settle") == SYNCS - 2  # the last one: the drain
    assert reasons.count("backpressure") == SYNCS and "drain" in reasons
    phases = _named(spans, "sync_wait")
    for s in _named(spans, "worker.sync_exposed"):
        if s["args"]["reason"] in ("settle", "backpressure"):
            assert any(
                p["ts"] <= s["ts"] + 1e-6 and _end(s) <= _end(p) + 1e-6
                for p in phases
            )


@FORMS
def test_what_reaches_the_master_is_bit_identical_to_whole_syncs(
    tmp_path, monkeypatch, form
):
    whole = _job(tmp_path / "w", monkeypatch, form, worker_cls=_WholeSyncs)
    # the sync's thread and the step loop now run side by side: give
    # them every chance to interleave
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        early = _job(tmp_path / "e", monkeypatch, form)
    finally:
        sys.setswitchinterval(interval)
    assert _released(whole[3]) == [("settled", "first")] * SYNCS
    assert whole[0].sync_releases == {"copied": 0, "settled": SYNCS}
    assert early[0].sync_releases["copied"] == SYNCS - 1
    assert _model_bytes(early[2]) == _model_bytes(whole[2])
    assert early[2]._version == STEPS
    assert early[1].calls[SYNC] == whole[1].calls[SYNC] == SYNCS
    assert early[1].pushes == whole[1].pushes
    # every task reported done once, after the sync that covers it
    for _w, master, _s, _spans in (whole, early):
        assert [err for _, err, _ in master.reports] == [""] * (RECORDS // 64)
        assert [v for _, _, v in master.reports] == [4, 8, 12]


def _another_worker_writes(servicer, at):
    """Before the worker's `at`-th report reaches the servicer another
    worker's delta of two steps does."""

    def before(n, request):
        if n == at:
            size = np.asarray(request["delta_flat"]).size
            servicer.report_local_update({
                "delta_flat": np.full(size, 0.01, np.float32), "steps": 2,
                "base_version": servicer._version, "aux_state": None,
                "report_key": "another-worker",
            })

    return before


@FORMS
def test_a_merged_answer_is_absorbed_a_window_late_as_at_depth_one(
    tmp_path, monkeypatch, form
):
    """The answer to sync 3 brings a merged model behind window 4: it
    is folded in before delta 4 is formed, against sync 3's base
    snapshot, as the overlapped chain with one sync in flight folds
    it; then the worker waits for whole syncs until an answer without
    a merged model."""
    from elasticdl_tpu.common.constants import ENV_SYNC_DEPTH

    def before(servicer):
        return _another_worker_writes(servicer, 3)

    serial = _job(tmp_path / "s", monkeypatch, form, before=before)
    assert serial[2]._version == STEPS + 2
    assert _released(serial[3]) == [
        ("settled", "first"), ("copied", None), ("copied", None),
        ("settled", "merged"), ("copied", None), ("copied", None),
    ]
    absorbs = _named(serial[3], "worker.absorb")
    syncs = _named(serial[3], "worker.window_sync")
    runs = {s["args"]["seq"]: s for s in _named(serial[3], "worker.device_run")}
    # one absorb, after window 4 had been asked for and before sync 4
    assert len(absorbs) == 1
    assert runs[4]["args"]["asked"] < absorbs[0]["ts"] < syncs[3]["ts"]

    # the overlapped chain, one sync in flight, its answer in by the
    # next boundary
    real_sync = Worker._sync_local_updates

    def answered_first(self, blocking=True):
        if self._sync_thread is not None:
            self._sync_thread.join()
        real_sync(self, blocking)

    monkeypatch.setattr(Worker, "_sync_local_updates", answered_first)
    monkeypatch.setenv(ENV_SYNC_DEPTH, "1")
    depth1 = _job(tmp_path / "d", monkeypatch, form, before=before, chain="on")
    assert depth1[0]._max_inflight_syncs == 1
    assert _released(depth1[3]) == [(None, None)] * SYNCS
    assert _model_bytes(serial[2]) == _model_bytes(depth1[2])
    assert serial[1].pushes == depth1[1].pushes


def test_a_sparse_plane_waits_for_whole_syncs(tmp_path):
    """`EDL_SYNC_DEPTH=0`'s promise to a model with embeddings: each
    flush lands before the next lookup."""
    from elasticdl_tpu.models import deepfm_edl_embedding
    from elasticdl_tpu.models import record_codec as rc
    from elasticdl_tpu.testing import build_job

    path = str(tmp_path / "tabular.rio")
    rc.write_synthetic_tabular_records(
        path, 32, deepfm_edl_embedding.NUM_FIELDS, 50
    )
    dispatcher = TaskDispatcher({path: 32}, {}, {}, 8, 1, shuffle_seed=7)
    spec = spec_from_module(deepfm_edl_embedding)
    servicer, _evs, _ckpt = build_job(spec, dispatcher, grads_to_wait=1)
    worker = Worker(
        0, InProcessMaster(servicer), spec, minibatch_size=8,
        local_updates=2, overlap_sync="off",
    )
    assert worker.run()
    worker.close()
    assert dispatcher.finished()
    released = _released(trace.RECORDER.snapshot())
    assert len(released) >= 2 and set(released) == {("settled", "sparse")}
    assert worker.sync_releases == {"copied": 0, "settled": len(released)}


@FORMS
def test_an_rpc_that_fails_in_the_hidden_tail(tmp_path, monkeypatch, form):
    """Sync 3's request fails after the step loop has gone on: window
    4 is thrown away at its boundary, task 3 (its report deferred
    behind sync 3) and task 4 go back to the dispatcher as failures,
    no task is reported done before its sync has landed, and the job
    ends at the fault-free version."""

    def before(_servicer):
        def hook(n, _request):
            if n == 3:
                raise ConnectionError("the master went away")

        return hook

    worker, master, servicer, spans = _job(
        tmp_path, monkeypatch, form, before=before, records_per_task=32,
    )
    assert servicer._version == STEPS
    # two tasks failed, each once; both trained again
    failed = [(t, err) for t, err, _ in master.reports if err]
    assert len(failed) == 2 and len({t for t, _ in failed}) == 2
    assert any("sync failed" in err for _, err in failed)
    done = [(t, v) for t, err, v in master.reports if not err]
    assert len(done) == SYNCS and len({t for t, _ in done}) == SYNCS
    # a task is one window here: when task k is reported done the
    # master has applied k windows
    assert [v for _, v in done] == [WINDOW * k for k in range(1, SYNCS + 1)]
    # the failed tasks were not reported done before they failed
    for task, _err in failed:
        reports = [err for t, err, _ in master.reports if t == task]
        assert reports[0] and reports[-1] == ""
    # one sync more than windows applied, the failed one (the window
    # thrown away never spawned its own); after the reset the worker
    # waits for a whole sync again, once
    released = _released(spans)
    assert len(released) == len(master.pushes) == SYNCS + 1
    assert master.calls[SYNC] == SYNCS  # what reached the servicer
    assert released[:4] == [
        ("settled", "first"), ("copied", None), ("copied", None),
        ("settled", "first"),
    ]
    assert set(released[4:]) == {("copied", None)}
    assert worker.sync_releases == {"copied": SYNCS - 1, "settled": 2}


class _Slice:
    """What `DeltaStream` asks of a device array."""

    def __init__(self, values, error=None):
        self._values, self._error = values, error

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        if self._error is not None:
            raise self._error
        return self._values


@pytest.mark.parametrize("fails", [False, True], ids=["landed", "failed"])
def test_the_stream_says_once_when_it_holds_no_slice_any_more(fails):
    """`on_end` is the sliced form's release point: called once, on
    the stream's thread, after the last slice has landed or a copy has
    failed, when the thread's frame (the slice asked for last) is gone."""
    import gc
    import weakref

    vec = np.arange(10, dtype=np.float32)
    bounds = [(0, 4), (4, 8), (8, 10)]
    alive, ends = [], []

    def cut(i):
        piece = _Slice(
            vec[slice(*bounds[i])],
            ValueError("lost") if fails and i == 1 else None,
        )
        alive.append(weakref.ref(piece))
        return piece

    def on_end():
        gc.collect()
        ends.append((
            threading.current_thread().name, [r() is not None for r in alive]
        ))

    stream = delta_stream.DeltaStream(
        bounds, (cut(i) for i in range(len(bounds))), on_end=on_end
    )
    vector = stream.vector()
    stream.start()
    stream.settle()
    # (the slice whose copy failed lives on in its error's traceback)
    assert ends == [("delta-stream", [False, fails, False])]
    if fails:
        with pytest.raises(RuntimeError, match="did not land"):
            vector.pieces[2].landed(1)
    else:
        assert np.array_equal(np.asarray(vector), vec)
