"""The serial chain lets the step loop go before its sync is over
(PR 57), and since PR 59 before the sync has copied anything where the
delta leaves in slices: the device forms no delta there, the sync's
thread copies the next window's base out beside that window and the
host subtracts its copy of the base before (`released: "snapshot"`). A
delta in one copy is formed on the device and holds the step loop
until it has left and is deleted (`released: "copied"`). The rest (the
send, the master's apply, the answer, the reports) runs behind the next
window either way. A tiny window job against an in-process master whose
`ReportLocalUpdate` is held for a moment, the delta in slices (the
slice lowered to four floats, as `test_delta_stream.py` does) and in
one copy."""

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
# where the step loop goes on alone, by the delta's form
EARLY = {"sliced": ("snapshot", None), "whole": ("copied", None)}


def _releases(**counts):
    return {"snapshot": 0, "copied": 0, "settled": 0, **counts}


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
         records_per_task=64, chain="off", slice_bytes=16, **worker_kw):
    """-> (worker, master, servicer, spans) of one finished job;
    `before(servicer)` gives `_HeldMaster` its hook."""
    monkeypatch.setattr(
        delta_stream, "DELTA_SLICE_BYTES",
        slice_bytes if form == "sliced" else 1 << 20,
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


def _parts(spans, name, sync):
    return [
        s for s in _named(spans, name) if s["args"]["seq"] == sync["args"]["seq"]
    ]


def _check_the_waits(spans, after_spawn, for_the_program=0):
    """The wait before the next delta found the sync settled or waited
    for it under a reason of its own; `after_spawn` syncs held the step
    loop after their spawn for what they read from the device,
    `for_the_program` only until the device had made the snapshot, and
    all of it lies inside `sync_wait`."""
    reasons = [s["args"]["reason"] for s in _named(spans, "worker.sync_exposed")]
    assert reasons.count("settle") == SYNCS - 2  # the last one: the drain
    assert reasons.count("backpressure") == after_spawn and "drain" in reasons
    assert reasons.count("snapshot") == for_the_program
    phases = _named(spans, "sync_wait")
    for s in _named(spans, "worker.sync_exposed"):
        if s["args"]["reason"] in ("settle", "backpressure", "snapshot"):
            assert any(
                p["ts"] <= s["ts"] + 1e-6 and _end(s) <= _end(p) + 1e-6
                for p in phases
            )


@pytest.mark.parametrize("carry", ["flat", "leaves"])
def test_the_step_loop_goes_on_before_any_slice_has_landed(
    tmp_path, monkeypatch, carry
):
    """The sliced form: the spawn asks for the snapshot's program and
    nothing else, the next window is asked for while the stream's
    thread has not begun, and the device never holds a delta or a
    second base. Where the window's loop carries leaves, the cut of
    the model's vector waits until the device has made the snapshot
    (so that what the join and the snapshot read is free by then)."""
    from elasticdl_tpu.worker import worker as worker_module

    if carry == "leaves":
        monkeypatch.setattr(worker_module, "CARRY_LEAVES_MIN_MEAN_ELEMENTS", 1)
    let_go, workers = [], []
    real_base, real_copy = Worker._base_in_slices, delta_stream.DeltaStream._copy

    def base_in_slices(self, bounds):
        workers.append(self)
        # the worker holds no base when the program that makes the next
        # one is asked for
        let_go.append(self._base_flat is None)
        return real_base(self, bounds)

    def copy(stream):
        # a copy that starts late: not before the next window has been
        # asked for (the last sync of a job has none after it)
        runs = workers[0]._device_runs
        seq, deadline = runs.seq, time.monotonic() + 0.5
        while runs.seq == seq and time.monotonic() < deadline:
            time.sleep(0.001)
        real_copy(stream)

    def no_delta_on_the_device(self, *_a):
        raise AssertionError("the serial chain formed a delta on the device")

    monkeypatch.setattr(Worker, "_base_in_slices", base_in_slices)
    monkeypatch.setattr(delta_stream.DeltaStream, "_copy", copy)
    monkeypatch.setattr(Worker, "_delta_from_base", no_delta_on_the_device)
    monkeypatch.setattr(Worker, "_delta_in_slices", no_delta_on_the_device)
    worker, _master, servicer, spans = _job(tmp_path, monkeypatch, "sliced")
    assert servicer._version == STEPS
    syncs = _named(spans, "worker.window_sync")
    runs = {s["args"]["seq"]: s for s in _named(spans, "worker.device_run")}
    assert len(syncs) == SYNCS and len(runs) == SYNCS
    assert _released(spans) == [("settled", "first")] + [("snapshot", None)] * (
        SYNCS - 1
    )
    assert worker.sync_releases == _releases(snapshot=SYNCS - 1, settled=1)
    assert [
        (s["args"].get("released"), s["args"].get("why"))
        for s in _named(spans, "worker.sync_spawn")
    ] == _released(spans)
    first, later = syncs[0], syncs[1:-1]  # the last has no window after it
    assert runs[first["args"]["seq"] + 1]["args"]["asked"] >= _end(first)
    # the first sync found no base on the host and copied the device's
    # out before the snapshot; no later one did
    assert [s["args"]["seq"] for s in _named(spans, "worker.base_d2h")] == [
        first["args"]["seq"]
    ]
    for sync in later:
        after = runs[sync["args"]["seq"] + 1]
        assert after["args"]["asked"] < _end(sync)
        (trip,) = _parts(spans, "worker.d2h", sync)
        (less,) = _parts(spans, "worker.host_delta", sync)
        # no slice had been asked for, let alone landed, when the next
        # window was; all of them landed inside the sync, and the host
        # subtracted inside it too
        assert after["args"]["asked"] <= trip["ts"]
        assert trip["args"]["slices"] == 3 and _end(trip) <= _end(sync) + 1e-6
        assert trip["ts"] <= less["ts"] and _end(less) <= _end(sync) + 1e-6
        assert less["args"]["busy_ms"] >= 0
    # (the rebase, then every sync)
    assert let_go == [True] * (1 + SYNCS)
    programs = [s["args"]["program"] for s in _named(spans, "setup.program")]
    assert programs.count("jit_snapshot") == 1
    assert not {"jit_subtract", "jit_copy", "jit_delta_slice"} & set(programs)
    # no sync but the first held the step loop after its spawn; every
    # window's cut found its base made, or waited for that alone
    _check_the_waits(
        spans, after_spawn=1, for_the_program=SYNCS * (carry == "leaves")
    )


def test_the_step_loop_goes_on_when_a_whole_delta_has_left_the_device(
    tmp_path, monkeypatch
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
    worker, _master, servicer, spans = _job(tmp_path, monkeypatch, "whole")
    assert servicer._version == STEPS
    syncs = _named(spans, "worker.window_sync")
    runs = {s["args"]["seq"]: s for s in _named(spans, "worker.device_run")}
    assert len(syncs) == SYNCS and len(runs) == SYNCS
    # a worker's first sync is waited for whole; every later one lets
    # the step loop go at its release point
    assert _released(spans) == [("settled", "first")] + [("copied", None)] * (
        SYNCS - 1
    )
    assert worker.sync_releases == _releases(copied=SYNCS - 1, settled=1)
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
        (trip,) = _parts(spans, "worker.d2h", sync)
        assert _end(trip) <= after["args"]["asked"]
        assert trip["args"]["slices"] == 1
    # nothing of a delta is on the device when the step loop goes on,
    # and no snapshot holds the base: every subtraction donates it
    assert freed == [True] * SYNCS and donated == [True] * SYNCS
    assert not _named(spans, "worker.host_delta")
    assert not _named(spans, "worker.base_d2h")
    _check_the_waits(spans, after_spawn=SYNCS)


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
    assert whole[0].sync_releases == _releases(settled=SYNCS)
    assert early[0].sync_releases[EARLY[form][0]] == SYNCS - 1
    assert _model_bytes(early[2]) == _model_bytes(whole[2])
    assert early[2]._version == STEPS
    assert early[1].calls[SYNC] == whole[1].calls[SYNC] == SYNCS
    assert early[1].pushes == whole[1].pushes
    # every task reported done once, after the sync that covers it
    for _w, master, _s, _spans in (whole, early):
        assert [err for _, err, _ in master.reports] == [""] * (RECORDS // 64)
        assert [v for _, _, v in master.reports] == [4, 8, 12]


@pytest.mark.parametrize(
    "slice_bytes", [16, 20, 4], ids=["short_tail", "equal_slices", "a_float"]
)
def test_the_hosts_subtraction_is_the_devices_bit_for_bit(
    tmp_path, monkeypatch, slice_bytes
):
    """What reaches the master over the job's windows, delta by delta:
    a landed snapshot less the host's base (ten parameters in slices of
    4, 4, 2 | 5, 5 | one float each) against `flat - base` on the
    device, fetched whole."""
    device = _job(tmp_path / "d", monkeypatch, "whole")
    host = _job(tmp_path / "h", monkeypatch, "sliced", slice_bytes=slice_bytes)
    assert not _named(device[3], "worker.host_delta")
    assert len(_named(host[3], "worker.host_delta")) == SYNCS
    assert {s["args"]["slices"] for s in _named(host[3], "worker.d2h")} == {
        -(-40 // slice_bytes)
    }
    assert host[1].pushes == device[1].pushes and len(host[1].pushes) == SYNCS
    assert _model_bytes(host[2]) == _model_bytes(device[2])
    assert host[2]._version == STEPS


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
        ("settled", "first"), EARLY[form], EARLY[form],
        ("settled", "merged"), EARLY[form], EARLY[form],
    ]
    absorbs = _named(serial[3], "worker.absorb")
    syncs = _named(serial[3], "worker.window_sync")
    runs = {s["args"]["seq"]: s for s in _named(serial[3], "worker.device_run")}
    # one absorb, after window 4 had been asked for and before sync 4
    assert len(absorbs) == 1
    assert runs[4]["args"]["asked"] < absorbs[0]["ts"] < syncs[3]["ts"]
    # the absorb shifted the device's base, so the host's copy was
    # dropped: the held sync that follows copies the shifted one out
    assert [s["args"]["seq"] for s in _named(serial[3], "worker.base_d2h")] == (
        [syncs[0]["args"]["seq"], syncs[3]["args"]["seq"]]
        if form == "sliced" else []
    )

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
    assert worker.sync_releases == _releases(settled=len(released))


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
        ("settled", "first"), EARLY[form], EARLY[form], ("settled", "first"),
    ]
    assert set(released[4:]) == {EARLY[form]}
    assert worker.sync_releases == _releases(
        settled=2, **{EARLY[form][0]: SYNCS - 1}
    )


class _Lost:
    """A slice of a snapshot whose copy is lost once the step loop has
    asked its device for the next window."""

    shape, dtype = (4,), np.dtype(np.float32)

    def __init__(self, runs):
        self._runs, self._seq = runs, runs.seq

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        deadline = time.monotonic() + 5
        while self._runs.seq == self._seq and time.monotonic() < deadline:
            time.sleep(0.001)
        raise RuntimeError("the device lost slice 1")


def test_a_slice_that_fails_to_land_after_the_step_loop_went_on(
    tmp_path, monkeypatch
):
    """Sync 3's second slice never lands, and the step loop has gone
    on by then: the sync fails at the settle before delta 4, window 4
    is thrown away, the tasks behind sync 3 go unreported and back to
    the dispatcher, the host's base goes with the device's vector, and
    the sync after the reset copies its base out again."""
    real, calls = Worker._base_in_slices, []

    def base_in_slices(self, bounds):
        base = real(self, bounds)
        calls.append(self._pending_steps)
        if len(calls) == 4:  # the rebase, syncs 1 and 2, then sync 3
            base = (base[0], _Lost(self._device_runs), base[2])
        return base

    monkeypatch.setattr(Worker, "_base_in_slices", base_in_slices)
    worker, master, servicer, spans = _job(
        tmp_path, monkeypatch, "sliced", records_per_task=32,
    )
    assert servicer._version == STEPS
    failed = [(t, err) for t, err, _ in master.reports if err]
    assert len(failed) == 2 and len({t for t, _ in failed}) == 2
    assert any("did not land" in err for _, err in failed)
    done = [(t, v) for t, err, v in master.reports if not err]
    assert [v for _, v in done] == [WINDOW * k for k in range(1, SYNCS + 1)]
    released = _released(spans)
    assert released[:4] == [
        ("settled", "first"), ("snapshot", None), ("snapshot", None),
        ("settled", "first"),
    ]
    assert set(released[4:]) == {("snapshot", None)}
    # the failed sync went on alone and was found failed at the settle
    # (the step loop had asked for window 4 by then)
    syncs = _named(spans, "worker.window_sync")
    runs = {s["args"]["seq"]: s for s in _named(spans, "worker.device_run")}
    assert runs[syncs[2]["args"]["seq"] + 1]["args"]["asked"] >= syncs[2]["ts"]
    # two rebases (calls 1 and 5), and the sync that follows each finds
    # no base on the host and copies the device's out
    assert calls[0] == calls[4] == 0 and all(calls[1:4]) and all(calls[5:])
    assert [s["args"]["seq"] for s in _named(spans, "worker.base_d2h")] == [
        syncs[0]["args"]["seq"], syncs[3]["args"]["seq"]
    ]
    assert worker._host_base is not None  # the last sync's, landed
    assert worker.sync_releases == _releases(snapshot=SYNCS - 1, settled=2)


def _at_a_boundary(monkeypatch, boundary, act):
    """`act(worker)` before the `boundary`-th window is made ready."""
    real, windows = Worker._ensure_local_ready, []

    def ensure_local_ready(self, features, task):
        if self._pending_steps == 0:
            windows.append(1)
            if len(windows) == boundary:
                act(self)
        return real(self, features, task)

    monkeypatch.setattr(Worker, "_ensure_local_ready", ensure_local_ready)


def _shard_recovery(worker):
    worker._join_sync()
    worker._reset_local_state()  # what `_await_shard_recovery` ends in


def _stale(worker):
    worker._join_sync()
    with worker._report_lock:
        worker._fresh = False  # the model is pulled again at the boundary


@pytest.mark.parametrize("act, why", [
    (_shard_recovery, "first"), (_stale, "base"),
], ids=["reset", "pull"])
def test_a_vector_that_is_replaced_takes_the_hosts_base_with_it(
    tmp_path, monkeypatch, act, why
):
    """Before window 4 the device's vector is replaced (a failover's
    reset; a pull at the boundary): the rebase makes a new base on the
    device and drops the host's, so sync 4 copies its base out first
    and is waited for whole, and the master ends with the model the
    device-side subtraction gives under the same disturbance."""
    _at_a_boundary(monkeypatch, 4, act)
    host = _job(tmp_path / "h", monkeypatch, "sliced")
    _at_a_boundary(monkeypatch, 4, act)
    device = _job(tmp_path / "d", monkeypatch, "whole")
    assert _released(host[3]) == [
        ("settled", "first"), ("snapshot", None), ("snapshot", None),
        ("settled", why), ("snapshot", None), ("snapshot", None),
    ]
    syncs = _named(host[3], "worker.window_sync")
    assert [s["args"]["seq"] for s in _named(host[3], "worker.base_d2h")] == [
        syncs[0]["args"]["seq"], syncs[3]["args"]["seq"]
    ]
    assert host[1].pushes == device[1].pushes
    assert _model_bytes(host[2]) == _model_bytes(device[2])
    assert host[2]._version == device[2]._version == STEPS


@FORMS
def test_a_drain_waits_for_the_whole_sync(tmp_path, monkeypatch, form):
    """`_finalize_local_updates` pushes what is pending with
    `blocking=True`: that sync runs on the step loop's own thread."""
    real, calls = Worker._sync_local_updates, []

    def sync_local_updates(self, blocking=True):
        if self._pending_steps:
            calls.append(blocking)
        real(self, blocking or (len(calls) == 3 and bool(self._pending_steps)))

    monkeypatch.setattr(Worker, "_sync_local_updates", sync_local_updates)
    worker, _master, servicer, spans = _job(tmp_path, monkeypatch, form)
    assert servicer._version == STEPS
    released = _released(spans)
    assert released[:4] == [
        ("settled", "first"), EARLY[form], ("settled", "drain"), EARLY[form],
    ]
    drained = _named(spans, "worker.window_sync")[2]
    assert drained["tid"] == _named(spans, "worker.sync_spawn")[2]["tid"]
    assert worker.sync_releases == _releases(
        settled=2, **{EARLY[form][0]: SYNCS - 2}
    )
