"""Overlap-plane tests (worker double-buffered sync + async absorb).

Three tiers:

1. Gate parity — ``overlap_sync=off`` must restore the serial sync
   chain bit-for-bit: deterministic across runs, and content-identical
   (final version, sync-call count, per-push wire-byte counts) to the
   overlap-on path on the same single-worker fixture.
2. Staged-absorb unit tier — the background page-in's hand-off rules
   pinned directly: monotonic version guard, piggyback-outranks-page-in
   deferral, busy-chain deferral, and the off-gate.
3. Chaos parity — the drop-retry dedup shape from test_chaos.py run at
   the in-process tier over the window path, parametrized over
   ``overlap_sync`` on/off and the f32/int8/topk_int8 wire forms:
   a replayed (same report_key) window report must be absorbed by the
   master's dedup ring so the chaos run lands at EXACTLY the fault-free
   run's final version, both ways.
"""

import random
import threading

import numpy as np
import pytest

from elasticdl_tpu.common.timing import PhaseTimers
from elasticdl_tpu.common import messages
from elasticdl_tpu.master.ps_optimizer import PSOptimizer
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.api.model_spec_helpers import spec_from_module
from elasticdl_tpu.testing import InProcessMaster, write_linear_records
from elasticdl_tpu.worker.worker import Worker

from tests.fixtures import linear_module

SYNC_METHOD = "ReportLocalUpdate"


class ByteCountingMaster(InProcessMaster):
    """Records the packed wire size of every window report — the
    overlap gate must not change what crosses the link, only when."""

    def __init__(self, servicer):
        super().__init__(servicer)
        self.sync_wire_bytes = []

    def call(self, method, request=None):
        if method == SYNC_METHOD:
            self.sync_wire_bytes.append(
                len(messages.pack(request if request is not None else {}))
            )
        return super().call(method, request)


class DropRetryMaster(InProcessMaster):
    """Every Nth window report's response is 'lost': the server APPLIED
    the push, and the worker-side retry resends the SAME report_key —
    the chaos 'drop' fault shape (test_chaos.py) at the in-process
    tier. The dedup ring must absorb every resend."""

    def __init__(self, servicer, every=2):
        super().__init__(servicer)
        self._every = every
        self._n = 0
        self.replayed = 0

    def call(self, method, request=None):
        resp = super().call(method, request)
        if method == SYNC_METHOD:
            self._n += 1
            if self._n % self._every == 0:
                self.replayed += 1
                dup = super().call(method, request)
                assert dup.get("duplicate") is True, (
                    "replayed report_key was re-applied, not deduped"
                )
        return resp


def _run_window_job(
    tmp_path,
    overlap,
    *,
    epochs=4,
    master_cls=ByteCountingMaster,
    sync_dtype=None,
    sync_compress=None,
):
    """One single-worker window-mode job (64 records, minibatch 16,
    records_per_task 32, W=2: exactly one window per task, no ragged
    tails). Seeded shuffle -> identical task order across runs."""
    path = str(tmp_path / "train.rio")
    write_linear_records(path, 64, noise=0.05)
    random.seed(7)
    dispatcher = TaskDispatcher({path: 64}, {}, {}, 32, epochs)
    servicer = MasterServicer(
        grads_to_wait=1,
        optimizer=PSOptimizer(linear_module.optimizer()),
        task_dispatcher=dispatcher,
    )
    master = master_cls(servicer)
    worker = Worker(
        0,
        master,
        spec_from_module(linear_module),
        minibatch_size=16,
        local_updates=2,
        sync_dtype=sync_dtype,
        sync_compress=sync_compress,
        overlap_sync=overlap,
    )
    worker.run()
    assert dispatcher.finished()
    params, _aux, version = servicer.get_params_copy()
    return {
        "params": params,
        "version": version,
        "sync_calls": master.calls.get(SYNC_METHOD, 0),
        "master": master,
        "servicer": servicer,
        "worker": worker,
    }


def test_overlap_off_is_bit_identical_serial_path(tmp_path):
    """The gate's acceptance claim: ``overlap_sync=off`` is the serial
    path — deterministic to the bit across runs, with the overlap
    machinery provably never engaged — and flipping the gate on changes
    NOTHING the PS can see: same final version, same sync-call count,
    same per-push wire-byte counts (64 records x 4 epochs / mb 16 =
    16 steps; W=2 -> 8 window pushes, version 16)."""
    off_a = _run_window_job(tmp_path / "a", "off")
    off_b = _run_window_job(tmp_path / "b", "off")
    on = _run_window_job(tmp_path / "c", "on")

    # off twice: bit-identical params, versions, and wire bytes
    assert off_a["version"] == off_b["version"] == 16
    np.testing.assert_array_equal(
        np.asarray(off_a["params"]["Dense_0"]["kernel"]),
        np.asarray(off_b["params"]["Dense_0"]["kernel"]),
    )
    np.testing.assert_array_equal(
        np.asarray(off_a["params"]["Dense_0"]["bias"]),
        np.asarray(off_b["params"]["Dense_0"]["bias"]),
    )
    assert (
        off_a["master"].sync_wire_bytes == off_b["master"].sync_wire_bytes
    )

    # off vs on: identical content on the wire and on the PS; only the
    # overlap (when work happens) differs
    assert on["version"] == off_a["version"]
    assert on["sync_calls"] == off_a["sync_calls"] == 8
    assert on["master"].sync_wire_bytes == off_a["master"].sync_wire_bytes
    np.testing.assert_allclose(
        np.asarray(on["params"]["Dense_0"]["kernel"]),
        np.asarray(off_a["params"]["Dense_0"]["kernel"]),
        rtol=1e-5,
    )

    # structural: off disarms the whole plane...
    w_off, w_on = off_a["worker"], on["worker"]
    assert w_off._overlap_sync is False
    assert w_off._max_inflight_syncs == 0, "off must force the serial chain"
    assert w_off._bg_pulls == 0 and w_off._staged_applied == 0
    # ...and on arms it (pipelined chain; a single up-to-date worker
    # never NEEDS a background page-in, so none may have started)
    assert w_on._overlap_sync is True
    assert w_on._max_inflight_syncs > 0
    assert w_on._bg_pulls == 0 and w_on._staged_applied == 0


def test_overlap_env_gate_and_bad_value(monkeypatch):
    """EDL_OVERLAP_SYNC drives the default; junk fails loud."""
    from elasticdl_tpu.common.constants import ENV_OVERLAP_SYNC

    spec = spec_from_module(linear_module)
    master = InProcessMaster(
        MasterServicer(
            grads_to_wait=1,
            optimizer=PSOptimizer(linear_module.optimizer()),
            task_dispatcher=TaskDispatcher({}, {}, {}, 1, 1),
        )
    )
    monkeypatch.setenv(ENV_OVERLAP_SYNC, "off")
    w = Worker(0, master, spec, minibatch_size=16, local_updates=2)
    assert w._overlap_sync is False and w._max_inflight_syncs == 0
    monkeypatch.delenv(ENV_OVERLAP_SYNC)
    w = Worker(0, master, spec, minibatch_size=16, local_updates=2)
    assert w._overlap_sync is True  # default on
    with pytest.raises(ValueError, match="overlap_sync"):
        Worker(
            0, master, spec, minibatch_size=16, overlap_sync="sideways"
        )


# -- staged-absorb unit tier --------------------------------------------------


def _staged_worker():
    """Worker skeleton with exactly the overlap-plane state
    (mirrors test_sync_pipeline._bare_worker)."""
    w = Worker.__new__(Worker)
    w.timers = PhaseTimers()
    w._report_lock = threading.Lock()
    w._overlap_sync = True
    w._absorb_staged = None
    w._sync_result = None
    w._sync_thread = None
    w._version = 4
    w._base_version = 4
    w._lineage_version = 4
    w._own_steps_abs = 9
    w._lineage_anchor_abs = 2
    w._shard_versions = None
    w._shard_lineage = None
    w._restore_snap = None
    w._fresh = False
    w._opt_state = object()
    w._staged_applied = 0
    w._bg_pulls = 0
    w._id = 0
    w._applied = []
    w._set_flat = lambda vec, aux: w._applied.append((vec, aux))
    return w


def test_staged_apply_folds_in_and_rebases():
    w = _staged_worker()
    vec = np.arange(8, dtype=np.float32)
    w._absorb_staged = ([7, 9], 7, vec, {"m": 1})
    assert w._apply_staged_model() is True
    assert w._applied and w._applied[0][1] == {"m": 1}
    assert (w._version, w._base_version, w._lineage_version) == (7, 7, 7)
    assert w._lineage_anchor_abs == w._own_steps_abs == 9
    assert w._shard_versions == [7, 9] and w._shard_lineage == [7, 9]
    assert w._restore_snap is not None and w._restore_snap[0] == [7, 9]
    assert w._fresh is True
    assert w._opt_state is None, "params swapped: opt state must rebase"
    assert w._staged_applied == 1
    assert w._absorb_staged is None


def test_staged_apply_monotonic_guard_discards_stale():
    """A page-in that arrived stale (a sync absorbed a newer piggyback
    meanwhile) is DROPPED — same monotonic rule as
    _absorb_report_response."""
    w = _staged_worker()
    w._absorb_staged = (None, 4, np.zeros(4, np.float32), None)  # == cur
    assert w._apply_staged_model() is False
    assert w._absorb_staged is None, "stale page-in must be consumed"
    assert w._applied == [] and w._staged_applied == 0


def test_staged_apply_defers_to_pending_piggyback_and_busy_chain():
    """An unabsorbed sync piggyback outranks the page-in (absorb order
    is what keeps base snapshots coherent), and a live sync chain
    defers the fold — in both cases the staged model SURVIVES for the
    next boundary."""
    w = _staged_worker()
    staged = (None, 9, np.zeros(4, np.float32), None)
    w._absorb_staged = staged
    w._sync_result = (1, np.zeros(4, np.float32), None, 5, None)
    assert w._apply_staged_model() is False
    assert w._absorb_staged is staged, "page-in lost instead of deferred"

    w._sync_result = None
    gate = threading.Event()
    t = threading.Thread(target=gate.wait, daemon=True)
    t.start()
    w._sync_thread = t
    try:
        assert w._apply_staged_model() is False
        assert w._absorb_staged is staged
    finally:
        gate.set()
        t.join()
    # chain settled: now it folds
    w._sync_thread = None
    assert w._apply_staged_model() is True


def test_staged_apply_gate_off_is_inert():
    w = _staged_worker()
    w._overlap_sync = False
    w._absorb_staged = (None, 9, np.zeros(4, np.float32), None)
    assert w._apply_staged_model() is False
    assert w._applied == []


def test_bg_pull_stages_only_newer_and_same_epoch():
    """_maybe_start_bg_pull + _bg_pull_once over the single-master
    GetModel path: an up-to-date worker never pulls; a behind worker
    stages the newer model; a pull spanning an epoch flip (local state
    was reset meanwhile) is DROPPED."""
    w = _staged_worker()
    w._sync_epoch = 0
    w._aux = None
    w._bg_pull_thread = None
    w._use_flat = lambda: True
    w._ensure_ps = lambda: None
    w._model_wire_dtype = lambda: None

    served = np.arange(6, dtype=np.float32)

    class FakeMaster:
        def __init__(self):
            self.calls = 0

        def call(self, method, req):
            assert method == "GetModel" and req["only_if_newer"]
            self.calls += 1
            return {"version": 9, "params_flat": served}

    w._master = FakeMaster()
    w._fresh = True
    w._maybe_start_bg_pull(4)  # fresh at v4, task wants v4: no pull
    assert w._bg_pull_thread is None and w._bg_pulls == 0

    w._maybe_start_bg_pull(8)  # behind: page-in starts
    assert w._bg_pulls == 1
    w._join_bg_pull()
    assert w._master.calls == 1
    assert w._absorb_staged is not None and w._absorb_staged[1] == 9

    # epoch flip between spawn and landing: stale lineage, dropped
    w._absorb_staged = None
    real_lock = w._report_lock

    class FlippingLock:
        def __enter__(self):
            real_lock.acquire()
            w._sync_epoch += 1  # reset raced the pull
            return self

        def __exit__(self, *exc):
            real_lock.release()
            return False

    w2_lock_holder = FlippingLock()
    # flip the epoch AFTER the spawn snapshot but BEFORE staging: run
    # the pull body synchronously with a lock that bumps the epoch
    w._sync_epoch = 0
    spawn_epoch = w._sync_epoch
    w._report_lock = w2_lock_holder
    w._bg_pull_once(None, None, 4, False, spawn_epoch)
    w._report_lock = real_lock
    assert w._absorb_staged is None, "cross-epoch page-in must drop"


# -- chaos parity over the window path ----------------------------------------


@pytest.mark.chaos
@pytest.mark.parametrize("overlap", ["off", "on"])
@pytest.mark.parametrize(
    "wire",
    [
        ("float32", None),
        ("int8", None),
        ("int8", "topk:0.25"),
    ],
    ids=["f32", "int8", "topk_int8"],
)
def test_overlap_chaos_drop_retry_parity(tmp_path, overlap, wire):
    """Drop-retry dedup over the WINDOW path, overlap on and off, per
    wire form: every second window report is applied server-side and
    then resent under the same report_key (the lost-response shape).
    The chaos run must land at EXACTLY the fault-free run's final
    version (64 records x 2 epochs / mb 16 = 8 steps -> version 8),
    with every resend absorbed by the dedup ring."""
    sync_dtype, sync_compress = wire
    chaos = _run_window_job(
        tmp_path / "chaos",
        overlap,
        epochs=2,
        master_cls=DropRetryMaster,
        sync_dtype=sync_dtype,
        sync_compress=sync_compress,
    )
    clean = _run_window_job(
        tmp_path / "clean",
        overlap,
        epochs=2,
        sync_dtype=sync_dtype,
        sync_compress=sync_compress,
    )
    assert chaos["master"].replayed == 2, "drop-retry shape did not fire"
    dup = chaos["servicer"].get_sched_stats({})["duplicate_local_updates"]
    assert dup == 2, "resends must be deduped, not re-applied"
    assert clean["servicer"].get_sched_stats({})[
        "duplicate_local_updates"
    ] == 0
    # exact fault-free final versions, both ways
    assert chaos["version"] == clean["version"] == 8
    # master.calls counts the resends too: originals == clean run
    assert (
        chaos["sync_calls"] - chaos["master"].replayed
        == clean["sync_calls"]
        == 4
    )
    # and the model still converged through the faults (y = 2x + 1)
    kernel = float(
        np.asarray(chaos["params"]["Dense_0"]["kernel"]).ravel()[0]
    )
    assert abs(kernel - 2.0) < 0.6, kernel
