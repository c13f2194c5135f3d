"""White-box tests for the worker's chained async delta-sync pipeline.

The pipeline (worker.py `_sync_local_updates` / `_absorb_sync_result`)
lets up to two window deltas ride the host<->device link while the
device trains ahead. Two invariants are easy to break and hard to see
in an e2e run, so they are pinned here directly:

1. **No double-merge.** Absorbing the piggybacked merged model of sync
   i applies shift_i = merged_i - snapshot_i. The still-pending younger
   snapshot_{i+1} was recorded BEFORE that absorb, so it must be
   shifted too — otherwise absorbing sync i+1 re-applies shift_i and
   other workers' progress lands twice (divergence in exactly the
   multi-worker case local-update mode exists for).
2. **No premature success report.** A task's deferred result may only
   flush once its COVERING sync (the one carrying the task's last
   delta) has landed on the PS; an older sync landing must not flush
   it. On a broken chain every entry flushes — covered ones with their
   own result, uncovered ones as failures so the dispatcher requeues.
"""

import threading

import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.common.timing import PhaseTimers
from elasticdl_tpu.worker.worker import Worker


def _bare_worker():
    """A Worker skeleton with just the sync-pipeline state (no master,
    no model): exactly the fields the pipeline methods touch."""
    w = Worker.__new__(Worker)
    w.timers = PhaseTimers()
    w._report_lock = threading.Lock()
    w._base_snapshots = {}
    w._sync_result = None
    w._sync_error = None
    w._sync_seq = 0
    w._synced_seq = 0
    w._sync_epoch = 0
    w._pending_steps = 0
    w._deferred_reports = []
    w._flushed_report_ids = set()
    w._aux = None
    w._id = 0
    w._lineage_version = -1
    w._shard_lineage = None
    w._own_steps_abs = 0
    w._lineage_anchor_abs = 0
    w._spawn_abs = {}
    return w


def test_absorb_shifts_younger_snapshots_no_double_merge():
    w = _bare_worker()
    # local trajectory: base snapshots at spawn of syncs 1 and 2
    snap1 = jnp.asarray(np.array([10.0, 20.0], np.float32))
    delta2 = jnp.asarray(np.array([1.0, 1.0], np.float32))
    snap2 = snap1 + delta2
    w._base_snapshots = {1: snap1, 2: snap2}
    w._flat = snap2
    w._base_flat = snap2

    # sync 1's piggyback: other workers contributed shift1
    shift1 = np.array([0.5, -0.5], np.float32)
    w._sync_result = (1, np.asarray(snap1) + shift1, None, 5, None)
    w._absorb_sync_result()
    np.testing.assert_allclose(np.asarray(w._flat), np.asarray(snap2) + shift1)

    # sync 2's piggyback: PS now reflects snap2 + shift1 + others_new
    others_new = np.array([0.25, 0.25], np.float32)
    w._sync_result = (2, np.asarray(snap2) + shift1 + others_new, None, 7, None)
    w._absorb_sync_result()
    # shift1 must be applied ONCE, others_new once
    np.testing.assert_allclose(
        np.asarray(w._flat), np.asarray(snap2) + shift1 + others_new
    )
    np.testing.assert_allclose(
        np.asarray(w._base_flat), np.asarray(snap2) + shift1 + others_new
    )
    assert not w._base_snapshots


class _RecordingMaster:
    def __init__(self):
        self.calls = []

    def call(self, method, req):
        self.calls.append((method, req))
        return {}


def test_deferred_report_waits_for_covering_sync():
    w = _bare_worker()
    w._master = _RecordingMaster()
    # task ends with a ragged tail: 3 unsynced steps -> covering sync
    # is the NEXT spawn (seq 2); sync 1 is still in flight
    w._sync_seq = 1
    w._synced_seq = 0
    w._pending_steps = 3
    w._defer_report(7, "")
    assert w._deferred_reports == [(7, "", 2)]

    # sync 1 lands and flushes: task 7's tail is still in flight
    w._synced_seq = 1
    w._flush_deferred_reports()
    assert w._master.calls == []
    assert w._deferred_reports, "entry must survive an older sync's flush"

    # covering sync 2 lands: now it reports success
    w._synced_seq = 2
    w._flush_deferred_reports()
    assert [
        (m, r["task_id"], r["err_message"]) for m, r in w._master.calls
    ] == [("ReportTaskResult", 7, "")]
    assert 7 in w._flushed_report_ids


def test_broken_chain_flushes_covered_ok_uncovered_failed():
    w = _bare_worker()
    w._master = _RecordingMaster()
    w._sync_seq = 2
    w._synced_seq = 1
    w._deferred_reports = [(3, "", 1), (4, "", 2)]  # 3 covered, 4 not
    w._flush_deferred_reports(err="sync failed: boom")
    results = {r["task_id"]: r["err_message"] for _, r in w._master.calls}
    assert results[3] == ""  # data landed: success stands
    assert results[4] == "sync failed: boom"  # requeue the lost shard


def test_exact_window_task_covered_by_last_spawned_sync():
    w = _bare_worker()
    w._master = _RecordingMaster()
    # task ended exactly on a window boundary: pending_steps == 0, the
    # already-spawned sync 5 carries everything
    w._sync_seq = 5
    w._synced_seq = 4
    w._pending_steps = 0
    w._defer_report(9, "")
    assert w._deferred_reports == [(9, "", 5)]
    w._flush_deferred_reports()
    assert w._master.calls == []
    w._synced_seq = 5
    w._flush_deferred_reports()
    assert w._master.calls[0][1]["task_id"] == 9
