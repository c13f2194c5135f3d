"""Regression tests for the cross-thread races the thread-provenance
lint family surfaced (see analysis/thread_provenance.py): the
aggregator's attach/stats TOCTOU, the KV mirror thread's counter
exactness, the worker's sync-error publish/check handoff, the process
backend's callback swap, and the scenario driver's ps_dead flag. Each
test drives the FIXED behavior; the analysis suite separately proves
the live tree carries no unbaselined findings."""

import threading
import time

import pytest

from elasticdl_tpu.common.timing import PhaseTimers
from elasticdl_tpu.agg.aggregator import AggregatorServicer
from elasticdl_tpu.chaos.scenario import JobRun
from elasticdl_tpu.cluster.pod_backend import ProcessBackend
from elasticdl_tpu.master.kv_shard import KVShardServicer
from elasticdl_tpu.worker.worker import Worker


# -- aggregator: attach_* vs stats() ------------------------------------------


class _FakeWire:
    def snapshot(self):
        return {"bytes_sent": 1, "bytes_received": 2, "transports": {}}


def test_aggregator_attach_visible_in_stats():
    agg = AggregatorServicer(0, [])
    try:
        assert "bytes_sent" not in agg.stats()
        agg.attach_wire_stats(_FakeWire())
        agg.attach_admission_stats(lambda: {"q": 1})
        out = agg.stats()
        assert out["bytes_sent"] == 1 and out["bytes_received"] == 2
        assert out["admission"] == {"q": 1}
    finally:
        agg.close()


def test_aggregator_stats_never_tears_mid_attach():
    """Pre-fix, stats() re-read self._wire after its None check: an
    attacher swapping the reference back to None in that window raised
    AttributeError. The snapshot-under-lock contract means every
    stats() sees wire fields either fully present or fully absent."""
    agg = AggregatorServicer(0, [])
    stop = threading.Event()
    errors = []

    def attacher():
        wire = _FakeWire()
        while not stop.is_set():
            agg.attach_wire_stats(wire)
            agg.attach_admission_stats(lambda: {"q": 1})
            agg.attach_wire_stats(None)
            agg.attach_admission_stats(None)

    def reader():
        try:
            while not stop.is_set():
                out = agg.stats()
                assert ("bytes_sent" in out) == ("bytes_received" in out)
        except Exception as e:  # pragma: no cover - the regression
            errors.append(e)

    threads = [threading.Thread(target=attacher)] + [
        threading.Thread(target=reader) for _ in range(2)
    ]
    try:
        for t in threads:
            t.start()
        time.sleep(0.3)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
        agg.close()
    assert not errors


# -- KV shard: mirror-thread counters -----------------------------------------


class _FlakyMirrorClient:
    """Stands in for RpcClient on the mirror thread: every other
    forward fails, so both counters advance."""

    calls = 0

    def __init__(self, endpoint):
        self._endpoint = endpoint

    def call(self, method, req, timeout=None):
        type(self).calls += 1
        if type(self).calls % 2 == 0:
            raise RuntimeError("mirror target down")
        return {}

    def close(self):
        pass


def test_kv_mirror_counters_account_every_forward(monkeypatch):
    """mirrored_writes + mirror_drops equals the number of enqueued
    forwards exactly — the counters ride _mirror_lock, so a stats()
    racing the mirror thread can never read a torn tally."""
    monkeypatch.setattr(
        "elasticdl_tpu.rpc.client.RpcClient", _FlakyMirrorClient
    )
    _FlakyMirrorClient.calls = 0
    kv = KVShardServicer(0, 1)
    try:
        kv.kv_set_mirror({"endpoint": "fake://mirror"})
        n = 40
        for i in range(n):
            kv.kv_update(
                {"layer": "emb", "ids": [i], "values": [[float(i)]]}
            )
        assert kv.mirror_flush(timeout=10.0)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            s = kv.stats()
            if s["mirrored_writes"] + s["mirror_drops"] == n:
                break
            time.sleep(0.01)
        s = kv.stats()
        assert s["mirrored_writes"] + s["mirror_drops"] == n
        assert s["mirrored_writes"] == n // 2
        assert s["mirror_drops"] == n // 2
    finally:
        kv.close()


# -- worker: sync-error publish / check handoff -------------------------------


def _bare_worker():
    w = Worker.__new__(Worker)
    w.timers = PhaseTimers()
    w._report_lock = threading.Lock()
    w._sync_error = None
    w._flushed = []
    w._flush_deferred_reports = lambda err=None: w._flushed.append(err)
    w._reset_local_state = lambda: None
    return w


def test_worker_check_sync_error_reads_and_clears_atomically():
    w = _bare_worker()
    w._check_sync_error()  # no error: no-op
    boom = ValueError("boom")
    with w._report_lock:  # publish exactly as thread_main does
        w._sync_error = boom
    with pytest.raises(RuntimeError, match="sync failed") as ei:
        w._check_sync_error()
    assert ei.value.__cause__ is boom
    assert w._sync_error is None  # consumed
    assert len(w._flushed) == 1
    w._check_sync_error()  # and cleared: second check is a no-op
    assert len(w._flushed) == 1


def test_worker_sync_error_handoff_loses_nothing():
    """Publisher thread posts N errors, each waiting for the previous
    to be consumed; the checker must surface every one exactly once.
    Pre-fix, the bare read-then-clear could drop a publish landing
    between the two steps."""
    w = _bare_worker()
    n = 200

    def publisher():
        for i in range(n):
            while True:
                with w._report_lock:
                    if w._sync_error is None:
                        w._sync_error = ValueError(f"e{i}")
                        break
                time.sleep(0)

    t = threading.Thread(target=publisher)
    t.start()
    caught = 0
    deadline = time.monotonic() + 30.0
    while caught < n and time.monotonic() < deadline:
        try:
            w._check_sync_error()
        except RuntimeError:
            caught += 1
    t.join(timeout=5)
    assert caught == n
    assert len(w._flushed) == n


# -- process backend: callback swap under the monitor thread ------------------


def test_process_backend_callback_swap_is_locked():
    """set_event_callback publishes under the backend lock while the
    monitor thread (running since __init__) reads per event: swapping
    callbacks from several threads must neither deadlock nor race the
    monitor's snapshot."""
    be = ProcessBackend(poll_interval=0.01)
    stop = threading.Event()

    def swapper():
        while not stop.is_set():
            be.set_event_callback(lambda ev: None)
            be.set_event_callback(None)

    threads = [threading.Thread(target=swapper) for _ in range(2)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.2)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
        be.stop()


# -- chaos scenario: the ps_dead flag -----------------------------------------


def test_jobrun_ps_dead_is_an_event():
    """The unrecoverable-PS flag crosses from the recovery plane's
    monitor thread to the scenario driver loop: it must be a
    threading.Event (a real happens-before edge), not a bare bool."""
    run = JobRun(spec=None, run_dir="", worker_env={})
    assert isinstance(run.ps_dead, threading.Event)
    assert not run.ps_dead.is_set()
    t = threading.Thread(target=run.ps_dead.set)  # monitor-thread side
    t.start()
    assert run.ps_dead.wait(timeout=5)  # driver-loop side
    t.join(timeout=5)
