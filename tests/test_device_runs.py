"""`worker.device_run`, `seq`, and the records a process keeps of having
been held up itself (PR 54): one span a call of a training program in
every shape of the worker, on the CPU; `proc.stall`, `proc.gc` and
`rpc.server.slow` from clocks and handlers the tests hold."""

import gc
import logging
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from elasticdl_tpu.common import timing
from elasticdl_tpu.common.timing import DEVICE_RUN, DeviceRuns, PhaseTimers
from elasticdl_tpu.master.ps_optimizer import PSOptimizer
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.obs import trace
from elasticdl_tpu.rpc import transport
from elasticdl_tpu.worker import worker as worker_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_recorder():
    trace.configure(0.0)
    trace.RECORDER.clear()
    yield
    trace.configure(None)
    trace.RECORDER.clear()


def _spans(name=None):
    return [s for s in trace.RECORDER.snapshot()
            if name is None or s["name"] == name]


# -- the worker's four shapes ------------------------------------------------

# shape -> (local_updates, overlap_sync, leaves, records): 16 a minibatch
SHAPES = {
    "serial+leaves": (2, "off", True, 128),
    "overlapped+leaves": (2, "on", True, 128),
    "flat carry": (2, "on", False, 128),
    "per-step": (0, "on", False, 128),
    # nine steps: four windows and a tail of one `jit_step`
    "ragged tail": (2, "off", True, 144),
}


def _train(tmp_path, monkeypatch, shape):
    from elasticdl_tpu.api.model_spec_helpers import spec_from_module
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.testing import InProcessMaster, write_linear_records
    from tests.fixtures import linear_module

    local_updates, overlap, leaves, records = SHAPES[shape]
    if leaves:
        monkeypatch.setattr(worker_module, "CARRY_LEAVES_MIN_MEAN_ELEMENTS", 1)
    path = str(tmp_path / "train.rio")
    write_linear_records(path, records, noise=0.05)
    dispatcher = TaskDispatcher({path: records}, {}, {}, records, 1)
    servicer = MasterServicer(
        grads_to_wait=1, optimizer=PSOptimizer(linear_module.optimizer()),
        task_dispatcher=dispatcher,
    )
    worker = worker_module.Worker(
        0, InProcessMaster(servicer), spec_from_module(linear_module),
        minibatch_size=16, local_updates=local_updates, overlap_sync=overlap,
    )
    watched = []
    watch = worker._device_runs.watch
    monkeypatch.setattr(
        worker._device_runs, "watch",
        lambda run, result: watched.append(result) or watch(run, result),
    )
    worker.run()
    worker.close()
    assert dispatcher.finished()
    steps = records // 16
    deadline = time.time() + 10  # the watcher stamps from its own thread
    while time.time() < deadline and sum(
        s["args"]["steps"] for s in _spans(DEVICE_RUN)
    ) < steps:
        time.sleep(0.01)
    return worker, watched


@pytest.mark.parametrize("shape", list(SHAPES))
def test_every_call_of_a_training_program_leaves_one_device_run(
    tmp_path, monkeypatch, shape
):
    local_updates, _overlap, leaves, records = SHAPES[shape]
    worker, watched = _train(tmp_path, monkeypatch, shape)
    runs = sorted(_spans(DEVICE_RUN), key=lambda s: s["args"]["seq"])
    steps = records // 16
    # one a call: the steps they say are the steps trained, a window at
    # a time and the tail a step at a time
    windows = steps // local_updates if local_updates else 0
    assert [r["args"]["steps"] for r in runs] == (
        [local_updates] * windows + [1] * (steps - windows * local_updates)
    )
    assert [r["args"]["program"] for r in runs] == (
        ["jit_window"] * windows + ["jit_step"] * (len(runs) - windows)
    )
    assert [r["args"]["seq"] for r in runs] == list(range(1, len(runs) + 1))
    for r in runs:
        a = r["args"]
        assert a["queued_ms"] >= 0
        assert a["asked"] <= r["ts"] + 1e-6
        assert r["ts"] == pytest.approx(a["asked"] + a["queued_ms"] / 1e3, abs=2e-6)
        assert "bytes_in_use" not in a  # the CPU reports no memory_stats()
    for a, b in zip(runs, runs[1:]):  # no two overlap
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-6
    # where each was stamped: the wait that stands there, or the watcher
    if shape in ("serial+leaves", "ragged tail"):
        waits = {s["args"]["seq"]: s for s in _spans("worker.window_wait")}
        for r in runs[:windows]:
            assert r["args"]["thread"] == "MainThread"
            wait = waits[r["args"]["seq"]]
            assert wait["ts"] <= r["ts"] + r["dur"] <= wait["ts"] + wait["dur"]
            assert wait["args"]["thread"] == "MainThread"
        assert [r["args"]["thread"] for r in runs[windows:]] == (
            ["edl-device-runs"] * (len(runs) - windows)
        )
        assert len(watched) == len(runs) - windows
    elif shape == "per-step":
        assert {r["args"]["thread"] for r in runs} == {"MainThread"}
        ends = {s["ts"] + s["dur"] for s in _spans("worker.delta_wait")}
        for r in runs:  # inside the wait for the step that stands there
            assert min(abs(r["ts"] + r["dur"] - e) for e in ends) < 0.005
        assert not watched and not _spans("worker.window_wait")
    else:
        assert {r["args"]["thread"] for r in runs} == {"edl-device-runs"}
        assert len(watched) == len(runs) and not _spans("worker.window_wait")
    # the watcher is handed the loss alone, never a donated buffer
    assert all(getattr(w, "shape", None) == () for w in watched)
    # rule 5 of the timeline: nothing with a dot enters the seconds the
    # autoscaler reads
    assert not [n for n in worker.timers.snapshot() if "." in n]


@pytest.mark.parametrize("shape", ["serial+leaves", "overlapped+leaves",
                                   "flat carry"])
def test_a_sync_and_every_part_of_it_carry_the_seq_of_its_last_run(
    tmp_path, monkeypatch, shape
):
    _train(tmp_path, monkeypatch, shape)
    seqs = [r["args"]["seq"] for r in _spans(DEVICE_RUN)]
    syncs = _spans("worker.window_sync")
    assert sorted(s["args"]["seq"] for s in syncs) == sorted(seqs)
    for sync in syncs:
        mine = [s for s in _spans() if s["args"].get("seq") == sync["args"]["seq"]
                and s["name"] not in (DEVICE_RUN, "worker.window_wait")]
        names = {s["name"] for s in mine}
        assert {"worker.window_sync", "worker.sync_spawn", "worker.delta_wait",
                "worker.d2h", "worker.flush_reports"} <= names
        assert names <= {"worker.window_sync", "worker.sync_spawn",
                         "worker.chain_wait", "worker.delta_wait", "worker.d2h",
                         "worker.flush_reports", "worker.window_stats"}
        # each part once, and every one of them the sync's own in time
        assert len(mine) == len(names)
        for s in mine:
            assert sync["ts"] - 1e-6 <= s["ts"]
            assert s["ts"] + s["dur"] <= sync["ts"] + sync["dur"] + 1e-6
    # every part of every sync says whose it is
    for name in ("worker.sync_spawn", "worker.chain_wait", "worker.delta_wait",
                 "worker.d2h", "worker.flush_reports"):
        assert all("seq" in s["args"] for s in _spans(name))


# -- DeviceRuns by itself -----------------------------------------------------


def _runs(wait=lambda result: None, memory_stats=None):
    return DeviceRuns(PhaseTimers(sink=trace.record_phase), wait, memory_stats)


def test_a_run_asked_for_while_one_runs_starts_at_that_one_s_end():
    runs = _runs()
    first = runs.asked("jit_window", 8)
    second = runs.asked("jit_window", 8)  # the host is ahead
    time.sleep(0.02)
    runs.ready(first)
    time.sleep(0.01)
    runs.ready(second)
    time.sleep(0.02)  # the device waits for the host
    third = runs.asked("jit_step", 1)
    runs.ready(third)
    a, b, c = _spans(DEVICE_RUN)
    assert a["args"]["queued_ms"] == 0.0 and a["ts"] == a["args"]["asked"]
    assert b["ts"] == pytest.approx(a["ts"] + a["dur"], abs=1e-6)
    assert b["args"]["queued_ms"] >= 19.0
    assert c["args"]["queued_ms"] == 0.0
    assert c["ts"] - (b["ts"] + b["dur"]) >= 0.019  # the gap is the host's
    assert (runs.seq, c["args"]["program"], c["args"]["steps"]) == (3, "jit_step", 1)


def test_the_memory_is_one_reading_right_after_the_call_and_absent_without():
    calls = []

    def memory_stats():
        calls.append(time.time())
        return {"bytes_in_use": 7, "bytes_reserved": 11, "peak_bytes_in_use": 99}

    runs = _runs(memory_stats=memory_stats)
    run = runs.asked("jit_window", 8)
    runs.watch(run, None)  # reads nothing more
    runs.close()
    assert len(calls) == 1 and calls[0] >= run["asked"]
    none = _runs(memory_stats=lambda: None)  # the CPU's answer
    none.ready(none.asked("jit_window", 8))
    deadline = time.time() + 5
    while len(_spans(DEVICE_RUN)) < 2 and time.time() < deadline:
        time.sleep(0.01)
    with_memory, without = sorted(
        _spans(DEVICE_RUN), key=lambda s: "bytes_in_use" not in s["args"]
    )
    assert (with_memory["args"]["bytes_in_use"],
            with_memory["args"]["bytes_reserved"]) == (7, 11)
    assert "peak_bytes_in_use" not in with_memory["args"]
    assert "bytes_in_use" not in without["args"]
    assert "bytes_reserved" not in without["args"]


def test_the_watcher_waits_on_what_it_is_handed_and_lets_it_go():
    released = threading.Event()
    waited = []

    def wait(result):
        waited.append(result)
        if result == "broken":
            raise RuntimeError("deleted")
        released.wait(5)

    others = set(threading.enumerate())  # an earlier test's, on its way out
    runs = _runs(wait, memory_stats=lambda: {"bytes_in_use": 1})
    runs.watch(runs.asked("jit_window", 2), "loss-1")
    (watcher,) = [t for t in set(threading.enumerate()) - others
                  if t.name == "edl-device-runs"]
    runs.watch(runs.asked("jit_window", 2), "broken")
    runs.watch(runs.asked("jit_window", 2), "loss-3")
    assert not _spans(DEVICE_RUN)  # the device is not done
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    timing.logger.addHandler(handler)
    try:
        released.set()
        runs.close()
        deadline = time.time() + 5
        while len(_spans(DEVICE_RUN)) < 2 and time.time() < deadline:
            time.sleep(0.01)
    finally:
        timing.logger.removeHandler(handler)
    assert waited == ["loss-1", "broken", "loss-3"]
    # a result that cannot be waited for costs its own span, no other
    assert [s["args"]["seq"] for s in _spans(DEVICE_RUN)] == [1, 3]
    assert all(s["args"]["thread"] == "edl-device-runs" for s in _spans(DEVICE_RUN))
    assert all(s["args"]["bytes_in_use"] == 1 for s in _spans(DEVICE_RUN))
    assert len(said) == 1 and "no worker.device_run for jit_window 2" in said[0]
    watcher.join(10)  # closed: the thread goes once it has stamped
    assert not watcher.is_alive()


def test_runs_stamped_from_many_threads_never_overlap():
    """The watcher and a caller's own wait stamp side by side: whatever
    order the threads are run in, each span starts at or after the
    last recorded end (a lost update of `_last_end` would overlap two)."""
    out = []  # the ring is bounded; a list takes them all
    runs = DeviceRuns(
        PhaseTimers(sink=lambda name, ts, dur, args, ctx: out.append(
            (ts, ts + dur, args["seq"])
        )),
        lambda result: None,
    )
    begin = threading.Lock()

    def stamp():
        for _ in range(150):
            with begin:  # calls are asked for in one order, as by a step loop
                run = runs.asked("jit_step", 1)
            runs.ready(run)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=stamp) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(before)
    spans = sorted(out)
    assert len(spans) == 16 * 150 == runs.seq
    assert len({seq for _ts, _end, seq in spans}) == len(spans)
    for (_a, end, _s), (ts, _b, _t) in zip(spans, spans[1:]):
        assert end <= ts + 1e-9


def test_timing_still_imports_nothing_of_obs():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import elasticdl_tpu.common.timing; "
         "print([m for m in sys.modules if m.startswith('elasticdl_tpu.obs') "
         "or m == 'jax'])"],
        capture_output=True, text=True, cwd=ROOT, check=True,
    )
    assert out.stdout.strip() == "[]"


# -- a process says when it was held up itself --------------------------------


class _Clock:
    """`time.monotonic` that a test moves."""

    def __init__(self):
        self.now, self.jumps = 100.0, []

    def __call__(self):
        if self.jumps:
            self.now += self.jumps.pop(0)
        return self.now


@pytest.mark.parametrize("late, stalled", [(1.5, True), (0.2, False)])
def test_a_wait_that_timed_out_late_is_a_proc_stall(tmp_path, late, stalled):
    """The span file's loop held by a fake clock: its first wait of
    10 ms comes back `late` seconds after it was due."""
    clock = _Clock()
    recorder = trace.SpanRecorder()
    clock.jumps = [0.0, 0.01 + late]  # the reading before the wait, after
    spans = trace.SpanFile(
        str(tmp_path / "master.spans.jsonl"), period_secs=0.01,
        recorder=recorder, role="master", clock=clock,
    ).start()
    deadline = time.time() + 5
    while clock.jumps and time.time() < deadline:
        time.sleep(0.005)
    time.sleep(0.05)
    spans.stop()
    found = [s for s in trace.load_span_file(spans.path)
             if s["name"] == "proc.stall"]
    assert len(found) == (1 if stalled else 0)
    if stalled:
        (stall,) = found
        assert stall["args"]["late_ms"] == pytest.approx(1500.0, abs=1.0)
        assert stall["args"]["role"] == "master"
        assert stall["args"]["thread"] == "edl-span-file"
        assert stall["dur"] == pytest.approx(1.5, abs=1e-3)
        assert stall["ts"] + stall["dur"] <= time.time()


def test_a_wait_that_pressure_ended_is_no_stall(tmp_path):
    """A burst wakes the loop early: however long the clock says that
    took, the wait did not time out."""
    clock = _Clock()
    recorder = trace.SpanRecorder()
    clock.jumps = [0.0, 30.0]
    recorder.pressure.set()
    spans = trace.SpanFile(
        str(tmp_path / "w.spans.jsonl"), period_secs=5.0, recorder=recorder,
        role="worker-0", clock=clock,
    ).start()
    time.sleep(0.1)
    spans.stop()
    assert not [s for s in trace.load_span_file(spans.path)
                if s["name"] == "proc.stall"]


def test_a_stopped_process_says_so_once_it_runs_again(tmp_path):
    """SIGSTOP for a second, SIGCONT: the process's own span file holds
    a `proc.stall` over the time it did not run."""
    child = textwrap.dedent(f"""
        import sys, time
        sys.path.insert(0, {ROOT!r})
        from elasticdl_tpu.obs import trace
        trace.SpanFile({str(tmp_path / "master.spans.jsonl")!r},
                       period_secs=0.2, role="master").start()
        print("up", flush=True)
        time.sleep(30)
    """)
    proc = subprocess.Popen([sys.executable, "-c", child],
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "up"
        time.sleep(0.3)
        os.kill(proc.pid, signal.SIGSTOP)
        time.sleep(1.2)
        os.kill(proc.pid, signal.SIGCONT)
        time.sleep(0.6)
    finally:
        proc.kill()
        proc.wait()
    stalls = [s for s in trace.load_span_file(str(tmp_path / "master.spans.jsonl"))
              if s["name"] == "proc.stall"]
    assert len(stalls) == 1
    assert 900.0 <= stalls[0]["args"]["late_ms"] <= 1300.0
    assert stalls[0]["args"]["role"] == "master" and stalls[0]["pid"] == proc.pid


def test_a_long_collection_is_a_proc_gc_and_a_short_one_nothing(monkeypatch):
    now = [1000.0]
    monkeypatch.setattr(trace.time, "time", lambda: now[0])
    for took, generation in ((0.2, 2), (0.01, 0)):
        trace._on_gc("start", {"generation": generation})
        now[0] += took
        trace._on_gc("stop", {"generation": generation, "collected": 5,
                              "uncollectable": 0})
    # held where no lock is taken, in the ring once a reader looks
    assert len(trace.RECORDER.held) == 1 and len(trace.RECORDER) == 0
    (span,) = _spans("proc.gc")
    assert not trace.RECORDER.held
    assert span["dur"] == pytest.approx(0.2) and span["ts"] == 1000.0
    assert span["args"]["generation"] == 2 and span["args"]["collected"] == 5
    assert span["args"]["thread"] == threading.current_thread().name
    assert trace.RECORDER.drain() == [span]


def test_a_process_with_a_span_file_watches_its_collections(tmp_path):
    before = list(gc.callbacks)
    try:
        spans = trace.start_span_file(str(tmp_path), "worker-3")
        trace.start_span_file(str(tmp_path), "worker-3").stop()  # once only
        assert gc.callbacks.count(trace._on_gc) == 1
        assert spans._role == "worker-3"
        spans.stop()
        assert trace.start_span_file("", "worker-3") is None
    finally:
        gc.callbacks[:] = before


def _dispatcher(handlers):
    from elasticdl_tpu.rpc.policy import WireStats

    return transport.ServerDispatcher(handlers, WireStats())


def test_a_slow_call_of_any_method_is_recorded_and_a_quick_one_is_not(
    monkeypatch,
):
    from elasticdl_tpu.common import messages

    monkeypatch.setattr(transport, "SLOW_CALL_SECS", 0.05)

    def slow(req):
        time.sleep(0.08)
        return {"ok": True}

    def fails(req):
        time.sleep(0.06)
        raise ValueError("no")

    dispatcher = _dispatcher({
        "GetSchedStats": slow, "ReportPhaseStats": lambda req: {},
        "GetTask": fails,
    })
    frame = messages.pack({"worker_id": 0})
    t0 = time.time()
    dispatcher.dispatch("ReportPhaseStats", frame, transport.TRANSPORT_UDS)
    assert not _spans("rpc.server.slow")
    dispatcher.dispatch("GetSchedStats", frame, transport.TRANSPORT_UDS)
    with pytest.raises(Exception):
        dispatcher.dispatch("GetTask", frame, transport.TRANSPORT_UDS)
    slow_span, failed = _spans("rpc.server.slow")
    assert slow_span["args"]["method"] == "GetSchedStats"
    assert failed["args"]["method"] == "GetTask"
    for s in (slow_span, failed):
        assert s["cat"] == trace.PHASE_CAT and s["ts"] >= t0
        assert s["dur"] > 0.05
        assert 0 <= s["args"]["queued_ms"] < 30.0
        assert s["args"]["handled_ms"] >= 59.0
        assert s["args"]["queued_ms"] + s["args"]["handled_ms"] <= s["dur"] * 1e3 + 0.2


def test_a_call_that_queued_says_how_long_before_its_handler_began(monkeypatch):
    """The loop core admits a call before a thread is free for it: the
    time from admission is `queued_ms`, not the handler's."""
    from elasticdl_tpu.common import messages

    monkeypatch.setattr(transport, "SLOW_CALL_SECS", 0.05)
    dispatcher = _dispatcher({"GetModel": lambda req: {"version": 1}})
    dispatcher._dispatch_blocking(
        "GetModel", messages.pack({}), transport.TRANSPORT_UDS,
        t_admit=time.time() - 0.3,
    )
    (s,) = _spans("rpc.server.slow")
    assert s["args"]["queued_ms"] >= 299.0 and s["args"]["handled_ms"] < 50.0
    assert s["dur"] >= 0.3
