"""The phase timeline (common/timing.py + obs/trace.py): `PhaseTimers`
keeps times as well as seconds, every process drains its recorder to a
JSON-lines file as it goes, and the master has phases of its own."""

import json
import logging
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import optax
import pytest

from elasticdl_tpu.common import timing
from elasticdl_tpu.common.constants import MASTER_UPDATE_METHODS
from elasticdl_tpu.common.messages import MethodType
from elasticdl_tpu.common.timing import PhaseTimers
from elasticdl_tpu.master.ps_optimizer import PSOptimizer
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.obs import trace
from elasticdl_tpu.obs.__main__ import main as obs_main
from elasticdl_tpu.rpc.client import RpcClient
from elasticdl_tpu.rpc.server import RpcServer
from elasticdl_tpu.sched import PhaseStatsAggregator, merge_phase_snapshots


@pytest.fixture(autouse=True)
def _clean_recorder():
    trace.configure(0.0)  # the timeline must not need sampling
    trace.RECORDER.clear()
    yield
    trace.RECORDER.clear()
    trace.configure(None)


def _spans(name=None):
    spans = trace.RECORDER.snapshot()
    return [s for s in spans if name is None or s["name"] == name]


# -- the instrument ----------------------------------------------------------


def test_span_bounds_enclose_the_work_and_nest_as_phases_do():
    t = PhaseTimers(sink=trace.record_phase)
    before = time.time()
    with t.phase("outer", task=7):
        with t.phase("inner") as info:
            time.sleep(0.02)
            info["bytes"] = 12
    after = time.time()
    (outer,), (inner,) = _spans("outer"), _spans("inner")
    for s in (outer, inner):
        assert s["cat"] == trace.PHASE_CAT and "trace_id" not in s
        assert s["pid"] == os.getpid()
        assert s["tid"] == threading.get_ident()
        assert s["args"]["thread"] == threading.current_thread().name
        assert before <= s["ts"] and s["ts"] + s["dur"] <= after + 1e-3
    assert outer["args"]["task"] == 7 and inner["args"]["bytes"] == 12
    assert inner["dur"] >= 0.02 - 1e-3
    # inclusive spans: the inner lies inside the outer
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-4


@pytest.mark.parametrize("how", ["span", "record_span"])
def test_exclusive_seconds_unchanged_by_timeline_only_spans(how):
    """`span()` / `record_span()` write the timeline and leave the
    exclusive accounting the autoscaler reads exactly as it was."""
    t = PhaseTimers(sink=trace.record_phase)
    with t.phase("sync_wait"):
        if how == "span":
            with t.span("worker.sync_exposed", reason="join"):
                time.sleep(0.02)
        else:
            t0 = time.time()
            time.sleep(0.02)
            t.record_span("worker.window_sync", t0, time.time(), steps=8)
    snap = t.snapshot()
    assert set(snap) == {"sync_wait"}
    assert snap["sync_wait"]["seconds"] >= 0.02 - 1e-3  # nothing subtracted
    names = {s["name"] for s in _spans()}
    assert names == {"sync_wait", "worker.sync_exposed"} or names == {
        "sync_wait", "worker.window_sync"
    }


def test_record_is_a_phase_timed_by_hand():
    t = PhaseTimers(sink=trace.record_phase)
    t.record("apply", 100.0, 100.25, kind="gradient", version=3)
    assert t.snapshot()["apply"] == {"seconds": 0.25, "count": 1}
    (s,) = _spans("apply")
    assert (s["ts"], s["dur"]) == (100.0, 0.25)
    assert s["args"]["kind"] == "gradient" and s["args"]["version"] == 3


def test_timeline_is_on_whatever_the_sample_rate_and_chrome_exportable():
    t = PhaseTimers(sink=trace.record_phase)
    with t.phase("compute", steps=8):
        pass
    assert not trace.enabled()
    doc = trace.chrome_trace()
    (event,) = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert event["name"] == "compute" and event["args"]["steps"] == 8
    assert "trace_id" not in event["args"]
    (meta,) = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta["name"] == "thread_name" and meta["tid"] == event["tid"]


def test_slow_phase_logs_one_warning_line(monkeypatch):
    monkeypatch.setattr(timing, "SLOW_SECS", 0.05)
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    timing.logger.addHandler(handler)
    try:
        t = PhaseTimers(sink=trace.record_phase)
        for _ in range(6):
            with t.phase("get_batch"):
                time.sleep(0.002)
        assert not seen  # usual instances say nothing
        with t.phase("get_batch"):
            time.sleep(0.08)
    finally:
        timing.logger.removeHandler(handler)
    assert len(seen) == 1
    assert "get_batch" in seen[0] and "MainThread" in seen[0]


@pytest.mark.perf
def test_phase_call_is_cheap():
    """The timeline is on by default, so `phase()` is on the hot path:
    one `time.time()`, one striped append. The bound is loose (CI is
    noisy); a regression that adds I/O or a global lock lands far
    above it."""
    t = PhaseTimers(sink=trace.record_phase)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with t.phase("x"):
            pass
    cost = (time.perf_counter() - t0) / n
    assert cost < 50e-6, f"PhaseTimers.phase() {cost * 1e6:.1f}us a call"
    # the ring stayed bounded
    assert len(trace.RECORDER) <= trace._DEFAULT_CAPACITY


def test_process_start_time_is_before_now_and_recent():
    started = timing.process_start_time()
    assert started <= time.time()
    assert time.time() - started < 3600


# -- the way out -------------------------------------------------------------


def test_drain_returns_each_span_once_under_concurrent_writers():
    rec = trace.SpanRecorder(capacity=64 * 8, stripes=8)
    per_thread, writers = 2000, 6
    seen, stop = [], threading.Event()

    def write(k):
        for i in range(per_thread):
            rec.record({"name": f"w{k}", "ts": float(i), "dur": 0.0, "i": i})

    def drain():
        while not stop.is_set():
            seen.extend(rec.drain())
        seen.extend(rec.drain())

    drainer = threading.Thread(target=drain)
    threads = [threading.Thread(target=write, args=(k,)) for k in range(writers)]
    drainer.start()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    stop.set()
    drainer.join()
    keys = [(s["name"], s["i"]) for s in seen]
    assert len(keys) == len(set(keys))  # none twice
    assert len(keys) + rec.dropped >= per_thread * writers - 64 * 8
    assert len(rec) <= 64 * 8  # the ring stayed bounded
    assert rec.drain() == []  # and nothing is handed out again
    assert len(rec.snapshot()) == len(rec)  # snapshot still serves the ring


def test_drain_then_snapshot_keeps_serving_gettrace():
    t = PhaseTimers(sink=trace.record_phase)
    with t.phase("a"):
        pass
    assert [s["name"] for s in trace.RECORDER.drain()] == ["a"]
    with t.phase("b"):
        pass
    assert [s["name"] for s in trace.RECORDER.drain()] == ["b"]
    assert [s["name"] for s in trace.RECORDER.snapshot()] == ["a", "b"]


def test_span_file_appends_lines_and_a_relaunch_appends(tmp_path, monkeypatch):
    monkeypatch.setenv("EDL_SCHED_PHASE_SECS", "0.05")
    t = PhaseTimers(sink=trace.record_phase)
    out = trace.start_span_file(str(tmp_path / "logs"), "worker-3")
    assert out.path.endswith("logs/worker-3.spans.jsonl")
    with t.phase("get_batch"):
        pass
    deadline = time.time() + 5
    while time.time() < deadline and not os.path.getsize(out.path):
        time.sleep(0.02)
    out.stop()
    again = trace.start_span_file(str(tmp_path / "logs"), "worker-3")
    with t.phase("compute", steps=2):
        pass
    again.stop()
    spans = trace.load_span_file(out.path)
    assert [s["name"] for s in spans] == ["get_batch", "compute"]
    assert all(abs(s["ts"] - time.time()) < 60 for s in spans)


def test_no_directory_writes_nothing_and_no_knob_switches_the_file_off(
    tmp_path, monkeypatch
):
    assert trace.start_span_file("", "master") is None
    assert not list(tmp_path.iterdir())
    # EDL_SCHED_PHASE_SECS=0 turns the phase stats off, not the timeline
    monkeypatch.setenv("EDL_SCHED_PHASE_SECS", "0")
    out = trace.start_span_file(str(tmp_path / "tb"), "master")
    out.stop()
    assert os.path.isfile(out.path)


def test_a_burst_is_drained_before_the_ring_evicts_it(tmp_path, monkeypatch):
    """Half a stripe waiting sets `pressure`, which wakes the file's
    thread before its period is up."""
    monkeypatch.setenv("EDL_SCHED_PHASE_SECS", "30")
    out = trace.start_span_file(str(tmp_path), "worker-0")
    t = PhaseTimers(sink=trace.record_phase)
    stripe = trace._DEFAULT_CAPACITY // trace._STRIPES
    n = 3 * stripe  # three stripes' worth

    def written():
        with open(out.path) as f:
            return f.read().count("\n")

    # with half a stripe not in the file yet the drainer gets the GIL
    # until it has written, however loaded the host; in all for less
    # long than its period, so a thread that `pressure` did not wake
    # still loses spans
    deadline = time.time() + 20
    for i in range(n):
        with t.phase("step", i=i):
            pass
        if i % 256 == 0:
            while i + 1 - written() >= stripe // 2 and time.time() < deadline:
                time.sleep(0.005)
    out.stop()
    spans = trace.load_span_file(out.path)
    assert [s["args"]["i"] for s in spans] == list(range(n))


CHILD = """
import sys, time
from elasticdl_tpu.common.messages import MethodType
from elasticdl_tpu.common.timing import PhaseTimers
from elasticdl_tpu.obs import trace
trace.start_span_file(sys.argv[1], "worker-0")
t = PhaseTimers(sink=trace.record_phase)
i = 0
while True:
    with t.phase("step", i=i):
        time.sleep(0.01)
    i += 1
"""


def test_span_file_survives_sigkill_up_to_its_last_period(tmp_path):
    env = dict(os.environ, EDL_SCHED_PHASE_SECS="0.2", JAX_PLATFORMS="cpu")
    child = subprocess.Popen(
        [sys.executable, "-c", textwrap.dedent(CHILD), str(tmp_path)], env=env
    )
    path = tmp_path / "worker-0.spans.jsonl"
    try:
        deadline = time.time() + 60
        while time.time() < deadline:
            if path.exists() and path.stat().st_size > 2000:
                break
            time.sleep(0.05)
        time.sleep(0.5)
    finally:
        killed = time.time()
        os.kill(child.pid, signal.SIGKILL)
        child.wait()
    spans = trace.load_span_file(str(path))
    assert spans, "the killed process left no span"
    assert all(s["pid"] == child.pid and s["name"] == "step" for s in spans)
    assert [s["args"]["i"] for s in spans] == list(range(len(spans)))
    last = spans[-1]["ts"] + spans[-1]["dur"]
    # at most one period (0.2 s) and a step lost, with room for a slow host
    assert killed - last < 1.5, f"the file ends {killed - last:.2f}s early"


def test_obs_cli_merges_span_files_into_one_chrome_trace(tmp_path):
    t = PhaseTimers(sink=trace.record_phase)
    with t.phase("get_batch"):
        pass
    worker = tmp_path / "worker-0.spans.jsonl"
    worker.write_text(
        "".join(json.dumps(s) + "\n" for s in trace.RECORDER.drain())
    )
    t.record("apply", time.time(), time.time() + 0.01, kind="local_update")
    master = tmp_path / "master.spans.jsonl"
    master.write_text(  # another process, and a line a kill cut short
        "".join(
            json.dumps(dict(s, pid=s["pid"] + 1)) + "\n"
            for s in trace.RECORDER.drain()
        )
        + '{"name": "cut short by a ki'
    )
    out = tmp_path / "timeline.json"
    assert obs_main(["--spans", str(worker), str(master), "--out", str(out)]) == 0
    events = json.loads(out.read_text())["traceEvents"]
    assert {e["name"] for e in events if e["ph"] == "X"} == {"get_batch", "apply"}
    names = {e["args"]["name"] for e in events if e["name"] == "process_name"}
    assert any(n.startswith("worker-0") for n in names)
    assert any(n.startswith("master") for n in names)


FIXTURE_XPLANE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "benchmark", "recorded.xplane.pb"
)


def _trace_dir(tmp_path, record):
    """The committed fixture trace laid out as the probe leaves it
    (`<pid>.json` beside `trace-<pid>/`); `asked` is where the trace's
    clock starts."""
    import shutil

    asked = 1_790_000_000.0
    trace_dir = tmp_path / "probe" / "trace-4242"
    run = trace_dir / "plugins" / "profile" / "2026_09_27"
    run.mkdir(parents=True)
    shutil.copy(FIXTURE_XPLANE, run / "host.xplane.pb")
    if record == "probe":
        (tmp_path / "probe" / "4242.json").write_text(
            json.dumps({"trace": {"asked": asked, "t0": asked, "t1": asked + 4}})
        )
    return trace_dir, asked


def test_obs_cli_lays_the_device_trace_under_the_phases(tmp_path):
    trace_dir, asked = _trace_dir(tmp_path, "probe")
    worker = tmp_path / "worker-0.spans.jsonl"
    worker.write_text(json.dumps({
        "name": "get_batch", "cat": "phase", "ts": asked + 0.008,
        "dur": 0.002, "pid": 7, "tid": 1, "args": {"thread": "MainThread"},
    }) + "\n")
    out = tmp_path / "timeline.json"
    assert obs_main(["--spans", str(worker), "--device-trace", str(trace_dir),
                     "--out", str(out)]) == 0
    events = json.loads(out.read_text())["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    device = [e for e in spans if e["cat"] == "device"]
    # the fixture's device planes, every line of them, none of the host's
    assert {e["name"] for e in device} >= {
        "fusion.1", "while.2", "fusion.3", "jit_window(123)", "jit_copy(45)"
    }
    assert "PjitFunction(window)" not in {e["name"] for e in spans}
    rows = {e["args"]["name"] for e in events if e["name"] == "process_name"}
    assert {"/device:TPU:0", "/device:TPU:1"} <= rows
    # on the phases' clock: trace time t is wall-clock `asked + t`
    chip0 = 1_000_000_000
    at = {(e["name"], e["pid"]): e for e in device}
    assert at["while.2", chip0]["ts"] == pytest.approx(asked * 1e6 + 4000, abs=1)
    assert at["while.2", chip0]["dur"] == pytest.approx(4000)
    # the gap [8000, 10000) us on chip 0 lies under the get_batch span
    (batch,) = [e for e in spans if e["name"] == "get_batch"]
    assert batch["ts"] == pytest.approx(asked * 1e6 + 8000, abs=1)
    busy = [e for e in device if e["pid"] == chip0
            and e["args"]["thread"] == "XLA Ops"]
    assert not [e for e in busy if e["ts"] < batch["ts"] + batch["dur"]
                and e["ts"] + e["dur"] > batch["ts"]]


def test_obs_cli_refuses_a_device_trace_nothing_dates(tmp_path):
    trace_dir, _ = _trace_dir(tmp_path, record=None)
    worker = tmp_path / "worker-0.spans.jsonl"
    worker.write_text("")
    with pytest.raises(SystemExit, match="where its clock starts"):
        obs_main(["--spans", str(worker), "--device-trace", str(trace_dir),
                  "--out", str(tmp_path / "timeline.json")])
    with pytest.raises(SystemExit):  # the knobs nothing needed are gone
        obs_main(["--spans", str(worker), "--min-us", "5"])


# -- the master's phases -----------------------------------------------------


def _servicer():
    return MasterServicer(
        grads_to_wait=1,
        optimizer=PSOptimizer(optax.sgd(1.0)),
        init_params={"w": np.zeros(4, dtype=np.float32)},
    )


@pytest.mark.parametrize("method", ["ReportLocalUpdate", "ReportGradient"])
def test_master_phases_for_one_update_through_the_dispatcher(method):
    s = _servicer()
    server = RpcServer(s.handlers(), port=0, timers=s.timers,
                       timed_methods=MASTER_UPDATE_METHODS)
    server.start()
    client = RpcClient(f"localhost:{server.port}",
                       timeline=MASTER_UPDATE_METHODS)
    try:
        if method == "ReportLocalUpdate":
            resp = client.call(method, {
                "delta_flat": np.ones(4, np.float32), "steps": 2,
                "base_version": 0, "report_key": "k", "want_model": True,
            })
            kind = "local_update"
        else:
            resp = client.call(method, {
                "worker_id": 0, "version": 0, "return_model": True,
                "gradient_flat": np.ones(4, np.float32),
            })
            kind = "gradient"
        client.call("GetTask", {"worker_id": 0})  # not on the timeline
    finally:
        client.close()
        server.stop()
    assert resp["version"] > 0
    by = {s["name"]: s for s in _spans()}
    chain = ["rpc.decode", "apply_wait", "grad_decode", "apply",
             "model_encode", "rpc.encode"]
    assert set(chain) <= set(by)
    # one handler thread, in order, each on time.time()
    assert len({by[n]["tid"] for n in chain}) == 1
    starts = [by[n]["ts"] for n in chain]
    assert starts == sorted(starts)
    for n in ("apply_wait", "grad_decode", "apply", "model_encode"):
        assert by[n]["args"]["kind"] == kind
    # each interval is a part of the handler's time, none holds another
    ends = [by[n]["ts"] + by[n]["dur"] for n in chain]
    assert all(e <= s + 1e-6 for e, s in zip(ends, starts[1:]))
    # every one carries the version the response named: the join key
    for n in chain:
        assert by[n]["args"]["version"] == resp["version"], n
    assert by[f"rpc.client.{method}"]["args"]["version"] == resp["version"]
    assert by["rpc.decode"]["args"]["bytes"] > 16
    assert by["rpc.encode"]["args"]["bytes"] > 16
    # the client's side of the same call
    for n in ("rpc.client.encode", f"rpc.client.{method}", "rpc.client.decode"):
        assert n in by, sorted(by)
    assert not any("GetTask" in n for n in by)
    # and the master's own counters
    snap = s.timers.snapshot()
    assert {n: snap[n]["count"] for n in chain} == dict.fromkeys(chain, 1)


def test_duplicate_local_update_records_no_apply():
    s = _servicer()
    req = {"delta_flat": np.ones(4, np.float32), "steps": 1,
           "base_version": 0, "report_key": "same"}
    s.report_local_update(dict(req))
    s.report_local_update(dict(req))
    assert len(_spans("apply")) == 1
    assert s.timers.snapshot()["apply"]["count"] == 1


def test_a_report_that_only_joins_the_sum_is_not_an_apply():
    s = MasterServicer(
        grads_to_wait=2,
        optimizer=PSOptimizer(optax.sgd(1.0)),
        init_params={"w": np.zeros(4, dtype=np.float32)},
    )
    for worker_id in (0, 1):
        s.report_gradient({"worker_id": worker_id, "version": 0,
                           "gradient_flat": np.ones(4, np.float32)})
    kinds = [a["args"]["kind"] for a in _spans("apply")]
    assert kinds == ["accumulate", "gradient"]
    # the decode is its own span, before the apply and not inside it
    for decode, apply in zip(_spans("grad_decode"), _spans("apply")):
        assert decode["ts"] + decode["dur"] <= apply["ts"] + 1e-6


def test_master_phases_are_recorded_with_the_model_lock_released():
    s = _servicer()
    held = []

    def sink(name, begin, dur, args, ctx=None):
        held.append((name, s._lock.locked()))
        trace.record_phase(name, begin, dur, args, ctx)

    s.timers = PhaseTimers(sink=sink)
    s.report_local_update({"delta_flat": np.ones(4, np.float32), "steps": 1,
                           "base_version": 0, "want_model": True})
    s.report_gradient({"worker_id": 0, "version": 1, "return_model": True,
                       "gradient_flat": np.ones(4, np.float32)})
    s.get_model({"version": -1, "method": MethodType.MINIMUM, "flat": True})
    names = [n for n, _ in held]
    assert names.count("apply_wait") == 3 and names.count("model_encode") == 3
    assert not [n for n, locked in held if locked]
    pulls = [a for a in _spans("apply_wait") if a["args"]["kind"] == "get_model"]
    assert len(pulls) == 1


def test_shard_dispatcher_without_timers_records_nothing():
    s = _servicer()
    server = RpcServer(s.handlers(), port=0)  # a shard: no timers
    server.start()
    client = RpcClient(f"localhost:{server.port}")
    try:
        client.call("GetModel", {
            "version": -1, "method": MethodType.MINIMUM, "flat": True,
        })
    finally:
        client.close()
        server.stop()
    assert not _spans("rpc.decode") and not _spans("rpc.encode")
    assert _spans("model_encode")  # the servicer's own phase stays


def test_cumulative_and_master_phases_ride_sched_stats():
    """What master/main.py composes into GetSchedStats.phases."""
    agg = PhaseStatsAggregator()
    w0, w1 = PhaseTimers(sink=trace.record_phase), PhaseTimers(sink=trace.record_phase)
    with w0.phase("compute", steps=8):
        pass
    with w1.phase("compute", steps=8):
        pass
    agg.ingest(0, w0.snapshot(), {"platform": "cpu"})
    agg.ingest(1, w1.snapshot())
    merged = merge_phase_snapshots(agg.latest_cumulative().values())
    assert merged["compute"]["count"] == 2
    master = _servicer()
    master.report_local_update({"delta_flat": np.ones(4, np.float32),
                                "steps": 1, "base_version": 0})
    assert master.timers.snapshot()["apply"]["count"] == 1


# -- the worker's side -------------------------------------------------------


def _worker(local_updates=2):
    from elasticdl_tpu.api.model_spec_helpers import spec_from_module
    from elasticdl_tpu.testing import InProcessMaster
    from elasticdl_tpu.worker.worker import Worker
    from tests.fixtures import linear_module

    servicer = MasterServicer(
        grads_to_wait=1, optimizer=PSOptimizer(linear_module.optimizer())
    )
    return Worker(0, InProcessMaster(servicer), spec_from_module(linear_module),
                  minibatch_size=16, local_updates=local_updates)


def test_jitted_programs_keep_the_names_the_device_trace_shows():
    """No program is renamed, wrapped or re-traced by the timeline: the
    compile cache keys on the module's name (PERF.md, PR 24: a rename
    cost ResNet-50's set-up 3.3 s), and `idle_gaps` in the ledger reads
    `after jit_copy`. Read from the functions the worker builds."""
    import jax
    import jax.numpy as jnp

    worker = _worker(local_updates=2)
    features = np.zeros((16, 1), np.float32)
    worker._init_model(features, None)
    window = worker._build_local_window_fn()
    local = worker._build_local_step()
    assert (window.__name__, local.__name__) == ("window", "step")
    # the jitted callable itself is what the call site calls: no object
    # wrapped around it
    assert type(window) is type(jax.jit(lambda x: x))
    step = worker._shard_jit(lambda *a: a)
    assert type(step) is type(window)
    # and the lowered module carries `jit_<name>`
    flat = jnp.zeros(2)
    opt_state = worker._spec.optimizer().init(flat)
    stacked = np.zeros((2, 16, 1), np.float32)
    lowered = window.lower(flat, opt_state, worker._aux, stacked, stacked)
    assert "module @jit_window" in lowered.as_text()
    names = {
        fn.__code__.co_name
        for fn in (worker._build_train_step(), worker._build_eval_step())
    }
    assert names == {"run"}  # the closures that call jit_step
    assert (jnp.copy(flat) - flat).shape == (2,)  # jit_copy, jit_subtract: eager
    # `worker.device_run` stands beside the call, round nothing: after a
    # call the worker still holds the jitted callable itself, and the
    # span's `program` is the name the lowered module carries
    worker._flat, worker._opt_state = flat, opt_state
    worker._local_window_fn = window
    worker._run_window(flat, opt_state, worker._aux, stacked, stacked)
    worker._device_runs.close()
    assert worker._local_window_fn is window
    deadline = time.time() + 10
    while not _spans("worker.device_run") and time.time() < deadline:
        time.sleep(0.01)
    (run,) = _spans("worker.device_run")
    assert "module @" + run["args"]["program"] in lowered.as_text()


@pytest.fixture
def compile_cache(tmp_path):
    """jax's persistent compile cache in a directory of the test's own,
    taking every program however small."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path / "cache"), 0, -1)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("local_updates", [2, 0])
def test_a_second_worker_s_training_program_is_a_compile_cache_hit(
    tmp_path, monkeypatch, compile_cache, local_updates
):
    """The cache keys on the module: a second worker of this process
    traces `jit_window` / `jit_step` anew and is served the first one's
    executable, device runs recorded or not."""
    hits = []
    for _ in range(2):
        trace.RECORDER.clear()
        _train(tmp_path, monkeypatch, local_updates)
        (program,) = [
            s["args"] for s in _spans("setup.program")
            if s["args"]["program"] in ("jit_window", "jit_step")
        ]
        assert program["compiles"] == 1
        hits.append(program["cache_hit"])
        assert _spans("worker.device_run")
    assert hits == [False, True]


def test_a_program_s_first_call_is_one_setup_span_at_its_call_site():
    import jax
    import jax.numpy as jnp

    worker = _worker()
    double = jax.jit(lambda x: x * 2)
    double.__name__ = "double"
    for _ in range(3):
        with worker._first_call(double):
            double(jnp.ones(4))
        with worker._first_call("jit_subtract"):
            jnp.ones(4) - jnp.ones(4)
    spans = _spans("setup.program")
    assert [s["args"]["program"] for s in spans] == ["jit_double", "jit_subtract"]
    assert spans[0]["args"]["compiles"] >= 0
    for s in spans:  # says so only where jax compiled or loaded something
        assert ("cache_hit" in s["args"]) == bool(s["args"]["compiles"])
    # timeline only: nothing entered the exclusive seconds
    assert "setup.program" not in worker.timers.snapshot()


def test_an_adapter_s_init_says_it_was_not_traced():
    """`how: init` with `traced: false` is the adapter's own draw (the
    LMs' numpy normals), with no compile to report."""
    from elasticdl_tpu.api.model_spec_helpers import spec_from_module
    from elasticdl_tpu.models import transformer_lm_zoo
    from elasticdl_tpu.testing import InProcessMaster
    from elasticdl_tpu.worker.worker import Worker

    spec = spec_from_module(transformer_lm_zoo)
    servicer = MasterServicer(
        grads_to_wait=1, optimizer=PSOptimizer(spec.optimizer())
    )
    worker = Worker(0, InProcessMaster(servicer), spec, minibatch_size=2)
    worker._lazy_init_model(np.zeros((2, 8), np.int32))
    args = [s["args"] for s in _spans("setup.model_init")]
    assert [a["how"] for a in args] == ["init", "report", "pull"]
    assert args[0]["traced"] is False
    assert "compiles" not in args[0] and "cache_hit" not in args[0]
    assert all("traced" not in a for a in args[1:])


def _train(tmp_path, monkeypatch, local_updates, records=128):
    """A real Worker against a real servicer, in process."""
    from elasticdl_tpu.api.model_spec_helpers import spec_from_module
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.testing import InProcessMaster, write_linear_records
    from elasticdl_tpu.worker.worker import Worker
    from tests.fixtures import linear_module

    path = str(tmp_path / "train.rio")
    write_linear_records(path, records, noise=0.05)
    dispatcher = TaskDispatcher({path: records}, {}, {}, 64, 1)
    servicer = MasterServicer(
        grads_to_wait=1,
        optimizer=PSOptimizer(linear_module.optimizer()),
        task_dispatcher=dispatcher,
    )
    master = InProcessMaster(servicer)
    worker = Worker(0, master, spec_from_module(linear_module),
                    minibatch_size=16, local_updates=local_updates)
    worker.run()
    worker.close()
    assert dispatcher.finished()
    return worker, servicer, master


@pytest.mark.parametrize("local_updates", [2, 0])
def test_a_worker_s_run_is_on_the_timeline(tmp_path, monkeypatch, local_updates):
    worker, servicer, master = _train(tmp_path, monkeypatch, local_updates)
    names = {s["name"] for s in _spans()}
    assert {"get_task", "read_records", "get_batch", "compute",
            "setup.model_init", "setup.program", "setup.first_window"} <= names
    # compute's count and steps: the progress counter finer than an update
    computes = _spans("compute")
    assert sum(s["args"]["steps"] for s in computes) == 128 // 16
    assert worker.timers.snapshot()["compute"]["count"] == len(computes)
    programs = {s["args"]["program"] for s in _spans("setup.program")}
    inits = _spans("setup.model_init")
    # the failed first pull, then the handshake in its order
    assert [s["args"]["how"] for s in inits][-3:] == ["init", "report", "pull"]
    # a flax module's `init` is traced, and says what it compiled as a
    # `setup.program` does; the init program has no span of that name
    (init,) = [s["args"] for s in inits if s["args"]["how"] == "init"]
    assert init["traced"] is True and init["compiles"] >= 0
    assert ("cache_hit" in init) == bool(init["compiles"])
    assert not [p for p in programs if "init" in p]
    if local_updates:
        assert {"jit_window", "jit_subtract", "jit_copy"} <= programs
        syncs = _spans("worker.window_sync")
        assert sum(s["args"]["steps"] for s in syncs) == 128 // 16
        assert all(s["args"]["bytes"] > 0 for s in syncs)
        assert {"worker.sync_spawn", "worker.delta_wait", "worker.d2h",
                "worker.flush_reports"} <= names
        # the step loop's part begins where the sync's own span does
        spawns = {s["ts"] for s in _spans("worker.sync_spawn")}
        assert {s["ts"] for s in syncs} == spawns
        assert len(_spans("apply")) == len(syncs)  # the master's side
        # inside one sync its parts follow each other, none in another;
        # they are the sync's by `seq`, the last `worker.device_run` it
        # carries (a thread id is handed on by a finished sync's thread)
        sync = syncs[-1]
        runs = _spans("worker.device_run")
        assert sync["args"]["seq"] == max(r["args"]["seq"] for r in runs)
        parts = sorted(
            (s for s in _spans() if s["args"].get("seq") == sync["args"]["seq"]
             and s["name"] not in ("worker.window_sync", "worker.device_run",
                                   "worker.sync_spawn", "worker.window_wait")),
            key=lambda s: s["ts"],
        )
        assert {s["tid"] for s in parts} == {sync["tid"]}
        assert [s["name"] for s in parts if s["name"] != "worker.chain_wait"] == [
            "worker.delta_wait", "worker.d2h", "worker.flush_reports",
        ]  # an in-process master: no wire, so no rpc.client.*
        for a, b in zip(parts, parts[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-6
    else:
        assert "jit_step" in programs
        assert {"report_gradient", "worker.delta_wait", "worker.d2h",
                "worker.absorb"} <= names
        assert len(_spans("apply")) == 128 // 16
    # rule 5: timeline-only spans never enter the exclusive seconds the
    # autoscaler, `input_wait_pct` and `sync_exposed_pct` read
    assert not [n for n in worker.timers.snapshot() if "." in n]
    # the first window's span holds its program's, and is closed by the
    # thread that waits for the device anyway, never by a wait of the
    # step loop's own (window mode: the sync thread)
    (first,) = _spans("setup.first_window")
    (program,) = [s for s in _spans("setup.program")
                  if s["args"]["program"] in ("jit_window", "jit_step")]
    assert first["ts"] <= program["ts"]
    assert first["ts"] + first["dur"] >= program["ts"] + program["dur"]
    assert (first["args"]["thread"] == "MainThread") == (not local_updates)
    # rule 2: ReportPhaseStats stays where and what it is: at most once
    # a pass of the task loop, `phases` and `device` and nothing else
    assert master.calls.get("ReportPhaseStats", 0) <= master.calls["GetTask"]


def test_phase_stats_payload_is_what_it_was(monkeypatch):
    sent = []
    worker = _worker()
    monkeypatch.setattr(
        worker._master, "call",
        lambda method, req, **kw: sent.append((method, req)) or {},
    )
    worker._maybe_report_phase_stats()
    ((method, req),) = sent
    assert method == "ReportPhaseStats"
    assert sorted(req) == ["device", "phases", "worker_id"]
    assert "memory_stats" not in req["device"]


def test_a_sampled_interval_is_recorded_once_and_serves_both_readers(
    tmp_path, monkeypatch
):
    trace.configure(1.0)  # every chain is sampled
    _train(tmp_path, monkeypatch, local_updates=2)
    spans = _spans()
    seen = [(s["name"], s["pid"], s["tid"], s["ts"]) for s in spans]
    assert len(seen) == len(set(seen)), "an interval recorded twice"
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    syncs = by_name["worker.window_sync"]
    assert sum(s["args"]["steps"] for s in syncs) == 128 // 16
    # one `worker.window_sync` record a sync, not two: the one span is
    # the timeline's (thread, steps) and the trace's (ids)
    assert len(syncs) == len(by_name["worker.d2h"])
    for s in syncs + by_name["worker.d2h"] + by_name["worker.sync_exposed"]:
        assert s["cat"] == trace.PHASE_CAT and s["args"]["thread"]
        assert s["trace_id"] and s["span_id"]
    roots = {s["trace_id"]: s["span_id"] for s in syncs}
    for s in by_name["worker.d2h"] + by_name["worker.delta_wait"]:
        assert s["parent_id"] == roots[s["trace_id"]]
    # the step loop's phases belong to no trace
    assert "trace_id" not in by_name["compute"][0]
    # both ways of joining a sync's chain work on the ring as it is:
    # the trace's ids and the timeline's `seq` name the same parts
    for sync in syncs:
        by_trace = {
            (s["name"], s["ts"]) for s in spans
            if s.get("trace_id") == sync["trace_id"]
            and s["name"] in ("worker.delta_wait", "worker.d2h")
        }
        by_seq = {
            (s["name"], s["ts"]) for s in spans
            if s["args"].get("seq") == sync["args"]["seq"]
            and s["name"] in ("worker.delta_wait", "worker.d2h")
        }
        assert by_trace == by_seq and len(by_seq) == 2
    assert len({s["args"]["seq"] for s in syncs}) == len(syncs)
    # with sampling off the same spans are there and start no trace
    trace.RECORDER.clear()
    trace.configure(0.0)
    _train(tmp_path, monkeypatch, local_updates=2)
    assert _spans("worker.window_sync")
    assert not [s for s in _spans() if s.get("trace_id")]


def test_a_sync_s_parts_by_seq_sum_within_its_gate_over_the_wire(tmp_path):
    """A sync over a real gRPC hop: its parts, joined by `seq` (the
    device wait, the copy out, the report flush) and, for the round
    trip, which carries none, by its thread between them, sum within
    10 % of the sync's wall, the queue behind earlier syncs taken out."""
    from elasticdl_tpu.api.model_spec_helpers import spec_from_module
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.testing import write_linear_records
    from elasticdl_tpu.worker.worker import Worker
    from tests.fixtures import linear_module

    trace.configure(1.0)
    path = str(tmp_path / "train.rio")
    write_linear_records(path, 256, noise=0.05)
    dispatcher = TaskDispatcher({path: 256}, {}, {}, 64, 1)
    servicer = MasterServicer(
        grads_to_wait=1, optimizer=PSOptimizer(linear_module.optimizer()),
        task_dispatcher=dispatcher,
    )
    slow = servicer.report_local_update

    def report_local_update(req):  # a sync long enough to measure
        time.sleep(0.05)
        return slow(req)

    handlers = dict(servicer.handlers(), ReportLocalUpdate=report_local_update)
    server = RpcServer(handlers, port=0, timers=servicer.timers,
                       timed_methods=MASTER_UPDATE_METHODS)
    server.start()
    client = RpcClient(f"localhost:{server.port}",
                       timeline=MASTER_UPDATE_METHODS)
    try:
        Worker(0, client, spec_from_module(linear_module), minibatch_size=16,
               local_updates=2).run()
    finally:
        client.close()
        server.stop()
    spans = _spans()
    syncs = [s for s in spans if s["name"] == "worker.window_sync"]
    assert len(syncs) == 256 // 32
    assert len({s["trace_id"] for s in syncs}) == len(syncs)
    own = parts = 0.0
    for sync in syncs:
        mine = {
            s["name"]: s for s in spans
            if s["args"].get("seq") == sync["args"]["seq"]
        }
        assert {"worker.sync_spawn", "worker.delta_wait", "worker.d2h",
                "worker.flush_reports"} <= set(mine)
        (trip,) = [
            s for s in spans if s["name"] == "rpc.client.ReportLocalUpdate"
            and s["tid"] == sync["tid"]
            and mine["worker.d2h"]["ts"] <= s["ts"]
            <= mine["worker.flush_reports"]["ts"]
        ]
        queued = mine.get("worker.chain_wait", {"dur": 0.0})["dur"]
        own += sync["dur"] - queued
        parts += trip["dur"] + sum(
            mine[n]["dur"] for n in ("worker.sync_spawn", "worker.delta_wait",
                                     "worker.d2h", "worker.flush_reports")
        )
    assert 0.9 <= parts / own <= 1.1, (parts, own)


def test_a_sampled_update_rpc_is_one_client_span_with_the_trace_s_ids():
    trace.configure(1.0)
    s = _servicer()
    server = RpcServer(s.handlers(), port=0, timers=s.timers,
                       timed_methods=MASTER_UPDATE_METHODS)
    server.start()
    client = RpcClient(f"localhost:{server.port}",
                       timeline=MASTER_UPDATE_METHODS)
    try:
        client.call("GetModel", {"version": -1, "method": MethodType.MINIMUM,
                                 "flat": True})
        client.call("GetTask", {"worker_id": 0})
    finally:
        client.close()
        server.stop()
    (pull,) = _spans("rpc.client.GetModel")
    assert pull["cat"] == trace.PHASE_CAT and pull["trace_id"]
    (served,) = _spans("rpc.server.GetModel")  # its child, across the wire
    assert served["trace_id"] == pull["trace_id"]
    assert served["parent_id"] == pull["span_id"]
    # the pack and the unpack are the client span's children: the trace
    # still accounts for them
    for name in ("rpc.client.encode", "rpc.client.decode"):
        (part,) = _spans(name)
        assert part["trace_id"] == pull["trace_id"]
        assert part["parent_id"] == pull["span_id"]
    # a method off the timeline keeps its sampled span, once
    (task,) = _spans("rpc.client.GetTask")
    assert task["cat"] == "rpc"
