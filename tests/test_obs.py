"""Observability plane (elasticdl_tpu/obs/): span propagation across
every transport tier, SpanRecorder ring bounds under concurrent
writers, the Prometheus text golden, flight-recorder causal order,
the GetTrace/GetMetrics RPC surface, and the overhead guards: the
disabled path, a phase with a sink, one `worker.device_run`.
"""

import json
import threading
import time

import pytest

from elasticdl_tpu.common.constants import ENV_TRANSPORT, ENV_UDS_DIR
from elasticdl_tpu.obs import flight, metrics, trace
from elasticdl_tpu.obs.fetch import fetch_metrics, fetch_trace
from elasticdl_tpu.rpc.client import RpcClient
from elasticdl_tpu.rpc.server import RpcServer


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts traced-at-1.0 with empty recorders and ends
    with the env-driven default restored (off unless EDL_TRACE_SAMPLE
    is set) so no obs state leaks between tests."""
    trace.configure(1.0)
    trace.RECORDER.clear()
    flight.RECORDER.clear()
    yield
    trace.configure(None)
    trace.RECORDER.clear()
    flight.RECORDER.clear()
    metrics.reset_registry_for_tests()
    metrics.stop_serving_for_tests()


# -- span propagation over the transport tiers -------------------------------


def _echo_roundtrip():
    server = RpcServer({"Echo": lambda req: {"x": req.get("x")}}, port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}")
    try:
        assert client.call("Echo", {"x": 41}, timeout=30)["x"] == 41
        with trace.span("outer", cat="test", root=True) as outer:
            assert outer is not None
            client.call("Echo", {"x": 42}, timeout=30)
            outer_id = outer.ctx.span_id
    finally:
        client.close()
        server.stop()
    return outer_id


@pytest.mark.parametrize("tier", ["grpc", "uds", "inproc", "unset"])
def test_span_parent_child_roundtrip_per_tier(tier, monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    if tier == "unset":
        # nothing set: a local peer is carried by the Unix socket
        monkeypatch.delenv(ENV_TRANSPORT, raising=False)
        tier = "uds"
    else:
        monkeypatch.setenv(ENV_TRANSPORT, tier)
    outer_id = _echo_roundtrip()
    spans = trace.RECORDER.snapshot()
    clients = [s for s in spans if s["name"] == "rpc.client.Echo"]
    servers = [s for s in spans if s["name"] == "rpc.server.Echo"]
    assert len(clients) == 2 and len(servers) == 2
    # the envelope crossed the tier: every server span is the child of
    # its client span, in the same trace
    by_id = {c["span_id"]: c for c in clients}
    for sv in servers:
        cl = by_id[sv["parent_id"]]
        assert sv["trace_id"] == cl["trace_id"]
        assert sv["args"]["transport"] == tier
    # the first call had no surrounding context -> fresh root; the
    # second chained under the explicit outer span
    roots = [c for c in clients if c["parent_id"] is None]
    chained = [c for c in clients if c["parent_id"] == outer_id]
    assert len(roots) == 1 and len(chained) == 1


def test_unsampled_request_carries_no_envelope():
    trace.configure(0.0)
    seen = {}

    def echo(req):
        seen.update(req)
        return {}

    server = RpcServer({"Echo": echo}, port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}")
    try:
        client.call("Echo", {"x": 1}, timeout=30)
    finally:
        client.close()
        server.stop()
    assert trace.ENVELOPE_KEY not in seen
    assert len(trace.RECORDER) == 0


# -- SpanRecorder ring --------------------------------------------------------


def test_span_recorder_bounds_and_thread_safety():
    rec = trace.SpanRecorder(capacity=64, stripes=4)
    errors = []

    def writer(k):
        try:
            for i in range(500):
                rec.record({"name": f"s{k}", "ts": float(i), "dur": 0.0})
        except Exception as e:  # pragma: no cover - the assertion
            errors.append(e)

    threads = [
        threading.Thread(target=writer, args=(k,)) for k in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(rec) <= 64  # bounded: overflow evicts, never grows
    assert rec.dropped > 0  # and says so
    snap = rec.snapshot()
    assert len(snap) == len(rec)
    assert snap == sorted(snap, key=lambda s: s["ts"])
    rec.clear()
    assert len(rec) == 0 and rec.dropped == 0


def test_chrome_trace_export_is_perfetto_shaped(tmp_path):
    with trace.span("parent", cat="test", root=True):
        with trace.span("child", cat="test"):
            pass
    doc = trace.chrome_trace()
    events = doc["traceEvents"]
    assert {e["name"] for e in events} == {"parent", "child"}
    for e in events:
        assert e["ph"] == "X"  # complete events
        assert {"name", "cat", "ts", "dur", "pid", "tid", "args"} <= set(e)
    # parent/child linkage rides args for trace-processor queries
    child = next(e for e in events if e["name"] == "child")
    parent = next(e for e in events if e["name"] == "parent")
    assert child["args"]["parent_id"] == parent["args"]["span_id"]
    path = trace.dump_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        assert json.load(f)["traceEvents"]


# -- metrics surface ----------------------------------------------------------


def test_prometheus_text_golden():
    reg = metrics.MetricsRegistry(
        declared={
            "edl_demo_total": "Things counted.",
            "edl_demo_level": "A level.",
        }
    )
    reg.inc("edl_demo_total", 2, endpoint="a")
    reg.inc("edl_demo_total", 3, endpoint="a")
    reg.set_gauge("edl_demo_level", 1.5)
    reg.register_collector(
        lambda sink: sink.counter("edl_demo_total", 7, endpoint="b")
    )
    golden = (
        "# HELP edl_demo_level A level.\n"
        "# TYPE edl_demo_level gauge\n"
        "edl_demo_level 1.5\n"
        "# HELP edl_demo_total Things counted.\n"
        "# TYPE edl_demo_total counter\n"
        'edl_demo_total{endpoint="a"} 5\n'
        'edl_demo_total{endpoint="b"} 7\n'
    )
    assert reg.prometheus_text() == golden


def test_undeclared_metric_raises():
    reg = metrics.MetricsRegistry(declared={"edl_known_total": "k"})
    with pytest.raises(ValueError, match="edl_sneaky_total"):
        reg.inc("edl_sneaky_total")
    with pytest.raises(ValueError, match="METRIC_REGISTRY"):
        reg.set_gauge("edl_sneaky", 1)


def test_default_registry_has_obs_health_collectors():
    with trace.span("s", root=True):
        pass
    flight.record("evt")
    snap = metrics.get_registry().snapshot()
    assert snap["edl_trace_spans"][0]["value"] == 1
    assert snap["edl_flight_events"][0]["value"] == 1
    assert set(snap) <= set(metrics.METRIC_REGISTRY)


def test_http_metrics_listener():
    import urllib.request

    server = metrics.serve(0)
    port = server.server_address[1]
    metrics.get_registry().inc("edl_chaos_injected_total", kind="test")
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5
    ).read().decode()
    assert 'edl_chaos_injected_total{kind="test"} 1' in body


# -- GetTrace / GetMetrics RPC surface ---------------------------------------


def test_get_trace_and_metrics_rpcs_on_a_shard():
    from elasticdl_tpu.master.kv_shard import KVShardServicer

    servicer = KVShardServicer(shard_id=0, num_shards=1)
    server = RpcServer(servicer.handlers(), port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}")
    try:
        fetch_trace(client)
        # the first GetTrace call itself produced a server span; the
        # second fetch reads it back out of the recorder
        got = fetch_trace(client)
        names = {s["name"] for s in got["spans"]}
        assert "rpc.server.GetTrace" in names
        assert "dropped" in got
        servicer.register_metrics()
        m = fetch_metrics(client)["metrics"]
        assert m["edl_kv_rows"][0]["labels"] == {"shard": "0"}
        assert set(m) <= set(metrics.METRIC_REGISTRY)
    finally:
        client.close()
        server.stop()


# -- flight recorder ----------------------------------------------------------


def test_flight_recorder_causal_order_under_concurrent_writers():
    rec = flight.FlightRecorder(capacity=100_000)

    def writer(k):
        for i in range(400):
            rec.record("evt", writer=k, i=i)

    threads = [
        threading.Thread(target=writer, args=(k,)) for k in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = rec.snapshot()
    assert len(events) == 8 * 400 and rec.dropped == 0
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # per-writer program order is preserved in the global seq order
    for k in range(8):
        per = [e["i"] for e in events if e["writer"] == k]
        assert per == sorted(per)


def test_flight_recorder_ring_bound():
    rec = flight.FlightRecorder(capacity=16)
    for i in range(50):
        rec.record("evt", i=i)
    assert len(rec) == 16 and rec.dropped == 34
    assert [e["i"] for e in rec.snapshot()] == list(range(34, 50))


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_crash_dump_on_thread_exception(tmp_path):
    path = str(tmp_path / "flight.json")
    flight.install_crash_dump(path)
    flight.record("before_crash", k=1)

    def boom():
        raise RuntimeError("chaos")

    t = threading.Thread(target=boom, name="crashy")
    t.start()
    t.join()
    with open(path) as f:
        doc = json.load(f)
    kinds = [e["kind"] for e in doc["events"]]
    assert "before_crash" in kinds
    assert "uncaught_thread_exception" in kinds
    assert kinds.index("before_crash") < kinds.index(
        "uncaught_thread_exception"
    )


# -- disabled-path overhead guard --------------------------------------------


@pytest.mark.perf
def test_tracing_off_is_near_free():
    """EDL_TRACE_SAMPLE=0 must keep the hot-loop instrumentation at a
    function call + one float compare — no locks, no allocation. The
    bounds are deliberately loose (CI machines are noisy); a regression
    that adds locking or recording lands orders of magnitude above."""
    trace.configure(0.0)
    n = 100_000

    t0 = time.perf_counter()
    for _ in range(n):
        sp = trace.start_span("x", cat="test", root=True)
        if sp is not None:  # pragma: no cover - off path
            sp.end()
    start_cost = (time.perf_counter() - t0) / n

    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("x", cat="test"):
            pass
    cm_cost = (time.perf_counter() - t0) / n

    t0 = time.perf_counter()
    for _ in range(n):
        trace.record_event("x", 0.0, 0.0)
    ev_cost = (time.perf_counter() - t0) / n

    assert len(trace.RECORDER) == 0
    assert start_cost < 5e-6, f"start_span off-path {start_cost * 1e6:.2f}us"
    assert cm_cost < 10e-6, f"span() off-path {cm_cost * 1e6:.2f}us"
    assert ev_cost < 5e-6, f"record_event off-path {ev_cost * 1e6:.2f}us"

    # the phase timeline is on whatever the sample rate, so a phase
    # with a sink is on the hot path: one time.time(), one striped
    # append. A regression that adds I/O or a global lock lands far
    # above this (loose) bound.
    from elasticdl_tpu.common.timing import PhaseTimers

    timers = PhaseTimers(sink=trace.record_phase)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with timers.phase("x"):
            pass
    phase_cost = (time.perf_counter() - t0) / n
    assert phase_cost < 50e-6, f"phase() with a sink {phase_cost * 1e6:.1f}us"
    assert len(trace.RECORDER) <= trace._DEFAULT_CAPACITY  # still bounded
    trace.RECORDER.clear()


@pytest.mark.perf
def test_a_device_run_costs_its_caller_microseconds():
    """`worker.device_run` is always on, once a call of a training
    program: `asked` (one `memory_stats()`) and `ready` are a few dict
    writes, two `time.time()`, one lock and one striped append. The same loose bound as a phase with a sink; the chip's
    own `memory_stats()` is measured there (CHANGES.md, PR 54)."""
    from elasticdl_tpu.common.timing import DeviceRuns, PhaseTimers

    stats = {"bytes_in_use": 1, "bytes_reserved": 2}
    runs = DeviceRuns(
        PhaseTimers(sink=trace.record_phase), lambda result: None,
        lambda: stats,
    )
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        runs.ready(runs.asked("jit_window", 8))
    cost = (time.perf_counter() - t0) / n
    assert cost < 50e-6, f"one worker.device_run {cost * 1e6:.1f}us"
    (last,) = [s for s in trace.RECORDER.snapshot()
               if s["args"].get("seq") == n]
    assert last["args"]["bytes_in_use"] == 1
    assert len(trace.RECORDER) <= trace._DEFAULT_CAPACITY
    trace.RECORDER.clear()
