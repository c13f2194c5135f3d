"""Unit tests for the shared RPC retry/deadline policy and the
per-endpoint circuit breaker (rpc/policy.py) — all on virtual clocks:
no sleeps, no wall-clock dependence, deterministic under a fixed seed."""

import threading

import grpc
import pytest

from elasticdl_tpu.rpc.policy import (
    IDEMPOTENT_METHODS,
    CircuitBreaker,
    CircuitOpenError,
    DeadlineExhausted,
    RetryPolicy,
)


class Unavailable(grpc.RpcError):
    def code(self):
        return grpc.StatusCode.UNAVAILABLE


class Internal(grpc.RpcError):
    def code(self):
        return grpc.StatusCode.INTERNAL


class VClock:
    """Virtual time: sleeps advance it, calls can charge time too."""

    def __init__(self):
        self.t = 0.0
        self.sleeps = []

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.sleeps.append(dt)
        self.t += dt


def make_policy(vc, **kw):
    kw.setdefault("seed", 7)
    return RetryPolicy(sleep_fn=vc.sleep, clock=vc, **kw)


def flaky(n_failures, err=None, record=None):
    """fn that fails `n_failures` times, then returns 'ok'."""
    state = {"calls": 0}

    def fn(remaining):
        state["calls"] += 1
        if record is not None:
            record.append(remaining)
        if state["calls"] <= n_failures:
            raise (err or Unavailable())
        return "ok"

    fn.state = state
    return fn


# -- backoff determinism ---------------------------------------------------


def test_backoff_deterministic_and_bounded():
    p1 = RetryPolicy(seed=3)
    p2 = RetryPolicy(seed=3)
    p3 = RetryPolicy(seed=4)
    s1 = [p1.backoff_for("M", k) for k in range(1, 5)]
    s2 = [p2.backoff_for("M", k) for k in range(1, 5)]
    s3 = [p3.backoff_for("M", k) for k in range(1, 5)]
    assert s1 == s2, "same seed must give the identical schedule"
    assert s1 != s3, "different seeds must jitter differently"
    for k, b in enumerate(s1, start=1):
        base = min(p1.initial_backoff * p1.multiplier ** (k - 1), p1.max_backoff)
        assert base * (1 - p1.jitter) <= b <= base
    # jitter differs across methods too (decorrelates lockstep retries)
    assert p1.backoff_for("A", 1) != p1.backoff_for("B", 1)


def test_backoff_capped_at_max():
    p = RetryPolicy(initial_backoff=0.1, multiplier=10.0, max_backoff=0.5, jitter=0.0)
    assert p.backoff_for("M", 4) == 0.5


# -- retry semantics -------------------------------------------------------


def test_idempotent_retries_until_success():
    vc = VClock()
    p = make_policy(vc)
    fn = flaky(2)
    assert p.call(fn, "M", timeout=30.0, idempotent=True) == "ok"
    assert fn.state["calls"] == 3
    assert vc.sleeps == [p.backoff_for("M", 1), p.backoff_for("M", 2)]


def test_non_idempotent_never_retries():
    vc = VClock()
    p = make_policy(vc)
    fn = flaky(1)
    with pytest.raises(Unavailable):
        p.call(fn, "M", timeout=30.0, idempotent=False)
    assert fn.state["calls"] == 1
    assert vc.sleeps == []


def test_non_retryable_code_never_retries():
    vc = VClock()
    p = make_policy(vc)
    fn = flaky(1, err=Internal())
    with pytest.raises(Internal):
        p.call(fn, "M", timeout=30.0, idempotent=True)
    assert fn.state["calls"] == 1


def test_max_attempts_exhaustion_raises_last_error():
    vc = VClock()
    p = make_policy(vc, max_attempts=3)
    fn = flaky(99)
    with pytest.raises(Unavailable):
        p.call(fn, "M", timeout=30.0, idempotent=True)
    assert fn.state["calls"] == 3
    assert len(vc.sleeps) == 2


def test_deadline_budget_bounds_retries():
    """Retries + backoffs must fit the caller's timeout — the budget is
    total, not per-attempt."""
    vc = VClock()
    p = make_policy(vc, max_attempts=50, initial_backoff=0.1, jitter=0.0)
    fn = flaky(99)
    with pytest.raises(Unavailable):
        p.call(fn, "M", timeout=0.5, idempotent=True)
    # backoffs 0.1+0.2 fit in 0.5; adding 0.4 would not — so 3 attempts
    assert fn.state["calls"] == 3
    assert vc.t < 0.5


def test_per_attempt_timeout_is_remaining_budget():
    vc = VClock()
    p = make_policy(vc, initial_backoff=0.1, jitter=0.0)
    remaining = []

    def fn(r):
        remaining.append(r)
        if len(remaining) == 1:
            vc.t += 0.3  # the attempt itself burned 0.3s
            raise Unavailable()
        return "ok"

    assert p.call(fn, "M", timeout=1.0, idempotent=True) == "ok"
    assert remaining[0] == pytest.approx(1.0)
    # second attempt only gets what's left: 1.0 - 0.3 (call) - 0.1 (backoff)
    assert remaining[1] == pytest.approx(0.6)


def test_spent_budget_raises_deadline_exhausted():
    vc = VClock()
    p = make_policy(vc)
    vc.t = 100.0

    def fn(r):  # pragma: no cover - must not run
        raise AssertionError("attempt started with no budget")

    with pytest.raises(DeadlineExhausted) as ei:
        p.call(fn, "M", timeout=0.0, idempotent=True)
    assert ei.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED


def test_from_env_overrides(monkeypatch):
    monkeypatch.setenv("EDL_RPC_RETRIES", "7")
    monkeypatch.setenv("EDL_RPC_BACKOFF", "0.25")
    monkeypatch.setenv("EDL_RPC_SEED", "42")
    p = RetryPolicy.from_env()
    assert (p.max_attempts, p.initial_backoff, p.seed) == (7, 0.25, 42)


def test_idempotency_classification():
    # writes with no server-side dedup must never be auto-retried
    for m in ("GetTask", "ReportGradient",
              "ReportWindowMeta", "EmbeddingUpdate"):
        assert m not in IDEMPOTENT_METHODS, m
    # report_key-deduped / read-only / SETNX ops must be
    # (ReportLocalUpdate joined when the master servicer grew its own
    # dedup ring — workers always send a report_key now)
    for m in ("PSPushGrad", "PSPushDelta", "PSPull", "PSInit",
              "KVLookup", "KVUpdate", "GetModel", "ReportTaskResult",
              "ReportLocalUpdate"):
        assert m in IDEMPOTENT_METHODS, m


# -- circuit breaker -------------------------------------------------------


def test_breaker_opens_after_consecutive_failures():
    vc = VClock()
    b = CircuitBreaker("ep", failure_threshold=3, reset_interval=5.0, clock=vc)
    for _ in range(3):
        b.before_call()
        b.record_failure()
    assert b.is_open
    with pytest.raises(CircuitOpenError) as ei:
        b.before_call()
    assert ei.value.code() == grpc.StatusCode.UNAVAILABLE
    assert "ep" in str(ei.value)


def test_breaker_success_resets_consecutive_count():
    vc = VClock()
    b = CircuitBreaker("ep", failure_threshold=3, clock=vc)
    for _ in range(2):
        b.record_failure()
    b.record_success()
    for _ in range(2):
        b.record_failure()
    assert not b.is_open


def test_breaker_half_open_probe_then_close():
    vc = VClock()
    b = CircuitBreaker("ep", failure_threshold=1, reset_interval=5.0, clock=vc)
    b.record_failure()
    assert b.is_open
    vc.t = 6.0
    b.before_call()  # the single probe is admitted
    with pytest.raises(CircuitOpenError):
        b.before_call()  # concurrent calls during the probe fail fast
    b.record_success()
    assert not b.is_open
    b.before_call()


def test_breaker_failed_probe_reopens_and_rearms_timer():
    vc = VClock()
    b = CircuitBreaker("ep", failure_threshold=1, reset_interval=5.0, clock=vc)
    b.record_failure()
    vc.t = 6.0
    b.before_call()  # probe
    b.record_failure()  # probe failed: re-open, timer restarts at t=6
    with pytest.raises(CircuitOpenError):
        b.before_call()
    vc.t = 10.0  # only 4s since re-open: still closed to traffic
    with pytest.raises(CircuitOpenError):
        b.before_call()
    vc.t = 11.5
    b.before_call()  # next probe window


def test_policy_with_breaker_fails_fast_when_open():
    vc = VClock()
    b = CircuitBreaker("ep", failure_threshold=2, reset_interval=9.0, clock=vc)
    p = make_policy(vc, max_attempts=2)
    fn = flaky(99)
    with pytest.raises(Unavailable):
        p.call(fn, "M", timeout=30.0, idempotent=True, breaker=b)
    assert b.is_open  # 2 consecutive failures tripped it
    calls_before = fn.state["calls"]
    with pytest.raises(CircuitOpenError):
        p.call(fn, "M", timeout=30.0, idempotent=True, breaker=b)
    assert fn.state["calls"] == calls_before, "open breaker must not dial"


# -- RpcClient integration -------------------------------------------------


def test_client_call_memoization_is_thread_safe():
    """Concurrent FIRST calls of the same method race on the stub
    memoization dict; with the lock they must all succeed and agree."""
    from elasticdl_tpu.rpc.client import RpcClient
    from elasticdl_tpu.rpc.server import RpcServer

    server = RpcServer({"Echo": lambda req: {"x": req.get("x")}}, port=0)
    server.start()
    try:
        client = RpcClient(f"localhost:{server.port}")
        client.wait_ready(timeout=10)
        results, errors = [], []

        def hit(i):
            try:
                results.append(client.call("Echo", {"x": i}, timeout=10)["x"])
            except Exception as e:  # pragma: no cover - failure detail
                errors.append(e)

        threads = [threading.Thread(target=hit, args=(i,)) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert sorted(results) == list(range(16))
        assert set(client._calls) == {"Echo"}
        client.close()
    finally:
        server.stop()


def test_server_abort_carries_sanitized_detail():
    """Satellite fix: a handler exception must surface its message in
    the INTERNAL status details, not a constant 'handler error'."""
    from elasticdl_tpu.rpc.client import RpcClient
    from elasticdl_tpu.rpc.server import RpcServer

    def boom(req):
        raise ValueError("slice shape (3,) != (5,)")

    server = RpcServer({"Boom": boom}, port=0)
    server.start()
    try:
        client = RpcClient(f"localhost:{server.port}")
        client.wait_ready(timeout=10)
        with pytest.raises(grpc.RpcError) as ei:
            client.call("Boom", {}, timeout=10)
        assert ei.value.code() == grpc.StatusCode.INTERNAL
        assert "ValueError" in ei.value.details()
        assert "slice shape" in ei.value.details()
        client.close()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# WireStats striping: exact totals under contention, unchanged shape
# ---------------------------------------------------------------------------


def test_wire_stats_striped_totals_exact_under_contention():
    """N threads hammer record() concurrently; the merged snapshot must
    equal the arithmetic sum exactly — striping trades contention for a
    merge at snapshot time, never for accuracy."""
    from elasticdl_tpu.rpc.policy import WireStats

    ws = WireStats("test:0")
    n_threads, n_iters = 16, 400
    start = threading.Barrier(n_threads)

    def hammer(tid):
        start.wait()
        for i in range(n_iters):
            ws.record(
                "Report" if i % 2 else "Pull",
                sent=tid + 1,
                received=2 * (tid + 1),
                transport="uds" if i % 3 else "inproc",
            )

    threads = [
        threading.Thread(target=hammer, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    snap = ws.snapshot()
    total_sent = n_iters * sum(t + 1 for t in range(n_threads))
    assert snap["bytes_sent"] == total_sent
    assert snap["bytes_received"] == 2 * total_sent
    assert snap["calls"] == n_threads * n_iters
    # per-method split: even i -> Pull, odd i -> Report, 200 each
    per_method_sent = total_sent // 2
    for m in ("Report", "Pull"):
        assert snap["methods"][m]["bytes_sent"] == per_method_sent
        assert snap["methods"][m]["calls"] == n_threads * n_iters // 2
    # transport dimension sums to the same totals
    assert (
        sum(v["bytes_sent"] for v in snap["transports"].values())
        == total_sent
    )
    assert set(snap["transports"]) == {"uds", "inproc"}


def test_wire_stats_threads_spread_across_stripes():
    """Round-robin pinning: distinct threads land on distinct stripes
    (until the stripe count wraps), so concurrent recorders don't
    convoy on one lock."""
    from elasticdl_tpu.rpc.policy import WireStats, _stripe_index

    seen = []
    seen_lock = threading.Lock()

    def probe():
        idx = _stripe_index()
        with seen_lock:
            seen.append(idx)

    threads = [threading.Thread(target=probe) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(0 <= i < WireStats._NUM_STRIPES for i in seen)
    # 8 fresh threads over 8 stripes: more than one stripe must be hit
    # (exact assignment depends on prior pinning in this process)
    assert len(set(seen)) > 1


def test_wire_stats_snapshot_shape_and_reset():
    """The striped snapshot keeps the pre-striping contract: same keys,
    plain dicts; reset() clears every stripe."""
    from elasticdl_tpu.rpc.policy import WireStats

    ws = WireStats("ep:1")
    ws.record("Push", sent=10, received=4, transport="grpc")
    ws.record("Push", sent=0, received=0, transport="inproc", calls=1)
    snap = ws.snapshot()
    assert set(snap) == {
        "endpoint", "bytes_sent", "bytes_received", "calls",
        "methods", "transports",
    }
    assert snap["endpoint"] == "ep:1"
    assert set(snap["methods"]["Push"]) == {
        "bytes_sent", "bytes_received", "calls"
    }
    assert snap["methods"]["Push"]["calls"] == 2  # explicit inproc call
    assert snap["transports"]["inproc"]["bytes_sent"] == 0

    ws.reset()
    empty = ws.snapshot()
    assert empty["bytes_sent"] == 0
    assert empty["methods"] == {} and empty["transports"] == {}


def test_aggregate_wire_snapshots_shape_identical():
    """aggregate over striped snapshots: same rollup shape and exact
    sums as the pre-striping implementation."""
    from elasticdl_tpu.rpc.policy import WireStats, aggregate_wire_snapshots

    a, b = WireStats("a"), WireStats("b")
    a.record("Report", sent=100, received=8, transport="uds")
    b.record("Report", sent=50, received=4, transport="uds")
    b.record("Pull", sent=3, received=900, transport="grpc")
    agg = aggregate_wire_snapshots([a.snapshot(), b.snapshot()])
    assert set(agg) == {
        "bytes_sent", "bytes_received", "methods", "transports",
    }
    assert agg["bytes_sent"] == 153
    assert agg["bytes_received"] == 912
    assert agg["methods"]["Report"]["bytes_sent"] == 150
    assert agg["transports"]["uds"]["calls"] == 2
