"""Transport-tier tests: the inproc/UDS fast paths under the gRPC
call surface (rpc/transport.py).

Covers tier selection (conservative fallback to gRPC on any doubt),
one carrier contract held on both carriers a link between processes
can get — the Unix socket and gRPC, its silent fallback: the SAME
failure semantics (fencing -> FAILED_PRECONDITION, handler bugs ->
INTERNAL with sanitized detail, unknown method -> UNIMPLEMENTED, a
stopped server -> UNAVAILABLE), chaos FaultPlan injection, frames
bit-equal, their memory reused only once nothing reads it — the
WireStats transport dimension
(per-endpoint bytes summing correctly across mixed tiers, inproc calls
counted with ZERO wire bytes), a frame the socket header cannot
describe, and the resource lifecycle of the socket listener.
"""

import os
import socket
import threading
import time
import weakref

import grpc
import numpy as np
import pytest

from elasticdl_tpu.common import codec, messages
from elasticdl_tpu.common.constants import ENV_TRANSPORT, ENV_UDS_DIR
from elasticdl_tpu.rpc import transport
from elasticdl_tpu.rpc.chaos import FaultPlan, InjectedRpcError
from elasticdl_tpu.rpc.client import RpcClient
from elasticdl_tpu.rpc.fencing import EpochFencedError, is_fenced_error
from elasticdl_tpu.rpc.policy import (
    PolicyRpcError,
    RetryPolicy,
    WireStats,
    aggregate_wire_snapshots,
)
from elasticdl_tpu.rpc.server import RpcServer


def fast_policy(**kw):
    kw.setdefault("initial_backoff", 0.01)
    kw.setdefault("max_backoff", 0.05)
    return RetryPolicy(**kw)


def _echo_handlers(hits=None):
    def echo(req):
        if hits is not None:
            hits.append(req.get("x"))
        return {"x": req.get("x"), "arr": np.arange(4, dtype=np.float32)}

    def boom(req):
        raise ValueError("kaboom\nwith newline")

    def fenced(req):
        raise EpochFencedError("ps", 0, 3, int(req.get("epoch", -1)))

    return {"Echo": echo, "Boom": boom, "Fenced": fenced}


@pytest.fixture
def uds_env(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_TRANSPORT, "uds")
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))


@pytest.fixture
def inproc_env(monkeypatch):
    monkeypatch.setenv(ENV_TRANSPORT, "inproc")


@pytest.fixture
def grpc_env(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_TRANSPORT, "grpc")
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))


@pytest.fixture(params=["uds", "grpc"])
def carrier(request, monkeypatch, tmp_path):
    """The two carriers of a link between processes. gRPC serves any
    call whose socket cannot connect, unannounced, so whatever the
    socket tier promises a caller gRPC is held to as well."""
    monkeypatch.setenv(ENV_TRANSPORT, request.param)
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    return request.param


def _tier(client) -> str:
    return client._transport.name if client._transport else "grpc"


# -- tier selection -----------------------------------------------------------


def test_mode_default_and_unknown(monkeypatch):
    # unset: a local peer gets the Unix-socket carrier (never `auto`:
    # no inproc); an explicit grpc or an unknown value is grpc
    monkeypatch.delenv(ENV_TRANSPORT, raising=False)
    assert transport.transport_mode() == "uds"
    assert transport.server_fast_paths_enabled()
    monkeypatch.setenv(ENV_TRANSPORT, "grpc")
    assert transport.transport_mode() == "grpc"
    assert not transport.server_fast_paths_enabled()
    monkeypatch.setenv(ENV_TRANSPORT, "warp-drive")
    assert transport.transport_mode() == "grpc"
    monkeypatch.setenv(ENV_TRANSPORT, "AUTO")
    assert transport.transport_mode() == "auto"


def test_select_grpc_mode_returns_none(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    monkeypatch.setenv(ENV_TRANSPORT, "grpc")
    assert transport.select_transport("localhost:12345") is None
    # unset and local, but no listener there: gRPC
    monkeypatch.delenv(ENV_TRANSPORT, raising=False)
    assert transport.select_transport("localhost:12345") is None


def test_select_remote_host_falls_back(monkeypatch):
    monkeypatch.setenv(ENV_TRANSPORT, "auto")
    assert transport.select_transport("ps-7.example.com:50051") is None
    assert transport.select_transport("not-an-endpoint") is None


def test_select_local_without_counterpart_falls_back(
    monkeypatch, tmp_path
):
    """Local host but no registered dispatcher and no socket file:
    conservative fallback to gRPC, never a broken fast path."""
    monkeypatch.setenv(ENV_TRANSPORT, "auto")
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    assert transport.select_transport("localhost:45999") is None


def test_select_auto_prefers_inproc_over_uds(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_TRANSPORT, "auto")
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    disp = transport.ServerDispatcher({}, WireStats("t"))
    transport.register_inproc(45998, disp)
    try:
        # socket file ALSO present; inproc must win (fewer copies)
        path = transport.uds_path_for(45998)
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.bind(path)
        try:
            t = transport.select_transport("localhost:45998")
            assert t is not None and t.name == "inproc"
        finally:
            s.close()
            os.unlink(path)
    finally:
        transport.unregister_inproc(45998)


def test_link_tier_pins_one_link_and_unknown_means_ambient(
    monkeypatch, tmp_path
):
    """`tier` overrides the process's mode for ONE link (the
    aggregator's upstream leg). A value that is not a tier decides
    nothing: the ambient mode does, as for an unknown EDL_TRANSPORT."""
    monkeypatch.delenv(ENV_TRANSPORT, raising=False)
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    addr = f"localhost:{server.port}"
    try:
        assert transport.select_transport(addr, tier="grpc") is None
        assert transport.select_transport(addr, tier=" UDS ").name == "uds"
        assert transport.select_transport(addr, tier="auto").name == "inproc"
        assert transport.select_transport(addr, tier="warp").name == "uds"
        monkeypatch.setenv(ENV_TRANSPORT, "grpc")
        assert transport.select_transport(addr, tier="warp") is None
        assert transport.select_transport(addr, tier="uds").name == "uds"
    finally:
        server.stop()


def test_endpoint_is_local_variants():
    assert transport.endpoint_is_local("localhost:1")
    assert transport.endpoint_is_local("127.0.0.1:1")
    assert transport.endpoint_is_local("[::1]:1")
    assert transport.endpoint_is_local(f"{socket.gethostname()}:1")
    assert not transport.endpoint_is_local("10.0.0.7:1")


def test_uds_dir_env_override(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    assert transport.uds_path_for(77) == str(tmp_path / "edl-uds-77.sock")


# -- round-trips over each tier ----------------------------------------------


def _roundtrip(client):
    resp = client.call("Echo", {"x": 41}, timeout=10)
    assert resp["x"] == 41
    np.testing.assert_array_equal(
        resp["arr"], np.arange(4, dtype=np.float32)
    )


@pytest.mark.parametrize("env_fixture", ["uds_env", "inproc_env", "grpc_env"])
def test_fast_tier_roundtrip_and_errors(env_fixture, request):
    """Echo round-trip plus the three failure classifications, on each
    tier — byte-identical semantics whichever carries the call."""
    request.getfixturevalue(env_fixture)
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        assert _tier(client) == os.environ[ENV_TRANSPORT]
        _roundtrip(client)
        # handler bug -> INTERNAL, sanitized single-line detail
        with pytest.raises(grpc.RpcError) as ei:
            client.call("Boom", {}, timeout=10)
        assert ei.value.code() == grpc.StatusCode.INTERNAL
        assert "ValueError" in ei.value.details()
        assert "\n" not in ei.value.details()
        # fencing -> FAILED_PRECONDITION, client-side classifier agrees
        with pytest.raises(grpc.RpcError) as ei:
            client.call("Fenced", {"epoch": 9}, timeout=10)
        assert ei.value.code() == grpc.StatusCode.FAILED_PRECONDITION
        assert is_fenced_error(ei.value)
    finally:
        client.close()
        server.stop()


def test_unknown_method_unimplemented(carrier):
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        with pytest.raises(grpc.RpcError) as ei:
            client.call("NoSuch", {}, timeout=5)
        assert ei.value.code() == grpc.StatusCode.UNIMPLEMENTED
    finally:
        client.close()
        server.stop()


def test_inproc_server_gone_is_unavailable(inproc_env):
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        _roundtrip(client)
        server.stop()  # unregisters the dispatcher
        with pytest.raises(grpc.RpcError) as ei:
            client.call("Echo", {"x": 1}, timeout=1)
        assert ei.value.code() in (
            grpc.StatusCode.UNAVAILABLE,
            grpc.StatusCode.DEADLINE_EXCEEDED,
        )
    finally:
        client.close()
        server.stop()


def test_server_gone_is_unavailable(carrier):
    """stop() severs pooled connections: the next call fails like a
    stopped gRPC server's, never hangs and never reaches a handler."""
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        assert _tier(client) == carrier
        _roundtrip(client)
        server.stop()
        # stopped, not stopping: inside stop()'s grace a gRPC server
        # still answers a new call, with CANCELLED
        server.wait()
        with pytest.raises(grpc.RpcError) as ei:
            client.call("Echo", {"x": 1}, timeout=1)
        assert ei.value.code() in (
            grpc.StatusCode.UNAVAILABLE,
            grpc.StatusCode.DEADLINE_EXCEEDED,
        )
    finally:
        client.close()
        server.stop()


def test_concurrent_calls_stay_paired(carrier):
    """The worker's pipelined reports overlap calls on one client; the
    connection pool (the channel's streams) must keep request/response
    frames paired."""
    from concurrent.futures import ThreadPoolExecutor

    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        with ThreadPoolExecutor(max_workers=8) as pool:
            futs = [
                pool.submit(client.call, "Echo", {"x": i}, 30)
                for i in range(32)
            ]
            got = sorted(f.result()["x"] for f in futs)
        assert got == list(range(32))
    finally:
        client.close()
        server.stop()


def test_uds_large_payload_roundtrip(uds_env):
    """A multi-megabyte codec frame (a real model delta) crosses the
    socket intact — exercises the chunked recv_into path."""
    vec = np.random.default_rng(3).standard_normal(1 << 19).astype(np.float32)

    def big(req):
        np.testing.assert_array_equal(req["v"], vec)
        return {"v": req["v"] * 2}

    server = RpcServer({"Big": big}, port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        assert client._transport is not None
        resp = client.call("Big", {"v": vec}, timeout=30)
        np.testing.assert_allclose(resp["v"], vec * 2)
    finally:
        client.close()
        server.stop()


# -- chaos injection on the fast paths ---------------------------------------


def test_client_error_injection_retried(carrier):
    hits = []
    server = RpcServer(_echo_handlers(hits), port=0)
    server.start()
    plan = FaultPlan.from_spec(
        {"faults": [{"kind": "error", "methods": ["Echo"], "nth": 1}]}
    )
    client = RpcClient(
        f"localhost:{server.port}", policy=fast_policy(), fault_plan=plan
    )
    try:
        assert _tier(client) == carrier
        assert client.call("Echo", {"x": 1}, timeout=10, idempotent=True)[
            "x"
        ] == 1
        assert hits == [1], "injected attempt must never reach the server"
    finally:
        client.close()
        server.stop()


def test_drop_applies_then_retry_reaches_server(carrier):
    """One contract for the socket tier's hooks and the gRPC
    interceptor: a dropped response means the handler RAN; the retry
    hits the server a second time (which is why mutating ops carry
    report_keys)."""
    hits = []
    server = RpcServer(_echo_handlers(hits), port=0)
    server.start()
    plan = FaultPlan.from_spec(
        {"faults": [{"kind": "drop", "methods": ["Echo"], "nth": 1}]}
    )
    client = RpcClient(
        f"localhost:{server.port}", policy=fast_policy(), fault_plan=plan
    )
    try:
        assert client.call("Echo", {"x": 7}, timeout=10, idempotent=True)[
            "x"
        ] == 7
        assert hits == [7, 7]
    finally:
        client.close()
        server.stop()


def test_inproc_server_side_error_injection(inproc_env):
    hits = []
    plan = FaultPlan.from_spec(
        {
            "faults": [
                {"kind": "error", "methods": ["Echo"], "side": "server",
                 "nth": 1, "code": "UNAVAILABLE"}
            ]
        }
    )
    server = RpcServer(_echo_handlers(hits), port=0, fault_plan=plan)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        assert client._transport is not None
        assert client.call("Echo", {"x": 2}, timeout=10, idempotent=True)[
            "x"
        ] == 2
        # server-side injection fires before the handler; retry landed
        assert hits == [2]
    finally:
        client.close()
        server.stop()


def test_injected_error_is_policy_error(carrier):
    """Non-idempotent calls surface the injected error unretried, as
    the exact class the policy/chaos stack uses everywhere."""
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    plan = FaultPlan.from_spec(
        {"faults": [{"kind": "error", "methods": ["Echo"], "nth": 1}]}
    )
    client = RpcClient(
        f"localhost:{server.port}", policy=fast_policy(), fault_plan=plan
    )
    try:
        with pytest.raises(InjectedRpcError) as ei:
            client.call("Echo", {"x": 1}, timeout=10, idempotent=False)
        assert isinstance(ei.value, PolicyRpcError)
    finally:
        client.close()
        server.stop()


# -- WireStats transport dimension -------------------------------------------


def test_wire_stats_transport_rows():
    w = WireStats("t")
    w.record("M", sent=100, transport="grpc")
    w.record("M", received=50, transport="grpc")
    w.record("M", sent=30, received=7, transport="uds")
    w.record("M", sent=0, received=0, transport="inproc", calls=1)
    snap = w.snapshot()
    assert snap["bytes_sent"] == 130
    assert snap["bytes_received"] == 57
    t = snap["transports"]
    assert t["grpc"] == {"bytes_sent": 100, "bytes_received": 50, "calls": 1}
    assert t["uds"] == {"bytes_sent": 30, "bytes_received": 7, "calls": 1}
    # the inproc row proves the call HAPPENED with zero wire bytes
    assert t["inproc"] == {"bytes_sent": 0, "bytes_received": 0, "calls": 1}
    w.reset()
    assert w.snapshot()["transports"] == {}


def test_wire_stats_aggregate_mixed_tiers():
    """Per-endpoint snapshots from a mixed fan-out (some shards over
    gRPC, one co-located over UDS, one inproc) roll up per tier AND in
    total — the bytes-per-sync bench splits on exactly this."""
    a, b, c = WireStats("a"), WireStats("b"), WireStats("c")
    a.record("Push", sent=400, received=20, transport="grpc")
    b.record("Push", sent=100, received=5, transport="uds")
    c.record("Push", sent=0, received=0, transport="inproc", calls=1)
    agg = aggregate_wire_snapshots(
        [a.snapshot(), b.snapshot(), c.snapshot()]
    )
    assert agg["bytes_sent"] == 500
    assert agg["bytes_received"] == 25
    assert agg["methods"]["Push"]["calls"] == 3
    t = agg["transports"]
    assert t["grpc"]["bytes_sent"] == 400
    assert t["uds"]["bytes_sent"] == 100
    assert t["inproc"] == {"bytes_sent": 0, "bytes_received": 0, "calls": 1}


def test_wire_stats_aggregate_tolerates_legacy_snapshots():
    """Snapshots from an older process (no "transports" key) still
    aggregate — rolling upgrades must not crash the rollup."""
    w = WireStats("new")
    w.record("M", sent=10, transport="uds")
    legacy = {
        "bytes_sent": 5,
        "bytes_received": 1,
        "methods": {"M": {"bytes_sent": 5, "bytes_received": 1, "calls": 1}},
    }
    agg = aggregate_wire_snapshots([legacy, w.snapshot()])
    assert agg["bytes_sent"] == 15
    assert agg["transports"]["uds"]["bytes_sent"] == 10


def test_endpoint_accounting_over_uds_matches_grpc(uds_env, monkeypatch):
    """The client's per-endpoint WireStats must tally UDS payload bytes
    exactly like gRPC would (same codec frames, tier label aside), and
    the server's side must mirror them."""
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        client.wire.reset()
        _roundtrip(client)
        snap = client.wire.snapshot()
        assert list(snap["transports"]) == ["uds"]
        row = snap["transports"]["uds"]
        assert row["bytes_sent"] > 0 and row["bytes_received"] > 0
        srv = server.wire.snapshot()["transports"]["uds"]
        # client sent == server received, and vice versa
        assert srv["bytes_received"] == row["bytes_sent"]
        assert srv["bytes_sent"] == row["bytes_received"]
    finally:
        client.close()
        server.stop()


def test_inproc_calls_report_zero_wire_bytes(inproc_env):
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        client.wire.reset()
        for i in range(3):
            client.call("Echo", {"x": i}, timeout=10)
        snap = client.wire.snapshot()
        assert snap["bytes_sent"] == 0 and snap["bytes_received"] == 0
        assert snap["transports"]["inproc"]["calls"] == 3
        assert snap["methods"]["Echo"]["calls"] == 3
        srv = server.wire.snapshot()["transports"]["inproc"]
        assert srv == {"bytes_sent": 0, "bytes_received": 0, "calls": 3}
    finally:
        client.close()
        server.stop()


# -- dispatcher conformance ---------------------------------------------------


def test_dispatcher_methods_match_handler_table():
    h = _echo_handlers()
    disp = transport.ServerDispatcher(h, WireStats("t"))
    assert disp.methods() == frozenset(h)


def test_uds_path_rendezvous_is_port_keyed(monkeypatch, tmp_path):
    """Parent and shard subprocesses agree on the socket path from the
    endpoint port alone (master/shard_host.py pins ENV_UDS_DIR)."""
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    assert transport.uds_path_for(50051) == transport.uds_path_for(50051)
    assert transport.uds_path_for(50051) != transport.uds_path_for(50052)


# -- the tier registry ---------------------------------------------------------


def test_transport_tiers_registry():
    """The tier registry is the single source the lint rules, docs and
    benches enumerate — adding a tier without registering it here is
    the drift the static-analysis suite exists to catch."""
    assert transport.TRANSPORT_TIERS == (
        transport.TRANSPORT_GRPC,
        transport.TRANSPORT_UDS,
        transport.TRANSPORT_INPROC,
    )


# -- resource lifecycle on the failure paths (regressions) --------------------


@pytest.fixture
def captured_sockets(monkeypatch):
    """Every AF_UNIX socket the code under test creates, so the
    failure-path tests can assert the fd was actually released."""
    created = []
    real_socket = socket.socket

    def capture(*args, **kwargs):
        s = real_socket(*args, **kwargs)
        created.append(s)
        return s

    monkeypatch.setattr(transport.socket, "socket", capture)
    return created


def test_uds_server_bind_failure_closes_socket(
    monkeypatch, tmp_path, captured_sockets
):
    # regression: a half-built listener has no owner — __init__ raised
    # out of bind() with the fd still open, and every boot retry
    # against an unusable path leaked another one
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path / ("x" * 200)))
    disp = transport.ServerDispatcher(_echo_handlers(), WireStats("t"))
    with pytest.raises(OSError):
        transport.UdsServer(45997, disp)
    assert captured_sockets
    assert all(s.fileno() == -1 for s in captured_sockets)


def test_async_uds_server_bind_failure_closes_socket(
    monkeypatch, tmp_path, captured_sockets
):
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path / ("x" * 200)))
    disp = transport.ServerDispatcher(_echo_handlers(), WireStats("t"))
    with pytest.raises(OSError):
        transport.AsyncUdsServer(45997, disp, core=object())
    assert captured_sockets
    assert all(s.fileno() == -1 for s in captured_sockets)


def test_uds_transport_close_drains_pool(tmp_path):
    # regression: UdsTransport had no close() at all — RpcClient's
    # hasattr('close') hook found nothing and a dropped client
    # stranded up to 8 pooled fds until GC
    class _Conn:
        def __init__(self):
            self.closed = False

        def close(self):
            self.closed = True

    t = transport.UdsTransport(str(tmp_path / "never.sock"))
    conns = [_Conn(), _Conn(), _Conn()]
    t._pool = list(conns)
    t.close()
    assert all(c.closed for c in conns)
    assert t._pool == []


# -- the unset default: a local peer gets the local carrier -------------------


@pytest.fixture
def unset_env(monkeypatch, tmp_path):
    monkeypatch.delenv(ENV_TRANSPORT, raising=False)
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))


@pytest.fixture
def client_log():
    """Records of rpc/client.py's logger (it does not propagate)."""
    import logging

    from elasticdl_tpu.rpc import client as client_mod

    records = []

    class _Keep(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = _Keep(level=logging.INFO)
    client_mod.logger.addHandler(handler)
    yield records
    client_mod.logger.removeHandler(handler)


def _stale_socket_file(port: int) -> str:
    """What a SIGKILLed server leaves: a socket file nothing listens
    behind."""
    path = transport.uds_path_for(port)
    dead = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    dead.bind(path)
    dead.close()
    assert os.path.exists(path)
    return path


def test_unset_local_endpoint_with_live_server_selects_uds(
    unset_env, client_log
):
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    addr = f"localhost:{server.port}"
    client = RpcClient(addr, policy=fast_policy())
    try:
        assert os.path.exists(transport.uds_path_for(server.port))
        assert client._transport is not None
        assert client._transport.name == "uds"
        assert f"link {addr}: uds" in client_log
        # the row is the process's for this endpoint, and the kernel
        # hands a port out again: count from here, not from whichever
        # earlier test's server had the number
        client.wire.reset()
        _roundtrip(client)
        by_tier = client.wire.snapshot()["transports"]
        assert by_tier["uds"]["calls"] == 1 and "grpc" not in by_tier
        # the hostname form of this host is local too
        assert transport.select_transport(
            f"{socket.gethostname()}:{server.port}"
        ).name == "uds"
    finally:
        client.close()
        server.stop()
    assert not os.path.exists(transport.uds_path_for(server.port))


def test_unset_remote_host_selects_grpc(unset_env, client_log):
    """A non-local host string gets gRPC even when a socket file of
    the same port number lies in this host's directory (the k8s path
    advertises pod IPs; another pod's port is not this host's)."""
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    try:
        port = server.port
        assert os.path.exists(transport.uds_path_for(port))
        assert transport.select_transport(f"10.0.0.7:{port}") is None
        assert transport.select_transport(f"ps-7.example.com:{port}") is None
        client = RpcClient(f"ps-7.example.com:{port}", policy=fast_policy())
        try:
            assert client._transport is None
            assert f"link ps-7.example.com:{port}: grpc" in client_log
        finally:
            client.close()
    finally:
        server.stop()


def test_explicit_grpc_is_pure_grpc_and_opens_no_listener(
    monkeypatch, tmp_path, client_log
):
    monkeypatch.setenv(ENV_TRANSPORT, "grpc")
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    addr = f"localhost:{server.port}"
    client = RpcClient(addr, policy=fast_policy())
    try:
        assert server._uds is None
        assert os.listdir(str(tmp_path)) == []
        assert client._transport is None
        assert f"link {addr}: grpc" in client_log
        client.wire.reset()
        _roundtrip(client)
        assert set(client.wire.snapshot()["transports"]) == {"grpc"}
    finally:
        client.close()
        server.stop()


def _big_tree(seed: int, mb: int = 64):
    """A nested tree of `mb` MB and a bit: bf16 and f32 leaves, far
    above the socket buffer (208 KiB)."""
    import ml_dtypes

    rng = np.random.default_rng(seed)
    n = mb * (1 << 20) // 4
    f32 = rng.standard_normal(n // 2, dtype=np.float32)
    bf16 = rng.standard_normal(n, dtype=np.float32).astype(ml_dtypes.bfloat16)
    return {
        "delta": {
            "layer0": {"kernel": f32.reshape(-1, 256), "bias": f32[:7].copy()},
            "layer1": [bf16.reshape(128, -1), {"scale": bf16[:33].copy()}],
        },
        "steps": 8,
    }


def _assert_bit_equal(got, want):
    import jax

    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("env_fixture", ["unset_env", "uds_env", "grpc_env"])
def test_large_frame_roundtrip_is_bit_equal(env_fixture, request):
    """A 64 MB+ nested frame up and the same frame down: every leaf
    arrives bit for bit, on the default carrier and on both explicit
    ones."""
    request.getfixturevalue(env_fixture)
    seen = {}

    def mirror(req):
        seen["nbytes"] = sum(
            a.nbytes for a in (
                req["delta"]["layer0"]["kernel"], req["delta"]["layer1"][0]
            )
        )
        return req

    server = RpcServer({"Mirror": mirror}, port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        expected = os.environ.get(ENV_TRANSPORT, "uds")
        assert _tier(client) == expected
        client.wire.reset()
        tree = _big_tree(seed=3)
        resp = client.call("Mirror", tree, timeout=120, idempotent=False)
        assert seen["nbytes"] >= 64 * (1 << 20)
        _assert_bit_equal(resp, tree)
        # decoded leaves are read-only views of the received frame,
        # as they were over the `bytes` it used to be copied into
        assert not resp["delta"]["layer0"]["kernel"].flags.writeable
        row = client.wire.snapshot()["transports"][expected]
        assert row["bytes_sent"] >= 64 * (1 << 20)
        assert row["bytes_received"] >= 64 * (1 << 20)
    finally:
        client.close()
        server.stop()


@pytest.mark.parametrize("env_fixture", ["unset_env", "grpc_env"])
def test_arrays_of_request_n_survive_request_n_plus_1(env_fixture, request):
    """The reuse guard. The master keeps views of a request past its
    handler (`grads_to_wait` > 1 accumulation, fan-in); the receive
    path hands out the very buffer `recv_into` filled, so if that
    buffer were ever reused for the connection's next frame the kept
    arrays would silently change. Same on the client for responses."""
    request.getfixturevalue(env_fixture)
    kept = []

    def keep(req):
        kept.append(req["grad"])
        # a frame of its own per response
        return {"model": np.full(2 << 20, float(len(kept)), np.float32)}

    server = RpcServer({"Keep": keep}, port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        n = 2 << 20  # 8 MB of f32
        models = []
        for i in range(1, 4):
            resp = client.call(
                "Keep", {"grad": np.full(n, float(i), np.float32)},
                timeout=60, idempotent=False,
            )
            models.append(resp["model"])
        if client._transport is not None:
            # one pooled connection carried all three
            assert len(client._transport._pool) == 1
        for i, (grad, model) in enumerate(zip(kept, models), start=1):
            assert grad.shape == (n,) and model.shape == (n,)
            assert float(grad.min()) == float(grad.max()) == float(i)
            assert float(model.min()) == float(model.max()) == float(i)
    finally:
        client.close()
        server.stop()


# -- a connection receives into the memory it already holds -------------------


_LARGE = 2 << 20  # float32s: an 8 MB frame, over `keep_from_1mb`
_OTHER = _LARGE + (1 << 19)  # 2 MB more: another size of kept memory
_SMALL = 1 << 15  # 128 KB: under it


@pytest.fixture
def keep_from_1mb(monkeypatch):
    """The threshold is the chip's host's (32 MiB: PERF.md, PR 31);
    the rule is the same from 1 MiB on, with frames a test can afford."""
    monkeypatch.setattr(transport, "KEEP_FRAME_BYTES", 1 << 20)


def _frame_memory(arr):
    """The array that owns the memory `arr`'s frame lay in: down the
    bases to the frame's `memoryview`, then what that was exported by
    (a lease on the connection's memory, or a frame's own buffer).
    None where the frame is gRPC's `bytes`."""
    while isinstance(arr, np.ndarray):
        arr = arr.base
    if not isinstance(arr, memoryview):
        return None
    owner = arr.obj
    return owner if owner.base is None else owner.base


class _ReceivingEnd:
    """One server, one client, one pooled connection, and frames that
    travel in `direction`: `recv` moves one frame of `n` float32s of
    `value` and gives what its receiver decoded: the array's address,
    a weak reference to the memory its frame lay in, and the array
    itself where the receiver keeps it (the handler's `kept` list for
    a request: the master's accumulation; the caller's hand for a
    response)."""

    def __init__(self, direction, server_kw=None, **client_kw):
        self.direction = direction
        self.kept = []
        self._keep = False
        self._seen = None
        self.server = RpcServer(
            {"Move": self._move}, port=0, **(server_kw or {})
        )
        self.server.start()
        self.client = RpcClient(
            f"localhost:{self.server.port}", policy=fast_policy(), **client_kw
        )
        self.socket = _tier(self.client) == "uds"

    def _note(self, arr):
        memory = _frame_memory(arr)
        return (
            arr.__array_interface__["data"][0],
            None if memory is None else weakref.ref(memory),
        )

    def _move(self, req):
        if self.direction == "response":
            return {"x": np.full(req["n"], req["value"], np.float32),
                    "version": 1}
        x = req["x"]
        assert float(x.min()) == float(x.max()) == req["value"]
        self._seen = self._note(x)
        if self._keep:
            self.kept.append(x)
        return {"version": 1}

    def recv(self, value, n=_LARGE, keep=False):
        if self.direction == "response":
            resp = self.client.call(
                "Move", {"n": n, "value": value}, timeout=60, idempotent=False
            )
            arr = resp["x"]
            assert float(arr.min()) == float(arr.max()) == value
            if keep:
                self.kept.append(arr)
            return self._note(arr)
        self._keep = keep
        self.client.call(
            "Move", {"x": np.full(n, value, np.float32), "value": value},
            timeout=60, idempotent=False,
        )
        return self._seen

    def close(self):
        self.client.close()
        self.server.stop()


def _dies(ref, timeout=10.0):
    """Whether the weakly referenced memory goes within `timeout` (a
    server's connection thread frees its own once it reads the EOF)."""
    deadline = time.monotonic() + timeout
    while ref() is not None and time.monotonic() < deadline:
        time.sleep(0.01)
    return ref() is None


def _kept_nothing(end):
    """(a) A receiver that keeps nothing: the second large frame of the
    same size lies where the first did; one of another size replaces
    the memory (the old freed, not kept beside) and is reused in turn."""
    first, memory = end.recv(1.0)
    second, again = end.recv(2.0)
    if end.socket:  # the buffer itself, not an address malloc gave twice
        assert again() is memory() is not None
    del again
    third, _ = end.recv(3.0, n=_OTHER)
    fourth, _ = end.recv(4.0, n=_OTHER)
    end.recv(5.0)  # and back to the first size
    if end.socket:
        assert second == first
        assert fourth == third
        assert _dies(memory)


def _kept_one(end):
    """(b) A receiver that keeps an array of frame n: frame n+1 lies
    elsewhere, frame n+2 where n+1 did, and the kept array reads frame
    n's values after both."""
    first, _ = end.recv(1.0, keep=True)
    second, _ = end.recv(2.0)
    third, _ = end.recv(3.0)
    (kept,) = end.kept
    assert float(kept.min()) == float(kept.max()) == 1.0
    assert kept.__array_interface__["data"][0] == first
    if end.socket:
        assert second != first
        assert third == second


def _dropped_later(end):
    """(c) A kept array that is dropped later gives the memory back:
    the frame after that lies where the kept one did."""
    first, memory = end.recv(1.0, keep=True)
    second, spare = end.recv(2.0)
    assert float(end.kept[0].max()) == 1.0
    del end.kept[:]
    third, _ = end.recv(3.0)
    if end.socket:
        assert second != first
        assert third == first
        assert memory() is not None
        # one buffer a connection, never two: frame n+1's went when
        # frame n's came back
        assert _dies(spare)


def _closed(end):
    """(d) A closed connection holds no memory; an array that outlives
    it still reads its frame, and takes the memory with it when it
    goes."""
    _, idle = end.recv(1.0)
    _, held = end.recv(2.0, keep=True)
    if end.socket:
        assert idle() is held() is not None
    end.close()
    (kept,) = end.kept
    assert float(kept.min()) == float(kept.max()) == 2.0
    del kept, end.kept[:]
    if end.socket:
        assert _dies(held)


def _small(end):
    """(e) A frame under the threshold never keeps memory: it lies in
    a buffer of its own, which goes with its last array while the
    connection lives on."""
    assert _SMALL * 4 < transport.KEEP_FRAME_BYTES
    first, memory = end.recv(1.0, n=_SMALL, keep=True)
    end.recv(2.0, n=_SMALL)
    (kept,) = end.kept
    assert float(kept.min()) == float(kept.max()) == 1.0
    if end.socket:
        # the frame's own buffer, not a lease on a longer one
        assert _frame_memory(kept).nbytes < _SMALL * 4 + 4096
        del kept, end.kept[:]
        assert _dies(memory, timeout=1.0)
        assert len(end.client._transport._pool) == 1


_REUSE_CASES = {
    "kept_nothing": _kept_nothing,
    "kept_one": _kept_one,
    "dropped_later": _dropped_later,
    "closed": _closed,
    "small": _small,
}


@pytest.mark.parametrize("env_fixture", ["unset_env", "grpc_env"])
@pytest.mark.parametrize("direction", ["request", "response"])
@pytest.mark.parametrize("case", sorted(_REUSE_CASES))
def test_a_connection_receives_into_the_memory_it_holds(
    case, direction, env_fixture, request, keep_from_1mb
):
    """Beside the reuse guard above: a connection lends the memory of
    its last large frame to the next one exactly when nothing reads
    the old frame any more. Requests into the server and responses
    into the client alike; over gRPC, which has no such memory, the
    same traffic reads the same values."""
    request.getfixturevalue(env_fixture)
    end = _ReceivingEnd(direction)
    try:
        assert end.socket == (env_fixture == "unset_env")
        _REUSE_CASES[case](end)
        if end.socket and case != "closed":
            # one pooled connection carried every frame
            assert len(end.client._transport._pool) == 1
    finally:
        end.close()


def test_a_peers_large_requests_keep_to_one_connection(
    unset_env, keep_from_1mb
):
    """A worker's threads leave several connections in the pool (the
    sync thread's, the task loop's). Its large requests go by the one
    that carried the last, whichever order the pool holds them in, and
    its small ones by another: the server keeps one buffer a peer."""
    end = _ReceivingEnd("request")
    try:
        pool = end.client._transport
        two = [pool._checkout(), pool._checkout()]
        for conn in two:
            pool._checkin(conn)
        _, memory = end.recv(1.0)
        for value in (2.0, 3.0, 4.0):
            end.recv(value, n=_SMALL)
            for conn in [pool._checkout(), pool._checkout()]:
                pool._checkin(conn)  # the other way round each time
            _, again = end.recv(value)
            assert again() is memory() is not None
            del again
        assert sorted(conn.large for conn in two) == [False, True]
        assert len(pool._pool) == 2
    finally:
        end.close()


class _Spans:
    """A `PhaseTimers` stand-in that keeps what the dispatcher records."""

    def __init__(self):
        self.records = []

    def record(self, name, t0, t1, **args):
        self.records.append((name, args))


@pytest.mark.parametrize("env_fixture", ["unset_env", "grpc_env"])
def test_the_spans_say_whether_the_memory_was_reused(
    env_fixture, request, timeline_spans, keep_from_1mb
):
    """The master's `rpc.decode` of a request and the client's round
    trip carry `recv_reused` (false for a connection's first frame,
    for a small one and on gRPC), and the round trip the socket
    buffers as the kernel granted them."""
    request.getfixturevalue(env_fixture)
    timers = _Spans()
    end = _ReceivingEnd(
        "request", timeline=("Move",),
        server_kw={"timers": timers, "timed_methods": ("Move",)},
    )
    try:
        for value in (1.0, 2.0, 3.0):
            end.recv(value)
        end.recv(4.0, n=_SMALL)
        uds = end.socket
        if uds:
            (conn,) = end.client._transport._pool
            granted = {
                "sndbuf": conn.getsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF),
                "rcvbuf": conn.getsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF),
            }
            # asked for more than the kernel's default, and got it or
            # the cap; Linux reports twice what it grants
            assert granted["sndbuf"] > 212992
            assert granted["sndbuf"] <= 2 * transport.SOCKET_BUFFER_BYTES
    finally:
        end.close()
    decodes = [a for name, a in timers.records if name == "rpc.decode"]
    assert [a["recv_reused"] for a in decodes] == [False, uds, uds, False]
    trips = [s["args"] for s in timeline_spans("rpc.client.Move")]
    # the acknowledgement is a small frame: never reused
    assert [a["recv_reused"] for a in trips] == [False] * 4
    for args in trips:
        if uds:
            assert {k: args[k] for k in granted} == granted
        else:
            assert "sndbuf" not in args and "rcvbuf" not in args


def test_a_large_response_says_reused_on_the_clients_span(
    unset_env, timeline_spans, keep_from_1mb
):
    end = _ReceivingEnd("response", timeline=("Move",))
    try:
        for value in (1.0, 2.0, 3.0):
            end.recv(value)
    finally:
        end.close()
    trips = [s["args"] for s in timeline_spans("rpc.client.Move")]
    assert [a["recv_reused"] for a in trips] == [False, True, True]


# -- a frame the socket header cannot describe --------------------------------


def test_oversize_request_is_refused_unsent_and_unretried(
    unset_env, monkeypatch
):
    """The header's length field is a u32. A request it cannot say is
    refused before a byte leaves, with a status the policy does not
    retry (the link is not down: the same frame would be refused
    again) and a message that names the size and the limit."""
    monkeypatch.setattr(transport, "MAX_FRAME_BYTES", 4096)
    hits = []
    server = RpcServer(_echo_handlers(hits), port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    attempts = []
    real_call = client._transport.call

    def counted(*args):
        attempts.append(args[0])
        return real_call(*args)

    monkeypatch.setattr(client._transport, "call", counted)
    try:
        with pytest.raises(PolicyRpcError) as ei:
            client.call(
                "Echo", {"x": np.zeros(4096, np.float32)},
                timeout=10, idempotent=True,
            )
        assert ei.value.code() == grpc.StatusCode.OUT_OF_RANGE
        assert "4096" in ei.value.details()
        assert "request frame of Echo is 16" in ei.value.details()
        assert attempts == ["Echo"] and hits == []
        assert server.wire.snapshot()["bytes_received"] == 0
        # a frame that fits is carried as before, on the same client
        _roundtrip(client)
        assert _tier(client) == "uds"
    finally:
        client.close()
        server.stop()


@pytest.mark.parametrize("core", ["threads", "loop"])
def test_oversize_response_is_refused_by_the_server(
    unset_env, monkeypatch, core
):
    """The response side of the same field, on both listeners: the
    handler's answer is not sent, the client gets the refusal as a
    status (not a reset, which it would take for a dead link and
    retry), and the connection serves the next call."""
    from elasticdl_tpu.common.constants import ENV_DISPATCH

    monkeypatch.setenv(ENV_DISPATCH, core)
    monkeypatch.setattr(transport, "MAX_FRAME_BYTES", 4096)
    hits = []

    def big(req):
        hits.append(req["x"])
        return {"v": np.zeros(4096, np.float32)}

    server = RpcServer({"Big": big, **_echo_handlers()}, port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        assert _tier(client) == "uds"
        with pytest.raises(grpc.RpcError) as ei:
            client.call("Big", {"x": 1}, timeout=10, idempotent=True)
        assert ei.value.code() == grpc.StatusCode.OUT_OF_RANGE
        assert "response frame of Big is 16" in ei.value.details()
        assert "4096" in ei.value.details()
        assert hits == [1]  # answered once, not retried
        _roundtrip(client)
        assert len(client._transport._pool) == 1
    finally:
        client.close()
        server.stop()


def test_stale_socket_file_is_served_over_grpc_and_logged_once(
    monkeypatch, tmp_path, client_log
):
    """A master relaunched under EDL_TRANSPORT=grpc on a reused port
    (or a dead one's socket file, never swept) must not strand a
    worker whose rule says uds: the connect fails, the call goes over
    the channel the client holds anyway, and the log says so once."""
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    monkeypatch.setenv(ENV_TRANSPORT, "grpc")
    hits = []
    server = RpcServer(_echo_handlers(hits), port=0)  # no listener
    server.start()
    monkeypatch.delenv(ENV_TRANSPORT)
    path = _stale_socket_file(server.port)
    addr = f"localhost:{server.port}"
    client = RpcClient(addr, policy=fast_policy())
    try:
        assert client._transport.name == "uds"  # the file is there
        client.wire.reset()
        for i in range(3):
            assert client.call("Echo", {"x": i}, timeout=10)["x"] == i
        assert hits == [0, 1, 2]  # each served once
        fallbacks = [m for m in client_log if "carrier is down" in m]
        assert len(fallbacks) == 1 and "uds" in fallbacks[0]
        by_tier = client.wire.snapshot()["transports"]
        assert by_tier["grpc"]["calls"] == 3 and "uds" not in by_tier
        # ENOENT is the same story as ECONNREFUSED
        os.unlink(path)
        assert client.call("Echo", {"x": 9}, timeout=10)["x"] == 9
        assert len([m for m in client_log if "carrier is down" in m]) == 1
    finally:
        client.close()
        server.stop()


def test_carrier_down_draws_no_client_fault(unset_env):
    """The fallback happens before the FaultPlan is consulted, so the
    gRPC interceptor is the one injection layer of a call the channel
    serves: an `nth: 1` error fires exactly once, not once a tier."""
    plan = FaultPlan.from_spec({"faults": [
        {"kind": "error", "methods": ["Echo"], "side": "client", "nth": 1},
    ]})
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    server._uds.close()  # the listener dies, gRPC lives
    _stale_socket_file(server.port)
    client = RpcClient(
        f"localhost:{server.port}", policy=fast_policy(), fault_plan=plan
    )
    try:
        assert client._transport.name == "uds"
        resp = client.call("Echo", {"x": 5}, timeout=10, idempotent=True)
        assert resp["x"] == 5
        # two attempts, each counted once: the injected error, the retry
        assert (plan.faults[0]._count, plan.faults[0]._fires) == (2, 1)
    finally:
        client.close()
        server.stop()  # closing the listener again unlinks the file


def test_boot_sweeps_dead_sockets_and_spares_live_ones(unset_env):
    """Every RpcServer makes a socket file and a SIGKILLed one cannot
    remove its own: the next boot in the directory does, and leaves
    every listening neighbour alone."""
    neighbour = RpcServer(_echo_handlers(), port=0)
    neighbour.start()
    dead = _stale_socket_file(45997)
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    client = RpcClient(f"localhost:{neighbour.port}", policy=fast_policy())
    try:
        assert not os.path.exists(dead)
        assert os.path.exists(transport.uds_path_for(neighbour.port))
        assert client._transport.name == "uds"
        _roundtrip(client)
    finally:
        client.close()
        server.stop()
        neighbour.stop()


def test_socket_directory_deeper_than_an_af_unix_address(
    monkeypatch, tmp_path
):
    """sun_path holds 108 bytes; a TMPDIR inside a checkout is easily
    deeper. The carrier reaches its directory through a descriptor
    instead of silently meaning gRPC."""
    deep = tmp_path / ("d" * 60) / ("e" * 60)
    deep.mkdir(parents=True)
    monkeypatch.delenv(ENV_TRANSPORT, raising=False)
    monkeypatch.setenv(ENV_UDS_DIR, str(deep))
    assert len(transport.uds_path_for(50000)) > 108
    server = RpcServer(_echo_handlers(), port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        assert server._uds is not None
        assert client._transport.name == "uds"
        client.wire.reset()
        _roundtrip(client)
        assert set(client.wire.snapshot()["transports"]) == {"uds"}
    finally:
        client.close()
        server.stop()
    assert os.listdir(str(deep)) == []


# -- a request reaches the socket as its parts --------------------------------


class _FrameKeeper:
    """Stands where a `ServerDispatcher` does and keeps the bytes of
    every frame the listener hands it."""

    def __init__(self):
        self.frames = []

    def dispatch(self, method, frame, tier, recv_reused=False):
        self.frames.append((method, bytes(frame)))
        return messages.pack_parts({"ok": len(self.frames)})


@pytest.fixture
def keeper(unset_env):
    """A `UdsServer` over a `_FrameKeeper`, and a transport to it."""
    dispatcher = _FrameKeeper()
    server = transport.UdsServer(50123, dispatcher)
    server.start()
    client = transport.UdsTransport(server.path)
    yield dispatcher, client
    client.close()
    server.close()


def _rng(seed=0):
    return np.random.default_rng(seed)


_PART_TREES = {
    # leaf sizes off the 64-byte grid (a pad before each), a leaf long
    # enough to be written from where it lies, bf16, an empty leaf
    "unaligned": lambda: {
        "a": _rng(1).standard_normal(7, dtype=np.float32),
        "b": {"c": _rng(2).standard_normal(70001, dtype=np.float32),
              "d": np.arange(13, dtype=np.int64)},
        "e": [np.ones(3, np.float32).astype(codec._BFLOAT16),
              np.zeros(0, np.float32)],
        "v": 7, "key": "w0-t3",
    },
    "non_contiguous": lambda: {
        "t": _rng(3).standard_normal((300, 200), dtype=np.float32).T,
        "s": np.arange(40000, dtype=np.float32)[::2],
    },
    "indexed_rows": lambda: {
        "emb": codec.IndexedRows(
            values=_rng(4).standard_normal((1031, 17), dtype=np.float32),
            indices=np.arange(1031, dtype=np.int64) * 3,
        ),
    },
    "quantized_delta": lambda: {
        "delta": codec.QuantizedDelta(
            q=_rng(5).integers(-127, 128, 100003).astype(np.int8),
            scale=_rng(6).random(25, dtype=np.float32), chunk=4096, offset=5,
        ),
        "steps": 8,
    },
    "empty": lambda: {},
}


@pytest.mark.parametrize("tree", sorted(_PART_TREES))
def test_the_parts_on_the_socket_are_the_frame(keeper, tree):
    """What the listener reads from a request sent as its parts is,
    byte for byte, `codec.dumps` of that request: pads, a compacted
    leaf and the structured wire forms included. A receiver of either
    version reads either sender."""
    dispatcher, client = keeper
    request = _PART_TREES[tree]()
    payload = messages.pack_parts(request)
    frame = codec.dumps(request)
    assert len(payload) == len(frame)
    resp = client.call("Push", payload, 10.0)
    assert messages.unpack(resp) == {"ok": 1}
    assert dispatcher.frames == [("Push", frame)]
    assert not payload.joined
    # sent again (a retry), the same bytes
    client.call("Push", payload, 10.0)
    assert dispatcher.frames[1] == ("Push", frame)


def test_a_small_frame_is_one_write_and_a_long_leaf_leaves_uncopied(keeper):
    """A `GetTask` and its kind leave in one system call, header and
    all; a long leaf is gathered from the array it views, over as
    many turns as the socket buffer makes of it."""
    dispatcher, client = keeper
    turns = []

    class _Spy:
        def __init__(self, conn):
            self._conn = conn

        def sendmsg(self, bufs):
            turns.append(list(bufs))
            return self._conn.sendmsg(bufs)

        def __getattr__(self, name):
            return getattr(self._conn, name)

    real_checkout = client._checkout

    def checkout(large=False):  # the pool hands a checked-in spy back as it is
        conn = real_checkout(large)
        return conn if isinstance(conn, _Spy) else _Spy(conn)

    client._checkout = checkout
    client.call("GetTask", messages.pack_parts({"worker_id": 3}), 10.0)
    assert len(turns) == 1
    del turns[:]
    # several turns: four times what the kernel granted the connection
    granted = client._checkout()
    client._checkin(granted)
    big = np.arange(granted.sndbuf, dtype=np.float32)
    request = {"a": np.ones(5, np.float32), "big": big, "z": np.ones(9)}
    client.call("Push", messages.pack_parts(request), 10.0)
    assert len(turns) > 1
    # the leaf is offered whole from where it lies, and what a turn
    # left behind is offered again from there
    for bufs, whole in ((turns[0], True), (turns[1], False)):
        (leaf,) = [
            b for b in bufs
            if np.shares_memory(np.frombuffer(b, np.uint8), big)
        ]
        assert (leaf.nbytes == big.nbytes) == whole
    assert dispatcher.frames[1] == ("Push", codec.dumps(request))


def test_more_parts_than_iov_max_and_a_shrunk_socket_buffer(keeper):
    """Neither the gather list's limit nor the socket buffer's size
    bounds a frame: over 6,000 parts (`IOV_MAX` is 1024) through a
    send buffer smaller than one leaf arrive whole."""
    dispatcher, client = keeper
    conn = client._checkout()
    conn.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1024)
    client._checkin(conn)
    assert os.sysconf("SC_IOV_MAX") == transport._IOV_MAX < 3000
    rng = _rng(7)
    request = {
        f"leaf{i}": rng.standard_normal(1000 + i % 7, dtype=np.float32)
        for i in range(3000)
    }
    payload = messages.pack_parts(request)
    assert len(payload.parts) > 3000
    client.call("Push", payload, 60.0)
    assert len(client._pool) == 1  # the shrunk connection carried it
    assert dispatcher.frames == [("Push", codec.dumps(request))]
    assert not payload.joined


def test_the_deadline_is_one_budget_over_all_the_parts(unset_env, tmp_path):
    """A peer that stops reading fails the call once the call's budget
    is spent, however many parts are still to go: not a budget a part."""
    path = str(tmp_path / "stuck.sock")
    listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    listener.bind(path)
    listener.listen(1)  # accepts in the kernel, never reads
    client = transport.UdsTransport(path)
    request = {f"l{i}": np.zeros(1 << 18, np.float32) for i in range(40)}
    t0 = time.monotonic()
    try:
        with pytest.raises(PolicyRpcError) as ei:
            client.call("Push", messages.pack_parts(request), 0.5)
    finally:
        client.close()
        listener.close()
    assert ei.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
    assert time.monotonic() - t0 < 5.0  # 40 parts of 0.5 s each: 20 s


@pytest.mark.parametrize("kind", ["error", "drop"])
def test_a_retry_after_a_drawn_fault_resends_the_parts_intact(
    unset_env, kind
):
    """An injected client error costs an attempt before anything left;
    a dropped response one after the whole frame did. Either way the
    retry sends the same parts, and the server decodes what was
    meant."""
    seen = []

    def push(req):
        seen.append({k: np.array(v) for k, v in req["delta"].items()})
        return {"ok": True}

    server = RpcServer({"Push": push}, port=0)
    server.start()
    plan = FaultPlan.from_spec(
        {"faults": [{"kind": kind, "methods": ["Push"], "nth": 1}]}
    )
    client = RpcClient(
        f"localhost:{server.port}", policy=fast_policy(), fault_plan=plan
    )
    delta = {
        "w": _rng(8).standard_normal(100003, dtype=np.float32),
        "b": _rng(9).standard_normal(5, dtype=np.float32),
    }
    try:
        assert _tier(client) == "uds"
        client.wire.reset()
        resp = client.call(
            "Push", {"delta": delta}, timeout=10, idempotent=True
        )
        assert resp == {"ok": True}
        assert len(seen) == (2 if kind == "drop" else 1)
        for got in seen:
            _assert_bit_equal(got, delta)
        frame = len(codec.dumps({"delta": delta}))
        row = client.wire.snapshot()["transports"]["uds"]
        # both attempts tally the frame's length, unjoined as it is
        assert row["bytes_sent"] == 2 * frame
    finally:
        client.close()
        server.stop()


@pytest.fixture
def timeline_spans():
    from elasticdl_tpu.obs import trace

    trace.configure(0.0)  # the timeline needs no sampling
    trace.RECORDER.clear()
    yield lambda name: [
        s for s in trace.RECORDER.snapshot() if s["name"] == name
    ]
    trace.RECORDER.clear()
    trace.configure(None)


@pytest.fixture
def joins(monkeypatch):
    """Every `PackedParts` packed in this process (a call's request,
    then the server's response to it), and how often each was really
    joined."""
    made, counts = [], []
    real_pack, real_join = messages.pack_parts, messages.PackedParts.contiguous

    def pack_parts(obj):
        made.append(real_pack(obj))
        return made[-1]

    def contiguous(self, *timeout):
        if not self.joined:
            counts.append(id(self))
        return real_join(self, *timeout)

    monkeypatch.setattr(messages, "pack_parts", pack_parts)
    monkeypatch.setattr(messages.PackedParts, "contiguous", contiguous)
    return made, counts


def _span_args(spans, name):
    (span,) = spans(name)
    return span["args"]


def test_the_socket_carrier_never_joins_and_the_spans_say_so(
    unset_env, timeline_spans, joins
):
    """Neither way: not the request, not the response (PR 34)."""
    made, counts = joins
    timers = _Spans()
    server = RpcServer(
        _echo_handlers(), port=0, timers=timers, timed_methods=("Echo",)
    )
    server.start()
    client = RpcClient(
        f"localhost:{server.port}", policy=fast_policy(), timeline=("Echo",)
    )
    try:
        client.call("Echo", {"x": np.ones(1 << 16, np.float32)}, timeout=10)
    finally:
        client.close()
        server.stop()
    assert len(made) == 2 and counts == []
    request, response = made
    assert not request.joined and not response.joined
    encode = _span_args(timeline_spans, "rpc.client.encode")
    assert encode["parts"] == len(request.parts) >= 3
    assert encode["bytes"] == len(request)
    trip = _span_args(timeline_spans, "rpc.client.Echo")
    assert (trip["transport"], trip["joined"]) == ("uds", False)
    assert trip["bytes"] == len(request)
    (served,) = [a for name, a in timers.records if name == "rpc.encode"]
    assert served["joined"] is False
    assert served["parts"] == len(response.parts) >= 3
    assert served["bytes"] == len(response)


def test_the_grpc_fallback_joins_exactly_once_over_its_retries(
    unset_env, timeline_spans, joins
):
    """A call whose socket cannot connect is gRPC's, which needs one
    buffer: joined once, however many attempts send it."""
    made, counts = joins
    plan = FaultPlan.from_spec({"faults": [
        {"kind": "error", "methods": ["Echo"], "side": "client", "nth": 1},
    ]})
    hits = []
    server = RpcServer(_echo_handlers(hits), port=0)
    server.start()
    server._uds.close()
    _stale_socket_file(server.port)
    client = RpcClient(
        f"localhost:{server.port}", policy=fast_policy(), fault_plan=plan,
        timeline=("Echo",),
    )
    try:
        assert client._transport.name == "uds"
        resp = client.call("Echo", {"x": 5}, timeout=10, idempotent=True)
        assert resp["x"] == 5 and hits == [5]
        assert plan.faults[0]._count == 2  # two attempts over gRPC
    finally:
        client.close()
        server.stop()
    # the request, sent twice, and the one response: each joined once
    assert len(made) == 2 and counts == [id(made[0]), id(made[1])]
    assert made[0].contiguous() is made[0].contiguous()
    trip = _span_args(timeline_spans, "rpc.client.Echo")
    assert (trip["transport"], trip["joined"]) == ("grpc", True)
    assert _span_args(timeline_spans, "rpc.client.encode")["parts"] >= 2


@pytest.mark.parametrize("env_fixture", ["inproc_env", "grpc_env"])
def test_a_one_buffer_carrier_joins_exactly_once(
    env_fixture, request, timeline_spans, joins
):
    """The request, and the response the same (PR 34): joined inside
    the server's `rpc.encode` span, which says so."""
    request.getfixturevalue(env_fixture)
    made, counts = joins
    timers = _Spans()
    server = RpcServer(
        _echo_handlers(), port=0, timers=timers, timed_methods=("Echo",)
    )
    server.start()
    client = RpcClient(
        f"localhost:{server.port}", policy=fast_policy(), timeline=("Echo",)
    )
    try:
        tier = _tier(client)
        _roundtrip(client)
    finally:
        client.close()
        server.stop()
    assert len(made) == 2 and counts == [id(made[0]), id(made[1])]
    trip = _span_args(timeline_spans, "rpc.client.Echo")
    assert (trip["transport"], trip["joined"]) == (tier, True)
    (served,) = [a for name, a in timers.records if name == "rpc.encode"]
    assert served["joined"] is True and served["bytes"] == len(made[1])


def test_a_prepacked_request_passes_through_unjoined(keeper):
    """The one part its maker joined: sent as it lies on the socket,
    handed over as it is where one buffer is asked for."""
    dispatcher, client = keeper
    frame = codec.dumps({"delta": np.arange(9, dtype=np.float32)})
    payload = messages.pack_parts(messages.Prepacked(frame))
    assert len(payload) == len(frame) and payload.parts == [frame]
    client.call("Push", payload, 10.0)
    assert dispatcher.frames == [("Push", frame)] and not payload.joined
    assert payload.contiguous() is frame


# -- a piece of the frame may still be on its way (PR 45) ---------------------


class _Slices:
    """A float32 vector in pieces that land one by one, in order, from
    a thread of their own and at their own pace: what
    `worker/delta_stream.py` is to a request, without a device."""

    def __init__(self, vec, cuts, pace=0.0, fail_at=None, stop_at=None):
        self.vec = vec
        self.bounds = list(zip([0] + cuts, cuts + [vec.size]))
        self.events = [threading.Event() for _ in self.bounds]
        self.error = None
        self._pace, self._fail_at, self._stop_at = pace, fail_at, stop_at
        self.thread = threading.Thread(target=self._land, daemon=True)

    def _land(self):
        for i, event in enumerate(self.events):
            time.sleep(self._pace * (1 + i % 3))  # out of step with the send
            if i == self._stop_at:
                return  # this one and the rest never land
            if i == self._fail_at:
                self.error = RuntimeError(f"the copy of slice {i} failed")
                for e in self.events:
                    e.set()
                return
            event.set()

    def _wait(self, i, timeout):
        if not self.events[i].wait(timeout):
            raise TimeoutError(f"slice {i} has not landed")
        if self.error is not None and i >= self._fail_at:
            raise self.error
        lo, hi = self.bounds[i]
        return self.vec[lo:hi]

    def request(self):
        pieces = [
            codec.PendingPiece(hi - lo, lambda t, i=i: self._wait(i, t))
            for i, (lo, hi) in enumerate(self.bounds)
        ]
        return {"delta_flat": codec.LeafVector(pieces), "steps": 16,
                "aux_state": {"m": np.ones(5, np.float32)}, "report_key": "k"}

    def plain(self):
        return {**self.request(), "delta_flat": self.vec}


def _late(n=300_003, cuts=(70_001, 70_002, 200_000), **kw):
    return _Slices(_rng(45).standard_normal(n, dtype=np.float32), list(cuts), **kw)


@pytest.mark.parametrize("max_call", [None, 100_000], ids=["whole", "cut"])
def test_pieces_that_land_late_arrive_as_the_frame(keeper, monkeypatch, max_call):
    """What the listener reads is `head + b"".join(parts)` of the
    vector itself, though the pieces landed from another thread while
    the frame was leaving; with the bytes a call may move lowered, a
    piece is cut across turns like any long part."""
    if max_call:
        monkeypatch.setattr(transport, "MAX_CALL_BYTES", max_call)
    dispatcher, client = keeper
    slices = _late(pace=0.02)
    payload = messages.pack_parts(slices.request())
    frame = codec.dumps(slices.plain())
    assert payload.pending and len(payload) == len(frame)
    slices.thread.start()
    resp = client.call("ReportLocalUpdate", payload, 10.0)
    assert messages.unpack(resp) == {"ok": 1}
    assert dispatcher.frames == [("ReportLocalUpdate", frame)]
    assert payload.streamed and not payload.joined
    assert payload.waited > 0.02  # it stood waiting, and says for how long
    # sent again (a retry): from the host copies, waiting for nothing
    waited = payload.waited
    client.call("ReportLocalUpdate", payload, 10.0)
    assert dispatcher.frames[1] == ("ReportLocalUpdate", frame)
    assert payload.waited == waited


def test_a_retry_after_a_broken_connection_resends_the_same_bytes(keeper):
    """The first attempt's connection breaks with the frame half
    gone; the second goes out whole from what had landed by then and
    what lands still."""
    dispatcher, client = keeper
    slices = _late(pace=0.01)
    payload = messages.pack_parts(slices.request())
    real_checkout, broken = client._checkout, []

    class _Breaks:
        def __init__(self, conn):
            self._conn, self._turns = conn, 0

        def sendmsg(self, bufs):
            self._turns += 1
            if self._turns == 3:
                raise ConnectionResetError("cut")
            return self._conn.sendmsg(bufs)

        def __getattr__(self, name):
            return getattr(self._conn, name)

    def checkout(large=False):
        conn = real_checkout(large)
        if not broken:
            broken.append(conn)
            return _Breaks(conn)
        return conn

    client._checkout = checkout
    slices.thread.start()
    with pytest.raises(PolicyRpcError) as ei:
        client.call("ReportLocalUpdate", payload, 10.0)
    assert ei.value.code() == grpc.StatusCode.UNAVAILABLE
    assert broken[0].fileno() == -1 and dispatcher.frames == []
    client.call("ReportLocalUpdate", payload, 10.0)
    assert dispatcher.frames == [
        ("ReportLocalUpdate", codec.dumps(slices.plain()))
    ]


@pytest.mark.parametrize("kind", ["error", "drop"])
def test_a_drawn_fault_s_retry_resends_the_landed_pieces(unset_env, kind):
    """Through `RpcClient` and its policy: the dropped response costs
    a whole second frame, and the server decodes the same delta both
    times."""
    seen = []

    def update(req):
        seen.append(np.array(req["delta_flat"]))
        return {"version": len(seen)}

    server = RpcServer({"ReportLocalUpdate": update}, port=0)
    server.start()
    plan = FaultPlan.from_spec(
        {"faults": [{"kind": kind, "methods": ["ReportLocalUpdate"], "nth": 1}]}
    )
    client = RpcClient(
        f"localhost:{server.port}", policy=fast_policy(), fault_plan=plan
    )
    slices = _late(pace=0.01)
    slices.thread.start()
    try:
        resp = client.call(
            "ReportLocalUpdate", slices.request(), timeout=10, idempotent=True
        )
    finally:
        client.close()
        server.stop()
    assert resp == {"version": len(seen)} and len(seen) == (2 if kind == "drop" else 1)
    for got in seen:
        assert got.dtype == np.float32 and np.array_equal(got, slices.vec)


@pytest.mark.parametrize("env_fixture", ["inproc_env", "grpc_env"])
def test_a_one_buffer_carrier_waits_for_every_piece_and_joins_once(
    env_fixture, request, timeline_spans, joins
):
    """gRPC and `inproc` need the frame in one buffer: they wait for
    the pieces (inside the call's deadline) and join, which is what
    they did with a delta that was there; the span says `streamed:
    false` and how long the join waited."""
    request.getfixturevalue(env_fixture)
    made, counts = joins
    seen = []

    def update(req):
        seen.append(np.array(req["delta_flat"]))
        return {"version": 1}

    server = RpcServer({"ReportLocalUpdate": update}, port=0)
    server.start()
    client = RpcClient(
        f"localhost:{server.port}", policy=fast_policy(),
        timeline=("ReportLocalUpdate",),
    )
    slices = _late(pace=0.02)
    slices.thread.start()
    try:
        tier = _tier(client)
        client.call("ReportLocalUpdate", slices.request(), timeout=10)
    finally:
        client.close()
        server.stop()
    assert np.array_equal(seen[0], slices.vec)
    assert counts == [id(made[0]), id(made[1])]  # request, response: once each
    assert made[0].pending and made[0].joined and not made[0].streamed
    trip = _span_args(timeline_spans, "rpc.client.ReportLocalUpdate")
    assert (trip["transport"], trip["joined"], trip["streamed"]) == (
        tier, True, False
    )
    assert trip["waited_ms"] > 20


def test_the_socket_s_span_says_streamed_and_how_long_it_waited(
    unset_env, timeline_spans
):
    server = RpcServer({"ReportLocalUpdate": lambda req: {"version": 3}}, port=0)
    server.start()
    client = RpcClient(
        f"localhost:{server.port}", policy=fast_policy(),
        timeline=("ReportLocalUpdate",),
    )
    slices = _late(pace=0.02)
    slices.thread.start()
    try:
        client.call("ReportLocalUpdate", slices.request(), timeout=10)
        client.call("ReportLocalUpdate", slices.plain(), timeout=10)
    finally:
        client.close()
        server.stop()
    late, plain = [s["args"] for s in timeline_spans("rpc.client.ReportLocalUpdate")]
    assert (late["transport"], late["joined"], late["streamed"]) == ("uds", False, True)
    assert late["waited_ms"] > 20 and late["version"] == 3
    assert (plain["joined"], plain["streamed"], plain["waited_ms"]) == (False, False, 0.0)


def test_a_piece_that_fails_to_land_closes_the_connection(keeper):
    """The error comes out of the call as it was raised; the listener
    reads a peer that closed mid-frame, hands its dispatcher nothing,
    and serves the next frame, which comes by another connection."""
    dispatcher, client = keeper
    slices = _late(pace=0.01, fail_at=2)
    payload = messages.pack_parts(slices.request())
    conns, real_checkout = [], client._checkout

    def checkout(large=False):
        conns.append(real_checkout(large))
        return conns[-1]

    client._checkout = checkout
    slices.thread.start()
    with pytest.raises(RuntimeError, match="the copy of slice 2 failed"):
        client.call("ReportLocalUpdate", payload, 10.0)
    assert conns[0].fileno() == -1 and client._pool == []
    after = {"delta_flat": np.ones(9, np.float32)}
    resp = client.call("ReportLocalUpdate", messages.pack_parts(after), 10.0)
    assert messages.unpack(resp) == {"ok": 1}
    assert dispatcher.frames == [("ReportLocalUpdate", codec.dumps(after))]
    assert conns[1] is not conns[0]


def test_the_call_s_deadline_covers_the_waiting(keeper):
    """A piece that never lands fails the call when the call's budget
    is spent, as a peer that stops reading does: DEADLINE_EXCEEDED,
    the connection closed, nothing applied."""
    dispatcher, client = keeper
    slices = _late(pace=0.01, stop_at=1)
    slices.thread.start()
    t0 = time.monotonic()
    with pytest.raises(PolicyRpcError) as ei:
        client.call(
            "ReportLocalUpdate", messages.pack_parts(slices.request()), 0.4
        )
    assert ei.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
    assert 0.35 < time.monotonic() - t0 < 3.0
    assert client._pool == [] and dispatcher.frames == []


def _parent_send_parts(conn, head, parts, deadline=None):
    """`transport._send_parts` as the parent commit (e7e4fb3) has it:
    the reference for the system calls a frame with every piece there
    makes."""
    bufs = [memoryview(head)]
    bufs += [memoryview(part) for part in parts if len(part)]
    i, left = 0, sum(buf.nbytes for buf in bufs)
    while i < len(bufs):
        if deadline is not None:
            conn.settimeout(max(0.001, deadline - time.monotonic()))
        turn = bufs[i:i + transport._IOV_MAX]
        if left > transport.MAX_CALL_BYTES:
            turn = transport._cut_to(turn, transport.MAX_CALL_BYTES)
        sent = conn.sendmsg(turn)
        left -= sent
        while sent:
            n = bufs[i].nbytes
            if sent < n:
                bufs[i] = bufs[i][sent:]
                break
            sent -= n
            i += 1


class _CountingSocket:
    """Takes a fixed number of bytes a call, like a full socket
    buffer, and keeps what each call offered and what it took."""

    def __init__(self, room):
        self.room, self.calls, self.taken = room, [], bytearray()

    def settimeout(self, _):
        pass

    def sendmsg(self, bufs):
        self.calls.append([b.nbytes for b in bufs])
        data = b"".join(bytes(b) for b in bufs)[:self.room]
        self.taken += data
        return len(data)


@pytest.mark.parametrize("tree", ["perstep", "landed", "long"])
def test_a_frame_with_every_piece_there_makes_the_parent_s_system_calls(
    monkeypatch, tree
):
    """B's 270-part per-step frame, a retry's frame whose pieces have
    all landed, and a frame longer than a call moves: gathered turn
    by turn exactly as before."""
    if tree == "long":
        monkeypatch.setattr(transport, "MAX_CALL_BYTES", 50_000)
    if tree == "landed":
        slices = _late()
        slices.thread.start()
        request = slices.request()
        np.asarray(request["delta_flat"])  # every piece waited for
        parts = messages.pack_parts(request).parts
        reference = messages.pack_parts(slices.plain()).parts
    else:
        parts = reference = messages.pack_parts(
            {"gradient": _leaf_model(269), "version": 4}
        ).parts
        assert len(parts) > 270
    head = transport._REQ_HEADER.pack(4, 1) + b"Push"
    new, old = _CountingSocket(65_536), _CountingSocket(65_536)
    assert transport._send_parts(new, head, parts, time.monotonic() + 5) == 0.0
    _parent_send_parts(old, head, reference, time.monotonic() + 5)
    assert bytes(new.taken) == bytes(old.taken)
    if tree == "landed":
        # the same bytes in the same turns; the landed pieces are the
        # buffers the plain vector was one of
        assert [sum(c) for c in new.calls] == [sum(c) for c in old.calls]
    else:
        assert new.calls == old.calls


# -- a response reaches the socket as its parts, a model as its leaves --------


def _leaf_model(leaves=161, seed=34, elems=300):
    """A model of ResNet-50's leaf count, sizes off the 64-byte grid."""
    rng = _rng(seed)
    return {
        f"l{i:04d}": rng.standard_normal(
            (elems + i % 11, 3) if i % 4 else (elems + i % 13,)
        ).astype(np.float32)
        for i in range(leaves)
    }


def _model_master(kind):
    """A real `MasterServicer` holding the model with the leaves of
    `kind`: `read_only` after a `PSOptimizer` step (sent by view),
    `writeable` as set-up and `_add_delta` leave them (copied under
    the lock)."""
    import jax
    import optax

    from elasticdl_tpu.master.ps_optimizer import PSOptimizer
    from elasticdl_tpu.master.servicer import MasterServicer

    s = MasterServicer(grads_to_wait=1, optimizer=PSOptimizer(optax.sgd(0.5)))
    s.report_variable(
        {"params": _leaf_model(), "aux": {"stats": np.arange(5.0)}}
    )
    if kind == "read_only":
        grads = jax.tree_util.tree_map(np.ones_like, s.get_params_copy()[0])
        s.report_gradient({"version": 0, "gradient": grads})
    return s


@pytest.fixture
def frames(monkeypatch):
    """Every frame unpacked in this process, as the bytes it was: a
    call's request where the server decodes it, then its response
    where the client does."""
    seen = []
    real = messages.unpack

    def unpack(data):
        seen.append(bytes(data))
        return real(data)

    monkeypatch.setattr(messages, "unpack", unpack)
    return seen


@pytest.mark.parametrize("kind", ["read_only", "writeable"])
@pytest.mark.parametrize("env_fixture", ["uds_env", "grpc_env", "inproc_env"])
def test_a_model_response_is_the_raveled_frame_byte_for_byte(
    env_fixture, kind, request, frames, joins
):
    """What a client receives for a model pull is `codec.dumps` of the
    response with the vector `ravel_np` made, to the byte, whichever
    carrier and whichever way the leaves went: no client can tell.
    The socket never joins it; a one-buffer carrier joins it once."""
    request.getfixturevalue(env_fixture)
    made, counts = joins
    s = _model_master(kind)
    timers = _Spans()
    server = RpcServer(
        s.handlers(), port=0, timers=timers, timed_methods=("GetModel",)
    )
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        tier = _tier(client)
        resp = client.call(
            "GetModel", {"method": "minimum", "flat": True}, timeout=30
        )
    finally:
        client.close()
        server.stop()
    params, aux, version = s.get_params_copy()
    raveled = {
        "version": version,
        "params_flat": codec.ravel_np(params),
        "aux": aux,
    }
    assert version == (1 if kind == "read_only" else 0)
    assert frames[-1] == codec.dumps(raveled)
    assert resp["params_flat"].tobytes() == raveled["params_flat"].tobytes()
    response = made[-1]
    # prefix, header, pad, then the vector's one segment: a view a leaf
    assert 161 < len(response.parts) < 161 + 8
    assert response.joined == (tier != "uds")
    assert counts.count(id(response)) == (tier != "uds")
    (served,) = [a for name, a in timers.records if name == "rpc.encode"]
    assert served["joined"] == (tier != "uds")
    assert served["parts"] == len(response.parts)
    assert served["bytes"] == len(frames[-1])


def test_a_model_of_more_leaves_than_iov_max_through_a_shrunk_buffer(
    unset_env, monkeypatch, frames
):
    """The response direction of the gather list's limit: 3,000 leaves
    (`IOV_MAX` is 1024) through socket buffers smaller than one leaf,
    both ends', arrive as the frame."""
    monkeypatch.setattr(transport, "SOCKET_BUFFER_BYTES", 1024)
    model = _leaf_model(leaves=3000, elems=1000)
    pieces = [a.reshape(-1) for a in model.values()]

    def pull(req):
        return {"version": 7, "params_flat": codec.LeafVector(pieces)}

    server = RpcServer({"Pull": pull}, port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        assert _tier(client) == "uds"
        resp = client.call("Pull", {}, timeout=60)
        (conn,) = client._transport._pool
        assert conn.sndbuf < 8192 > conn.rcvbuf
    finally:
        client.close()
        server.stop()
    want = np.concatenate(pieces)
    assert resp["params_flat"].tobytes() == want.tobytes()
    assert frames[-1] == codec.dumps({"version": 7, "params_flat": want})
    assert len(messages.pack_parts(pull({})).parts) > 2 * transport._IOV_MAX


@pytest.mark.parametrize("core", ["threads", "loop"])
def test_an_oversize_or_failed_model_response_leaves_the_link_serving(
    unset_env, monkeypatch, core
):
    """A response packed as its parts is refused by its length before
    a part is sent, a handler's failure is an error frame, and the
    connection carries the next model after either, on both listeners."""
    from elasticdl_tpu.common.constants import ENV_DISPATCH

    monkeypatch.setenv(ENV_DISPATCH, core)
    pieces = [a.reshape(-1) for a in _leaf_model(leaves=20).values()]
    nbytes = 4 * sum(p.size for p in pieces)

    def pull(req):
        if req.get("fail"):
            raise ValueError("no model\nyet")
        return {"version": 1, "params_flat": codec.LeafVector(pieces)}

    server = RpcServer({"Pull": pull}, port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        assert _tier(client) == "uds"
        monkeypatch.setattr(transport, "MAX_FRAME_BYTES", nbytes // 2)
        with pytest.raises(grpc.RpcError) as ei:
            client.call("Pull", {}, timeout=10, idempotent=True)
        assert ei.value.code() == grpc.StatusCode.OUT_OF_RANGE
        assert "response frame of Pull is" in ei.value.details()
        monkeypatch.setattr(transport, "MAX_FRAME_BYTES", 0xFFFFFFFF)
        with pytest.raises(grpc.RpcError) as ei:
            client.call("Pull", {"fail": True}, timeout=10)
        assert ei.value.code() == grpc.StatusCode.INTERNAL
        assert "ValueError: no model yet" in ei.value.details()
        resp = client.call("Pull", {}, timeout=10)
        assert resp["params_flat"].tobytes() == np.concatenate(pieces).tobytes()
        assert len(client._transport._pool) == 1
    finally:
        client.close()
        server.stop()


def test_a_response_in_its_parts_is_let_go_once_it_has_left(unset_env):
    """The listener names a response only while it sends it: what the
    parts view (a model's leaves, the memory the master lent) is free
    again while the connection waits for its next request."""
    leaf = np.ones(1 << 18, np.float32)
    alive = weakref.ref(leaf)

    def pull(req):
        return {"params_flat": codec.LeafVector([handed.pop()])}

    handed = [leaf]
    del leaf
    server = RpcServer({"Pull": pull}, port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
    try:
        assert _tier(client) == "uds"
        resp = client.call("Pull", {}, timeout=10)
        assert float(resp["params_flat"].sum()) == float(1 << 18)
        assert _dies(alive, timeout=2.0)
        assert len(client._transport._pool) == 1  # and the link is up
    finally:
        client.close()
        server.stop()
