"""A frame longer than one `sendmsg` or `recv_into` may move (Linux:
2^31 - 4096 bytes a call; the first such frame is Kimi-Linear's
2,409.7 MB delta) crosses the Unix socket in turns. The limit a call is
held to is `transport.MAX_CALL_BYTES`; lowered far below a short
frame's length, the same loops walk the same turns with nothing large
allocated. And the serial chain's sync forms its delta in the old
base's place."""

import socket
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common.constants import ENV_TRANSPORT, ENV_UDS_DIR
from elasticdl_tpu.rpc import transport
from elasticdl_tpu.rpc.client import RpcClient
from elasticdl_tpu.rpc.policy import RetryPolicy
from elasticdl_tpu.rpc.server import RpcServer


class _Spy:
    """A socket that records what each call was asked to move."""

    def __init__(self, conn):
        self._conn, self.sent, self.asked = conn, [], []

    def sendmsg(self, bufs):
        bufs = list(bufs)
        self.sent.append(sum(b.nbytes for b in bufs))
        return self._conn.sendmsg(bufs)

    def recv_into(self, view, nbytes):
        self.asked.append(nbytes)
        return self._conn.recv_into(view, nbytes)

    def settimeout(self, value):
        self._conn.settimeout(value)


@pytest.mark.parametrize("limit", [1000, 4096, 65536])
def test_a_frame_leaves_and_arrives_in_turns_under_the_per_call_limit(
    monkeypatch, limit
):
    monkeypatch.setattr(transport, "MAX_CALL_BYTES", limit)
    head = b"\x07" * 10
    parts = [
        np.arange(50_000, dtype=np.uint8).view(np.uint8),  # 50 limits long
        b"",
        np.full(333, 9, np.uint8),
        np.arange(20_001, dtype=np.uint8),
    ]
    want = head + b"".join(bytes(memoryview(p)) for p in parts)
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    sender, receiver = _Spy(a), _Spy(b)
    got = np.empty(len(want), np.uint8)
    reader = threading.Thread(
        target=transport._recv_fill, args=(receiver, memoryview(got), len(want))
    )
    reader.start()
    try:
        transport._send_parts(sender, head, parts)
        reader.join(10)
        assert not reader.is_alive()
    finally:
        a.close()
        b.close()
    assert got.tobytes() == want
    # no call was asked for more than the limit, and the long part
    # took many turns: the loops' own partial-transfer path
    assert max(sender.sent) <= limit and max(receiver.asked) <= limit
    assert len(sender.sent) >= len(want) // limit
    assert len(receiver.asked) >= len(want) // limit


def test_the_limit_is_what_linux_moves_in_a_call_and_under_the_frame_s():
    assert transport.MAX_CALL_BYTES == (1 << 31) - 4096
    assert transport.MAX_CALL_BYTES < 602_434_432 * 4 < transport.MAX_FRAME_BYTES


def test_a_sync_crosses_uds_whole_with_calls_capped_below_its_length(
    monkeypatch, tmp_path
):
    """Through `RpcClient` and `RpcServer`, request and response: the
    delta is 1 MB, no call moves more than 64 KB of it."""
    monkeypatch.setenv(ENV_TRANSPORT, "uds")
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    monkeypatch.setattr(transport, "MAX_CALL_BYTES", 1 << 16)
    delta = np.random.default_rng(3).standard_normal(1 << 18).astype(np.float32)

    def push(req):
        np.testing.assert_array_equal(req["delta_flat"], delta)
        return {"version": 16, "params_flat": req["delta_flat"] * 2}

    server = RpcServer({"Push": push}, port=0)
    server.start()
    client = RpcClient(
        f"localhost:{server.port}",
        policy=RetryPolicy(initial_backoff=0.01, max_backoff=0.05),
    )
    try:
        assert client._transport is not None and client._transport.name == "uds"
        resp = client.call("Push", {"delta_flat": delta, "steps": 16}, timeout=30)
        assert resp["version"] == 16
        np.testing.assert_array_equal(np.asarray(resp["params_flat"]), delta * 2)
    finally:
        client.close()
        server.stop()


# ------------------------------------------------ the sync's moment (D1b)


def _worker(depth):
    from elasticdl_tpu.common.timing import PhaseTimers
    from elasticdl_tpu.worker.worker import Worker

    w = Worker.__new__(Worker)
    w.timers = PhaseTimers()
    w._report_lock = threading.Lock()
    w._base_snapshots = {}
    w._max_inflight_syncs = depth
    w._subtract_into_base = None
    w._flat = jnp.arange(8, dtype=jnp.float32) * 3
    w._base_flat = jnp.arange(8, dtype=jnp.float32)
    return w


def test_the_serial_chain_forms_its_delta_where_the_old_base_lay():
    w = _worker(depth=0)
    base = w._base_flat
    delta = w._delta_from_base()
    np.testing.assert_array_equal(np.asarray(delta), np.arange(8) * 2.0)
    assert w._base_flat is None  # the sync copies the new one next
    assert base.is_deleted()  # donated: no fifth buffer at that moment
    assert not w._flat.is_deleted()
    # and again, by the same program
    w._base_flat = jnp.zeros(8, jnp.float32)
    program = w._subtract_into_base
    np.testing.assert_array_equal(np.asarray(w._delta_from_base()), np.arange(8) * 3.0)
    assert w._subtract_into_base is program


@pytest.mark.parametrize("depth,snapshot", [(2, False), (0, True), (2, True)])
def test_a_base_a_sync_in_flight_may_still_read_is_left_alone(depth, snapshot):
    """With syncs in flight, or while an unsettled sync's merged model
    is still to be folded in against this very buffer, the base is
    subtracted from, not donated."""
    w = _worker(depth)
    base = w._base_flat
    if snapshot:
        w._base_snapshots[7] = base
    delta = w._delta_from_base()
    np.testing.assert_array_equal(np.asarray(delta), np.arange(8) * 2.0)
    assert w._base_flat is base and not base.is_deleted()
    assert w._subtract_into_base is None
