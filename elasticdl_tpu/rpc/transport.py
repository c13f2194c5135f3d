"""Carriers for the RPC plane: grpc / uds / inproc.

A link between two processes has two carriers. A peer on this host
whose listener is there is carried by a Unix socket; everyone else,
and any call whose local carrier cannot connect, by gRPC. Which one a
link gets is decided from what the code can observe — is the peer on
this host, is its socket file there — with `EDL_TRANSPORT` as the
override (and the test handle):

- **uds** — a Unix-domain-socket byte protocol carrying codec frames
  with a minimal length-prefixed header, skipping gRPC/HTTP-2 framing
  entirely. A frame, request or response, arrives here as the parts
  the codec made (`messages.PackedParts`: prefix, header, pads, views
  of the source arrays) and they are written to the socket in order,
  never joined into a buffer of their own (`_send_parts`); the
  receiver hands the codec one contiguous buffer to build
  `np.frombuffer` views over — the zero-copy contract of codec v2
  holds end to end. The bytes on the socket are the frame
  `codec.dumps` would have made: the receiving side cannot tell. A
  part may be a `codec.PendingPiece` whose bytes have not landed yet
  (a slice of a window's delta still on its way off the device): the
  send waits for it there, inside the call's deadline.
- **inproc** — when the serving `RpcServer` lives in the SAME
  interpreter (bench/test mode, `PSShardGroup` inproc shards), the call
  dispatches directly into the server's handler table: the frame, which
  this carrier asks the request to join (the dispatcher decodes from
  one buffer), is passed by reference, no socket at all. WireStats
  records these calls with zero wire bytes under the "inproc" tier.

The join (`PackedParts.contiguous()`, at most once a frame however
many attempts send it) is the one-buffer carriers' alone: `inproc`,
gRPC, which `RpcClient` also hands any call whose socket cannot
connect, and for a response `AsyncUdsServer` (`EDL_DISPATCH=loop`),
which copies on the way in too. `ServerDispatcher` packs a response
as its parts and joins it, inside its `rpc.encode` span, only for
those.

Every tier runs the identical server-side core, `ServerDispatcher`:
chaos faults (rpc/chaos.py, via `transport_faults_before/after` — the
exact interceptor semantics), EpochFencedError -> FAILED_PRECONDITION
classification, and INTERNAL sanitization are applied once here, so the
fault model and edl-verify's fencing conformance hold unchanged on the
fast paths. Client-side chaos is likewise applied by each client
transport with the same FaultPlan the gRPC interceptors use. The
rpc-conformance lint cross-checks both wirings (transport-chaos-bypass)
so a tier cannot silently bypass FaultPlan injection.

Selection (`select_transport`) is conservative: a non-grpc tier is used
only when the endpoint host resolves local AND the counterpart is
reachable (a registered in-process dispatcher, or an existing socket
file); otherwise the caller falls back to gRPC. `auto` prefers
inproc > uds > grpc.

With `EDL_TRANSPORT` unset the mode is **uds**: every `RpcServer`
opens its Unix-socket listener beside gRPC, a client whose endpoint is
local and whose socket file exists is carried by it, and a remote
endpoint (the k8s path advertises the pod IP) gets gRPC. Unset is not
`auto`: no `inproc` (in-process tests keep their sockets).
`EDL_TRANSPORT=grpc` is pure gRPC: no listener, no fast path.

The socket tier receives a frame with no copy beyond the kernel's:
`_recv_frame` reads into memory that was never zero-filled and hands
the dispatcher / `messages.unpack` a read-only view of it, over which
the codec builds its `np.frombuffer` views. A frame's memory lives as
long as an array decoded from it (the master keeps such views past the
handler: `grads_to_wait` > 1, fan-in). A connection keeps the memory
its last large frame lay in (`FrameMemory`) and receives the next one
there, but only once nothing reads the old frame any more: the memory
comes back to the connection when the last view of the frame dies, so
whoever keeps a view keeps the memory and the connection's next frame
gets fresh pages, as every frame did before. Both ends ask the kernel
for socket buffers of `SOCKET_BUFFER_BYTES`, so a long frame crosses
in turns of megabytes.

A frame longer than the socket header's u32 length field can say
(`MAX_FRAME_BYTES`) is refused before a byte of it is sent, by the
client for a request and by the server for a response, as OUT_OF_RANGE:
not retryable, since the same frame would be refused again.

A local carrier that cannot CONNECT (the socket file of a dead server,
a server relaunched under `EDL_TRANSPORT=grpc` on a reused port) raises
`CarrierDown` before anything was sent or any client-side fault was
drawn; `RpcClient` then serves that call over the gRPC channel it
holds anyway, so a stale file costs a failed `connect()` a call, never
an endpoint that answers UNAVAILABLE for ever.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import os
import socket
import struct
import tempfile
import threading
import time
import weakref
from concurrent import futures
from typing import Callable, Dict, Optional

import grpc
import numpy as np

from elasticdl_tpu.common import codec, messages
from elasticdl_tpu.common.constants import (
    ENV_TRANSPORT,
    ENV_UDS_DIR,
)
from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.obs import trace as obs_trace
from elasticdl_tpu.rpc import dispatch as dispatch_mod
from elasticdl_tpu.rpc.chaos import (
    transport_faults_after,
    transport_faults_before,
)
from elasticdl_tpu.rpc.policy import PolicyRpcError

logger = get_logger(__name__)

TRANSPORT_GRPC = "grpc"
TRANSPORT_UDS = "uds"
TRANSPORT_INPROC = "inproc"
#: The tiers WireStats rows may carry; "auto" is a selection policy,
#: not a tier.
TRANSPORT_TIERS = (TRANSPORT_GRPC, TRANSPORT_UDS, TRANSPORT_INPROC)

# a served call this long, frame received to response packed, is one
# `rpc.server.slow` span on the serving process's timeline
SLOW_CALL_SECS = 0.25

_LOCAL_HOSTS = frozenset(
    {"localhost", "127.0.0.1", "[::1]", "::1", "0.0.0.0", "[::]", ""}
)

#: UDS request: u16 method length, u32 body length, then method utf-8
#: and the codec frame.
_REQ_HEADER = struct.Struct("<HI")
#: UDS ok response: status 0, u32 body length, then the codec frame.
_RESP_OK = struct.Struct("<BI")
#: UDS error response: status 1, i32 grpc status-code value, u16 detail
#: length, then the detail utf-8 — enough to rebuild the PolicyRpcError
#: the gRPC tier would have surfaced.
_RESP_ERR = struct.Struct("<BiH")

#: The longest frame either header's u32 length field can say.
MAX_FRAME_BYTES = 0xFFFFFFFF

_CODE_BY_VALUE = {c.value[0]: c for c in grpc.StatusCode}


#: The mode when EDL_TRANSPORT is unset: a local peer with a listener
#: gets the Unix-socket carrier, everyone else gRPC.
DEFAULT_MODE = TRANSPORT_UDS


def transport_mode(env=None) -> str:
    """The configured tier ("grpc"/"uds"/"inproc"/"auto"); unset
    means DEFAULT_MODE, unknown values log once and mean grpc."""
    env = os.environ if env is None else env
    mode = (env.get(ENV_TRANSPORT, "") or DEFAULT_MODE).strip().lower()
    if mode not in TRANSPORT_TIERS and mode != "auto":
        logger.warning("unknown %s=%r; using grpc", ENV_TRANSPORT, mode)
        return TRANSPORT_GRPC
    return mode


def server_fast_paths_enabled() -> bool:
    """Whether RpcServer should open the UDS listener (the inproc
    registry is always populated — it is a dict entry, not a socket)."""
    return transport_mode() in (TRANSPORT_UDS, "auto")


def uds_dir(env=None) -> str:
    env = os.environ if env is None else env
    return env.get(ENV_UDS_DIR) or tempfile.gettempdir()


#: sockaddr_un.sun_path holds 108 bytes, the terminating NUL included
_SUN_PATH_MAX = 107


@contextlib.contextmanager
def _sock_addr(path: str):
    """The address to bind or connect `path` by. A path too long for
    an AF_UNIX address (a TMPDIR deep inside a checkout) is reached
    through an open descriptor of its directory, so a long
    EDL_UDS_DIR costs nothing instead of silently meaning gRPC."""
    if len(os.fsencode(path)) <= _SUN_PATH_MAX:
        yield path
        return
    fd = os.open(os.path.dirname(path), os.O_RDONLY | os.O_DIRECTORY)
    try:
        yield f"/proc/self/fd/{fd}/{os.path.basename(path)}"
    finally:
        os.close(fd)


def uds_path_for(port: int) -> str:
    """Socket path a server listening on gRPC `port` also serves; the
    port number is the rendezvous, so clients derive the path from the
    endpoint they already hold (GetPSConfig / shard_host endpoints)."""
    return os.path.join(uds_dir(), f"edl-uds-{int(port)}.sock")


def _sanitized_detail(e: BaseException) -> str:
    return f"{type(e).__name__}: {e}".replace("\n", " ")[:256]


class ServerDispatcher:
    """The transport-independent server core: every tier's receive path
    funnels through `dispatch`, so wire accounting, chaos injection,
    fencing classification, and INTERNAL sanitization are applied
    identically no matter how the bytes arrived.

    For the grpc tier the chaos server interceptor already wraps the
    handler, so dispatch applies server-side faults only for the fast
    paths — exactly one injection layer per tier.

    Two dispatch cores (`EDL_DISPATCH`, rpc/dispatch.py): `threads`
    (default) runs the handler on whatever thread delivered the bytes —
    the blocking thread-per-request model. `loop` serves every tier
    from the process event loop: requests pass per-method-class bounded
    admission queues (full -> RESOURCE_EXHAUSTED, retryable), sync
    handlers are bridged through this dispatcher's bounded executor,
    uds connections are read non-blocking on the loop
    (`AsyncUdsServer`), grpc pool threads park on a loop future (the
    reactor shim), and inproc callers run admission + handler inline
    (direct scheduling — no socket, so no loop hop).
    """

    def __init__(
        self,
        handlers: Dict[str, Callable],
        wire,
        fault_plan=None,
        mode: Optional[str] = None,
        timers=None,
        timed_methods=(),
    ):
        self._handlers = dict(handlers)
        self._wire = wire
        # the owner's PhaseTimers and the methods it names (the master:
        # its update and model RPCs): their request decode and response
        # encode are the owner's phases
        self._timers = timers
        self._timed_methods = frozenset(timed_methods) if timers else ()
        self._plan = fault_plan
        self._mode = dispatch_mod.dispatch_mode() if mode is None else mode
        self._admission = None
        self._executor = None
        self._core = None
        if self._mode == dispatch_mod.DISPATCH_LOOP:
            self._admission = dispatch_mod.AdmissionQueues()
            self._executor = futures.ThreadPoolExecutor(
                max_workers=dispatch_mod.executor_width(),
                thread_name_prefix="edl-dispatch-exec",
            )
            self._core = dispatch_mod.get_loop_core()

    @property
    def mode(self) -> str:
        return self._mode

    def methods(self) -> frozenset:
        return frozenset(self._handlers)

    def admission_stats(self) -> Optional[dict]:
        return None if self._admission is None else self._admission.stats()

    def close(self):
        if self._executor is not None:
            self._executor.shutdown(wait=False)

    def dispatch(
        self, method: str, request_bytes, transport: str, recv_reused=False
    ) -> messages.PackedParts:
        """The response, packed as its parts: the socket carrier sends
        them as they lie, a carrier that needs one buffer asks
        `contiguous()`. `recv_reused`: the carrier received the request
        into memory an earlier frame of its connection lay in (the
        `rpc.decode` span of a timed method says so; the loop core does
        not)."""
        if self._core is not None:
            if transport == TRANSPORT_INPROC:
                # direct scheduling: there is no socket to multiplex, so
                # the caller's thread runs admission + handler inline —
                # a loop hop would only add two context switches
                t_admit = time.time()
                cls = self._admission.enter(method)
                try:
                    return self._dispatch_blocking(
                        method, request_bytes, transport, t_admit
                    )
                finally:
                    self._admission.leave(cls)
            if not self._core.on_loop_thread():
                # reactor shim (grpc tier): the pool thread parks on the
                # loop's future; admission/scheduling happen on the loop
                return self._core.submit(
                    self.dispatch_async(method, request_bytes, transport)
                ).result()
            # on the loop thread itself fall through to inline dispatch
            # (loop-side callers normally await dispatch_async)
        after = []
        if transport != TRANSPORT_GRPC:
            after = transport_faults_before(self._plan, method, "server")
        payload = self._invoke(
            method, request_bytes, transport, recv_reused=recv_reused
        )
        # drop/crash-after fire with the handler APPLIED (same contract
        # as the server interceptor: state changed, response withheld)
        transport_faults_after(after, method)
        return payload

    async def dispatch_async(
        self, method: str, request_bytes, transport: str
    ) -> messages.PackedParts:
        """Loop-mode dispatch: admission on the loop, then the blocking
        half (chaos hooks + legacy sync handler) bridged through the
        bounded executor — handler work and chaos latency sleeps never
        run ON the loop (async-discipline lint)."""
        t_admit = time.time()
        cls = self._admission.enter(method)
        try:
            return await self._core.loop.run_in_executor(
                self._executor,
                self._dispatch_blocking,
                method,
                request_bytes,
                transport,
                t_admit,
            )
        finally:
            self._admission.leave(cls)

    def _dispatch_blocking(
        self, method: str, request_bytes, transport: str, t_admit=None
    ) -> messages.PackedParts:
        after = []
        if transport != TRANSPORT_GRPC:
            after = transport_faults_before(self._plan, method, "server")
        payload = self._invoke(method, request_bytes, transport, t_admit)
        transport_faults_after(after, method)
        return payload

    def _invoke(
        self, method: str, request_bytes, transport: str, t_admit=None,
        recv_reused=False,
    ) -> messages.PackedParts:
        """Unpack, handle, pack. A call of any method that takes over
        `SLOW_CALL_SECS` from the frame's receipt (the loop core: from
        admission) to the packed response, or to the error that ends
        it, leaves one `rpc.server.slow` span on the process's
        timeline: `queued_ms` up to the handler's start, `handled_ms`
        inside it. A call under it leaves nothing."""
        t_recv = time.time() if t_admit is None else t_admit
        handler = []  # `_handle` leaves the handler's start and end here
        try:
            return self._handle(
                method, request_bytes, transport, t_admit, recv_reused, handler
            )
        finally:
            now = time.time()
            if now - t_recv > SLOW_CALL_SECS:
                begun, done = (handler + [now, now])[:2]  # as far as it came
                obs_trace.record_phase(
                    "rpc.server.slow", t_recv, now - t_recv,
                    {
                        "method": method,
                        "queued_ms": round((begun - t_recv) * 1e3, 1),
                        "handled_ms": round((done - begun) * 1e3, 1),
                    },
                )

    def _handle(
        self, method, request_bytes, transport, t_admit, recv_reused, handler
    ) -> messages.PackedParts:
        from elasticdl_tpu.rpc.fencing import EpochFencedError

        fn = self._handlers.get(method)
        if fn is None:
            raise PolicyRpcError(
                grpc.StatusCode.UNIMPLEMENTED, f"no handler for {method}"
            )
        inproc = transport == TRANSPORT_INPROC
        nbytes = len(request_bytes) if request_bytes else 0
        self._wire.record(
            method, received=0 if inproc else nbytes, transport=transport
        )
        # a timed method's decode and encode are spans of the owner's
        # timeline, recorded once the handler has answered: they carry
        # the version its response names, which joins them to the
        # handler's own spans and to the client's round trip
        timed = method in self._timed_methods
        t_decode = time.time() if timed else 0.0
        req = messages.unpack(request_bytes) if request_bytes else None
        t_decoded = time.time() if timed else 0.0
        # trace envelope: always popped (handlers never see the key);
        # a context materializes only when the sender sampled this
        # request AND this process has tracing on
        tctx = obs_trace.extract(req)
        sp = None
        if tctx is not None:
            sp = obs_trace.start_span(
                f"rpc.server.{method}",
                cat="rpc",
                parent=tctx,
                args={"transport": transport},
            )
            if sp is not None and t_admit is not None:
                # retro-recorded: admission enter + executor queueing
                # happened before the envelope was parsed
                obs_trace.record_event(
                    "rpc.admission_wait",
                    t_admit,
                    time.time(),
                    cat="rpc",
                    parent=sp.ctx,
                    args={"method": method},
                )
        prev_ctx = obs_trace.bind(sp.ctx) if sp is not None else None
        handler.append(time.time())  # begins
        try:
            try:
                resp = fn(req) if req is not None else fn({})
            except EpochFencedError as e:
                # fencing rejections are a protocol answer, not a bug:
                # FAILED_PRECONDITION is non-retryable (policy.RETRYABLE_CODES)
                # so the client re-resolves instead of re-sending (rpc/fencing.py)
                logger.warning("RPC %s fenced: %s", method, e)
                raise PolicyRpcError(
                    grpc.StatusCode.FAILED_PRECONDITION, _sanitized_detail(e)
                )
            except PolicyRpcError:
                # a handler that classified its own status (e.g. the
                # unadopted-standby gate answering UNAVAILABLE) keeps it —
                # re-wrapping as INTERNAL would defeat the classification
                raise
            except Exception as e:
                logger.exception("RPC handler %s failed", method)
                # carry a sanitized one-line summary so the client can tell
                # a shape mismatch from an uninitialized shard without
                # reading server logs
                raise PolicyRpcError(
                    grpc.StatusCode.INTERNAL, _sanitized_detail(e)
                )
        finally:
            handler.append(time.time())  # and ends
            if sp is not None:
                obs_trace.bind(prev_ctx)
                sp.end()
        t_encode = time.time() if timed else 0.0
        # packed, not joined: the blocking socket listener gathers the
        # parts to the socket from where they lie (a model on its way
        # down: from the leaves it lies in). Every other carrier needs
        # one buffer, and gets it here, inside the span that times the
        # packing, once
        payload = messages.pack_parts(resp)
        if transport != TRANSPORT_UDS or self._core is not None:
            payload.contiguous()
        if timed:
            version = resp.get("version") if isinstance(resp, dict) else None
            record = self._timers.record
            record(
                "rpc.decode", t_decode, t_decoded, method=method,
                bytes=nbytes, version=version, recv_reused=recv_reused,
            )
            record(
                "rpc.encode", t_encode, time.time(), method=method,
                bytes=len(payload), version=version,
                parts=len(payload.parts), joined=payload.joined,
            )
        self._wire.record(
            method,
            sent=0 if inproc else len(payload),
            transport=transport,
            calls=1,
        )
        return payload


# --------------------------------------------------------------------------
# inproc: same-interpreter dispatch registry, keyed by the gRPC port


_inproc_lock = threading.Lock()
_inproc_registry: Dict[int, ServerDispatcher] = {}


def register_inproc(port: int, dispatcher: ServerDispatcher) -> None:
    with _inproc_lock:
        _inproc_registry[int(port)] = dispatcher


def unregister_inproc(port: int) -> None:
    with _inproc_lock:
        _inproc_registry.pop(int(port), None)


def inproc_dispatcher(port: int) -> Optional[ServerDispatcher]:
    with _inproc_lock:
        return _inproc_registry.get(int(port))


class InprocTransport:
    """Direct dispatch into a same-interpreter RpcServer. The joined
    codec frame crosses by reference — zero wire bytes.
    The dispatcher is re-resolved per call so a shard relaunch (new
    server object on a new port -> new client) or a stopped server
    surfaces as UNAVAILABLE for the retry/recovery machinery, never a
    stale handler table."""

    name = TRANSPORT_INPROC

    def __init__(self, port: int, fault_plan=None):
        self._port = int(port)
        self._plan = fault_plan

    def call(self, method: str, payload, timeout: float) -> bytes:
        after = transport_faults_before(self._plan, method, "client")
        dispatcher = inproc_dispatcher(self._port)
        if dispatcher is None:
            raise PolicyRpcError(
                grpc.StatusCode.UNAVAILABLE,
                f"inproc server for port {self._port} is gone",
            )
        # the dispatcher decodes from one buffer, and so does the caller
        resp = dispatcher.dispatch(
            method, payload.contiguous(timeout), TRANSPORT_INPROC
        ).contiguous()
        transport_faults_after(after, method)
        return resp


# --------------------------------------------------------------------------
# uds: length-prefixed codec frames over AF_UNIX


def _rpc_error_fields(e: grpc.RpcError):
    """(status code, clamped detail bytes) for a dispatch failure —
    enough to rebuild the PolicyRpcError the gRPC tier would have
    surfaced."""
    code = e.code() if callable(getattr(e, "code", None)) else None
    if not isinstance(code, grpc.StatusCode):
        code = grpc.StatusCode.INTERNAL
    details = ""
    if callable(getattr(e, "details", None)):
        details = e.details() or ""
    return code, details.encode("utf-8")[:1024]


def _refuse_oversize(what: str, method: str, n: int) -> None:
    """A frame the length field cannot say is refused whole, before a
    byte of it is sent. OUT_OF_RANGE is not retryable (the same frame
    would be refused again), unlike the UNAVAILABLE a link that is
    down answers with."""
    if n > MAX_FRAME_BYTES:
        raise PolicyRpcError(
            grpc.StatusCode.OUT_OF_RANGE,
            f"uds {what} frame of {method} is {n} bytes; the carrier's "
            f"limit is {MAX_FRAME_BYTES}",
        )


def _error_frame(e: grpc.RpcError) -> bytes:
    """The UDS error response frame for a dispatch failure."""
    code, detail_b = _rpc_error_fields(e)
    return _RESP_ERR.pack(1, code.value[0], len(detail_b)) + detail_b


#: The most bytes one `sendmsg` or `recv_into` is asked to move. Linux
#: moves at most 2^31 - 4096 a call whatever it is asked (MAX_RW_COUNT)
#: and a frame may be longer (`MAX_FRAME_BYTES`; the first was a
#: 2,409.7 MB delta), so a long frame leaves and arrives in turns. The
#: limit is stated here, not left to the kernel, so that a test can
#: lower it and walk a short frame through the same turns.
MAX_CALL_BYTES = (1 << 31) - 4096


def _recv_fill(conn: socket.socket, view, n: int, eof_ok: bool = False) -> bool:
    """Fill view[:n] from the socket, at most `MAX_CALL_BYTES` a call;
    False on a clean EOF before the first byte (eof_ok),
    ConnectionError on EOF after it."""
    got = 0
    while got < n:
        k = conn.recv_into(view[got:], min(n - got, MAX_CALL_BYTES))
        if k == 0:
            if eof_ok and got == 0:
                return False
            raise ConnectionError(f"peer closed mid-frame ({got}/{n} bytes)")
        got += k
    return True


def _recv_exact(conn: socket.socket, n: int, *, eof_ok: bool = False):
    """Read exactly n bytes (headers, names, details: small); None on
    a clean EOF at a frame boundary (eof_ok), ConnectionError on EOF
    mid-frame."""
    buf = bytearray(n)
    if not _recv_fill(conn, memoryview(buf), n, eof_ok):
        return None
    return bytes(buf)


def _frame_buffer(n: int):
    """(writable view, read-only view) of fresh memory for a frame of
    n bytes, which belongs to the frame alone and goes when the last
    array decoded from it does: what a small frame gets, and a large
    one whose connection has no memory to lend. Not zero-filled (a
    `bytearray(n)` is, one pass over the frame for nothing), and the
    read-only view is what the codec decodes from: no trailing
    `bytes()` copy. Read-only, like the `bytes` it replaces, so
    decoded arrays stay read-only views."""
    buf = np.empty(n, dtype=np.uint8)
    return memoryview(buf), memoryview(buf).toreadonly()


#: From this size on a connection keeps the memory a frame lay in.
#: Chosen on the chip's host (PERF.md, PR 31): up to 32 MiB, glibc's
#: largest mmap threshold, malloc hands a freed buffer's pages back
#: mapped as they were and keeping them buys nothing (a 31 MiB frame:
#: 7.0 ms a call fresh, 6.5 kept); over it every `np.empty` is a
#: mapping of its own, 4 KiB a fault as `recv_into` fills it (33 MiB:
#: 41 ms fresh, 7.6 kept). Task dispatch, stats and acknowledgements
#: are far under it.
KEEP_FRAME_BYTES = 32 << 20

#: Kept memory is sized in whole multiples of this, so a header that
#: grows by a byte does not replace the buffer of a 649 MB frame.
_KEEP_GRANULE = 1 << 20


class FrameMemory:
    """The memory one connection receives its large frames into (and
    the master copies a model it may not send by view into: see
    `MasterServicer._flat_model`): at most one buffer, lent to a frame
    and back here when the last view of that frame dies.

    What decides is what the program can observe, not a setting. Each
    frame is decoded over a lease, an array of its own over the
    buffer, which every `np.frombuffer` view of the frame keeps alive
    through the read-only `memoryview` it was built on; the lease's
    `weakref.finalize` holds the buffer and hands it back, in CPython
    the moment the last reference goes. A handler that keeps nothing
    has returned the buffer before the connection reads its next
    header; one that keeps an array of request n (`grads_to_wait` > 1,
    fan-in, a client that holds the model it pulled) leaves the
    connection with nothing to lend, and frame n+1 gets fresh memory.
    A frame of another size replaces the buffer, the old one freed
    first; `close()` frees it with the connection."""

    def __init__(self):
        # whichever thread drops the last view puts the buffer back,
        # possibly inside a collection that interrupted `lend` on this
        # one: a deque's append and pop are atomic and take no lock
        # that a thread could ask for twice. maxlen: never two
        self._spare = collections.deque(maxlen=1)
        self._open = True

    def lend(self, n: int):
        """(writable view, read-only view, reused) for a frame of n
        bytes; `reused` says the memory is the one an earlier frame of
        this connection lay in."""
        if n < KEEP_FRAME_BYTES:
            return (*_frame_buffer(n), False)
        size = -(-n // _KEEP_GRANULE) * _KEEP_GRANULE
        try:
            buf = self._spare.pop()
        except IndexError:
            buf = None
        reused = buf is not None and buf.nbytes == size
        if not reused:
            buf = None  # replaced, not grown beside: freed first
            buf = np.empty(size, dtype=np.uint8)
        lease = buf[:n]
        weakref.finalize(lease, self._give_back, buf).atexit = False
        return memoryview(lease), memoryview(lease).toreadonly(), reused

    def _give_back(self, buf):
        if self._open:
            self._spare.append(buf)

    def close(self):
        """Keep nothing from here on; a frame still read elsewhere
        keeps its memory until its last view dies, then frees it."""
        self._open = False
        self._spare.clear()


def _recv_frame(conn: socket.socket, n: int, memory: FrameMemory):
    """Read a frame body of exactly n bytes with no copy beyond the
    kernel's, into the memory the connection lends (the pages of its
    last large frame, mapped already, when nobody reads that one any
    more); ConnectionError on EOF inside it. Returns the read-only
    frame and whether its memory was reused."""
    view, frame, reused = memory.lend(n)
    _recv_fill(conn, view, n)
    return frame, reused


#: The send and receive buffer both ends of a Unix-socket link ask
#: for. The kernel's default (208 KB) makes 3,000 turns of a 649 MB
#: frame, each taking the interpreter lock two or three times on
#: either side; 4 MB makes under 200. Chosen on the chip by C's
#: `sync_wire_ms` (PERF.md, PR 31: 1 MB 233, 2 MB 183, 4 MB 161 ms; 8
#: MB is granted as 4 there). `wmem_max` / `rmem_max` may cap it: what
#: the kernel granted is what the spans report.
SOCKET_BUFFER_BYTES = 4 << 20


def _ask_socket_buffers(sock: socket.socket):
    """Ask for `SOCKET_BUFFER_BYTES` each way; (sndbuf, rcvbuf) as the
    kernel granted them (Linux reports twice what it was asked, for
    its own bookkeeping)."""
    for opt in (socket.SO_SNDBUF, socket.SO_RCVBUF):
        sock.setsockopt(socket.SOL_SOCKET, opt, SOCKET_BUFFER_BYTES)
    return (
        sock.getsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF),
        sock.getsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF),
    )


#: The most buffers one `sendmsg` may gather (Linux's UIO_MAXIOV).
_IOV_MAX = 1024


def _cut_to(bufs, room: int):
    """The front of `bufs` that holds `room` bytes, the last buffer
    cut where they end. Walked only while more than a call's worth is
    left of a frame: a turn of a shorter frame gathers its buffers as
    they are (a per-step cell sends 270 of them in tens of turns)."""
    turn = []
    for buf in bufs:
        if buf.nbytes >= room:
            turn.append(buf[:room])
            break
        turn.append(buf)
        room -= buf.nbytes
    return turn


def _send_parts(conn: socket.socket, head: bytes, parts, deadline=None) -> float:
    """Write `head`, then a frame's parts in order, gathered by
    `sendmsg` from where they lie: no buffer the size of the frame,
    and a frame that fits the socket buffer is one system call, header
    and all. A turn sends at most a socket buffer's worth (what the
    kernel granted of `SOCKET_BUFFER_BYTES`; 208 KB where nothing was
    asked), so a long part leaves over many turns: what has left is
    dropped from the front and the rest gathered again, at most
    `_IOV_MAX` buffers and `MAX_CALL_BYTES` bytes a turn (a part
    longer than that, a 2.4 GB delta, is cut for the call and goes on
    from where the call left it). `deadline` (monotonic)
    is one budget over all the turns; with none the socket's own
    timeout stands (a server's connection blocks). The bytes on the socket are
    `head + b"".join(parts)`. (One `sendall` a long part, short parts
    joined, read 12-28 ms more wire a 649 MB sync on the chip's host:
    PERF.md, PR 30.)

    A part may be a `codec.PendingPiece` whose bytes have not landed:
    what lies before it leaves first, then the call waits for it,
    inside the same `deadline` (`TimeoutError`, which is the socket's
    own), and goes on. One that has landed (a retry's) is gathered
    like any other part. Returns the seconds spent waiting so; a frame
    with no such part makes the system calls it always made."""
    bufs = [memoryview(head)]
    waited = 0.0
    for part in parts:
        if isinstance(part, codec.PendingPiece):
            if part.peek() is None:
                _send_bufs(conn, bufs, deadline)
                bufs = []
                t0 = time.monotonic()
                part.landed(None if deadline is None else max(0.0, deadline - t0))
                waited += time.monotonic() - t0
            part = codec.part_bytes(part)
        if len(part):
            bufs.append(memoryview(part))
    _send_bufs(conn, bufs, deadline)
    return waited


def _send_bufs(conn: socket.socket, bufs, deadline) -> None:
    """`_send_parts`' turns over buffers that are all there; `bufs` is
    consumed."""
    i, left = 0, sum(buf.nbytes for buf in bufs)
    while i < len(bufs):
        if deadline is not None:
            conn.settimeout(max(0.001, deadline - time.monotonic()))
        turn = bufs[i:i + _IOV_MAX]
        if left > MAX_CALL_BYTES:
            turn = _cut_to(turn, MAX_CALL_BYTES)
        sent = conn.sendmsg(turn)
        left -= sent
        while sent:
            n = bufs[i].nbytes
            if sent < n:
                bufs[i] = bufs[i][sent:]
                break
            sent -= n
            i += 1


class CarrierDown(PolicyRpcError):
    """A local carrier could not connect: nothing was sent and no
    client-side fault was drawn, so `RpcClient` may serve the call
    over gRPC instead. UNAVAILABLE to anyone who does not."""

    def __init__(self, details: str):
        super().__init__(grpc.StatusCode.UNAVAILABLE, details)


def _listen_unix(path: str) -> socket.socket:
    """A listening AF_UNIX socket at `path`. The name appears only
    once the socket listens (bound under a temporary name, then
    renamed over whatever a predecessor on this port left), so a
    socket file that refuses a connection belongs to a dead process —
    which is what lets every boot sweep the directory of them: each
    RpcServer makes such a file, and a SIGKILLed one cannot remove
    its own. OSError when the directory is unusable."""
    tmp = f"{path}.{os.getpid()}.tmp"
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        with _sock_addr(tmp) as addr:
            sock.bind(addr)
        sock.listen(128)
        os.rename(tmp, path)
    except OSError:
        # a half-built listener has no owner to close() it: the
        # caller never gets the object, so release the fd here
        sock.close()
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _reap_dead_sockets(os.path.dirname(path))
    return sock


def _reap_dead_sockets(directory: str) -> None:
    try:
        names = os.listdir(directory)
    except OSError:
        return
    for name in names:
        if not (name.startswith("edl-uds-") and name.endswith(".sock")):
            continue
        path = os.path.join(directory, name)
        probe = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        probe.settimeout(0.2)
        try:
            with _sock_addr(path) as addr:
                probe.connect(addr)
        except ConnectionRefusedError:
            try:
                os.unlink(path)
            except OSError:
                pass
        except OSError:
            pass  # gone already, or busy: alive
        finally:
            probe.close()


class UdsServer:
    """Threaded Unix-domain-socket listener sharing an RpcServer's
    dispatcher. One thread per connection; each connection carries
    sequential request/response frames (clients pool connections for
    concurrency). Raises OSError from __init__ when the socket path is
    unusable — the caller logs and serves gRPC only."""

    def __init__(self, port: int, dispatcher: ServerDispatcher):
        self.path = uds_path_for(port)
        self._sock = _listen_unix(self.path)
        self._dispatcher = dispatcher
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        # live connections, severed on close(): a stopped server must
        # refuse pooled clients exactly like a stopped gRPC server — a
        # zombie serve thread answering after stop() would let a fenced
        # shard keep applying requests
        self._conns: set = set()
        self._conns_lock = threading.Lock()

    def start(self):
        self._thread = threading.Thread(
            target=self._accept_loop, name=f"uds-accept-{self.path}", daemon=True
        )
        self._thread.start()

    def _is_closed(self) -> bool:
        with self._conns_lock:
            return self._closed

    def _accept_loop(self):
        while not self._is_closed():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return  # closed
            threading.Thread(
                target=self._serve_conn, args=(conn,), daemon=True
            ).start()

    def _serve_conn(self, conn: socket.socket):
        with self._conns_lock:
            if self._closed:
                conn.close()
                return
            self._conns.add(conn)
        memory = FrameMemory()
        try:
            _ask_socket_buffers(conn)
            while not self._is_closed():
                header = _recv_exact(conn, _REQ_HEADER.size, eof_ok=True)
                if header is None:
                    return
                mlen, blen = _REQ_HEADER.unpack(header)
                method = _recv_exact(conn, mlen).decode("utf-8")
                try:
                    self._serve_frame(conn, method, blen, memory)
                except grpc.RpcError as e:
                    conn.sendall(_error_frame(e))
        except (ConnectionError, OSError):
            pass  # client went away
        finally:
            memory.close()
            with self._conns_lock:
                self._conns.discard(conn)
            try:
                conn.close()
            except OSError:
                pass

    def _serve_frame(self, conn, method: str, blen: int, memory):
        # a call of its own, so that nothing names the frame or the
        # response once it returns: the frame lives on only in what
        # the handler kept of it, and a 649 MB request the handler
        # only read has given its memory back before the connection's
        # next; the response's parts are views of what the handler
        # answered with (a model's leaves, or the copy the master lent
        # them), let go the moment they have left
        frame, reused = _recv_frame(conn, blen, memory)
        resp = self._dispatcher.dispatch(
            method, frame, TRANSPORT_UDS, recv_reused=reused
        )
        # before a byte of the answer leaves, as when this returned the
        # response: the caller may drop what it keeps on the answer,
        # and the request's memory has to be back by then
        del frame
        _refuse_oversize("response", method, len(resp))
        _send_parts(conn, _RESP_OK.pack(0, len(resp)), resp.parts)

    def close(self):
        with self._conns_lock:
            self._closed = True
            conns = list(self._conns)
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        try:
            self._sock.close()
        except OSError:
            pass
        try:
            os.unlink(self.path)
        except OSError:
            pass


class AsyncUdsServer:
    """Event-loop Unix-domain-socket listener (`EDL_DISPATCH=loop`):
    the same framing and close semantics as UdsServer, but connections
    are read with non-blocking socket IO on the process LoopCore — N
    idle worker connections cost zero threads instead of N. Requests
    are served through the shared ServerDispatcher's async path
    (admission queues + bounded handler executor), so chaos, fencing,
    and abort classification stay tier-identical. Raises OSError from
    __init__ when the socket path is unusable, like UdsServer."""

    #: Touched only from LoopCore coroutines after construction; the
    #: async-discipline lint flags executor-bridged code reaching them.
    LOOP_ONLY_ATTRS = ("_server", "_writers")

    def __init__(self, port: int, dispatcher: ServerDispatcher, core=None):
        self.path = uds_path_for(port)
        self._dispatcher = dispatcher
        self._core = core if core is not None else dispatch_mod.get_loop_core()
        self._sock = _listen_unix(self.path)
        self._sock.setblocking(False)
        self._server = None
        # live connection writers, severed on close(): a stopped server
        # must refuse pooled clients exactly like a stopped gRPC server
        self._writers: set = set()
        self._closed = False

    def start(self):
        self._core.submit(self._start_async()).result(timeout=10)

    async def _start_async(self):
        self._server = await asyncio.start_unix_server(
            self._serve_conn, sock=self._sock
        )

    async def _serve_conn(self, reader, writer):
        if self._closed:
            writer.close()
            return
        self._writers.add(writer)
        try:
            while not self._closed:
                try:
                    header = await reader.readexactly(_REQ_HEADER.size)
                except asyncio.IncompleteReadError as e:
                    if e.partial:
                        logger.warning(
                            "uds peer closed mid-header (%d bytes)",
                            len(e.partial),
                        )
                    return
                mlen, blen = _REQ_HEADER.unpack(header)
                method = (await reader.readexactly(mlen)).decode("utf-8")
                body = await reader.readexactly(blen)
                try:
                    payload = await self._dispatcher.dispatch_async(
                        method, body, TRANSPORT_UDS
                    )
                    _refuse_oversize("response", method, len(payload))
                    # one buffer, as on the way in (`readexactly`):
                    # this listener copies both ways (ROADMAP D4b)
                    resp = payload.contiguous()
                except grpc.RpcError as e:
                    writer.write(_error_frame(e))
                    await writer.drain()
                    continue
                writer.write(_RESP_OK.pack(0, len(resp)))
                writer.write(resp)
                await writer.drain()
        except (ConnectionError, OSError, asyncio.IncompleteReadError):
            pass  # client went away; per-connection state is none
        finally:
            self._writers.discard(writer)
            try:
                writer.close()
            except OSError:  # pragma: no cover
                pass

    def close(self):
        try:
            self._core.submit(self._close_async()).result(timeout=5)
        except Exception:  # pragma: no cover - loop already gone
            pass
        # asyncio owns the fd once start() ran (_server.close() closes
        # it); socket.close() is idempotent, so this also releases the
        # constructed-but-never-started and loop-already-dead paths
        try:
            self._sock.close()
        except OSError:  # pragma: no cover
            pass
        try:
            os.unlink(self.path)
        except OSError:
            pass

    async def _close_async(self):
        self._closed = True
        if self._server is not None:
            self._server.close()
        for w in list(self._writers):
            try:
                w.close()
            except OSError:  # pragma: no cover
                pass


class _ClientConn(socket.socket):
    """A pooled client connection and what it keeps between calls: the
    memory its last large response lay in, the socket buffers the
    kernel granted it, and whether it has carried a large request."""

    def __init__(self):
        super().__init__(socket.AF_UNIX, socket.SOCK_STREAM)
        self.memory = FrameMemory()
        self.sndbuf = self.rcvbuf = 0
        self.large = False

    def close(self):
        self.memory.close()
        super().close()


class UdsTransport:
    """Client side of the UDS fast path: a small pool of persistent
    connections (the worker's pipelined step reports overlap calls), a
    per-call socket timeout from the remaining deadline budget, and
    PolicyRpcError surfaces mirroring the gRPC tier: timeouts become
    DEADLINE_EXCEEDED, connection failures UNAVAILABLE — both retryable
    — and server error frames rebuild the server's status code."""

    name = TRANSPORT_UDS

    def __init__(self, path: str, fault_plan=None):
        self._path = path
        self._plan = fault_plan
        self._pool: list = []
        self._pool_lock = threading.Lock()
        self._last = threading.local()  # .link: see last_call()

    def _checkout(self, large: bool = False) -> "_ClientConn":
        """A pooled connection, or a new one. A large request goes by
        the connection that carried the last large one, whose far end
        holds the memory that one lay in, and a small request by
        another where one is pooled: a peer's large frames keep to one
        connection, and the server to one buffer a peer, however many
        connections the peer's threads opened."""
        with self._pool_lock:
            for i in reversed(range(len(self._pool))):
                if self._pool[i].large == large:
                    return self._pool.pop(i)
            if self._pool:
                return self._pool.pop()
        conn = _ClientConn()
        try:
            with _sock_addr(self._path) as addr:
                conn.connect(addr)
            conn.sndbuf, conn.rcvbuf = _ask_socket_buffers(conn)
        except OSError as e:
            conn.close()
            raise CarrierDown(f"uds connect {self._path}: {e}")
        return conn

    def _checkin(self, conn: socket.socket):
        with self._pool_lock:
            if len(self._pool) < 8:
                self._pool.append(conn)
                return
        conn.close()

    def close(self):
        """Drain the connection pool. RpcClient.close()/reconnect()
        call this through the hasattr('close') transport hook, so a
        worker dropping its client (or re-resolving after a master
        migration) no longer strands up to 8 pooled UDS fds until GC."""
        with self._pool_lock:
            while self._pool:
                try:
                    self._pool.pop().close()
                except OSError:  # pragma: no cover - already severed
                    pass

    def last_call(self) -> dict:
        """What the connection that served the calling thread's last
        answered call can say of it, for the round trip's span:
        `sndbuf` and `rcvbuf` as the kernel granted them, and
        `recv_reused`, whether the response lies in memory an earlier
        one of that connection lay in. (Not a parameter of `call`: the
        tiers' `call` is one signature, rpc-conformance.)"""
        return getattr(self._last, "link", {})

    def call(self, method: str, payload, timeout: float) -> bytes:
        """`payload` is a `messages.PackedParts`: its parts go to the
        socket in order and are never joined."""
        _refuse_oversize("request", method, len(payload))
        # connect first: CarrierDown leaves the FaultPlan untouched, so
        # the gRPC channel that serves the call instead draws its fault
        large = len(payload) >= KEEP_FRAME_BYTES
        conn = self._checkout(large)
        try:
            if large:
                conn.large = True
            after = transport_faults_before(self._plan, method, "client")
            mb = method.encode("utf-8")
            payload.waited += _send_parts(
                conn,
                _REQ_HEADER.pack(len(mb), len(payload)) + mb,
                payload.parts,
                time.monotonic() + float(timeout),
            )
            status = _recv_exact(conn, 1)[0]
            if status == 0:
                (blen,) = struct.unpack("<I", _recv_exact(conn, 4))
                body, reused = _recv_frame(conn, blen, conn.memory)
                self._last.link = {
                    "recv_reused": reused,
                    "sndbuf": conn.sndbuf,
                    "rcvbuf": conn.rcvbuf,
                }
            else:
                code_val, dlen = struct.unpack("<iH", _recv_exact(conn, 6))
                detail = _recv_exact(conn, dlen).decode("utf-8", "replace")
                code = _CODE_BY_VALUE.get(code_val, grpc.StatusCode.UNKNOWN)
                self._checkin(conn)
                conn = None
                raise PolicyRpcError(code, detail)
        except socket.timeout:
            conn.close()
            conn = None
            raise PolicyRpcError(
                grpc.StatusCode.DEADLINE_EXCEEDED,
                f"uds call {method} timed out after {timeout:.3f}s",
            )
        except (ConnectionError, OSError) as e:
            conn.close()
            conn = None
            raise PolicyRpcError(
                grpc.StatusCode.UNAVAILABLE, f"uds {self._path}: {e}"
            )
        except PolicyRpcError:
            raise  # a drawn fault or the server's answer: a frame's edge
        except BaseException:
            # a piece of the frame did not land: the frame is cut
            # short and so is the connection, for the far end to read
            # "peer closed mid-frame" and apply nothing
            if conn is not None:
                conn.close()
                conn = None
            raise
        finally:
            if conn is not None:
                self._checkin(conn)
        transport_faults_after(after, method)
        return body


# --------------------------------------------------------------------------
# selection


def _endpoint_port(addr: str) -> Optional[int]:
    host, _, port_s = addr.rpartition(":")
    try:
        return int(port_s)
    except ValueError:
        return None


def endpoint_is_local(addr: str) -> bool:
    """Co-location detection from the endpoint string the client
    already holds (GetPSConfig / shard_host hand out localhost:<port>
    for same-host shards; see master/shard_host.py)."""
    host = addr.rpartition(":")[0].strip().lower()
    if host in _LOCAL_HOSTS:
        return True
    try:
        return host == socket.gethostname().lower()
    except OSError:  # pragma: no cover
        return False


def select_transport(addr: str, fault_plan=None, tier: Optional[str] = None):
    """The fast-path transport for `addr` under the configured mode, or
    None for plain gRPC. Never raises: any doubt (remote host, no
    socket file, unparseable endpoint) means gRPC.

    `tier` overrides the process-wide EDL_TRANSPORT mode for ONE link —
    the aggregation tree uses it to pin the aggregator->PS upstream leg
    (agg/aggregator.py). Unknown values fall back to the env mode
    rather than raising (same never-raises contract)."""
    mode = transport_mode()
    if tier is not None:
        tier = tier.strip().lower()
        if tier in TRANSPORT_TIERS or tier == "auto":
            mode = tier
    if mode == TRANSPORT_GRPC:
        return None
    port = _endpoint_port(addr)
    if port is None or not endpoint_is_local(addr):
        return None
    if mode in (TRANSPORT_INPROC, "auto") and inproc_dispatcher(port) is not None:
        return InprocTransport(port, fault_plan)
    if mode in (TRANSPORT_UDS, "auto"):
        path = uds_path_for(port)
        if os.path.exists(path):
            return UdsTransport(path, fault_plan)
    return None
