"""Transport tiers for the RPC plane: grpc / uds / shm / inproc.

The elastic window path is link-bound (docs/performance.md), yet a
co-located PS shard pays full gRPC framing for bytes that never leave
the host. This module adds three fast paths under the SAME call
surface. Which carrier a link uses is decided from what the code can
observe — is the peer on this host, and is its local listener there —
with `EDL_TRANSPORT` as the override (and the test handle):

- **uds** — a Unix-domain-socket byte protocol carrying codec frames
  with a minimal length-prefixed header, skipping gRPC/HTTP-2 framing
  entirely. The frame bytes go to `sendall` as-is (no re-serialization)
  and the receiver hands the codec one contiguous buffer to build
  `np.frombuffer` views over — the zero-copy contract of codec v2 holds
  end to end.
- **shm** — per-connection shared-memory segments
  (`multiprocessing.shared_memory`) carrying the same codec frames for
  co-located SEPARATE processes (shard_host subprocesses): the sender
  writes the frame into its connection's ring region, a tiny
  Unix-socket doorbell message carries only the wakeup + method name +
  frame length, and the server hands the dispatcher `np.frombuffer`
  views built directly over the mapped region — request payload bytes
  never cross a socket and are never copied on the receive side. The
  server additionally publishes read-only BROADCAST segments for
  prepacked fan-out responses (PSShard pull's per-version model frame):
  the reply is then a marker the client resolves against its own
  mapping of the published segment, so N co-located pullers share one
  encode and zero per-pull payload copies. Rendezvous is a port-keyed
  JSON file next to the doorbell socket embedding the serving fencing
  generation; a relaunched shard sweeps its predecessor's segments and
  rendezvous files at boot, so a client can never attach a dead ring.
- **inproc** — when the serving `RpcServer` lives in the SAME
  interpreter (bench/test mode, `PSShardGroup` inproc shards), the call
  dispatches directly into the server's handler table: the packed frame
  is passed by reference, no socket at all. WireStats records these
  calls with zero wire bytes under the "inproc" tier.

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
reachable (a registered in-process dispatcher, a readable shm
rendezvous file with its doorbell socket, or an existing socket file);
otherwise the caller falls back to gRPC. `auto` prefers
inproc > shm > uds > grpc.

With `EDL_TRANSPORT` unset the mode is **uds**: every `RpcServer`
opens its Unix-socket listener beside gRPC, a client whose endpoint is
local and whose socket file exists is carried by it, and a remote
endpoint (the k8s path advertises the pod IP) gets gRPC. Unset is not
`auto`: no `inproc` (in-process tests keep their sockets) and no `shm`
(a segment per connection). `EDL_TRANSPORT=grpc` is pure gRPC: no
listener, no fast path.

The socket tiers receive a frame with no copy beyond the kernel's:
`_recv_frame` reads into memory that was never zero-filled and hands
the dispatcher / `messages.unpack` a read-only view of it, over which
the codec builds its `np.frombuffer` views. A buffer is never reused:
each frame gets its own, which lives as long as an array decoded from
it (the master keeps such views past the handler: `grads_to_wait` > 1,
fan-in).

A local carrier that cannot CONNECT (the socket file of a dead server,
a server relaunched under `EDL_TRANSPORT=grpc` on a reused port) raises
`CarrierDown` before anything was sent or any client-side fault was
drawn; `RpcClient` then serves that call over the gRPC channel it
holds anyway, so a stale file costs a failed `connect()` a call, never
an endpoint that answers UNAVAILABLE for ever.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import os
import socket
import struct
import tempfile
import threading
import time
from concurrent import futures
from multiprocessing import shared_memory as _shm_mod
from typing import Callable, Dict, Optional

import grpc
import numpy as np

from elasticdl_tpu.common import codec, messages
from elasticdl_tpu.common.constants import (
    ENV_TRANSPORT,
    ENV_TRANSPORT_SHM_DOORBELL_TIMEOUT,
    ENV_TRANSPORT_SHM_RING,
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
TRANSPORT_SHM = "shm"
TRANSPORT_INPROC = "inproc"
#: The tiers WireStats rows may carry; "auto" is a selection policy,
#: not a tier.
TRANSPORT_TIERS = (
    TRANSPORT_GRPC,
    TRANSPORT_UDS,
    TRANSPORT_SHM,
    TRANSPORT_INPROC,
)

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

_CODE_BY_VALUE = {c.value[0]: c for c in grpc.StatusCode}

#: shm handshake (server -> client on accept): u32 fencing generation,
#: u32 segment-name length, u64 per-direction ring bytes; then the
#: segment name utf-8. The client attaches the named segment: request
#: region [0, ring), response region [ring, 2*ring).
_SHM_HELLO = struct.Struct("<IIQ")
#: shm request doorbell: kind (1 = whole frame already in the request
#: region, 2 = chunked transfer follows), u16 method length, u32 frame
#: length (total length for kind 2); then the method utf-8.
_SHM_REQ = struct.Struct("<BHI")
#: shm response doorbell: status (0 = ok frame in the response region,
#: 1 = error, 2 = chunked ok follows, 3 = broadcast marker frame in the
#: response region), u32 length.
_SHM_RESP = struct.Struct("<BI")
#: chunk sub-header (either direction): u32 chunk length; each chunk is
#: acked with one byte before the region is overwritten.
_SHM_CHUNK = struct.Struct("<I")
#: shm error tail after a status-1 doorbell: i32 grpc status-code
#: value, u16 detail length; then the detail utf-8.
_SHM_ERR = struct.Struct("<iH")
_SHM_ACK = b"\x06"
#: Top-level key of a broadcast marker frame; the value is the segment
#: descriptor {"seg": <name>, "n": <frame bytes>}.
_SHM_BCAST_KEY = "__shm_bcast__"


#: The mode when EDL_TRANSPORT is unset: a local peer with a listener
#: gets the Unix-socket carrier, everyone else gRPC.
DEFAULT_MODE = TRANSPORT_UDS


def transport_mode(env=None) -> str:
    """The configured tier ("grpc"/"uds"/"shm"/"inproc"/"auto"); unset
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


def server_shm_enabled() -> bool:
    """Whether RpcServer should open the shared-memory listener."""
    return transport_mode() in (TRANSPORT_SHM, "auto")


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


_SHM_DEFAULT_RING = 1 << 22  # 4 MiB per direction


def shm_ring_bytes(env=None) -> int:
    """Per-direction ring capacity for each shm connection, rounded up
    to the codec's 64-byte segment alignment so region offset 0 always
    satisfies the zero-copy view contract."""
    env = os.environ if env is None else env
    try:
        n = int(env.get(ENV_TRANSPORT_SHM_RING, "") or _SHM_DEFAULT_RING)
    except ValueError:
        n = _SHM_DEFAULT_RING
    n = max(n, 4096)
    return (n + 63) // 64 * 64


def shm_doorbell_timeout(env=None) -> float:
    """Socket timeout for the doorbell handshake and chunk-ack phases
    (the per-call deadline still comes from the caller's budget)."""
    env = os.environ if env is None else env
    try:
        t = float(env.get(ENV_TRANSPORT_SHM_DOORBELL_TIMEOUT, "") or 5.0)
    except ValueError:
        t = 5.0
    return max(t, 0.001)


def shm_doorbell_path(port: int) -> str:
    """Doorbell socket path for a server on gRPC `port`; like the UDS
    tier, the port number is the rendezvous key."""
    return os.path.join(uds_dir(), f"edl-shm-{int(port)}.sock")


def shm_rendezvous_path(port: int) -> str:
    """Rendezvous JSON for a server on gRPC `port`: scope, fencing
    generation, segment-name prefix, doorbell path, ring bytes, pid.
    Written atomically AFTER the doorbell socket is listening, so its
    existence is the client-visible signal the tier is up; swept by the
    successor's boot reclamation when the writer dies."""
    return os.path.join(uds_dir(), f"edl-shm-{int(port)}.json")


def read_shm_rendezvous(port: int) -> Optional[dict]:
    try:
        with open(shm_rendezvous_path(port), "r", encoding="utf-8") as f:
            info = json.load(f)
    except (OSError, ValueError):
        return None
    return info if isinstance(info, dict) else None


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

    def dispatch(self, method: str, request_bytes, transport: str) -> bytes:
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
        resp_bytes = self._invoke(method, request_bytes, transport)
        # drop/crash-after fire with the handler APPLIED (same contract
        # as the server interceptor: state changed, response withheld)
        transport_faults_after(after, method)
        return resp_bytes

    async def dispatch_async(
        self, method: str, request_bytes, transport: str
    ) -> bytes:
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
    ) -> bytes:
        after = []
        if transport != TRANSPORT_GRPC:
            after = transport_faults_before(self._plan, method, "server")
        resp_bytes = self._invoke(method, request_bytes, transport, t_admit)
        transport_faults_after(after, method)
        return resp_bytes

    def _invoke(
        self, method: str, request_bytes, transport: str, t_admit=None
    ) -> bytes:
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
            if sp is not None:
                obs_trace.bind(prev_ctx)
                sp.end()
        if (
            transport == TRANSPORT_SHM
            and isinstance(resp, messages.Prepacked)
            and getattr(resp, "shm_ref", None)
        ):
            # broadcast substitution: the wire carries only a tiny
            # descriptor frame — the payload stays in the published
            # read-only segment every co-located client maps once per
            # version. WireStats therefore records marker bytes here
            # (the documented shm asymmetry: clients account the
            # resolved frame length they actually consumed).
            resp_bytes = _ShmBcastMarkerBytes(
                codec.dumps({_SHM_BCAST_KEY: dict(resp.shm_ref)})
            )
        elif timed:
            version = resp.get("version") if isinstance(resp, dict) else None
            t_encode = time.time()
            resp_bytes = messages.pack(resp)
            record = self._timers.record
            record(
                "rpc.decode", t_decode, t_decoded, method=method,
                bytes=nbytes, version=version,
            )
            record(
                "rpc.encode", t_encode, time.time(), method=method,
                bytes=len(resp_bytes), version=version,
            )
        else:
            resp_bytes = messages.pack(resp)
        self._wire.record(
            method,
            sent=0 if inproc else len(resp_bytes),
            transport=transport,
            calls=1,
        )
        return resp_bytes


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
    """Direct dispatch into a same-interpreter RpcServer. The packed
    codec frame crosses by reference — zero wire bytes, zero copies.
    The dispatcher is re-resolved per call so a shard relaunch (new
    server object on a new port -> new client) or a stopped server
    surfaces as UNAVAILABLE for the retry/recovery machinery, never a
    stale handler table."""

    name = TRANSPORT_INPROC

    def __init__(self, port: int, fault_plan=None):
        self._port = int(port)
        self._plan = fault_plan

    def call(self, method: str, payload: bytes, timeout: float) -> bytes:
        after = transport_faults_before(self._plan, method, "client")
        dispatcher = inproc_dispatcher(self._port)
        if dispatcher is None:
            raise PolicyRpcError(
                grpc.StatusCode.UNAVAILABLE,
                f"inproc server for port {self._port} is gone",
            )
        resp = dispatcher.dispatch(method, payload, TRANSPORT_INPROC)
        transport_faults_after(after, method)
        return resp


# --------------------------------------------------------------------------
# uds: length-prefixed codec frames over AF_UNIX


def _rpc_error_fields(e: grpc.RpcError):
    """(status code, clamped detail bytes) for a dispatch failure —
    enough to rebuild the PolicyRpcError the gRPC tier would have
    surfaced; shared by the uds and shm error framings."""
    code = e.code() if callable(getattr(e, "code", None)) else None
    if not isinstance(code, grpc.StatusCode):
        code = grpc.StatusCode.INTERNAL
    details = ""
    if callable(getattr(e, "details", None)):
        details = e.details() or ""
    return code, details.encode("utf-8")[:1024]


def _error_frame(e: grpc.RpcError) -> bytes:
    """The UDS error response frame for a dispatch failure."""
    code, detail_b = _rpc_error_fields(e)
    return _RESP_ERR.pack(1, code.value[0], len(detail_b)) + detail_b


def _recv_fill(conn: socket.socket, view, n: int, eof_ok: bool = False) -> bool:
    """Fill view[:n] from the socket; False on a clean EOF before the
    first byte (eof_ok), ConnectionError on EOF after it."""
    got = 0
    while got < n:
        k = conn.recv_into(view[got:], n - got)
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
    n bytes. The memory is not zero-filled (a `bytearray(n)` is, one
    pass over 649 MB for nothing) and the read-only view is what the
    codec decodes from: no trailing `bytes()` copy. Read-only, like
    the `bytes` it replaces, so decoded arrays stay read-only views."""
    buf = np.empty(n, dtype=np.uint8)
    return memoryview(buf), memoryview(buf).toreadonly()


def _recv_frame(conn: socket.socket, n: int):
    """Read a frame body of exactly n bytes with no copy beyond the
    kernel's; ConnectionError on EOF inside it."""
    view, frame = _frame_buffer(n)
    _recv_fill(conn, view, n)
    return frame


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
        try:
            while not self._is_closed():
                header = _recv_exact(conn, _REQ_HEADER.size, eof_ok=True)
                if header is None:
                    return
                mlen, blen = _REQ_HEADER.unpack(header)
                method = _recv_exact(conn, mlen).decode("utf-8")
                try:
                    # no local names the frame: once dispatched it lives
                    # on only in what the handler kept of it, and a 649
                    # MB request is gone before the connection's next
                    resp = self._dispatcher.dispatch(
                        method, _recv_frame(conn, blen), TRANSPORT_UDS
                    )
                except grpc.RpcError as e:
                    conn.sendall(_error_frame(e))
                    continue
                conn.sendall(_RESP_OK.pack(0, len(resp)))
                conn.sendall(resp)
        except (ConnectionError, OSError):
            pass  # client went away; per-connection state is none
        finally:
            with self._conns_lock:
                self._conns.discard(conn)
            try:
                conn.close()
            except OSError:
                pass

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
                    resp = await self._dispatcher.dispatch_async(
                        method, body, TRANSPORT_UDS
                    )
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

    def _checkout(self) -> socket.socket:
        with self._pool_lock:
            if self._pool:
                return self._pool.pop()
        conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            with _sock_addr(self._path) as addr:
                conn.connect(addr)
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

    def call(self, method: str, payload: bytes, timeout: float) -> bytes:
        # connect first: CarrierDown leaves the FaultPlan untouched, so
        # the gRPC channel that serves the call instead draws its fault
        conn = self._checkout()
        try:
            after = transport_faults_before(self._plan, method, "client")
            conn.settimeout(max(0.001, float(timeout)))
            mb = method.encode("utf-8")
            conn.sendall(_REQ_HEADER.pack(len(mb), len(payload)) + mb)
            conn.sendall(payload)
            status = _recv_exact(conn, 1)[0]
            if status == 0:
                (blen,) = struct.unpack("<I", _recv_exact(conn, 4))
                body = _recv_frame(conn, blen)
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
        except (ConnectionError, OSError, struct.error) as e:
            conn.close()
            conn = None
            raise PolicyRpcError(
                grpc.StatusCode.UNAVAILABLE, f"uds {self._path}: {e}"
            )
        finally:
            if conn is not None:
                self._checkin(conn)
        transport_faults_after(after, method)
        return body


# --------------------------------------------------------------------------
# shm: codec frames through per-connection shared-memory rings, with a
# Unix-socket doorbell for wakeup (no spinning) and read-only broadcast
# segments for prepacked fan-out responses


class _ShmBcastMarkerBytes(bytes):
    """Response-bytes subtype produced by `ServerDispatcher._invoke`
    when an shm response was substituted by a broadcast marker; the
    ShmServer conn loop keys the status-3 doorbell off this type so the
    marker survives the ordinary bytes-returning dispatch chain (both
    dispatch cores, including the loop executor bridge)."""


def _shm_error_frame(e: grpc.RpcError) -> bytes:
    code, detail_b = _rpc_error_fields(e)
    return (
        _SHM_RESP.pack(1, 0)
        + _SHM_ERR.pack(code.value[0], len(detail_b))
        + detail_b
    )


class _QuietSharedMemory(_shm_mod.SharedMemory):
    """SharedMemory whose destructor tolerates still-exported views.
    At interpreter shutdown GC order is arbitrary, so a caller-held
    np view over a mapping can outlive the segment object; the base
    destructor then raises BufferError into "Exception ignored"
    noise. The kernel reclaims the mapping at process exit either
    way."""

    def __del__(self):
        try:
            super().__del__()
        except BufferError:
            pass


_attach_lock = threading.Lock()


def _attach_shm_segment(name: str) -> _shm_mod.SharedMemory:
    """Attach (never create) an existing segment. CPython < 3.13
    registers even attachments with the multiprocessing resource
    tracker, which would unlink server-owned segments when THIS
    process exits (and warn about "leaks"); suppress the registration
    for the attach — segment lifecycle belongs to the serving side.
    (Suppression beats unregistering afterwards: an unregister without
    a matching registration in the same process makes the tracker
    daemon print KeyError tracebacks at exit.)

    The suppression monkeypatch is process-global, so every segment
    CREATE must hold the same lock (`_create_shm_segment`) — a create
    landing inside another thread's suppression window would lose its
    tracker registration, and its eventual unlink would feed the
    tracker daemon an unmatched unregister (KeyError traceback)."""
    from multiprocessing import resource_tracker

    with _attach_lock:
        orig = resource_tracker.register
        resource_tracker.register = lambda *a, **k: None
        try:
            return _QuietSharedMemory(name=name)
        finally:
            resource_tracker.register = orig


def _create_shm_segment(name: str, size: int) -> _shm_mod.SharedMemory:
    """Create a segment under `_attach_lock` so its tracker
    registration cannot be swallowed by a concurrent attach's
    register-suppression window (see `_attach_shm_segment`)."""
    with _attach_lock:
        return _QuietSharedMemory(name=name, create=True, size=size)


def _sanitize_scope(scope: str) -> str:
    out = "".join(c if c.isalnum() or c in "._-" else "-" for c in scope)
    return out[:48] or "s"


def _unlink_segments(prefix: str) -> None:
    """Best-effort unlink of every segment whose name starts with
    `prefix`. Enumeration uses /dev/shm (Linux shm_open backing); on
    platforms without it the rendezvous-file sweep still removes the
    doorbell + json, and the kernel reclaims segments with the last
    unmap."""
    if not prefix:
        return
    try:
        names = os.listdir("/dev/shm")
    except OSError:
        return
    for name in names:
        if name.startswith(prefix):
            try:
                os.unlink(os.path.join("/dev/shm", name))
            except OSError:
                pass


class ShmBroadcaster:
    """Server-owned publisher of read-only broadcast segments: one
    whole codec frame per segment, written via `codec.dumps_parts` +
    `write_frame_into` straight into the fresh mapping (the final join
    copy of `dumps` never happens). Keeps the last few segments alive
    so clients racing a version bump can still attach the previous
    one; everything is unlinked on close."""

    KEEP = 4

    def __init__(self, prefix: str):
        self._prefix = prefix
        self._lock = threading.Lock()
        self._segments: list = []  # [(name, SharedMemory, view)]
        self._retired: list = []  # evicted but still-referenced mappings
        self._seq = 0
        self._closed = False

    def publish(self, obj) -> Optional[tuple]:
        """Encode `obj` into a new segment; returns (ref, view) where
        `ref` is the marker descriptor and `view` a memoryview over the
        published frame, or None once closed."""
        parts, total = codec.dumps_parts(obj)
        with self._lock:
            if self._closed:
                return None
            self._seq += 1
            name = f"{self._prefix}b{self._seq}"
        seg = _create_shm_segment(name, max(total, 1))
        codec.write_frame_into(parts, total, seg.buf)
        view = memoryview(seg.buf)[:total]
        with self._lock:
            if self._closed:
                view.release()
                seg.close()
                try:
                    seg.unlink()
                except OSError:
                    pass
                return None
            self._segments.append((name, seg, view))
            evicted = []
            while len(self._segments) > self.KEEP:
                evicted.append(self._segments.pop(0))
            retired, self._retired = self._retired, []
        for old_name, old_seg, old_view in evicted:
            try:
                old_seg.unlink()
            except OSError:
                pass
            old_view.release()
            self._close_or_retire(old_seg)
        for old_seg in retired:
            self._close_or_retire(old_seg)
        return {"seg": name, "n": int(total)}, view

    def _close_or_retire(self, seg) -> None:
        try:
            seg.close()
        except BufferError:
            # a served Prepacked still holds a view over the mapping;
            # retry on the next publish/close instead of crashing the
            # serve path
            with self._lock:
                self._retired.append(seg)

    def close(self) -> None:
        with self._lock:
            self._closed = True
            segments = self._segments
            retired = self._retired
            self._segments = []
            self._retired = []
        for name, seg, view in segments:
            try:
                seg.unlink()
            except OSError:
                pass
            view.release()
            try:
                seg.close()
            except BufferError:  # pragma: no cover - caller kept a view
                pass
        for seg in retired:
            try:
                seg.close()
            except BufferError:  # pragma: no cover
                pass


class ShmServer:
    """Threaded shared-memory listener sharing an RpcServer's
    dispatcher. Each accepted doorbell connection gets its own
    SharedMemory segment (request region [0, ring), response region
    [ring, 2*ring)); the doorbell socket carries only wakeups, method
    names, and frame lengths. Request frames that fit the ring are
    handed to the dispatcher as a memoryview over the mapping — the
    codec builds `np.frombuffer` views directly over shared memory, so
    request payloads cross processes with zero copies; oversize frames
    fall back to a chunked copy through the ring. Serves BOTH
    `EDL_DISPATCH` cores through `ServerDispatcher.dispatch` (under
    the loop core the conn thread parks on the reactor shim, exactly
    like a grpc pool thread — shm connections are few per host, so the
    thread-per-connection read side costs what the grpc pool already
    pays).

    Boot order is the crash-safety story: sweep the dead predecessor's
    segments/rendezvous (same port, or same scope at any older
    generation), bind the doorbell, then atomically publish the
    rendezvous file embedding THIS fencing generation — a client
    resolving the file can never attach a dead ring. Raises OSError
    from __init__ when the doorbell path is unusable — the caller logs
    and serves gRPC only."""

    def __init__(
        self,
        port: int,
        dispatcher: ServerDispatcher,
        scope: Optional[str] = None,
        generation: int = 0,
    ):
        self.port = int(port)
        self._dispatcher = dispatcher
        self.generation = int(generation)
        self._scope = _sanitize_scope(scope) if scope else f"p{self.port}"
        self._ring = shm_ring_bytes()
        self._prefix = f"edlshm.{self._scope}.g{self.generation}."
        self._reclaim_stale()
        self.doorbell = shm_doorbell_path(self.port)
        self.path = shm_rendezvous_path(self.port)
        try:
            os.unlink(self.doorbell)
        except FileNotFoundError:
            pass
        self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            with _sock_addr(self.doorbell) as addr:
                self._sock.bind(addr)
            self._sock.listen(128)
            self.broadcaster = ShmBroadcaster(self._prefix + "x")
            self._conn_seq = 0
            self._thread: Optional[threading.Thread] = None
            # live connections, severed on close(): a stopped server
            # must refuse pooled clients exactly like a stopped gRPC
            # server
            self._conns: set = set()
            self._conn_threads: list = []
            self._conns_lock = threading.Lock()
            self._closed = False
            tmp = self.path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(
                    {
                        "scope": self._scope,
                        "generation": self.generation,
                        "prefix": self._prefix,
                        "doorbell": self.doorbell,
                        "ring": self._ring,
                        "pid": os.getpid(),
                    },
                    f,
                )
            os.replace(tmp, self.path)
        except Exception:
            # a raise between the doorbell bind and the rendezvous
            # write (disk full, unlinkable path, broadcast segment
            # collision) leaves a half-built server the caller cannot
            # close(): release the doorbell socket/path and the
            # broadcast segment before re-raising so a relaunch on the
            # same port starts clean instead of inheriting our debris
            self._sock.close()
            broadcaster = getattr(self, "broadcaster", None)
            if broadcaster is not None:
                broadcaster.close()
            for leftover in (self.doorbell, self.path + ".tmp"):
                try:
                    os.unlink(leftover)
                except OSError:
                    pass
            raise

    def _reclaim_stale(self) -> None:
        """Sweep a dead predecessor's rings. The rendezvous file keyed
        by MY port is stale by construction (the caller's gRPC bind
        proved the port free); any segment carrying MY scope predates
        this server (one live server per scope slot, and this server
        has created nothing yet); and same-scope rendezvous files on
        OTHER ports at an OLDER fencing generation belong to a
        SIGKILLed incarnation whose relaunch (this one) got a fresh
        port."""
        mine = read_shm_rendezvous(self.port)
        if mine is not None:
            _unlink_segments(str(mine.get("prefix", "")))
            for p in (
                str(mine.get("doorbell", "")),
                shm_rendezvous_path(self.port),
            ):
                try:
                    os.unlink(p)
                except OSError:
                    pass
        _unlink_segments(f"edlshm.{self._scope}.")
        try:
            names = os.listdir(uds_dir())
        except OSError:
            return
        for name in names:
            if not (name.startswith("edl-shm-") and name.endswith(".json")):
                continue
            path = os.path.join(uds_dir(), name)
            try:
                with open(path, "r", encoding="utf-8") as f:
                    other = json.load(f)
            except (OSError, ValueError):
                continue
            if not isinstance(other, dict):
                continue
            try:
                other_gen = int(other.get("generation", -1))
            except (TypeError, ValueError):
                continue
            if other.get("scope") == self._scope and other_gen < self.generation:
                _unlink_segments(str(other.get("prefix", "")))
                for p in (str(other.get("doorbell", "")), path):
                    try:
                        os.unlink(p)
                    except OSError:
                        pass

    def start(self):
        self._thread = threading.Thread(
            target=self._accept_loop,
            name=f"shm-accept-{self.doorbell}",
            daemon=True,
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
            t = threading.Thread(
                target=self._serve_conn, args=(conn,), daemon=True
            )
            with self._conns_lock:
                # reap finished conn threads so a long-lived server
                # under connection churn doesn't grow the list (and
                # close()'s join sweep) without bound
                for dead in [
                    x for x in self._conn_threads if not x.is_alive()
                ]:
                    self._conn_threads.remove(dead)
                self._conn_threads.append(t)
            t.start()

    def _serve_conn(self, conn: socket.socket):
        with self._conns_lock:
            if self._closed:
                conn.close()
                return
            self._conns.add(conn)
            self._conn_seq += 1
            name = f"{self._prefix}c{self._conn_seq}"
        seg = None
        req_region = resp_region = None
        try:
            seg = _create_shm_segment(name, 2 * self._ring)
            mb = name.encode("utf-8")
            conn.sendall(
                _SHM_HELLO.pack(self.generation, len(mb), self._ring) + mb
            )
            req_region = memoryview(seg.buf)[: self._ring]
            resp_region = memoryview(seg.buf)[self._ring : 2 * self._ring]
            while not self._is_closed():
                header = _recv_exact(conn, _SHM_REQ.size, eof_ok=True)
                if header is None:
                    return
                kind, mlen, length = _SHM_REQ.unpack(header)
                method = _recv_exact(conn, mlen).decode("utf-8")
                if kind == 1:
                    if length > self._ring:
                        raise ConnectionError(
                            f"shm frame length {length} exceeds ring"
                        )
                    # zero-copy hand-off: the dispatcher (and the codec
                    # below it) reads straight from the mapped region,
                    # which stays untouched until the response doorbell
                    body = req_region[:length]
                else:
                    body = self._recv_chunked(conn, req_region, length)
                try:
                    resp = self._dispatcher.dispatch(method, body, TRANSPORT_SHM)
                except grpc.RpcError as e:
                    conn.sendall(_shm_error_frame(e))
                    continue
                if isinstance(resp, _ShmBcastMarkerBytes):
                    resp_region[: len(resp)] = resp
                    conn.sendall(_SHM_RESP.pack(3, len(resp)))
                elif len(resp) <= self._ring:
                    resp_region[: len(resp)] = resp
                    conn.sendall(_SHM_RESP.pack(0, len(resp)))
                else:
                    self._send_chunked(conn, resp_region, resp)
        except (ConnectionError, OSError, struct.error):
            pass  # client went away; per-connection state is the segment
        finally:
            with self._conns_lock:
                self._conns.discard(conn)
            try:
                conn.close()
            except OSError:
                pass
            if req_region is not None:
                req_region.release()
            if resp_region is not None:
                resp_region.release()
            if seg is not None:
                try:
                    seg.close()
                except BufferError:  # pragma: no cover - handler kept a view
                    pass
                try:
                    seg.unlink()
                except OSError:
                    pass

    def _recv_chunked(self, conn, region, total: int):
        """Oversize-request fallback: assemble the frame through the
        ring in ring-sized pieces (one copy — the zero-copy contract
        holds only for frames that fit the ring)."""
        out, frame = _frame_buffer(total)
        got = 0
        conn.settimeout(shm_doorbell_timeout())
        try:
            while got < total:
                (clen,) = _SHM_CHUNK.unpack(_recv_exact(conn, _SHM_CHUNK.size))
                if clen > len(region) or got + clen > total:
                    raise ConnectionError(f"shm chunk overrun ({clen} bytes)")
                out[got : got + clen] = region[:clen]
                got += clen
                conn.sendall(_SHM_ACK)  # client may reuse the region
        finally:
            conn.settimeout(None)
        return frame

    def _send_chunked(self, conn, region, resp: bytes) -> None:
        total = len(resp)
        conn.sendall(_SHM_RESP.pack(2, total))
        rv = memoryview(resp)
        sent = 0
        conn.settimeout(shm_doorbell_timeout())
        try:
            while sent < total:
                clen = min(self._ring, total - sent)
                region[:clen] = rv[sent : sent + clen]
                conn.sendall(_SHM_CHUNK.pack(clen))
                _recv_exact(conn, 1)  # client copied the chunk out
                sent += clen
        finally:
            conn.settimeout(None)

    def close(self):
        with self._conns_lock:
            self._closed = True
            conns = list(self._conns)
            threads = list(self._conn_threads)
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        try:
            self._sock.close()
        except OSError:
            pass
        # deterministic teardown: wait for each conn thread's segment
        # unlink so close() returning means /dev/shm is clean (tests
        # and operators check exactly that); the prefix sweep backstops
        # a thread that outlives the join timeout
        for t in threads:
            t.join(timeout=5)
        self.broadcaster.close()
        _unlink_segments(self._prefix)
        for p in (self.doorbell, self.path):
            try:
                os.unlink(p)
            except OSError:
                pass


class _ShmConn:
    """One client connection: the doorbell socket plus this
    connection's mapped segment regions. Destroyed (never pooled) on
    any protocol error — a fresh connection re-runs the handshake."""

    __slots__ = ("sock", "seg", "ring", "generation", "req", "resp")

    def __init__(self, doorbell: str):
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            with _sock_addr(doorbell) as addr:
                sock.connect(addr)
        except OSError as e:
            sock.close()
            raise CarrierDown(f"shm connect {doorbell}: {e}")
        try:
            sock.settimeout(shm_doorbell_timeout())
            hello = _recv_exact(sock, _SHM_HELLO.size)
            gen, nlen, ring = _SHM_HELLO.unpack(hello)
            name = _recv_exact(sock, nlen).decode("utf-8")
            seg = _attach_shm_segment(name)
        except (ConnectionError, OSError, struct.error) as e:
            sock.close()
            raise PolicyRpcError(
                grpc.StatusCode.UNAVAILABLE, f"shm connect {doorbell}: {e}"
            )
        self.sock = sock
        self.seg = seg
        self.ring = int(ring)
        self.generation = int(gen)
        self.req = memoryview(seg.buf)[: self.ring]
        self.resp = memoryview(seg.buf)[self.ring : 2 * self.ring]

    def destroy(self):
        try:
            self.sock.close()
        except OSError:
            pass
        self.req.release()
        self.resp.release()
        try:
            self.seg.close()
        except BufferError:  # pragma: no cover - caller kept a view
            pass


class ShmTransport:
    """Client side of the shm tier: a small pool of persistent
    connections (pipelined step reports overlap calls, like the UDS
    pool), per-call socket timeouts from the deadline budget, and the
    same PolicyRpcError surfaces as the other tiers. Ordinary
    responses are copied out of the response region (one copy, the
    same cost as a socket recv); broadcast markers resolve to a
    memoryview over the published segment this process maps once per
    version — the zero-copy model-down path."""

    name = TRANSPORT_SHM

    #: broadcast attachments kept mapped per transport
    BCAST_KEEP = 4

    def __init__(self, port: int, fault_plan=None):
        self._port = int(port)
        self._doorbell = shm_doorbell_path(port)
        self._plan = fault_plan
        self._pool: list = []
        self._pool_lock = threading.Lock()
        self._bcast: Dict[str, tuple] = {}  # name -> (SharedMemory, view)
        self._bcast_order: list = []
        self._bcast_retired: list = []
        self._bcast_lock = threading.Lock()

    def _checkout(self) -> _ShmConn:
        with self._pool_lock:
            if self._pool:
                return self._pool.pop()
        return _ShmConn(self._doorbell)

    def _checkin(self, conn: _ShmConn):
        with self._pool_lock:
            if len(self._pool) < 8:
                self._pool.append(conn)
                return
        conn.destroy()

    def call(self, method: str, payload: bytes, timeout: float) -> bytes:
        # connect first, as UdsTransport.call does and for its reason
        conn = self._checkout()
        try:
            after = transport_faults_before(self._plan, method, "client")
            conn.sock.settimeout(max(0.001, float(timeout)))
            mb = method.encode("utf-8")
            n = len(payload)
            if n <= conn.ring:
                conn.req[:n] = payload
                conn.sock.sendall(_SHM_REQ.pack(1, len(mb), n) + mb)
            else:
                conn.sock.sendall(_SHM_REQ.pack(2, len(mb), n) + mb)
                pv = memoryview(payload)
                sent = 0
                while sent < n:
                    clen = min(conn.ring, n - sent)
                    conn.req[:clen] = pv[sent : sent + clen]
                    conn.sock.sendall(_SHM_CHUNK.pack(clen))
                    _recv_exact(conn.sock, 1)  # server copied the chunk
                    sent += clen
            status, length = _SHM_RESP.unpack(
                _recv_exact(conn.sock, _SHM_RESP.size)
            )
            if status == 0:
                # private copy: the response region is reused by the
                # next call on this connection
                body = bytes(conn.resp[:length])
            elif status == 3:
                body = self._resolve_bcast(bytes(conn.resp[:length]))
            elif status == 2:
                buf, body = _frame_buffer(length)
                got = 0
                while got < length:
                    (clen,) = _SHM_CHUNK.unpack(
                        _recv_exact(conn.sock, _SHM_CHUNK.size)
                    )
                    if clen > conn.ring or got + clen > length:
                        raise ConnectionError(
                            f"shm chunk overrun ({clen} bytes)"
                        )
                    buf[got : got + clen] = conn.resp[:clen]
                    got += clen
                    conn.sock.sendall(_SHM_ACK)
            else:
                code_val, dlen = _SHM_ERR.unpack(
                    _recv_exact(conn.sock, _SHM_ERR.size)
                )
                detail = _recv_exact(conn.sock, dlen).decode("utf-8", "replace")
                code = _CODE_BY_VALUE.get(code_val, grpc.StatusCode.UNKNOWN)
                self._checkin(conn)
                conn = None
                raise PolicyRpcError(code, detail)
        except socket.timeout:
            conn.destroy()
            conn = None
            raise PolicyRpcError(
                grpc.StatusCode.DEADLINE_EXCEEDED,
                f"shm call {method} timed out after {timeout:.3f}s",
            )
        except (ConnectionError, OSError, struct.error) as e:
            conn.destroy()
            conn = None
            raise PolicyRpcError(
                grpc.StatusCode.UNAVAILABLE, f"shm {self._doorbell}: {e}"
            )
        finally:
            if conn is not None:
                self._checkin(conn)
        transport_faults_after(after, method)
        return body

    def _resolve_bcast(self, marker: bytes):
        """Resolve a broadcast marker to a memoryview over this
        process's mapping of the published segment. An attach race with
        segment rotation surfaces as UNAVAILABLE — retryable, and the
        retried pull lands on the current version's segment."""
        try:
            ref = messages.unpack(marker).get(_SHM_BCAST_KEY)
        except Exception:
            ref = None
        if not isinstance(ref, dict):
            raise PolicyRpcError(
                grpc.StatusCode.INTERNAL, "shm broadcast marker malformed"
            )
        name = str(ref.get("seg", ""))
        n = int(ref.get("n", 0))
        with self._bcast_lock:
            ent = self._bcast.get(name)
        if ent is None:
            # first touch of this segment in this process: the actual
            # page-in cost of the zero-copy model-down path — spanned
            # so the overlap A/B's traces show where it lands (on the
            # background absorb thread, not the step loop)
            with obs_trace.span(
                "rpc.client.bcast_map", cat="rpc", args={"seg": name}
            ):
                try:
                    seg = _attach_shm_segment(name)
                except (OSError, ValueError) as e:
                    raise PolicyRpcError(
                        grpc.StatusCode.UNAVAILABLE,
                        f"shm broadcast segment {name} rotated: {e}",
                    )
                view = memoryview(seg.buf)
            evicted = []
            with self._bcast_lock:
                if name not in self._bcast:
                    self._bcast[name] = (seg, view)
                    self._bcast_order.append(name)
                    while len(self._bcast_order) > self.BCAST_KEEP:
                        evicted.append(
                            self._bcast.pop(self._bcast_order.pop(0))
                        )
                    retired, self._bcast_retired = self._bcast_retired, []
                else:
                    evicted.append((seg, view))
                    retired = []
                ent = self._bcast[name]
            for old_seg, old_view in evicted:
                old_view.release()
                self._close_or_retire(old_seg)
            for old_seg in retired:
                self._close_or_retire(old_seg)
        return ent[1][:n]

    def _close_or_retire(self, seg) -> None:
        try:
            seg.close()  # attachment only; the server owns the unlink
        except BufferError:
            # a resolved pull response still references the mapping;
            # retry on a later eviction instead of invalidating it
            with self._bcast_lock:
                self._bcast_retired.append(seg)

    def close(self) -> None:
        """Destroy pooled connections and drop broadcast attachments
        (mappings a caller still references are left to the GC)."""
        with self._pool_lock:
            pool, self._pool = self._pool, []
        for conn in pool:
            conn.destroy()
        with self._bcast_lock:
            entries = list(self._bcast.values())
            self._bcast.clear()
            self._bcast_order.clear()
            retired, self._bcast_retired = self._bcast_retired, []
        for seg, view in entries:
            view.release()
            self._close_or_retire(seg)
        for seg in retired:
            self._close_or_retire(seg)


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
    to uds/grpc while the worker->aggregator leg keeps the ambient shm
    mode (agg/aggregator.py). Unknown values fall back to the env mode
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
    if mode in (TRANSPORT_SHM, "auto"):
        info = read_shm_rendezvous(port)
        if info is not None and os.path.exists(str(info.get("doorbell", ""))):
            return ShmTransport(port, fault_plan)
    if mode in (TRANSPORT_UDS, "auto"):
        path = uds_path_for(port)
        if os.path.exists(path):
            return UdsTransport(path, fault_plan)
    return None
