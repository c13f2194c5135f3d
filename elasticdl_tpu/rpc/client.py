"""Generic gRPC client: method-name-addressed unary calls with the
pytree codec (see rpc/server.py). Replaces the generated MasterStub
(reference: elasticdl/python/worker/main.py:88-97).

Failure handling is centralized here: every call runs under the shared
`RetryPolicy` (idempotent methods retry UNAVAILABLE/DEADLINE_EXCEEDED
with deterministic backoff inside the caller's deadline budget) behind
a per-endpoint `CircuitBreaker`, and the channel is wrapped with the
process's chaos interceptors when `EDL_CHAOS_SPEC` is set — so fault
injection exercises exactly the production path (see rpc/chaos.py,
docs/fault_model.md).

A local peer gets a local carrier: when the endpoint resolves to this
host and its listener is there (with `EDL_TRANSPORT` unset: the
server's Unix-socket file; the variable overrides, rpc/transport.py),
the attempt routes the packed request (`messages.PackedParts`: the
socket takes its parts as they lie; a carrier that needs one buffer
joins them, once) over that tier INSIDE the
same policy/breaker envelope, with the same FaultPlan applied by the
transport — tier selection changes how bytes move, never the failure
semantics. A remote endpoint, or `EDL_TRANSPORT=grpc`, gets gRPC. The
choice is logged once per (re)selection (`link <addr>: <tier>`); every
`rpc.client.<Method>` span and WireStats row carries the tier that
served, so bytes-per-sync distinguishes wire bytes from co-located ones
(inproc counts calls but zero bytes). A carrier that cannot connect
(`transport.CarrierDown`: a dead server's socket file) hands that call
to the gRPC channel this client holds anyway.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Optional

import grpc

from elasticdl_tpu.common import messages
from elasticdl_tpu.common.constants import GRPC_OPTIONS, SERVICE_NAME
from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.obs import trace as obs_trace
from elasticdl_tpu.rpc import chaos
from elasticdl_tpu.rpc import transport as transport_mod
from elasticdl_tpu.rpc.policy import (
    IDEMPOTENT_METHODS,
    CircuitBreaker,
    RetryPolicy,
    wire_stats_for,
)

logger = get_logger(__name__)


class RpcClient:
    def __init__(
        self,
        addr: str,
        service_name: str = SERVICE_NAME,
        policy: Optional[RetryPolicy] = None,
        breaker: Optional[CircuitBreaker] = None,
        fault_plan: Optional[chaos.FaultPlan] = None,
        transport: Optional[str] = None,
        timeline=(),
    ):
        # the methods whose encode, round trip and decode this client's
        # owner wants on its phase timeline (per update RPCs; the
        # worker's master client names MASTER_UPDATE_METHODS)
        self._timeline = frozenset(timeline)
        channel = grpc.insecure_channel(addr, options=GRPC_OPTIONS)
        plan = fault_plan if fault_plan is not None else chaos.FaultPlan.from_env()
        if plan is not None:
            interceptors = plan.client_interceptors()
            if interceptors:
                channel = grpc.intercept_channel(channel, *interceptors)
        self._channel = channel
        self._service = service_name
        # kept for reconnect(): a re-pointed client must rebuild its
        # channel/transport with the SAME chaos plan and tier pin
        self._fault_plan = plan
        self._tier = transport
        # fast-path tier for co-located endpoints (None = plain gRPC).
        # The transport shares `plan` with the interceptors above, so
        # chaos counters advance identically whichever tier serves.
        # `transport` pins this client's tier regardless of the ambient
        # EDL_TRANSPORT mode (per-link selection: the aggregation tree
        # pins uds/grpc for aggregator->PS); None = the env mode.
        self._transport = self._select(addr)
        self._policy = policy if policy is not None else RetryPolicy.from_env()
        self._breaker = breaker if breaker is not None else CircuitBreaker(addr)
        self._calls: dict[str, Any] = {}
        # worker threads race on the first call of each method; the
        # memoization dict insert must be atomic
        self._calls_lock = threading.Lock()
        # per-endpoint wire-byte accounting, shared across reconnects
        # (rpc/policy.wire_stats_for); counted around the policy call
        # so retries of one logical call still tally each resend
        self.wire = wire_stats_for(addr)

    def _select(self, addr: str):
        transport = transport_mod.select_transport(
            addr, fault_plan=self._fault_plan, tier=self._tier
        )
        logger.info(
            "link %s: %s", addr, transport.name if transport else "grpc"
        )
        # the first fallback of a selection is logged, the rest are not
        self._fell_back = False
        return transport

    def wait_ready(self, timeout: float = 30.0):
        grpc.channel_ready_future(self._channel).result(timeout=timeout)

    def reconnect(self, addr: str):
        """Re-point this client at a different endpoint IN PLACE — the
        worker's master-failover path (worker/worker.py): every layer
        holding this client object (task loop, PS client fan-out,
        phase-stats reporter) keeps its reference while the channel,
        transport tier, memoized stubs, circuit breaker and wire-stats
        row are swapped for the new address. In-flight calls race the
        swap harmlessly: they finish (or fail) against the old channel,
        and a retry memoizes a fresh stub on the new one. The chaos
        plan and tier pin from construction are reapplied, so fault
        injection and bytes accounting survive the move."""
        channel = grpc.insecure_channel(addr, options=GRPC_OPTIONS)
        plan = self._fault_plan
        if plan is not None:
            interceptors = plan.client_interceptors()
            if interceptors:
                channel = grpc.intercept_channel(channel, *interceptors)
        transport = self._select(addr)
        # the swap is deliberately lock-free: each attribute move is a
        # single reference assignment, and a call racing the swap
        # harmlessly finishes (or fails and retries) on whichever
        # object it already read — self._calls_lock guards ONLY the
        # stub memoization dict. Swap first, clear last: a stale stub
        # memoized mid-swap is dropped by the clear, and everything
        # memoized after it binds the new channel.
        old_channel = self._channel
        old_transport = self._transport
        self._channel = channel
        self._transport = transport
        self._breaker = CircuitBreaker(addr)
        self.wire = wire_stats_for(addr)
        with self._calls_lock:
            self._calls = {}
        try:
            old_channel.close()
        except Exception:
            pass
        if old_transport is not None and hasattr(old_transport, "close"):
            try:
                old_transport.close()
            except Exception:
                pass

    def call(
        self,
        method: str,
        request: Any = None,
        timeout: float = 300.0,
        idempotent: Optional[bool] = None,
    ) -> Any:
        with self._calls_lock:
            stub = self._calls.get(method)
            if stub is None:
                stub = self._channel.unary_unary(
                    f"/{self._service}/{method}",
                    request_serializer=None,
                    response_deserializer=None,
                )
                self._calls[method] = stub
        if idempotent is None:
            idempotent = method in IDEMPOTENT_METHODS
        # trace envelope: the span must exist BEFORE the request is
        # packed (the envelope rides inside the frame). A call with no
        # surrounding context starts a new sampled trace — every RPC is
        # a root candidate. The span covers the whole policy call, so
        # retries/backoff show inside it.
        tspan = None
        if request is None or isinstance(request, dict):
            tspan = obs_trace.start_span(
                f"rpc.client.{method}", cat="rpc", root=True
            )
            if tspan is not None:
                request = dict(request or {})
                request[obs_trace.ENVELOPE_KEY] = tspan.envelope()
        # a method the owner put on its phase timeline is there always:
        # encode, the round trip as this side sees it, decode. Each
        # interval is ONE span; in a sampled trace the three carry its
        # ids (the round trip is the trace's client span, the other two
        # its children), so the trace still accounts for the pack
        timeline = method in self._timeline
        tctx = tspan.ctx if tspan is not None else None
        t_pack = time.time() if timeline else 0.0
        # packed, not joined: the socket carrier sends the parts as
        # they lie; a carrier that needs one buffer joins them, once
        payload = messages.pack_parts(request if request is not None else {})
        t_sent = time.time() if timeline else 0.0
        if timeline:
            obs_trace.record_phase(
                "rpc.client.encode", t_pack, t_sent - t_pack,
                {
                    "method": method,
                    "bytes": len(payload),
                    "parts": len(payload.parts),
                },
                ctx=obs_trace.child_context(tctx),
            )

        transport = self._transport
        tier = transport.name if transport else "grpc"
        # what the socket carrier says of the call that served, for the
        # round trip's span: whether the response lies in memory an
        # earlier one of its connection lay in (never, on the one-buffer
        # carriers), and the socket buffers as the kernel granted them
        link = {"recv_reused": False}

        def sent_how():
            # read when the call has settled: whether the frame left as
            # its pieces landed, and how long it stood waiting for one
            return {
                "joined": payload.joined,
                "streamed": payload.streamed,
                "waited_ms": round(payload.waited * 1e3, 3),
            }

        def over_grpc(remaining):
            self.wire.record(method, sent=len(payload))
            resp_bytes = stub(payload.contiguous(remaining), timeout=remaining)
            self.wire.record(method, received=len(resp_bytes))
            return resp_bytes

        def attempt(remaining):
            nonlocal tier
            if transport is None:
                return over_grpc(remaining)
            inproc = transport.name == "inproc"
            sent = 0 if inproc else len(payload)
            calls = 1 if inproc else None
            try:
                resp_bytes = transport.call(method, payload, remaining)
            except transport_mod.CarrierDown as e:
                # nothing left this process: the channel serves the
                # call (and draws its chaos fault)
                tier = "grpc"
                if not self._fell_back:
                    self._fell_back = True
                    logger.warning(
                        "link %s: %s carrier is down (%s); serving over "
                        "grpc until it is back",
                        self.wire.endpoint, transport.name, e.details(),
                    )
                return over_grpc(remaining)
            except BaseException:
                # the frame left, or may have: a retry's resend tallies
                self.wire.record(
                    method, sent=sent, transport=transport.name, calls=calls
                )
                raise
            tier = transport.name
            if not inproc:
                link.update(transport.last_call())
            self.wire.record(
                method,
                sent=sent,
                received=0 if inproc else len(resp_bytes),
                transport=transport.name,
                calls=calls,
            )
            return resp_bytes

        settled = False
        try:
            resp = self._policy.call(
                attempt,
                method=method,
                timeout=timeout,
                idempotent=idempotent,
                breaker=self._breaker,
            )
            settled = True
        finally:
            if not timeline:
                if tspan is not None:
                    tspan.end(transport=tier, **sent_how(), **link)
            elif not settled:  # the call raised: no response to date it by
                obs_trace.record_phase(
                    f"rpc.client.{method}", t_sent, time.time() - t_sent,
                    {
                        "bytes": len(payload),
                        "transport": tier,
                        **sent_how(),
                        "failed": True,
                        **link,
                    },
                    ctx=tctx,
                )
        if not timeline:
            return messages.unpack(resp)
        t_recv = time.time()
        out = messages.unpack(resp)
        t_done = time.time()
        # the round trip as this side sees it, under the version the
        # response names: what joins it to the master's spans of the
        # same update
        obs_trace.record_phase(
            f"rpc.client.{method}", t_sent, t_recv - t_sent,
            {
                "bytes": len(payload),
                "transport": tier,
                **sent_how(),
                "version": out.get("version") if isinstance(out, dict) else None,
                **link,
            },
            ctx=tctx,
        )
        obs_trace.record_phase(
            "rpc.client.decode", t_recv, t_done - t_recv,
            {"method": method, "bytes": len(resp)},
            ctx=obs_trace.child_context(tctx),
        )
        return out

    def close(self):
        self._channel.close()
        # the uds transport holds pooled connections; other tiers
        # have no client-side resources
        if self._transport is not None and hasattr(self._transport, "close"):
            self._transport.close()
