"""Generic gRPC server over raw-bytes methods.

The reference compiles a .proto into stubs (elasticdl/Makefile:3-4); we
instead register generic unary-unary handlers with identity serializers
and run our own codec on the payloads — no codegen step, and the wire
format supports bf16 and nested pytrees (see common/codec.py).

Every server also serves the transport fast paths (rpc/transport.py):
its handler table is registered in the in-process dispatch registry
keyed by the bound port, and a Unix-domain-socket listener
(`edl-uds-<port>.sock` in `EDL_UDS_DIR`) opens beside gRPC — the
carrier a client on this host gets — unless `EDL_TRANSPORT=grpc`. All
share the same `ServerDispatcher`, so chaos/fencing/abort
classification is identical on every tier.
"""

from __future__ import annotations

from concurrent import futures
from typing import Callable, Dict

import grpc

from elasticdl_tpu.common.constants import GRPC_OPTIONS, SERVICE_NAME
from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.rpc import transport as transport_mod
from elasticdl_tpu.rpc.policy import PolicyRpcError

logger = get_logger(__name__)


def _grpc_adapter(dispatcher, method: str) -> Callable:
    """Thin gRPC shim over the shared ServerDispatcher: the dispatcher
    raises PolicyRpcError with the status code the tier-independent
    classifier chose; here that becomes context.abort."""

    def handler(request_bytes: bytes, context) -> bytes:
        try:
            # gRPC sends one buffer
            return dispatcher.dispatch(
                method, request_bytes, transport_mod.TRANSPORT_GRPC
            ).contiguous()
        except PolicyRpcError as e:
            # abort() raises — nothing after it runs
            context.abort(e.code(), e.details())

    return handler


class RpcServer:
    """Threaded gRPC server exposing `handlers` {method_name: fn(dict)->dict}.

    Mirrors the reference master's 64-thread server
    (elasticdl/python/master/main.py:197-223).
    """

    def __init__(
        self,
        handlers: Dict[str, Callable],
        port: int = 0,
        service_name: str = SERVICE_NAME,
        max_workers: int = 64,
        fault_plan=None,
        timers=None,
        timed_methods=(),
    ):
        # server-side wire-byte accounting (payload bytes per method);
        # surfaced via `wire_stats()` and shard `stats()` RPCs
        from elasticdl_tpu.rpc.policy import WireStats

        self.wire = WireStats("server")
        # server-side chaos: active when EDL_CHAOS_SPEC is set (shard
        # subprocesses inherit it) or a plan is passed in explicitly.
        # The grpc tier injects via interceptors; the fast-path tiers
        # via the dispatcher itself (exactly one layer per tier).
        from elasticdl_tpu.rpc import chaos

        plan = fault_plan if fault_plan is not None else chaos.FaultPlan.from_env()
        self._dispatcher = transport_mod.ServerDispatcher(
            handlers, self.wire, fault_plan=plan, timers=timers,
            timed_methods=timed_methods,
        )
        method_handlers = {
            name: grpc.unary_unary_rpc_method_handler(
                _grpc_adapter(self._dispatcher, name),
                request_deserializer=None,
                response_serializer=None,
            )
            for name in handlers
        }
        generic = grpc.method_handlers_generic_handler(service_name, method_handlers)
        interceptors = tuple(plan.server_interceptors()) if plan else ()
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=max_workers),
            options=GRPC_OPTIONS,
            interceptors=interceptors,
        )
        self._server.add_generic_rpc_handlers((generic,))
        self.port = self._server.add_insecure_port(f"[::]:{port}")
        # co-located fast paths share the dispatcher (rpc/transport.py)
        transport_mod.register_inproc(self.port, self._dispatcher)
        self._uds = None
        if transport_mod.server_fast_paths_enabled():
            # loop dispatch serves UDS with non-blocking reads on the
            # process event loop; threads dispatch keeps the blocking
            # thread-per-connection listener (rpc/dispatch.py)
            uds_cls = (
                transport_mod.AsyncUdsServer
                if self._dispatcher.mode == "loop"
                else transport_mod.UdsServer
            )
            try:
                self._uds = uds_cls(self.port, self._dispatcher)
            except OSError as e:
                logger.warning(
                    "UDS fast path unavailable for port %s (%s); gRPC only",
                    self.port,
                    e,
                )

    def start(self):
        self._server.start()
        if self._uds is not None:
            self._uds.start()
        self._register_metrics()

    def _register_metrics(self):
        """Feed this server's wire/admission counters into the process
        MetricsRegistry (pull collectors — zero hot-path cost) and
        start the optional EDL_METRICS_PORT scrape listener."""
        from elasticdl_tpu.obs import metrics as obs_metrics

        port = self.port
        wire = self.wire
        dispatcher = self._dispatcher

        def collector(sink):
            snap = wire.snapshot()
            sink.counter(
                "edl_wire_bytes_sent_total",
                snap.get("bytes_sent", 0),
                side="server",
                port=port,
            )
            sink.counter(
                "edl_wire_bytes_received_total",
                snap.get("bytes_received", 0),
                side="server",
                port=port,
            )
            sink.counter(
                "edl_wire_calls_total",
                snap.get("calls", 0),
                side="server",
                port=port,
            )
            admission = dispatcher.admission_stats()
            if admission:
                for cls, row in admission.items():
                    sink.gauge(
                        "edl_admission_depth",
                        row["depth"],
                        cls=cls,
                        port=port,
                    )
                    sink.gauge(
                        "edl_admission_inflight",
                        row["inflight"],
                        cls=cls,
                        port=port,
                    )
                    sink.counter(
                        "edl_admission_rejected_total",
                        row["rejected"],
                        cls=cls,
                        port=port,
                    )

        obs_metrics.get_registry().register_collector(collector)
        obs_metrics.maybe_serve_from_env()

    def wire_stats(self) -> dict:
        """Per-method bytes_sent/bytes_received snapshot (see
        rpc/policy.WireStats)."""
        return self.wire.snapshot()

    def admission_stats(self):
        """Per-method-class admission queue depth/inflight/rejections
        from the loop dispatch core, or None under threads dispatch
        (rpc/transport.ServerDispatcher.admission_stats). Surfaced in
        shard `stats()` and the master's GetSchedStats so the
        autoscaler and operators can see queue pressure."""
        return self._dispatcher.admission_stats()

    def stop(self, grace: float = 0.5):
        transport_mod.unregister_inproc(self.port)
        if self._uds is not None:
            self._uds.close()
        self._server.stop(grace)
        self._dispatcher.close()

    def wait(self):
        self._server.wait_for_termination()
