"""Aggregator node: the host-local rung of the aggregation tree.

PR 7's flat CombineBuffer presums cohorts AT the PS shard, but every
worker still holds a socket to the master host, so fan-in degree — and
wire bytes into the master's link — scale with fleet size. The
aggregator moves that same combine stage onto the worker's host (the
BytePS-style hierarchical-PS shape; Horovod's hierarchical allreduce
is the collective-side analog): workers push per-shard window deltas
to their host aggregator over the local carrier, the aggregator
presums each rendezvoused cohort with the IDENTICAL `fanin.presum_f32` math (dense cache-blocked adds, int8
dequant, top-k scatter-add — bitwise-identical to the serial
interleaving for exactly-representable values), and forwards ONE
combined delta per cohort upstream over uds/grpc carrying the member
`report_key` list. The PS shard applies the combined delta once and
registers every member key (`ps_shard.push_delta_combined`), so dedup,
replay, and exact-resume semantics are unchanged — a member replaying
DIRECT after an aggregator crash still dedups against its own key.

The aggregator holds NO model state: it is a stateless combine/forward
stage, which is why the recovery plane relaunches a dead aggregator
without any restore step (master/recovery.py) and why workers can fall
back to direct PS pushes the moment their aggregator is absent or
fenced (rpc/ps_client.ShardedPS) — versions stay exact either way.

Protocol invariants (the chaos e2e is the referee):

- **fencing** — `epoch` on AggPushDelta fences the AGGREGATOR's own
  generation (bumped per relaunch, so a cohort from before a crash can
  never land on the replacement); the PS shard's fencing epoch rides
  separately as `shard_epoch` and is forwarded upstream verbatim.
- **dedup** — the aggregator never dedups; the PS shard checks every
  member key under its lock. A combined forward the shard cannot take
  whole (accepted=False: replayed member, staleness window) is
  decomposed into serial per-member PSPushDelta forwards, each deduped
  individually — no replay interleaving can double-apply.
- **fallback** — any upstream failure errors the parked members; the
  worker's client classifies it as an aggregator outage and replays
  the SAME report_key direct to the PS shard.

Spans: `agg.park` (member wait, via the shared CombineBuffer),
`agg.presum` (cohort sum), `agg.forward` (upstream call) — all chained
into the worker->transport->admission->apply trace tree.
"""

from __future__ import annotations

import os
import threading
import weakref
from typing import Any, Dict, List, Optional

from elasticdl_tpu.common import codec
from elasticdl_tpu.common.constants import (
    ENV_AGG_BATCH,
    ENV_AGG_UPSTREAM_TIER,
    ENV_AGG_WAIT_MS,
)
from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.master import fanin
from elasticdl_tpu.obs import trace as obs_trace

logger = get_logger(__name__)

#: Upstream forward budget: one combined apply on a contended shard
#: can wait behind pulls, but minutes means the link is gone and the
#: members should fall back direct instead of hanging.
_FORWARD_TIMEOUT_S = 120.0


def agg_batch(env=None) -> int:
    env = os.environ if env is None else env
    raw = env.get(ENV_AGG_BATCH, "")
    try:
        n = int(raw) if raw else 32
    except ValueError:
        logger.warning("bad %s=%r; using 32", ENV_AGG_BATCH, raw)
        n = 32
    return max(1, n)


def agg_wait_s(env=None) -> float:
    env = os.environ if env is None else env
    raw = env.get(ENV_AGG_WAIT_MS, "")
    try:
        ms = float(raw) if raw else 0.0
    except ValueError:
        logger.warning("bad %s=%r; using 0", ENV_AGG_WAIT_MS, raw)
        ms = 0.0
    return max(0.0, ms) / 1000.0


def upstream_tier(env=None) -> str:
    """Transport tier for the aggregator->PS leg (default uds: Unix
    socket when the shard resolves local, else the selector's grpc
    fallback)."""
    env = os.environ if env is None else env
    return (env.get(ENV_AGG_UPSTREAM_TIER, "") or "uds").strip().lower()


class AggregatorServicer:
    """One aggregator node: worker-facing AggPushDelta surface plus the
    upstream forward clients, one per PS shard. Served behind the same
    RpcServer/ServerDispatcher stack as a PS shard (local carrier,
    loop core, admission queues, chaos hooks all reused)."""

    #: obs reads answer for the PROCESS (postmortems want them from a
    #: fenced node); AggStats is the bench/test counters surface and
    #: must stay readable after a fence for exactness accounting.
    UNFENCED_HANDLERS = frozenset({"GetTrace", "GetMetrics", "AggStats"})

    def __init__(
        self,
        agg_id: int,
        ps_endpoints: List[str],
        generation: int = 0,
        max_batch: Optional[int] = None,
        max_wait_s: Optional[float] = None,
        tier: Optional[str] = None,
    ):
        self.agg_id = int(agg_id)
        # fencing epoch: bumped by the group on every relaunch of this
        # slot; immutable for the servicer's lifetime (a relaunch
        # constructs a NEW servicer), like a PS shard's.
        self.generation = int(generation)
        self._max_batch = agg_batch() if max_batch is None else max_batch
        self._max_wait = agg_wait_s() if max_wait_s is None else max_wait_s
        self._tier = upstream_tier() if tier is None else tier
        self._lock = threading.Lock()
        self._ps_endpoints = list(ps_endpoints)
        self._upstream: Dict[int, Any] = {}  # shard -> RpcClient
        # one combine buffer PER SHARD: each gets its own combiner
        # thread, so cohorts bound for different shards forward in
        # parallel instead of serializing on one thread
        self._buffers: Dict[int, fanin.CombineBuffer] = {}
        self._closed = False
        # accounting (exactness + degree evidence for bench/chaos):
        # members_in counts accepted AggPushDelta requests;
        # cohorts_forwarded counts combined upstream calls;
        # singles_forwarded counts k=1 passthrough forwards;
        # decompositions counts accepted=False unwinds;
        # upstream_errors counts forwards that errored their members
        self._members_in = 0
        self._cohorts_forwarded = 0
        self._singles_forwarded = 0
        self._decompositions = 0
        self._upstream_errors = 0
        self._wire = None
        self._admission_fn = None

    # -- handler table -------------------------------------------------------

    def handlers(self) -> Dict[str, Any]:
        return {
            "AggPushDelta": self.push_delta,
            "AggStats": self.agg_stats,
            "AggUpdateUpstream": self.update_upstream,
            "GetTrace": self.get_trace,
            "GetMetrics": self.get_metrics,
        }

    def get_trace(self, req: dict) -> dict:
        """This process's SpanRecorder contents (obs/trace.py)."""
        return {
            "spans": obs_trace.RECORDER.snapshot(),
            "dropped": obs_trace.RECORDER.dropped,
        }

    def get_metrics(self, req: dict) -> dict:
        """This process's MetricsRegistry snapshot (obs/metrics.py)."""
        from elasticdl_tpu.obs import metrics as obs_metrics

        return {"metrics": obs_metrics.get_registry().snapshot()}

    def register_metrics(self, registry=None) -> None:
        """Feed this node's counters into the MetricsRegistry as a pull
        collector, weakly referenced like a PS shard's."""
        from elasticdl_tpu.obs import metrics as obs_metrics

        reg = registry if registry is not None else obs_metrics.get_registry()
        ref = weakref.ref(self)
        agg = str(self.agg_id)

        def collector(sink):
            s = ref()
            if s is None:
                return
            st = s.stats()
            sink.counter(
                "edl_agg_members_total", st["members_in"], agg=agg
            )
            sink.counter(
                "edl_agg_cohorts_total", st["cohorts_forwarded"], agg=agg
            )
            sink.counter(
                "edl_agg_singles_total", st["singles_forwarded"], agg=agg
            )
            sink.counter(
                "edl_agg_decompositions_total",
                st["decompositions"],
                agg=agg,
            )
            sink.counter(
                "edl_agg_upstream_errors_total",
                st["upstream_errors"],
                agg=agg,
            )
            sink.gauge("edl_agg_generation", st["generation"], agg=agg)

        reg.register_collector(collector)

    def _check_epoch(self, req: dict):
        from elasticdl_tpu.rpc.fencing import check_epoch

        check_epoch(req, self.generation, "agg", self.agg_id)

    # -- RPCs ----------------------------------------------------------------

    def push_delta(self, req: dict):
        """Worker push: park in the target shard's combine buffer and
        answer with the upstream result the cohort's forward earned.
        The wire delta enters the buffer in its decoded form — dense
        f32 view / bf16 widen / int8 dequant happen here, OUTSIDE any
        lock, and top-k stays sparse so the presum scatter-adds only
        the shipped entries per member (fanin.presum_f32)."""
        self._check_epoch(req)
        shard = int(req["shard"])
        with self._lock:
            if self._closed:
                raise RuntimeError("aggregator closed")
            self._members_in += 1
            buf = self._buffers.get(shard)
        if buf is None:
            # built OUTSIDE the lock: the combiner thread this spawns
            # re-enters self._lock via _forward_batch, so constructing
            # it under the lock would put the forward plane on the
            # handler's lock chain
            fresh = fanin.CombineBuffer(
                lambda members, s=shard: self._forward_batch(s, members),
                max_batch=self._max_batch,
                max_wait_s=self._max_wait,
                span_prefix="agg",
            )
            with self._lock:
                if not self._closed:
                    buf = self._buffers.setdefault(shard, fresh)
            if buf is not fresh:
                fresh.close()  # lost the race (or closed underneath)
            if buf is None:
                raise RuntimeError("aggregator closed")
        # cohort lineage: response dtype + the PS epoch the member
        # believes — mixed-epoch members must not share a forward (a
        # post-recovery member would smuggle a pre-recovery one past
        # the shard's fence)
        key = (req.get("model_dtype") or "", int(req["shard_epoch"]))
        wire = req["delta"]
        if isinstance(wire, codec.SparseDelta):
            return buf.submit(key, req, wire)
        return buf.submit(key, req, codec.delta_to_f32(wire))

    def agg_stats(self, req: dict) -> dict:
        return self.stats()

    def update_upstream(self, req: dict) -> dict:
        """Master re-point after a PS relaunch: adopt the new endpoint
        list (index = shard id) and drop the stale clients; in-flight
        forwards against a dead shard fail over member-by-member (the
        members replay direct)."""
        self._check_epoch(req)
        endpoints = [str(e) for e in (req.get("endpoints") or [])]
        with self._lock:
            self._ps_endpoints = endpoints
            stale, self._upstream = self._upstream, {}
        for c in stale.values():
            try:
                c.close()
            except Exception:  # edl-lint: disable=abort-discipline -- stale-client teardown is best-effort; the re-point itself already happened under the lock, so nothing downstream depends on the close
                pass
        return {"endpoints": len(endpoints)}

    # -- forward plane -------------------------------------------------------

    def _client_for(self, shard: int):
        with self._lock:
            c = self._upstream.get(shard)
            if c is None:
                if shard >= len(self._ps_endpoints):
                    raise ValueError(
                        f"no PS endpoint for shard {shard} "
                        f"({len(self._ps_endpoints)} known)"
                    )
                from elasticdl_tpu.rpc.client import RpcClient

                # per-link tier: uds/grpc upstream regardless of the
                # ambient EDL_TRANSPORT — rpc/client.py `transport=`
                c = RpcClient(
                    self._ps_endpoints[shard], transport=self._tier
                )
                self._upstream[shard] = c
        return c

    def _forward_batch(self, shard: int, members) -> None:
        """CombineBuffer callback: presum the cohort, forward ONE
        combined delta upstream, fan the shared response back. Runs on
        the shard's combiner thread."""
        cli = None
        try:
            cli = self._client_for(shard)
        except Exception as e:  # edl-lint: disable=abort-discipline -- not swallowed: the error lands on every parked member and CombineBuffer.submit re-raises it on each member's handler thread, where the server classifier sees it
            for m in members:
                m.error = e
            return
        if len(members) == 1:
            self._forward_single(cli, members[0])
            return
        lens = {codec.delta_length(m.delta) for m in members}
        if len(lens) != 1:
            # heterogeneous slice lengths cannot share a forward;
            # degrade to serial per-member passthrough
            for m in members:
                self._forward_single(cli, m)
            return
        with obs_trace.span(
            "agg.presum",
            cat="agg",
            args={"agg": self.agg_id, "shard": shard,
                  "members": len(members)},
        ):
            acc = fanin.presum_f32(
                [m.delta for m in members], n=next(iter(lens))
            )
        keys = [m.req.get("report_key") or "" for m in members]
        steps = sum(int(m.req["steps"]) for m in members)
        first = members[0].req
        try:
            with obs_trace.span(
                "agg.forward",
                cat="agg",
                args={"agg": self.agg_id, "shard": shard,
                      "members": len(members)},
            ):
                resp = cli.call(
                    "PSPushDeltaCombined",
                    {
                        "delta": acc,
                        "steps": steps,
                        "report_keys": keys,
                        "model_dtype": first.get("model_dtype"),
                        "epoch": int(first["shard_epoch"]),
                    },
                    timeout=_FORWARD_TIMEOUT_S,
                )
        except Exception:  # edl-lint: disable=abort-discipline -- not swallowed: the cohort decomposes to per-member forwards below, and each single's failure re-raises at its parked member
            # the combined call is NOT retried blind (it is not
            # idempotent — rpc/policy.py): decompose into per-member
            # forwards, each individually deduped and retryable
            with self._lock:
                self._upstream_errors += 1
            for m in members:
                self._forward_single(cli, m)
            return
        if not resp.get("accepted"):
            # the shard could not take the batch whole (replayed
            # member, staleness window): nothing was applied — unwind
            # to serial per-member semantics
            with self._lock:
                self._decompositions += 1
            for m in members:
                self._forward_single(cli, m)
            return
        with self._lock:
            self._cohorts_forwarded += 1
        # one serialization for the whole cohort: every member's base
        # fell behind the combined version, so every member gets the
        # merged slice — identical bytes, shared by reference (the
        # same prepacked fan-out the PS-side combine stage does)
        from elasticdl_tpu.common import messages

        shared = messages.Prepacked(
            messages.pack({"version": resp["version"], "vec": resp["vec"]})
        )
        for m in members:
            m.resp = shared

    def _forward_single(self, cli, m) -> None:
        """Passthrough forward of one member as a plain PSPushDelta —
        the k=1 cohort and the decompose path. The ORIGINAL wire delta
        is forwarded (not the decoded view), so compressed forms stay
        compressed upstream; the shard-side dedup makes this exact
        under any retry/replay interleaving."""
        try:
            with obs_trace.span(
                "agg.forward",
                cat="agg",
                args={"agg": self.agg_id,
                      "shard": int(m.req["shard"]), "members": 1},
            ):
                m.resp = cli.call(
                    "PSPushDelta",
                    {
                        "delta": m.req["delta"],
                        "steps": m.req["steps"],
                        "base_version": m.req["base_version"],
                        "want_model": m.req.get("want_model", False),
                        "report_key": m.req.get("report_key", ""),
                        "model_dtype": m.req.get("model_dtype"),
                        "epoch": int(m.req["shard_epoch"]),
                    },
                    timeout=_FORWARD_TIMEOUT_S,
                )
            with self._lock:
                self._singles_forwarded += 1
        except Exception as e:  # edl-lint: disable=abort-discipline -- not swallowed: m.error re-raises in CombineBuffer.submit on the member's handler thread, reaching the server classifier (fence aborts and chaos faults included)
            with self._lock:
                self._upstream_errors += 1
            m.error = e

    # -- wiring / accounting -------------------------------------------------

    def attach_wire_stats(self, wire):
        """Point stats() at the hosting RpcServer's WireStats (same
        contract as PSShardServicer.attach_wire_stats). Attachment
        happens while handler threads may already be serving (the
        server wires accounting after bind), so the reference swap
        rides the stats mutex."""
        with self._lock:
            self._wire = wire

    def attach_admission_stats(self, fn):
        with self._lock:
            self._admission_fn = fn

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = {
                "members_in": self._members_in,
                "cohorts_forwarded": self._cohorts_forwarded,
                "singles_forwarded": self._singles_forwarded,
                "decompositions": self._decompositions,
                "upstream_errors": self._upstream_errors,
                "generation": self.generation,
                "num_upstream": len(self._ps_endpoints),
            }
            wire = self._wire
            admission_fn = self._admission_fn
        if wire is not None:
            snap = wire.snapshot()
            out["bytes_sent"] = snap["bytes_sent"]
            out["bytes_received"] = snap["bytes_received"]
            # per-tier rows so a remote caller (bench smoke, operator)
            # can see which carrier the worker-facing side rode
            out["transports"] = snap.get("transports", {})
        if admission_fn is not None:
            adm = admission_fn()
            if adm:
                out["admission"] = adm
        return out

    def close(self):
        with self._lock:
            self._closed = True
            buffers = list(self._buffers.values())
            clients = list(self._upstream.values())
            self._buffers = {}
            self._upstream = {}
        for b in buffers:
            b.close()
        for c in clients:
            try:
                c.close()
            except Exception:  # noqa: BLE001 - teardown is best-effort
                pass
