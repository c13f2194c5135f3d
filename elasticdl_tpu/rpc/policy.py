"""Unified retry/backoff/deadline policy for the whole RPC plane.

Every RPC in the system — worker->master, worker->PS shard,
master->KV shard — used to handle failure its own way (mostly: not at
all; ps_client hand-rolled one 3-attempt loop). This module is the one
place failure handling lives:

- `RetryPolicy`: exponential backoff with DETERMINISTIC seeded jitter
  (a stable hash of (seed, method, attempt) — no shared RNG, no wall
  clock — so a fixed seed makes every retry schedule reproducible in
  tests), per-status-code retryability, and an overall deadline budget:
  the caller's `timeout` bounds the WHOLE call including retries and
  backoff sleeps, never timeout*attempts.
- Idempotency awareness: only calls that are safe to re-send are
  retried. Reads are naturally idempotent; PS/KV writes are idempotent
  because the shards dedup on `report_key` (ps_shard._is_duplicate) or
  have SETNX/overwrite semantics; master-plane gradient reports and
  GetTask are NOT (GetTask assigns — a retried GetTask whose first
  response was lost would orphan a task in the doing-map), so they fall
  through to the coarser recovery ladder: task requeue + pod relaunch
  (see docs/fault_model.md).
- `CircuitBreaker`: per-endpoint fail-fast after repeated consecutive
  errors, half-opens after a cool-down to probe with a single call.
  Keeps a worker from burning its whole deadline budget re-dialing a
  dead shard on every operation.

Errors raised here subclass grpc.RpcError and expose `.code()`, so
every existing `getattr(e, "code", lambda: None)()` site keeps working.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, FrozenSet, Optional

import grpc

from elasticdl_tpu.common.constants import (
    ENV_RPC_BACKOFF,
    ENV_RPC_RETRIES,
    ENV_RPC_SEED,
)

#: Status codes worth re-sending an idempotent call for. INTERNAL is
#: deliberately absent: a handler exception is deterministic — retrying
#: re-raises it N times and hides the real error. RESOURCE_EXHAUSTED is
#: the loop dispatcher's admission-queue backpressure (rpc/dispatch.py):
#: the server sheds load it will accept again once the queue drains, so
#: backing off and re-sending is exactly right.
RETRYABLE_CODES: FrozenSet[grpc.StatusCode] = frozenset(
    {
        grpc.StatusCode.UNAVAILABLE,
        grpc.StatusCode.DEADLINE_EXCEEDED,
        grpc.StatusCode.RESOURCE_EXHAUSTED,
    }
)

#: Method-level idempotency classification (the request shapes make
#: these safe to re-send; see module docstring + docs/fault_model.md).
#: Everything NOT listed gets zero retries — same behavior as before
#: this module existed.
IDEMPOTENT_METHODS: FrozenSet[str] = frozenset(
    {
        # master plane: pure reads + the dedup-guarded task report
        # (TaskDispatcher.report drops duplicate/stale reports)
        "GetModel",
        "GetAux",
        "GetPSConfig",
        "GetSampleBatch",
        "ReportTaskResult",
        "EmbeddingLookup",
        # single-PS window sync: report_key-deduped on the servicer
        # (MasterServicer.report_local_update absorbs resends with the
        # current version + model piggyback, mirroring PSPushDelta)
        "ReportLocalUpdate",
        # policy plane: phase telemetry is a cumulative last-write-wins
        # snapshot per worker; sched stats is a pure read
        "ReportPhaseStats",
        "GetSchedStats",
        # obs plane: both are reads of process-local recorders
        "GetTrace",
        "GetMetrics",
        # migration plane (master/migration.py): GetJobManifest is a
        # pure read of the published manifest; BeginHandoff is a latch
        # (a resend finds the dispatcher already paused); the refence
        # RPCs are idempotent by target generation — a resend of the
        # same bump no-ops (== current), and a stale one is rejected
        # FAILED_PRECONDITION, which is non-retryable anyway
        "GetJobManifest",
        "BeginHandoff",
        "PSRefence",
        "KVRefence",
        # PS shard plane: reads, SETNX init, report_key-deduped pushes,
        # overwrite-semantics opt restore
        "PSInit",
        "PSPull",
        "PSPushGrad",
        "PSPushDelta",
        # bucketed streaming push (EDL_SYNC_BUCKET_BYTES): parked
        # buckets overwrite idempotently by (report_key, bucket_index)
        # and an applied set dedups per bucket on report_key — a resend
        # of any bucket, before or after the atomic apply, is exact
        "PSPushDeltaBucket",
        "PSOptState",
        "PSOptRestore",
        # aggregation tree (agg/): AggPushDelta is the worker-facing
        # push surface — the PS-side per-member report_key dedup makes
        # a resend exact even if the first attempt was absorbed into a
        # cohort that already forwarded. AggStats is a read;
        # AggUpdateUpstream overwrites one endpoint list (LWW).
        # PSPushDeltaCombined is deliberately NOT here: a combined
        # forward carries k member keys, and a blind resend could
        # interleave with members replaying direct — the aggregator
        # handles forward failure by erroring its members instead, who
        # each retry under their own key.
        "AggPushDelta",
        "AggStats",
        "AggUpdateUpstream",
        # recovery plane (master RPC): the master keeps at most one
        # restore candidate per (worker, shard) — a resend overwrites
        # it with the identical payload (master/recovery.py)
        "PSRestoreFromWorker",
        # KV shard plane: lookup/len/snapshot are reads; update/restore
        # are last-write-wins row overwrites (or SETNX) — a resend
        # rewrites the same rows with the same values
        "KVLookup",
        "KVUpdate",
        "KVSnapshot",
        "KVRestore",
        "KVLen",
        # replica mirroring: KVMirror is the same LWW row overwrite as
        # KVUpdate (per source shard); KVMirrorSnapshot is a read;
        # KVSetMirror overwrites one endpoint string
        "KVMirror",
        "KVMirrorSnapshot",
        "KVSetMirror",
    }
)

#: Idempotent MUTATIONS that are only safe to re-send because the
#: receiving shard dedups on a per-report `report_key`
#: (ps_shard._is_duplicate). Every call site of these methods MUST put
#: a `report_key` in the request dict — the rpc-conformance lint
#: (analysis/rpc_conformance.py) fails CI on one that doesn't, because
#: a keyless push whose first attempt WAS applied would double-apply on
#: retry.
DEDUP_KEYED_METHODS: FrozenSet[str] = frozenset(
    {
        "PSPushGrad",
        "PSPushDelta",
        "PSPushDeltaBucket",
        "ReportLocalUpdate",
        "AggPushDelta",
    }
)


class WireStats:
    """Per-endpoint wire-byte accounting: bytes_sent / bytes_received /
    calls, broken down by method. One instance is shared by every
    `RpcClient` dialing the same endpoint (see `wire_stats_for`) and
    one per `RpcServer`, so "how many bytes does a sync cost" is
    answerable from either side of the link without packet captures —
    the policy layer is the one place every RPC already flows through,
    so the counters live next to the retry/breaker state.

    Counters are payload bytes as handed to / received from the
    transport (post-codec, pre-framing): exactly the bytes the codec
    controls, which is what the bf16-vs-f32 and v1-vs-v2 comparisons
    need. Each record carries the transport TIER that moved the bytes
    ("grpc" / "uds" / "inproc"), tallied separately so bytes-per-sync
    honestly distinguishes a co-located fast path from the network: an
    in-process call reports zero wire bytes but still counts its call
    (callers pass `calls=1` explicitly there, since the default
    heuristic counts a call per non-empty send). Thread-safe;
    snapshot() returns plain dicts for stats()/bench JSON surfaces.

    Counters are STRIPED (lock per stripe, threads pinned round-robin
    to stripes): every RPC on every tier records here, so under the
    loop-dispatch fan-in hundreds of concurrent recorders would
    otherwise convoy on one accounting mutex. snapshot() merges the
    stripes — its output shape is unchanged."""

    _NUM_STRIPES = 8

    def __init__(self, endpoint: str = ""):
        self.endpoint = endpoint
        # stripe -> (lock, method -> [sent, recv, calls],
        #           transport tier -> [sent, recv, calls])
        self._stripes = [
            (threading.Lock(), {}, {})
            for _ in range(self._NUM_STRIPES)
        ]

    def record(
        self,
        method: str,
        sent: int = 0,
        received: int = 0,
        transport: str = "grpc",
        calls=None,
    ):
        n = (1 if sent else 0) if calls is None else int(calls)
        lock, methods, transports = self._stripes[_stripe_index()]
        with lock:
            row = methods.get(method)
            if row is None:
                row = methods[method] = [0, 0, 0]
            row[0] += int(sent)
            row[1] += int(received)
            row[2] += n
            trow = transports.get(transport)
            if trow is None:
                trow = transports[transport] = [0, 0, 0]
            trow[0] += int(sent)
            trow[1] += int(received)
            trow[2] += n

    def snapshot(self) -> dict:
        methods: dict = {}
        transports: dict = {}
        for lock, smethods, stransports in self._stripes:
            with lock:
                srows = [(m, list(r)) for m, r in smethods.items()]
                trows = [(t, list(r)) for t, r in stransports.items()]
            for m, r in srows:
                agg = methods.setdefault(
                    m, {"bytes_sent": 0, "bytes_received": 0, "calls": 0}
                )
                agg["bytes_sent"] += r[0]
                agg["bytes_received"] += r[1]
                agg["calls"] += r[2]
            for t, r in trows:
                agg = transports.setdefault(
                    t, {"bytes_sent": 0, "bytes_received": 0, "calls": 0}
                )
                agg["bytes_sent"] += r[0]
                agg["bytes_received"] += r[1]
                agg["calls"] += r[2]
        return {
            "endpoint": self.endpoint,
            "bytes_sent": sum(v["bytes_sent"] for v in methods.values()),
            "bytes_received": sum(
                v["bytes_received"] for v in methods.values()
            ),
            "calls": sum(v["calls"] for v in methods.values()),
            "methods": methods,
            "transports": transports,
        }

    def reset(self):
        for lock, methods, transports in self._stripes:
            with lock:
                methods.clear()
                transports.clear()


# Threads are pinned to stripes round-robin at first record: cheaper
# and better-spread than hashing thread ids (CPython idents are
# pointer-aligned, so their low bits collide).
_stripe_tl = threading.local()
_stripe_seq_lock = threading.Lock()
_stripe_seq = 0


def _stripe_index() -> int:
    idx = getattr(_stripe_tl, "idx", None)
    if idx is None:
        global _stripe_seq
        with _stripe_seq_lock:
            idx = _stripe_seq % WireStats._NUM_STRIPES
            _stripe_seq += 1
        _stripe_tl.idx = idx
    return idx


_wire_registry_lock = threading.Lock()
_wire_registry: dict = {}


def wire_stats_for(endpoint: str) -> WireStats:
    """The process-wide WireStats for `endpoint` (created on first
    use). Sharing per endpoint means a reconnect (new RpcClient, e.g.
    after a shard failover) keeps accumulating into the same row."""
    with _wire_registry_lock:
        ws = _wire_registry.get(endpoint)
        if ws is None:
            ws = _wire_registry[endpoint] = WireStats(endpoint)
        return ws


def all_wire_stats() -> dict:
    """{endpoint: snapshot} for every endpoint this process dialed."""
    with _wire_registry_lock:
        entries = list(_wire_registry.items())
    return {ep: ws.snapshot() for ep, ws in entries}


def aggregate_wire_snapshots(snapshots) -> dict:
    """Sum WireStats snapshots (e.g. a shard fan-out's N clients) into
    one {bytes_sent, bytes_received, methods} rollup: one logical push
    is num_shards slice sends, and "bytes per sync" means their SUM."""
    methods: dict = {}
    transports: dict = {}
    for snap in snapshots:
        for m, row in snap["methods"].items():
            agg = methods.setdefault(
                m, {"bytes_sent": 0, "bytes_received": 0, "calls": 0}
            )
            for k in agg:
                agg[k] += row[k]
        # tolerate pre-transport-dimension snapshots (no "transports")
        for t, row in snap.get("transports", {}).items():
            agg = transports.setdefault(
                t, {"bytes_sent": 0, "bytes_received": 0, "calls": 0}
            )
            for k in agg:
                agg[k] += row[k]
    return {
        "bytes_sent": sum(v["bytes_sent"] for v in methods.values()),
        "bytes_received": sum(v["bytes_received"] for v in methods.values()),
        "methods": methods,
        "transports": transports,
    }


def reset_wire_stats():
    with _wire_registry_lock:
        entries = list(_wire_registry.values())
    for ws in entries:
        ws.reset()


class PolicyRpcError(grpc.RpcError):
    """grpc.RpcError with an explicit status code, raisable client-side."""

    def __init__(self, code: grpc.StatusCode, details: str):
        self._code = code
        self._details = details
        super().__init__(f"{code.name}: {details}")

    def code(self) -> grpc.StatusCode:
        return self._code

    def details(self) -> str:
        return self._details


class DeadlineExhausted(PolicyRpcError):
    """The per-call deadline budget ran out across attempts."""


class CircuitOpenError(PolicyRpcError):
    """Fail-fast: the endpoint's breaker is open (recent repeated errors)."""

    def __init__(self, endpoint: str):
        super().__init__(
            grpc.StatusCode.UNAVAILABLE, f"circuit open for {endpoint}"
        )


def _code_of(e: Exception) -> Optional[grpc.StatusCode]:
    return getattr(e, "code", lambda: None)()


@dataclass(frozen=True)
class RetryPolicy:
    """Retry schedule shared by every RpcClient.

    `max_attempts` counts total tries (1 = the old no-retry behavior).
    Backoff before attempt k (k>=1 retries) is
    ``min(initial_backoff * multiplier**(k-1), max_backoff)`` shrunk by
    up to `jitter` fraction using a hash of (seed, method, k) — fully
    deterministic for a fixed seed, different across methods/attempts.
    """

    max_attempts: int = 4
    initial_backoff: float = 0.05
    multiplier: float = 2.0
    max_backoff: float = 2.0
    jitter: float = 0.5
    seed: int = 0
    retryable_codes: FrozenSet[grpc.StatusCode] = RETRYABLE_CODES
    # injectable for tests: virtual clocks make schedules wall-clock-free
    sleep_fn: Callable[[float], None] = field(default=time.sleep, repr=False)
    clock: Callable[[], float] = field(default=time.monotonic, repr=False)

    @classmethod
    def from_env(cls, env=None) -> "RetryPolicy":
        env = os.environ if env is None else env
        kw = {}
        if env.get(ENV_RPC_RETRIES):
            kw["max_attempts"] = max(1, int(env[ENV_RPC_RETRIES]))
        if env.get(ENV_RPC_BACKOFF):
            kw["initial_backoff"] = float(env[ENV_RPC_BACKOFF])
        if env.get(ENV_RPC_SEED):
            kw["seed"] = int(env[ENV_RPC_SEED])
        return cls(**kw)

    def backoff_for(self, method: str, attempt: int) -> float:
        """Backoff before retry number `attempt` (1-based). Deterministic."""
        base = min(
            self.initial_backoff * self.multiplier ** (attempt - 1),
            self.max_backoff,
        )
        h = hashlib.sha256(
            f"{self.seed}:{method}:{attempt}".encode()
        ).digest()
        frac = int.from_bytes(h[:8], "big") / 2**64  # [0, 1)
        return base * (1.0 - self.jitter * frac)

    def call(
        self,
        fn: Callable[[float], object],
        method: str,
        timeout: float,
        idempotent: bool,
        breaker: Optional["CircuitBreaker"] = None,
    ):
        """Run fn(per_attempt_timeout) under the policy.

        `timeout` is the TOTAL budget: each attempt gets the remaining
        slice, and a retry is only scheduled when its backoff still
        fits inside the budget — retries can never exceed the caller's
        deadline."""
        deadline = self.clock() + timeout
        attempt = 0
        while True:
            remaining = deadline - self.clock()
            if remaining <= 0:
                raise DeadlineExhausted(
                    grpc.StatusCode.DEADLINE_EXCEEDED,
                    f"{method}: deadline budget spent after {attempt} attempts",
                )
            if breaker is not None:
                breaker.before_call()
            try:
                result = fn(remaining)
            except grpc.RpcError as e:
                if breaker is not None:
                    breaker.record_failure()
                attempt += 1
                code = _code_of(e)
                if (
                    not idempotent
                    or code not in self.retryable_codes
                    or attempt >= self.max_attempts
                ):
                    raise
                pause = self.backoff_for(method, attempt)
                if self.clock() + pause >= deadline:
                    # no room for the backoff + another try: surface the
                    # real failure instead of sleeping into the deadline
                    raise
                self.sleep_fn(pause)
                continue
            if breaker is not None:
                breaker.record_success()
            return result


class CircuitBreaker:
    """Per-endpoint breaker: after `failure_threshold` CONSECUTIVE
    failures the circuit opens and calls fail fast with
    `CircuitOpenError` (code UNAVAILABLE). After `reset_interval`
    seconds it half-opens: exactly one probe call is let through;
    success closes the circuit, failure re-opens it (and re-arms the
    timer). The clock is injectable so tests never sleep."""

    def __init__(
        self,
        endpoint: str = "",
        failure_threshold: int = 5,
        reset_interval: float = 5.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.endpoint = endpoint
        self._threshold = max(1, failure_threshold)
        self._reset_interval = reset_interval
        self._clock = clock
        self._lock = threading.Lock()
        self._failures = 0
        self._open = False
        self._opened_at = 0.0
        self._probing = False

    @property
    def is_open(self) -> bool:
        with self._lock:
            return self._open

    def before_call(self):
        with self._lock:
            if not self._open:
                return
            now = self._clock()
            if (
                now - self._opened_at >= self._reset_interval
                and not self._probing
            ):
                self._probing = True  # half-open: this call is the probe
                return
            raise CircuitOpenError(self.endpoint)

    def record_success(self):
        with self._lock:
            self._failures = 0
            self._open = False
            self._probing = False

    def record_failure(self):
        with self._lock:
            self._failures += 1
            self._probing = False
            if self._failures >= self._threshold:
                if not self._open:
                    # log-free state flip; the caller sees CircuitOpenError
                    # with the endpoint name on the next call
                    self._open = True
                self._opened_at = self._clock()
