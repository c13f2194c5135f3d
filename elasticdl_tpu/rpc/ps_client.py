"""Worker/master-side client for the sharded parameter server.

One logical PS spread over N endpoints (master/ps_shard.py): every
operation fans out to all shards on a thread pool — N concurrent RPCs
on N sockets, so wire time scales down with the shard count (the
whole point of sharding the PS; SURVEY §7.3 item 3). Slices follow
`slice_boundaries`, computed locally from (n_params, num_shards).
"""

from __future__ import annotations

import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import grpc
import numpy as np

from elasticdl_tpu.common import codec
from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.master.ps_shard import slice_boundaries
from elasticdl_tpu.rpc.client import RpcClient

logger = get_logger(__name__)


class ShardedPS:
    """Fan-out client over the PS shard endpoints."""

    def __init__(
        self,
        endpoints: List[str],
        n_params: int,
        generations: Optional[List[int]] = None,
    ):
        if not endpoints:
            raise ValueError("ShardedPS needs at least one endpoint")
        self.endpoints = list(endpoints)
        self.n_params = int(n_params)
        self.bounds = slice_boundaries(self.n_params, len(endpoints))
        # fencing epochs (one per shard, master/recovery.py): stamped on
        # every request so a zombie or relaunched shard whose generation
        # moved rejects us with FAILED_PRECONDITION instead of silently
        # applying. None = unfenced (pre-recovery jobs, direct tests).
        self.generations = list(generations) if generations else None
        self._clients = [RpcClient(ep) for ep in self.endpoints]
        self._pool = ThreadPoolExecutor(
            max_workers=len(endpoints), thread_name_prefix="ps-shard"
        )
        # pull_async runner — deliberately NOT self._pool: pull() itself
        # fans out into that pool, so running pull() ON it would
        # deadlock at num_shards in-flight pulls (classic nested-submit
        # starvation). Lazy: most callers never go async.
        self._async_pool = None
        # aggregation tree (agg/): when armed, window-delta pushes
        # route through the host aggregator (AggPushDelta) instead of
        # direct to the shards — one client per shard so the per-shard
        # fan-out keeps its connection parallelism.
        # Any agg-path failure drops the route and replays direct under
        # the SAME report_key (shard dedup keeps versions exact); the
        # worker re-arms from GetPSConfig once `agg_dropped` reports it.
        self._agg_lock = threading.Lock()
        self._agg_clients: Optional[List[RpcClient]] = None
        self._agg_endpoint: Optional[str] = None
        self._agg_generation = -1
        self._agg_graveyard: List[RpcClient] = []
        self.agg_dropped = False

    # -- aggregation tree ----------------------------------------------------

    def set_aggregator(self, endpoint: str, generation: int = -1):
        """Arm the aggregator route: pushes go worker->agg->PS. A
        re-arm at the same (endpoint, generation) is a no-op so callers
        can re-assert from every GetPSConfig poll."""
        with self._agg_lock:
            if (
                self._agg_clients is not None
                and self._agg_endpoint == endpoint
                and self._agg_generation == int(generation)
            ):
                return
            if self._agg_clients is not None:
                self._agg_graveyard.extend(self._agg_clients)
            self._agg_clients = [
                RpcClient(endpoint) for _ in self.endpoints
            ]
            self._agg_endpoint = endpoint
            self._agg_generation = int(generation)
            self.agg_dropped = False

    def clear_aggregator(self):
        """Disarm the aggregator route (pushes go direct). Clients are
        parked, not closed: sibling fan-out threads may still be
        mid-call on them — they drain at close()."""
        with self._agg_lock:
            if self._agg_clients is not None:
                self._agg_graveyard.extend(self._agg_clients)
            self._agg_clients = None
            self._agg_endpoint = None
            self._agg_generation = -1

    def _drop_aggregator(self, shard: int, exc: BaseException):
        with self._agg_lock:
            if self._agg_clients is None:
                return  # a sibling shard's failure already dropped it
            logger.warning(
                "aggregator %s failed on shard %d (%s); falling back "
                "to direct PS pushes",
                self._agg_endpoint, shard, exc,
            )
            self._agg_graveyard.extend(self._agg_clients)
            self._agg_clients = None
            self._agg_endpoint = None
            self._agg_generation = -1
            self.agg_dropped = True

    @property
    def num_shards(self) -> int:
        return len(self.endpoints)

    def _stamp_epoch(self, req: dict, i: int) -> dict:
        if self.generations is not None:
            req["epoch"] = self.generations[i]
        return req

    def update_endpoints(
        self, endpoints: List[str], generations: Optional[List[int]] = None
    ):
        """Re-resolution after a shard relaunch (master/recovery.py):
        swap in the new endpoint+generation set. The shard COUNT is
        fixed for the job (slices don't re-split), so bounds stand."""
        if len(endpoints) != len(self.endpoints):
            raise ValueError(
                f"re-resolution changed shard count "
                f"{len(self.endpoints)} -> {len(endpoints)}"
            )
        old = self._clients
        self._clients = [RpcClient(ep) for ep in endpoints]
        self.endpoints = list(endpoints)
        self.generations = list(generations) if generations else None
        for c in old:
            c.close()

    def wait_ready(self, timeout: float = 30.0):
        """Channel readiness under ONE shared deadline: the waits run
        concurrently and each is clipped to the remaining budget, so
        the worst case is `timeout` total — never N×timeout."""
        deadline = time.monotonic() + timeout

        def wait(c, i):
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise grpc.FutureTimeoutError()
            c.wait_ready(remaining)

        self._map(wait)

    def _map(self, fn):
        """fn(client, shard_index) on every shard concurrently; returns
        results in shard order, re-raising the first failure.

        Failure model — TORN REPORTS, bounded to hard shard death.
        Shards apply their slices independently; there is no
        cross-shard transaction, so when one shard's RPC fails for good
        after the others applied theirs, the report is torn: the caller
        (worker) resets local state and re-trains the covered tasks, so
        no *work* is lost, but the applied slices' version histories
        run ahead by one report — permanent exactness across slices
        would need 2PC, which this plane deliberately omits
        (ps_shard.py design note). TRANSIENT blips don't tear: retry
        now lives in RpcClient.call under the shared RetryPolicy
        (rpc/policy.py) — every PS method is classified idempotent
        there, because reads/init are naturally idempotent and pushes
        carry a per-report `report_key` the shard dedups on
        (ps_shard.py `_is_duplicate`), so a resend whose first attempt
        WAS applied (gRPC can surface UNAVAILABLE after the server
        processed the request) no-ops instead of double-applying.

        DEDUP RING BOUND. The retry-safety above only holds while the
        shard still REMEMBERS a report_key, so the ring's capacity must
        dominate the number of keys that can still be legally resent.
        A key is resendable only while its originating sync is in
        flight; each worker holds at most `EDL_SYNC_DEPTH` (default 2,
        worker.py) syncs in flight, one report_key each, and abandons
        the key when the sync resolves. Hence at most
        ``num_workers x max_inflight_syncs`` live keys exist
        system-wide, and the group sizes each shard's ring as that
        product with a safety factor (PSShardGroup.dedup_cap_for) —
        a fixed 512 ring silently broke the guarantee for large fleets
        (ADVICE r5: 64 workers x 8 deep ring around it in one window)."""
        # pool threads do not inherit the caller's trace context; carry
        # it across the submit so per-shard client RPC spans chain under
        # the caller's window/pull span (obs/trace.py)
        from elasticdl_tpu.obs import trace as obs_trace

        tctx = obs_trace.current()

        def run(c, i):
            if tctx is None:
                return fn(c, i)
            prev = obs_trace.bind(tctx)
            try:
                return fn(c, i)
            finally:
                obs_trace.bind(prev)

        futs = [
            self._pool.submit(run, c, i)
            for i, c in enumerate(self._clients)
        ]
        return [f.result() for f in futs]

    # -- operations ----------------------------------------------------------

    def init_model(self, vec: np.ndarray, version: int = 0) -> List[int]:
        """Push initial slices (SETNX per shard); returns shard versions."""
        vec = np.asarray(vec, dtype=np.float32)
        if vec.size != self.n_params:
            raise ValueError(f"init vec size {vec.size} != {self.n_params}")

        def do(c, i):
            s, e = self.bounds[i]
            req = self._stamp_epoch({"vec": vec[s:e], "version": version}, i)
            return c.call("PSInit", req)["version"]

        # SETNX semantics on the shard make a resend a no-op
        return self._map(do)

    def pull(
        self,
        versions: Optional[List[int]] = None,
        model_dtype: Optional[str] = None,
    ) -> Tuple[List[int], Optional[np.ndarray]]:
        """Assemble the full flat vector from all shards.

        With `versions` given, shards at or below their known version
        return no payload (only_if_newer) — if ANY shard advanced, the
        stale slices are re-pulled so the result is complete. Returns
        (shard_versions, vec|None): None when nothing advanced or the
        PS is uninitialized."""
        only_if_newer = versions is not None

        def do(c, i):
            req = {"only_if_newer": only_if_newer}
            if only_if_newer:
                req["version"] = versions[i]
            if model_dtype:
                req["model_dtype"] = model_dtype
            return c.call("PSPull", self._stamp_epoch(req, i))

        resps = self._map(do)  # read-only
        new_versions = [r["version"] for r in resps]
        if any(v < 0 for v in new_versions):
            return new_versions, None
        if only_if_newer and all(r.get("vec") is None for r in resps):
            return new_versions, None
        missing = [i for i, r in enumerate(resps) if r.get("vec") is None]
        if missing:

            def refill(c, i):
                req = {}
                if model_dtype:
                    req["model_dtype"] = model_dtype
                return c.call("PSPull", self._stamp_epoch(req, i))

            for i, r in zip(
                missing,
                [
                    self._pool.submit(refill, self._clients[i], i)
                    for i in missing
                ],
            ):
                resps[i] = r.result()
                new_versions[i] = resps[i]["version"]
        return new_versions, self._assemble([r["vec"] for r in resps])

    def pull_async(
        self,
        versions: Optional[List[int]] = None,
        model_dtype: Optional[str] = None,
    ):
        """Non-blocking `pull`: returns a Future resolving to the same
        (shard_versions, vec|None). The worker's overlap plane uses
        this to page a newer model in while the step loop computes —
        the transport stack is safe for it (RpcClient serializes per
        endpoint under `_calls_lock`; the uds tier checks out pooled
        connections per call), so an async pull may overlap concurrent
        push_delta fan-outs on the same client."""
        if self._async_pool is None:
            self._async_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ps-pull-async"
            )
        return self._async_pool.submit(
            self.pull, versions=versions, model_dtype=model_dtype
        )

    def push_delta(
        self,
        delta: np.ndarray,
        steps: int,
        base_versions: List[int],
        model_dtype: Optional[str] = None,
        want_model: bool = False,
        report_key: Optional[str] = None,
    ) -> Tuple[List[int], Dict[int, np.ndarray]]:
        """Window-delta fan-out. Returns (shard_versions,
        {shard_index: merged_slice}) — merged slices only for shards
        whose version ran ahead of base+steps (or on want_model).

        `report_key` pins the dedup key across CALLERS, not just
        retries: a speculated task's primary and backup derive the
        same deterministic key for the same window
        (worker "{spec_key}.w{idx}"), so whichever copy lands second
        is absorbed by the shard dedup ring instead of double-applied.
        Default (None) keeps the per-call uuid — retry-safe only.

        `delta` may be a dense array or a compressed wire form
        (codec.QuantizedDelta / codec.SparseDelta): `slice_delta`
        splits either per shard without decompressing, so the wire
        savings survive the fan-out and each shard decodes only its
        slice (ps_shard applies via codec.delta_to_f32)."""
        if not isinstance(delta, (codec.QuantizedDelta, codec.SparseDelta)):
            delta = np.asarray(delta)
        size = codec.delta_length(delta)
        if size != self.n_params:
            raise ValueError(f"delta size {size} != {self.n_params}")

        # shard-side dedup: retry-safe (speculation-safe when pinned)
        report_key = report_key or uuid.uuid4().hex
        # snapshot the agg route ONCE per fan-out so every shard of one
        # logical push takes the same path decision
        with self._agg_lock:
            agg_clients = self._agg_clients
            agg_generation = self._agg_generation

        def do(c, i):
            s, e = self.bounds[i]
            req = {
                "delta": codec.slice_delta(delta, s, e),
                "steps": steps,
                "base_version": base_versions[i],
                "want_model": want_model,
                "report_key": report_key,
            }
            if model_dtype:
                req["model_dtype"] = model_dtype
            if agg_clients is not None:
                # tree route: same slice, same report_key, plus the
                # target shard + the shard's fencing epoch for the
                # upstream forward; `epoch` fences the AGGREGATOR's
                # generation (agg/aggregator.py)
                try:
                    return agg_clients[i].call(
                        "AggPushDelta",
                        {
                            "delta": req["delta"],
                            "steps": steps,
                            "base_version": base_versions[i],
                            "want_model": want_model,
                            "report_key": report_key,
                            "model_dtype": model_dtype,
                            "shard": i,
                            "shard_epoch": (
                                self.generations[i]
                                if self.generations is not None
                                else -1
                            ),
                            "epoch": agg_generation,
                        },
                    )
                except Exception as exc:  # noqa: BLE001 - any agg-path
                    # failure (fenced, dead, upstream error) means
                    # bypass: replay DIRECT under the same report_key —
                    # shard dedup absorbs whatever the cohort already
                    # landed, so versions stay exact
                    self._drop_aggregator(i, exc)
            return c.call("PSPushDelta", self._stamp_epoch(req, i))

        resps = self._map(do)
        merged = {
            i: r["vec"] for i, r in enumerate(resps) if r.get("vec") is not None
        }
        return [r["version"] for r in resps], merged

    def push_delta_bucketed(
        self,
        delta,
        steps: int,
        base_versions: List[int],
        bucket_bounds: List[int],
        model_dtype: Optional[str] = None,
        want_model: bool = False,
        report_key: Optional[str] = None,
    ) -> Tuple[List[int], Dict[int, np.ndarray]]:
        """Streaming window-delta fan-out: the delta is cut at
        `bucket_bounds` (absolute [0, c1, ..., n] — layer-aligned by
        the worker) and each shard receives its intersection with each
        bucket as a SEQUENCE of PSPushDeltaBucket parts under ONE
        `report_key`. The shard parks parts until the set is complete,
        then applies atomically (version advances by `steps` once), so
        the first bytes fly while later layers are still materializing
        and replay/dedup semantics match push_delta exactly: a resend
        of an already-applied set dedups per part, a re-sent parked
        part overwrites idempotently. Shards stream in parallel; parts
        within a shard stay in order (the stream IS the pipeline).
        Always direct — the aggregation-tree route only understands
        whole-slice pushes. Returns (shard_versions,
        {shard_index: merged_slice}) like push_delta."""
        if not isinstance(delta, (codec.QuantizedDelta, codec.SparseDelta)):
            delta = np.asarray(delta)
        size = codec.delta_length(delta)
        if size != self.n_params:
            raise ValueError(f"delta size {size} != {self.n_params}")
        cuts = list(bucket_bounds)
        if (
            len(cuts) < 2
            or cuts[0] != 0
            or cuts[-1] != size
            or any(b <= a for a, b in zip(cuts, cuts[1:]))
        ):
            raise ValueError(f"malformed bucket bounds {bucket_bounds!r}")

        report_key = report_key or uuid.uuid4().hex

        def do(c, i):
            s, e = self.bounds[i]
            parts = [
                (max(bs, s), min(be, e))
                for bs, be in zip(cuts, cuts[1:])
                if max(bs, s) < min(be, e)
            ]
            if not parts:  # empty shard slice (more shards than params)
                parts = [(s, s)]
            resp = None
            for j, (ps_, pe) in enumerate(parts):
                req = {
                    "delta": codec.slice_delta(delta, ps_, pe),
                    "steps": steps,
                    "base_version": base_versions[i],
                    "offset": ps_ - s,
                    "bucket_index": j,
                    "num_buckets": len(parts),
                    "want_model": want_model,
                    "report_key": report_key,
                }
                if model_dtype:
                    req["model_dtype"] = model_dtype
                resp = c.call("PSPushDeltaBucket", self._stamp_epoch(req, i))
            return resp  # the final part's response carries the apply

        resps = self._map(do)
        merged = {
            i: r["vec"] for i, r in enumerate(resps) if r.get("vec") is not None
        }
        return [r["version"] for r in resps], merged

    def push_grad(
        self,
        grad: np.ndarray,
        versions: List[int],
        model_dtype: Optional[str] = None,
        return_model: bool = False,
        report_key: Optional[str] = None,
    ) -> Tuple[List[int], Optional[np.ndarray]]:
        """Per-step gradient fan-out (async / windowed-sync shards).
        Returns (shard_versions, full_model|None) — the model comes
        back only when return_model was set and every shard advanced
        past the reported version (async mode always advances).

        `report_key` lets a caller REPLAY a logical push after a shard
        failover (master/recovery.py): one key spans the whole fan-out,
        so on the resend the shards that applied the first attempt
        dedup it while the relaunched shard (restored to the pre-push
        version) applies it — the partially-torn report heals to
        exactly-once on every slice, keeping version accounting
        bit-exact across the failover.

        Like push_delta, `grad` may arrive int8-quantized
        (codec.QuantizedDelta) from the worker's EF grad path."""
        if not isinstance(grad, (codec.QuantizedDelta, codec.SparseDelta)):
            grad = np.asarray(grad)
        size = codec.delta_length(grad)
        if size != self.n_params:
            raise ValueError(f"grad size {size} != {self.n_params}")

        # shard-side dedup: retry-safe (and replay-safe when the caller
        # pins the key)
        report_key = report_key or uuid.uuid4().hex

        def do(c, i):
            s, e = self.bounds[i]
            req = {
                "grad": codec.slice_delta(grad, s, e),
                "version": versions[i],
                "return_model": return_model,
                "report_key": report_key,
            }
            if model_dtype:
                req["model_dtype"] = model_dtype
            return c.call("PSPushGrad", self._stamp_epoch(req, i))

        resps = self._map(do)
        new_versions = [r["version"] for r in resps]
        vec = None
        if return_model and all(r.get("vec") is not None for r in resps):
            vec = self._assemble([r["vec"] for r in resps])
        return new_versions, vec

    def export_opt(self) -> List[Optional[list]]:
        """Per-shard optimizer-state leaves (exact resume)."""
        return [
            r["leaves"]
            for r in self._map(
                lambda c, i: c.call("PSOptState", self._stamp_epoch({}, i))
            )
        ]

    def export_opt_shard(self, i: int) -> Optional[list]:
        """One shard's optimizer-state leaves (the recovery plane's
        opt-state mirror polls shards independently)."""
        return self._clients[i].call(
            "PSOptState", self._stamp_epoch({}, i)
        )["leaves"]

    def restore_opt(self, shards: List[Optional[list]]):
        if len(shards) != self.num_shards:
            raise ValueError(
                f"opt state has {len(shards)} shards, group has "
                f"{self.num_shards} — exact resume needs the same "
                "--num_ps as the checkpointing job"
            )
        # restore overwrites; a resend is a no-op (retry-safe)
        self._map(
            lambda c, i: c.call(
                "PSOptRestore", self._stamp_epoch({"leaves": shards[i]}, i)
            )
        )

    def _assemble(self, slices: List[np.ndarray]) -> np.ndarray:
        out = np.empty(self.n_params, dtype=np.asarray(slices[0]).dtype)
        for (s, e), sl in zip(self.bounds, slices):
            out[s:e] = sl
        return out

    def wire_stats(self) -> dict:
        """Aggregate wire-byte accounting across the shard fan-out
        (one logical push = num_shards slice sends; bytes-per-sync
        means their SUM — see rpc/policy.WireStats)."""
        from elasticdl_tpu.rpc.policy import aggregate_wire_snapshots

        return aggregate_wire_snapshots(
            c.wire.snapshot() for c in self._clients
        )

    def close(self):
        self._pool.shutdown(wait=False)
        if self._async_pool is not None:
            self._async_pool.shutdown(wait=False)
        for c in self._clients:
            c.close()
        with self._agg_lock:
            agg = list(self._agg_clients or []) + self._agg_graveyard
            self._agg_clients = None
            self._agg_graveyard = []
        for c in agg:
            c.close()
