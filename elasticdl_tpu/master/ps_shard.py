"""Sharded parameter server: the dense model split across N endpoints.

The reference's master is a single PS holding the whole model; its own
design docs call the resulting full-model-pull / full-gradient-push
bandwidth the scaling wall (reference:
elasticdl/doc/worker_optimization_design.md — get_model/report_gradient
dominate the step; SURVEY §7.3 item 3 names "model-sharded PS" as the
remedy that must preserve the any-K-reports elasticity semantics).

This module provides that remedy natively for the flat-buffer
transport: the raveled f32 parameter vector (codec.ravel_np order) is
split into `num_shards` contiguous slices, each owned by a
`PSShardServicer` behind its own RPC endpoint. Workers push gradient /
delta SLICES to all shards in parallel — N sockets, N servicer locks,
N optimizer applies — so PS bandwidth and PS CPU scale with the shard
count instead of walling at one endpoint. The control plane (tasks,
evaluation, checkpoints, the sparse embedding store) stays on the
master: shards are deliberately dumb slice-holders, like the
reference's Redis shards were for embeddings (reference:
elasticdl/python/master/embedding_service.py:82-99 — 6 independent
stores behind one logical table).

Consistency model per protocol:

- **local-update / SSP windows** (the TPU-idiomatic hot path): deltas
  are additive and never rejected, so per-shard application commutes —
  a single worker gets exactly per-step-sync math (as with one PS) and
  multiple workers get local-SGD merge semantics, per slice. Staleness
  down-weighting applies per shard with each shard's own version.
- **async per-step**: each shard applies its gradient slice
  immediately (optionally staleness-LR-modulated). Elementwise
  optimizers (sgd/momentum/adam/...) make the slice-wise apply
  identical to the whole-vector apply.
- **strict sync per-step** (version-equality rejection) is NOT offered
  across shards: a gradient accepted by shard A and rejected by shard
  B would leave a torn update with no atomic retry. Master boot
  rejects that configuration (use a staleness window, async, or
  windows — or a single PS).

Shard versions advance independently; they agree on the NUMBER of
applied steps per worker stream but may interleave concurrent workers
differently (the standard sharded-PS relaxation — each slice still
sees every report exactly once).
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from elasticdl_tpu.common import codec, messages
from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.master import fanin
from elasticdl_tpu.master.ps_optimizer import PSOptimizer
from elasticdl_tpu.obs import trace as obs_trace

logger = get_logger(__name__)


def slice_boundaries(n_params: int, num_shards: int) -> List[Tuple[int, int]]:
    """Deterministic near-equal split of [0, n_params) into contiguous
    shard slices — computed identically by master and workers from
    (n_params, num_shards) alone, so no boundary table rides the wire."""
    if num_shards <= 0:
        raise ValueError(f"num_shards must be > 0, got {num_shards}")
    edges = np.linspace(0, n_params, num_shards + 1).astype(np.int64)
    return [(int(edges[i]), int(edges[i + 1])) for i in range(num_shards)]


class PSShardServicer:
    """One shard: a contiguous slice of the flat f32 model vector plus
    its optimizer state. Mirrors MasterServicer's gradient semantics
    (servicer.py report_gradient / report_local_update) restricted to a
    single array; see the module docstring for the consistency model."""

    def __init__(
        self,
        shard_id: int,
        num_shards: int,
        optimizer: Optional[PSOptimizer] = None,
        grads_to_wait: int = 1,
        use_async: bool = False,
        lr_staleness_modulation: bool = False,
        staleness_window: int = 0,
        generation: int = 0,
        dedup_cap: Optional[int] = None,
        fanin_combine: Optional[bool] = None,
    ):
        self.shard_id = shard_id
        self.num_shards = num_shards
        # fencing epoch: bumped by the group on every relaunch of this
        # shard slot (a relaunch constructs a NEW servicer), or moved
        # in place by PSRefence during a master-migration cutover.
        # Requests carrying a different epoch are rejected hard
        # (rpc/fencing.py). Written under self._lock; _check_epoch
        # reads it bare — a torn read is impossible for an int, and a
        # request racing the refence is rejected either way.
        self.generation = int(generation)
        self._opt = optimizer
        self._grads_to_wait = grads_to_wait
        self._use_async = use_async
        self._lr_staleness_modulation = lr_staleness_modulation
        self._staleness_window = staleness_window

        self._lock = threading.Lock()
        self._vec: Optional[np.ndarray] = None  # f32 [slice_len]
        self._version = 0
        self._grad_sum: Optional[np.ndarray] = None
        self._grad_n = 0
        # Push dedup ring (report_key -> None, insertion-ordered): a
        # retried push whose first attempt WAS applied (gRPC can surface
        # UNAVAILABLE after the server processed the request) must
        # no-op instead of double-applying — this is what makes the
        # client's transient retry safe for mutating ops and shrinks
        # the torn-report window to hard shard death (ADVICE r3 #2).
        #
        # Capacity: the ring only has to remember keys that can still be
        # retried, i.e. every in-flight sync of every worker — the group
        # sizes it as num_workers x max in-flight syncs per worker, with
        # headroom (see PSShardGroup / the bound derivation next to the
        # retry classification in rpc/ps_client.py). 512 is the
        # standalone default for direct-constructed servicers.
        self._seen_reports: "OrderedDict[str, None]" = OrderedDict()
        self._seen_cap = max(64, int(dedup_cap)) if dedup_cap else 512
        # observability: chaos tests assert the dedup ring actually
        # absorbed retried pushes (a dropped-response retry MUST land
        # here, not double-apply)
        self._duplicate_pushes = 0
        self._applied_pushes = 0
        # Bucketed-push parking (PSPushDeltaBucket): partial bucket
        # sets park here keyed by report_key — bucket_index ->
        # (offset, dense f32 part) — until num_buckets parts arrived,
        # then the WHOLE set applies atomically under self._lock (the
        # fan-in CombineBuffer's park-then-apply shape, per window
        # instead of per cohort). A re-sent parked part overwrites its
        # slot idempotently. Capacity-capped like the dedup ring: an
        # abandoned partial set (worker died mid-stream — its delta
        # never applies, matching a dropped flat push) must not leak.
        self._parked_buckets: "OrderedDict[str, dict]" = OrderedDict()
        self._parked_cap = 64
        self._parked_evictions = 0
        # wire-byte accounting: the hosting RpcServer's WireStats,
        # attached by shard_host/ps_group after server construction so
        # `stats()` answers bytes questions over the existing stats RPC
        self._wire = None
        # RPC admission counters (rpc/transport.ServerDispatcher),
        # attached the same way — stats() carries both
        self._admission_fn = None
        # hierarchical fan-in stage (master/fanin.py, --fanin_combine /
        # EDL_FANIN_COMBINE): compatible concurrent pushes are summed
        # OUTSIDE self._lock and applied as one batch — one lock
        # acquisition, one apply, one shared packed response per batch
        if fanin_combine is None:
            fanin_combine = fanin.combine_enabled()
        self._delta_combine = (
            fanin.CombineBuffer(self._apply_delta_batch)
            if fanin_combine
            else None
        )
        self._grad_combine = (
            fanin.CombineBuffer(self._apply_grad_batch)
            if fanin_combine
            else None
        )
        # combine observability: ratio = combined_reports / batches
        self._combined_batches = 0
        self._combined_reports = 0
        # pull prepack cache: one encoded {"version", "vec"} frame per
        # (version, wire form), built OUTSIDE self._lock and served to
        # every concurrent puller until the version bumps — model-down
        # cost is one encode per version instead of one per puller, and
        # pullers never serialize against push appliers on the shard
        # lock. Guarded by its own lock: the cache must be consultable
        # while an apply holds self._lock.
        self._prepack_lock = threading.Lock()
        self._prepack: Dict[Tuple[int, str], messages.Prepacked] = {}
        self._prepack_encodes = 0
        self._prepack_served = 0
        self._prepack_copy_bytes = 0

    # -- handler table -------------------------------------------------------

    #: Handlers that deliberately skip the fencing epoch check: the obs
    #: reads answer for the PROCESS (spans/metrics survive a fence and
    #: are exactly what a postmortem wants from a fenced shard), and
    #: PSRefence IS the fence mover — it carries the NEW generation, so
    #: it cannot pass a check against the old one; its own monotonicity
    #: check (reject generation < current) is the fence for it.
    UNFENCED_HANDLERS = frozenset({"GetTrace", "GetMetrics", "PSRefence"})

    def handlers(self) -> Dict[str, Any]:
        return {
            "PSInit": self.init_slice,
            "PSPull": self.pull,
            "PSPushGrad": self.push_grad,
            "PSPushDelta": self.push_delta,
            "PSPushDeltaBucket": self.push_delta_bucket,
            "PSPushDeltaCombined": self.push_delta_combined,
            "PSOptState": self.opt_state,
            "PSOptRestore": self.opt_restore,
            "PSRefence": self.refence,
            "GetTrace": self.get_trace,
            "GetMetrics": self.get_metrics,
        }

    def refence(self, req: dict) -> dict:  # edl-lint: disable=thread-provenance -- self.generation is a single int word (design note at the attribute): a torn read is impossible, the bump is monotonic under self._lock, and a request racing the move is rejected either way
        """In-place fencing-generation bump — the master-migration
        cutover (master/migration.py). Unlike a relaunch (which
        constructs a NEW servicer at the bumped generation and boots
        empty), a refence moves the epoch under the live slice: state
        survives, and every client still stamping the old generation —
        the deposed master and anything it spawned — bounces with
        FAILED_PRECONDITION from then on. Monotonic and idempotent by
        target: generation == current answers ok (a retried bump),
        generation < current is rejected as the stale caller it is."""
        target = int(req.get("generation", -1))
        with self._lock:
            if target < self.generation:
                from elasticdl_tpu.rpc.fencing import EpochFencedError

                raise EpochFencedError(
                    "ps", self.shard_id, self.generation, target
                )
            if target > self.generation:
                logger.info(
                    "PS shard %d refenced: generation %d -> %d",
                    self.shard_id, self.generation, target,
                )
                self.generation = target
            return {"generation": self.generation}

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
        """Feed this shard's counters into the MetricsRegistry as a
        pull collector (called by the hosting group/shard-main wiring,
        like attach_wire_stats). Weakly referenced: a replaced
        (re-fenced) servicer stops reporting once collected."""
        from elasticdl_tpu.obs import metrics as obs_metrics

        reg = registry if registry is not None else obs_metrics.get_registry()
        ref = weakref.ref(self)
        shard = str(self.shard_id)

        def collector(sink):
            s = ref()
            if s is None:
                return
            st = s.stats()
            sink.counter(
                "edl_ps_applied_pushes_total",
                st["applied_pushes"],
                shard=shard,
            )
            sink.counter(
                "edl_ps_duplicate_pushes_total",
                st["duplicate_pushes"],
                shard=shard,
            )
            sink.gauge("edl_ps_version", st["version"], shard=shard)
            sink.gauge("edl_ps_generation", st["generation"], shard=shard)
            sink.counter(
                "edl_ps_combined_batches_total",
                st["combined_batches"],
                shard=shard,
            )
            sink.counter(
                "edl_ps_combined_reports_total",
                st["combined_reports"],
                shard=shard,
            )
            sink.counter(
                "edl_prepack_encodes_total",
                st["prepack_encodes"],
                shard=shard,
            )
            sink.counter(
                "edl_prepack_served_pulls_total",
                st["prepack_served_pulls"],
                shard=shard,
            )
            sink.counter(
                "edl_prepack_copy_bytes_total",
                st["prepack_encode_copy_bytes"],
                shard=shard,
            )

        reg.register_collector(collector)

    def _check_epoch(self, req: dict):  # edl-lint: disable=lock-discipline -- deliberate bare read of the single int epoch word (design note at the attribute): a request racing the refence bump is rejected either way, and taking self._lock here would serialize every fence check against push appliers
        from elasticdl_tpu.rpc.fencing import check_epoch

        check_epoch(req, self.generation, "ps", self.shard_id)

    def opt_state(self, req: dict) -> dict:
        """Flat optimizer-state leaves of this slice (exact resume)."""
        self._check_epoch(req)
        with self._lock:
            leaves = (
                self._opt.state_snapshot()
                if self._opt is not None and self._opt.initialized
                else None
            )
        return {"leaves": leaves}

    def opt_restore(self, req: dict) -> dict:
        """Adopt checkpointed optimizer state for this slice."""
        self._check_epoch(req)
        with self._lock:
            if self._vec is None:
                raise ValueError("opt restore before slice init")
            if self._opt is not None and req.get("leaves") is not None:
                self._opt.restore_state(self._vec, req["leaves"])
        return {}

    @property
    def version(self) -> int:
        with self._lock:
            return self._version

    def initialized(self) -> bool:
        with self._lock:
            return self._vec is not None

    # -- RPCs ----------------------------------------------------------------

    def init_slice(self, req: dict) -> dict:
        """SETNX semantics (like the embedding store's set_if_not_exist,
        reference embedding_service.py:315-357): the first initializer
        wins; late/racing initializers get the current version back."""
        self._check_epoch(req)
        with self._lock:
            if self._vec is None:
                self._vec = np.asarray(req["vec"], dtype=np.float32).copy()
                self._version = int(req.get("version", 0))
                logger.info(
                    "PS shard %d/%d initialized: %d params at v%d",
                    self.shard_id,
                    self.num_shards,
                    self._vec.size,
                    self._version,
                )
            return {"version": self._version, "size": self._vec.size}

    def pull(self, req: dict):
        """Model-down for this slice. The lock is held only to snapshot
        (version, vec reference); the encode happens OUTSIDE it via the
        per-(version, wire-form) prepack cache, so a fleet of pullers
        costs one encode per version and never serializes push
        appliers. Returns the response dict for the metadata-only
        answers and a `messages.Prepacked` frame (byte-identical to
        packing the dict) for model-carrying ones."""
        self._check_epoch(req)
        with self._lock:
            vec = self._vec
            version = self._version
        if vec is None:
            return {"version": -1, "vec": None}
        if req.get("only_if_newer") and version <= req.get("version", -1):
            return {"version": version, "vec": None}
        return self._pull_prepacked(
            version, vec, req.get("model_dtype") or "float32"
        )

    def _pull_prepacked(
        self, version: int, vec: np.ndarray, form: str
    ) -> messages.Prepacked:
        key = (version, form)
        with self._prepack_lock:
            entry = self._prepack.get(key)
            if entry is not None:
                self._prepack_served += 1
                return entry
        # encode outside BOTH locks. push_delta mutates self._vec in
        # place, so an unlocked read can tear — but every in-place
        # mutation bumps self._version inside the same critical
        # section, so re-checking the version after the encode detects
        # any possible tear; serving the re-snapshotted NEWER version
        # is always valid for pull.
        for _ in range(3):
            before = codec.encode_copy_stats()["bytes"]
            entry = self._encode_pull_entry(version, vec, form)
            copied = codec.encode_copy_stats()["bytes"] - before
            with self._lock:
                if self._version == version:
                    break
                version = self._version
                vec = self._vec
        else:
            # the shard is bumping faster than we can encode: fall back
            # to a private snapshot (copy under the lock — the only
            # pull path that pays a lock-held copy, and only under
            # pathological churn) and encode that
            with self._lock:
                version = self._version
                vec = self._vec.copy()
            before = codec.encode_copy_stats()["bytes"]
            entry = self._encode_pull_entry(version, vec, form)
            copied = codec.encode_copy_stats()["bytes"] - before
        key = (version, form)
        with self._prepack_lock:
            cur = self._prepack.get(key)
            if cur is not None:
                self._prepack_served += 1
                return cur
            self._prepack_encodes += 1
            self._prepack_copy_bytes += copied
            self._prepack_served += 1
            # version-bump invalidation: keep only the newest version's
            # forms (the cache never grows past the handful of wire
            # forms in use)
            newest = max(k[0] for k in self._prepack) if self._prepack else -1
            newest = max(newest, version)
            for k in list(self._prepack):
                if k[0] < newest:
                    del self._prepack[k]
            if version == newest:
                self._prepack[key] = entry
        return entry

    def _encode_pull_entry(
        self, version: int, vec: np.ndarray, form: str
    ) -> messages.Prepacked:
        """One pull frame for (version, form). f32 packs the live slice
        directly (zero-copy into the frame — the caller's version
        recheck covers the unlocked read); other wire forms pay their
        dtype conversion once per version."""
        with obs_trace.span(
            "ps.prepack_encode",
            cat="ps",
            args={"shard": self.shard_id, "form": form},
        ):
            arr = (
                vec
                if form == "float32"
                else vec.astype(codec.dtype_from_str(form))
            )
            return messages.Prepacked(
                messages.pack({"version": version, "vec": arr})
            )

    def push_grad(self, req: dict) -> dict:
        """Per-step gradient slice. Async mode applies immediately
        (optionally LR-modulated by 1/staleness); sync mode accumulates
        `grads_to_wait` reports within the staleness window. Strict
        equality rejection is refused at configuration time (module
        docstring) so an accept can never be torn across shards.

        With fan-in combining on, same-lineage concurrent reports
        rendezvous in the combine buffer and are accumulated as one
        batch (master/fanin.py)."""
        self._check_epoch(req)
        # no-copy when the wire already carried a dense f32 array: the
        # decoded frombuffer view is applied as-is (it is read-only,
        # and every consumer below uses it only as a ufunc operand).
        # Compressed wire forms decode here — OUTSIDE the lock — and
        # NOWHERE else: bf16 widens, int8 (QuantizedDelta) dequantizes;
        # shard math is always full precision
        grad = codec.delta_to_f32(req["grad"])
        # combine only the pure-accumulate regime (sync, no staleness
        # scaling): async applies one optimizer step PER report, and
        # staleness down-weighting depends on each member's version —
        # neither commutes with presumming. return_model rides the key
        # so plain reports never share a (fallback) batch with it.
        if (
            self._grad_combine is not None
            and not self._use_async
            and not self._staleness_window
        ):
            key = (
                "grad",
                req.get("model_dtype") or "",
                bool(req.get("return_model")),
            )
            return self._grad_combine.submit(key, req, grad)
        # the span covers lock WAIT plus apply — on a contended shard
        # the wait is the interesting part of the sync critical path
        with obs_trace.span(
            "ps.apply",
            cat="ps",
            args={"shard": self.shard_id, "kind": "grad"},
        ):
            with self._lock:
                return self._push_grad_locked(req, grad)

    def _push_grad_locked(self, req: dict, grad: np.ndarray) -> dict:  # edl-lint: disable=lock-discipline -- caller holds self._lock
        """Serial gradient-report semantics (caller holds the lock):
        the exactness reference the combined fast path must match."""
        if self._vec is None:
            raise ValueError("gradient pushed before shard init")
        if self._is_duplicate(req):
            resp = {"accepted": True, "version": self._version,
                    "duplicate": True}
            if req.get("return_model"):
                resp["vec"] = self._wire_vec(req)
            return resp
        if grad.shape != self._vec.shape:
            raise ValueError(
                f"grad slice shape {grad.shape} != {self._vec.shape}"
            )
        report_version = int(req.get("version", -1))
        staleness = self._version - report_version
        if self._use_async:
            scale = 1.0
            if self._lr_staleness_modulation and staleness > 1:
                scale = 1.0 / float(staleness)
            self._apply(grad * scale if scale != 1.0 else grad)
        else:
            # windowed sync: accumulate K reports; staleness beyond
            # the window is down-weighted (window/staleness) rather
            # than rejected — rejection cannot be atomic across
            # shards (module docstring)
            if self._staleness_window and staleness > self._staleness_window:
                grad = grad * (self._staleness_window / float(staleness))
            if self._grad_sum is None:
                self._grad_sum = grad.copy()
            else:
                self._grad_sum += grad
            self._grad_n += 1
            if self._grad_n >= self._grads_to_wait:
                self._apply(self._grad_sum / self._grad_n)
                self._grad_sum = None
                self._grad_n = 0
        self._record_applied(req)
        resp = {"accepted": True, "version": self._version}
        if req.get("return_model") and self._version != report_version:
            resp["vec"] = self._wire_vec(req)
        return resp

    def push_delta(self, req: dict) -> dict:
        """Local-update window delta for this slice — mirrors
        MasterServicer.report_local_update: add, advance version by
        `steps`, hand the merged slice back when the pusher's base fell
        behind (another worker synced in between).

        With fan-in combining on, same-base concurrent deltas
        rendezvous in the combine buffer and apply as one batch
        (master/fanin.py)."""
        self._check_epoch(req)
        # with no staleness window the delta apply is base-version-
        # independent (base only shapes the response, and a combined
        # member always gets the merged slice back), so the lineage key
        # is just the kind + response dtype — concurrent cohorts stay
        # in ONE group instead of fragmenting by base
        if self._delta_combine is not None and not self._staleness_window:
            key = ("delta", req.get("model_dtype") or "")
            wire = req["delta"]
            if isinstance(wire, codec.SparseDelta):
                # top-k deltas enter the combine stage UN-densified:
                # the presum scatter-adds just the k shipped entries
                # per member (fanin.presum_f32), so the member cost
                # scales with the compression ratio while the dense
                # full-slice sweeps happen once per batch
                return self._delta_combine.submit(key, req, wire)
            return self._delta_combine.submit(
                key, req, codec.delta_to_f32(wire)
            )
        # dense f32 passes through as a view; bf16 widens; int8 /
        # top-k (QuantizedDelta / SparseDelta slices) decode to the
        # dense f32 slice here, OUTSIDE the lock — the compression
        # never leaks into the apply math
        delta = codec.delta_to_f32(req["delta"])
        with obs_trace.span(
            "ps.apply",
            cat="ps",
            args={"shard": self.shard_id, "kind": "delta"},
        ):
            with self._lock:
                return self._push_delta_locked(req, delta)

    def _push_delta_locked(self, req: dict, delta: np.ndarray) -> dict:  # edl-lint: disable=lock-discipline -- caller holds self._lock
        """Serial window-delta semantics (caller holds the lock): the
        exactness reference the combined fast path must match."""
        if self._vec is None:
            raise ValueError("delta pushed before shard init")
        if self._is_duplicate(req):
            # already applied: answer like a base-fell-behind merge
            # so a retrying worker still rebases onto the result
            return {
                "version": self._version,
                "vec": self._wire_vec(req),
                "duplicate": True,
            }
        steps = int(req["steps"])
        base_version = int(req["base_version"])
        if delta.shape != self._vec.shape:
            raise ValueError(
                f"delta slice shape {delta.shape} != {self._vec.shape}"
            )
        scale = 1.0
        if self._staleness_window:
            staleness = self._version - base_version
            if staleness > self._staleness_window:
                scale = self._staleness_window / float(staleness)
        self._vec += scale * delta if scale != 1.0 else delta
        self._version += steps
        self._record_applied(req)
        resp = {"version": self._version}
        if base_version + steps != self._version or req.get("want_model"):
            resp["vec"] = self._wire_vec(req)
        return resp

    def push_delta_bucket(self, req: dict) -> dict:
        """One layer-aligned bucket of a window delta (the
        worker's streaming push, ps_client.push_delta_bucketed). Parts
        of one window share `report_key`; partial sets PARK (the
        fan-in CombineBuffer's park-then-apply shape) and the full set
        applies atomically at the window boundary — `version` advances
        by `steps` exactly once, and `_record_applied` registers the
        key only then, so:

        - a replayed part of an already-applied set dedups
          (`_is_duplicate`) and answers like push_delta's duplicate
          path — the retrying/replaying worker rebases onto the result;
        - a re-sent parked part overwrites its slot idempotently;
        - a worker dying mid-stream leaves a partial set that never
          applies (eventually evicted), exactly like a flat push whose
          RPC never arrived."""
        self._check_epoch(req)
        key = req.get("report_key") or ""
        if not key:
            raise ValueError("bucketed push requires a report_key")
        # decode to the dense f32 part OUTSIDE the lock (push_delta's
        # contract: compression never leaks into the apply math)
        part = codec.delta_to_f32(req["delta"])
        idx = int(req.get("bucket_index", 0))
        total = int(req.get("num_buckets", 1))
        offset = int(req.get("offset", 0))
        with obs_trace.span(
            "ps.apply",
            cat="ps",
            args={"shard": self.shard_id, "kind": "delta_bucket"},
        ):
            with self._lock:
                if self._vec is None:
                    raise ValueError("delta pushed before shard init")
                if self._is_duplicate(req):
                    return {
                        "version": self._version,
                        "vec": self._wire_vec(req),
                        "duplicate": True,
                    }
                if offset < 0 or offset + part.shape[0] > self._vec.shape[0]:
                    raise ValueError(
                        f"bucket [{offset}, {offset + part.shape[0]}) "
                        f"outside slice of {self._vec.shape[0]}"
                    )
                parked = self._parked_buckets.get(key)
                if parked is None:
                    parked = self._parked_buckets[key] = {}
                    while len(self._parked_buckets) > self._parked_cap:
                        self._parked_buckets.popitem(last=False)
                        self._parked_evictions += 1
                parked[idx] = (offset, part)
                if len(parked) < total:
                    # incomplete set: nothing applied yet (atomicity —
                    # the model other pullers see never contains a
                    # torn window)
                    return {"version": self._version, "parked": len(parked)}
                del self._parked_buckets[key]
                steps = int(req["steps"])
                base_version = int(req["base_version"])
                scale = 1.0
                if self._staleness_window:
                    staleness = self._version - base_version
                    if staleness > self._staleness_window:
                        scale = self._staleness_window / float(staleness)
                for off, d in parked.values():
                    self._vec[off:off + d.shape[0]] += (
                        scale * d if scale != 1.0 else d
                    )
                self._version += steps
                self._record_applied(req)
                resp = {"version": self._version}
                if base_version + steps != self._version or req.get(
                    "want_model"
                ):
                    resp["vec"] = self._wire_vec(req)
                return resp

    def push_delta_combined(self, req: dict):  # edl-lint: disable=exactness-lineage -- deliberately unclassified (rpc/policy.py): a combined forward carries k member keys and is NEVER resent as-is — forward failure errors the members, who each retry DIRECT under their own dedup key
        """One presummed cohort from an aggregator node (agg/): apply
        the combined delta once, register EVERY member report_key, and
        answer with the merged slice the aggregator fans back to all
        members (their bases fell behind the combined version by
        construction, exactly like the fan-in fast path above).

        All-or-nothing: if the batch cannot take the fast path —
        staleness down-weighting active (member-base-dependent), any
        member key already applied, an intra-batch duplicate, a shape
        mismatch, or an uninitialized slice — NOTHING is applied and
        the response says accepted=False with the already-seen keys;
        the aggregator decomposes into serial per-member PSPushDelta
        forwards, each deduped individually, so no replay interleaving
        can double-apply."""
        self._check_epoch(req)
        delta = codec.delta_to_f32(req["delta"])
        keys = [k for k in (req.get("report_keys") or []) if k]
        with obs_trace.span(
            "ps.apply",
            cat="ps",
            args={"shard": self.shard_id, "kind": "delta_combined"},
        ):
            with self._lock:
                dupes = [k for k in keys if k in self._seen_reports]
                ok = (
                    self._vec is not None
                    and not self._staleness_window
                    and delta.shape == self._vec.shape
                    and keys
                    and len(keys) == len(set(keys))
                    and not dupes
                )
                if not ok:
                    for k in dupes:
                        self._duplicate_pushes += 1
                    return {
                        "accepted": False,
                        "version": self._version,
                        "duplicates": dupes,
                    }
                self._combined_batches += 1
                self._combined_reports += len(keys)
                self._vec += delta
                self._version += int(req["steps"])
                for k in keys:
                    self._record_applied({"report_key": k})
                version = self._version
                vec = self._wire_vec(req)
        return {"accepted": True, "version": version, "vec": vec}

    # -- fan-in combine appliers (fanin.CombineBuffer callbacks) -------------

    def _apply_delta_batch(self, members) -> None:
        """Apply k same-lineage window deltas in ONE lock acquisition.
        The presum happens outside the lock; the fast path does one
        vector add, advances the version by the summed steps, and
        answers every member with one shared pre-packed merged slice.
        Any anomaly — replayed report_key, staleness down-weighting
        active, shape mismatch, uninitialized slice — falls back to
        member-by-member serial semantics under the same single
        acquisition, so dedup/exactness survive unchanged."""
        acc = None
        if len(members) > 1:
            lens = [codec.delta_length(m.delta) for m in members]
            if len(set(lens)) == 1:
                # delta views are read-only (codec zero-copy); the
                # presum builds one writable f32 accumulator, cache-
                # blocked so the accumulator slice stays L2-resident
                # across the dense adds; sparse (top-k) members
                # scatter-add only their shipped entries
                with obs_trace.span(
                    "fanin.presum",
                    cat="fanin",
                    args={"members": len(members)},
                ):
                    acc = fanin.presum_f32(
                        [m.delta for m in members], n=lens[0]
                    )
        shared_version = None
        shared_vec = None
        # a replay can share a batch with its original (client timed
        # out while the original was still parked in the buffer): the
        # fast path must see one key at most once or it double-applies
        keys = [
            m.req.get("report_key")
            for m in members
            if m.req.get("report_key")
        ]
        with obs_trace.span(
            "ps.apply",
            cat="ps",
            args={"shard": self.shard_id, "kind": "delta_batch"},
        ):
            with self._lock:
                self._combined_batches += 1
                self._combined_reports += len(members)
                fast = (
                    acc is not None
                    and self._vec is not None
                    and not self._staleness_window
                    and acc.shape == self._vec.shape
                    and len(keys) == len(set(keys))
                    and not any(k in self._seen_reports for k in keys)
                )
                if fast:
                    self._vec += acc
                    self._version += sum(
                        int(m.req["steps"]) for m in members
                    )
                    for m in members:
                        self._record_applied(m.req)
                    shared_version = self._version
                    shared_vec = self._wire_vec(members[0].req)
                else:
                    for m in members:
                        try:
                            # densify on demand: anomaly batches are
                            # rare and must match serial semantics
                            # exactly
                            m.resp = self._push_delta_locked(
                                m.req, codec.delta_to_f32(m.delta)
                            )
                        except Exception as e:
                            m.error = e
        if fast:
            # one serialization for the whole batch, done off-lock on
            # the leader's thread: every member's base fell behind the
            # combined version, so every member gets the merged slice —
            # identical bytes, shared by reference
            shared = messages.Prepacked(
                messages.pack({"version": shared_version, "vec": shared_vec})
            )
            for m in members:
                m.resp = shared

    def _apply_grad_batch(self, members) -> None:
        """Accumulate k same-version sync gradient reports in ONE lock
        acquisition. The fast path is the pure-accumulate case (sync
        mode, no staleness scaling, the batch stays strictly below the
        grads_to_wait apply threshold, no model-down requested): adding
        the presum IS the serial math. Everything else — async applies,
        threshold crossings, replays — runs member-by-member under the
        same single acquisition."""
        acc = None
        if len(members) > 1 and len({m.delta.shape for m in members}) == 1:
            with obs_trace.span(
                "fanin.presum",
                cat="fanin",
                args={"members": len(members)},
            ):
                acc = fanin.presum_f32([m.delta for m in members])
        # same intra-batch uniqueness requirement as the delta applier:
        # a replay sharing a batch with its original must fall back
        keys = [
            m.req.get("report_key")
            for m in members
            if m.req.get("report_key")
        ]
        with obs_trace.span(
            "ps.apply",
            cat="ps",
            args={"shard": self.shard_id, "kind": "grad_batch"},
        ):
            with self._lock:
                self._combined_batches += 1
                self._combined_reports += len(members)
                fast = (
                    acc is not None
                    and self._vec is not None
                    and not self._use_async
                    and not self._staleness_window
                    and self._grad_n + len(members) < self._grads_to_wait
                    and acc.shape == self._vec.shape
                    and not any(
                        m.req.get("return_model") for m in members
                    )
                    and len(keys) == len(set(keys))
                    and not any(k in self._seen_reports for k in keys)
                )
                if fast:
                    if self._grad_sum is None:
                        self._grad_sum = acc
                    else:
                        self._grad_sum += acc
                    self._grad_n += len(members)
                    for m in members:
                        self._record_applied(m.req)
                    version = self._version
                    for m in members:
                        m.resp = {"accepted": True, "version": version}
                else:
                    for m in members:
                        try:
                            m.resp = self._push_grad_locked(
                                m.req, m.delta
                            )
                        except Exception as e:
                            m.error = e

    # -- internals -----------------------------------------------------------

    def attach_wire_stats(self, wire):
        """Point stats() at the hosting RpcServer's WireStats (called
        once right after server construction, before start)."""
        self._wire = wire

    def attach_admission_stats(self, fn):
        """Point stats() at the hosting RpcServer's admission counters
        (RpcServer.admission_stats), same contract as
        attach_wire_stats."""
        self._admission_fn = fn

    def stats(self) -> Dict[str, int]:
        """Push accounting (exactness evidence for the chaos tests):
        `applied_pushes` counts pushes that mutated state,
        `duplicate_pushes` counts retried resends the dedup ring
        absorbed. applied + duplicate == pushes received. When the
        hosting server attached its WireStats, also wire bytes in/out
        of this shard (bytes_received ~ push payload cost, bytes_sent ~
        model-down cost)."""
        with self._lock:
            out = {
                "applied_pushes": self._applied_pushes,
                "duplicate_pushes": self._duplicate_pushes,
                "version": self._version,
                "generation": self.generation,
                # fan-in combine ratio = combined_reports / batches
                # (1.0 when combining is off or every batch had k=1)
                "combined_batches": self._combined_batches,
                "combined_reports": self._combined_reports,
                # bucketed-push parking: partial window sets
                # currently parked + abandoned sets evicted (a healthy
                # run shows 0 evictions — parked sets complete within
                # one push)
                "parked_bucket_sets": len(self._parked_buckets),
                "parked_bucket_evictions": self._parked_evictions,
            }
        with self._prepack_lock:
            # pull amortization evidence: served / encodes is the
            # pulls-per-encode ratio the prepack cache buys; copy_bytes
            # is codec-counted compaction bytes on the encode path
            # (0 == the zero-copy contract held)
            out["prepack_encodes"] = self._prepack_encodes
            out["prepack_served_pulls"] = self._prepack_served
            out["prepack_encode_copy_bytes"] = self._prepack_copy_bytes
        if self._wire is not None:
            snap = self._wire.snapshot()
            out["bytes_sent"] = snap["bytes_sent"]
            out["bytes_received"] = snap["bytes_received"]
        if self._admission_fn is not None:
            adm = self._admission_fn()
            if adm:
                out["admission"] = adm
        return out

    def _is_duplicate(self, req: dict) -> bool:  # edl-lint: disable=lock-discipline -- caller holds self._lock
        """True if req's report_key was already APPLIED (caller holds
        the lock). Pure membership check: the key is registered by
        `_record_applied` only after the mutation succeeds (ADVICE r5 —
        registering before validation meant a push that FAILED mid-apply
        was answered as an applied duplicate on retry, silently losing
        the report). Keyless pushes are never deduped."""
        key = req.get("report_key")
        if key and key in self._seen_reports:
            self._duplicate_pushes += 1
            return True
        return False

    def _record_applied(self, req: dict):  # edl-lint: disable=lock-discipline -- caller holds self._lock
        """Register req's report_key AFTER its mutation succeeded
        (caller holds the lock). A validation/apply exception unwinds
        before reaching here, so the key stays unregistered and the
        client's retry gets a real second attempt."""
        self._applied_pushes += 1
        key = req.get("report_key")
        if not key:
            return
        self._seen_reports[key] = None
        while len(self._seen_reports) > self._seen_cap:
            self._seen_reports.popitem(last=False)

    def _wire_vec(self, req: dict) -> np.ndarray:  # edl-lint: disable=lock-discipline -- caller holds self._lock
        dtype = req.get("model_dtype")
        if dtype and dtype != "float32":
            return self._vec.astype(codec.dtype_from_str(dtype))
        return self._vec.copy()

    def _apply(self, grad: np.ndarray):  # edl-lint: disable=lock-discipline -- caller holds self._lock
        """Optimizer step on the slice (caller holds the lock).
        Elementwise optimizers make the slice-wise apply exact."""
        if self._opt is not None:
            self._vec = np.asarray(self._opt.step(self._vec, grad))
        else:
            self._vec = self._vec - grad
        self._version += 1
