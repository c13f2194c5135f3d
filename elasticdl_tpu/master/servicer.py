"""The master servicer: gRPC front-end + parameter server.

Re-design of the reference's `MasterServicer`
(elasticdl/python/master/servicer.py:21-423). The master holds the
model as a numpy pytree + version counter, serves tasks and model
pulls, and applies gradients:

- **sync mode** (the reference's core, servicer.py:169-229, 305-402):
  accept only gradients computed at the current version (optionally
  within a staleness window — see below), accumulate, and on the
  `grads_to_wait`-th report average dense grads, sparse-apply embedding
  grads, run the optimizer, bump the version, and fire eval/checkpoint
  hooks. `grads_to_wait` counts *reports*, not workers, so membership
  churn never stalls a step.
- **async mode** (designed but never landed in the reference,
  doc/async_sgd_design.md:44-82): apply each report immediately,
  optionally modulating the effective LR by 1/staleness.

TPU-first deltas from the reference: gradients arrive *pre-reduced
per host* (each gRPC worker is a TPU-VM host that already all-reduced
over its local chips via shard_map — SURVEY §5.8), may be bf16 on the
wire, and a `staleness_window > 0` relaxes strict version equality so
churn-induced retry storms don't sink throughput (SURVEY §7.3 item 2).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Dict, Optional

import jax
import numpy as np

from elasticdl_tpu.common import codec
from elasticdl_tpu.common.codec import IndexedRows, merge_indexed_rows
from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.common.messages import MethodType, Task, TaskType
from elasticdl_tpu.common.timing import PhaseTimers
from elasticdl_tpu.master.embedding_store import EmbeddingStore
from elasticdl_tpu.master.ps_optimizer import PSOptimizer
from elasticdl_tpu.master.sparse_optimizer import SparseOptimizer
from elasticdl_tpu.obs import trace as obs_trace
from elasticdl_tpu.rpc.transport import FrameMemory

logger = get_logger(__name__)


def _is_shard_outage_exc(exc) -> bool:
    """Walk the cause chain looking for a shard-outage signature
    (rpc/fencing.is_shard_outage) — store wrappers re-raise RPC errors
    under their own types, so the grpc error may sit a few links deep."""
    from elasticdl_tpu.rpc.fencing import is_shard_outage

    hops = 0
    while exc is not None and hops < 8:
        if is_shard_outage(exc):
            return True
        exc = exc.__cause__ or exc.__context__
        hops += 1
    return False


def _own_f32(tree):
    """The tree as the master adopts it: every leaf an array of its own
    (writeable, C-contiguous; float leaves as float32), never the
    caller's array or a view of a received frame, because
    `report_local_update` adds into these leaves in place."""

    def own(a):
        a = np.asarray(a)
        floating = np.issubdtype(a.dtype, np.floating)
        return np.array(a, dtype=np.float32 if floating else None, order="C")

    return jax.tree_util.tree_map(own, tree)


def _add_delta(params, delta, scale: float):
    """`p + scale * d` for every leaf, bit for bit (a float32 product,
    then a float32 add; 1.0 * d is d), written into `p` where `p` is a
    writeable float32 array. Any other leaf (read-only out of
    `PSOptimizer.step`, not float32) is replaced by the freshly
    allocated sum, and is added into from the next delta on. `delta` is
    only read: it may be a view of a received frame. Returns the tree,
    how many of its leaves were added in place, and how many it has."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    in_place = 0
    steps = jax.tree_util.tree_leaves(delta)
    for i, (p, d) in enumerate(zip(leaves, steps, strict=True)):
        step = d if scale == 1.0 else scale * d
        if (
            isinstance(p, np.ndarray)
            and p.dtype == np.float32
            and p.flags.writeable
        ):
            np.add(p, step, out=p)
            in_place += 1
        else:
            leaves[i] = np.asarray(p + step)
    return treedef.unflatten(leaves), in_place, len(leaves)


class MasterServicer:
    def __init__(
        self,
        grads_to_wait: int,
        optimizer: Optional[PSOptimizer] = None,
        task_dispatcher=None,
        evaluation_service=None,
        checkpoint_service=None,
        embedding_store: Optional[EmbeddingStore] = None,
        sparse_optimizer: Optional[SparseOptimizer] = None,
        init_params: Any = None,
        init_aux: Any = None,
        init_version: int = 0,
        use_async: bool = False,
        lr_staleness_modulation: bool = False,
        staleness_window: int = 0,
        ps_group=None,
        kv_group=None,
        agg_group=None,
    ):
        # Sharded PS (master/ps_group.py): the dense model lives behind
        # N shard endpoints and workers push slices there directly; the
        # master keeps the TEMPLATE tree (structure/shapes for
        # assembly), the control plane, and the cadence mirror driven
        # by ReportWindowMeta. None = classic single-PS-in-master.
        # Public alias: main/tests tear the group down through the
        # servicer, like tb_service.
        self._ps_group = self.ps_group = ps_group
        # Scale-out embedding service (master/kv_group.py): the tables
        # live behind N KV shard endpoints; `embedding_store` is then a
        # ShardedEmbeddingStore client over them, and workers discover
        # the endpoints via GetPSConfig to hit the shards directly.
        self._kv_group = self.kv_group = kv_group
        # Aggregation tree (agg/): host-local presum aggregators ahead
        # of the PS shards; workers discover their aggregator via
        # GetPSConfig (worker_id % len(agg_endpoints)) and fall back to
        # direct shard pushes when the list is empty.
        self._agg_group = self.agg_group = agg_group
        self._lock = threading.Lock()
        # the master's own phases (GetSchedStats.phases.master, and as
        # spans in the process's recorder): `apply_wait` (handler entry
        # to lock held), `grad_decode` (the update's wire form to an f32
        # tree, validated), `apply` (delta add or PSOptimizer step;
        # `kind: accumulate` where a report only joined the sum),
        # `model_encode` (the model laid out for the way down: see
        # `_flat_model`), and the dispatcher's `rpc.decode` /
        # `rpc.encode`. All are timed under the model lock and recorded
        # after it is released.
        self.timers = PhaseTimers(sink=obs_trace.record_phase)
        # where `_flat_model` copies the leaves it may not send by view:
        # one buffer, lent to a response and back when its frame has left
        self._model_memory = FrameMemory()
        # Sparse applies serialize among THEMSELVES (read-modify-write
        # per id) but run OUTSIDE self._lock: with a KV-shard-backed
        # store every apply is several RPC fan-outs, and holding the
        # global lock across them would serialize the whole control
        # plane behind network round-trips. Each handler applies before
        # returning, so a worker still reads its own writes.
        self._sparse_lock = threading.Lock()
        self._grads_to_wait = grads_to_wait
        self._opt = optimizer
        self._task_d = task_dispatcher
        self._evaluation_service = evaluation_service
        self._checkpoint_service = checkpoint_service
        self._embedding_store = embedding_store
        self._sparse_opt = sparse_optimizer
        self._use_async = use_async
        self._lr_staleness_modulation = lr_staleness_modulation
        self._staleness_window = staleness_window

        self._params = _own_f32(init_params) if init_params is not None else None
        # non-trainable collections (e.g. batch_stats) — restored from a
        # checkpoint alongside init_params, or lazily set by the first
        # worker's ReportVariable
        self._aux = init_aux
        self._version = init_version
        self._grad_sum: Any = None
        self._pending_aux: Any = None
        self._grad_n = 0
        self._edl_grads: Dict[str, list] = {}
        # sharded mode: per-PS-shard elementwise-MAX of every version
        # vector reported via ReportWindowMeta — the recovery plane's
        # restore fence (the highest version each shard ever acked; any
        # acked apply is covered by some worker snapshot at >= it)
        self._shard_version_max: Optional[list] = None
        self._recovery_plane = None
        # model-pull hot path: the unravel plan (shapes/sizes/treedef
        # of self._params) is derived once and reused — see
        # codec.make_unraveler. Rebuilt lazily if the template ever
        # changes size (checkpoint restore of a different model).
        self._unraveler = None
        # ReportLocalUpdate dedup ring (mirrors ps_shard's): keyed
        # window pushes from a speculated task's primary/backup pair —
        # or a retry resend — are absorbed, never double-applied.
        # Guarded by self._lock; bounded FIFO eviction.
        self._seen_local_updates: "OrderedDict[str, bool]" = OrderedDict()
        self._local_update_dedup_cap = 1024
        self._duplicate_local_updates = 0
        # exactness evidence (chaos/scenario.py probes): optimizer
        # steps actually APPLIED to this master's model. The invariant
        # `version == init_version + applied_update_steps` holds at
        # any instant under self._lock; a duplicate absorbed by the
        # dedup ring advances neither. The probe asserts the invariant
        # continuously and the exact fault-free version at job end —
        # together they pin "every update applied exactly once".
        self._init_version = init_version
        self._applied_update_steps = 0
        # migration plane (master/migration.py): the master's OWN
        # fencing word. Bumped when an adopting master takes over
        # (cutover = shard refence at gen+1 + this bump); workers read
        # it from GetPSConfig and treat a higher value as "a new master
        # owns the job" during candidate probing. Distinct from the
        # per-shard generations — those fence shard relaunches, this
        # fences master hand-offs.
        self._master_generation = 0
        # adoption keeps get_ps_config's n_params honest before the
        # template tree is lazily re-established (the manifest carries
        # the scalar, never the tensors)
        self._n_params_hint = -1

    # -- handler table (the 6 reference RPCs + embedding plane) -------------

    def handlers(self) -> Dict[str, Any]:
        return {
            "GetTask": self.get_task,
            "GetModel": self.get_model,
            "ReportVariable": self.report_variable,
            "ReportGradient": self.report_gradient,
            "ReportLocalUpdate": self.report_local_update,
            "ReportEvaluationMetrics": self.report_evaluation_metrics,
            "ReportTaskResult": self.report_task_result,
            "EmbeddingLookup": self.embedding_lookup,
            "EmbeddingUpdate": self.embedding_update,
            "GetPSConfig": self.get_ps_config,
            "ReportWindowMeta": self.report_window_meta,
            "GetAux": self.get_aux,
            "GetSampleBatch": self.get_sample_batch,
            "PSRestoreFromWorker": self.ps_restore_from_worker,
            "ReportPhaseStats": self.report_phase_stats,
            "GetSchedStats": self.get_sched_stats,
            "GetTrace": self.get_trace,
            "GetMetrics": self.get_metrics,
            "GetJobManifest": self.get_job_manifest,
            "BeginHandoff": self.begin_handoff,
        }

    # -- migration plane (master/migration.py) -------------------------------

    def set_job_manifest_fn(self, fn):
        """fn() -> manifest dict; wired by master main / the chaos
        runner to migration.build_job_manifest over this servicer, its
        dispatcher and the worker manager. Until wired, GetJobManifest
        answers {"manifest": None} — a standby treats that the same as
        an unreachable primary and keeps its last cached manifest."""
        self._job_manifest_fn = fn

    def get_job_manifest(self, req: dict) -> dict:
        """The continuously publishable job manifest — everything an
        adopting master needs short of the model tensors (those live on
        the PS/KV shards and are restored through the recovery plane's
        worker-upload/mirror paths, never through this RPC)."""
        fn = getattr(self, "_job_manifest_fn", None)
        return {"manifest": fn() if fn is not None else None}

    def begin_handoff(self, req: dict) -> dict:
        """Planned-migration drain latch: pause the dispatcher (workers
        WAIT at task boundaries, in-flight reports keep landing) and
        report whether the doing-map has drained. Latch-idempotent —
        the standby polls this until quiesced, then adopts from the
        final manifest."""
        if self._task_d is None:
            return {"paused": False, "quiesced": True}
        reason = req.get("reason") or ""
        if reason:
            logger.info("BeginHandoff: draining for hand-off (%s)", reason)
        self._task_d.pause()
        return {"paused": True, "quiesced": self._task_d.is_quiesced()}

    @property
    def master_generation(self) -> int:
        with self._lock:
            return self._master_generation

    def set_master_generation(self, generation: int):
        with self._lock:
            self._master_generation = max(
                self._master_generation, int(generation)
            )

    def export_model_state(self) -> dict:
        """The servicer's portable control-plane state for the job
        manifest — version lineage, the per-shard restore floors, and
        the local-update dedup ring keys. One lock acquisition, so the
        exactness invariant (version == init + applied) holds inside
        the snapshot. Deliberately NO tensors: params/aux templates are
        re-established lazily (ReportVariable / first report's
        aux_state) and the authoritative values live on the shards."""
        with self._lock:
            n = (
                sum(
                    int(np.asarray(leaf).size)
                    for leaf in jax.tree_util.tree_leaves(self._params)
                )
                if self._params is not None
                else self._n_params_hint
            )
            vm = self._shard_version_max
            return {
                "version": self._version,
                "init_version": self._init_version,
                "applied_update_steps": self._applied_update_steps,
                "shard_version_max": list(vm) if vm is not None else None,
                "seen_local_updates": list(self._seen_local_updates),
                "duplicate_local_updates": self._duplicate_local_updates,
                "n_params": n,
            }

    def restore_model_state(self, state: dict):
        """Adopt an exported model-control state. Restoring
        `shard_version_max` is what keeps shard_version_floor correct
        for the NEW master's recovery plane — a shard that died
        together with the old master must still be restored to the
        floor the old master had mirrored, or the resume silently
        loses acked steps."""
        with self._lock:
            self._version = int(state["version"])
            self._init_version = int(state["init_version"])
            self._applied_update_steps = int(state["applied_update_steps"])
            vm = state.get("shard_version_max")
            self._shard_version_max = (
                [int(v) for v in vm] if vm is not None else None
            )
            self._seen_local_updates = OrderedDict(
                (k, True) for k in state.get("seen_local_updates") or ()
            )
            self._duplicate_local_updates = int(
                state.get("duplicate_local_updates", 0)
            )
            self._n_params_hint = int(state.get("n_params", -1))

    # -- observability plane (elasticdl_tpu/obs/) ----------------------------

    def get_trace(self, req: dict) -> dict:
        """The master process's SpanRecorder contents (obs/trace.py).
        Merge with per-shard GetTrace snapshots via
        trace.chrome_trace_from_spans — wall-clock timestamps align
        processes on one Perfetto timeline."""
        from elasticdl_tpu.obs import trace as obs_trace

        return {
            "spans": obs_trace.RECORDER.snapshot(),
            "dropped": obs_trace.RECORDER.dropped,
        }

    def get_metrics(self, req: dict) -> dict:
        """Fleet metrics surface: the master's own MetricsRegistry
        snapshot (which already includes inproc shard collectors) plus
        one best-effort GetMetrics poll of every out-of-process PS/KV
        shard, keyed ps<i>/kv<i>."""
        from elasticdl_tpu.obs import metrics as obs_metrics

        shards = {}
        if self._ps_group is not None:
            shards.update(self._ps_group.collect_shard_metrics())
        if self._kv_group is not None:
            shards.update(self._kv_group.collect_shard_metrics())
        return {
            "metrics": obs_metrics.get_registry().snapshot(),
            "shards": shards,
        }

    def set_standby_fn(self, fn):
        """fn(worker_id) -> bool; wired to WorkerManager.is_standby."""
        self._standby_fn = fn

    def set_sample_batch_fn(self, fn):
        """fn(n) -> list[bytes]; serves raw records for standby
        pre-warming (the master already reads the shards to count
        records, so it has data access by construction)."""
        self._sample_batch_fn = fn

    def get_sample_batch(self, req: dict) -> dict:
        fn = getattr(self, "_sample_batch_fn", None)
        if fn is None:
            return {"records": None}
        return {"records": fn(int(req.get("n", 1)))}

    # -- policy plane (elasticdl_tpu/sched/) --------------------------------

    def set_phase_stats_sink(self, fn):
        """fn(worker_id, phases, device); wired to
        sched.PhaseStatsAggregator.ingest — the autoscaler's telemetry
        feed. Without a sink, ReportPhaseStats is a no-op ack."""
        self._phase_stats_sink = fn

    def set_sched_stats_fn(self, fn):
        """fn() -> dict of policy-plane stats (autoscaler / arbiter /
        speculation / fleet counters), composed by master main."""
        self._sched_stats_fn = fn

    def set_admission_stats_fn(self, fn):
        """fn() -> per-method-class admission-queue snapshot or None;
        wired to RpcServer.admission_stats."""
        self._admission_stats_fn = fn

    def report_phase_stats(self, req: dict) -> dict:
        """Cumulative PhaseTimers snapshot from one worker.
        Last-write-wins per worker — resends and reordering are
        harmless, which is what makes this RPC idempotent."""
        sink = getattr(self, "_phase_stats_sink", None)
        if sink is not None:
            sink(
                int(req.get("worker_id", -1)),
                req.get("phases"),
                req.get("device"),
            )
        return {}

    def get_sched_stats(self, req: dict) -> dict:
        """The policy-plane stats surface (sched.fetch_sched_stats)."""
        fn = getattr(self, "_sched_stats_fn", None)
        out = dict(fn() or {}) if fn is not None else {}
        adm = getattr(self, "_admission_stats_fn", None)
        out["admission"] = adm() if adm is not None else None
        with self._lock:
            out["duplicate_local_updates"] = self._duplicate_local_updates
            # one lock acquisition = a mutually consistent exactness
            # snapshot: the scenario probes (chaos/scenario.py) assert
            # version == init + applied_update_steps at every poll
            out["exactness"] = {
                "version": self._version,
                "init_version": self._init_version,
                "applied_update_steps": self._applied_update_steps,
                "duplicate_local_updates": self._duplicate_local_updates,
            }
        return out

    # -- model state --------------------------------------------------------

    @property
    def version(self) -> int:
        with self._lock:
            return self._version

    def model_initialized(self) -> bool:
        with self._lock:
            return self._params is not None

    def get_params_copy(self):
        if self._ps_group is not None and self.model_initialized():
            # assemble the authoritative values from the shards; the
            # master's tree is only the template. Slices are pulled
            # concurrently and may straddle a step (relaxed snapshot —
            # see ps_shard.py's consistency model); the reported
            # version is the lowest shard version in the snapshot.
            # During the lazy-init window (template set, shards not yet
            # seeded) the template IS the current model — serve it
            # rather than crashing a caller on an uninitialized group.
            vec = None
            if self._ps_group.initialized:
                versions, vec = self._ps_group.assemble()
            if vec is not None:
                with self._lock:
                    aux = jax.tree_util.tree_map(np.copy, self._aux)
                return (
                    self._unravel_model(vec),
                    aux,
                    min(versions),
                )
        with self._lock:
            return (
                jax.tree_util.tree_map(np.copy, self._params),
                jax.tree_util.tree_map(np.copy, self._aux),
                self._version,
            )

    # -- RPC: tasks ---------------------------------------------------------

    def get_task(self, req: dict) -> dict:
        """reference: servicer.py:98-115 — next shard or WAIT.

        Adds an explicit `finished` flag so workers exit cleanly instead
        of inferring job completion from an empty shard name. Standby
        workers (worker_manager.is_standby) are held in reserve: WAIT +
        standby=True, which tells them to pre-warm (pull model, AOT
        compile on a sample batch) so promotion costs nothing."""
        standby_fn = getattr(self, "_standby_fn", None)
        if standby_fn is not None and standby_fn(req["worker_id"]):
            finished = self._task_d.finished() if self._task_d else True
            if finished and self._evaluation_service is not None:
                finished = not self._evaluation_service.has_pending()
            return {
                "task": Task(type=TaskType.WAIT).to_wire(),
                "finished": finished,
                "standby": True,
            }
        task = self._task_d.get(req["worker_id"]) if self._task_d else None
        if task is None:
            finished = self._task_d.finished() if self._task_d else True
            # keep workers alive while an evaluation job is still pending:
            # its EVALUATION tasks may not have been enqueued yet
            if finished and self._evaluation_service is not None:
                finished = not self._evaluation_service.has_pending()
            resp = {
                "task": Task(type=TaskType.WAIT).to_wire(),
                "finished": finished,
            }
            if finished and self._task_d is not None:
                # a poison task was dropped: completion is partial; the
                # master exit path and workers must not report success
                resp["failed"] = self._task_d.has_failed_tasks()
            return resp
        return {"task": task.to_wire(), "finished": False}

    def report_task_result(self, req: dict) -> dict:
        """reference: servicer.py:408-414."""
        err = req.get("err_message", "")
        if err:
            logger.warning("Worker reported error: %s", err)
        self._task_d.report(
            req["task_id"], not err, worker_id=req.get("worker_id")
        )
        return {}

    # -- RPC: model ---------------------------------------------------------

    def get_model(self, req: dict) -> dict:
        """reference: servicer.py:117-139 — MINIMUM serves the latest
        under lock; FIXED serves an exact version from the evaluation
        snapshot store."""
        version = req.get("version", 0)
        method = req.get("method", MethodType.MINIMUM)
        if method == MethodType.MINIMUM and self._ps_group is not None:
            # sharded mode: workers normally pull slices straight from
            # the shards — this path serves worker BOOT (the template
            # tree must ride along once) and tree-form callers, so it
            # assembles unconditionally
            with self._lock:
                template = self._params
                aux = jax.tree_util.tree_map(np.copy, self._aux)
            if template is None or not self._ps_group.initialized:
                return {"version": -1, "params": None, "aux": None}
            versions, vec = self._ps_group.assemble()
            if vec is None:  # shards racing their SETNX init
                return {"version": -1, "params": None, "aux": None}
            v = min(versions)
            if req.get("flat"):
                return {"version": v, "params_flat": vec, "aux": aux}
            return {
                "version": v,
                "params": self._unravel_model(vec),
                "aux": aux,
            }
        if method == MethodType.MINIMUM:
            t_enter = time.time()
            sent = {}
            with self._lock:
                t_locked = time.time()
                if self._params is None:
                    resp = {"version": -1, "params": None, "aux": None}
                elif req.get("only_if_newer") and self._version <= version:
                    # Bandwidth saver over the reference's always-full
                    # model pulls (servicer.py:282-287): the worker
                    # already holds this version.
                    resp = {"version": self._version, "params": None, "aux": None}
                elif req.get("flat"):
                    # one vector on the wire (see codec.ravel_np)
                    vec, sent = self._flat_model()
                    resp = {
                        "version": self._version,
                        "params_flat": vec,
                        "aux": jax.tree_util.tree_map(np.copy, self._aux),
                    }
                else:
                    resp = {
                        "version": self._version,
                        "params": jax.tree_util.tree_map(np.copy, self._params),
                        "aux": jax.tree_util.tree_map(np.copy, self._aux),
                    }
                t_done = time.time()
            # the wait for the model lock and the model copied out under
            # it (µs where none goes), recorded after its release
            version = resp["version"]
            self.timers.record(
                "apply_wait", t_enter, t_locked, kind="get_model",
                version=version,
            )
            self.timers.record(
                "model_encode", t_locked, t_done, kind="get_model",
                version=version, **sent,
            )
            return resp
        # FIXED: serve the exact version — from live PS state when it
        # still matches (standalone eval jobs never train past it),
        # else from the eval-snapshot store / durable checkpoints.
        # Sharded mode never live-serves: the master tree is only the
        # template; exact versions come from snapshots.
        with self._lock:
            if (
                self._ps_group is None
                and version == self._version
                and self._params is not None
            ):
                return {
                    "version": self._version,
                    "params": jax.tree_util.tree_map(np.copy, self._params),
                    "aux": jax.tree_util.tree_map(np.copy, self._aux),
                }
        if self._checkpoint_service is None:
            raise ValueError("FIXED model pull requires a checkpoint service")
        model = self._checkpoint_service.get_eval_model(version)
        if model is None:
            model = self._checkpoint_service.load_version(version)
        if model is None:
            raise ValueError(f"no snapshot for model version {version}")
        return {"version": model.version, "params": model.params, "aux": model.aux}

    def report_variable(self, req: dict) -> dict:
        """Lazy model init from the first worker
        (reference: servicer.py:299-303). In sharded mode the master
        keeps the tree as the assembly template and seeds the shards
        (their SETNX makes racing initializers harmless)."""
        seed_flat = None
        with self._lock:
            first = self._params is None
            if first:
                self._params = _own_f32(req["params"])
                if req.get("aux") is not None:
                    self._aux = req["aux"]
                if self._ps_group is not None:
                    seed_flat = codec.ravel_np(self._params)
            seed_version = self._version
        if seed_flat is not None:
            self._ps_group.ensure_init(seed_flat, seed_version)
        return {}

    # -- RPC: gradients (the hot path) --------------------------------------

    def report_gradient(self, req: dict) -> dict:  # edl-lint: disable=exactness-lineage -- single-PS legacy path: a failed report rides the task-requeue ladder (the whole minibatch recomputes at a fresh version), never an RPC-level resend of the same payload, so per-report dedup keys don't apply
        """reference: servicer.py:305-402. Returns {accepted, version}."""
        if self._ps_group is not None:
            raise ValueError(
                "sharded PS: gradients go to the shard endpoints "
                "(PSPushGrad), not the master"
            )
        report_version = req.get("version", -1)
        grads = req.get("gradient")
        edl_grads: Dict[str, IndexedRows] = req.get("edl_gradient") or {}
        aux_state = req.get("aux_state")

        applied = False
        applied_version = -1
        ckpt_snapshot = None
        sparse_to_apply = None
        t_enter = time.time()
        with self._lock:
            t_locked = time.time()
            if self._params is None:
                raise ValueError("gradient reported before model init")
            if grads is None and req.get("gradient_flat") is not None:
                # delta_to_f32: the flat gradient may arrive bf16 or
                # int8-quantized (codec.QuantizedDelta) from the
                # worker's EF plane; decode before unraveling
                grads = self._unravel_model(
                    codec.delta_to_f32(req["gradient_flat"])
                )
            staleness = self._version - report_version
            if not self._use_async and staleness > self._staleness_window:
                # stale: reject AND piggyback the fresh model so the
                # worker's retry needs no separate pull round-trip
                resp = {"accepted": False, "version": self._version}
                if req.get("return_model"):
                    resp["params_flat"], _ = self._flat_model(
                        req.get("model_dtype")
                    )
                    resp["aux"] = jax.tree_util.tree_map(np.copy, self._aux)
                return resp
            if report_version > self._version:
                raise ValueError(
                    f"future gradient version {report_version} > {self._version}"
                )
            self._validate(grads)
            t_decoded = time.time()

            if self._use_async:
                scale = 1.0
                if self._lr_staleness_modulation and staleness > 1:
                    # doc/async_sgd_design.md:75-82
                    scale = 1.0 / float(staleness)
                self._apply(grads, dense_scale=scale, aux_state=aux_state)
                applied = True
                sparse_to_apply = edl_grads
            else:
                # sync accumulate
                if self._grad_sum is None:
                    self._grad_sum = jax.tree_util.tree_map(
                        lambda g: np.asarray(g, dtype=np.float32).copy(), grads
                    )
                else:
                    self._grad_sum = jax.tree_util.tree_map(
                        lambda s, g: s + np.asarray(g, dtype=np.float32),
                        self._grad_sum,
                        grads,
                    )
                for layer, ir in edl_grads.items():
                    self._edl_grads.setdefault(layer, []).append(ir)
                if aux_state is not None:
                    self._pending_aux = aux_state
                self._grad_n += 1
                if self._grad_n >= self._grads_to_wait:
                    n = float(self._grad_n)
                    avg = jax.tree_util.tree_map(
                        lambda s: s / n, self._grad_sum
                    )
                    merged = {
                        layer: merge_indexed_rows(irs)
                        for layer, irs in self._edl_grads.items()
                    }
                    # clear BEFORE apply: a failed apply raises to the
                    # reporter (which retries its batch), and leftover
                    # accumulators would double-count on that retry
                    aux_pending = self._pending_aux
                    self._pending_aux = None
                    self._grad_sum = None
                    self._grad_n = 0
                    self._edl_grads = {}
                    self._apply(avg, aux_state=aux_pending)
                    applied = True
                    sparse_to_apply = merged
            resp = {"accepted": True, "version": self._version}
            t_applied = time.time()
            sent = None
            if req.get("return_model") and self._version != report_version:
                # a step was applied (by this report or a concurrent
                # one): hand back the new model inline — the sync-SGD
                # inner loop becomes ONE rpc per minibatch
                resp["params_flat"], sent = self._flat_model(
                    req.get("model_dtype")
                )
                resp["aux"] = jax.tree_util.tree_map(np.copy, self._aux)
            marks = (
                "gradient" if applied else "accumulate",
                t_enter, t_locked, t_decoded, t_applied, time.time(),
                resp["version"], sent,
            )
            if applied:
                # snapshot the exact applied version UNDER the lock so a
                # concurrent report can't skip a checkpoint/eval trigger;
                # params are copied only when this version checkpoints
                applied_version = self._version
                if self._checkpoint_service and self._checkpoint_service.crossed(
                    applied_version - 1, applied_version
                ):
                    ckpt_snapshot = (
                        jax.tree_util.tree_map(np.copy, self._params),
                        jax.tree_util.tree_map(np.copy, self._aux),
                        self._opt_state_snapshot(),
                    )
        self._record_update(*marks)
        self._apply_sparse(sparse_to_apply)
        if applied:
            # hooks run OUTSIDE the lock: the eval service calls back
            # into get_params_copy and must not deadlock
            self._on_version_bump(applied_version, ckpt_snapshot, applied_version - 1)
            self._report_train_loss(applied_version, req.get("loss"))
        return resp

    def report_local_update(self, req: dict) -> dict:
        """SSP / local-update mode: the worker ran `steps` optimizer
        updates ON DEVICE (the reference designed but never landed this
        — doc/async_sgd_design.md:84-103, `get_model_frequency`) and
        ships one cumulative parameter DELTA. The PS adds the delta,
        advances the version by `steps`, and hands back the merged
        model when the worker's base has fallen behind (another worker
        synced in between).

        For a single worker this is mathematically identical to
        per-step sync SGD — the delta is exactly the sum of its local
        updates — while moving the model over the wire once per window
        instead of twice per minibatch.

        The model is updated IN PLACE (`_add_delta`): the delta is
        added into the leaves the master holds, and nothing the size of
        the model is allocated per sync. So every reader of
        `self._params` must copy what it hands out while it holds
        `self._lock`; a reference to a leaf kept across the lock is a
        torn read. The request's buffer is only read."""
        if self._ps_group is not None:
            raise ValueError(
                "sharded PS: deltas go to the shard endpoints "
                "(PSPushDelta), not the master"
            )
        steps = int(req["steps"])
        base_version = int(req["base_version"])
        aux_state = req.get("aux_state")
        report_key = req.get("report_key") or ""
        applied_version = -1
        ckpt_snapshot = None
        t_apply = time.time()
        with self._lock:
            t_locked = time.time()
            if self._params is None:
                raise ValueError("local update reported before model init")
            if report_key and report_key in self._seen_local_updates:
                # duplicate: a retry resend, or a speculated task's twin
                # pushing the same deterministic window key. Absorb it
                # and hand back the merged model so the absorbed pusher
                # rebases through the normal merged-back path.
                self._duplicate_local_updates += 1
                return {
                    "version": self._version,
                    "params_flat": self._flat_model(req.get("model_dtype"))[0],
                    "aux": jax.tree_util.tree_map(np.copy, self._aux),
                    "duplicate": True,
                }
            prev_version = self._version
            # Staleness policy: with `staleness_window > 0`, a delta
            # whose base fell more than the window behind is
            # down-weighted by window/staleness instead of applied at
            # full weight (a worker that slept through many syncs must
            # not drag the model back toward its stale base). Note the
            # semantics differ from the sync path by necessity: there
            # the window relaxes *rejection* and `lr_staleness_modulation`
            # separately opts into down-weighting; deltas have no
            # reject-and-retry protocol, so here the window alone
            # enables down-weighting and nothing is ever rejected.
            scale = 1.0
            if self._staleness_window:
                staleness = self._version - base_version
                if staleness > self._staleness_window:
                    scale = self._staleness_window / float(staleness)
            # decode the worker's wire form first: dense f32 is a
            # pass-through view; bf16 / int8 / top-k (QuantizedDelta /
            # SparseDelta) decode to the dense f32 vector here
            delta = self._unravel_model(codec.delta_to_f32(req["delta_flat"]))
            t_decoded = time.time()
            self._params, in_place, leaves = _add_delta(
                self._params, delta, scale
            )
            if aux_state is not None:
                self._aux = aux_state
            self._version += steps
            self._applied_update_steps += steps
            applied_version = self._version
            if self._checkpoint_service and self._checkpoint_service.crossed(
                prev_version, self._version
            ):
                ckpt_snapshot = (
                    jax.tree_util.tree_map(np.copy, self._params),
                    jax.tree_util.tree_map(np.copy, self._aux),
                    self._opt_state_snapshot(),
                )
            if report_key:
                # key registered only after the mutation succeeded,
                # same discipline as ps_shard._record_applied
                self._seen_local_updates[report_key] = True
                while (
                    len(self._seen_local_updates)
                    > self._local_update_dedup_cap
                ):
                    self._seen_local_updates.popitem(last=False)
            resp = {"version": self._version}
            t_applied = time.time()
            sent = None
            # base fell behind (concurrent syncs): return the merged model
            if base_version + steps != self._version or req.get("want_model"):
                resp["params_flat"], sent = self._flat_model(
                    req.get("model_dtype")
                )
                resp["aux"] = jax.tree_util.tree_map(np.copy, self._aux)
            marks = (
                "local_update", t_apply, t_locked, t_decoded, t_applied,
                time.time(), resp["version"], sent,
            )
        self._record_update(*marks, in_place=in_place, leaves=leaves)
        # lock wait + apply, retro-recorded under the server span (the
        # duplicate early-return above deliberately skips it)
        obs_trace.record_event(
            "master.apply",
            t_apply,
            time.time(),
            cat="ps",
            args={"kind": "local_update"},
        )
        # the window's accumulated BET gradients: applied at full
        # weight like the per-step path (the slot state, not an LR
        # damper, governs sparse staleness); outside the lock — see
        # _apply_sparse
        self._apply_sparse(req.get("edl_gradient") or {})
        self._on_version_bump(applied_version, ckpt_snapshot, prev_version)
        self._report_train_loss(applied_version, req.get("loss"))
        return resp

    def get_ps_config(self, req: dict) -> dict:
        """Shard-endpoint discovery for (re)joining workers — a
        relaunched worker must not depend on argv staying current.
        Covers BOTH planes: dense PS shards and embedding KV shards.
        Also the recovery plane's worker-facing status word: the
        ``recovering`` sets tell a worker which shards are fenced (so
        it should offer its restore snapshot via PSRestoreFromWorker
        and hold off re-resolving until the sets clear), and the
        generation lists let it stamp correct fencing epochs after a
        relaunch."""
        kv = self._kv_group.endpoints if self._kv_group is not None else []
        kv_gens = (
            list(self._kv_group.generations)
            if self._kv_group is not None
            else []
        )
        agg = self._agg_group.endpoints if self._agg_group is not None else []
        agg_gens = (
            list(self._agg_group.generations)
            if self._agg_group is not None
            else []
        )
        plane = self._recovery_plane
        recovering = (
            plane.status()
            if plane is not None
            else {"ps": [], "kv": [], "agg": []}
        )
        if self._ps_group is None:
            return {
                "endpoints": [],
                "n_params": -1,
                "kv_endpoints": kv,
                "ps_generations": [],
                "kv_generations": kv_gens,
                "agg_endpoints": agg,
                "agg_generations": agg_gens,
                "recovering": recovering,
                "master_generation": self.master_generation,
            }
        with self._lock:
            n = (
                sum(
                    int(np.asarray(leaf).size)
                    for leaf in jax.tree_util.tree_leaves(self._params)
                )
                if self._params is not None
                # adoption window: template not yet re-established but
                # the manifest told us the true size
                else self._n_params_hint
            )
            master_generation = self._master_generation
        return {
            "endpoints": self._ps_group.endpoints,
            "n_params": n,
            "kv_endpoints": kv,
            "ps_generations": list(self._ps_group.generations),
            "kv_generations": kv_gens,
            "agg_endpoints": agg,
            "agg_generations": agg_gens,
            "recovering": recovering,
            "master_generation": master_generation,
        }

    # -- recovery plane ------------------------------------------------------

    def set_recovery_plane(self, plane):
        """Attach the RecoveryPlane (master/recovery.py): GetPSConfig
        starts advertising its fenced-shard status and
        PSRestoreFromWorker uploads route to it."""
        self._recovery_plane = plane

    def shard_version_floor(self, shard_id: int) -> int:
        """Highest version this PS shard was ever reported to have
        acked — the recovery plane's restore fence. -1 before any
        report (restore-from-anything is then acceptable)."""
        with self._lock:
            vm = self._shard_version_max
            i = int(shard_id)
            if vm is None or i >= len(vm):
                return -1
            return vm[i]

    def ps_restore_from_worker(self, req: dict) -> dict:
        """A worker's restore snapshot slice for a fenced PS shard.
        Idempotent: the plane keeps only the highest-version candidate
        per shard, so resends are absorbed. `accepted` is False when
        the shard is not recovering (late upload) or no plane is
        attached — the worker just drops its snapshot."""
        plane = self._recovery_plane
        if plane is None:
            return {"accepted": False}
        return {
            "accepted": plane.offer_upload(
                int(req.get("worker_id", -1)),
                int(req["shard_id"]),
                req["vec"],
                int(req["version"]),
            )
        }

    def get_aux(self, req: dict) -> dict:
        """Non-trainable state for sharded-mode pull refreshes: shards
        hold only the dense vector, so a worker re-syncing its params
        from them fetches the matching aux here (single-PS pulls carry
        aux inline — get_model)."""
        with self._lock:
            return {
                "aux": jax.tree_util.tree_map(np.copy, self._aux),
                "version": self._version,
            }

    def report_window_meta(self, req: dict) -> dict:  # edl-lint: disable=exactness-lineage -- metadata mirror of an already-dedup-keyed shard push: the version bump here is monotonic bookkeeping (max over shard reports), and a resend re-reports the same maximum — idempotent by construction, enforced where the state lives (shard-side dedup)
        """Sharded-mode control-plane report: after pushing slices to
        the shards, workers send the tiny metadata here — per-shard
        versions, window loss, non-trainable aux. This drives the
        master's version mirror, the checkpoint/eval cadence (which the
        single-PS path drives from its own version bumps), and the
        metrics sink. Aux is last-writer-wins, as in _apply."""
        versions = req.get("versions") or []
        version = min(int(v) for v in versions) if versions else -1
        resp = {}
        with self._lock:
            prev = self._version
            advanced = version > prev
            if advanced:
                self._version = version
                # the mirror advance IS applied update steps — they ran
                # on the shards, not here — so count them or the
                # exactness invariant (version == init + applied,
                # get_sched_stats) breaks in sharded-PS mode
                self._applied_update_steps += version - prev
            if versions:
                # per-shard max mirror: the recovery plane's restore
                # fence (shard_version_floor)
                vm = self._shard_version_max
                if vm is None or len(vm) != len(versions):
                    vm = self._shard_version_max = [-1] * len(versions)
                for i, v in enumerate(versions):
                    if int(v) > vm[i]:
                        vm[i] = int(v)
            if req.get("aux_state") is not None:
                self._aux = req["aux_state"]
            if req.get("want_aux"):
                # the pusher absorbed merged slices (its base fell
                # behind) and wants the matching non-trainable state —
                # mirrors the aux piggyback on report_local_update
                resp["aux"] = jax.tree_util.tree_map(np.copy, self._aux)
        # sharded-PS mode: dense slices rode the shards; the sparse
        # IndexedRows ride this control-plane report — applied outside
        # the lock (see _apply_sparse), and BEFORE the version-bump
        # hooks so a cadence checkpoint's embedding snapshot includes
        # this very report's rows
        self._apply_sparse(req.get("edl_gradient") or {})
        if advanced:
            ckpt_snapshot = None
            if self._checkpoint_service and self._checkpoint_service.crossed(
                prev, version
            ):
                # assembled AFTER the crossing report: a relaxed
                # snapshot at >= the crossing version (ps_shard.py).
                # Shard optimizer state rides along (same shape as
                # save_latest_checkpoint) — without it a resume from a
                # CADENCE checkpoint of a sharded job silently
                # cold-starts the optimizer moments (ADVICE r4)
                params, aux, v = self.get_params_copy()
                shard_states = self._ps_group.export_opt()
                opt_state = (
                    {"kind": "sharded", "shards": shard_states}
                    if shard_states is not None
                    else None
                )
                ckpt_snapshot = (params, aux, opt_state)
                version = max(version, v)
            self._on_version_bump(version, ckpt_snapshot, prev)
        # every applied report carries a real loss even when its min
        # shard version trails the mirror (other workers ran ahead) —
        # gating on `advanced` would undercount the metrics sink in
        # sharded mode relative to single-PS, which records every apply
        self._report_train_loss(max(version, prev), req.get("loss"))
        return resp

    def _record_update(
        self, kind, t_enter, t_locked, t_decoded, t_applied, t_encoded,
        version, sent, **apply_args
    ):
        """One update's phases from the marks taken under the model
        lock, recorded once it is released: the wait for the lock, the
        update decoded, the apply (`kind: accumulate` for a gradient
        that only joined the sum: no step was taken; of a local update,
        `apply_args` say how many `leaves` the model has and into how
        many the delta was added `in_place`), and the model laid out
        for the way down where one goes (`sent`: what `_flat_model`
        says of how)."""
        record = self.timers.record
        record("apply_wait", t_enter, t_locked, kind=kind, version=version)
        record("grad_decode", t_locked, t_decoded, kind=kind, version=version)
        record(
            "apply", t_decoded, t_applied, kind=kind, version=version,
            **apply_args,
        )
        if sent is not None:
            record(
                "model_encode", t_applied, t_encoded, kind=kind,
                version=version, **sent,
            )

    def _unravel_model(self, vec):  # edl-lint: disable=lock-discipline -- template read only: the param STRUCTURE is fixed for the life of a job (values are irrelevant to the unravel plan), and report callers already hold the non-reentrant self._lock
        """vec -> pytree against the current param template, through
        the cached unravel plan (structure is fixed for the life of a
        job; a size mismatch — different model restored — rebuilds)."""
        u = self._unraveler
        if u is None:
            u = self._unraveler = codec.make_unraveler(self._params)
        try:
            return u(vec)
        except ValueError:
            u = self._unraveler = codec.make_unraveler(self._params)
            return u(vec)

    def _flat_model(self, model_dtype=None):  # edl-lint: disable=lock-discipline -- caller holds self._lock
        """The model as the one float32 vector that goes down to a
        worker (`ravel_np`'s, tree_flatten order), never concatenated:
        a `codec.LeafVector` over the leaves, which the frame is built
        from and the socket gathers from where they lie. And how each
        leaf got there, which `model_encode` reports. What decides is
        what can be seen of the leaf:

        - read-only float32 (`PSOptimizer.step`'s: the next step
          replaces it and nothing ever writes it) goes `by_view`; the
          view keeps it alive, and the send, after the lock is
          released, cannot be torn;
        - any other (`_add_delta` writes it in place; not float32) is
          `copied` here, under the lock, into memory this servicer
          keeps and lends (`lent`: the copy went into pages an earlier
          response lay in): back when the response's last view dies,
          so a second response in flight gets memory of its own.

        A `model_dtype` other than float32 narrows the raveled vector
        (bf16 halves the piggyback bytes; the worker re-widens): a copy
        by nature."""
        leaves = jax.tree_util.tree_leaves(self._params)
        if model_dtype and model_dtype != "float32":
            vec = codec.ravel_np(self._params).astype(
                codec.dtype_from_str(model_dtype)
            )
            return vec, {"by_view": 0, "copied": len(leaves), "lent": False}
        stays = [
            isinstance(a, np.ndarray)
            and a.dtype == np.float32
            and a.flags.c_contiguous
            and not a.flags.writeable
            for a in leaves
        ]
        to_copy = sum(np.size(a) for a, stay in zip(leaves, stays) if not stay)
        view, _, lent = self._model_memory.lend(4 * to_copy)
        room = np.frombuffer(view, dtype=np.float32)
        pieces, at = [], 0
        for a, stay in zip(leaves, stays):
            if stay:
                pieces.append(a.reshape(-1))
                continue
            a = np.asarray(a)
            piece = room[at:at + a.size]
            np.copyto(piece, a.reshape(-1), casting="unsafe")
            pieces.append(piece)
            at += a.size
        by_view = sum(stays)
        return codec.LeafVector(pieces), {
            "by_view": by_view, "copied": len(leaves) - by_view, "lent": lent,
        }

    def _apply_sparse(self, edl_grads):  # edl-lint: disable=lock-discipline -- ride-through deliberately blocks: no sparse apply can proceed mid-recovery
        """Apply IndexedRows to the (possibly RPC-backed) store —
        callers invoke AFTER releasing self._lock, BEFORE returning.

        KV-outage ride-through: with a recovery plane armed, a shard
        death mid-apply must NOT fail the worker's report — the dense
        slices for this step already applied on the PS shards, so
        failing here would requeue the task and double-apply them. We
        block (under _sparse_lock — queueing later reports behind the
        outage is exactly right) until the plane finishes the KV
        recovery, then retry. The retried rows are read-modify-write
        over the restored (bounded-staleness) replica, which is the
        same staleness contract the mirror itself provides."""
        if not edl_grads or self._sparse_opt is None:
            return
        with self._sparse_lock:
            try:
                self._sparse_opt.apply_gradients(edl_grads)
                return
            except Exception as exc:
                if self._recovery_plane is None or not _is_shard_outage_exc(
                    exc
                ):
                    raise
                logger.warning(
                    "sparse apply hit a KV shard outage; riding through "
                    "recovery: %s",
                    exc,
                )
            deadline = time.monotonic() + 90.0
            while True:
                time.sleep(0.5)
                if self._recovery_plane.status().get("kv"):
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            "KV recovery did not complete within the "
                            "sparse-apply ride-through deadline"
                        )
                    continue
                try:
                    self._sparse_opt.apply_gradients(edl_grads)
                    return
                except Exception as exc:
                    if time.monotonic() > deadline or not _is_shard_outage_exc(
                        exc
                    ):
                        raise

    def _validate(self, grads):  # edl-lint: disable=lock-discipline -- caller holds self._lock
        """Shape sanity checks (reference: servicer.py:320-370)."""
        if grads is None:
            return
        flat_g, tree_g = jax.tree_util.tree_flatten(grads)
        flat_p, tree_p = jax.tree_util.tree_flatten(self._params)
        if tree_g != tree_p:
            raise ValueError("gradient pytree does not match model pytree")
        for g, p in zip(flat_g, flat_p):
            if np.asarray(g).shape != np.asarray(p).shape:
                raise ValueError(
                    f"gradient shape {np.asarray(g).shape} != param shape "
                    f"{np.asarray(p).shape}"
                )

    def _apply(self, dense_grads, dense_scale: float = 1.0, aux_state=None):  # edl-lint: disable=lock-discipline -- caller holds self._lock
        """DENSE optimizer step + version bump (caller holds the lock;
        reference: servicer.py:169-229, 398-402). Non-trainable state
        (BN moving stats) is last-writer-wins from the reporting hosts.
        Sparse grads go through _apply_sparse OUTSIDE the lock — never
        here (the RPC-backed store must not serialize the control
        plane, and _sparse_lock owns that serialization)."""
        if aux_state is not None:
            self._aux = aux_state
        if dense_grads is not None and self._opt is not None:
            if dense_scale != 1.0:
                dense_grads = jax.tree_util.tree_map(
                    lambda g: np.asarray(g, dtype=np.float32) * dense_scale,
                    dense_grads,
                )
            self._params = self._opt.step(self._params, dense_grads)
        self._version += 1
        self._applied_update_steps += 1

    def set_train_loss_hook(self, hook):
        """hook(version, loss) — fed from worker-reported minibatch/
        window losses; wired to the TensorBoard/metrics sink."""
        self._train_loss_hook = hook

    def _report_train_loss(self, version: int, loss):
        hook = getattr(self, "_train_loss_hook", None)
        if hook is not None and loss is not None:
            try:
                hook(version, float(loss))
            except Exception:  # edl-lint: disable=abort-discipline -- a metrics sink must never fail training; the hook call is the last statement, so nothing downstream depends on it
                logger.exception("train-loss hook failed")

    def _opt_state_snapshot(self):
        """Dense optimizer state for exact resume (taken under the
        lock with the matching params copy). None before the first
        apply or in sharded mode (shards own their slices' state —
        save_latest_checkpoint assembles those explicitly)."""
        if self._opt is None or not self._opt.initialized:
            return None
        return {"kind": "single", "leaves": self._opt.state_snapshot()}

    def _on_version_bump(self, version: int, ckpt_snapshot=None, prev_version=None):
        """Checkpoint/eval hooks for an applied version. Caller must NOT
        hold the lock (reference fires these inside its mutex,
        servicer.py:269-280; here the eval hook re-enters
        get_params_copy). `ckpt_snapshot` was taken under the lock at
        exactly `version`. Cadence checks are floor-crossing so
        multi-step bumps (local-update syncs) can't skip triggers."""
        if ckpt_snapshot is not None and self._checkpoint_service:
            params, aux, opt_state = ckpt_snapshot
            self._checkpoint_service.save(
                params, version, aux=aux, opt_state=opt_state
            )
        if self._evaluation_service:
            self._evaluation_service.add_evaluation_task_if_needed(
                version, prev_version
            )

    def set_evaluation_service(self, evaluation_service):
        """Late wiring: the eval service needs the servicer's model
        getter and the servicer needs the eval service's hooks."""
        self._evaluation_service = evaluation_service

    # -- RPC: evaluation -----------------------------------------------------

    def report_evaluation_metrics(self, req: dict) -> dict:
        """Per-minibatch metric report (reference: servicer.py evaluation
        path -> evaluation_service.py:28-46)."""
        if self._evaluation_service:
            self._evaluation_service.report_metrics(
                req.get("model_version", -1),
                req.get("metrics", {}),
                req.get("num_examples", 1),
            )
        return {}

    # -- RPC: embedding plane (replaces the Redis side channel) --------------

    def embedding_lookup(self, req: dict) -> dict:
        values, unknown = self._embedding_store.lookup(req["layer"], req["ids"])
        return {"values": values, "unknown_index": unknown}

    def embedding_update(self, req: dict) -> dict:
        self._embedding_store.update(
            req["layer"],
            req["ids"],
            req["values"],
            set_if_not_exist=req.get("set_if_not_exist", False),
        )
        return {}

    # -- checkpoint helpers (called from master main) ------------------------

    def save_latest_checkpoint(self, output_path: str):
        """reference: servicer.py:255-267. The final model carries the
        embedding tables too — without them a deepfm-style `--output`
        artifact would be unusable for serving/resume (the periodic
        CheckpointService snapshots them; the final save must match)."""
        from elasticdl_tpu.master.checkpoint import save_model_file

        emb = (
            self._embedding_store.snapshot()
            if self._embedding_store is not None
            else None
        )
        if self._ps_group is not None:
            params, aux, version = self.get_params_copy()
            shard_states = self._ps_group.export_opt()
            opt_state = (
                {"kind": "sharded", "shards": shard_states}
                if shard_states is not None
                else None
            )
            save_model_file(
                output_path,
                params,
                version,
                aux=aux,
                embeddings=emb,
                opt_state=opt_state,
            )
            return
        with self._lock:
            save_model_file(
                output_path,
                self._params,
                self._version,
                aux=self._aux,
                embeddings=emb,
                opt_state=self._opt_state_snapshot(),
            )
