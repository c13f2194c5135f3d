"""Wire messages for the master<->worker protocol.

Replaces the reference's protobuf contract
(elasticdl/proto/elasticdl.proto:7-120) with msgpack-serialized
dataclasses over the dtype-aware codec. The RPC surface is preserved:
GetTask, GetModel, ReportVariable, ReportGradient,
ReportEvaluationMetrics, ReportTaskResult (elasticdl.proto:113-120) —
plus the embedding-store RPCs that replace the reference's external
Redis side channel (elasticdl/python/master/embedding_service.py:270-357).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

from elasticdl_tpu.common import codec


class TaskType(object):
    """reference: elasticdl/proto/elasticdl.proto:7-12"""

    TRAINING = "training"
    EVALUATION = "evaluation"
    PREDICTION = "prediction"
    WAIT = "wait"


class MethodType(object):
    """Model-pull semantics (reference: elasticdl.proto:14-17).

    MINIMUM: any model with version >= requested. FIXED: exactly the
    requested version (served from a pinned evaluation snapshot).
    """

    MINIMUM = "minimum"
    FIXED = "fixed"


@dataclasses.dataclass
class Task:
    """A dynamic data shard: records [start, end) of one file
    (reference: elasticdl.proto:22-41)."""

    task_id: int = -1
    shard_file_name: str = ""
    start: int = 0
    end: int = 0
    type: str = TaskType.WAIT
    model_version: int = -1
    # speculation attempt key: identical for a primary and its backup
    # copy, fresh per requeue — workers derive per-window report_keys
    # from it so duplicate pushes from racing copies dedup server-side
    spec_key: str = ""
    backup: bool = False  # this copy IS the speculative backup

    def to_wire(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_wire(cls, d: dict) -> "Task":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


@dataclasses.dataclass
class Model:
    """Versioned parameter pytree (reference: elasticdl.proto:57-60,
    generalized from a flat name->Tensor map to a nested pytree).

    `aux` carries non-trainable collections (e.g. flax batch_stats);
    the reference's TF variables mix both, JAX separates them.
    """

    version: int = 0
    params: Any = None  # trainable pytree of np.ndarray
    aux: Any = None  # non-trainable state pytree (or None)

    def to_wire(self) -> dict:
        return {"version": self.version, "params": self.params, "aux": self.aux}

    @classmethod
    def from_wire(cls, d: dict) -> "Model":
        return cls(version=d["version"], params=d["params"], aux=d.get("aux"))


class _WireRequest:
    """Shared to_wire/from_wire for the request dataclasses below.

    from_wire ignores unknown keys on purpose: an old server must keep
    decoding requests from a newer client that added an optional field
    (the same forward-compatibility protobuf gives for free)."""

    def to_wire(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_wire(cls, d: dict):
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


@dataclasses.dataclass
class GetTaskRequest(_WireRequest):
    worker_id: int = -1


@dataclasses.dataclass
class GetModelRequest(_WireRequest):
    version: int = 0
    method: str = MethodType.MINIMUM
    flat: bool = False
    only_if_newer: bool = False
    model_dtype: Optional[str] = None


@dataclasses.dataclass
class GetAuxRequest(_WireRequest):
    pass


@dataclasses.dataclass
class GetPSConfigRequest(_WireRequest):
    pass


@dataclasses.dataclass
class GetSampleBatchRequest(_WireRequest):
    n: int = 1


@dataclasses.dataclass
class ReportVariableRequest(_WireRequest):
    params: Any = None
    aux: Any = None


@dataclasses.dataclass
class ReportGradientRequest(_WireRequest):
    worker_id: int = -1
    version: int = -1
    gradient: Any = None  # pytree of arrays (tree transport)
    gradient_flat: Any = None  # raveled vector (flat transport)
    edl_gradient: Any = None  # {layer: IndexedRows}
    aux_state: Any = None
    loss: Any = None
    return_model: bool = False
    model_dtype: Optional[str] = None


@dataclasses.dataclass
class ReportLocalUpdateRequest(_WireRequest):
    steps: int = 0
    base_version: int = -1
    delta_flat: Any = None
    edl_gradient: Any = None
    aux_state: Any = None
    loss: Any = None
    want_model: bool = False
    report_key: str = ""
    model_dtype: Optional[str] = None


@dataclasses.dataclass
class ReportEvaluationMetricsRequest(_WireRequest):
    model_version: int = -1
    metrics: Any = None
    num_examples: int = 1


@dataclasses.dataclass
class ReportTaskResultRequest(_WireRequest):
    task_id: int = -1
    err_message: str = ""
    worker_id: int = -1


@dataclasses.dataclass
class ReportWindowMetaRequest(_WireRequest):
    worker_id: int = -1
    versions: Any = None  # per-shard versions after the pushes
    steps: int = 0
    aux_state: Any = None
    edl_gradient: Any = None
    loss: Any = None
    want_aux: bool = False


@dataclasses.dataclass
class ReportPhaseStatsRequest(_WireRequest):
    """Cumulative PhaseTimers snapshot from one worker — the
    autoscaler's telemetry feed (sched/telemetry.py). Last-write-wins
    per worker, so resends are harmless."""

    worker_id: int = -1
    phases: Any = None  # {phase: {"seconds": float, "count": int}}
    device: Any = None  # {"platform", "device_kind", "chips"}: where they ran


@dataclasses.dataclass
class GetSchedStatsRequest(_WireRequest):
    """Policy-plane stats surface: autoscaler/arbiter/speculation
    counters plus the RPC admission-queue snapshot."""


@dataclasses.dataclass
class GetTraceRequest(_WireRequest):
    """Drain-free read of a process's SpanRecorder (obs/trace.py):
    the response carries recorder-shaped span dicts mergeable into one
    Perfetto timeline via chrome_trace_from_spans."""


@dataclasses.dataclass
class GetMetricsRequest(_WireRequest):
    """Read of a process's MetricsRegistry snapshot (obs/metrics.py);
    on the master the response also aggregates process-mode shard
    fleets."""


@dataclasses.dataclass
class EmbeddingLookupRequest(_WireRequest):
    layer: str = ""
    ids: Any = None


@dataclasses.dataclass
class EmbeddingUpdateRequest(_WireRequest):
    layer: str = ""
    ids: Any = None
    values: Any = None
    set_if_not_exist: bool = False


@dataclasses.dataclass
class PSInitRequest(_WireRequest):
    vec: Any = None
    version: int = 0
    epoch: int = -1  # fencing epoch; -1 = unfenced (see master/recovery.py)


@dataclasses.dataclass
class PSPullRequest(_WireRequest):
    only_if_newer: bool = False
    version: int = -1
    model_dtype: Optional[str] = None
    epoch: int = -1


@dataclasses.dataclass
class PSPushGradRequest(_WireRequest):
    grad: Any = None
    version: int = -1
    return_model: bool = False
    report_key: str = ""
    model_dtype: Optional[str] = None
    epoch: int = -1


@dataclasses.dataclass
class PSPushDeltaRequest(_WireRequest):
    delta: Any = None
    steps: int = 0
    base_version: int = -1
    want_model: bool = False
    report_key: str = ""
    model_dtype: Optional[str] = None
    epoch: int = -1


@dataclasses.dataclass
class PSPushDeltaBucketRequest(_WireRequest):
    """One layer-aligned bucket of a window delta (worker
    streaming push, worker._sync_local_updates). All buckets of one
    window share `report_key` (the dedup/lineage key); `offset`
    places this bucket's slice inside the SHARD's slice, and
    `bucket_index`/`num_buckets` let the shard detect the complete set
    — partial sets park (like fan-in's CombineBuffer) and the whole
    set applies atomically at the window boundary, so `version`
    advances by `steps` exactly once. A replay of an already-applied
    set dedups per bucket on `report_key`; a re-sent parked bucket
    overwrites its slot idempotently."""

    delta: Any = None
    steps: int = 0
    base_version: int = -1
    offset: int = 0
    bucket_index: int = 0
    num_buckets: int = 1
    want_model: bool = False
    report_key: str = ""
    model_dtype: Optional[str] = None
    epoch: int = -1


@dataclasses.dataclass
class PSPushDeltaCombinedRequest(_WireRequest):
    """One presummed cohort forwarded by an aggregator node (agg/):
    `delta` is the f32 presum of the member deltas, `steps` the member
    sum, and `report_keys` the member dedup keys — the shard applies
    the combined delta once and registers EVERY member key, so a member
    replaying direct after an aggregator crash still dedups exactly.
    A shard that cannot take the batch whole (staleness window active,
    any member already seen) answers accepted=False and the aggregator
    decomposes into serial per-member PSPushDelta forwards."""

    delta: Any = None
    steps: int = 0
    base_version: int = -1
    want_model: bool = False
    report_keys: Any = None  # list[str], one per member
    model_dtype: Optional[str] = None
    epoch: int = -1


@dataclasses.dataclass
class AggPushDeltaRequest(_WireRequest):
    """Worker->aggregator push: PSPushDelta plus the target PS shard
    and the PS shard's fencing epoch. `epoch` fences the AGGREGATOR's
    own generation (bumped on relaunch so a stale cohort from before a
    crash cannot land); `shard_epoch` rides upstream as the combined
    call's `epoch` so PS fencing is unchanged."""

    delta: Any = None
    steps: int = 0
    base_version: int = -1
    want_model: bool = False
    report_key: str = ""
    model_dtype: Optional[str] = None
    epoch: int = -1
    shard: int = -1
    shard_epoch: int = -1


@dataclasses.dataclass
class AggStatsRequest(_WireRequest):
    """Aggregator counters surface (cohorts, members, forwards,
    decompositions) — bench/tests read it like PS stats()."""


@dataclasses.dataclass
class AggUpdateUpstreamRequest(_WireRequest):
    """Master->aggregator re-point after a PS relaunch: the new PS
    endpoint list (index = shard id). The aggregator rebuilds its
    upstream clients; in-flight cohorts fail over member-by-member."""

    endpoints: Any = None  # list[str]
    epoch: int = -1


@dataclasses.dataclass
class PSOptStateRequest(_WireRequest):
    epoch: int = -1


@dataclasses.dataclass
class PSOptRestoreRequest(_WireRequest):
    leaves: Any = None
    epoch: int = -1


@dataclasses.dataclass
class PSRestoreFromWorkerRequest(_WireRequest):
    """A worker's flat-buffer slice offered as the restore source for a
    relaunched PS shard (master RPC, see master/recovery.py)."""

    worker_id: int = -1
    shard_id: int = -1
    vec: Any = None  # the worker's absorbed slice for that shard
    version: int = -1  # the worker's absorbed version for that shard


@dataclasses.dataclass
class GetJobManifestRequest(_WireRequest):
    """Read of the master's continuously published job manifest — the
    compact, versioned serialization of everything a standby needs to
    adopt the running job with no checkpoint file (master/migration.py):
    dispatcher task/dedup state, servicer exactness counters, shard
    topology with fencing generations, and the worker-manager roster."""


@dataclasses.dataclass
class BeginHandoffRequest(_WireRequest):
    """Planned-migration drain latch: the master pauses the task
    dispatcher (workers get WAIT) so in-flight tasks settle and the
    manifest quiesces before a standby adopts. Latch-idempotent — a
    resend finds the dispatcher already paused."""

    reason: str = ""


@dataclasses.dataclass
class PSRefenceRequest(_WireRequest):
    """In-place fencing-generation bump on a live PS shard — the
    adoption cutover (master/migration.py). Unlike a relaunch, the
    slice and optimizer state survive; only the epoch moves, so the old
    master's stale-generation clients bounce with FAILED_PRECONDITION.
    Monotonic: generation < current is rejected, == current no-ops."""

    generation: int = -1


@dataclasses.dataclass
class KVRefenceRequest(_WireRequest):
    """In-place fencing-generation bump on a live KV shard (the KV leg
    of the adoption cutover; same monotonic contract as PSRefence)."""

    generation: int = -1


@dataclasses.dataclass
class KVLookupRequest(_WireRequest):
    layer: str = ""
    ids: Any = None
    epoch: int = -1


@dataclasses.dataclass
class KVUpdateRequest(_WireRequest):
    layer: str = ""
    ids: Any = None
    values: Any = None
    set_if_not_exist: bool = False
    epoch: int = -1


@dataclasses.dataclass
class KVSnapshotRequest(_WireRequest):
    epoch: int = -1


@dataclasses.dataclass
class KVRestoreRequest(_WireRequest):
    layers: Any = None  # {layer: {"ids": [n], "values": [n, dim]}}
    epoch: int = -1


@dataclasses.dataclass
class KVLenRequest(_WireRequest):
    epoch: int = -1


@dataclasses.dataclass
class KVMirrorRequest(_WireRequest):
    """Async write mirroring primary -> paired replica shard. The
    replica keeps mirrored rows per source shard, outside its own
    primary store; recovery drains them back via KVMirrorSnapshot."""

    source_shard: int = -1
    layer: str = ""
    ids: Any = None
    values: Any = None
    set_if_not_exist: bool = False


@dataclasses.dataclass
class KVMirrorSnapshotRequest(_WireRequest):
    source_shard: int = -1


@dataclasses.dataclass
class KVSetMirrorRequest(_WireRequest):
    """Points a shard at its mirror target (the group wires pairs after
    endpoints exist; '' disables mirroring)."""

    endpoint: str = ""


#: The declared request contract, method name -> wire dataclass. The
#: rpc-conformance lint (elasticdl_tpu/analysis/rpc_conformance.py)
#: checks every client call-site dict and every server handler read
#: against these fields, so schema drift fails CI instead of surfacing
#: as a KeyError mid-job.
WIRE_SCHEMAS: Dict[str, type] = {
    "GetTask": GetTaskRequest,
    "GetModel": GetModelRequest,
    "GetAux": GetAuxRequest,
    "GetPSConfig": GetPSConfigRequest,
    "GetSampleBatch": GetSampleBatchRequest,
    "ReportVariable": ReportVariableRequest,
    "ReportGradient": ReportGradientRequest,
    "ReportLocalUpdate": ReportLocalUpdateRequest,
    "ReportEvaluationMetrics": ReportEvaluationMetricsRequest,
    "ReportTaskResult": ReportTaskResultRequest,
    "ReportWindowMeta": ReportWindowMetaRequest,
    "ReportPhaseStats": ReportPhaseStatsRequest,
    "GetSchedStats": GetSchedStatsRequest,
    "GetJobManifest": GetJobManifestRequest,
    "BeginHandoff": BeginHandoffRequest,
    "PSRefence": PSRefenceRequest,
    "KVRefence": KVRefenceRequest,
    "GetTrace": GetTraceRequest,
    "GetMetrics": GetMetricsRequest,
    "EmbeddingLookup": EmbeddingLookupRequest,
    "EmbeddingUpdate": EmbeddingUpdateRequest,
    "PSInit": PSInitRequest,
    "PSPull": PSPullRequest,
    "PSPushGrad": PSPushGradRequest,
    "PSPushDelta": PSPushDeltaRequest,
    "PSPushDeltaBucket": PSPushDeltaBucketRequest,
    "PSPushDeltaCombined": PSPushDeltaCombinedRequest,
    "AggPushDelta": AggPushDeltaRequest,
    "AggStats": AggStatsRequest,
    "AggUpdateUpstream": AggUpdateUpstreamRequest,
    "PSOptState": PSOptStateRequest,
    "PSOptRestore": PSOptRestoreRequest,
    "PSRestoreFromWorker": PSRestoreFromWorkerRequest,
    "KVLookup": KVLookupRequest,
    "KVUpdate": KVUpdateRequest,
    "KVSnapshot": KVSnapshotRequest,
    "KVRestore": KVRestoreRequest,
    "KVLen": KVLenRequest,
    "KVMirror": KVMirrorRequest,
    "KVMirrorSnapshot": KVMirrorSnapshotRequest,
    "KVSetMirror": KVSetMirrorRequest,
}


class Prepacked:
    """A response already serialized by the handler. The fan-in combine
    stage (master/fanin.py) answers every member of a batch with the
    same merged-model payload; packing it once and handing the SAME
    bytes to each member's transport turns k response serializations
    into one. `pack` passes the bytes through untouched.

    Mapping-style reads (`resp["vec"]`, `resp.get(...)`) decode the
    frame lazily, so a handler returning Prepacked still duck-types as
    its response dict for direct (non-RPC) callers."""

    __slots__ = ("data", "_obj")

    def __init__(self, data: bytes):
        self.data = data
        self._obj = None

    def _decoded(self) -> Any:
        if self._obj is None:
            self._obj = unpack(self.data)
        return self._obj

    def __getitem__(self, key):
        return self._decoded()[key]

    def __contains__(self, key):
        return key in self._decoded()

    def get(self, key, default=None):
        return self._decoded().get(key, default)


def pack(obj: Any) -> bytes:
    if isinstance(obj, Prepacked):
        return obj.data
    return codec.dumps(obj)


class PackedParts:
    """A packed frame that has not been joined: the ordered parts
    `codec.dumps_parts` makes (bytes, flat uint8 views of the source
    arrays, which the views keep alive, and `codec.PendingPiece`s whose
    bytes may still be on their way: `pending` says there are such)
    and their total length, which
    `len()` answers. A carrier that writes to a socket sends the parts
    as they lie and waits at a pending one for its bytes; one that
    needs a single buffer (gRPC, inproc) asks
    `contiguous()`, which waits for every piece and joins once however
    often it is asked, so a
    retry resends what the first attempt sent. `waited` is the seconds
    whoever sent or joined the frame stood waiting for a piece."""

    __slots__ = ("parts", "nbytes", "pending", "waited", "_data")

    def __init__(self, parts, nbytes: int):
        self.parts = parts
        self.nbytes = nbytes
        self.pending = any(isinstance(p, codec.PendingPiece) for p in parts)
        self.waited = 0.0
        self._data = None

    def __len__(self) -> int:
        return self.nbytes

    @property
    def joined(self) -> bool:
        """Whether a carrier asked for the one buffer."""
        return self._data is not None

    @property
    def streamed(self) -> bool:
        """Whether the frame went out as its pieces landed: it has
        pending pieces and no carrier asked for the one buffer."""
        return self.pending and self._data is None

    def contiguous(self, timeout=None) -> bytes:
        """The frame in one buffer; `timeout` bounds the wait for the
        pending pieces together (`TimeoutError`)."""
        if self._data is None:
            parts = self.parts
            if self.pending:
                t0 = time.monotonic()
                try:
                    parts = [
                        codec.part_bytes(
                            p,
                            None if timeout is None
                            else max(0.0, t0 + timeout - time.monotonic()),
                        )
                        for p in parts
                    ]
                finally:
                    self.waited += time.monotonic() - t0
            self._data = parts[0] if len(parts) == 1 else b"".join(parts)
        return self._data


def pack_parts(obj: Any) -> PackedParts:
    """`pack` without the join: what `RpcClient` hands its carrier. A
    `Prepacked` is the one part its maker joined."""
    if isinstance(obj, Prepacked):
        return PackedParts([obj.data], len(obj.data))
    return PackedParts(*codec.dumps_parts(obj))


def unpack(data: bytes) -> Any:
    return codec.loads(data)
