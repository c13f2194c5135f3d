"""Shared constants (reference: elasticdl/python/common/constants.py:1-35)."""

# gRPC message caps: full models ride single messages on the PS path
# (reference caps at 256 MiB, constants.py:1-5; we allow 1 GiB because
# ResNet-50-scale bf16 payloads plus headroom fit comfortably and XLA
# hosts have the memory).
GRPC_MAX_MESSAGE_LENGTH = 1024 * 1024 * 1024

GRPC_OPTIONS = [
    ("grpc.max_send_message_length", GRPC_MAX_MESSAGE_LENGTH),
    ("grpc.max_receive_message_length", GRPC_MAX_MESSAGE_LENGTH),
]

SERVICE_NAME = "elasticdl_tpu.Master"
# that service's update and model RPCs: one per update, never per chunk
# or per record. Their owners (master/main.py's server, worker/main.py's
# master client) put both sides of them on the phase timeline.
MASTER_UPDATE_METHODS = frozenset(
    ("ReportLocalUpdate", "ReportGradient", "GetModel")
)


# Process exit code for "job completed but with dropped poison tasks":
# deliberate partial-data completion, distinct from a crash — the
# WorkerManager must NOT relaunch a worker that exits with it.
EXIT_CODE_JOB_FAILED = 2

# Worker exit code for "master unreachable past the retry budget":
# graceful degradation instead of a hang — distinct from a crash (1)
# so operators can tell a network partition from a worker bug, while
# the WorkerManager still treats it as relaunch-eligible (the master
# may have moved / recovered by relaunch time).
EXIT_CODE_MASTER_UNREACHABLE = 3


class WorkerManagerStatus(object):
    PENDING = "Pending"
    RUNNING = "Running"
    FINISHED = "Finished"


class JobType(object):
    TRAINING_ONLY = "training"
    EVALUATION_ONLY = "evaluation"
    PREDICTION_ONLY = "prediction"
    TRAINING_WITH_EVALUATION = "training_with_evaluation"


# the non-trainable collection a model may keep for the timeline: what a
# window's last step leaves there goes out as `args` of one
# `worker.window_stats` span a window sync (worker/worker.py)
WINDOW_STATS = "window_stats"


class Mode(object):
    TRAINING = "training"
    EVALUATION = "evaluation"
    PREDICTION = "prediction"


# Worker gives up on a minibatch after this many stale-gradient retries
# (reference: elasticdl/python/worker/worker.py:20).
MAX_MINIBATCH_RETRY_NUM = 64


# -- environment-variable registry ------------------------------------------
#
# Every EDL_*/K8S_* environment variable the framework reads, by name.
# Code must read env vars through these constants, and every constant
# must be registered in ENV_REGISTRY with a one-line description: the
# env-registry lint (elasticdl_tpu/analysis/env_registry.py) fails CI
# on any read of an EDL_*/K8S_* variable that is not declared here, so
# the table below is, by construction, the complete operator surface.

ENV_CHAOS_SPEC = "EDL_CHAOS_SPEC"
ENV_CHAOS_ROLE = "EDL_CHAOS_ROLE"
ENV_CHAOS_TARGET_ID = "EDL_CHAOS_TARGET_ID"
ENV_RPC_RETRIES = "EDL_RPC_RETRIES"
ENV_RPC_BACKOFF = "EDL_RPC_BACKOFF"
ENV_RPC_SEED = "EDL_RPC_SEED"
ENV_SYNC_DEPTH = "EDL_SYNC_DEPTH"
ENV_OVERLAP_SYNC = "EDL_OVERLAP_SYNC"
ENV_SYNC_DTYPE = "EDL_SYNC_DTYPE"
ENV_SYNC_COMPRESS = "EDL_SYNC_COMPRESS"
ENV_SYNC_BUCKET_BYTES = "EDL_SYNC_BUCKET_BYTES"
ENV_TRANSPORT = "EDL_TRANSPORT"
ENV_UDS_DIR = "EDL_UDS_DIR"
ENV_DISPATCH = "EDL_DISPATCH"
ENV_DISPATCH_EXECUTOR = "EDL_DISPATCH_EXECUTOR"
ENV_QUEUE_DEPTH_REPORT = "EDL_QUEUE_DEPTH_REPORT"
ENV_QUEUE_DEPTH_PULL = "EDL_QUEUE_DEPTH_PULL"
ENV_QUEUE_DEPTH_CONTROL = "EDL_QUEUE_DEPTH_CONTROL"
ENV_FANIN_COMBINE = "EDL_FANIN_COMBINE"
ENV_FANIN_BATCH = "EDL_FANIN_BATCH"
ENV_FANIN_WAIT_MS = "EDL_FANIN_WAIT_MS"
ENV_AGG_BATCH = "EDL_AGG_BATCH"
ENV_AGG_WAIT_MS = "EDL_AGG_WAIT_MS"
ENV_AGG_UPSTREAM_TIER = "EDL_AGG_UPSTREAM_TIER"
ENV_OPT_MIRROR_SECS = "EDL_OPT_MIRROR_SECS"
ENV_BET_PREFETCH = "EDL_BET_PREFETCH"
ENV_WORKER_LOG_DIR = "EDL_WORKER_LOG_DIR"
ENV_TB_BACKEND = "EDL_TPU_TB_BACKEND"
ENV_NO_NATIVE_KV = "EDL_TPU_NO_NATIVE_KV"
ENV_TPU_FLASH = "EDL_TPU_FLASH"
ENV_TPU_TESTS = "EDL_TPU_TESTS"
ENV_SCHED_QOS = "EDL_SCHED_QOS"
ENV_SCHED_PHASE_SECS = "EDL_SCHED_PHASE_SECS"
ENV_SCHED_AUTOSCALE = "EDL_SCHED_AUTOSCALE"
ENV_SCHED_UP_FRAC = "EDL_SCHED_UP_FRAC"
ENV_SCHED_DOWN_FRAC = "EDL_SCHED_DOWN_FRAC"
ENV_SCHED_COOLDOWN_SECS = "EDL_SCHED_COOLDOWN_SECS"
ENV_SCHED_SPECULATE = "EDL_SCHED_SPECULATE"
ENV_SCHED_SPEC_FACTOR = "EDL_SCHED_SPEC_FACTOR"
ENV_SCHED_SPEC_PCTL = "EDL_SCHED_SPEC_PCTL"
ENV_SCHED_MAX_BACKUPS = "EDL_SCHED_MAX_BACKUPS"
ENV_MIGRATE_LEASE_SECS = "EDL_MIGRATE_LEASE_SECS"
ENV_MIGRATE_MANIFEST_SECS = "EDL_MIGRATE_MANIFEST_SECS"
ENV_MIGRATE_STANDBY = "EDL_MIGRATE_STANDBY"
ENV_TRACE_SAMPLE = "EDL_TRACE_SAMPLE"
ENV_METRICS_PORT = "EDL_METRICS_PORT"
ENV_FLIGHT_RECORDER_EVENTS = "EDL_FLIGHT_RECORDER_EVENTS"
ENV_FLIGHT_DIR = "EDL_FLIGHT_DIR"
ENV_TRACE_SEED = "EDL_TRACE_SEED"
ENV_TRACE_PROBE_SECS = "EDL_TRACE_PROBE_SECS"
ENV_K8S_TESTS = "K8S_TESTS"
ENV_K8S_TEST_IMAGE = "K8S_TEST_IMAGE"
ENV_K8S_TEST_NAMESPACE = "K8S_TEST_NAMESPACE"

ENV_REGISTRY = {
    ENV_CHAOS_SPEC: (
        "chaos activation: inline FaultPlan JSON or @/path/to/spec.json "
        "(rpc/chaos.py); inherited by every spawned subprocess"
    ),
    ENV_CHAOS_ROLE: (
        "chaos scoping: this process's role (worker/ps/kv/master), "
        "stamped by the spawner"
    ),
    ENV_CHAOS_TARGET_ID: (
        "chaos scoping: this process's target id (worker/shard index), "
        "stamped by the spawner"
    ),
    ENV_RPC_RETRIES: "RetryPolicy max_attempts override (>=1; 1 = no retries)",
    ENV_RPC_BACKOFF: "RetryPolicy initial backoff seconds override",
    ENV_RPC_SEED: "RetryPolicy deterministic-jitter seed override",
    ENV_SYNC_DEPTH: (
        "max in-flight pipelined window syncs per worker (0 serializes; "
        "default 2)"
    ),
    ENV_OVERLAP_SYNC: (
        "worker overlap plane: on (default) pipelines window-delta "
        "encode/push on sync threads, absorbs model-down in the "
        "background at step boundaries, and enables BET prefetch; off "
        "restores the serial blocking sync chain bit-for-bit "
        "(worker/worker.py; CLI --overlap_sync)"
    ),
    ENV_SYNC_DTYPE: (
        "sync-plane wire dtype: bf16 or int8 sends window deltas / "
        "per-step grads quantized with error-feedback residuals held "
        "on the worker (default float32 = bit-exact)"
    ),
    ENV_SYNC_COMPRESS: (
        "sync-plane delta sparsification: topk:<ratio> ships only the "
        "ratio*n largest-magnitude window-delta entries as "
        "(indices, values) frames, error-feedback corrected; composes "
        "with EDL_SYNC_DTYPE int8/bf16 for the values (default off)"
    ),
    ENV_SYNC_BUCKET_BYTES: (
        "bucketed delta push: split each window delta into "
        "~this-many-byte layer-aligned buckets streamed per push; the "
        "PS parks partial sets and applies the full set atomically at "
        "the window boundary (0 = unbucketed flat push, the default; "
        "no CLI flag; sharded-PS route only)"
    ),
    ENV_TRANSPORT: (
        "RPC transport tier override. Unset (the default) means uds: "
        "every RpcServer opens a Unix-domain-socket listener beside "
        "gRPC and a client whose endpoint resolves to this host, with "
        "that socket file present, is carried by it; a remote endpoint "
        "gets gRPC. Explicit values: grpc (pure gRPC, no listener), uds, "
        "inproc (same-interpreter direct dispatch), or auto (prefer "
        "inproc, then uds, then grpc); every non-grpc tier applies "
        "only to a local endpoint whose counterpart is there, and one "
        "that cannot connect hands "
        "the call to gRPC (rpc/transport.py)"
    ),
    ENV_UDS_DIR: (
        "directory for the Unix-socket carrier's sockets "
        "(edl-uds-<port>.sock, one per RpcServer; a boot sweeps those "
        "of dead servers); default: the system temp dir — must be "
        "shared by co-located processes; may be deeper than an AF_UNIX "
        "address holds"
    ),
    ENV_DISPATCH: (
        "server dispatch core: threads (default; blocking "
        "thread-per-request) or loop (single asyncio event loop serving "
        "every tier with bounded-executor handler bridging and "
        "per-method-class admission queues — rpc/dispatch.py)"
    ),
    ENV_DISPATCH_EXECUTOR: (
        "loop dispatch: bounded executor width for bridged sync "
        "handlers, per ServerDispatcher (default 32)"
    ),
    ENV_QUEUE_DEPTH_REPORT: (
        "loop dispatch: max in-flight report-class RPCs (push/report "
        "mutations) before RESOURCE_EXHAUSTED backpressure (default "
        "1024; retryable under the rpc/policy.py schedule)"
    ),
    ENV_QUEUE_DEPTH_PULL: (
        "loop dispatch: max in-flight pull-class RPCs (model/state "
        "reads) before RESOURCE_EXHAUSTED backpressure (default 256)"
    ),
    ENV_QUEUE_DEPTH_CONTROL: (
        "loop dispatch: max in-flight control-class RPCs (everything "
        "else) before RESOURCE_EXHAUSTED backpressure (default 256)"
    ),
    ENV_FANIN_COMBINE: (
        "1 enables the hierarchical window-delta fan-in stage: "
        "compatible PS-shard pushes are summed OUTSIDE the shard lock "
        "and applied as one batch (master/fanin.py; default off, also "
        "--fanin_combine)"
    ),
    ENV_FANIN_BATCH: (
        "fan-in combine: max member pushes per combined batch "
        "(default 32)"
    ),
    ENV_FANIN_WAIT_MS: (
        "fan-in combine: optional straggler linger in milliseconds — "
        "a drained batch below EDL_FANIN_BATCH waits this long for "
        "late arrivals before applying (default 0 = off; the batch "
        "window is naturally the previous apply's duration)"
    ),
    ENV_AGG_BATCH: (
        "aggregation tree (agg/): max member pushes per presummed "
        "cohort an aggregator forwards upstream as one "
        "PSPushDeltaCombined (default 32)"
    ),
    ENV_AGG_WAIT_MS: (
        "aggregation tree: optional cohort linger in milliseconds — a "
        "drained cohort below EDL_AGG_BATCH waits this long for late "
        "host-local arrivals before forwarding (default 0 = off; the "
        "rendezvous window is naturally the previous forward's "
        "duration)"
    ),
    ENV_AGG_UPSTREAM_TIER: (
        "aggregation tree: transport tier for the aggregator->PS "
        "upstream link (default uds = Unix socket when the PS resolves "
        "local, else grpc; grpc forces gRPC; inproc/auto as in "
        "EDL_TRANSPORT) — the worker->aggregator leg keeps following "
        "EDL_TRANSPORT"
    ),
    ENV_OPT_MIRROR_SECS: (
        "recovery plane: seconds between PS optimizer-state mirror "
        "snapshots (bounded-staleness restore ring, master/recovery.py; "
        "default 2.0)"
    ),
    ENV_BET_PREFETCH: (
        "0 disables the batched-embedding-training lookup prefetch "
        "overlap (default on)"
    ),
    ENV_WORKER_LOG_DIR: (
        "directory for per-worker log files under the ProcessBackend "
        "(empty = inherit stdio)"
    ),
    ENV_TB_BACKEND: (
        "TensorBoard event-writer backend override "
        "(master/tensorboard_service.py)"
    ),
    ENV_NO_NATIVE_KV: (
        "1 disables the C++ embedding-store arena, forcing the "
        "lock-striped Python store"
    ),
    ENV_TPU_FLASH: (
        "force the Pallas flash-attention kernels on (1) or off (0); "
        "unset = size heuristic"
    ),
    ENV_TPU_TESTS: "1 enables hardware-gated tests (tests/test_cluster_gated.py)",
    ENV_SCHED_QOS: (
        "policy plane: this job's QoS class (guaranteed/burstable/"
        "best-effort) when sharing a fleet under the priority arbiter; "
        "--qos_class beats it (default burstable — sched/qos.py)"
    ),
    ENV_SCHED_PHASE_SECS: (
        "policy plane: seconds between worker ReportPhaseStats "
        "telemetry sends (PhaseTimers snapshots feeding the "
        "autoscaler; 0 disables; default 2.0). Also the period at "
        "which a worker or master appends its phase timeline to "
        "<log dir>/<process>.spans.jsonl (obs/trace.SpanFile; 2 s "
        "where this is 0: the timeline has no switch)"
    ),
    ENV_SCHED_AUTOSCALE: (
        "1 enables the utilization autoscaler on the master (also "
        "--autoscale): scale up on compute-bound fleets with queued "
        "tasks, down when sync_wait dominates (sched/autoscaler.py)"
    ),
    ENV_SCHED_UP_FRAC: (
        "autoscaler: recent fleet compute-fraction at or above which "
        "a scale-up fires, given headroom and queued work "
        "(default 0.6)"
    ),
    ENV_SCHED_DOWN_FRAC: (
        "autoscaler: recent fleet sync_wait-fraction at or above "
        "which a scale-down fires (default 0.5)"
    ),
    ENV_SCHED_COOLDOWN_SECS: (
        "autoscaler: minimum seconds between executed resizes "
        "(default 5.0)"
    ),
    ENV_SCHED_SPECULATE: (
        "1 enables speculative straggler backups in the task "
        "dispatcher (also --speculate): a task running past the "
        "sibling-runtime threshold is re-dispatched to an idle worker, "
        "first-report-wins via report_key dedup"
    ),
    ENV_SCHED_SPEC_FACTOR: (
        "speculation: multiplier over the completed-sibling runtime "
        "percentile before a task counts as a straggler (default 1.5)"
    ),
    ENV_SCHED_SPEC_PCTL: (
        "speculation: percentile (0..1) of completed sibling runtimes "
        "used as the straggler baseline (default 0.5 = median)"
    ),
    ENV_SCHED_MAX_BACKUPS: (
        "speculation: max concurrent backup copies in flight "
        "(default 2)"
    ),
    ENV_MIGRATE_LEASE_SECS: (
        "migration plane: seconds of consecutive failed GetJobManifest "
        "polls after which a standby master declares the primary dead "
        "and adopts the job from its last cached manifest "
        "(master/migration.py; default 3.0)"
    ),
    ENV_MIGRATE_MANIFEST_SECS: (
        "migration plane: seconds between a standby's GetJobManifest "
        "polls of the primary — the manifest publication cadence, and "
        "the bound on how much dispatcher state a crash failover "
        "replays through dedup (default 0.5)"
    ),
    ENV_MIGRATE_STANDBY: (
        "1 arms a standby master for the job (chaos/scenario.py "
        "master-failover traces; equivalent to the trace's "
        "master_standby flag): the standby serves UNAVAILABLE until it "
        "adopts, then answers on its pre-advertised endpoint"
    ),
    ENV_TRACE_SAMPLE: (
        "obs plane: trace sampling probability in [0,1] (default 0 = "
        "off; 1 traces every request) — per-RPC trace_id/span_id "
        "envelopes + SpanRecorder spans at every hop (obs/trace.py); "
        "the off path is a single float compare"
    ),
    ENV_METRICS_PORT: (
        "obs plane: port for the optional Prometheus /metrics HTTP "
        "listener (obs/metrics.py; unset = no listener — GetMetrics "
        "RPC and dump APIs still work)"
    ),
    ENV_FLIGHT_RECORDER_EVENTS: (
        "obs plane: flight-recorder ring capacity in events "
        "(obs/flight.py; default 4096, min 16)"
    ),
    ENV_FLIGHT_DIR: (
        "obs plane: directory for flight-recorder crash dumps "
        "(edl_flight_<pid>.json); default <tmpdir>/edl-flight — never "
        "the working directory (obs/flight.py)"
    ),
    ENV_TRACE_SEED: (
        "churn harness: seed override for the scenario scheduler's "
        "victim picks (chaos/scenario.py; default = the trace file's "
        "seed field — same seed, same fleet => byte-identical timeline)"
    ),
    ENV_TRACE_PROBE_SECS: (
        "churn harness: seconds between mid-run exactness probes "
        "against GetSchedStats (chaos/scenario.py; default 0.5)"
    ),
    ENV_K8S_TESTS: "1 enables live-cluster tests (tests/test_cluster_gated.py)",
    ENV_K8S_TEST_IMAGE: "worker image for the live-cluster tests",
    ENV_K8S_TEST_NAMESPACE: "namespace for the live-cluster tests",
}
