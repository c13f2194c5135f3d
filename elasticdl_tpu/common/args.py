"""Layered CLI argument sets — the inter-process config protocol.

Re-design of the reference's flag system (elasticdl/python/common/args.py:45-296,
master/args.py:41-64, worker/main.py:10-83): shared model-spec flags are
defined once and composed into the master and worker parsers, and the
master *forwards* the model-spec subset to workers as command-line args
(reference master/main.py:229-255) — the flag namespace is the config
protocol between processes, so worker flags must stay a subset of
master flags by construction (`worker_forward_args`).
"""

from __future__ import annotations

import argparse
import os
from typing import List


def pos_int(value: str) -> int:
    v = int(value)
    if v <= 0:
        raise argparse.ArgumentTypeError(f"must be > 0, got {value}")
    return v


def non_neg_int(value: str) -> int:
    v = int(value)
    if v < 0:
        raise argparse.ArgumentTypeError(f"must be >= 0, got {value}")
    return v


def parse_envs(env_str: str) -> dict:
    """``"k=v,k2=v2"`` -> dict (reference: common/args.py:17-42)."""
    out = {}
    if not env_str:
        return out
    for kv in env_str.split(","):
        if not kv.strip():
            continue
        k, _, v = kv.partition("=")
        out[k.strip()] = v.strip()
    return out


def add_model_spec_args(parser: argparse.ArgumentParser):
    """Flags describing the user model — shared by master and worker
    and forwarded master->worker verbatim (reference: common/args.py:45-174)."""
    parser.add_argument(
        "--model_zoo", required=True,
        help="directory containing the model-zoo modules",
    )
    parser.add_argument(
        "--model_def", required=True,
        help='"file.symbol" of the model factory inside --model_zoo, '
        'e.g. "mnist_functional_api.custom_model"',
    )
    parser.add_argument("--model_params", default="", help='"k=v,k2=v2" ctor params')
    parser.add_argument("--dataset_fn", default="dataset_fn")
    parser.add_argument("--loss", default="loss")
    parser.add_argument("--optimizer", default="optimizer")
    parser.add_argument("--eval_metrics_fn", default="eval_metrics_fn")
    parser.add_argument(
        "--prediction_outputs_processor", default="PredictionOutputsProcessor"
    )
    parser.add_argument("--minibatch_size", type=pos_int, required=True)
    parser.add_argument(
        "--local_updates", type=non_neg_int, default=0,
        help="N>0: on-device optimizer with one delta sync per N steps "
        "(SSP/local-SGD); 0: per-step sync SGD via the PS",
    )
    parser.add_argument(
        "--transport_dtype", default="float32", choices=("float32", "bfloat16"),
        help="wire dtype for gradients/deltas",
    )
    parser.add_argument(
        "--sync_dtype", default="",
        choices=("", "float32", "bfloat16", "bf16", "int8"),
        help="sync-plane wire dtype: bf16/int8 send window deltas / "
        "per-step grads quantized (int8 = per-chunk scaled) with an "
        "error-feedback residual held on the worker (converges to the "
        "f32 trajectory; default float32 = bit-exact). "
        "EDL_SYNC_DTYPE overrides.",
    )
    parser.add_argument(
        "--sync_compress", default="",
        help="sync-plane delta sparsification: topk:<ratio> ships only "
        "the ratio*n largest-magnitude window-delta entries as "
        "(indices, values) frames, error-feedback corrected; composes "
        "with --sync_dtype int8/bf16 for the values (default off). "
        "EDL_SYNC_COMPRESS overrides.",
    )
    parser.add_argument(
        "--overlap_sync", default="", choices=("", "on", "off"),
        help="worker overlap plane: on (default) pipelines window-delta "
        "encode/push on sync threads, pages model-down in on a "
        "background thread, and enables BET prefetch; off is the serial "
        "chain: no device memory beside a window but one snapshot of "
        "the model (16 B a parameter; a delta over 128 MiB is formed "
        "on the host from that snapshot, a smaller one on the device: "
        "20 B at the sync's moment), no second delta on the host, the "
        "same bytes to the same master in the same order (A/B + "
        "exactness audits); a worker that is alone goes on before its "
        "sync is over, so the answer may arrive behind the next "
        "window. EDL_OVERLAP_SYNC overrides.",
    )
    parser.add_argument("--log_level", default="INFO")
    parser.add_argument(
        "--profile_dir", default="",
        help="write a jax.profiler device trace per worker here "
        "(TensorBoard/Perfetto-viewable)",
    )


def add_master_args(parser: argparse.ArgumentParser):
    """Master-only flags (reference: master/args.py:12-35 +
    common/args.py train params :177-270)."""
    parser.add_argument("--port", type=non_neg_int, default=0)
    parser.add_argument("--job_name", default="elasticdl-job")
    parser.add_argument(
        "--training_data_dir", default="",
        help="RecordIO file or directory of shards for training",
    )
    parser.add_argument("--evaluation_data_dir", default="")
    parser.add_argument("--prediction_data_dir", default="")
    parser.add_argument("--records_per_task", type=pos_int, default=4096)
    parser.add_argument("--num_epochs", type=pos_int, default=1)
    parser.add_argument("--grads_to_wait", type=pos_int, default=2)
    parser.add_argument("--use_async", action="store_true")
    parser.add_argument("--lr_staleness_modulation", action="store_true")
    parser.add_argument("--staleness_window", type=non_neg_int, default=0)
    parser.add_argument(
        "--step_pipeline", type=int, default=-1,
        help="per-step pipeline DEPTH: up to N gradient reports in "
        "flight while later batches compute, so the report round's "
        "latency is divided across N batches (each report may land up "
        "to N versions stale). 0=off; -1=auto (4, clamped to "
        "--staleness_window in sync mode; async mode accepts any "
        "depth and down-weights by staleness)",
    )
    parser.add_argument(
        "--num_ps", type=non_neg_int, default=0,
        help="N>0: shard the dense model across N parameter-server "
        "endpoints (workers push/pull slices in parallel); 0: the "
        "master is the single PS",
    )
    parser.add_argument(
        "--ps_mode", default="process", choices=("process", "inproc"),
        help="sharded-PS hosting: dedicated subprocesses (default) or "
        "threads inside the master (tests/single-host)",
    )
    parser.add_argument(
        "--fanin_combine", action="store_true",
        help="hierarchical fan-in on the PS shards: compatible "
        "concurrent pushes are summed outside the shard lock and "
        "applied as one batch (master/fanin.py; default honors "
        "EDL_FANIN_COMBINE)",
    )
    parser.add_argument(
        "--num_agg", type=non_neg_int, default=0,
        help="N>0: interpose N aggregation-tree nodes between the "
        "workers and the PS shards (agg/): each worker's window-delta "
        "pushes land on its host aggregator, which presums the cohort "
        "and forwards ONE combined delta per shard — master-side "
        "fan-in drops from #workers to #aggregators. Requires "
        "--num_ps > 0; 0: workers push direct",
    )
    parser.add_argument(
        "--agg_mode", default="process", choices=("process", "inproc"),
        help="aggregator hosting, like --ps_mode",
    )
    parser.add_argument(
        "--num_kv_shards", type=non_neg_int, default=0,
        help="N>0: host the embedding tables behind N KV shard "
        "endpoints (workers look rows up directly, bypassing the "
        "master — the reference's worker->Redis topology); 0: tables "
        "live in the master process",
    )
    parser.add_argument(
        "--kv_mode", default="process", choices=("process", "inproc"),
        help="KV shard hosting, like --ps_mode",
    )
    parser.add_argument("--eval_steps", type=non_neg_int, default=0)
    parser.add_argument("--eval_start_delay_secs", type=float, default=0.0)
    parser.add_argument("--eval_throttle_secs", type=float, default=0.0)
    parser.add_argument("--checkpoint_dir", default="")
    parser.add_argument("--checkpoint_steps", type=non_neg_int, default=0)
    parser.add_argument("--keep_checkpoint_max", type=non_neg_int, default=0)
    parser.add_argument(
        "--checkpoint_filename_for_init", default="",
        help="boot the PS from this checkpoint (required for "
        "evaluate/predict jobs, reference master/args.py:53-64)",
    )
    parser.add_argument(
        "--output", default="",
        help="save the final model here when the job finishes",
    )
    parser.add_argument(
        "--tensorboard_log_dir", default="",
        help="write train-loss + eval-metric summaries here "
        "(torch SummaryWriter when available, JSONL fallback)",
    )
    parser.add_argument(
        "--keep_tensorboard_running", action="store_true",
        help="after the job completes, keep the master alive serving "
        "TensorBoard until its process dies or the pod is deleted "
        "(reference master/main.py:311-324)",
    )
    # elasticity / cluster
    parser.add_argument("--num_workers", type=pos_int, default=1)
    parser.add_argument(
        "--worker_backend", default="process", choices=("process", "k8s"),
        help="process: local subprocess workers (hermetic); "
        "k8s: pods via the kubernetes API",
    )
    parser.add_argument(
        "--max_worker_relaunches", type=non_neg_int, default=10,
        help="total replacement workers to launch before giving up",
    )
    parser.add_argument(
        "--num_standby_workers", type=non_neg_int, default=0,
        help="warm standby workers held in reserve (pre-booted and "
        "AOT-compiled); a standby is promoted instantly when an active "
        "worker dies, removing the boot/compile transient from "
        "preemption recovery",
    )
    # policy plane (elasticdl_tpu/sched/)
    parser.add_argument(
        "--qos_class", default="",
        choices=("", "guaranteed", "burstable", "best-effort"),
        help="QoS class of this job when it shares a worker fleet "
        "under a PriorityArbiter: guaranteed jobs may preempt "
        "best-effort workers to get capacity. Default: EDL_SCHED_QOS "
        "env, else burstable",
    )
    parser.add_argument(
        "--autoscale", action="store_true",
        help="utilization-driven worker autoscaling: the master "
        "aggregates worker phase telemetry and scales the fleet up "
        "when compute dominates (with pending tasks), down when "
        "sync_wait dominates — resizes ride the elastic requeue path, "
        "so exactness is preserved (EDL_SCHED_AUTOSCALE=1 also enables)",
    )
    parser.add_argument(
        "--min_workers", type=pos_int, default=1,
        help="autoscaler floor: never scale below this many active workers",
    )
    parser.add_argument(
        "--max_workers", type=non_neg_int, default=0,
        help="autoscaler ceiling (0 = no ceiling)",
    )
    parser.add_argument(
        "--speculate", action="store_true",
        help="speculative straggler backups: a task whose runtime "
        "exceeds EDL_SCHED_SPEC_FACTOR x the EDL_SCHED_SPEC_PCTL "
        "percentile of completed siblings gets a backup copy on an "
        "idle worker; first report wins, the twin's pushes are "
        "absorbed by report_key dedup (window mode only; "
        "EDL_SCHED_SPECULATE=1 also enables)",
    )
    parser.add_argument("--worker_image", default="")
    parser.add_argument("--namespace", default="default")
    parser.add_argument(
        "--worker_resource_request", default="cpu=1,memory=2048Mi",
        help='k8s resource DSL, e.g. "cpu=1,memory=4096Mi,tpu=1"',
    )
    parser.add_argument("--worker_resource_limit", default="")
    parser.add_argument(
        "--ps_resource_request", default="",
        help="k8s resources for PS shard pods (CPU processes); default "
        "= worker_resource_request with accelerator entries stripped",
    )
    parser.add_argument("--ps_resource_limit", default="")
    parser.add_argument("--worker_pod_priority", default="")
    parser.add_argument(
        "--volume", default="",
        help='k8s volume DSL: "claim_name=...,mount_path=..."',
    )
    parser.add_argument("--envs", default="", help='extra worker env "k=v,..."')
    parser.add_argument(
        "--cluster_spec", default="",
        help="python file providing with_pod(pod) for on-prem mutation",
    )
    parser.add_argument(
        "--compile_cache_dir", default="auto",
        help="persistent XLA compile cache shared by all workers, so a "
        "relaunched replacement, a promoted standby or the next job "
        "reuses the compiled programs instead of re-paying the XLA "
        "compile on boot (the recovery transient the reference re-pays "
        "on every pod relaunch, k8s_worker_manager.py:139-145). A "
        "JAX_COMPILATION_CACHE_DIR in the master's environment wins "
        'over this flag. "auto" (default): <checkout>/.jax_cache for '
        "process workers, the same path for every job; on k8s auto is "
        "OFF because pods need a shared --volume mount to see one "
        'cache — pass an explicit path on that mount. "" disables',
    )


def add_worker_args(parser: argparse.ArgumentParser):
    """Worker-process flags (reference: worker/main.py:10-83)."""
    parser.add_argument("--worker_id", type=non_neg_int, required=True)
    parser.add_argument("--master_addr", required=True)
    # master-migration plane (master/migration.py): every endpoint a
    # master for this job may answer at, comma-separated, primary first;
    # "" = no in-job failover (exit for relaunch as before)
    parser.add_argument("--master_candidates", default="")
    # already resolved by the master (resolve_step_pipeline): the
    # worker itself doesn't know the PS staleness policy
    parser.add_argument("--step_pipeline", type=non_neg_int, default=0)


def resolve_step_pipeline(args) -> int:
    """Resolve the per-step pipeline DEPTH (in-flight gradient
    reports). Legality: a report may be up to `depth` versions stale
    when it lands, so sync mode clamps the depth to --staleness_window
    (anything deeper would just bounce off the rejection path); async
    mode accepts any staleness (down-weighted), so the requested depth
    stands. Auto (-1) picks 4 — enough to cover a high-latency link's
    report round with compute at typical step times — capped by the
    window. Window mode (local_updates) has its own chained-sync
    pipeline and keeps per-step off."""
    if args.local_updates:
        return 0
    depth = 4 if args.step_pipeline < 0 else args.step_pipeline
    if not args.use_async:
        depth = min(depth, args.staleness_window)
    return depth


def master_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="elasticdl_tpu.master", description="ElasticDL-TPU master"
    )
    add_model_spec_args(p)
    add_master_args(p)
    return p


def worker_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="elasticdl_tpu.worker", description="ElasticDL-TPU worker"
    )
    add_model_spec_args(p)
    add_worker_args(p)
    return p


def validate_master_args(args) -> str:
    """Job-type inference + combination checks (reference:
    master/main.py:111-136, master/args.py:41-64). Returns the job type."""
    from elasticdl_tpu.common.constants import JobType

    if args.prediction_data_dir:
        if args.training_data_dir or args.evaluation_data_dir:
            raise ValueError(
                "prediction_data_dir is exclusive of training/evaluation dirs"
            )
        if not args.checkpoint_filename_for_init:
            raise ValueError(
                "prediction jobs require --checkpoint_filename_for_init"
            )
        return JobType.PREDICTION_ONLY
    if args.training_data_dir and args.evaluation_data_dir:
        return JobType.TRAINING_WITH_EVALUATION
    if args.training_data_dir:
        return JobType.TRAINING_ONLY
    if args.evaluation_data_dir:
        if not args.checkpoint_filename_for_init:
            raise ValueError(
                "evaluation jobs require --checkpoint_filename_for_init"
            )
        return JobType.EVALUATION_ONLY
    raise ValueError("one of training/evaluation/prediction data dirs required")


def validate_ps_args(args):
    """Sharded-PS combination checks (see master/ps_shard.py's
    consistency model): strict per-step sync rejection cannot be
    atomic across shards, so num_ps > 0 needs a protocol whose
    application commutes."""
    if getattr(args, "num_ps", 0) <= 0:
        if getattr(args, "num_agg", 0) > 0:
            raise ValueError(
                "--num_agg > 0 requires --num_ps > 0 (the aggregation "
                "tree forwards to sharded-PS endpoints)"
            )
        return
    if (
        not args.use_async
        and args.local_updates == 0
        and args.staleness_window == 0
    ):
        raise ValueError(
            "--num_ps > 0 with strict per-step sync SGD is not "
            "supported (a stale-gradient rejection cannot be atomic "
            "across shards): use --local_updates N, --use_async, or "
            "--staleness_window W"
        )


def add_client_args(parser: argparse.ArgumentParser):
    """Client-only flags: image build & master-pod shape (reference:
    common/args.py image/registry params :45-174, api.py:11-227)."""
    parser.add_argument(
        "--image_base", default="python:3.10-slim",
        help="base image for the synthesized job Dockerfile",
    )
    parser.add_argument(
        "--docker_image_repository", default="",
        help="registry prefix to tag (and optionally push) the job image",
    )
    parser.add_argument(
        "--push_image", action="store_true",
        help="push the built image to --docker_image_repository",
    )
    parser.add_argument(
        "--image_name", default="",
        help="use this prebuilt image instead of building one",
    )
    parser.add_argument(
        "--master_resource_request", default="cpu=1,memory=2048Mi",
        help="k8s resource DSL for the master pod",
    )
    parser.add_argument("--master_resource_limit", default="")
    parser.add_argument("--master_pod_priority", default="")
    parser.add_argument(
        "--dry_run", action="store_true",
        help="print the master pod manifest instead of creating it",
    )


def client_parser(verb: str) -> argparse.ArgumentParser:
    """One sub-verb parser: the client accepts the full master flag
    surface (it forwards them as the master pod's container args —
    the flag namespace is the submit protocol, reference api.py:23-91)
    plus the client-only image/submit flags."""
    p = argparse.ArgumentParser(
        prog=f"elasticdl_tpu {verb}",
        description=f"ElasticDL-TPU client: {verb} job",
    )
    add_model_spec_args(p)
    add_master_args(p)
    add_client_args(p)
    return p


_CLIENT_ONLY_DESTS = frozenset(
    (
        "image_base",
        "docker_image_repository",
        "push_image",
        "image_name",
        "master_resource_request",
        "master_resource_limit",
        "master_pod_priority",
        "dry_run",
    )
)


def master_forward_args(args) -> List[str]:
    """Serialize a parsed arg-set back into master argv — the client
    assembles the master pod's container args from exactly the flags it
    parsed (reference api.py:23-91). Client-only flags are dropped;
    defaults are skipped so the manifest stays readable; the round trip
    `master_parser().parse_args(master_forward_args(a))` reproduces `a`
    (asserted by tests/test_client.py)."""
    argv: List[str] = []
    for action in master_parser()._actions:
        dest = action.dest
        if dest in ("help",) or dest in _CLIENT_ONLY_DESTS:
            continue
        if not hasattr(args, dest):
            continue
        value = getattr(args, dest)
        if isinstance(action, argparse._StoreTrueAction):
            if value:
                argv.append(action.option_strings[0])
            continue
        if not action.required and value == action.default:
            continue
        argv += [action.option_strings[0], str(value)]
    return argv


def ps_shard_forward_args(args) -> List[str]:
    """The model-spec flag subset a master forwards to each PS shard
    process (the shard resolves `optimizer()` from the model zoo the
    same way workers do)."""
    argv = [
        "--model_zoo", args.model_zoo,
        "--model_def", args.model_def,
        "--minibatch_size", str(args.minibatch_size),
        "--log_level", args.log_level,
    ]
    for flag in (
        "model_params",
        "dataset_fn",
        "loss",
        "optimizer",
        "eval_metrics_fn",
        "prediction_outputs_processor",
    ):
        value = getattr(args, flag)
        if value:
            argv += [f"--{flag}", value]
    return argv


ENV_COMPILE_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"


def default_compile_cache_dir() -> str:
    """`<checkout>/.jax_cache`: one fixed path for every job, because
    the path is part of the cache key — a directory that moves never
    hits."""
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, ".jax_cache")


def resolve_compile_cache_envs(args=None) -> dict:
    """Where the persistent XLA compile cache of spawned workers lives,
    as the environment to add to theirs.

    A JAX_COMPILATION_CACHE_DIR already in this process's environment
    is inherited by every child and the code sets no other: neither
    --compile_cache_dir nor --envs moves it. Otherwise "auto" (the
    default, and what a caller without args gets) is
    `default_compile_cache_dir()`, an explicit --compile_cache_dir is
    itself, and "" is off. MIN_COMPILE_TIME_SECS=0 caches every
    program — an elastic job's win is the replacement's boot, and its
    model may well compile in under the 1s default threshold.
    INCLUDE_METADATA_IN_KEY: jax leaves an instruction's `op_name` out
    of the cache's key by default, so a program whose scopes alone were
    edited came back with the executable, and the names, of the source
    before the edit (obs/hlo_scopes.py reads those names). With it an
    edited program compiles once more; a relaunched worker of the same
    checkout still hits."""
    tuning = {
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY": "1",
    }
    if ENV_COMPILE_CACHE_DIR in os.environ:
        return tuning
    cache_dir = getattr(args, "compile_cache_dir", "auto")
    if cache_dir == "auto":
        if getattr(args, "worker_backend", "process") != "process":
            return {}  # k8s pods need a shared volume: explicit path only
        cache_dir = default_compile_cache_dir()
    if not cache_dir:
        return {}
    return {ENV_COMPILE_CACHE_DIR: cache_dir, **tuning}


def compile_cache_dir() -> str:
    """Where a process started without flags keeps its cache: the
    environment's directory, else the default."""
    return os.environ.get(ENV_COMPILE_CACHE_DIR) or default_compile_cache_dir()


def enable_compile_cache():
    """The same placement for a process that compiles itself
    (chip_smoke's children): call before the first compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def worker_forward_args(args, worker_id: int, master_addr: str) -> List[str]:
    """The model-spec flag subset a master forwards to each worker
    (reference: master/main.py:229-255)."""
    argv = [
        "--worker_id", str(worker_id),
        "--master_addr", master_addr,
        "--model_zoo", args.model_zoo,
        "--model_def", args.model_def,
        "--minibatch_size", str(args.minibatch_size),
        "--local_updates", str(args.local_updates),
        "--transport_dtype", args.transport_dtype,
        "--step_pipeline", str(resolve_step_pipeline(args)),
        "--log_level", args.log_level,
    ]
    if getattr(args, "sync_dtype", ""):
        argv += ["--sync_dtype", args.sync_dtype]
    if getattr(args, "sync_compress", ""):
        argv += ["--sync_compress", args.sync_compress]
    if getattr(args, "overlap_sync", ""):
        argv += ["--overlap_sync", args.overlap_sync]
    if getattr(args, "master_candidates", ""):
        argv += ["--master_candidates", args.master_candidates]
    for flag in (
        "model_params",
        "dataset_fn",
        "loss",
        "optimizer",
        "eval_metrics_fn",
        "prediction_outputs_processor",
        "profile_dir",
    ):
        value = getattr(args, flag)
        if value:
            argv += [f"--{flag}", value]
    return argv
