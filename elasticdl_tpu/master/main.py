"""Master process entrypoint: controller + sharder + parameter server.

Re-design of the reference master main
(elasticdl/python/master/main.py:67-309):

1. collect + count RecordIO shards -> TaskDispatcher (:36-64);
2. load the user model spec (job type inferred from data dirs, :111-136);
3. optionally boot the PS from --checkpoint_filename_for_init
   (servicer.py:80-84; required for evaluate/predict jobs);
4. start checkpoint/evaluation services (:138-172);
5. start the gRPC server (:197-223);
6. launch workers through the WorkerManager over a pod backend
   (:225-282) — `process` spawns local subprocesses, `k8s` creates pods;
7. poll dispatcher completion, save --output, tear down (:292-309).

Exit codes: 0 = success; 1 = boot/config error; 2 = job completed with
failed (dropped poison) tasks — partial data is not success.
"""

from __future__ import annotations

import os
import sys
import threading
import time

from elasticdl_tpu.common.args import (
    ENV_COMPILE_CACHE_DIR,
    master_parser,
    parse_envs,
    resolve_compile_cache_envs,
    validate_master_args,
    worker_forward_args,
)
from elasticdl_tpu.common.constants import (
    ENV_WORKER_LOG_DIR,
    MASTER_UPDATE_METHODS,
    JobType,
    WorkerManagerStatus,
)
from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.obs import trace as obs_trace

logger = get_logger(__name__)


def collect_shards(path: str) -> dict:
    """{file: record_count} for a RecordIO file or directory of shards
    (reference: master/main.py:36-64 counts via the recordio index)."""
    from elasticdl_tpu.data.recordio import count_records

    if not path:
        return {}
    if os.path.isfile(path):
        files = [path]
    else:
        # only regular files: a stray subdirectory (or socket) in the
        # data dir must not crash count_records at master boot
        files = sorted(
            p
            for f in os.listdir(path)
            if not f.startswith(".")
            and os.path.isfile(p := os.path.join(path, f))
        )
    shards = {f: count_records(f) for f in files}
    if not shards or not any(shards.values()):
        raise ValueError(f"no records found under {path!r}")
    return shards


def make_sample_batch_fn(training_data_dir: str):
    """Serves the first n raw records of the first training shard —
    standby workers AOT-compile against this sample (the master reads
    the same shards to count records, so access is a given)."""

    def fn(n: int):
        from elasticdl_tpu.data.recordio import RecordIOReader

        shards = collect_shards(training_data_dir)
        records: list = []
        # top up across shards: a short (or empty) first shard must not
        # shrink the sample below the minibatch — the standby would
        # AOT-compile a wrong-shape program and silently pay the full
        # compile on promotion anyway
        for path in sorted(shards):
            take = min(n - len(records), shards[path])
            if take > 0:
                with RecordIOReader(path) as reader:
                    records.extend(reader.read_range(0, take))
            if len(records) >= n:
                break
        if records and len(records) < n:
            logger.warning(
                "sample batch short: %d/%d records — standby pre-warm "
                "will compile a non-hot shape", len(records), n,
            )
        return records or None

    return fn


def build_master(args, job_type: str, cluster_backend=None):
    """Dispatcher + servicer + services, shared by main() and tests.
    `cluster_backend` (a K8sBackend) is required only when a sharded PS
    must run as dedicated pods (worker_backend=k8s + num_ps>0)."""
    from elasticdl_tpu.api.model_spec import get_model_spec
    from elasticdl_tpu.master.embedding_store import EmbeddingStore
    from elasticdl_tpu.master.sparse_optimizer import SparseOptimizer

    spec = get_model_spec(
        model_zoo=args.model_zoo,
        model_def=args.model_def,
        model_params=args.model_params,
        dataset_fn=args.dataset_fn,
        loss=args.loss,
        optimizer=args.optimizer,
        eval_metrics_fn=args.eval_metrics_fn,
        prediction_outputs_processor=args.prediction_outputs_processor,
    )

    training = (
        collect_shards(args.training_data_dir)
        if job_type
        in (JobType.TRAINING_ONLY, JobType.TRAINING_WITH_EVALUATION)
        else {}
    )
    evaluation = (
        collect_shards(args.evaluation_data_dir)
        if args.evaluation_data_dir
        else {}
    )
    prediction = (
        collect_shards(args.prediction_data_dir)
        if job_type == JobType.PREDICTION_ONLY
        else {}
    )
    store = sparse_opt = None
    kv_group = None
    ps_group = None
    agg_group = None
    # one try covers EVERYTHING after the first shard spawn: shard
    # subprocesses/pods must not outlive a failed boot, whichever later
    # step (optimizer construction, PS group boot, servicer wiring)
    # raises
    try:
        if spec.embedding_specs:
            if getattr(args, "num_kv_shards", 0) > 0:
                # scale-out embedding service: tables live behind N KV
                # shard endpoints (kv_group.py); the master's sparse
                # optimizer and checkpoints reach them through the same
                # store interface, and workers hit them DIRECTLY
                from elasticdl_tpu.master.kv_group import KVShardGroup

                kv_mode = getattr(args, "kv_mode", "process")
                if getattr(args, "worker_backend", "") == "k8s":
                    kv_mode = "k8s"  # pods: worker-reachable endpoints
                kv_group = KVShardGroup(
                    args.num_kv_shards,
                    mode=kv_mode,
                    k8s_backend=(
                        cluster_backend if kv_mode == "k8s" else None
                    ),
                )
                kv_group.start()
                store = kv_group.store()
            else:
                store = EmbeddingStore()
            sparse_opt = SparseOptimizer(
                store, **(spec.sparse_optimizer or {})
            )

        # Sharded PS (master/ps_shard.py): the dense model behind N
        # endpoints; workers push/pull slices in parallel while the
        # master keeps the control plane. See ps_shard.py for the
        # consistency model and validate_ps_args for the protocol
        # constraints. Elastic-embedding models compose: dense slices
        # ride the PS shards while the sparse IndexedRows ride
        # ReportWindowMeta to the master's sparse optimizer (whose
        # store may itself be the KV shard group).
        if getattr(args, "num_ps", 0) > 0:
            from elasticdl_tpu.common.args import (
                ps_shard_forward_args,
                validate_ps_args,
            )
            from elasticdl_tpu.master.ps_group import PSShardGroup

            validate_ps_args(args)
            # k8s jobs need worker-REACHABLE shard endpoints: localhost
            # subprocesses inside the master pod are invisible to
            # worker pods, so the shards become dedicated pods
            # addressed by pod IP
            mode = getattr(args, "ps_mode", "process")
            if getattr(args, "worker_backend", "") == "k8s":
                mode = "k8s"
            ps_group = PSShardGroup(
                args.num_ps,
                mode=mode,
                optimizer_factory=spec.optimizer,
                shard_argv=ps_shard_forward_args(args),
                grads_to_wait=args.grads_to_wait,
                use_async=args.use_async,
                lr_staleness_modulation=args.lr_staleness_modulation,
                staleness_window=args.staleness_window,
                k8s_backend=cluster_backend if mode == "k8s" else None,
                num_workers=args.num_workers,
                fanin_combine=(
                    True if getattr(args, "fanin_combine", False) else None
                ),
            )
            ps_group.start()

            # Aggregation tree (agg/): host-local presum nodes between
            # the workers and the shards — master-side fan-in drops
            # from #workers to #aggregators. Built AFTER the PS group
            # because the nodes need the upstream shard endpoints.
            if getattr(args, "num_agg", 0) > 0:
                if getattr(args, "worker_backend", "") == "k8s":
                    # no pod builder for aggregators yet: worker pods
                    # could not reach localhost nodes, so degrade to
                    # direct pushes rather than strand the tree
                    logger.warning(
                        "--num_agg is ignored under worker_backend=k8s "
                        "(no aggregator pod builder): workers push "
                        "direct to the PS shards"
                    )
                else:
                    from elasticdl_tpu.agg.group import AggGroup

                    agg_group = AggGroup(
                        args.num_agg,
                        list(ps_group.endpoints),
                        mode=getattr(args, "agg_mode", "process"),
                    )
                    agg_group.start()

        return _finish_build(args, job_type, spec, ps_group, store,
                             sparse_opt, training, evaluation, prediction,
                             kv_group=kv_group, agg_group=agg_group)
    except Exception:
        if agg_group is not None:
            agg_group.stop()
        if ps_group is not None:
            ps_group.stop()
        if kv_group is not None:
            kv_group.stop()
        raise


def _finish_build(args, job_type, spec, ps_group, store, sparse_opt,
                  training, evaluation, prediction, kv_group=None,
                  agg_group=None):
    from elasticdl_tpu.master.checkpoint import (
        CheckpointService,
        load_model_file,
    )
    from elasticdl_tpu.master.evaluation_service import EvaluationService
    from elasticdl_tpu.master.ps_optimizer import PSOptimizer
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    # boot-from-checkpoint (reference: servicer.py:80-84) — the only
    # way evaluate/predict jobs get params, and the resume path for
    # training jobs
    init_params = init_aux = None
    init_version = 0
    ckpt_opt_state = None
    if args.checkpoint_filename_for_init:
        model = load_model_file(args.checkpoint_filename_for_init)
        init_params, init_aux = model.params, model.aux
        init_version = model.version
        ckpt_opt_state = getattr(model, "opt_state", None)
        if store is not None and model.embeddings:
            store.restore(model.embeddings)
        logger.info(
            "Initialized model v%d from %s",
            init_version,
            args.checkpoint_filename_for_init,
        )

    from elasticdl_tpu.common.constants import (
        ENV_SCHED_MAX_BACKUPS,
        ENV_SCHED_SPEC_FACTOR,
        ENV_SCHED_SPEC_PCTL,
        ENV_SCHED_SPECULATE,
    )

    speculate = bool(getattr(args, "speculate", False)) or os.environ.get(
        ENV_SCHED_SPECULATE, ""
    ) in ("1", "true")
    dispatcher = TaskDispatcher(
        training,
        evaluation,
        prediction,
        args.records_per_task,
        args.num_epochs,
        eval_model_version=init_version,
        speculate=speculate,
        spec_percentile=float(os.environ.get(ENV_SCHED_SPEC_PCTL, "") or 0.5),
        spec_factor=float(os.environ.get(ENV_SCHED_SPEC_FACTOR, "") or 1.5),
        max_backups=int(os.environ.get(ENV_SCHED_MAX_BACKUPS, "") or 2),
        # per-step sync grads carry no dedup key, so a backup's pushes
        # could double-apply — speculation covers training tasks only
        # in window mode (eval/predict tasks mutate nothing and are
        # always safe to speculate)
        speculate_training=args.local_updates > 0,
    )

    with_eval = job_type in (
        JobType.TRAINING_WITH_EVALUATION,
        JobType.EVALUATION_ONLY,
    )
    ckpt = CheckpointService(
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_steps=args.checkpoint_steps,
        keep_checkpoint_max=args.keep_checkpoint_max,
        include_evaluation=with_eval,
        embedding_store=store,
    )
    ps_opt = PSOptimizer(spec.optimizer())
    if init_params is not None and ckpt_opt_state:
        kind = ckpt_opt_state.get("kind")
        if kind == "single" and ps_group is None:
            # exact resume: the dense optimizer continues its
            # checkpointed momentum/Adam moments instead of cold-starting
            ps_opt.restore_state(init_params, ckpt_opt_state["leaves"])
            logger.info("Restored dense optimizer state from the checkpoint")
        elif kind == "single":
            logger.warning(
                "checkpoint has single-PS optimizer state but this job "
                "runs --num_ps shards: shard optimizers start COLD "
                "(resume is not exact)"
            )
        elif kind == "sharded" and ps_group is None:
            logger.warning(
                "checkpoint has sharded optimizer state but this job "
                "runs a single PS: the optimizer starts COLD "
                "(resume is not exact)"
            )
    servicer = MasterServicer(
        grads_to_wait=args.grads_to_wait,
        optimizer=ps_opt,
        task_dispatcher=dispatcher,
        checkpoint_service=ckpt,
        embedding_store=store,
        sparse_optimizer=sparse_opt,
        init_params=init_params,
        init_aux=init_aux,
        init_version=init_version,
        use_async=args.use_async,
        lr_staleness_modulation=args.lr_staleness_modulation,
        staleness_window=args.staleness_window,
        ps_group=ps_group,
        kv_group=kv_group,
        agg_group=agg_group,
    )
    if ps_group is not None and init_params is not None:
        from elasticdl_tpu.common import codec

        ps_group.ensure_init(codec.ravel_np(init_params), init_version)
        if ckpt_opt_state and ckpt_opt_state.get("kind") == "sharded":
            try:
                ps_group.restore_opt(ckpt_opt_state["shards"])
                logger.info(
                    "Restored per-shard optimizer state (exact resume)"
                )
            except ValueError as e:
                # a resized job must still resume (params re-split
                # fine); only the optimizer moments start cold — same
                # degradation as the other topology mismatches
                logger.warning(
                    "optimizer state not restored (%s): shard "
                    "optimizers start COLD (resume is not exact)", e,
                )
    tb_service = None
    if getattr(args, "tensorboard_log_dir", ""):
        from elasticdl_tpu.master.tensorboard_service import TensorBoardService

        tb_service = TensorBoardService(args.tensorboard_log_dir)
        servicer.set_train_loss_hook(tb_service.write_train_loss)
    eval_service = None
    if with_eval:
        eval_service = EvaluationService(
            ckpt,
            dispatcher,
            eval_steps=args.eval_steps,
            start_delay_secs=args.eval_start_delay_secs,
            throttle_secs=args.eval_throttle_secs,
            # a throttle implies the reference's time-based trigger
            # thread (evaluation_service.py:55-87)
            time_based=args.eval_throttle_secs > 0
            and job_type == JobType.TRAINING_WITH_EVALUATION,
            current_model_fn=servicer.get_params_copy,
            metrics_writer=(
                tb_service.write_eval_metrics if tb_service else None
            ),
        )
        dispatcher.set_evaluation_service(eval_service)
        servicer.set_evaluation_service(eval_service)
    # the servicer owns the sink's lifetime so callers of build_master
    # (main, tests, benches) can tear it down uniformly
    servicer.tb_service = tb_service
    return spec, dispatcher, servicer, eval_service, ckpt


def make_backend(args):
    if args.worker_backend == "process":
        from elasticdl_tpu.cluster.pod_backend import ProcessBackend
        from elasticdl_tpu.common.device import (
            chip_shares,
            cpu_requested,
            probe_device,
        )

        # one process for each chip: active workers and warm standbys
        # (a standby pre-compiles on a device) each get an even share
        # of the host's chips. The count comes from a short-lived
        # child — this process never initialises a TPU backend.
        shares = None
        if not (cpu_requested() or cpu_requested(parse_envs(args.envs))):
            t_probe = time.time()
            found = probe_device()
            obs_trace.record_phase(
                "setup.probe_device", t_probe, time.time() - t_probe
            )
            if found["platform"] != "tpu":
                raise ValueError(
                    f"no TPU on this host (jax found {found['platform']!r}) "
                    "and JAX_PLATFORMS=cpu was not set"
                )
            shares = chip_shares(
                found["chips"], args.num_workers + args.num_standby_workers
            )
            logger.info(
                "%s x%d: chip shares %s",
                found["device_kind"], len(found["chips"]), shares,
            )
        return ProcessBackend(
            log_dir=os.environ.get(ENV_WORKER_LOG_DIR, ""),
            chip_shares=shares,
        )
    from elasticdl_tpu.cluster.k8s_backend import K8sBackend

    return K8sBackend(
        job_name=args.job_name,
        image=args.worker_image,
        namespace=args.namespace,
        resource_request=args.worker_resource_request,
        resource_limit=args.worker_resource_limit,
        pod_priority=args.worker_pod_priority,
        volume=args.volume,
        envs=parse_envs(args.envs),
        cluster_spec=args.cluster_spec,
        ps_resource_request=getattr(args, "ps_resource_request", ""),
        ps_resource_limit=getattr(args, "ps_resource_limit", ""),
    )


def main(argv=None) -> int:
    # host math (PS optimizer applies, checkpoint assembly): the chips
    # belong to the workers this process spawns, so it must never
    # initialise a TPU backend — whatever the environment says
    from elasticdl_tpu.common.device import pin_cpu
    from elasticdl_tpu.common.timing import process_start_time

    pin_cpu()
    args = master_parser().parse_args(argv)
    # the phase timeline's way out of a process that ends by SIGKILL:
    # spans go to <tensorboard_log_dir>/master.spans.jsonl as they close
    obs_trace.start_span_file(
        getattr(args, "tensorboard_log_dir", "") or "", "master"
    )
    started = process_start_time()
    obs_trace.record_phase("setup.imports", started, time.time() - started)
    try:
        job_type = validate_master_args(args)
        # fail fast on a bad EDL_SCHED_QOS env (the flag itself is
        # choice-checked by argparse) before anything is built
        from elasticdl_tpu.sched import resolve_qos

        qos = resolve_qos(getattr(args, "qos_class", ""))
    except ValueError as e:
        logger.error("invalid arguments: %s", e)
        return 1

    import logging

    logging.getLogger().setLevel(args.log_level.upper())

    from elasticdl_tpu.master.worker_manager import WorkerManager
    from elasticdl_tpu.rpc.server import RpcServer

    # the cluster backend exists before build_master: a k8s sharded PS
    # creates its shard pods through it during the build
    try:
        backend = make_backend(args)
    except ValueError as e:
        logger.error("master boot failed: %s", e)
        return 1
    try:
        spec, dispatcher, servicer, eval_service, ckpt = build_master(
            args, job_type, cluster_backend=backend
        )
    except (ValueError, OSError) as e:
        # bad data dir / unreadable shards / malformed checkpoint are
        # config errors: exit 1 cleanly, like validate_master_args
        logger.error("master boot failed: %s", e)
        backend.stop()
        return 1
    if job_type in (JobType.EVALUATION_ONLY, JobType.PREDICTION_ONLY):
        if not servicer.model_initialized():
            logger.error("evaluate/predict jobs need an initialized model")
            if servicer.agg_group is not None:
                servicer.agg_group.stop()
            if servicer.ps_group is not None:
                servicer.ps_group.stop()
            backend.stop()
            return 1
    if job_type == JobType.EVALUATION_ONLY and eval_service is not None:
        from elasticdl_tpu.common.messages import TaskType

        eval_service.start_standalone_job(
            servicer.version, dispatcher.pending_count(TaskType.EVALUATION)
        )

    server = RpcServer(
        servicer.handlers(), port=args.port, timers=servicer.timers,
        timed_methods=MASTER_UPDATE_METHODS,
    )
    server.start()
    # the master's own RPC admission counters ride GetSchedStats, the
    # same surface the ps/kv shards expose through their stats() RPC
    servicer.set_admission_stats_fn(server.admission_stats)
    if args.worker_backend == "k8s":
        # worker pods cannot reach the master via localhost: advertise
        # the pod IP (k8s downward API) or the host's resolvable name
        import socket

        host = os.environ.get("MY_POD_IP") or socket.getfqdn()
    else:
        host = "localhost"
    addr = f"{host}:{server.port}"
    logger.info("Master (%s job) listening on %s", job_type, addr)

    if servicer.tb_service is not None and args.worker_backend == "k8s":
        # in-cluster: serve the summaries so the TensorBoard k8s
        # Service (created by the client) has a target on :6006
        servicer.tb_service.start_tensorboard_process()
    # shared XLA compile cache: incumbents populate it on first boot,
    # and every relaunched replacement / promoted standby reuses the
    # compiled programs instead of re-paying the XLA compile. The
    # resolver alone places it — --envs cannot move it.
    worker_envs = parse_envs(args.envs)
    if worker_envs.pop(ENV_COMPILE_CACHE_DIR, None) is not None:
        logger.warning(
            "--envs %s is ignored: set it in the master's environment or "
            "pass --compile_cache_dir", ENV_COMPILE_CACHE_DIR,
        )
    worker_envs.update(resolve_compile_cache_envs(args))
    manager = WorkerManager(
        backend,
        dispatcher,
        num_workers=args.num_workers,
        worker_argv_fn=lambda wid: worker_forward_args(args, wid, addr),
        envs=worker_envs,
        max_relaunches=args.max_worker_relaunches,
        num_standby=args.num_standby_workers,
    )
    # migration plane (master/migration.py): publish the job manifest
    # continuously so a standby master can adopt this job with no
    # checkpoint file — planned hand-off or crash failover
    from elasticdl_tpu.master.migration import attach_manifest_publisher

    attach_manifest_publisher(servicer, dispatcher, manager)
    if args.num_standby_workers:
        servicer.set_standby_fn(manager.is_standby)
        if args.training_data_dir:
            servicer.set_sample_batch_fn(
                make_sample_batch_fn(args.training_data_dir)
            )
    # -- policy plane (elasticdl_tpu/sched/) -----------------------------
    from elasticdl_tpu.common.constants import (
        ENV_SCHED_AUTOSCALE,
        ENV_SCHED_COOLDOWN_SECS,
        ENV_SCHED_DOWN_FRAC,
        ENV_SCHED_UP_FRAC,
    )
    from elasticdl_tpu.sched import (
        PhaseStatsAggregator,
        UtilizationAutoscaler,
        merge_phase_snapshots,
    )

    aggregator = PhaseStatsAggregator()
    servicer.set_phase_stats_sink(aggregator.ingest)
    autoscaler = None
    if getattr(args, "autoscale", False) or os.environ.get(
        ENV_SCHED_AUTOSCALE, ""
    ) in ("1", "true"):
        autoscaler = UtilizationAutoscaler(
            aggregator,
            manager,
            min_workers=args.min_workers,
            max_workers=args.max_workers,
            up_threshold=float(os.environ.get(ENV_SCHED_UP_FRAC, "") or 0.6),
            down_threshold=float(
                os.environ.get(ENV_SCHED_DOWN_FRAC, "") or 0.5
            ),
            cooldown_secs=float(
                os.environ.get(ENV_SCHED_COOLDOWN_SECS, "") or 5.0
            ),
            # scaling up is pointless with an empty todo queue: the new
            # worker would boot straight into WAIT
            pending_fn=dispatcher.pending_count,
        )
        logger.info(
            "Autoscaler armed: min=%d max=%d", args.min_workers,
            args.max_workers,
        )

    def _sched_stats() -> dict:
        out = {"qos_class": qos, "workers": manager.snapshot()}
        out.update(dispatcher.sched_stats())
        if autoscaler is not None:
            out["autoscaler"] = autoscaler.stats()
        out["phases"] = aggregator.snapshot()
        # cumulative seconds and counts beside the sliding share: the
        # fleet's merged, and the master's own phases
        out["phases"]["cumulative"] = merge_phase_snapshots(
            aggregator.latest_cumulative().values()
        )
        out["phases"]["master"] = servicer.timers.snapshot()
        # goodput accounting (completed/requeued/recomputed/
        # drain-flushed records) rides the same stats surface the
        # churn harness and operators already poll
        out["goodput"] = dispatcher.goodput_stats()
        return out

    servicer.set_sched_stats_fn(_sched_stats)
    # drain attribution: task completions reported by a worker that a
    # scale-down / QoS preemption is draining count as drain flushes
    dispatcher.set_draining_fn(manager.is_policy_stopped)
    # -- observability plane (elasticdl_tpu/obs/) ------------------------
    # crash flight recorder: an uncaught master exception dumps the
    # structured event ring (fences, chaos faults, recoveries,
    # autoscale decisions) as a JSON postmortem artifact
    from elasticdl_tpu.obs import flight as obs_flight
    from elasticdl_tpu.obs import metrics as obs_metrics

    obs_flight.install_crash_dump()

    def _phase_collector(sink):
        # fleet PhaseTimers, cumulative per (phase, worker) — the same
        # feed GetSchedStats exposes, under declared edl_* names.
        # Autoscaler/arbiter counters self-report at decision sites.
        for wid, phases in aggregator.latest_cumulative().items():
            for name, cell in (phases or {}).items():
                sink.counter(
                    "edl_phase_seconds_total",
                    float(cell.get("seconds", 0.0)),
                    phase=name,
                    worker=str(wid),
                )
                sink.counter(
                    "edl_phase_count_total",
                    float(cell.get("count", 0.0)),
                    phase=name,
                    worker=str(wid),
                )

    obs_metrics.get_registry().register_collector(_phase_collector)
    ps_dead = threading.Event()
    recovery = None
    if servicer.ps_group is not None or servicer.kv_group is not None:
        # Shard recovery plane (master/recovery.py): a dead PS/KV
        # shard is fenced, relaunched at a bumped generation, and
        # restored (worker flat-buffer upload + opt-state mirror for
        # PS; ring-pair mirror snapshot for KV). The job fails fast
        # ONLY when a shard is unrecoverable (no restore source before
        # the deadline) — the pre-recovery behavior, kept as the
        # degraded rung.
        from elasticdl_tpu.master.recovery import RecoveryPlane

        recovery = RecoveryPlane(
            servicer,
            ps_group=servicer.ps_group,
            kv_group=servicer.kv_group,
            agg_group=servicer.agg_group,
            on_unrecoverable=lambda kind, sid: ps_dead.set(),
        )
        servicer.set_recovery_plane(recovery)
        recovery.start()
        manager.on_shard_failure = recovery.on_shard_failure
        # fallback when the plane is torn down first (see finally)
        manager.on_ps_failure = lambda sid: ps_dead.set()
    t_spawn = time.time()
    manager.start_workers()
    obs_trace.record_phase(
        "setup.spawn_workers", t_spawn, time.time() - t_spawn,
        {"workers": args.num_workers},
    )
    if autoscaler is not None:
        autoscaler.start()
    logger.info("Worker manager status: %s", WorkerManagerStatus.RUNNING)

    exit_code = 0
    try:
        # reference main loop polls every 30s (main.py:292-300); poll
        # faster here — process workers finish in seconds under test
        while not dispatcher.finished():
            if ps_dead.is_set():
                logger.error(
                    "a PS/KV shard is unrecoverable: aborting the job"
                )
                exit_code = 2
                break
            if manager.all_exited():
                logger.error(
                    "all workers exited (relaunch budget spent) with "
                    "tasks outstanding"
                )
                exit_code = 2
                break
            time.sleep(0.5)
        while (
            exit_code == 0
            and eval_service is not None
            and eval_service.has_pending()
        ):
            time.sleep(0.2)
        if exit_code == 0 and dispatcher.has_failed_tasks():
            logger.error("job completed with dropped (poison) tasks")
            exit_code = 2
        if exit_code == 0 and args.output and servicer.model_initialized():
            servicer.save_latest_checkpoint(args.output)
            logger.info("Final model saved to %s", args.output)
    finally:
        logger.info("Worker manager status: %s", WorkerManagerStatus.FINISHED)
        if autoscaler is not None:
            autoscaler.stop()
        # disarm BEFORE teardown deletes shard pods: their DELETED
        # events are expected here, not a mid-job shard death
        manager.on_shard_failure = None
        manager.on_ps_failure = None
        if recovery is not None:
            recovery.stop()
        manager.stop_relaunch_and_remove_workers()
        ckpt.close()  # queued async checkpoint writes must land
        if eval_service is not None:
            eval_service.stop()
        # shard pods/processes and the watch free BEFORE any
        # TensorBoard keep-alive: serving summaries needs none of them,
        # and keep_running can block for days
        if servicer.agg_group is not None:
            # before the PS group: in-flight combined forwards fail
            # fast against live shards instead of hanging on dead ones
            servicer.agg_group.stop()
        if servicer.ps_group is not None:
            servicer.ps_group.stop()
        if servicer.kv_group is not None:
            servicer.kv_group.stop()
        backend.stop()
        server.stop()
        if servicer.tb_service is not None:
            if (
                exit_code == 0
                and getattr(args, "keep_tensorboard_running", False)
                and servicer.tb_service.is_active()
            ):
                # reference master/main.py:311-324: the job is done but
                # the master stays up serving TensorBoard until the
                # tensorboard process dies / the pod is deleted
                servicer.tb_service.keep_running()
            servicer.tb_service.close()
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
