"""Worker process entrypoint.

Re-design of the reference worker main
(elasticdl/python/worker/main.py:86-117): parse flags, open the gRPC
channel to the master, resolve the model spec from the model zoo, run
the task loop, exit 0 on clean completion.

Exit codes: 0 = job finished cleanly; 1 = crash;
EXIT_CODE_JOB_FAILED (2) = job finished but the master reported failed
(dropped poison) tasks — partial data must not look like success to
the pod phase / process supervisor, yet it must not be relaunched as a
crash either; EXIT_CODE_MASTER_UNREACHABLE (3) = the master stayed
unreachable past the RPC retry budget — the worker degrades gracefully
(exits instead of hanging) and the WorkerManager relaunches it, by
which time the master may be back.
"""

from __future__ import annotations

import os
import sys
import time

from elasticdl_tpu.api.model_spec import get_model_spec
from elasticdl_tpu.common.args import worker_parser
from elasticdl_tpu.common.constants import (
    ENV_WORKER_LOG_DIR,
    EXIT_CODE_JOB_FAILED,
    EXIT_CODE_MASTER_UNREACHABLE,
    MASTER_UPDATE_METHODS,
)
from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.common.timing import process_start_time
from elasticdl_tpu.obs import trace as obs_trace

logger = get_logger(__name__)


def _is_unreachable(e: BaseException) -> bool:
    """True when an error means 'peer endpoint gone past the retry
    budget' (the shared RetryPolicy already burned its attempts before
    this surfaced) rather than a worker-side bug. Walks the
    cause/context chain (same classification as
    worker.Worker._is_master_unreachable_exc): the sync and teardown
    layers wrap RPC errors, and a wrapped UNAVAILABLE exiting as an
    anonymous crash would cost the job a relaunch slot."""
    import grpc

    exc, hops = e, 0
    while exc is not None and hops < 8:
        if isinstance(exc, grpc.FutureTimeoutError):
            return True
        code = getattr(exc, "code", lambda: None)()
        if code in (
            grpc.StatusCode.UNAVAILABLE,
            grpc.StatusCode.DEADLINE_EXCEEDED,
            # a hard-stopped server (master SIGKILL cutover) tears
            # down in-flight calls as CANCELLED, not UNAVAILABLE
            grpc.StatusCode.CANCELLED,
        ):
            return True
        exc = exc.__cause__ or exc.__context__
        hops += 1
    return False


def _boot_handshake(client, primary_addr: str, candidates):
    """First master contact, with boot-time failover.

    A worker relaunched while a master cutover is in flight is handed
    the OLD master address in argv (the relaunching manager predates
    the adoption); without candidates it would stall the full handshake
    timeout against a dead endpoint and burn a relaunch slot. With
    candidates configured, fail the primary handshake fast, then probe
    the candidate set for the highest adopted `master_generation`
    responder — the same election rule as the in-job path
    (worker.Worker._await_master_failover): a standby that has not
    adopted yet answers UNAVAILABLE and is skipped, a zombie old
    master loses the generation comparison. On success the client is
    re-pointed IN PLACE (RpcClient.reconnect). Returns the GetPSConfig
    snapshot the rest of boot reads shard endpoints from."""
    try:
        client.wait_ready(timeout=5 if candidates else 60)
        # shard discovery: always ask the master (argv can go stale
        # across elastic relaunches; empty lists = classic single-PS /
        # in-master embedding store)
        return client.call("GetPSConfig", {})
    except Exception as e:
        if not candidates or not _is_unreachable(e):
            raise
        logger.warning(
            "master %s unreachable at boot (%s); probing %d failover "
            "candidate(s)", primary_addr, e, len(candidates),
        )
    import time

    import grpc

    from elasticdl_tpu.rpc.client import RpcClient

    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        best = None  # (master_generation, addr, cfg)
        for addr in candidates:
            probe = None
            try:
                probe = RpcClient(addr)
                cfg = probe.call("GetPSConfig", {}, timeout=2.0)
                gen = int(cfg.get("master_generation", 0) or 0)
                if best is None or gen > best[0]:
                    best = (gen, addr, cfg)
            except Exception:
                pass  # dead primary / still-gated standby: next one
            finally:
                if probe is not None:
                    try:
                        probe.close()
                    except Exception:
                        pass
        if best is not None:
            gen, addr, cfg = best
            logger.info(
                "boot failover: following master generation %d at %s",
                gen, addr,
            )
            client.reconnect(addr)
            return cfg
        time.sleep(0.5)
    # classified unreachable by the caller -> EXIT_CODE_MASTER_UNREACHABLE
    raise grpc.FutureTimeoutError(
        "no reachable master among candidates within the boot deadline"
    )


def main(argv=None) -> int:
    args = worker_parser().parse_args(argv)

    import logging

    logging.getLogger().setLevel(args.log_level.upper())
    # the phase timeline's way out of a process that ends by SIGKILL:
    # <EDL_WORKER_LOG_DIR>/worker-<id>.spans.jsonl; a relaunch appends
    obs_trace.start_span_file(
        os.environ.get(ENV_WORKER_LOG_DIR, ""), f"worker-{args.worker_id}"
    )
    started = process_start_time()
    t_backend = time.time()
    obs_trace.record_phase("setup.imports", started, t_backend - started)

    # the CPU is a device only when it was asked for: a worker that
    # finds no chip exits here instead of training on the CPU in silence
    from elasticdl_tpu.common.device import require_device
    from elasticdl_tpu.parallel.mesh import local_mesh

    device = require_device(f"worker {args.worker_id}")
    obs_trace.record_phase(
        "setup.backend_init", t_backend, time.time() - t_backend,
        {"platform": device["platform"]},
    )
    logger.info(
        "Worker %d boot: platform=%s device_kind=%s chips=%s",
        args.worker_id,
        device["platform"],
        device["device_kind"],
        device["chips"],
    )
    # dp mesh over every chip this process was given, so none sits idle
    # in silence; one chip is the trivial mesh and jits plain. CPU runs
    # are test runs (their "devices" are XLA_FLAGS virtual ones).
    mesh = local_mesh() if device["platform"] == "tpu" else None

    from elasticdl_tpu.rpc.client import RpcClient
    from elasticdl_tpu.worker.worker import Worker

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

    # master-failover candidates (master/migration.py): with these set,
    # a master cutover is ridden out in-job instead of via exit-3
    # relaunch, and the boot handshake itself fails over (parsed BEFORE
    # the handshake — a relaunched worker's argv addr may be the dead
    # pre-cutover master)
    candidates = [
        a.strip()
        for a in getattr(args, "master_candidates", "").split(",")
        if a.strip()
    ] or None
    # the master's update and model RPCs, both sides on the timeline
    client = RpcClient(args.master_addr, timeline=MASTER_UPDATE_METHODS)
    try:
        ps_cfg = _boot_handshake(client, args.master_addr, candidates)
    except Exception as e:
        if _is_unreachable(e):
            logger.error(
                "master %s unreachable past the retry budget; exiting %d "
                "for relaunch: %s",
                args.master_addr,
                EXIT_CODE_MASTER_UNREACHABLE,
                e,
            )
            return EXIT_CODE_MASTER_UNREACHABLE
        raise
    ps_endpoints = ps_cfg.get("endpoints") or None
    kv_endpoints = ps_cfg.get("kv_endpoints") or None
    if ps_endpoints:
        logger.info("sharded PS: %d endpoints", len(ps_endpoints))
    if kv_endpoints:
        logger.info("embedding KV: %d shards", len(kv_endpoints))
    worker = Worker(
        args.worker_id,
        client,
        spec,
        minibatch_size=args.minibatch_size,
        mesh=mesh,
        local_updates=args.local_updates,
        transport_dtype=args.transport_dtype,
        ps_endpoints=ps_endpoints,
        step_pipeline=args.step_pipeline,
        kv_endpoints=kv_endpoints,
        sync_dtype=args.sync_dtype or None,
        sync_compress=getattr(args, "sync_compress", "") or None,
        overlap_sync=getattr(args, "overlap_sync", "") or None,
        master_candidates=candidates,
    )
    # device-level tracing (SURVEY §5.1): a jax.profiler trace of the
    # whole task loop, viewable in TensorBoard/Perfetto/XProf. The
    # PhaseTimers in the worker cover host-side attribution; this
    # covers the XLA/device side.
    # Graceful teardown: the master deletes worker pods/processes both
    # at job end and on a policy stop (autoscaler shrink / QoS
    # preemption), SIGTERM first, SIGKILL after a grace period. Latch a
    # drain instead of raising: the run loop exits at the next task
    # boundary with every window synced and every report delivered, so
    # a preempted worker's tasks are fully settled (nothing requeues,
    # versions stay exact). A drain blocked past the grace period
    # degrades to the hard-kill path, which the elastic requeue covers.
    import signal

    signal.signal(signal.SIGTERM, lambda s, f: worker.request_drain())

    profiling = False
    if args.profile_dir:
        import jax

        trace_dir = os.path.join(
            args.profile_dir, f"worker-{args.worker_id}"
        )
        try:
            jax.profiler.start_trace(trace_dir)
            profiling = True
            logger.info("jax.profiler trace -> %s", trace_dir)
        except Exception:
            logger.exception("profiler start failed; continuing untraced")
    unreachable = False
    try:
        clean = worker.run()
    except Exception as e:
        if _is_unreachable(e):
            # graceful degradation: the control plane (master or a PS
            # shard) stayed gone through every retry — exit with the
            # distinct relaunch-eligible code instead of hanging or
            # dying as an anonymous crash; the dispatcher requeues the
            # in-flight task on the exit event
            logger.error(
                "RPC peer unreachable past the retry budget; exiting %d "
                "for relaunch: %s",
                EXIT_CODE_MASTER_UNREACHABLE,
                e,
            )
            unreachable = True
            clean = False
        else:
            raise
    finally:
        if profiling:
            import jax

            try:
                jax.profiler.stop_trace()
            except Exception:
                logger.exception("profiler stop failed")
        try:
            worker.close()
        except Exception:
            # teardown flushes the final sync over RPC — with the peer
            # already gone that fails too; it must not demote the
            # distinct exit code to an anonymous crash
            if not unreachable:
                raise
            logger.exception("teardown failed after unreachable peer")
        client.close()
    if unreachable:
        return EXIT_CODE_MASTER_UNREACHABLE
    return 0 if clean else EXIT_CODE_JOB_FAILED


if __name__ == "__main__":
    sys.exit(main())
