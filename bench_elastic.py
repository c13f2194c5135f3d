"""Elastic-throughput-retention benchmark — the north-star metric.

BASELINE.md's target for this framework is *throughput retention under
50% worker preemption* (>=95% on a preemptible TPU pool). This bench
measures exactly that, in process-mode on CPU so it runs anywhere:

1. **stable run**: N worker subprocesses train a model-zoo conv net
   through the real master (gRPC PS + dispatcher + WorkerManager),
   and we measure steady-state images/sec from the dispatcher's
   completed-record counter — the clock starts at the first completed
   task, so worker boot (python + jax import + compile) is excluded
   from BOTH runs identically.
2. **churn run**: same job, but once 25% of the records are trained,
   HALF the workers are SIGKILLed (a real preemption: no cleanup, no
   final sync). The WorkerManager must detect the deaths, requeue
   their in-flight shards, and relaunch replacements; throughput is
   measured over the whole post-warmup window, relaunch transient
   included.

    retention = churn_images_per_sec / stable_images_per_sec

The run fails loudly if the churn job does not complete, drops tasks,
or never relaunches. Prints ONE JSON line:

  {"metric": "elastic_throughput_retention_50pct_kill", "value": R,
   "unit": "ratio", "stable_images_per_sec": ..., "churn_images_per_sec": ...,
   "relaunches": ..., "target": 0.95}

Reference: the procedure `kubectl delete pod` + watch recovery that the
reference only documents manually (elasticdl/doc/elastic_scheduling.md);
BASELINE.md "throughput retention under 50% worker preemption".
"""

import json
import os
import signal
import sys
import tempfile
import time

# everything on CPU: N worker processes can't share the one TPU chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

N_WORKERS = int(os.environ.get("EDL_ELASTIC_BENCH_WORKERS", 4))
KILL_FRACTION = 0.5
# repeated kill waves at evenly spaced progress points in
# [KILL_FIRST, KILL_LAST] — BASELINE.md's regime is SUSTAINED churn on
# a pool, not one preemption event
KILL_WAVES = int(os.environ.get("EDL_ELASTIC_BENCH_WAVES", 3))
KILL_FIRST, KILL_LAST = 0.25, 0.75
SEEDS = int(os.environ.get("EDL_ELASTIC_BENCH_SEEDS", 2))
# standalone continuation: run seeds [BASE, BASE+SEEDS) — lets a
# truncated multi-seed session finish its remaining seeds in a second
# invocation with identical data/protocol
SEED_BASE = int(os.environ.get("EDL_ELASTIC_BENCH_SEED_BASE", 0))
MINIBATCH = 64
RECORDS_PER_TASK = 128  # = one full window per task (no ragged
# tails -> exactly one compiled program per worker)
# Window size is a real elastic-design axis: a preemption loses the
# current un-flushed window (plus in-flight syncs), so loss-per-kill
# scales with LOCAL_UPDATES x MINIBATCH while the sync frequency it
# buys only matters on high-latency links. Against a localhost master
# the sync round is sub-ms, so SHORT windows are the correct
# deployment config here: 2 steps = 128 records exposed per kill
# instead of 8 x 64 = 512 (measured ~4.7% -> ~1.2% of the churn
# window re-trained). Window mode (not per-step) is still the subject:
# the per-step RPC path would measure the PS lock with 4 workers on
# one host.
LOCAL_UPDATES = 2
# mnist (light conv) rather than cifar: the CI/bench host can be a
# single core, and the subject here is the elastic RUNTIME — relaunch,
# requeue, warm restart — not MXU throughput (bench.py covers that)
MODEL_DEF = "mnist_functional_api.custom_model"
IMAGE_SHAPE = (28, 28, 1)


def _write_data(tmp, n_records, seed=0):
    from elasticdl_tpu.models.record_codec import write_synthetic_image_records

    per_shard = n_records // 4
    assert per_shard % RECORDS_PER_TASK == 0, "shards must be whole tasks"
    for i in range(4):
        write_synthetic_image_records(
            os.path.join(tmp, f"shard-{i}.rio"),
            per_shard,
            IMAGE_SHAPE,
            10,
            seed=seed * 4 + i,
        )


def run_job(
    data_dir,
    n_records,
    *,
    churn: bool,
    epochs: int,
    cache_dir: str,
    standby: int = 0,
    time_limit: float = 0.0,
):
    from elasticdl_tpu.cluster.pod_backend import ProcessBackend
    from elasticdl_tpu.common.args import (
        master_parser,
        resolve_compile_cache_envs,
        worker_forward_args,
    )
    from elasticdl_tpu.master.main import (
        build_master,
        make_sample_batch_fn,
    )
    from elasticdl_tpu.master.worker_manager import WorkerManager
    from elasticdl_tpu.rpc.server import RpcServer

    args = master_parser().parse_args(
        [
            "--model_zoo", os.path.join(os.path.dirname(__file__), "elasticdl_tpu", "models"),
            "--model_def", MODEL_DEF,
            "--minibatch_size", str(MINIBATCH),
            "--training_data_dir", data_dir,
            "--records_per_task", str(RECORDS_PER_TASK),
            "--num_epochs", str(epochs),
            "--grads_to_wait", "1",
            "--local_updates", str(LOCAL_UPDATES),
            "--num_workers", str(N_WORKERS),
            "--worker_backend", "process",
            "--compile_cache_dir", cache_dir,
        ]
    )
    spec, dispatcher, servicer, _, _ = build_master(args, "training")
    server = RpcServer(servicer.handlers(), port=0)
    server.start()
    addr = f"localhost:{server.port}"
    backend = ProcessBackend(
        log_dir=os.path.join(data_dir, "logs-churn" if churn else "logs-stable")
    )
    manager = WorkerManager(
        backend,
        dispatcher,
        num_workers=N_WORKERS,
        worker_argv_fn=lambda wid: worker_forward_args(args, wid, addr),
        envs={
            "JAX_PLATFORMS": "cpu",
            # the framework's --compile_cache_dir feature: replacements
            # and standbys reuse the incumbents' compiled programs
            **resolve_compile_cache_envs(args),
            # Sync depth stays at the framework default (workers
            # inherit the bench environment, so EDL_SYNC_DEPTH set on
            # the bench reaches them): depth 0 was measured WORSE here
            # (the serialized chain amplifies contention during
            # churn), and in-flight exposure is already bounded by the
            # short windows above.
        },
        max_relaunches=2 * N_WORKERS,
        num_standby=standby,
    )
    if standby:
        servicer.set_standby_fn(manager.is_standby)
        servicer.set_sample_batch_fn(make_sample_batch_fn(data_dir))
    total = n_records * epochs
    # kill WAVES: 50% of the live active pool SIGKILLed at each of
    # KILL_WAVES evenly spaced progress points — sustained churn, not a
    # single preemption event
    if KILL_WAVES > 1:
        step_frac = (KILL_LAST - KILL_FIRST) / (KILL_WAVES - 1)
        kill_points = [
            int(total * (KILL_FIRST + i * step_frac))
            for i in range(KILL_WAVES)
        ]
    else:
        kill_points = [int(total * KILL_FIRST)]
    waves_done = 0
    launch = time.time()
    manager.start_workers()
    t0 = c0 = None

    def kill_half_alive():
        from elasticdl_tpu.cluster.pod_backend import PodPhase

        # candidates must have a LIVE pid: a worker SIGKILLed last wave
        # can still show RUNNING until the watcher reports, and a
        # pid-less victim would silently shrink the killed fraction
        alive = [
            wid
            for wid, ph in manager.phases().items()
            if ph in (PodPhase.PENDING, PodPhase.RUNNING)
            and not manager.is_standby(wid)
            and backend.pid_of(wid)
        ]
        victims = sorted(alive)[: max(1, int(len(alive) * KILL_FRACTION))]
        n = 0
        for wid in victims:
            pid = backend.pid_of(wid)
            if pid:
                try:
                    os.kill(pid, signal.SIGKILL)
                    n += 1
                except ProcessLookupError:
                    # victim died on its own between pid_of and the
                    # kill: count one fewer rather than aborting a
                    # multi-hour multi-seed run
                    pass
        return n, len(alive)

    try:
        # churn runs may be boot-aware-sized to many epochs on a slow
        # host (see main); give them proportional headroom
        limit = time_limit or (3600.0 if churn else 1800.0)
        deadline = time.time() + limit
        while not dispatcher.finished():
            if time.time() > deadline:
                raise RuntimeError(f"job did not finish in {limit:.0f}s")
            if manager.all_exited():
                raise RuntimeError("all workers exited with tasks left")
            done = dispatcher.completed_records()
            if t0 is None and done > 0:
                # steady-state clock: starts at first completed task so
                # initial worker boot is excluded from both runs
                t0, c0 = time.time(), done
            if (
                churn
                and waves_done < len(kill_points)
                and done >= kill_points[waves_done]
            ):
                n, alive = kill_half_alive()
                waves_done += 1
                print(
                    f"bench_elastic: wave {waves_done}/{len(kill_points)}: "
                    f"killed {n}/{alive} live workers at {done}/{total} "
                    "records",
                    file=sys.stderr,
                )
            time.sleep(0.05)
        elapsed = time.time() - t0
        processed = dispatcher.completed_records() - c0
        assert not dispatcher.has_failed_tasks(), "job dropped tasks"
        if churn:
            assert waves_done == len(kill_points), (
                f"only {waves_done}/{len(kill_points)} kill waves fired "
                "before the job finished — size the run longer or reduce "
                "EDL_ELASTIC_BENCH_WAVES"
            )
            assert manager.relaunches() >= 1, "no worker was relaunched"
        # boot = spawn -> first completed task: the cost a relaunched
        # replacement re-pays (python + jax import + jit compile)
        return (
            processed / elapsed,
            manager.relaunches(),
            t0 - launch,
            manager.promotions(),
            waves_done,
        )
    finally:
        manager.stop_relaunch_and_remove_workers()
        backend.stop()
        server.stop()


def _boot_sched_job(tmp, tag, n_records, epochs, num_workers, seed, extra=()):
    """Boot one window-mode ProcessBackend job (its own master/server/
    manager) for the sched contention section. Caller polls and stops."""
    from elasticdl_tpu.cluster.pod_backend import ProcessBackend
    from elasticdl_tpu.common.args import (
        master_parser,
        resolve_compile_cache_envs,
        worker_forward_args,
    )
    from elasticdl_tpu.master.main import build_master
    from elasticdl_tpu.master.worker_manager import WorkerManager
    from elasticdl_tpu.rpc.server import RpcServer

    data_dir = os.path.join(tmp, f"data-{tag}")
    os.makedirs(data_dir, exist_ok=True)
    _write_data(data_dir, n_records, seed=seed)
    args = master_parser().parse_args(
        [
            "--model_zoo", os.path.join(os.path.dirname(__file__), "elasticdl_tpu", "models"),
            "--model_def", MODEL_DEF,
            "--minibatch_size", str(MINIBATCH),
            "--training_data_dir", data_dir,
            "--records_per_task", str(RECORDS_PER_TASK),
            "--num_epochs", str(epochs),
            "--grads_to_wait", "1",
            "--local_updates", str(LOCAL_UPDATES),
            "--num_workers", str(num_workers),
            "--worker_backend", "process",
            *extra,
        ]
    )
    _spec, dispatcher, servicer, _, _ = build_master(args, "training")
    server = RpcServer(servicer.handlers(), port=0)
    server.start()
    backend = ProcessBackend(log_dir=os.path.join(tmp, f"logs-{tag}"))
    manager = WorkerManager(
        backend,
        dispatcher,
        num_workers=num_workers,
        worker_argv_fn=lambda wid: worker_forward_args(
            args, wid, f"localhost:{server.port}"
        ),
        envs={"JAX_PLATFORMS": "cpu", **resolve_compile_cache_envs(args)},
        max_relaunches=2 * num_workers,
    )
    return {
        "tag": tag,
        "total": n_records * epochs,
        "dispatcher": dispatcher,
        "servicer": servicer,
        "server": server,
        "backend": backend,
        "manager": manager,
        "t0": None,
        "t_end": None,
    }


def _stop_sched_job(job):
    job["manager"].stop_relaunch_and_remove_workers()
    job["backend"].stop()
    job["server"].stop()


def _annotate_nulls(record, reasons=None):
    """Honest-null pass (same contract as bench.py): a null headline
    field gets a `<field>_skipped_reason` sibling so a consumer can
    tell 'not applicable in this mode' from 'silently lost'."""
    reasons = reasons or {}
    for field in [k for k, v in record.items() if v is None]:
        record[f"{field}_skipped_reason"] = reasons.get(
            field, "not measured in this mode"
        )
    return record


def trace_main(name):
    """`--trace <name>` / EDL_ELASTIC_BENCH_TRACE: replay one churn
    trace (chaos/scenario.py) and print its scenario report as ONE
    JSON line — per-job goodput + retention + relaunch/preemption
    counters, with exact versions asserted at every probe point. The
    runner raises (and dumps the flight recorder) on any broken
    invariant, so reaching the JSON line IS the pass signal."""
    from elasticdl_tpu.chaos.scenario import ScenarioRunner, load_trace
    from elasticdl_tpu.common.constants import (
        ENV_ELASTIC_BENCH_TRACE_SCALE,
    )

    scale = float(os.environ.get(ENV_ELASTIC_BENCH_TRACE_SCALE, "1.0"))
    trace = load_trace(name)
    print(
        f"bench_elastic[trace]: {trace.name} (scale {scale:g}): "
        f"{trace.description}",
        file=sys.stderr,
    )
    report = ScenarioRunner(trace, scale=scale).run()
    null_reasons = {
        "retention": (
            "trace sets baseline=false: no fault-free twin was run "
            "to provide the denominator"
        ),
        "baseline_images_per_sec": (
            "trace sets baseline=false: no fault-free twin was run"
        ),
    }
    goodput_reasons = {
        "goodput_fraction": "no completed records in the clocked window",
        "gap_explained": (
            "no raw-vs-goodput gap: zero records were recomputed"
        ),
    }
    for job in report["jobs"].values():
        _annotate_nulls(job["goodput"], goodput_reasons)
        # acceptance bar: whatever gap exists must be explained by the
        # recompute counter (identity by construction; guards against
        # a future accounting change silently breaking it)
        explained = job["goodput"].get("gap_explained")
        if explained is not None:
            assert abs(explained - 1.0) <= 0.01, (
                f"goodput gap not explained by recomputed records: "
                f"{explained}"
            )
    # master-failover headline (master/migration.py): hoist the anchor
    # job's time-to-adopt so the master-failover traces read like every
    # other bench — one number, honest nulls when the trace exercised
    # no master kill
    anchor = report["jobs"].get(trace.jobs[0].tag) or {}
    failover = anchor.get("master_failover") or {}
    report["time_to_adopt_secs"] = failover.get("time_to_adopt_secs")
    report["failover_mode"] = failover.get("mode")
    no_failover = (
        "trace has no kill_master event: no master failover was exercised"
    )
    null_reasons["time_to_adopt_secs"] = no_failover
    null_reasons["failover_mode"] = no_failover
    print(json.dumps(_annotate_nulls(report, null_reasons)))


def sched_main():
    """The policy-plane contention bench (EDL_ELASTIC_BENCH_SCHED=1 or
    --sched): a best-effort job holds a 2-token arbiter fleet; at 25%
    progress a guaranteed job's capacity request preempts one token —
    the pod-kill path with a graceful drain — and both jobs run to
    completion. Prints ONE JSON line with per-job throughput and the
    preemption / speculative-backup / dedup counters, and hard-fails
    unless both jobs finish at their exact expected versions."""
    from elasticdl_tpu.sched import PriorityArbiter

    be_records = int(os.environ.get("EDL_SCHED_BENCH_RECORDS", 2048))
    g_records = be_records // 2
    tmp = tempfile.mkdtemp(prefix="edl_sched_bench_")
    arbiter = PriorityArbiter(capacity=2)
    # speculation on for the best-effort job: after the preemption it
    # runs degraded, exactly when a straggler clone can win
    be = _boot_sched_job(
        tmp, "be", be_records, 1, 2, seed=0,
        extra=("--qos_class", "best-effort", "--speculate"),
    )
    handle_be = arbiter.register(
        "be", "best-effort", preempt_cb=be["manager"].scale_down
    )
    assert arbiter.request(handle_be, 2) == 2
    be["manager"].start_workers()
    g = None
    handle_g = None
    t_preempt = None
    jobs = [be]
    try:
        deadline = time.time() + 3600.0
        while any(not j["dispatcher"].finished() for j in jobs):
            if time.time() > deadline:
                raise RuntimeError("sched bench did not finish in 3600s")
            for j in jobs:
                if j["manager"].all_exited() and not j["dispatcher"].finished():
                    raise RuntimeError(f"job {j['tag']}: all workers exited")
                done = j["dispatcher"].completed_records()
                if j["t0"] is None and done > 0:
                    j["t0"] = time.time()
                if j["t_end"] is None and j["dispatcher"].finished():
                    j["t_end"] = time.time()
            if (
                g is None
                and be["dispatcher"].completed_records() >= be["total"] // 4
            ):
                # saturated pool: the guaranteed request preempts one
                # best-effort worker (SIGTERM -> drain at task boundary)
                handle_g = arbiter.register("g", "guaranteed")
                got = arbiter.request(handle_g, 1)
                assert got == 1, f"guaranteed request got {got} tokens"
                t_preempt = time.time()
                g = _boot_sched_job(
                    tmp, "g", g_records, 1, 1, seed=7,
                    extra=("--qos_class", "guaranteed"),
                )
                g["manager"].start_workers()
                jobs.append(g)
                print(
                    "bench_elastic[sched]: preempted 1 best-effort "
                    "worker for the guaranteed job",
                    file=sys.stderr,
                )
            time.sleep(0.05)
        for j in jobs:
            if j["t_end"] is None:
                j["t_end"] = time.time()
            assert not j["dispatcher"].has_failed_tasks(), j["tag"]
            # the exactness bar: records exactly once, version exactly
            # execs x window steps — preemption added nothing
            assert j["dispatcher"].completed_records() == j["total"], j["tag"]
            expect = j["total"] // MINIBATCH
            got_v = j["servicer"].version
            assert got_v == expect, f"{j['tag']}: version {got_v} != {expect}"
    finally:
        for j in jobs:
            _stop_sched_job(j)

    def ips(j):
        return j["dispatcher"].completed_records() / (j["t_end"] - j["t0"])

    be_stats = be["manager"].snapshot()
    sched_be = be["dispatcher"].sched_stats()
    out = {
        "metric": "sched_two_job_contention_images_per_sec",
        "value": round(ips(be) + ips(g), 1),
        "unit": "images_per_sec",
        "be_images_per_sec": round(ips(be), 1),
        "g_images_per_sec": round(ips(g), 1),
        "g_wait_to_first_task_secs": round(g["t0"] - t_preempt, 1),
        "preemptions": arbiter.stats()["preemptions"],
        "be_policy_stops": be_stats["policy_stops"],
        "be_relaunches": be_stats["relaunches"],
        "be_backups_dispatched": sched_be["backups_dispatched"],
        "be_backup_wins": sched_be["backup_wins"],
        "workers": {"be": 2, "g": 1},
        "records": {"be": be_records, "g": g_records},
        "protocol": (
            "two window-mode ProcessBackend jobs over one 2-token "
            "PriorityArbiter: best-effort holds both tokens; at 25% "
            "progress a guaranteed request preempts one (SIGTERM, "
            "task-boundary drain) and the guaranteed job runs on it. "
            "Both jobs must finish at exact versions; throughput is "
            "clocked per job from its first completed task"
        ),
    }
    print(json.dumps(_annotate_nulls(out)))


def main():
    argv = sys.argv[1:]
    trace = os.environ.get("EDL_ELASTIC_BENCH_TRACE", "")
    if "--trace" in argv:
        idx = argv.index("--trace")
        if idx + 1 >= len(argv):
            print("--trace needs a trace name or path", file=sys.stderr)
            return 2
        trace = argv[idx + 1]
    if trace:
        return trace_main(trace)
    if (
        os.environ.get("EDL_ELASTIC_BENCH_SCHED", "") == "1"
        or "--sched" in argv
    ):
        return sched_main()
    # auto-scale to the host: on a single-core machine the worker
    # processes + master all share one core and the full-size run takes
    # over an hour — half the records and one epoch still cover 8 tasks
    # around the kill window
    small_host = (os.cpu_count() or 1) < 4
    # >= 4 tasks PER WORKER: with one task per worker the whole pool
    # finishes in one burst and "throughput" degenerates into the
    # completion spread (sub-second window, garbage rate) — the churn
    # sizing below then mis-sizes by orders of magnitude. This floor
    # dominates any host-size scaling at the default worker count.
    n_records = int(
        os.environ.get(
            "EDL_ELASTIC_BENCH_RECORDS", 16 * N_WORKERS * RECORDS_PER_TASK
        )
    )
    epochs = int(
        os.environ.get("EDL_ELASTIC_BENCH_EPOCHS", 1 if small_host else 2)
    )
    # Fast worker recovery via the framework's --compile_cache_dir
    # (default on, one fixed directory — common/args.py's resolver):
    # a relaunched replacement reuses the incumbents' compiled
    # programs instead of re-paying the XLA compile.
    # EDL_ELASTIC_BENCH_CACHE=0 measures the cold-boot path.
    use_cache = os.environ.get("EDL_ELASTIC_BENCH_CACHE", "1") == "1"
    # Warm standbys (--num_standby_workers) are the framework's answer
    # to the relaunch transient: a pre-booted, AOT-compiled spare is
    # promoted the moment an active worker dies, so recovery costs one
    # task-requeue round instead of a full python+jax+XLA boot. The
    # bench runs WITH one standby by default (it idles during the
    # stable run, so active capacity is identical in both runs);
    # EDL_ELASTIC_BENCH_STANDBY=0 measures the bare relaunch path.
    standby = int(os.environ.get("EDL_ELASTIC_BENCH_STANDBY", "1"))
    # honesty knob, not a cheat: 12x keeps the relaunch transients
    # weighted as a long job would weigh them; smaller values are for
    # MECHANICS smokes only and must not be quoted as retention
    BOOT_AMORTIZATION = float(os.environ.get("EDL_ELASTIC_BENCH_AMORT", "12"))

    per_seed = []
    for seed in range(SEED_BASE, SEED_BASE + SEEDS):
        tmp = tempfile.mkdtemp(prefix=f"edl_elastic_bench_s{seed}_")
        _write_data(tmp, n_records, seed=seed)
        print(
            f"bench_elastic[seed {seed}]: {n_records} records x {epochs} "
            f"epochs, {N_WORKERS} workers, {KILL_WAVES} kill waves of "
            f"{int(KILL_FRACTION * 100)}% between "
            f"{int(KILL_FIRST * 100)}% and {int(KILL_LAST * 100)}%",
            file=sys.stderr,
        )
        cache_dir = "auto" if use_cache else ""
        # The stable baseline must be measured over a window long
        # enough that scheduler noise averages out: a ~25s window
        # produced a 42% stable swing between seeds in a run where the
        # CHURN numbers agreed to 0.4% — the ratio's variance was all
        # baseline. 6+ epochs puts the stable window in the minutes.
        stable_epochs = max(epochs, 6)
        stable_ips, _, boot_secs, _, _ = run_job(
            tmp, n_records, churn=False, epochs=stable_epochs,
            cache_dir=cache_dir, standby=standby,
        )
        print(
            f"bench_elastic[seed {seed}]: stable {stable_ips:.1f} img/s "
            f"over {stable_epochs} epochs (worker boot {boot_secs:.0f}s)",
            file=sys.stderr,
        )
        # Boot-aware sizing: the retention target models a LONG
        # preemptible job, where a relaunch's boot+compile amortizes to
        # noise. On a slow/few-core host a fixed-size run can be
        # shorter than a few boots, and "retention" degenerates into a
        # measure of compile contention. Size the churn run so its
        # expected duration is >= BOOT_AMORTIZATION x the measured boot
        # ACROSS the whole wave window — each wave transient carries
        # the weight it has in a long-running job.
        base_secs = n_records * epochs / stable_ips
        churn_epochs = epochs
        if base_secs < BOOT_AMORTIZATION * boot_secs:
            import math

            churn_epochs = min(
                24,
                max(
                    epochs,
                    math.ceil(
                        BOOT_AMORTIZATION * boot_secs * stable_ips / n_records
                    ),
                ),
            )
            print(
                f"bench_elastic[seed {seed}]: churn run sized to "
                f"{churn_epochs} epochs "
                f"(~{n_records * churn_epochs / stable_ips:.0f}s) to "
                f"amortize the {boot_secs:.0f}s boot "
                f"{BOOT_AMORTIZATION:g}x",
                file=sys.stderr,
            )
        churn_ips, relaunches, _, promotions, waves_fired = run_job(
            tmp, n_records, churn=True, epochs=churn_epochs,
            cache_dir=cache_dir, standby=standby,
            time_limit=max(
                3600.0,
                (BOOT_AMORTIZATION + 4.0 * KILL_WAVES) * boot_secs
                + base_secs,
            ),
        )
        retention = churn_ips / stable_ips
        print(
            f"bench_elastic[seed {seed}]: churn {churn_ips:.1f} img/s "
            f"({relaunches} relaunches, {promotions} promotions) -> "
            f"retention {retention:.3f}",
            file=sys.stderr,
        )
        per_seed.append(
            {
                "seed": seed,
                "retention": round(retention, 3),
                "stable_images_per_sec": round(stable_ips, 1),
                "churn_images_per_sec": round(churn_ips, 1),
                "relaunches": relaunches,
                "promotions": promotions,
                "waves_fired": waves_fired,
                "worker_boot_secs": round(boot_secs, 1),
                "churn_epochs": churn_epochs,
            }
        )

    rets = [d["retention"] for d in per_seed]
    mean = sum(rets) / len(rets)
    spread = max(rets) - min(rets)
    print(
        json.dumps(
            _annotate_nulls({
                "metric": "elastic_throughput_retention_50pct_kill",
                "value": round(mean, 3),
                "unit": "ratio",
                "retention_per_seed": rets,
                "retention_spread": round(spread, 3),
                "seeds": SEEDS,
                "kill_waves": KILL_WAVES,
                "boot_amortization": BOOT_AMORTIZATION,
                "workers": N_WORKERS,
                "standby_workers": standby,
                "compile_cache": use_cache,
                "per_seed": per_seed,
                "target": 0.95,
                "protocol": (
                    f"{N_WORKERS} process workers (CPU), {KILL_WAVES} "
                    f"SIGKILL waves of {int(KILL_FRACTION * 100)}% of the "
                    f"LIVE active pool at evenly spaced progress points in "
                    f"[{int(KILL_FIRST * 100)}%, {int(KILL_LAST * 100)}%], "
                    f"repeated over {SEEDS} data seeds; value = mean "
                    "retention, spread = max-min. Throughput clocked from "
                    "first completed task (worker boot excluded "
                    "identically in stable and churn runs). Default mode "
                    "runs ONE warm standby worker (idle in the stable "
                    "run, so active capacity matches): on each kill a "
                    "pre-booted AOT-compiled standby is promoted and "
                    "recovery costs one task-requeue round — the "
                    "framework's --num_standby_workers feature; "
                    "EDL_ELASTIC_BENCH_STANDBY=0 measures the bare "
                    "relaunch path. In both modes every replacement's "
                    "full python+jax+compile boot is charged against "
                    "churn throughput, and the churn window is sized >= "
                    f"{BOOT_AMORTIZATION:g}x the measured boot so the "
                    "transients carry the weight they have in a "
                    f"long-running job. Windows are {LOCAL_UPDATES} steps "
                    f"x {MINIBATCH} records: "
                    "preemption loses the current un-flushed "
                    "window, so window size is itself an elastic "
                    "design axis — short windows bound loss-per-kill, "
                    "and the sync frequency they cost is sub-ms "
                    "against a localhost master (on a high-latency "
                    "link a deployment would size windows up and pay "
                    "the exposure). All workers share the job's "
                    "--compile_cache_dir persistent XLA cache (the "
                    "framework's default recovery feature; "
                    "EDL_ELASTIC_BENCH_CACHE=0 disables), so a "
                    "replacement reuses the incumbents' compiled "
                    "programs on boot"
                ),
            })
        )
    )


if __name__ == "__main__":
    sys.exit(main())
