"""Trace-driven churn scenarios: spot-market failure shapes as data.

The chaos plane (rpc/chaos.py) injects single faults at the RPC layer;
the e2e tests hard-code one churn shape each (a kill, a preemption).
What neither covers is the thing a spot-market deployment
actually faces: *composed* failure sequences —
a kill wave landing during a drain, a flash crowd of job arrivals on a
saturated host, a whole node taking an aggregator down with its
workers. This module makes those sequences declarative:

- a **trace** (JSON, see `parse_trace`) names jobs and a list of timed
  or progress-keyed events: ``kill`` (SIGKILL a seeded-random fraction
  of the live pool), ``drain`` (SIGTERM scale-down through the policy
  plane — workers flush at a task boundary), ``scale_up``,
  ``spawn_job`` (flash-crowd arrival of a deferred job), ``kill_host``
  (an aggregator node dies WITH every worker mapped to it),
  ``kill_master`` (the master itself dies — hard SIGKILL-shaped crash
  or planned drain — and a StandbyMaster adopts the job with no
  checkpoint file, master/migration.py), and
  ``chaos_arm``/``chaos_disarm`` (create/remove the latch file behind
  a FaultPlan entry's ``armed_file``, switching an inherited fault
  spec on for exactly one scenario window — e.g. drops composed into a
  drain);
- a **ScenarioScheduler** executes events deterministically: victim
  picks come from `random.Random(seed)` over the sorted live pool, and
  every decision is appended to a canonical-JSON timeline — same seed
  + same fleet states => byte-identical timeline (tested);
- a **ScenarioRunner** boots each job as a real master (dispatcher +
  servicer + RpcServer + ProcessBackend + WorkerManager, RecoveryPlane
  when the job has PS shards — the same wiring as master main), drives
  the trace, probes exactness mid-run THROUGH GetSchedStats (the
  ``exactness`` block: version == init_version + applied_update_steps
  under one servicer lock), and hard-fails unless every job finishes
  with zero dropped tasks at its exact expected version.

**Goodput accounting**: raw throughput counts every completed record —
including records that were trained, lost to a preemption, and trained
again. The dispatcher now separates those (task_dispatcher.py):

- ``requeued_records``: records put back on the todo queue by a death
  or failure (work *at risk* of recomputation);
- ``recomputed_records``: charged when a task finally succeeds, as
  (prior dispatches) x (task records) — exactly the records the fleet
  processed more than once;
- ``drain_flushed_records``: completions reported by a worker inside
  its policy-stop window (the graceful-drain flush). Informational:
  flushed work is real work, counted once — it is never subtracted
  and never double-counted into ``recomputed_records``.

    goodput_ips = (completed - recomputed) / elapsed
    raw_ips     = completed / elapsed

so raw - goodput == recomputed/elapsed *identically* — the gap between
the throughput a dashboard shows and the progress the job made is
explained record-for-record by the recompute counter (asserted by
`compute_goodput` consumers within float tolerance).

Run a packaged trace::

    python -m elasticdl_tpu.chaos preemption-storm
    python -m elasticdl_tpu.chaos /path/to/trace.json --scale 0.5

Reference: ElasticDL documents pod-kill drills manually
(elasticdl/doc/elastic_scheduling.md); here the drill is a versioned
artifact the CI replays (.github/workflows/ci.yml churn-scenario).
"""

from __future__ import annotations

import json
import os
import random
import signal
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from elasticdl_tpu.common.constants import (
    ENV_CHAOS_SPEC,
    ENV_TRACE_PROBE_SECS,
    ENV_TRACE_SEED,
)
from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.obs import flight as obs_flight

logger = get_logger(__name__)

MODEL_DEF = "mnist_functional_api.custom_model"
IMAGE_SHAPE = (28, 28, 1)
DATA_SHARDS = 4

ACTIONS = (
    "kill",
    "drain",
    "scale_up",
    "spawn_job",
    "chaos_arm",
    "chaos_disarm",
    "kill_host",
    "kill_master",
)

_JOB_KEYS = {
    "tag", "records", "epochs", "workers", "minibatch",
    "records_per_task", "local_updates", "num_ps", "num_agg",
    "speculate", "qos", "seed", "standby", "deferred", "extra_args",
    "master_standby",
}
_EVENT_KEYS = {
    "at_progress", "at_records", "at_elapsed", "job", "action",
    "fraction", "count", "latch", "host", "spawn", "mode",
}
_TRACE_KEYS = {
    "name", "seed", "description", "jobs", "events", "chaos", "expect",
    "baseline", "time_limit_secs", "gap_explained_tolerance",
}
_EXPECT_KEYS = {
    "min_relaunches", "min_promotions", "min_policy_stops",
    "min_requeued_records", "min_recomputed_records",
    "min_drain_flushed_records", "min_preempted_task_requeues",
    "min_scale_ups",
}


class TraceError(ValueError):
    """Malformed trace: the runner refuses to guess at churn shapes."""


@dataclass
class JobSpec:
    tag: str
    records: int
    epochs: int = 1
    workers: int = 3
    minibatch: int = 64
    records_per_task: int = 128
    local_updates: int = 2
    num_ps: int = 0
    num_agg: int = 0
    speculate: bool = False
    qos: str = ""
    seed: int = 0
    standby: int = 0
    deferred: bool = False
    extra_args: List[str] = field(default_factory=list)
    # boot a StandbyMaster beside the job so a kill_master event can
    # exercise checkpoint-free adoption (master/migration.py)
    master_standby: bool = False

    @property
    def total(self) -> int:
        return self.records * self.epochs

    @property
    def expected_version(self) -> int:
        return self.total // self.minibatch


@dataclass
class TraceEvent:
    action: str
    job: str
    at_progress: Optional[float] = None
    at_records: Optional[int] = None
    at_elapsed: Optional[float] = None
    fraction: float = 0.0
    count: int = 1
    latch: str = ""
    host: int = -1
    spawn: str = ""
    mode: str = ""  # kill_master: "sigkill" (crash) | "handoff" (drain)

    def due(self, completed: int, total: int, elapsed: float) -> bool:
        if self.at_elapsed is not None:
            return elapsed >= self.at_elapsed
        if self.at_records is not None:
            return completed >= self.at_records
        return total > 0 and completed / total >= self.at_progress


@dataclass
class TraceSpec:
    name: str
    seed: int
    description: str
    jobs: List[JobSpec]
    events: List[TraceEvent]
    chaos: Optional[dict]
    latches: List[str]
    expect: Dict[str, int]
    baseline: bool
    time_limit_secs: float
    # when set, every job's gap_explained must land within this of 1.0
    # — the goodput gap is explained by the recompute counter
    gap_explained_tolerance: Optional[float] = None

    def job(self, tag: str) -> JobSpec:
        for j in self.jobs:
            if j.tag == tag:
                return j
        raise KeyError(tag)


def _reject_unknown(d: dict, allowed: set, what: str) -> None:
    unknown = sorted(set(d) - allowed)
    if unknown:
        raise TraceError(f"{what}: unknown keys {unknown}")


def _parse_job(d: dict, idx: int) -> JobSpec:
    if not isinstance(d, dict):
        raise TraceError(f"jobs[{idx}] must be an object")
    _reject_unknown(d, _JOB_KEYS, f"jobs[{idx}]")
    for key in ("tag", "records"):
        if key not in d:
            raise TraceError(f"jobs[{idx}] missing required key {key!r}")
    spec = JobSpec(
        tag=str(d["tag"]),
        records=int(d["records"]),
        epochs=int(d.get("epochs", 1)),
        workers=int(d.get("workers", 3)),
        minibatch=int(d.get("minibatch", 64)),
        records_per_task=int(d.get("records_per_task", 128)),
        local_updates=int(d.get("local_updates", 2)),
        num_ps=int(d.get("num_ps", 0)),
        num_agg=int(d.get("num_agg", 0)),
        speculate=bool(d.get("speculate", False)),
        qos=str(d.get("qos", "")),
        seed=int(d.get("seed", 0)),
        standby=int(d.get("standby", 0)),
        deferred=bool(d.get("deferred", False)),
        extra_args=[str(a) for a in d.get("extra_args", [])],
        master_standby=bool(d.get("master_standby", False)),
    )
    if spec.workers < 1:
        raise TraceError(f"job {spec.tag!r}: workers must be >= 1")
    if spec.records_per_task % spec.minibatch != 0:
        raise TraceError(
            f"job {spec.tag!r}: records_per_task must be a multiple of "
            "minibatch (whole windows per task)"
        )
    chunk = DATA_SHARDS * spec.records_per_task
    if spec.records <= 0 or spec.records % chunk != 0:
        raise TraceError(
            f"job {spec.tag!r}: records must be a positive multiple of "
            f"{chunk} ({DATA_SHARDS} shards x records_per_task)"
        )
    if spec.num_agg > 0 and spec.num_ps <= 0:
        raise TraceError(f"job {spec.tag!r}: num_agg requires num_ps")
    if spec.master_standby and spec.num_ps <= 0:
        # checkpoint-free adoption needs the model to live somewhere
        # that survives the master — the PS shards
        raise TraceError(
            f"job {spec.tag!r}: master_standby requires num_ps > 0 "
            "(the model must outlive the master on PS shards)"
        )
    return spec


def _parse_event(d: dict, idx: int, jobs: List[JobSpec],
                 latches: List[str]) -> TraceEvent:
    if not isinstance(d, dict):
        raise TraceError(f"events[{idx}] must be an object")
    _reject_unknown(d, _EVENT_KEYS, f"events[{idx}]")
    action = d.get("action")
    if action not in ACTIONS:
        raise TraceError(
            f"events[{idx}]: unknown action {action!r} "
            f"(one of {', '.join(ACTIONS)})"
        )
    anchors = [k for k in ("at_progress", "at_records", "at_elapsed")
               if k in d]
    if len(anchors) != 1:
        raise TraceError(
            f"events[{idx}]: exactly one of at_progress/at_records/"
            f"at_elapsed required, got {anchors or 'none'}"
        )
    tags = [j.tag for j in jobs]
    job = str(d.get("job", tags[0]))
    if job not in tags:
        raise TraceError(f"events[{idx}]: unknown job {job!r}")
    ev = TraceEvent(
        action=action,
        job=job,
        at_progress=(float(d["at_progress"])
                     if "at_progress" in d else None),
        at_records=int(d["at_records"]) if "at_records" in d else None,
        at_elapsed=float(d["at_elapsed"]) if "at_elapsed" in d else None,
        fraction=float(d.get("fraction", 0.0)),
        count=int(d.get("count", 1)),
        latch=str(d.get("latch", "")),
        host=int(d.get("host", -1)),
        spawn=str(d.get("spawn", "")),
        mode=str(d.get("mode", "")),
    )
    if ev.at_progress is not None and not 0.0 <= ev.at_progress <= 1.0:
        raise TraceError(f"events[{idx}]: at_progress must be in [0,1]")
    if action == "kill" and ev.fraction <= 0.0 and "count" not in d:
        raise TraceError(
            f"events[{idx}]: kill needs fraction>0 or an explicit count"
        )
    if action in ("drain", "scale_up") and ev.count < 1:
        raise TraceError(f"events[{idx}]: {action} count must be >= 1")
    if action == "spawn_job":
        if ev.spawn not in tags:
            raise TraceError(
                f"events[{idx}]: spawn_job needs spawn=<job tag>, "
                f"got {ev.spawn!r}"
            )
        if not next(j for j in jobs if j.tag == ev.spawn).deferred:
            raise TraceError(
                f"events[{idx}]: spawned job {ev.spawn!r} must be "
                "declared deferred"
            )
    if action in ("chaos_arm", "chaos_disarm") and ev.latch not in latches:
        raise TraceError(
            f"events[{idx}]: latch {ev.latch!r} is not an armed_file of "
            f"any chaos fault (declared: {latches or 'none'})"
        )
    if action == "kill_host":
        target = next(j for j in jobs if j.tag == job)
        if not 0 <= ev.host < target.num_agg:
            raise TraceError(
                f"events[{idx}]: kill_host host {ev.host} out of range "
                f"for job {job!r} (num_agg={target.num_agg})"
            )
    if action == "kill_master":
        if ev.mode not in ("sigkill", "handoff"):
            raise TraceError(
                f"events[{idx}]: kill_master needs mode 'sigkill' or "
                f"'handoff', got {ev.mode!r}"
            )
        target = next(j for j in jobs if j.tag == job)
        if not target.master_standby:
            raise TraceError(
                f"events[{idx}]: kill_master target job {job!r} must "
                "declare master_standby (a standby to adopt the job)"
            )
    return ev


def parse_trace(raw: dict) -> TraceSpec:
    """Strict trace validation: unknown keys, unknown actions, missing
    anchors, dangling job/latch references all raise TraceError — a
    typo'd trace must fail loudly, not silently skip its churn."""
    if not isinstance(raw, dict):
        raise TraceError("trace must be a JSON object")
    _reject_unknown(raw, _TRACE_KEYS, "trace")
    for key in ("name", "seed", "jobs", "events"):
        if key not in raw:
            raise TraceError(f"trace missing required key {key!r}")
    jobs = [_parse_job(j, i) for i, j in enumerate(raw["jobs"] or [])]
    if not jobs:
        raise TraceError("trace needs at least one job")
    tags = [j.tag for j in jobs]
    if len(set(tags)) != len(tags):
        raise TraceError(f"duplicate job tags: {tags}")
    if jobs[0].deferred:
        raise TraceError("jobs[0] is the anchor job and cannot be deferred")

    chaos = raw.get("chaos")
    latches: List[str] = []
    if chaos is not None:
        if not isinstance(chaos, dict):
            raise TraceError("chaos must be an object (FaultPlan spec)")
        from elasticdl_tpu.rpc.chaos import Fault

        try:
            faults = [Fault.from_dict(f) for f in chaos.get("faults", [])]
        except ValueError as e:
            raise TraceError(f"chaos spec: {e}") from e
        for f in faults:
            # armed_file in a TRACE is a latch NAME; the runner rewrites
            # it to a file under the run dir (chaos_arm creates it)
            if f.armed_file and os.path.sep in f.armed_file:
                raise TraceError(
                    f"chaos armed_file {f.armed_file!r} must be a bare "
                    "latch name, not a path (the runner owns placement)"
                )
            if f.armed_file:
                latches.append(f.armed_file)

    events = [_parse_event(e, i, jobs, latches)
              for i, e in enumerate(raw["events"] or [])]
    spawned = [e.spawn for e in events if e.action == "spawn_job"]
    for j in jobs:
        if j.deferred and spawned.count(j.tag) != 1:
            raise TraceError(
                f"deferred job {j.tag!r} must be spawned by exactly one "
                f"spawn_job event (found {spawned.count(j.tag)})"
            )
    master_kills = [e.job for e in events if e.action == "kill_master"]
    for tag in set(master_kills):
        if master_kills.count(tag) > 1:
            # one standby per job: a second kill would have no master
            # left waiting to adopt
            raise TraceError(
                f"job {tag!r} has {master_kills.count(tag)} kill_master "
                "events; at most one per job (one standby)"
            )

    expect = raw.get("expect") or {}
    _reject_unknown(expect, _EXPECT_KEYS, "expect")
    return TraceSpec(
        name=str(raw["name"]),
        seed=int(raw["seed"]),
        description=str(raw.get("description", "")),
        jobs=jobs,
        events=events,
        chaos=chaos,
        latches=latches,
        expect={k: int(v) for k, v in expect.items()},
        baseline=bool(raw.get("baseline", False)),
        time_limit_secs=float(raw.get("time_limit_secs", 1800.0)),
        gap_explained_tolerance=(
            float(raw["gap_explained_tolerance"])
            if "gap_explained_tolerance" in raw
            else None
        ),
    )


def traces_dir() -> str:
    return os.path.join(os.path.dirname(__file__), "traces")


def list_traces() -> List[str]:
    return sorted(
        f[:-5] for f in os.listdir(traces_dir()) if f.endswith(".json")
    )


def load_trace(name_or_path: str) -> TraceSpec:
    """Packaged trace by name, or any path to a trace JSON."""
    path = name_or_path
    if not os.path.isfile(path):
        path = os.path.join(traces_dir(), f"{name_or_path}.json")
        if not os.path.isfile(path):
            raise TraceError(
                f"unknown trace {name_or_path!r} "
                f"(packaged: {', '.join(list_traces())})"
            )
    try:
        with open(path) as f:
            raw = json.load(f)
    except json.JSONDecodeError as e:
        raise TraceError(f"{path}: not valid JSON: {e}") from e
    return parse_trace(raw)


# -- deterministic event scheduling ------------------------------------------


class ScenarioScheduler:
    """Seeded decision core, separated from process execution so the
    determinism contract is testable without booting a fleet: every
    decision (victim picks, counts, event firings) appends one
    canonical-JSON line to `timeline`. Same seed + same observed fleet
    states => byte-identical timeline; wall-clock never enters it."""

    def __init__(self, trace: TraceSpec, seed: Optional[int] = None):
        self.trace = trace
        self.seed = trace.seed if seed is None else int(seed)
        self._rng = random.Random(self.seed)
        self.timeline: List[str] = []
        self._pending: List[TraceEvent] = list(trace.events)
        self._seq = 0

    def record(self, action: str, job: str, **fields) -> dict:
        entry = {"seq": self._seq, "action": action, "job": job}
        entry.update(fields)
        self._seq += 1
        self.timeline.append(
            json.dumps(entry, sort_keys=True, separators=(",", ":"))
        )
        return entry

    def pick_victims(self, alive: List[int], count: int) -> List[int]:
        """`count` victims from the live pool. Sorting before sampling
        makes the pick a pure function of (seed, draw index, pool as a
        SET) — the caller's iteration order can't perturb it."""
        pool = sorted(alive)
        count = min(max(0, int(count)), len(pool))
        if count == 0:
            return []
        return sorted(self._rng.sample(pool, count))

    def kill_count(self, alive: int, ev: TraceEvent) -> int:
        if ev.fraction > 0.0:
            return max(1, int(alive * ev.fraction)) if alive else 0
        return min(ev.count, alive)

    def due_events(
        self,
        progress: Callable[[str], int],
        totals: Dict[str, int],
        elapsed: float,
    ) -> List[TraceEvent]:
        """Pop every pending event whose anchor is satisfied, in
        declaration order (ties break by trace order, deterministic)."""
        due, still = [], []
        for ev in self._pending:
            if ev.due(progress(ev.job), totals.get(ev.job, 0), elapsed):
                due.append(ev)
            else:
                still.append(ev)
        self._pending = still
        return due

    def pending(self) -> int:
        return len(self._pending)


# -- goodput arithmetic (pure; unit-tested) ----------------------------------


def compute_goodput(counters: Dict[str, int], elapsed: float) -> dict:
    """Turn the dispatcher's goodput counters into rates. The defining
    identity — raw - net == recomputed/elapsed — holds exactly by
    construction (goodput_images_per_sec is the net clamped at zero:
    a job can spend more on recompute than its total unique records,
    but cannot have negative useful throughput); `gap_explained`
    reports the ratio over the unclamped gap so a scenario can assert
    its goodput/raw gap is explained by the recompute counter (1.0
    when there was any gap; None for a gapless fault-free run).

    drain_flushed_records is deliberately NOT in the arithmetic: a
    drain flush is real work counted once (it is also never inside
    recomputed_records — the dispatcher credits a drain flush at
    success and only charges recompute for PRIOR dispatches of the
    same task)."""
    completed = int(counters.get("completed_records", 0))
    recomputed = int(counters.get("recomputed_records", 0))
    raw = completed / elapsed if elapsed > 0 else 0.0
    # recomputed can legitimately EXCEED completed: recompute is
    # charged per PRIOR dispatch at success, so a task that needed
    # three dispatches (worker death requeue + master-cutover
    # requeue_doing, say) contributes 2x its records — the net useful
    # rate clamps at zero while the gap stays UNCLAMPED so the
    # defining identity above remains testable via gap_explained
    net = (completed - recomputed) / elapsed if elapsed > 0 else 0.0
    good = max(0.0, net)
    gap = raw - net
    return {
        "raw_images_per_sec": raw,
        "goodput_images_per_sec": good,
        "goodput_fraction": (good / raw) if raw > 0 else None,
        "gap_images_per_sec": gap,
        "gap_from_recompute_images_per_sec": (
            recomputed / elapsed if elapsed > 0 else 0.0
        ),
        "gap_explained": (recomputed / elapsed) / gap if gap > 0 else None,
        "completed_records": completed,
        "requeued_records": int(counters.get("requeued_records", 0)),
        "recomputed_records": recomputed,
        "drain_flushed_records": int(
            counters.get("drain_flushed_records", 0)
        ),
        "preempted_task_requeues": int(
            counters.get("preempted_task_requeues", 0)
        ),
    }


# -- job lifecycle -----------------------------------------------------------


class JobRun:
    """One trace job booted as a real master + ProcessBackend fleet —
    the same wiring as master main: RecoveryPlane when the job has PS
    shards, standby sample-batch service when it has standbys, the
    dispatcher's draining hook pointed at the manager's policy-stop
    set, and the goodput counters surfaced through GetSchedStats."""

    def __init__(self, spec: JobSpec, run_dir: str,
                 worker_env: Dict[str, str]):
        self.spec = spec
        self.t0: Optional[float] = None
        self.t_end: Optional[float] = None
        self.probes = 0
        # set by the recovery plane's monitor thread (on_unrecoverable
        # callback), polled by the scenario driver loop — an Event is
        # the cross-thread flag with a real happens-before edge, not a
        # bare bool
        self.ps_dead = threading.Event()
        self._run_dir = run_dir
        self._worker_env = dict(worker_env)
        self._recovery = None
        # master-migration plane (master/migration.py): armed when the
        # spec declares master_standby; kill_master drives it
        self.standby_master = None
        self.migration: Optional[dict] = None
        self._killed_server = None  # stopped in kill_master, skip in stop()
        self._data_dir = ""
        # boot products, set by start(); pre-initialized so stop() can
        # run against a PARTIAL boot (a raise mid-start must tear down
        # whatever already exists instead of stranding the fleet)
        self.dispatcher = None
        self.servicer = None
        self.server = None
        self.backend = None
        self.manager = None

    def start(self) -> None:
        try:
            self._start_inner()
        except Exception:
            # a raise between the server boot and start_workers (bad
            # spec args, standby bind failure, shard spawn failure)
            # leaves a half-booted job the runner never records in
            # _jobs — its finally sweep would miss it, leaking the RPC
            # server and any already-spawned worker Popens; stop() is
            # None-guarded for exactly this path
            try:
                self.stop()
            except Exception:
                logger.warning(
                    "scenario job %s: cleanup after failed boot also "
                    "failed", self.spec.tag, exc_info=True,
                )
            raise

    def _start_inner(self) -> None:
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
        from elasticdl_tpu.models.record_codec import (
            write_synthetic_image_records,
        )
        from elasticdl_tpu.rpc.server import RpcServer

        spec = self.spec
        data_dir = os.path.join(self._run_dir, f"data-{spec.tag}")
        os.makedirs(data_dir, exist_ok=True)
        per_shard = spec.records // DATA_SHARDS
        for i in range(DATA_SHARDS):
            write_synthetic_image_records(
                os.path.join(data_dir, f"shard-{i}.rio"),
                per_shard,
                IMAGE_SHAPE,
                10,
                seed=spec.seed * DATA_SHARDS + i,
            )
        argv = [
            "--model_zoo",
            os.path.join(
                os.path.dirname(os.path.dirname(__file__)), "models"
            ),
            "--model_def", MODEL_DEF,
            "--minibatch_size", str(spec.minibatch),
            "--training_data_dir", data_dir,
            "--records_per_task", str(spec.records_per_task),
            "--num_epochs", str(spec.epochs),
            "--grads_to_wait", "1",
            "--local_updates", str(spec.local_updates),
            "--num_workers", str(spec.workers),
            "--worker_backend", "process",
        ]
        if spec.num_ps:
            argv += ["--num_ps", str(spec.num_ps)]
        if spec.num_agg:
            argv += ["--num_agg", str(spec.num_agg)]
        if spec.speculate:
            argv += ["--speculate"]
        if spec.qos:
            argv += ["--qos_class", spec.qos]
        argv += spec.extra_args
        args = master_parser().parse_args(argv)
        _spec, self.dispatcher, self.servicer, _, _ = build_master(
            args, "training"
        )
        self.server = RpcServer(self.servicer.handlers(), port=0)
        self.server.start()
        self.backend = ProcessBackend(
            log_dir=os.path.join(self._run_dir, f"logs-{spec.tag}")
        )
        addr = f"localhost:{self.server.port}"
        self.addr = addr
        self._data_dir = data_dir
        worker_envs = {
            "JAX_PLATFORMS": "cpu",
            **resolve_compile_cache_envs(args),
            **self._worker_env,
        }
        if spec.master_standby:
            self._boot_standby(args, worker_envs)
        self.manager = WorkerManager(
            self.backend,
            self.dispatcher,
            num_workers=spec.workers,
            worker_argv_fn=lambda wid: worker_forward_args(
                args, wid, addr
            ),
            envs=worker_envs,
            max_relaunches=4 * spec.workers,
            num_standby=spec.standby,
        )
        # master-main wiring, reproduced: drain attribution + goodput
        # on the GetSchedStats surface + standby service + recovery
        self.dispatcher.set_draining_fn(self.manager.is_policy_stopped)
        dispatcher, manager = self.dispatcher, self.manager

        def _stats() -> dict:
            out = {"workers": manager.snapshot()}
            out.update(dispatcher.sched_stats())
            out["goodput"] = dispatcher.goodput_stats()
            return out

        self.servicer.set_sched_stats_fn(_stats)
        if spec.standby:
            self.servicer.set_standby_fn(self.manager.is_standby)
            self.servicer.set_sample_batch_fn(
                make_sample_batch_fn(data_dir)
            )
        if (self.servicer.ps_group is not None
                or self.servicer.kv_group is not None):
            from elasticdl_tpu.master.recovery import RecoveryPlane

            def _unrecoverable(kind, sid):
                self.ps_dead.set()

            self._recovery = RecoveryPlane(
                self.servicer,
                ps_group=self.servicer.ps_group,
                kv_group=self.servicer.kv_group,
                agg_group=self.servicer.agg_group,
                on_unrecoverable=_unrecoverable,
            )
            self.servicer.set_recovery_plane(self._recovery)
            self._recovery.start()
            self.manager.on_shard_failure = self._recovery.on_shard_failure
        if self.standby_master is not None:
            from elasticdl_tpu.master.migration import (
                attach_manifest_publisher,
            )

            attach_manifest_publisher(
                self.servicer, self.dispatcher, self.manager
            )
            self.standby_master.start()
        self.manager.start_workers()
        logger.info(
            "scenario job %s: %d workers on %s (total %d records)",
            spec.tag, spec.workers, addr, spec.total,
        )

    # -- fleet views used by the scheduler --------------------------------

    def alive_workers(self) -> List[int]:
        """Live, active, pid-backed workers — the kill-eligible pool
        (a pid-less victim would silently shrink the killed
        fraction)."""
        from elasticdl_tpu.cluster.pod_backend import PodPhase

        return [
            wid
            for wid, ph in self.manager.phases().items()
            if ph in (PodPhase.PENDING, PodPhase.RUNNING)
            and not self.manager.is_standby(wid)
            and not self.manager.is_policy_stopped(wid)
            and self.backend.pid_of(wid)
        ]

    def sigkill_workers(self, victims: List[int]) -> int:
        n = 0
        for wid in victims:
            pid = self.backend.pid_of(wid)
            if not pid:
                continue
            try:
                os.kill(pid, signal.SIGKILL)
                n += 1
            except ProcessLookupError:
                pass  # died on its own between pid_of and the kill
        return n

    def kill_host(self, host: int) -> dict:
        """A node dies: aggregator `host` AND every live worker mapped
        to it (worker->agg mapping is wid % num_agg, worker/worker.py)
        go down together, SIGKILL. The RecoveryPlane relaunches the
        aggregator (stateless, fresh generation); the WorkerManager
        relaunches the workers."""
        agg = self.servicer.agg_group
        workers = [
            wid for wid in self.alive_workers()
            if wid % self.spec.num_agg == host
        ]
        killed = self.sigkill_workers(workers)
        agg_pid = agg.pid_of(host) if agg is not None else None
        if agg_pid:
            try:
                os.kill(agg_pid, signal.SIGKILL)
            except ProcessLookupError:
                agg_pid = None
        return {
            "host": host,
            "workers": workers,
            "workers_killed": killed,
            "agg_killed": bool(agg_pid),
        }

    # -- master migration (master/migration.py) ----------------------------

    def _boot_standby(self, args, worker_envs: Dict[str, str]) -> None:
        """Boot a StandbyMaster beside the incumbent: a second
        servicer/dispatcher pair over the SAME shard groups (no new
        shards), gated UNAVAILABLE until adoption. Its stable address
        rides every worker's --master_candidates list."""
        from elasticdl_tpu.api.model_spec import get_model_spec
        from elasticdl_tpu.common.args import worker_forward_args
        from elasticdl_tpu.master.main import _finish_build, collect_shards
        from elasticdl_tpu.master.migration import StandbyMaster
        from elasticdl_tpu.master.worker_manager import WorkerManager

        spec, incumbent = self.spec, self.servicer
        data_dir = self._data_dir

        def _pair():
            mspec = get_model_spec(
                model_zoo=args.model_zoo,
                model_def=args.model_def,
                model_params=args.model_params,
                dataset_fn=args.dataset_fn,
                loss=args.loss,
                optimizer=args.optimizer,
                eval_metrics_fn=args.eval_metrics_fn,
                prediction_outputs_processor=(
                    args.prediction_outputs_processor
                ),
            )
            _, disp, serv, _, _ = _finish_build(
                args, "training", mspec,
                incumbent.ps_group, None, None,
                collect_shards(data_dir), {}, {},
                kv_group=incumbent.kv_group,
                agg_group=incumbent.agg_group,
            )
            return serv, disp

        def _manager(disp):
            # constructed only AT adoption: WorkerManager's __init__
            # takes over the backend's single event callback — that
            # swap IS the fleet adoption. Relaunched workers (if any)
            # dial the standby's address as their primary.
            return WorkerManager(
                self.backend,
                disp,
                num_workers=spec.workers,
                worker_argv_fn=lambda wid: worker_forward_args(
                    args, wid, self.standby_master.addr
                ),
                envs=worker_envs,
                max_relaunches=4 * spec.workers,
                num_standby=spec.standby,
            )

        # short lease: scenario masters die fast and CI minutes are real
        self.standby_master = StandbyMaster(
            self.addr, _pair, manager_fn=_manager,
            lease_secs=2.0, manifest_secs=0.2,
        )
        # every worker learns both candidates at launch
        args.master_candidates = f"{self.addr},{self.standby_master.addr}"

    def kill_master(self, mode: str) -> dict:
        """The incumbent master dies. Its RPC server and recovery plane
        go away; the shard groups, the standby, and the worker fleet
        are separate processes/threads and survive — that survival is
        the premise of checkpoint-free adoption.

        ``handoff``: drain first (BeginHandoff → quiesced manifest,
        the SIGTERM-preemption shape), then the standby adopts that
        manifest — nothing requeues, nothing relaunches.
        ``sigkill``: the primary just disappears; the standby's lease
        watcher adopts its last cached manifest on its own (the driver
        loop observes the adoption via poll_migration)."""
        sb = self.standby_master
        assert sb is not None, "kill_master needs master_standby"
        self.migration = {
            "mode": mode,
            "t_kill": time.time(),
            "t_adopted": None,
            "t_first_progress": None,
            "baseline_completed": None,
            "relaunches_at_adopt": None,
            "adopt_reason": None,
        }
        if mode == "handoff":
            from elasticdl_tpu.master.migration import planned_handoff

            manifest = planned_handoff(self.addr)
            self._kill_primary()
            sb.adopt_now(manifest)
            self._complete_adoption()
        else:
            self._kill_primary()
        return {"mode": mode}

    def _kill_primary(self) -> None:
        if self._recovery is not None:
            self._recovery.stop()
            self._recovery = None
        self._killed_server = self.server
        self.server.stop()

    def _complete_adoption(self) -> None:
        """Swap the run's control-plane refs to the adopting master —
        from here on every probe and finish check exercises the new
        master's surfaces — and rebuild the master-main wiring the old
        master owned (stats surface, standby service, recovery)."""
        from elasticdl_tpu.master.main import make_sample_batch_fn

        sb = self.standby_master
        self.dispatcher = sb.dispatcher
        self.servicer = sb.servicer
        self.server = sb.server
        self.manager = sb.manager
        dispatcher, manager = self.dispatcher, self.manager

        def _stats() -> dict:
            out = {"workers": manager.snapshot()}
            out.update(dispatcher.sched_stats())
            out["goodput"] = dispatcher.goodput_stats()
            return out

        self.servicer.set_sched_stats_fn(_stats)
        if self.spec.standby:
            self.servicer.set_standby_fn(manager.is_standby)
            self.servicer.set_sample_batch_fn(
                make_sample_batch_fn(self._data_dir)
            )
        if (self.servicer.ps_group is not None
                or self.servicer.kv_group is not None):
            from elasticdl_tpu.master.recovery import RecoveryPlane

            def _unrecoverable(kind, sid):
                self.ps_dead.set()

            self._recovery = RecoveryPlane(
                self.servicer,
                ps_group=self.servicer.ps_group,
                kv_group=self.servicer.kv_group,
                agg_group=self.servicer.agg_group,
                on_unrecoverable=_unrecoverable,
            )
            self.servicer.set_recovery_plane(self._recovery)
            self._recovery.start()
            self.manager.on_shard_failure = self._recovery.on_shard_failure
        self.migration.update(
            t_adopted=time.time(),
            adopt_reason=sb.adopt_reason,
            baseline_completed=self.dispatcher.completed_records(),
            relaunches_at_adopt=self.manager.snapshot()["relaunches"],
        )
        logger.info(
            "scenario job %s: standby adopted (%s) %.3fs after the kill",
            self.spec.tag, sb.adopt_reason,
            self.migration["t_adopted"] - self.migration["t_kill"],
        )

    def poll_migration(self) -> None:
        """Driver-loop hook: finalize a lease-expiry (sigkill) adoption
        when the watcher fires, and stamp the first post-cutover
        progress (completed records past the restored baseline)."""
        sb, mig = self.standby_master, self.migration
        if sb is None or mig is None:
            return
        if mig["t_adopted"] is None:
            if sb.adopted:
                self._complete_adoption()
            return
        if (mig["t_first_progress"] is None
                and self.dispatcher.completed_records()
                > mig["baseline_completed"]):
            mig["t_first_progress"] = time.time()

    def migration_report(self) -> Optional[dict]:
        """None when no kill_master fired; otherwise the failover block
        for the scenario report (time-to-adopt is the headline)."""
        mig = self.migration
        if mig is None:
            return None
        if mig["t_adopted"] is None:
            return {"adopted": False, "mode": mig["mode"]}
        relaunches_after = (
            self.manager.snapshot()["relaunches"]
            - mig["relaunches_at_adopt"]
        )
        return {
            "adopted": True,
            "mode": mig["mode"],
            "adopt_reason": mig["adopt_reason"],
            "time_to_adopt_secs": round(
                mig["t_adopted"] - mig["t_kill"], 3
            ),
            "time_to_first_progress_secs": (
                round(mig["t_first_progress"] - mig["t_kill"], 3)
                if mig["t_first_progress"] is not None
                else None
            ),
            "manifests_seen": self.standby_master.manifests_seen,
            "worker_relaunches_after_cutover": relaunches_after,
        }

    def exactness_probe(self) -> dict:
        """One GetSchedStats round — the REAL stats code path, not a
        private-field peek — asserting the master-version invariant.
        PS-sharded jobs carry their versions on the shards; those are
        asserted exactly at completion (a mid-restore assemble is not
        a stable read), so here their master invariant is the trivial
        one (version==init, applied==0) and still must hold."""
        st = self.servicer.get_sched_stats({})
        ex = st["exactness"]
        assert ex["version"] == (
            ex["init_version"] + ex["applied_update_steps"]
        ), (
            f"job {self.spec.tag}: version {ex['version']} != init "
            f"{ex['init_version']} + applied {ex['applied_update_steps']}"
            " — an update advanced the model without being counted"
        )
        self.probes += 1
        return st

    def finish_checks(self) -> dict:
        """Exactness at completion: zero dropped tasks, every record
        exactly once, version == applied pushes exactly."""
        spec = self.spec
        assert not self.dispatcher.has_failed_tasks(), (
            f"job {spec.tag}: dropped tasks"
        )
        done = self.dispatcher.completed_records()
        assert done == spec.total, (
            f"job {spec.tag}: completed {done} != total {spec.total}"
        )
        st = self.exactness_probe()
        versions: List[int] = []
        if self.servicer.ps_group is not None:
            versions, _ = self.servicer.ps_group.assemble()
            assert list(versions) == (
                [spec.expected_version] * spec.num_ps
            ), (
                f"job {spec.tag}: shard versions {list(versions)} != "
                f"{[spec.expected_version] * spec.num_ps}"
            )
        else:
            v = self.servicer.version
            assert v == spec.expected_version, (
                f"job {spec.tag}: version {v} != expected "
                f"{spec.expected_version} "
                f"({spec.total} records / {spec.minibatch} minibatch)"
            )
            versions = [v]
        return {"stats": st, "versions": list(versions)}

    def stop(self) -> None:
        if self.standby_master is not None:
            # join the lease watcher; its server is self.server after a
            # completed adoption (stopped below), still gated otherwise
            self.standby_master.stop(
                stop_server=self.standby_master.server is not self.server
            )
        if self._recovery is not None:
            self._recovery.stop()
        if self.manager is not None:
            self.manager.stop_relaunch_and_remove_workers()
        if self.backend is not None:
            self.backend.stop()
        # shard tiers in main.py's teardown order (agg, ps, kv),
        # best-effort each: a failed scenario must not leak orphan
        # shard processes holding the parent's stdio pipes open
        shard_groups = () if self.servicer is None else (
            self.servicer.agg_group,
            self.servicer.ps_group,
            self.servicer.kv_group,
        )
        for group in shard_groups:
            if group is not None:
                try:
                    group.stop()
                except Exception:
                    logger.warning(
                        "scenario job %s: shard group stop failed",
                        self.spec.tag,
                        exc_info=True,
                    )
        if self.server is not None and self.server is not self._killed_server:
            self.server.stop()


# -- the runner --------------------------------------------------------------


class ScenarioRunner:
    """Executes one TraceSpec against a live fleet and returns the
    scenario report (one JSON-able dict). Raises on any broken
    invariant — after dumping the flight recorder for the postmortem."""

    def __init__(
        self,
        trace: TraceSpec,
        *,
        scale: float = 1.0,
        seed: Optional[int] = None,
        probe_secs: Optional[float] = None,
        run_dir: Optional[str] = None,
    ):
        self.trace = trace
        self.scale = float(scale)
        env_seed = os.environ.get(ENV_TRACE_SEED, "").strip()
        self.sched = ScenarioScheduler(
            trace,
            seed=(seed if seed is not None
                  else int(env_seed) if env_seed else None),
        )
        self.probe_secs = (
            probe_secs
            if probe_secs is not None
            else float(os.environ.get(ENV_TRACE_PROBE_SECS, "0.5"))
        )
        self.run_dir = run_dir or tempfile.mkdtemp(
            prefix=f"edl_scenario_{trace.name}_"
        )
        self._jobs: Dict[str, JobRun] = {}

    # records are scaled in whole task-chunks so every sizing invariant
    # (whole windows per task, whole tasks per shard) survives the CI
    # shrink knob
    def _scaled(self, spec: JobSpec) -> JobSpec:
        if self.scale == 1.0:
            return spec
        chunk = DATA_SHARDS * spec.records_per_task
        records = max(chunk, round(spec.records * self.scale / chunk) * chunk)
        out = JobSpec(**{**spec.__dict__, "records": records})
        return out

    def _latch_path(self, name: str) -> str:
        return os.path.join(self.run_dir, "latches", f"{name}.armed")

    def _chaos_env(self) -> Dict[str, str]:
        """Rewrite latch names to run-dir paths and point the workers'
        inherited EDL_CHAOS_SPEC at the rewritten spec file. Worker-env
        only: the master process and PS/KV/agg shard spawns don't get
        the spec unless a fault's role scoping asks for them — which
        role-scoped entries do via the workers carrying the faults on
        their CLIENT side of every plane."""
        if self.trace.chaos is None:
            return {}
        os.makedirs(os.path.join(self.run_dir, "latches"), exist_ok=True)
        spec = json.loads(json.dumps(self.trace.chaos))  # deep copy
        for f in spec.get("faults", []):
            if f.get("armed_file"):
                f["armed_file"] = self._latch_path(f["armed_file"])
        path = os.path.join(self.run_dir, "chaos_spec.json")
        with open(path, "w") as fh:
            json.dump(spec, fh)
        return {ENV_CHAOS_SPEC: f"@{path}"}

    def _boot(self, spec: JobSpec, worker_env: Dict[str, str]) -> JobRun:
        run = JobRun(
            self._scaled(spec),
            self.run_dir,
            worker_env,
        )
        run.start()
        return run

    def _execute(self, ev: TraceEvent) -> None:
        sched, job = self.sched, self._jobs.get(ev.job)
        if job is None and ev.action in ("kill", "drain", "scale_up",
                                         "kill_host", "kill_master"):
            raise RuntimeError(
                f"trace event {ev.action} anchored to job {ev.job!r} "
                "which was never spawned"
            )
        if ev.action == "kill":
            alive = job.alive_workers()
            count = sched.kill_count(len(alive), ev)
            victims = sched.pick_victims(alive, count)
            killed = job.sigkill_workers(victims)
            sched.record(
                "kill", ev.job, victims=victims, killed=killed,
                alive=len(alive),
            )
        elif ev.action == "drain":
            stopped = job.manager.scale_down(ev.count)
            sched.record("drain", ev.job, count=ev.count, stopped=stopped)
        elif ev.action == "scale_up":
            started = job.manager.scale_up(ev.count)
            sched.record("scale_up", ev.job, started=started)
        elif ev.action == "spawn_job":
            spec = self.trace.job(ev.spawn)
            self._jobs[ev.spawn] = self._boot(spec, self._worker_env)
            sched.record("spawn_job", ev.job, spawn=ev.spawn)
        elif ev.action == "chaos_arm":
            path = self._latch_path(ev.latch)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w"):
                pass
            sched.record("chaos_arm", ev.job, latch=ev.latch)
        elif ev.action == "chaos_disarm":
            try:
                os.unlink(self._latch_path(ev.latch))
            except FileNotFoundError:
                pass
            sched.record("chaos_disarm", ev.job, latch=ev.latch)
        elif ev.action == "kill_host":
            result = job.kill_host(ev.host)
            sched.record("kill_host", ev.job, **result)
        elif ev.action == "kill_master":
            result = job.kill_master(ev.mode)
            sched.record("kill_master", ev.job, **result)
        logger.info("scenario %s: fired %s", self.trace.name,
                    sched.timeline[-1])

    def _run_baseline(self) -> Optional[float]:
        """Fault-free twin of the anchor job (same data seed, same
        sizing, no events, no chaos): the denominator for retention.
        Sequential on purpose — running it beside the churn fleet
        would contaminate both measurements with CPU contention."""
        if not self.trace.baseline:
            return None
        spec = self.trace.jobs[0]
        base = JobRun(
            self._scaled(
                JobSpec(**{**spec.__dict__, "tag": f"{spec.tag}-baseline"})
            ),
            self.run_dir,
            {},
        )
        base.start()
        try:
            deadline = time.time() + self.trace.time_limit_secs
            while not base.dispatcher.finished():
                if time.time() > deadline:
                    raise RuntimeError("baseline run timed out")
                if base.manager.all_exited():
                    raise RuntimeError("baseline: all workers exited")
                done = base.dispatcher.completed_records()
                if base.t0 is None and done > 0:
                    base.t0 = time.time()
                time.sleep(0.05)
            base.t_end = time.time()
            base.finish_checks()
            return base.dispatcher.completed_records() / (
                base.t_end - base.t0
            )
        finally:
            base.stop()

    def run(self) -> dict:
        trace = self.trace
        try:
            baseline_ips = self._run_baseline()
            self._worker_env = self._chaos_env()
            for spec in trace.jobs:
                if not spec.deferred:
                    self._jobs[spec.tag] = self._boot(
                        spec, self._worker_env
                    )
            report = self._drive(baseline_ips)
        except (AssertionError, RuntimeError) as e:
            # the postmortem: the in-memory flight ring (chaos fires,
            # generation bumps, scenario events) dumped to EDL_FLIGHT_DIR
            obs_flight.record(
                "scenario_failed", trace=trace.name, error=str(e)
            )
            path = obs_flight.dump_on_crash(reason="scenario_assert")
            print(
                f"chaos.scenario: {trace.name} FAILED: {e}\n"
                f"chaos.scenario: flight recorder dump: {path}",
                file=sys.stderr,
            )
            raise
        finally:
            self._stop_all()
        return report

    def _stop_all(self) -> None:
        """Stop every booted job, isolating per-job failures: on the
        assert-failure exit this runs as the finally sweep, and one
        job's raising stop() must not strand the Popen fleets of the
        jobs after it in the dict. The first error still propagates —
        a broken teardown is itself a scenario failure."""
        first_error: Optional[BaseException] = None
        for tag, run in list(self._jobs.items()):
            try:
                run.stop()
            except Exception as e:
                logger.warning(
                    "scenario: stopping job %s failed", tag, exc_info=True
                )
                if first_error is None:
                    first_error = e
        if first_error is not None:
            raise first_error

    def _drive(self, baseline_ips: Optional[float]) -> dict:
        trace, sched = self.trace, self.sched
        t_start = time.time()
        deadline = t_start + trace.time_limit_secs
        next_probe = t_start

        def progress(tag: str) -> int:
            run = self._jobs.get(tag)
            return run.dispatcher.completed_records() if run else 0

        def totals() -> Dict[str, int]:
            return {t: r.spec.total for t, r in self._jobs.items()}

        while True:
            now = time.time()
            if now > deadline:
                raise RuntimeError(
                    f"scenario {trace.name} exceeded its "
                    f"{trace.time_limit_secs:.0f}s time limit"
                )
            running = False
            for run in self._jobs.values():
                if run.ps_dead.is_set():
                    raise RuntimeError(
                        f"job {run.spec.tag}: unrecoverable PS/KV shard"
                    )
                run.poll_migration()
                done = run.dispatcher.completed_records()
                if run.t0 is None and done > 0:
                    run.t0 = now
                if run.dispatcher.finished():
                    if run.t_end is None:
                        run.t_end = now
                else:
                    running = True
                    if run.manager.all_exited():
                        raise RuntimeError(
                            f"job {run.spec.tag}: all workers exited "
                            "with tasks outstanding"
                        )
            for ev in sched.due_events(
                progress, totals(), now - t_start
            ):
                self._execute(ev)
            if now >= next_probe:
                for run in self._jobs.values():
                    if run.t_end is None:
                        run.exactness_probe()
                next_probe = now + self.probe_secs
            if not running:
                # leftover events fall through to the assert below: a
                # trace whose churn never fired proved nothing
                break
            time.sleep(0.05)

        assert sched.pending() == 0, (
            f"{sched.pending()} trace events never fired — the run "
            "finished before their anchors; size the trace down"
        )
        jobs_out: Dict[str, dict] = {}
        agg_expect: Dict[str, int] = {k: 0 for k in _EXPECT_KEYS}
        for tag, run in self._jobs.items():
            final = run.finish_checks()
            elapsed = (run.t_end - run.t0) if run.t0 else 0.0
            counters = run.dispatcher.goodput_stats()
            goodput = compute_goodput(counters, elapsed)
            snap = run.manager.snapshot()
            sched_stats = run.dispatcher.sched_stats()
            jobs_out[tag] = {
                "total_records": run.spec.total,
                "elapsed_secs": round(elapsed, 3),
                "goodput": {
                    k: (round(v, 3) if isinstance(v, float) else v)
                    for k, v in goodput.items()
                },
                "relaunches": snap["relaunches"],
                "promotions": snap["promotions"],
                "policy_stops": snap["policy_stops"],
                "scale_ups": snap["scale_ups"],
                "scale_downs": snap["scale_downs"],
                "backups_dispatched": sched_stats.get(
                    "backups_dispatched", 0
                ),
                "backup_wins": sched_stats.get("backup_wins", 0),
                "versions": final["versions"],
                "expected_version": run.spec.expected_version,
                "exactness_probes": run.probes,
            }
            mig = run.migration_report()
            if mig is not None:
                assert mig["adopted"], (
                    f"job {tag}: kill_master fired but the standby "
                    "never adopted the job"
                )
                if mig["mode"] == "handoff":
                    # the planned-drain contract: the fleet moves with
                    # the job — nobody restarts
                    assert mig["worker_relaunches_after_cutover"] == 0, (
                        f"job {tag}: planned hand-off relaunched "
                        f"{mig['worker_relaunches_after_cutover']} "
                        "worker(s); the drained fleet must move as-is"
                    )
                jobs_out[tag]["master_failover"] = mig
            if trace.gap_explained_tolerance is not None:
                g = goodput["gap_explained"]
                if g is not None:
                    assert abs(g - 1.0) <= trace.gap_explained_tolerance, (
                        f"job {tag}: gap_explained {g} strays more than "
                        f"{trace.gap_explained_tolerance} from 1.0 — the "
                        "goodput gap is not explained by the recompute "
                        "counter"
                    )
            agg_expect["min_relaunches"] += snap["relaunches"]
            agg_expect["min_promotions"] += snap["promotions"]
            agg_expect["min_policy_stops"] += snap["policy_stops"]
            agg_expect["min_scale_ups"] += snap["scale_ups"]
            agg_expect["min_requeued_records"] += counters[
                "requeued_records"
            ]
            agg_expect["min_recomputed_records"] += counters[
                "recomputed_records"
            ]
            agg_expect["min_drain_flushed_records"] += counters[
                "drain_flushed_records"
            ]
            agg_expect["min_preempted_task_requeues"] += counters[
                "preempted_task_requeues"
            ]
        for key, floor in trace.expect.items():
            assert agg_expect[key] >= floor, (
                f"expect.{key}: observed {agg_expect[key]} < {floor} — "
                "the scenario did not exercise what it claims to"
            )
        anchor = jobs_out[trace.jobs[0].tag]
        retention = (
            round(
                anchor["goodput"]["raw_images_per_sec"] / baseline_ips, 3
            )
            if baseline_ips
            else None
        )
        return {
            "metric": "churn_scenario",
            "trace": trace.name,
            "description": trace.description,
            "seed": sched.seed,
            "scale": self.scale,
            "retention": retention,
            "baseline_images_per_sec": (
                round(baseline_ips, 1) if baseline_ips else None
            ),
            "jobs": jobs_out,
            "events": [json.loads(line) for line in sched.timeline],
        }


def run_scenario(name_or_path: str, **kwargs) -> dict:
    return ScenarioRunner(load_trace(name_or_path), **kwargs).run()
