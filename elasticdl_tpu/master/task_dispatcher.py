"""Dynamic data sharder: the task queue that makes training elastic.

Re-implementation of the reference's `_TaskDispatcher`
(elasticdl/python/master/task_dispatcher.py:30-197) with identical
semantics:

- shards `{file: num_records}` into Tasks of `records_per_task` records;
- shuffles training tasks per epoch and lazily rolls epochs;
- `get(worker_id)` moves a task todo -> doing;
- `report(task_id, success)` requeues failures;
- `recover_tasks(worker_id)` requeues every in-flight task of a dead
  worker — the entire fault-tolerance story (no checkpoint recovery);
- evaluation tasks are pinned to a model version.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.common.messages import Task, TaskType

logger = get_logger(__name__)


def _percentile(sorted_vals: List[float], p: float) -> float:
    """Nearest-rank percentile of a pre-sorted list (p in 0..1)."""
    idx = int(round(p * (len(sorted_vals) - 1)))
    return sorted_vals[min(len(sorted_vals) - 1, max(0, idx))]


class TaskDispatcher:
    def __init__(
        self,
        training_shards: Dict[str, int],
        evaluation_shards: Dict[str, int],
        prediction_shards: Dict[str, int],
        records_per_task: int,
        num_epochs: int,
        max_task_retries: int = 10,
        eval_model_version: int = -1,
        shuffle_seed: Optional[int] = None,
        speculate: bool = False,
        spec_percentile: float = 0.5,
        spec_factor: float = 1.5,
        spec_min_completed: int = 3,
        max_backups: int = 2,
        speculate_training: bool = True,
        clock: Callable[[], float] = time.monotonic,
    ):
        self._lock = threading.Lock()
        # per-dispatcher RNG: a seed pins the epoch shuffle order
        # (deterministic replays / equivalence tests); None keeps the
        # reference's behavior — the process-global stream, which
        # `random.seed()` callers can still pin externally
        self._shuffle_rng = (
            random.Random(shuffle_seed) if shuffle_seed is not None else random
        )
        # Unlike the reference (which requeues failed tasks forever,
        # task_dispatcher.py:153-176), cap per-task retries so a poison
        # task (bad record / model bug) fails the shard loudly instead
        # of livelocking the job.
        self._max_task_retries = max_task_retries
        self._retry_count: Dict[int, int] = {}
        self.failed_tasks: list[Task] = []
        self._training_shards = training_shards
        self._evaluation_shards = evaluation_shards
        self._prediction_shards = prediction_shards
        self._records_per_task = records_per_task
        self._num_epochs = num_epochs
        self._epoch = 0
        self._task_id = 0
        self._todo: list[Task] = []
        # task_id -> (worker_id, task), mirrors reference :48-53
        self._doing: Dict[int, Tuple[int, Task]] = {}
        self._evaluation_service = None
        # cumulative records successfully trained (across epochs) —
        # progress/throughput introspection for benches and logs
        self._completed_records = 0
        # -- goodput accounting (chaos/scenario.py) -------------------
        # Goodput = useful records/sec after subtracting recomputation:
        # a task requeued by a death/failure is RE-trained from scratch,
        # so every prior dispatch of a task that eventually completes is
        # waste the raw throughput number silently absorbs. Dispatches
        # are counted per task; on success the (n_dispatches - 1) prior
        # attempts charge (end - start) records each to the recomputed
        # counter. Speculative-backup twins are deliberately NOT counted
        # here (they never ride the todo queue; the backup_* counters
        # already price that waste separately). Drain-flushed records —
        # tasks a SIGTERM'd worker finished before exiting — complete
        # exactly once, so they add to completed_records and the drain
        # counter but never to recomputed: no double-count.
        self._dispatch_counts: Dict[int, int] = {}
        self._requeued_records = 0
        self._recomputed_records = 0
        self._drain_flushed_records = 0
        self._preempted_task_requeues = 0
        # fn(worker_id) -> bool: the worker is mid graceful drain
        # (policy stop / SIGTERM); wired to
        # WorkerManager.is_policy_stopped by master main / the
        # scenario runner. Never called under the manager's lock.
        self._draining_fn: Optional[Callable[[int], bool]] = None
        # -- speculative straggler backups (elasticdl_tpu/sched/) -----
        # When a doing-task's runtime exceeds spec_factor x the
        # spec_percentile of completed same-type runtimes, an idle
        # worker gets a BACKUP copy carrying the same spec_key; the
        # copies' window pushes share deterministic report_keys, so
        # whichever lands second is absorbed by dedup, and the first
        # task report settles both (first-report-wins).
        # speculate_training is gated off by main in per-step sync mode
        # (no report_key dedup covers per-step grads).
        self._speculate = bool(speculate)
        self._spec_percentile = float(spec_percentile)
        self._spec_factor = float(spec_factor)
        self._spec_min_completed = max(1, int(spec_min_completed))
        self._max_backups = max(0, int(max_backups))
        self._speculate_training = bool(speculate_training)
        self._clock = clock
        self._attempt_seq = 0
        self._started: Dict[int, float] = {}  # task_id -> dispatch time
        self._durations: Dict[str, List[float]] = {}  # type -> runtimes
        self._backups: Dict[int, int] = {}  # task_id -> backup worker
        self._backups_dispatched = 0
        self._backup_wins = 0
        self._primary_wins = 0
        self._backup_promotions = 0
        self._late_reports = 0
        # migration plane (master/migration.py): while paused, get()
        # hands out nothing (workers WAIT at task boundaries) so the
        # doing-map drains and the exported manifest quiesces before a
        # planned hand-off cuts over
        self._paused = False

        if self._training_shards:
            logger.info("Starting epoch %d", self._epoch)
            self._create_training_tasks()
        elif self._evaluation_shards:
            # standalone evaluation job: tasks pinned to the version the
            # master booted from (its init checkpoint)
            self._create_tasks_no_lock(
                self._evaluation_shards, TaskType.EVALUATION, eval_model_version
            )
        elif self._prediction_shards:
            self._create_tasks_no_lock(self._prediction_shards, TaskType.PREDICTION)

    # -- task creation ------------------------------------------------------

    def _shard_to_tasks(self, shards: Dict[str, int], task_type: str, model_version: int = -1):
        tasks = []
        for name, num_records in shards.items():
            for start in range(0, num_records, self._records_per_task):
                tasks.append(
                    Task(
                        task_id=-1,  # assigned at queue time
                        shard_file_name=name,
                        start=start,
                        end=min(start + self._records_per_task, num_records),
                        type=task_type,
                        model_version=model_version,
                    )
                )
        return tasks

    def _create_training_tasks(self):
        tasks = self._shard_to_tasks(self._training_shards, TaskType.TRAINING)
        self._shuffle_rng.shuffle(tasks)  # per-epoch shuffle (reference :76-85)
        self._extend_todo(tasks)

    def _create_tasks_no_lock(self, shards, task_type, model_version=-1):
        self._extend_todo(self._shard_to_tasks(shards, task_type, model_version))

    def _extend_todo(self, tasks):  # edl-lint: disable=lock-discipline -- caller holds self._lock
        for t in tasks:
            self._task_id += 1
            t.task_id = self._task_id
            self._todo.append(t)

    def create_evaluation_tasks(self, model_version: int) -> int:
        """Pin EVALUATION tasks to a model version (reference :87-99).
        Returns the number of tasks created."""
        with self._lock:
            before = len(self._todo)
            self._create_tasks_no_lock(
                self._evaluation_shards, TaskType.EVALUATION, model_version
            )
            return len(self._todo) - before

    def set_evaluation_service(self, evaluation_service):
        self._evaluation_service = evaluation_service

    # -- worker-facing ------------------------------------------------------

    def get(self, worker_id: int) -> Optional[Task]:
        """Pop the next task (todo -> doing); lazily roll the next epoch
        (reference :130-151). Returns None when nothing is available."""
        with self._lock:
            if self._paused:
                # drain latch (BeginHandoff): nothing new goes out, but
                # reports for in-flight tasks keep landing — the worker
                # sees WAIT, exactly like an exhausted-but-unfinished
                # epoch boundary
                return None
            if not self._todo and self._training_shards:
                if self._epoch < self._num_epochs - 1:
                    self._epoch += 1
                    logger.info("Starting epoch %d", self._epoch)
                    self._create_training_tasks()
            if not self._todo:
                # idle worker + empty queue: maybe clone a straggler
                return self._pick_backup_locked(worker_id)
            task = self._todo.pop(0)
            # attempt key fixed at FIRST dispatch and kept across
            # failure requeues: the worker derives window report_keys
            # from it, so a retrained task re-pushing a window its dead
            # predecessor already landed is absorbed by dedup — the
            # speculation twin rule (first-report-wins) generalized to
            # requeues. Without this, a kill between a window push and
            # the task report inflates the final version past the
            # fault-free count. Epoch re-creations mint new task_ids,
            # so keys never straddle epochs.
            if not task.spec_key:
                self._attempt_seq += 1
                task.spec_key = f"t{task.task_id}.a{self._attempt_seq}"
            task.backup = False
            self._doing[task.task_id] = (worker_id, task)
            self._started[task.task_id] = self._clock()
            self._dispatch_counts[task.task_id] = (
                self._dispatch_counts.get(task.task_id, 0) + 1
            )
            return task

    def _pick_backup_locked(self, worker_id: int) -> Optional[Task]:  # edl-lint: disable=lock-discipline -- caller holds self._lock
        """Speculation: pick the worst straggler among other workers'
        in-flight tasks and hand `worker_id` a backup copy of it."""
        if not self._speculate or len(self._backups) >= self._max_backups:
            return None
        now = self._clock()
        best: Optional[Tuple[float, Task]] = None
        for tid, (owner, task) in self._doing.items():
            if owner == worker_id or tid in self._backups:
                continue
            if task.type == TaskType.TRAINING and not self._speculate_training:
                continue
            durations = self._durations.get(task.type)
            if durations is None or len(durations) < self._spec_min_completed:
                continue
            threshold = self._spec_factor * _percentile(
                sorted(durations), self._spec_percentile
            )
            started = self._started.get(tid)
            if started is None:
                continue
            overrun = (now - started) - threshold
            if overrun <= 0:
                continue
            if best is None or overrun > best[0]:
                best = (overrun, task)
        if best is None:
            return None
        task = best[1]
        self._backups[task.task_id] = worker_id
        self._backups_dispatched += 1
        logger.info(
            "Speculating: backup of straggler task %d (%.2fs past the "
            "threshold) dispatched to worker %d",
            task.task_id,
            best[0],
            worker_id,
        )
        # a copy, so requeueing the stored primary later never carries
        # the backup flag
        return dataclasses.replace(task, backup=True)

    def report(
        self, task_id: int, success: bool, worker_id: Optional[int] = None
    ) -> bool:
        """Worker reports task done/failed; failures are requeued
        (reference :153-176). Returns False for unknown ids.

        When `worker_id` is given it must match the doing-map owner —
        or the task's speculative backup worker: first-report-wins
        settles a speculated pair, and the loser's late report is
        absorbed here exactly like a stale duplicate. A stale duplicate
        report (e.g. a worker whose failed-sync path already reported
        the task, after which another worker claimed the requeued
        shard) must not pop the new owner's entry."""
        evaluation_task_completed = None
        # probed BEFORE taking our lock: the draining fn reaches into
        # the WorkerManager's lock, and nesting it under self._lock
        # would create a cross-module lock order for no benefit (a
        # drain latch cannot flip mid-report — the worker only exits
        # after this report returns)
        draining = (
            self._draining_fn is not None
            and worker_id is not None
            and self._draining_fn(worker_id)
        )
        with self._lock:
            worker_and_task = self._doing.get(task_id)
            if worker_and_task is None:
                # the usual benign case: the losing copy of an
                # already-settled speculated pair reporting late
                self._late_reports += 1
                logger.warning("Unknown task completion report: %d", task_id)
                return False
            owner, task = worker_and_task
            backup_wid = self._backups.get(task_id)
            from_backup = (
                worker_id is not None
                and worker_id == backup_wid
                and owner != worker_id
            )
            if worker_id is not None and owner != worker_id and not from_backup:
                logger.warning(
                    "Stale report for task %d from worker %d "
                    "(now owned by worker %d); ignoring",
                    task_id,
                    worker_id,
                    owner,
                )
                return False
            if not success and backup_wid is not None and worker_id is not None:
                # one copy of a speculated pair failed while its twin
                # still runs: drop only the failed copy — requeueing
                # here would race a THIRD copy against the live twin
                del self._backups[task_id]
                if not from_backup:
                    self._doing[task_id] = (backup_wid, task)
                    self._backup_promotions += 1
                    logger.info(
                        "Task %d primary failed; backup worker %d "
                        "promoted to owner",
                        task_id,
                        backup_wid,
                    )
                return True
            del self._doing[task_id]
            self._backups.pop(task_id, None)
            started = self._started.pop(task_id, None)
            if success:
                if started is not None:
                    durations = self._durations.setdefault(task.type, [])
                    durations.append(self._clock() - started)
                    if len(durations) > 256:
                        durations.pop(0)
                if from_backup:
                    self._backup_wins += 1
                elif backup_wid is not None:
                    self._primary_wins += 1
            if success and task.type == TaskType.TRAINING:
                self._completed_records += task.end - task.start
            if success:
                # goodput: every dispatch before the winning one was a
                # full re-train of this shard (requeued-and-retrained);
                # a first-dispatch success charges nothing
                prior = self._dispatch_counts.pop(task_id, 1) - 1
                if prior > 0 and task.type == TaskType.TRAINING:
                    self._recomputed_records += prior * (task.end - task.start)
                if draining and task.type == TaskType.TRAINING:
                    # flushed by a graceful drain: counted ONCE (it is
                    # already in completed_records); surfaced so the
                    # drain's overhead is attributable, never subtracted
                    self._drain_flushed_records += task.end - task.start
            if not success:
                n = self._retry_count.get(task_id, 0) + 1
                self._retry_count[task_id] = n
                if n >= self._max_task_retries:
                    logger.error(
                        "Task %d failed %d times, dropping (poison task)",
                        task_id,
                        n,
                    )
                    self.failed_tasks.append(task)
                    self._dispatch_counts.pop(task_id, None)
                    # a dropped EVALUATION task still counts toward the
                    # eval job's completion, else has_pending() wedges
                    # every worker in WAIT forever
                    if (
                        task.type == TaskType.EVALUATION
                        and self._evaluation_service is not None
                    ):
                        evaluation_task_completed = task
                else:
                    logger.warning("Task %d failed, requeueing", task_id)
                    if task.type == TaskType.TRAINING:
                        self._requeued_records += task.end - task.start
                    self._todo.append(task)
            elif (
                task.type == TaskType.EVALUATION
                and self._evaluation_service is not None
            ):
                evaluation_task_completed = task
        if evaluation_task_completed is not None:
            self._evaluation_service.complete_task()
        return True

    def completed_records(self) -> int:
        """Cumulative records successfully trained (across epochs)."""
        with self._lock:
            return self._completed_records

    def set_draining_fn(self, fn: Callable[[int], bool]):
        """fn(worker_id) -> True while the worker is mid graceful drain
        (wired to WorkerManager.is_policy_stopped); lets report()
        attribute drain-flushed completions."""
        self._draining_fn = fn

    def goodput_stats(self) -> dict:
        """Goodput accounting counters, one lock acquisition (a
        mutually consistent snapshot for the exactness probes):
        goodput subtracts `recomputed_records` from
        `completed_records`; `requeued_records` is the work currently
        owed to re-training (it becomes recomputed when the requeued
        task completes); `drain_flushed_records` is informational —
        that work completed exactly once."""
        with self._lock:
            return {
                "completed_records": self._completed_records,
                "requeued_records": self._requeued_records,
                "recomputed_records": self._recomputed_records,
                "drain_flushed_records": self._drain_flushed_records,
                "preempted_task_requeues": self._preempted_task_requeues,
            }

    def recover_tasks(self, worker_id: int):
        """Requeue every in-flight task of a dead worker
        (reference :182-190) — invoked from the pod-event callback.

        Does NOT touch the poison-task retry counter: worker preemption
        is the framework's normal elasticity event, and a healthy task
        that keeps landing on dying workers must never be classified as
        poison."""
        with self._lock:
            # the dead worker held BACKUP copies: drop just those —
            # the primaries are still running
            for tid in [t for t, w in self._backups.items() if w == worker_id]:
                del self._backups[tid]
            for tid in [
                tid for tid, (wid, _) in self._doing.items() if wid == worker_id
            ]:
                backup_wid = self._backups.pop(tid, None)
                if backup_wid is not None:
                    # the straggler died but its speculative twin is
                    # live: promote the backup instead of racing a
                    # requeued third copy against it
                    _, task = self._doing[tid]
                    self._doing[tid] = (backup_wid, task)
                    self._backup_promotions += 1
                    logger.info(
                        "Task %d owner %d died; backup worker %d "
                        "promoted to owner",
                        tid,
                        worker_id,
                        backup_wid,
                    )
                    continue
                _, task = self._doing.pop(tid)
                self._started.pop(tid, None)
                logger.info("Recovering task %d from dead worker %d", tid, worker_id)
                if task.type == TaskType.TRAINING:
                    self._requeued_records += task.end - task.start
                self._preempted_task_requeues += 1
                self._todo.append(task)

    def finished(self) -> bool:
        """All epochs exhausted and nothing in flight (reference :178-180).
        True even when tasks were dropped as poison — the job *ends*;
        callers must check `has_failed_tasks()` to decide success."""
        with self._lock:
            if self._training_shards and self._epoch < self._num_epochs - 1:
                return False
            return not self._todo and not self._doing

    def pending_count(self, task_type: Optional[str] = None) -> int:
        """Number of queued (todo) tasks, optionally of one type."""
        with self._lock:
            if task_type is None:
                return len(self._todo)
            return sum(1 for t in self._todo if t.type == task_type)

    def sched_stats(self) -> dict:
        """Speculation counters for the policy-plane stats surface
        (GetSchedStats) and the bench JSON."""
        with self._lock:
            return {
                "speculate": self._speculate,
                "backups_dispatched": self._backups_dispatched,
                "backups_inflight": len(self._backups),
                "backup_wins": self._backup_wins,
                "primary_wins": self._primary_wins,
                "backup_promotions": self._backup_promotions,
                "late_reports": self._late_reports,
            }

    def has_failed_tasks(self) -> bool:
        """True when any task was dropped after exhausting its retries —
        the job completed over partial data and must be reported as
        failed by the master exit path."""
        with self._lock:
            return bool(self.failed_tasks)

    # -- migration plane (master/migration.py) -------------------------------

    def pause(self):
        """Drain latch for a planned master hand-off (BeginHandoff):
        get() answers None (workers WAIT) until resume(), while
        report() keeps settling in-flight tasks — the doing-map drains
        to empty and the exported state quiesces. Latch-idempotent."""
        with self._lock:
            self._paused = True

    def resume(self):
        with self._lock:
            self._paused = False

    def is_quiesced(self) -> bool:
        """Paused with nothing in flight: the exported state is final
        until resume() — the planned hand-off's cut-over condition."""
        with self._lock:
            return self._paused and not self._doing

    def export_state(self) -> dict:
        """The dispatcher's full mutable state as one wire-serializable
        dict (the job manifest's task section), snapshotted under one
        lock acquisition so it is internally consistent. Tasks ride as
        their to_wire dicts WITH their pinned spec_keys — that is what
        lets an adopting master's re-dispatch of a replayed shard reuse
        the same window report_keys, so pushes the dead master's worker
        already landed are absorbed by shard dedup instead of
        double-applying. `_started` (dispatch wall-clock, meaningless
        in another process) stays behind; int-keyed maps ride as pair
        lists so the dict survives canonical-JSON serialization."""
        with self._lock:
            return {
                "schema": 1,
                "epoch": self._epoch,
                "task_id": self._task_id,
                "attempt_seq": self._attempt_seq,
                "paused": self._paused,
                "todo": [t.to_wire() for t in self._todo],
                "doing": [
                    [wid, t.to_wire()] for wid, t in self._doing.values()
                ],
                "retry_count": sorted(self._retry_count.items()),
                "failed_tasks": [t.to_wire() for t in self.failed_tasks],
                "dispatch_counts": sorted(self._dispatch_counts.items()),
                "backups": sorted(self._backups.items()),
                "durations": {
                    k: list(v) for k, v in sorted(self._durations.items())
                },
                "completed_records": self._completed_records,
                "requeued_records": self._requeued_records,
                "recomputed_records": self._recomputed_records,
                "drain_flushed_records": self._drain_flushed_records,
                "preempted_task_requeues": self._preempted_task_requeues,
                "backups_dispatched": self._backups_dispatched,
                "backup_wins": self._backup_wins,
                "primary_wins": self._primary_wins,
                "backup_promotions": self._backup_promotions,
                "late_reports": self._late_reports,
            }

    def restore_state(self, state: dict, requeue_doing: bool = True):
        """Adopt an exported dispatcher state (the new master's half of
        the manifest protocol). With `requeue_doing` (the adoption
        default) every in-flight task is put back at the head of the
        todo queue exactly like `recover_tasks` would: the old owner
        may still be running it, but its eventual report lands at this
        master as unknown/stale and is dropped, while the requeued
        copy's re-dispatch keeps the pinned spec_key — duplicate window
        pushes are absorbed shard-side, and the retrain is charged to
        `recomputed_records` through the surviving dispatch_counts
        entry, so the goodput gap stays explained. `requeue_doing=False`
        reproduces the exported state byte-identically (tests; planned
        hand-offs where the doing-map already drained to empty)."""
        if state.get("schema") != 1:
            raise ValueError(
                f"unknown dispatcher state schema: {state.get('schema')!r}"
            )
        with self._lock:
            self._epoch = int(state["epoch"])
            self._task_id = int(state["task_id"])
            self._attempt_seq = int(state["attempt_seq"])
            self._paused = bool(state["paused"])
            self._todo = [Task.from_wire(d) for d in state["todo"]]
            self._doing = {
                Task.from_wire(d).task_id: (int(wid), Task.from_wire(d))
                for wid, d in state["doing"]
            }
            self._retry_count = {
                int(k): int(v) for k, v in state["retry_count"]
            }
            self.failed_tasks = [
                Task.from_wire(d) for d in state["failed_tasks"]
            ]
            self._dispatch_counts = {
                int(k): int(v) for k, v in state["dispatch_counts"]
            }
            self._backups = {int(k): int(v) for k, v in state["backups"]}
            self._durations = {
                k: list(v) for k, v in state["durations"].items()
            }
            self._completed_records = int(state["completed_records"])
            self._requeued_records = int(state["requeued_records"])
            self._recomputed_records = int(state["recomputed_records"])
            self._drain_flushed_records = int(state["drain_flushed_records"])
            self._preempted_task_requeues = int(
                state["preempted_task_requeues"]
            )
            self._backups_dispatched = int(state["backups_dispatched"])
            self._backup_wins = int(state["backup_wins"])
            self._primary_wins = int(state["primary_wins"])
            self._backup_promotions = int(state["backup_promotions"])
            self._late_reports = int(state["late_reports"])
            self._started = {}
            if requeue_doing:
                requeued = []
                for tid in sorted(self._doing):
                    _, task = self._doing[tid]
                    if task.type == TaskType.TRAINING:
                        self._requeued_records += task.end - task.start
                    self._preempted_task_requeues += 1
                    requeued.append(task)
                self._doing = {}
                # a backup copy's owner map died with the old doing-map
                self._backups = {}
                self._todo = requeued + self._todo
            else:
                # in-flight tasks keep their owners; re-arm their
                # dispatch clocks so the speculation plane measures
                # from adoption, not from a dead master's monotonic era
                now = self._clock()
                self._started = {tid: now for tid in self._doing}
