"""Pod lifecycle backends.

The elasticity signal path is: backend watch -> PodEvent ->
WorkerManager callback -> TaskDispatcher.recover_tasks + relaunch
(reference: k8s_client.py:58-77 watch thread +
k8s_worker_manager.py:110-145 event handling).

`ProcessBackend` realizes "pods" as local worker subprocesses: a
monitor thread polls for exits and synthesizes DELETED/SUCCEEDED
events, so a SIGKILL on a worker process is indistinguishable (to the
WorkerManager) from a k8s pod preemption — which is exactly what the
preemption-injection tests exploit.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from elasticdl_tpu.common.device import (
    chip_env,
    cpu_requested,
    free_share,
    package_env,
)
from elasticdl_tpu.common.log_util import get_logger

logger = get_logger(__name__)


class PodPhase:
    PENDING = "Pending"
    RUNNING = "Running"
    SUCCEEDED = "Succeeded"
    FAILED = "Failed"
    DELETED = "Deleted"


@dataclass
class PodEvent:
    """One lifecycle transition of a worker (or PS shard) pod/process."""

    worker_id: int
    phase: str
    exit_code: Optional[int] = None
    replica_type: str = "worker"


class PodBackend:
    """Interface: start/stop worker pods and stream their events."""

    def start_worker(self, worker_id: int, argv: List[str], envs: Dict[str, str]):
        raise NotImplementedError

    def delete_worker(self, worker_id: int):
        raise NotImplementedError

    def set_event_callback(self, cb: Callable[[PodEvent], None]):
        raise NotImplementedError

    def victim_order(self, worker_ids: List[int]) -> List[int]:
        """Order candidates for a policy kill (autoscaler shrink / QoS
        preemption), most-preferred victim first. Default: youngest id
        first — the newest worker has the least warm state (compile
        cache, pulled model, in-flight windows) to throw away, so
        killing it loses the least invested boot cost."""
        return sorted(worker_ids, reverse=True)

    def stop(self):
        raise NotImplementedError


@dataclass
class _ProcEntry:
    proc: subprocess.Popen
    reported: bool = False
    deleted: bool = False
    log_path: str = ""
    started_at: float = 0.0  # monotonic spawn time (victim ordering)
    share: Tuple[int, ...] = ()  # chips this process was confined to


class ProcessBackend(PodBackend):
    """Workers as local subprocesses of ``python -m elasticdl_tpu.worker.main``.

    A daemon monitor thread polls child exits (the moral equivalent of
    the k8s watch stream) and fires the event callback with SUCCEEDED
    (exit 0), FAILED (nonzero), or DELETED (killed by signal /
    delete_worker) — the WorkerManager treats FAILED/DELETED alike:
    recover tasks, relaunch.
    """

    def __init__(
        self,
        worker_module: str = "elasticdl_tpu.worker.main",
        log_dir: str = "",
        poll_interval: float = 0.1,
        inherit_env: bool = True,
        chip_shares: Optional[Sequence[Tuple[int, ...]]] = None,
    ):
        # one process for each chip (common/device.py): every TPU
        # worker is confined to the first share no live worker holds,
        # so a relaunched replacement takes the chips its predecessor
        # released. {share: its environment}, in share order; None =
        # stamp nothing (CPU jobs, tests).
        self._share_envs = None
        if chip_shares is not None:
            host_chips = sum(len(s) for s in chip_shares)
            self._share_envs = {
                s: chip_env(s, host_chips) for s in chip_shares
            }
        self._worker_module = worker_module
        self._log_dir = log_dir
        self._poll = poll_interval
        self._inherit_env = inherit_env
        self._procs: Dict[int, _ProcEntry] = {}
        self._lock = threading.Lock()
        self._spawn_lock = threading.Lock()
        self._cb: Optional[Callable[[PodEvent], None]] = None
        self._stop = threading.Event()
        self._monitor = threading.Thread(target=self._watch, daemon=True)
        self._monitor.start()

    def set_event_callback(self, cb: Callable[[PodEvent], None]):
        # the monitor thread is already running (started in __init__)
        # and reads the callback per event — publish it under the lock
        with self._lock:
            self._cb = cb

    def start_worker(self, worker_id: int, argv: List[str], envs: Dict[str, str]):
        env = dict(os.environ) if self._inherit_env else {}
        env.update(envs)
        env = package_env(env)
        # chaos scoping: tag the child so an inherited EDL_CHAOS_SPEC
        # applies with role/target filters (inert when chaos is off) —
        # and so a spec aimed at workers never fires inside the master
        from elasticdl_tpu.rpc.chaos import chaos_env_for

        env.update(chaos_env_for("worker", worker_id))
        cmd = [sys.executable, "-m", self._worker_module] + list(argv)
        stdout = stderr = None
        log_path = ""
        if self._log_dir:
            os.makedirs(self._log_dir, exist_ok=True)
            log_path = os.path.join(self._log_dir, f"worker-{worker_id}.log")
            logf = open(log_path, "ab")
            stdout = stderr = logf
        # choosing a share and registering its holder are ONE step:
        # the monitor thread's relaunch and a policy scale-up may start
        # workers concurrently and must not pick the same free chips
        try:
            with self._spawn_lock:
                share = ()
                if self._share_envs is not None and not cpu_requested(env):
                    with self._lock:
                        held = [
                            e.share
                            for e in self._procs.values()
                            if e.share and e.proc.poll() is None
                        ]
                    share = free_share(list(self._share_envs), held)
                    env.update(self._share_envs[share])
                proc = subprocess.Popen(
                    cmd, env=env, stdout=stdout, stderr=stderr
                )
                with self._lock:
                    self._procs[worker_id] = _ProcEntry(
                        proc=proc,
                        log_path=log_path,
                        started_at=time.monotonic(),
                        share=share,
                    )
        finally:
            if stdout is not None:
                stdout.close()  # child holds its own descriptor
        logger.info(
            "Started worker %d (pid %d)%s",
            worker_id,
            proc.pid,
            f" on chips {list(share)}" if share else "",
        )
        with self._lock:
            cb = self._cb
        if cb:
            cb(PodEvent(worker_id, PodPhase.RUNNING))

    def delete_worker(self, worker_id: int):
        with self._lock:
            entry = self._procs.get(worker_id)
            if entry is None or entry.proc.poll() is not None:
                return
            entry.deleted = True
        try:
            entry.proc.send_signal(signal.SIGTERM)
            try:
                entry.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                entry.proc.kill()
        except ProcessLookupError:  # already gone
            pass

    def victim_order(self, worker_ids: List[int]) -> List[int]:
        """Prefer the most recently SPAWNED process, not the highest
        id: relaunches and standby refills can start a lower id after
        a higher one, and the youngest process is the one with the
        least jax-import/compile investment to lose."""
        with self._lock:
            started = {
                wid: entry.started_at for wid, entry in self._procs.items()
            }
        return sorted(
            worker_ids,
            key=lambda wid: started.get(wid, float("-inf")),
            reverse=True,
        )

    def pid_of(self, worker_id: int) -> Optional[int]:
        with self._lock:
            entry = self._procs.get(worker_id)
        if entry is None or entry.proc.poll() is not None:
            return None
        return entry.proc.pid

    def _watch(self):
        while not self._stop.is_set():
            events = []
            with self._lock:
                for wid, entry in self._procs.items():
                    if entry.reported:
                        continue
                    rc = entry.proc.poll()
                    if rc is None:
                        continue
                    entry.reported = True
                    if entry.deleted or rc < 0:
                        # explicit delete or killed by signal: the
                        # preemption shape — tasks must be recovered
                        phase = PodPhase.DELETED
                    elif rc == 0:
                        phase = PodPhase.SUCCEEDED
                    else:
                        phase = PodPhase.FAILED
                    events.append(PodEvent(wid, phase, exit_code=rc))
            for ev in events:
                logger.info(
                    "Worker %d exited: %s (rc=%s)",
                    ev.worker_id,
                    ev.phase,
                    ev.exit_code,
                )
                with self._lock:
                    cb = self._cb
                if cb:
                    try:
                        cb(ev)
                    except Exception:
                        logger.exception("pod event callback failed")
            time.sleep(self._poll)

    def stop(self):
        self._stop.set()
        with self._lock:
            entries = list(self._procs.values())
        for entry in entries:
            if entry.proc.poll() is None:
                entry.deleted = True
                entry.proc.terminate()
        for entry in entries:
            try:
                entry.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                entry.proc.kill()
        self._monitor.join(timeout=5)
