"""Per-phase step timing for the worker hot loop and the master's
update path.

The reference's only perf artifact is a manual timing table splitting
the training step into get_batch / input_fn / compute_loss / get_model /
report_gradient (elasticdl/doc/worker_optimization_design.md:33-60);
SURVEY §5.1 asks for this as a first-class subsystem since the
north-star metric is throughput retention. `PhaseTimers` is that
subsystem: near-zero-overhead cumulative wall-clock per phase,
snapshot-able by benches and loggable per task.

It also keeps times: closing a phase hands its owner's `sink` a span
(`name`, `time.time()` at entry, duration, the call site's `args`).
Worker and master pass `obs/trace.record_phase`, which records it into
the process's one `RECORDER`, always, so the phases of a process can be
laid beside a device trace on `time.time()`'s clock. A span is
inclusive; a reader charges a moment to the innermost span open then.

Thread-safe: the worker's chained sync threads log summaries (and may
time their own phases) while the main thread is inside `phase()` —
the totals are lock-guarded and the nesting stack is thread-local.

`DeviceRuns` lays the device's own busy intervals on the same clock:
one `worker.device_run` span a call of a training program, from the
moment the device could begin it to the moment the host saw its result.
"""

from __future__ import annotations

import os
import queue
import statistics
import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager
from typing import Callable, Dict, Optional

from elasticdl_tpu.common.log_util import get_logger

logger = get_logger(__name__)

# a phase instance this much over its own running median, and this
# long, logs one warning line (a 6 s stop then says where it was)
SLOW_FACTOR = 5.0
SLOW_SECS = 2.0
_RECENT = 33  # instances the running median is taken over
_MIN_RECENT = 5  # fewer say nothing about what is usual


def process_start_time() -> float:
    """`time.time()` at which this process started, from /proc (to
    1/100 s); now, where /proc does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(
                int(line.split()[1]) for line in f if line.startswith("btime")
            )
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


class PhaseTimers:
    """Phases may nest (e.g. `compute` wraps `get_model` and
    `report_gradient` in the sync hot loop); each phase is charged its
    *exclusive* time — child durations are subtracted from the parent —
    so the breakdown sums to real wall clock and percentages are
    honest. Nesting is tracked per thread.

    `phase()` keeps exclusive seconds and a span; `span()` only the
    span, for boundaries off the step loop (the sync thread's chain,
    set-up) whose seconds must not enter the shares the autoscaler
    reads; `record()` is `phase()` for an interval timed by hand.
    `sink(name, begin, dur, args, ctx)` gets every closed span (none:
    seconds and counts only); `ctx` is what `span()` was given, the
    sampled trace's context where one covers the interval."""

    def __init__(self, sink: Optional[Callable] = None):
        self._sink = sink
        self._seconds: Dict[str, float] = defaultdict(float)
        self._counts: Dict[str, int] = defaultdict(int)
        self._recent: Dict[str, deque] = {}
        self._local = threading.local()  # .stack: open phases, per thread
        self._lock = threading.Lock()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextmanager
    def phase(self, name: str, **args):
        """Yields `args`: the call site may add to it (bytes, steps)
        until the phase closes."""
        wall0 = time.time()
        t0 = time.perf_counter()
        stack = self._stack()
        stack.append([name, 0.0])
        try:
            yield args
        finally:
            elapsed = time.perf_counter() - t0
            _, child = stack.pop()
            with self._lock:
                self._seconds[name] += elapsed - child
                self._counts[name] += 1
            if stack:
                stack[-1][1] += elapsed
            self._close(name, wall0, elapsed, args)

    @contextmanager
    def span(self, name: str, ctx=None, **args):
        wall0 = time.time()
        t0 = time.perf_counter()
        try:
            yield args
        finally:
            self._close(name, wall0, time.perf_counter() - t0, args, ctx)

    def record(self, name: str, begin: float, end: float, **args):
        """A phase timed by hand: `begin`, `end` on `time.time()`."""
        elapsed = max(0.0, end - begin)
        with self._lock:
            self._seconds[name] += elapsed
            self._counts[name] += 1
        self._close(name, begin, elapsed, args)

    def record_span(
        self, name: str, begin: float, end: float, ctx=None, **args
    ):
        """`span()` for an interval timed by hand (one that starts on
        one thread and ends on another)."""
        self._close(name, begin, max(0.0, end - begin), args, ctx)

    def _close(self, name, wall0, elapsed, args, ctx=None):
        if self._sink is not None:
            self._sink(name, wall0, elapsed, args, ctx)
        # the instance joins its name's running median (under the lock:
        # the same name closes on several threads); one far over it says so
        usual = None
        with self._lock:
            recent = self._recent.get(name)
            if recent is None:
                recent = self._recent[name] = deque(maxlen=_RECENT)
            if elapsed > SLOW_SECS and len(recent) >= _MIN_RECENT:
                usual = statistics.median(recent)
            recent.append(elapsed)
        if usual is not None and elapsed > SLOW_FACTOR * usual:
            logger.warning(
                "slow phase: %s took %.2fs on %s, %.1f times its running "
                "median %.3fs",
                name, elapsed, threading.current_thread().name,
                elapsed / max(usual, 1e-9), usual,
            )

    def add(self, name: str, seconds: float):
        with self._lock:
            self._seconds[name] += seconds
            self._counts[name] += 1

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                k: {"seconds": self._seconds[k], "count": self._counts[k]}
                for k in self._seconds
            }

    def summary(self) -> str:
        with self._lock:
            items = sorted(self._seconds.items(), key=lambda kv: -kv[1])
            total = sum(self._seconds.values()) or 1.0
        return " ".join(
            f"{k}={v:.2f}s({100 * v / total:.0f}%)" for k, v in items
        )

    def reset(self):
        with self._lock:
            self._seconds.clear()
            self._counts.clear()


DEVICE_RUN = "worker.device_run"


class DeviceRuns:
    """One `worker.device_run` span a call of a training program
    (`jit_window`, `jit_step`), on `timers`' timeline: `ts` is the later
    of the moment the call was asked for and the end of the run before
    it (a device runs one program at a time, in the order asked), and
    `ts + dur` the moment the host saw the program's result ready. The
    call is asked for once it has RETURNED: its arguments staged and
    the program in the device's queue, which is the first moment the
    device could begin it (stamped before the call, a per-step batch's
    41 ms of staging counted as the device's: PERF.md, PR 54). The
    span says `program`, `steps`, `seq` (the call's number in this
    process, from 1), `asked` (that moment's `time.time()`),
    `queued_ms` (how long it stood behind the run before it: above 0
    the host was ahead and the device paces; 0 with a gap before `ts`,
    the device waited for the host) and, where the backend reports
    them, `bytes_in_use` and `bytes_reserved` of ONE `memory_stats()`
    read at that same moment: the device allocates a program's results
    when the program is asked for, so the two are of one moment inside
    the run's life.

    The call site says when the call has returned and where the result
    is seen:

        ... = program(*args)
        run = runs.asked("jit_window", steps)
        block_until_ready(result); runs.ready(run)  # a wait that stands there
        # or, where no wait of the caller's own is reached in time:
        runs.watch(run, loss)  # one daemon thread waits and stamps

    The watcher blocks on what it is handed and on nothing else: hand
    it a result no later program is given as a donation (the loss).
    It holds that one reference until the device is done with the run.
    Nothing here wraps the program or touches its arguments."""

    def __init__(self, timers, wait, memory_stats=None):
        self._timers = timers
        self._wait = wait  # blocks until the result it is given is ready
        self._memory_stats = memory_stats  # () -> dict | None
        self._lock = threading.Lock()  # the watcher and a caller's wait
        self._last_end = 0.0
        self._watched = None  # the watcher's queue, made with its thread
        self.seq = 0  # of the last call asked for

    def asked(self, program: str, steps: int) -> dict:
        self.seq += 1
        run = {
            "program": program, "steps": steps, "seq": self.seq,
            "asked": time.time(),
        }
        stats = self._memory_stats() if self._memory_stats else None
        if stats and "bytes_in_use" in stats:
            run["bytes_in_use"] = int(stats["bytes_in_use"])
            run["bytes_reserved"] = int(stats.get("bytes_reserved", 0))
        return run

    def ready(self, run: dict):
        with self._lock:  # the clock is read under it: ends never go back
            end = time.time()
            begin = min(max(run["asked"], self._last_end), end)
            self._last_end = end
        run["queued_ms"] = round((begin - run["asked"]) * 1e3, 3)
        self._timers.record_span(DEVICE_RUN, begin, end, **run)

    def watch(self, run: dict, result):
        if self._watched is None:
            self._watched = queue.SimpleQueue()
            threading.Thread(
                target=self._watch, args=(self._watched,), daemon=True,
                name="edl-device-runs",
            ).start()
        self._watched.put((run, result))

    def _watch(self, watched):
        while True:
            item = watched.get()
            if item is None:
                return
            run, result = item
            try:
                self._wait(result)
            except Exception:  # a reader's aid must not stop training
                logger.warning(
                    "no worker.device_run for %s %d: its result cannot be "
                    "waited for", run["program"], run["seq"], exc_info=True,
                )
                continue
            finally:
                del item, result
            self.ready(run)

    def close(self):
        """Let the watcher go once it has stamped what it holds."""
        if self._watched is not None:
            self._watched.put(None)
            self._watched = None
