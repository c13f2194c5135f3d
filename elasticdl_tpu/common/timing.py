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
"""

from __future__ import annotations

import os
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
