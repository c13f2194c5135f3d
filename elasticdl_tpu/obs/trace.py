"""Cross-process sync tracing: Dapper-style context propagation.

Every RPC carries a compact ``{"t": trace_id, "s": span_id}`` envelope
under ``ENVELOPE_KEY`` inside the request dict — the wire codec ignores
unknown keys, so the envelope rides every transport tier
(grpc|uds|inproc) without schema changes. Each hop records a span
into a bounded lock-striped :class:`SpanRecorder` ring (the striping
mirrors rpc/policy.WireStats): worker sync chain, transport send/recv,
dispatcher admission-queue wait, CombineBuffer park+presum, shard-lock
apply, prepack encode.

Sampling is controlled by ``EDL_TRACE_SAMPLE`` (a probability in
[0, 1], default 0 = off). The off path is a single module-global float
compare — no allocation, no locking — so the sync hot loop pays nothing
when tracing is disabled. The sampling decision is made once per trace
at the root span; child spans inherit it by construction (a child only
exists when its parent context does).

Beside the sampled traces the recorder holds the always-on phase
timeline: :func:`record_phase` spans (``cat == "phase"``) that the
owners' `common/timing.PhaseTimers` write on closing a phase, whatever
``EDL_TRACE_SAMPLE`` says. Their ``ts`` is ``time.time()``, the clock a
device trace is laid on. An interval is recorded once: where a sampled
trace covers it (:func:`child_context`), the phase span carries that
trace's ids and serves both readers; otherwise it has no ``trace_id``.
A process that owns a log directory appends them to a JSON-lines file
as it goes (:class:`SpanFile`), so a SIGKILL loses at most one flush
period. The same process says on that timeline when it was itself
held up: ``proc.stall`` (the file's thread woke late from a wait that
timed out: the process was stopped, starved or held the GIL elsewhere)
and ``proc.gc`` (a collection that took long).

Export is Chrome trace-event JSON ("X" complete events, wall-clock
microsecond timestamps so spans from different processes align on one
Perfetto timeline) via :func:`dump_trace` / :func:`chrome_trace`, and
cross-process via the ``GetTrace`` RPC (master/shard servicers return
their process recorder's spans; merge with
:func:`chrome_trace_from_spans`).
"""

from __future__ import annotations

import atexit
import contextlib
import gc
import itertools
import json
import os
import random
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from elasticdl_tpu.common.constants import (
    ENV_SCHED_PHASE_SECS,
    ENV_TRACE_SAMPLE,
)

# Request-dict key carrying the trace envelope across process
# boundaries. Popped server-side (rpc/transport.ServerDispatcher)
# before the handler sees the request.
ENVELOPE_KEY = "__edl_trace__"

_STRIPES = 8
_DEFAULT_CAPACITY = 8192

_tls = threading.local()

# `cat` of the always-on phase timeline (record_phase)
PHASE_CAT = "phase"

# a wait of the span file's thread that timed out and came back this
# much late is a `proc.stall`; a collection this long a `proc.gc`
STALL_SECS = 0.25
GC_SECS = 0.05

# Resolved sampling probability; None = not yet read from the env.
# Kept module-global so the disabled fast path is one float compare.
_sample: Optional[float] = None


def _resolve_sample() -> float:
    global _sample
    raw = os.environ.get(ENV_TRACE_SAMPLE, "")
    try:
        val = min(1.0, max(0.0, float(raw))) if raw.strip() else 0.0
    except ValueError:
        val = 0.0
    _sample = val
    return val


def configure(sample: Optional[float]) -> None:
    """Pin the sampling probability (tests); None re-reads the env."""
    global _sample
    _sample = None if sample is None else min(1.0, max(0.0, float(sample)))


def refresh() -> None:
    """Drop the cached EDL_TRACE_SAMPLE (call after mutating the env)."""
    global _sample
    _sample = None


def enabled() -> bool:
    s = _sample
    if s is None:
        s = _resolve_sample()
    return s > 0.0


def _sampled() -> bool:
    s = _sample
    if s is None:
        s = _resolve_sample()
    return s > 0.0 and (s >= 1.0 or random.random() < s)


def _new_id() -> str:
    return os.urandom(8).hex()


@dataclass(frozen=True)
class TraceContext:
    """Identity of one span: which trace, which span, whose child."""

    trace_id: str
    span_id: str
    parent_id: Optional[str] = None

    def envelope(self) -> Dict[str, str]:
        return {"t": self.trace_id, "s": self.span_id}


class SpanRecorder:
    """Bounded lock-striped ring of finished spans.

    Recording threads hash onto one of ``stripes`` (lock, deque)
    pairs by thread id — the same contention-avoidance shape as
    rpc/policy.WireStats. Each deque is bounded; overflow evicts the
    oldest span on that stripe and bumps the dropped counter, so a
    long-running job keeps the most recent window of spans.
    ``drain`` hands out what was recorded since the last drain (each
    span once) and leaves the ring as it is for ``snapshot``;
    ``pressure`` is set when half a stripe waits to be drained, so a
    drainer that waits on it loses nothing to a burst. ``held`` takes
    a span from where no lock may be taken (a gc callback runs on
    whatever thread allocated, a stripe's lock perhaps in its hand);
    a reader moves them into the ring first.
    """

    def __init__(
        self, capacity: int = _DEFAULT_CAPACITY, stripes: int = _STRIPES
    ):
        per = max(1, capacity // max(1, stripes))
        self._half = max(1, per // 2)
        self.pressure = threading.Event()
        self.held: deque = deque(maxlen=64)
        # counts: [dropped, recorded, drained]
        self._stripes = [
            (threading.Lock(), deque(maxlen=per), [0, 0, 0])
            for _ in range(max(1, stripes))
        ]

    def _stripe(self):
        return self._stripes[threading.get_ident() % len(self._stripes)]

    def record(self, span: Dict[str, Any]) -> None:
        lock, ring, counts = self._stripe()
        with lock:
            if len(ring) == ring.maxlen:
                counts[0] += 1
            ring.append(span)
            counts[1] += 1
            if counts[1] - counts[2] >= self._half:
                self.pressure.set()

    def _take_held(self) -> None:
        while self.held:
            try:
                self.record(self.held.popleft())
            except IndexError:  # another reader took it
                return

    def snapshot(self) -> List[Dict[str, Any]]:
        self._take_held()
        out: List[Dict[str, Any]] = []
        for lock, ring, _counts in self._stripes:
            with lock:
                out.extend(ring)
        out.sort(key=lambda s: s["ts"])
        return out

    def drain(self) -> List[Dict[str, Any]]:
        """The spans recorded since the last drain, oldest first. One
        that the ring evicted before it was drained is lost (and
        counted in ``dropped``)."""
        self._take_held()
        out: List[Dict[str, Any]] = []
        for lock, ring, counts in self._stripes:
            with lock:
                fresh = min(counts[1] - counts[2], len(ring))
                counts[2] = counts[1]
                if fresh:
                    out.extend(itertools.islice(ring, len(ring) - fresh, None))
        out.sort(key=lambda s: s["ts"])
        return out

    def clear(self) -> None:
        self.held.clear()
        for lock, ring, counts in self._stripes:
            with lock:
                ring.clear()
                counts[:] = [0, 0, 0]

    @property
    def dropped(self) -> int:
        total = 0
        for lock, _ring, counts in self._stripes:
            with lock:
                total += counts[0]
        return total

    def __len__(self) -> int:
        return sum(len(ring) for _l, ring, _c in self._stripes)


# Process-wide recorder: every instrumented hop in this process records
# here; GetTrace / dump_trace read it.
RECORDER = SpanRecorder()


def current() -> Optional[TraceContext]:
    return getattr(_tls, "ctx", None)


def bind(ctx: Optional[TraceContext]) -> Optional[TraceContext]:
    """Set the thread's current context; returns the previous one."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    return prev


class Span:
    """A live span; ``end()`` records it. Not thread-safe (one owner)."""

    __slots__ = ("name", "cat", "ctx", "args", "_t0", "_recorder", "_done")

    def __init__(self, name, cat, ctx, args, recorder):
        self.name = name
        self.cat = cat
        self.ctx = ctx
        self.args = args
        self._t0 = time.time()
        self._recorder = recorder
        self._done = False

    def envelope(self) -> Dict[str, str]:
        return self.ctx.envelope()

    def end(self, **extra: Any) -> None:
        if self._done:
            return
        self._done = True
        now = time.time()
        args = dict(self.args or {})
        args.update(extra)
        self._recorder.record(
            {
                "name": self.name,
                "cat": self.cat,
                "ts": self._t0,
                "dur": max(0.0, now - self._t0),
                "trace_id": self.ctx.trace_id,
                "span_id": self.ctx.span_id,
                "parent_id": self.ctx.parent_id,
                "pid": os.getpid(),
                "tid": threading.get_ident(),
                "args": args,
            }
        )


def start_span(
    name: str,
    cat: str = "edl",
    parent: Optional[TraceContext] = None,
    args: Optional[Dict[str, Any]] = None,
    root: bool = False,
    recorder: Optional[SpanRecorder] = None,
) -> Optional[Span]:
    """Open a span; returns None when tracing is off or unsampled.

    With no explicit ``parent`` the thread's current context is used;
    when there is no context at all, a new trace starts only if
    ``root=True`` and the sampling coin lands — otherwise the call is
    a no-op. Callers must ``end()`` the returned span.
    """
    ctx = child_context(parent, root)
    if ctx is None:
        return None
    return Span(name, cat, ctx, args, recorder or RECORDER)


def child_context(
    parent: Optional[TraceContext] = None, root: bool = False
) -> Optional[TraceContext]:
    """The context a span opened here would get, or None when tracing
    is off or unsampled (``start_span``'s rules). For an interval the
    phase timeline records anyway: ``record_phase(..., ctx=...)`` then
    writes the ONE span both the timeline and the trace read."""
    s = _sample
    if s is None:
        s = _resolve_sample()
    if s <= 0.0:
        return None
    if parent is None:
        parent = current()
    if parent is None:
        if not root or not _sampled():
            return None
        return TraceContext(_new_id(), _new_id(), None)
    return TraceContext(parent.trace_id, _new_id(), parent.span_id)


@contextlib.contextmanager
def span(
    name: str,
    cat: str = "edl",
    parent: Optional[TraceContext] = None,
    args: Optional[Dict[str, Any]] = None,
    root: bool = False,
):
    """Context manager: open a span and bind it as the thread's current
    context so nested instrumented calls chain automatically. Records
    on exit, including the error path."""
    sp = start_span(name, cat=cat, parent=parent, args=args, root=root)
    if sp is None:
        yield None
        return
    prev = bind(sp.ctx)
    try:
        yield sp
    except BaseException as e:
        sp.end(error=type(e).__name__)
        raise
    finally:
        bind(prev)
        sp.end()


def record_event(
    name: str,
    begin: float,
    end: float,
    cat: str = "edl",
    parent: Optional[TraceContext] = None,
    args: Optional[Dict[str, Any]] = None,
    recorder: Optional[SpanRecorder] = None,
) -> None:
    """Retro-record a span from explicit wall-clock bounds — used for
    intervals measured before the context existed (admission-queue
    wait: the enqueue timestamp is taken before the envelope is even
    parsed)."""
    if parent is None:
        parent = current()
    if parent is None or not enabled():
        return
    ctx = TraceContext(parent.trace_id, _new_id(), parent.span_id)
    (recorder or RECORDER).record(
        {
            "name": name,
            "cat": cat,
            "ts": begin,
            "dur": max(0.0, end - begin),
            "trace_id": ctx.trace_id,
            "span_id": ctx.span_id,
            "parent_id": ctx.parent_id,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": dict(args or {}),
        }
    )


def record_phase(
    name: str,
    begin: float,
    dur: float,
    args: Optional[Dict[str, Any]] = None,
    ctx: Optional[TraceContext] = None,
    recorder: Optional[SpanRecorder] = None,
) -> None:
    """One span of the always-on phase timeline: recorded whatever
    ``EDL_TRACE_SAMPLE`` says. ``begin`` is ``time.time()`` at entry.
    With ``ctx`` (a sampled trace covers the interval) the span carries
    the trace's ids; without, it has none."""
    span = _phase_span(name, begin, dur, args)
    if ctx is not None:
        span["trace_id"] = ctx.trace_id
        span["span_id"] = ctx.span_id
        span["parent_id"] = ctx.parent_id
    (RECORDER if recorder is None else recorder).record(span)


def _phase_span(name, begin, dur, args=None) -> Dict[str, Any]:
    thread = threading.current_thread()
    full = {"thread": thread.name}
    if args:
        full.update(args)
    return {
        "name": name,
        "cat": PHASE_CAT,
        "ts": begin,
        "dur": max(0.0, dur),
        "pid": os.getpid(),
        "tid": thread.ident,
        "args": full,
    }


_gc_began = [0.0]  # collections do not nest


def _on_gc(phase, info):
    """``proc.gc`` for a collection over ``GC_SECS``, on the thread it
    ran on: into ``RECORDER.held``, no lock taken."""
    if phase == "start":
        _gc_began[0] = time.time()
        return
    took = time.time() - _gc_began[0]
    if took > GC_SECS:
        RECORDER.held.append(_phase_span(
            "proc.gc", _gc_began[0], took,
            {"generation": info.get("generation"),
             "collected": info.get("collected")},
        ))


def watch_gc() -> None:
    """Have this process record its long collections (once)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def extract(req: Any) -> Optional[TraceContext]:
    """Pop the envelope from an unpacked request dict (server side).

    Always pops — a disabled server must not leak the envelope key into
    handlers — but only materializes a context when tracing is on."""
    if not isinstance(req, dict):
        return None
    env = req.pop(ENVELOPE_KEY, None)
    if not env or not enabled():
        return None
    try:
        return TraceContext(str(env["t"]), str(env["s"]), None)
    except (KeyError, TypeError):
        return None


def chrome_trace_from_spans(spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Chrome trace-event JSON from recorder-shaped span dicts.

    Timestamps are wall-clock microseconds, so spans gathered from
    several processes (GetTrace fan-out, the processes' span files, a
    device trace laid on the same clock) align on one timeline. A span
    that names its thread (``args.thread``) or its process
    (``process``) names that row."""
    events = []
    named = {}
    for s in spans:
        args = dict(s.get("args") or {})
        for key in ("trace_id", "span_id", "parent_id"):
            if s.get(key) is not None:
                args[key] = s[key]
        pid, tid = s.get("pid", 0), s.get("tid", 0)
        events.append(
            {
                "name": s["name"],
                "cat": s.get("cat", "edl"),
                "ph": "X",
                "ts": s["ts"] * 1e6,
                "dur": s["dur"] * 1e6,
                "pid": pid,
                "tid": tid,
                "args": args,
            }
        )
        if s.get("process"):
            named[(pid, None)] = s["process"]
        if args.get("thread"):
            named[(pid, tid)] = args["thread"]
    for (pid, tid), name in named.items():
        meta = {"ph": "M", "pid": pid, "args": {"name": name}}
        if tid is None:
            meta["name"] = "process_name"
        else:
            meta.update(name="thread_name", tid=tid)
        events.append(meta)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def spans_from_device_trace(
    xplane_path: str, asked: float, min_dur: float = 0.0
) -> List[Dict[str, Any]]:
    """The device planes of a ``jax.profiler`` trace as span dicts on
    ``time.time()``'s clock: the trace's clock starts where
    ``start_trace`` was called, at ``asked``. One row per line of each
    ``/device:`` plane (``XLA Modules``: a program run; ``XLA Ops``:
    its operations, which carry the ``jax.named_scope`` they ran
    under)."""
    from jax.profiler import ProfileData  # initialises no backend

    spans = []
    planes = ProfileData.from_file(xplane_path).planes
    for pi, plane in enumerate(p for p in planes if "/device:" in p.name):
        for li, line in enumerate(plane.lines):
            for e in line.events:
                dur = e.duration_ns / 1e9
                if dur < min_dur:
                    continue
                name = e.name.split(" = ", 1)[0].lstrip("%")[:120]
                spans.append(
                    {
                        "name": name,
                        "cat": "device",
                        "ts": asked + e.start_ns / 1e9,
                        "dur": dur,
                        "pid": 1_000_000_000 + pi,
                        "tid": li,
                        "process": plane.name,
                        "args": {"thread": line.name},
                    }
                )
    return spans


def chrome_trace(recorder: Optional[SpanRecorder] = None) -> Dict[str, Any]:
    return chrome_trace_from_spans((recorder or RECORDER).snapshot())


def dump_trace(
    path: str, recorder: Optional[SpanRecorder] = None
) -> str:
    """Write the recorder's spans as Perfetto-loadable JSON; returns
    the path."""
    doc = chrome_trace(recorder)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


class SpanFile:
    """The way out of a process that may be SIGKILLed: one daemon
    thread drains the recorder every ``period_secs`` and appends each
    span as a JSON line to ``path`` (line-buffered, so a line is whole
    or absent). A relaunched process appends to the same file under
    its new ``pid``. The thread is also the process's stall detector:
    a wait that timed out and returned more than ``STALL_SECS`` late
    (by ``clock``, which runs on while the process is stopped) is a
    ``proc.stall`` span with ``late_ms`` and the process's ``role``."""

    def __init__(
        self,
        path: str,
        period_secs: float = 2.0,
        recorder: Optional[SpanRecorder] = None,
        role: str = "",
        clock=time.monotonic,
    ):
        self.path = path
        self._period = float(period_secs)
        # not `or`: a recorder with nothing in it yet is falsy (`__len__`)
        self._recorder = RECORDER if recorder is None else recorder
        self._role = role
        self._clock = clock
        self._file = open(path, "a", buffering=1)
        self._lock = threading.Lock()  # flush() from the thread and stop()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="edl-span-file"
        )

    def start(self) -> "SpanFile":
        self._thread.start()
        atexit.register(self.stop)
        return self

    def _loop(self):
        pressure = self._recorder.pressure
        while not self._stop.is_set():
            due = self._clock() + self._period
            # a burst does not wait a period
            if not pressure.wait(self._period):
                late = self._clock() - due
                if late > STALL_SECS:
                    record_phase(
                        "proc.stall", time.time() - late, late,
                        {"late_ms": round(late * 1e3, 1), "role": self._role},
                        recorder=self._recorder,
                    )
            pressure.clear()
            self.flush()

    def flush(self) -> int:
        with self._lock:
            if self._file.closed:
                return 0
            spans = self._recorder.drain()
            if spans:  # one write a drain: a kill cuts between drains
                lines = [json.dumps(s, default=str) + "\n" for s in spans]
                self._file.write("".join(lines))  # edl-lint: disable=lock-discipline -- str.join, no thread is waited for
            return len(spans)

    def stop(self):
        self._stop.set()
        self._recorder.pressure.set()
        self.flush()
        with self._lock:
            self._file.close()


def start_span_file(directory: str, basename: str) -> Optional[SpanFile]:
    """``<directory>/<basename>.spans.jsonl``, flushed every
    ``EDL_SCHED_PHASE_SECS`` (2 s by default, and where that knob turns
    the phase stats off); ``basename`` is the role its ``proc.stall``
    spans name, and the process's long collections are recorded from
    here on (``proc.gc``). With no directory nothing is written and
    the ring is all there is."""
    if not directory:
        return None
    watch_gc()
    try:
        period = float(os.environ.get(ENV_SCHED_PHASE_SECS, "") or 2.0)
    except ValueError:
        period = 2.0
    if period <= 0:
        period = 2.0
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"{basename}.spans.jsonl")
    return SpanFile(path, period, role=basename).start()


def load_span_file(path: str) -> List[Dict[str, Any]]:
    """The spans of one ``.spans.jsonl`` file; a last line cut short by
    a kill is skipped."""
    spans = []
    with open(path) as f:
        for line in f:
            try:
                spans.append(json.loads(line))
            except ValueError:
                continue
    return spans
