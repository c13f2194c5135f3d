"""From a profiler trace (`.xplane.pb`) to device busy time.

Per chip: the union of the intervals in which a device operation ran,
clipped to the probe's slice (`find_slice`), so slice and operations
are on one clock; `window_s` is its length and `busy_s` the mean of the
unions over the chips, hence `0 < busy_s <= window_s` by construction.
A trace with no device plane, no operation line or no operation inside
the slice raises `TraceError`: nothing here prints a 0.

The same pass gives the breakdown: the operations that took most
device time under the names the trace has, and the longest idle gaps,
each labelled by the host span open on the worker's main thread at
the middle of the gap; where the trace has no host span, by the program
run it lies inside or follows; `unattributed` where it has neither.

What a device plane looks like was read from a real v5e trace (PERF.md
section 6): planes `/device:TPU:<n>` with the lines `XLA Modules` (one
event a program run, idle time inside it included), `XLA Ops` (the
TensorCore's operations, named by their whole HLO text), `Async XLA
Ops` (copies that overlap them) and `TC Overlay`; host threads are
lines of `/host:CPU` named after the thread (`python3`, `main/<tid>`),
on the same clock. Only `XLA Ops` counts as the device running.
"""

import glob
import os

from benchmark.harness.probe import SLICE_EVENT

# platform -> (plane name prefix, prefixes of the lines that hold ops).
# "cpu" is the sandbox rehearsal only: XLA:CPU runs its ops on host
# threads, and no number from it is ever reported as a device metric.
SELECTORS = {
    "tpu": ("/device:TPU:", ("XLA Ops",)),
    "cpu": ("/host:CPU", ("tf_XLAPjRtCpuClient", "tf_XLAEigen")),
}
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
MAIN_THREAD_PREFIX = "python"  # a line is named after its thread
TOP = 10


class TraceError(RuntimeError):
    pass


def find_xplane(trace_dir):
    files = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
    )
    if not files:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    """[(plane name, [(line name, [(event name, start_ns, end_ns)])])]."""
    from jax.profiler import ProfileData  # no backend is initialised

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events
            ]
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping ones."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def _clip(events, lo, hi):
    for name, start, end in events:
        start, end = max(start, lo), min(end, hi)
        if end > start:
            yield name, start, end


def find_slice(planes, hint=None):
    """(start_ns, end_ns) of the probe's slice on the trace's clock:
    its host annotation where the trace has host spans, else `hint`,
    the bounds the probe kept as seconds since it called `start_trace`
    (the trace's clock starts there: on the v5e a slice annotated
    0.04 s after the call begins at 44.8 ms — my chip run, PR 23)."""
    for plane, lines in planes:
        if not plane.startswith(HOST_PLANE):
            continue
        for _line, events in lines:
            for name, start, end in events:
                if name == SLICE_EVENT and end > start:
                    return start, end
    if hint is not None and hint[1] > hint[0] >= 0:
        return hint[0] * 1e9, hint[1] * 1e9
    raise TraceError(
        f"the trace holds no {SLICE_EVENT!r} annotation, and no bounds "
        "came with it"
    )


def _host_spans(planes):
    """Spans of the worker's main thread: the line with most events
    among those named like a Python thread, the probe's own excepted."""
    best = []
    for plane, lines in planes:
        if not plane.startswith(HOST_PLANE):
            continue
        for line, events in lines:
            if not line.startswith(MAIN_THREAD_PREFIX):
                continue
            if any(name == SLICE_EVENT for name, _s, _e in events):
                continue
            if len(events) > len(best):
                best = events
    return best


def _top(table):
    """The TOP rows of {name: seconds}, longest first."""
    return [
        [name, seconds]
        for name, seconds in sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
    ]


def _short(name):
    """`%fusion.1 = bf16[...] fusion(...)` -> `fusion.1`."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def _label(spans, modules, t):
    """What a gap around time t is charged to: the innermost (shortest)
    host span open then; where the trace has no host span, the program
    run the gap lies inside, or the one it follows (the host was
    between two dispatches); `unattributed` where it has neither."""
    open_ = [(e - s, name) for name, s, e in spans if s <= t < e]
    if open_:
        return min(open_)[1]
    before = [(s, e, name) for name, s, e in modules if s <= t]
    if not before:
        return "unattributed"
    _start, end, name = max(before)
    name = name.split("(", 1)[0]
    return f"inside {name}" if t < end else f"after {name}"


def reduce(planes, platform="tpu", hint=None):
    """-> {"window_s", "busy_s", "busy_s_by_chip", "device_ops",
    "idle_gaps"} from `load`'s planes; `hint` as `find_slice` takes it."""
    plane_prefix, line_prefixes = SELECTORS[platform]
    lo, hi = find_slice(planes, hint)
    spans = _host_spans(planes)
    busy_by_chip, op_seconds, gaps = {}, {}, []
    for plane, lines in planes:
        if not plane.startswith(plane_prefix):
            continue
        modules = [
            event for line, events in lines if line == MODULES_LINE
            for event in events
        ]
        ops = [
            event
            for line, events in lines
            if line.startswith(line_prefixes)
            for event in _clip(events, lo, hi)
        ]
        if not ops:
            continue
        merged = union((s, e) for _n, s, e in ops)
        busy_by_chip[plane] = sum(e - s for s, e in merged) / 1e9
        for name, s, e in ops:
            name = _short(name)
            op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / 1e9
        edges = [lo] + [t for pair in merged for t in pair] + [hi]
        for gap_start, gap_end in zip(edges[0::2], edges[1::2]):
            if gap_end > gap_start:
                gaps.append(
                    (gap_end - gap_start, (gap_start + gap_end) / 2, modules)
                )
    if not busy_by_chip:
        raise TraceError(
            f"no plane {plane_prefix}* with operations on a line "
            f"{line_prefixes} inside the slice; planes: "
            f"{[(p, [l for l, _ in ls]) for p, ls in planes]}"
        )
    chips = len(busy_by_chip)
    idle = {}
    for seconds, middle, modules in sorted(gaps, key=lambda g: -g[0])[:200]:
        label = _label(spans, modules, middle)
        idle[label] = idle.get(label, 0.0) + seconds / 1e9 / chips
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_by_chip.values()) / chips,
        "busy_s_by_chip": busy_by_chip,
        # nested ops (a while loop and its body) each count their own
        # time here; busy_s is the union and counts none twice
        "device_ops": _top({n: s / chips for n, s in op_seconds.items()}),
        "idle_gaps": _top(idle),
    }


def merge(reductions):
    """One cell's reduction from its workers' (one trace a process):
    chips side by side, `busy_s` their mean, `window_s` the (equal)
    slices' mean."""
    if not reductions:
        raise TraceError("no worker wrote a trace")
    chips = sum(len(r["busy_s_by_chip"]) for r in reductions)
    out = {
        "window_s": sum(r["window_s"] for r in reductions) / len(reductions),
        "busy_s": sum(
            sum(r["busy_s_by_chip"].values()) for r in reductions
        ) / chips,
        "chips_traced": chips,
    }
    for key in ("device_ops", "idle_gaps"):
        table = {}
        for r in reductions:
            share = len(r["busy_s_by_chip"]) / chips
            for name, seconds in r[key]:
                table[name] = table.get(name, 0.0) + seconds * share
        out[key] = _top(table)
    return out
