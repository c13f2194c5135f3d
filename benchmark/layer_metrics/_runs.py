"""Shared by the whole-window readers: the device's busy intervals as
the program itself records them, read over the whole timed window and
not the probe's 4 s slice.

Since PR 54 every call of a training program (`jit_window`, `jit_step`)
leaves one `worker.device_run` span in the worker's span file: `ts` is
the later of the moment the call was asked for and the end of the run
before it, `ts + dur` the moment the host saw the result ready, and its
arguments say `program`, `steps`, `seq`, `asked`, `queued_ms` and, on a
backend that reports them, `bytes_in_use` and `bytes_reserved` of one
`memory_stats()` read right after the call. A `worker.window_sync` and
each of its parts carry the `seq` of the last run the sync covers, and
the step loop's wait for a window's end is `worker.window_wait`. Both
processes also say when they were held up themselves: `proc.stall`,
`proc.gc`, `rpc.server.slow`.

Five readers, one file each beside this one:
- `window_exposed_pct`: from the first run that starts in the window to
  the last that ends in it, the share of time with no run in flight;
- `exposed_sync_ms`: per update, the gap after the run that ends its
  period, as far as the step loop's innermost phase is one of
  `_timeline.SYNC`; its parts go to stderr;
- `window_device_ms`: median length of the runs that end a period;
- `update_period_max_over_median`: the longest gap between applied
  updates over the median one, with the long period taken apart on
  stderr when it is over `LONG_PERIOD`;
- `window_resident_gb`: the most any training run found on the device.

A program without these spans (a parent commit these files are laid
over) reads 0.0 in all five, and one `timeline:` line says so. The span
files are `_timeline.load`'s: one that cannot be parsed raises there.
"""

import glob
import json
import os
import statistics

from benchmark.harness import trace_reduce
from benchmark.layer_metrics import _timeline

RUN, WAIT = "worker.device_run", "worker.window_wait"
TRAINS = {True: "jit_window", False: "jit_step"}  # by `window_mode`
HELD_UP = ("proc.stall", "proc.gc", "rpc.server.slow")
STEP_LOOP = (
    _timeline.INPUT + _timeline.STAGE + _timeline.SYNC + _timeline.OTHER
)
LONG_PERIOD = 1.5  # max over median from which the period is taken apart
USUAL_PERIODS = 48  # at most so many, evenly spaced, say what is usual
# the parts of one update's exposed sync: each moment charged to the
# first of these that holds it
PARTS = ("d2h alone", "the master's apply", "the rest of the round trip",
         "none of these")
_cache = {}
say = _timeline.say
_args = _timeline._args


def _end(span):
    return span["ts"] + span["dur"]


def _clip(intervals, lo, hi):
    return trace_reduce.union(
        (max(a, lo), min(b, hi)) for a, b in intervals
    )


def _minus(intervals, holes):
    """`intervals` without `holes`; both merged and sorted."""
    out = []
    for a, b in intervals:
        for c, d in holes:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c))
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def _both(intervals, others):
    return _minus(intervals, _minus(intervals, others))


def _seconds(intervals):
    return sum(b - a for a, b in intervals)


def _spans(spans):
    return trace_reduce.union((s["ts"], _end(s)) for s in spans)


class Worker:
    """One worker process's runs and what the readers lay beside them."""

    def __init__(self, timeline, pid, spans):
        self.pid = pid
        self.runs = sorted(
            (s for s in spans if s["name"] == RUN),
            key=lambda s: _args(s).get("seq", 0),
        )
        self.busy = _spans(self.runs)  # a run in flight
        named = {}
        for s in spans:
            named.setdefault(s["name"], []).append(s)
        self.named = named
        # the step loop's phases and its wait for a window's end, on
        # the main thread: (start, end, name), what `_innermost` cuts by
        self.phases = [
            (s["ts"], _end(s), s["name"]) for s in spans
            if s["name"] in STEP_LOOP + (WAIT,)
            and _args(s).get("thread") == _timeline.MAIN_THREAD
        ]
        window = _timeline.window_mode(timeline.run)
        self.program = TRAINS[window]
        inside = [r for r in self.runs if timeline.inside_window(r)]
        if window:
            synced = {
                _args(s).get("seq") for s in named.get(_timeline.WINDOW_SYNC, ())
            }
            self.enders = [r for r in inside if _args(r).get("seq") in synced]
        else:
            self.enders = inside


class Runs:
    def __init__(self, timeline):
        self.timeline = timeline
        by_pid = {}
        for s in timeline.worker_spans():
            by_pid.setdefault(s.get("pid"), []).append(s)
        self.workers = [
            Worker(timeline, pid, spans) for pid, spans in sorted(by_pid.items())
            if any(s["name"] == RUN for s in spans)
        ]
        self.handler = _spans(
            s for s in timeline.master if s["name"] in _timeline.HANDLER
        )
        self.exposed = None  # `exposed_syncs`' answer, computed once


def load(run, reader_file):
    """The run's `Runs`, or None where the program wrote no
    `worker.device_run` (said once)."""
    key = run["window"]["wall0"]
    if key not in _cache:
        _cache.clear()
        timeline = _timeline.load(run, reader_file)
        runs = Runs(timeline) if timeline.has_timeline else None
        if runs is None or not runs.workers:
            say(f"no {RUN} span: the program under test records no device "
                "runs; window_exposed_pct, exposed_sync_ms, window_device_ms, "
                "update_period_max_over_median and window_resident_gb read 0.0")
            runs = None
        else:
            log_runs(runs)
            log_trace_join(runs)
        _cache[key] = runs
    return _cache[key]


# ---------------------------------------------------------------- the runs


def window_extent(timeline, worker):
    """(lo, hi): the first run that starts in the window to the last
    that ends in it, or None."""
    starts = [r["ts"] for r in worker.runs if r["ts"] >= timeline.wall0]
    ends = [_end(r) for r in worker.runs if _end(r) <= timeline.wall1]
    if not starts or not ends or max(ends) <= min(starts):
        return None
    return min(starts), max(ends)


def window_exposed_pct(runs):
    shares = []
    for w in runs.workers:
        extent = window_extent(runs.timeline, w)
        if extent is None:
            continue
        busy = _seconds(_clip(w.busy, *extent))
        shares.append(100.0 * (1.0 - busy / (extent[1] - extent[0])))
    return statistics.mean(shares) if shares else 0.0


def window_device_ms(runs):
    return _timeline.median_ms(
        [r["dur"] for w in runs.workers for r in w.enders]
    )


def window_resident_gb(runs):
    held = [
        _args(r)["bytes_in_use"] + _args(r).get("bytes_reserved", 0)
        for w in runs.workers for r in w.runs
        if runs.timeline.inside_window(r)
        and _args(r).get("program") == w.program
        and "bytes_in_use" in _args(r)
    ]
    return max(held) / 1e9 if held else 0.0


def log_runs(runs):
    for w in runs.workers:
        inside = [r for r in w.runs if runs.timeline.inside_window(r)]
        queued = [_args(r).get("queued_ms", 0.0) for r in inside]
        by_program = {}
        for r in inside:
            by_program.setdefault(_args(r).get("program"), []).append(r["dur"])
        say(f"pid {w.pid}: {len(inside)} {RUN} in the window ("
            + ", ".join(
                f"{p} n {len(d)} median {statistics.median(d) * 1e3:.2f}ms "
                f"max {max(d) * 1e3:.2f}ms" for p, d in sorted(by_program.items())
            )
            + f"), {len(w.enders)} end an update period; queued_ms median "
            f"{statistics.median(queued) if queued else 0.0:.2f}, above 0 in "
            f"{sum(1 for q in queued if q > 0)}")
        carried = [
            _args(r) for r in inside if "bytes_in_use" in _args(r)
            and _args(r).get("program") == w.program
        ]
        if carried:
            top = max(carried, key=lambda a: a["bytes_in_use"] + a["bytes_reserved"])
            say(f"pid {w.pid}: of {len(carried)} {w.program} runs the fullest "
                f"(seq {top['seq']}) found bytes_in_use {top['bytes_in_use']} + "
                f"bytes_reserved {top['bytes_reserved']} of one moment; in use "
                f"alone ranged {min(a['bytes_in_use'] for a in carried)} to "
                f"{max(a['bytes_in_use'] for a in carried)}")


def log_trace_join(runs):
    """Lay the runs beside the probe's device trace, where there is
    one: every `jit_window` / `jit_step` event of the slice against the
    `worker.device_run` that shares most of its time. The trace cuts
    the program it begins in and the one it ends in to its own edges,
    so the first and the last event of a device's line are left out."""
    timeline = runs.timeline
    if not timeline.run.get("trace"):
        return
    probe_dir = os.path.join(timeline.run_dir, "probe")
    for path in sorted(glob.glob(os.path.join(probe_dir, "*.json"))):
        with open(path) as f:
            record = json.load(f)
        info = record.get("trace") or {}
        worker = next(
            (w for w in runs.workers if w.pid == record.get("pid")), None
        )
        if info.get("state") != "written" or worker is None:
            continue
        try:
            planes = trace_reduce.load(trace_reduce.find_xplane(info["dir"]))
            (lo, hi), origin = _timeline._slice_and_origin(planes, info)
        except trace_reduce.TraceError as e:
            say(f"device runs against the trace, pid {worker.pid}: {e}")
            continue
        events = [
            (origin + s / 1e9, origin + e / 1e9)
            for plane, lines in planes if plane.startswith("/device:")
            for line, evs in lines if line == trace_reduce.MODULES_LINE
            for name, s, e in sorted(evs, key=lambda ev: ev[1])[1:-1]
            if name.startswith(tuple(TRAINS.values())) and lo <= s and e <= hi
        ]
        if not events:
            continue
        starts, ends, lost = [], [], 0
        for begin, end in events:
            near = max(
                worker.runs,
                key=lambda r: min(_end(r), end) - max(r["ts"], begin),
            )
            if min(_end(near), end) <= max(near["ts"], begin):
                lost += 1
                continue
            starts.append((begin - near["ts"]) * 1e3)
            ends.append((_end(near) - end) * 1e3)
        if not ends:
            say(f"device runs against the trace, pid {worker.pid}: none of "
                f"{len(events)} program events shares a moment with a {RUN}")
            continue
        say(f"device runs against the trace, pid {worker.pid}: "
            f"{len(ends)} of {len(events)} {'/'.join(sorted(TRAINS.values()))} "
            f"events whole inside the slice have their {RUN} ({lost} have "
            f"none); the host saw the end {statistics.median(ends):.2f}ms "
            f"after the device (median; {min(ends):.2f} to {max(ends):.2f}), "
            f"the device began {statistics.median(starts):.2f}ms after the "
            f"span's ts (median; {min(starts):.2f} to {max(starts):.2f})")


# ---------------------------------------------------------- the exposed sync


def _exposed_sync(timeline, worker, run, following):
    """-> {part: seconds} of the gap between `run`'s end and the start
    of the run after it, as far as the step loop's innermost phase
    charges it to the sync; the parts sum to the whole."""
    lo, hi = _end(run), following["ts"]
    parts = dict.fromkeys(PARTS, 0.0)
    if hi <= lo:
        return parts
    sync = trace_reduce.union(
        (a, b) for a, b, name in _timeline._innermost(worker.phases, lo, hi)
        if name in _timeline.SYNC
    )
    rpcs = [
        s for name in _timeline.UPDATE_RPCS
        for s in worker.named.get(name, ()) if _end(s) > lo and s["ts"] < hi
    ]
    versions = {_args(s).get("version") for s in rpcs} - {None}
    trip = _spans(rpcs)
    apply = _both(trip, _spans(
        s for s in timeline.master if s["name"] == "apply"
        and _args(s).get("version") in versions
    ))
    d2h = _minus(_spans(
        s for s in worker.named.get("worker.d2h", ())
        if _end(s) > lo and s["ts"] < hi
    ), trip)
    parts[PARTS[0]] = _seconds(_both(sync, d2h))
    parts[PARTS[1]] = _seconds(_both(sync, apply))
    parts[PARTS[2]] = _seconds(_both(sync, _minus(trip, apply)))
    parts[PARTS[3]] = _seconds(sync) - sum(parts.values())
    return parts


def exposed_syncs(runs):
    """One {part: seconds} for each update of the window whose period's
    last run has a run after it."""
    if runs.exposed is None:
        runs.exposed = []
        for w in runs.workers:
            after = {
                _args(a).get("seq"): b for a, b in zip(w.runs, w.runs[1:])
            }
            for r in w.enders:
                following = after.get(_args(r).get("seq"))
                if following is not None:
                    runs.exposed.append(
                        _exposed_sync(runs.timeline, w, r, following)
                    )
    return runs.exposed


def exposed_sync_ms(runs):
    """The median update's exposed sync, and its parts to stderr: those
    of the middle update (the mean of the middle two), so that they sum
    to the number."""
    updates = sorted(exposed_syncs(runs), key=lambda p: sum(p.values()))
    if not updates:
        say("exposed sync: no update of the window has a run after it")
        return 0.0
    middle = updates[(len(updates) - 1) // 2:len(updates) // 2 + 1]
    parts = {
        name: statistics.mean(p[name] for p in middle) * 1e3 for name in PARTS
    }
    whole = sum(parts.values())
    say(f"exposed sync of the median update of {len(updates)}: "
        f"{whole:.2f}ms = "
        + " + ".join(f"{name} {parts[name]:.2f}" for name in PARTS)
        + f" (none of these {100 * parts[PARTS[3]] / whole if whole else 0:.1f}%"
        f" of it; the longest {sum(updates[-1].values()) * 1e3:.2f}ms)")
    return whole


# -------------------------------------------------------- the update periods


def applied(timeline):
    """When the master had applied each update of the window."""
    return sorted(
        _end(s) for s in timeline.master
        if s["name"] == "apply" and timeline.inside_window(s)
        and _args(s).get("kind") in _timeline.UPDATE_KINDS
    )


def _period(runs, lo, hi):
    """{component: seconds} of one period: `run` (a device run in
    flight), `exposed under <phase>` (none in flight, by the step
    loop's innermost phase) and `the master's handler`."""
    out = {}
    for w in runs.workers:
        busy = _clip(w.busy, lo, hi)
        out["run"] = out.get("run", 0.0) + _seconds(busy)
        idle = _minus([(lo, hi)], busy)
        for a, b, name in _timeline._innermost(w.phases, lo, hi):
            held = _seconds(_both(idle, [(a, b)]))
            if held > 0:
                key = f"exposed under {name or 'no phase'}"
                out[key] = out.get(key, 0.0) + held
    out["the master's handler"] = _seconds(_clip(runs.handler, lo, hi))
    return out


def update_period_max_over_median(runs):
    times = applied(runs.timeline)
    gaps = [b - a for a, b in zip(times, times[1:])]
    if len(gaps) < 3:
        say(f"update periods: {len(gaps)} between the window's applied "
            "updates, too few for a median")
        return 0.0
    median = statistics.median(gaps)
    longest = max(range(len(gaps)), key=gaps.__getitem__)
    ratio = gaps[longest] / median
    if ratio > LONG_PERIOD:
        _describe_period(runs, times, longest, median)
    return ratio


def _describe_period(runs, times, longest, median):
    timeline = runs.timeline
    lo, hi = times[longest], times[longest + 1]
    every = max(1, (len(times) - 1) // USUAL_PERIODS)
    periods = [
        _period(runs, a, b) for a, b in list(zip(times, times[1:]))[::every]
    ]
    long = _period(runs, lo, hi)
    excess = {
        key: long.get(key, 0.0)
        - statistics.median(p.get(key, 0.0) for p in periods)
        for key in {k for p in [long, *periods] for k in p}
    }
    say(f"update period {longest + 1} of {len(times) - 1}, "
        f"{lo - timeline.wall0:+.2f}s to {hi - timeline.wall0:+.2f}s from the "
        f"window's start, took {hi - lo:.3f}s where the median is "
        f"{median:.3f}s; of its excess {hi - lo - median:.3f}s over the "
        "median period's: " + ", ".join(
            f"{key} {value:+.3f}s" for key, value in
            sorted(excess.items(), key=lambda kv: -abs(kv[1]))
            if abs(value) >= 0.001
        ))
    held = [
        (label, s)
        for label, spans in [*timeline.workers.items(), ("master", timeline.master)]
        for s in spans
        if s["name"] in HELD_UP and _end(s) > lo and s["ts"] < hi
    ]
    for label, s in sorted(held, key=lambda item: item[1]["ts"]):
        args = {k: v for k, v in _args(s).items() if k != "thread"}
        say(f"  inside it, {label}: {s['name']} {s['ts'] - lo:+.3f}s from its "
            f"start for {s['dur']:.3f}s {args}")
    if not held:
        say(f"  inside it no {', '.join(HELD_UP)} of either process")
