"""Shared by the timeline readers: the phase timeline the program
writes from inside worker and master, read after the job is gone.

Each process appends its spans (`name`, `ts` on `time.time()`, `dur`,
`pid`, `tid`, `args.thread`, ...) to a JSON-lines file in a directory
the benchmark already points into its run directory:
`<run>/logs/worker-<id>.spans.jsonl` and `<run>/tb/master.spans.jsonl`.
`run.py` hands a reader neither that directory nor the sink's events,
so the run is found from the reader's own location:
`<root>/.bench_runs/*/probe/trace.latch` holds the `time.time()` at
which the latch was dropped, within a poll of `run["window"]["wall0"]`;
the one within a second is this run's (none, or two, raises).

The idle metrics lay the worker's main-thread phases over the probe's
device trace. A device-only trace's clock starts at the probe's `asked`
(the harness maps its slice the same way, `run.py:device_block`); a
trace with host spans (the CPU rehearsal) carries the slice's own
annotation, which began at the probe's `t0`. An idle moment of the
device is charged to the innermost of the worker's eleven step-loop
phases open on its main thread then.

A run directory with no span file at all is a program without a
timeline (a parent commit these files are laid over): every metric
reads 0.0, all idle time is `idle_other_pct`, and one line says so. A
span file that is there but cannot be parsed, or one process's file
without the other's, raises: a broken run must not print 0. A name
absent from a file that is there reads 0.0.

Every traced run logs to stderr, once, what the numbers come from
(`timeline:` lines): per process and span name the count, total,
median and max inside the window; the idle seconds of the slice under
each main-thread phase; the parts of one sync beside the whole; the
clock join.
"""

import bisect
import glob
import json
import os
import statistics
import sys

from benchmark.harness import trace_reduce

RUNS = ".bench_runs"
LATCH_SLACK_SECS = 1.0
# the worker's step-loop phases (`PhaseTimers.phase`): they nest
# properly on the main thread and partition its time
INPUT = ("get_batch", "read_records")
STAGE = ("compute",)
SYNC = ("sync_wait", "report_gradient", "get_model", "rebase")
OTHER = ("get_task", "task_other", "wait_poll", "device_wait")
IDLE_KEYS = {"input": INPUT, "stage": STAGE, "sync": SYNC}
MAIN_THREAD = "MainThread"
# one sync: the whole, what it queued behind, and its parts (none
# inside another); the worker's host work among the parts
WINDOW_SYNC, CHAIN_WAIT = "worker.window_sync", "worker.chain_wait"
STEP_SYNC = ("report_gradient", "get_model")
UPDATE_RPCS = ("rpc.client.ReportLocalUpdate", "rpc.client.ReportGradient")
CLIENT_HOST = ("worker.d2h", "worker.quantize", "worker.encode",
               "rpc.client.encode", "rpc.client.decode")
SPAWN = "worker.sync_spawn"  # on the step loop, from the sync's own start
PARTS = ("worker.delta_wait", "worker.flush_reports", "rpc.client.GetModel",
         "worker.absorb") + CLIENT_HOST + UPDATE_RPCS
# the master's handler thread, one update: in this order
HANDLER = ("rpc.decode", "apply_wait", "grad_decode", "apply",
           "model_encode", "rpc.encode")
CODEC = ("rpc.decode", "grad_decode", "model_encode", "rpc.encode")
UPDATE_METHODS = ("ReportLocalUpdate", "ReportGradient")
# `args.kind` of an update the master applied; a gradient that only
# joined the sum is `accumulate`, a pull `get_model`
UPDATE_KINDS = ("local_update", "gradient")
JOIN_SLACK_SECS = 0.005  # two processes of one host read one clock
_cache = {}


class TimelineError(RuntimeError):
    pass


def say(msg):
    print(f"timeline: {msg}", file=sys.stderr, flush=True)


def find_run_dir(run, reader_file):
    """The directory of the run `run` describes, by its latch."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file)
    )))
    wall0 = run["window"]["wall0"]
    near = []
    for latch in glob.glob(
        os.path.join(root, RUNS, "*", "probe", "trace.latch")
    ):
        try:
            with open(latch) as f:
                dropped = float(f.read().strip())
        except (OSError, ValueError):
            continue
        if abs(dropped - wall0) <= LATCH_SLACK_SECS:
            near.append(os.path.dirname(os.path.dirname(latch)))
    if len(near) != 1:
        raise TimelineError(
            f"{len(near)} run directories under {os.path.join(root, RUNS)} "
            f"dropped their latch within {LATCH_SLACK_SECS}s of the "
            f"window's start {wall0}: {near}"
        )
    return near[0]


def load_spans(path):
    """The spans of one file. The job ends by SIGKILL, so a last line
    without its newline is one the kill cut short and is left out; any
    other line that is no span raises."""
    with open(path) as f:
        text = f.read()
    lines = text.split("\n")
    cut_short = lines.pop()  # "" when the file ends on a whole line
    spans = []
    for number, line in enumerate(lines, 1):
        try:
            span = json.loads(line)
            span["ts"], span["dur"], span["name"]
        except (ValueError, KeyError, TypeError) as e:
            raise TimelineError(f"{path}:{number} is no span: {e!r}") from e
        spans.append(span)
    if cut_short:
        say(f"{path}: last line cut short by the kill, left out")
    return spans


class Timeline:
    """One run's spans: `workers` {file label: [span]}, `master` [span],
    both sorted by `ts`; `threads` {(pid, tid): [span]} of the workers."""

    def __init__(self, run, run_dir):
        self.run = run
        self.run_dir = run_dir
        self.wall0 = run["window"]["wall0"]
        self.wall1 = run["window"]["wall1"]
        self.workers, self.master, self.threads = {}, [], {}
        self.all_workers = []
        # idle_split's, worker_syncs' and master_updates' answers,
        # computed once
        self.idle = self.syncs = self.updates = None
        files = sorted(glob.glob(
            os.path.join(run_dir, "logs", "worker-*.spans.jsonl")
        ))
        master = os.path.join(run_dir, "tb", "master.spans.jsonl")
        self.has_timeline = bool(files) or os.path.isfile(master)
        if not self.has_timeline:
            say(f"no span file under {run_dir}: the program under test "
                "writes no phase timeline; every timeline metric reads 0.0 "
                "and all idle time is idle_other_pct")
            return
        if not files or not os.path.isfile(master):
            raise TimelineError(
                f"span files are missing under {run_dir}: workers {files}, "
                f"master's there: {os.path.isfile(master)}"
            )
        by_ts = lambda s: s["ts"]  # noqa: E731
        for path in files:
            label = os.path.basename(path).split(".spans.")[0]
            self.workers[label] = sorted(load_spans(path), key=by_ts)
        self.master = sorted(load_spans(master), key=by_ts)
        self.all_workers = [s for spans in self.workers.values() for s in spans]
        for s in self.all_workers:
            self.threads.setdefault((s.get("pid"), s.get("tid")), []).append(s)
        for spans in self.threads.values():
            spans.sort(key=by_ts)

    # -- selections ---------------------------------------------------------

    def worker_spans(self):
        return self.all_workers

    def inside_window(self, s):
        return self.wall0 <= s["ts"] <= self.wall1

    def in_window(self, spans, *names):
        """Spans of these names that started inside the window."""
        return [s for s in spans if s["name"] in names and self.inside_window(s)]

    def before_window(self, spans, *names):
        return [s for s in spans if s["name"] in names and s["ts"] < self.wall0]

    def on_thread(self, pid, tid, lo, hi):
        """Spans of one worker thread that started in [lo, hi]."""
        spans = self.threads.get((pid, tid), [])
        starts = [s["ts"] for s in spans]
        return spans[bisect.bisect_left(starts, lo):bisect.bisect_right(starts, hi)]

    def table(self):
        """Log, per process and span name, the count, total, median and
        max inside the window (set-up spans: whenever they were), and
        each process's spans a second."""
        groups = dict(self.workers, master=self.master)
        span_s = max(self.wall1 - self.wall0, 1e-9)
        for label, spans in sorted(groups.items()):
            by = {}
            for s in spans:
                if s["name"].startswith("setup.") or self.inside_window(s):
                    by.setdefault((s.get("pid"), s["name"]), []).append(s["dur"])
            for (pid, name), durs in sorted(by.items()):
                say(f"{label} pid {pid} {name}: n {len(durs)} "
                    f"total {sum(durs):.4f}s median "
                    f"{statistics.median(durs) * 1e3:.3f}ms "
                    f"max {max(durs) * 1e3:.3f}ms")
            n = sum(1 for s in spans if self.inside_window(s))
            say(f"{label}: {n / span_s:.1f} spans a second in the window")
        for s in self.worker_spans():
            if s["name"].startswith("setup."):
                args = {k: v for k, v in (s.get("args") or {}).items()
                        if k != "thread"}
                say(f"set-up, pid {s.get('pid')}: {s['name']} {args} "
                    f"{s['dur']:.3f}s, ending {s['ts'] + s['dur'] - self.wall0:+.1f}s "
                    "from the window's start")


def load(run, reader_file):
    """The run's `Timeline`, loaded once for all the readers."""
    key = run["window"]["wall0"]
    if key not in _cache:
        _cache.clear()
        timeline = _cache[key] = Timeline(run, find_run_dir(run, reader_file))
        if timeline.has_timeline:
            timeline.table()
            log_sync_parts(timeline)
    return _cache[key]


def median_ms(seconds):
    return statistics.median(seconds) * 1e3 if seconds else 0.0


def _args(span):
    return span.get("args") or {}


def _union_s(spans):
    """Seconds covered by `spans`, one inside another counted once."""
    return sum(
        e - s for s, e in trace_reduce.union(
            (s["ts"], s["ts"] + s["dur"]) for s in spans
        )
    )


# ------------------------------------------------------------------ the sync


def window_mode(run):
    return int(run["mix"]["master_flags"].get("local_updates", 0)) >= 1


def worker_syncs(timeline):
    """One dict for each sync the workers began inside the window:
    `own` seconds (the sync's own work: a `worker.window_sync` less the
    `worker.chain_wait` it spent queued behind the syncs before it; per
    step the `report_gradient` and `get_model` phases of one `compute`),
    `parts` (its spans, none being the whole or the queue) and `rpc`
    (the update's round trip as the client saw it, or None)."""
    if timeline.syncs is not None:
        return timeline.syncs
    syncs = timeline.syncs = []
    if window_mode(timeline.run):
        wholes = timeline.in_window(timeline.worker_spans(), WINDOW_SYNC)
        # the spawn (and the quantize inside it) runs on the step loop:
        # each belongs to the sync of its process that began last
        # before it (the spawn begins at the sync's own `ts`)
        starts = {}
        for w in wholes:
            starts.setdefault(w.get("pid"), []).append(w["ts"])
        spawned = {}
        for q in timeline.in_window(
            timeline.worker_spans(), SPAWN, "worker.quantize"
        ):
            at = bisect.bisect_right(starts.get(q.get("pid"), []), q["ts"])
            if at:
                key = (q.get("pid"), starts[q.get("pid")][at - 1])
                spawned.setdefault(key, []).append(q)
        for w in wholes:
            lo, hi = w["ts"], w["ts"] + w["dur"]
            inside = [
                s for s in timeline.on_thread(w.get("pid"), w.get("tid"), lo, hi)
                if s is not w and s["ts"] + s["dur"] <= hi + JOIN_SLACK_SECS
            ]
            queued = sum(s["dur"] for s in inside if s["name"] == CHAIN_WAIT)
            parts = [s for s in inside if s["name"] in PARTS]
            parts += spawned.get((w.get("pid"), w["ts"]), [])
            syncs.append({"own": w["dur"] - queued, "parts": parts, "whole": w})
    else:
        for step in timeline.in_window(timeline.worker_spans(), "compute"):
            if _args(step).get("thread") != MAIN_THREAD:
                continue
            inside = timeline.on_thread(
                step.get("pid"), step.get("tid"), step["ts"],
                step["ts"] + step["dur"],
            )
            phases = [s for s in inside if s["name"] in STEP_SYNC]
            if not phases:
                continue
            parts = [
                s for s in inside if s["name"] in PARTS and any(
                    p["ts"] <= s["ts"] <= p["ts"] + p["dur"] for p in phases
                )
            ]
            syncs.append({
                "own": sum(p["dur"] for p in phases), "parts": parts,
                "whole": step,
            })
    for sync in syncs:
        rpcs = [s for s in sync["parts"] if s["name"] in UPDATE_RPCS]
        sync["rpc"] = rpcs[-1] if rpcs else None
    return syncs


def master_updates(timeline):
    """One dict for each update request the master's dispatcher decoded
    inside the window and the servicer applied: `version`, `lo`..`hi`
    (the handler's spans' extent), `apply`, `codec` and `handler`
    (seconds: the apply alone, the four codec spans, all six). The
    handler's thread unpacks the request, waits for the lock, decodes
    the update's wire form, applies, ravels the model where one goes
    down and packs the response, in that order. A report that only
    joined the gradient sum (`kind: accumulate`) applied nothing and is
    no update."""
    if timeline.updates is not None:
        return timeline.updates
    by_thread = {}
    for s in timeline.master:
        if s["name"] in HANDLER:
            by_thread.setdefault((s.get("pid"), s.get("tid")), []).append(s)
    updates = []
    for spans in by_thread.values():
        group = None
        for s in spans:  # sorted by ts
            if s["name"] == "rpc.decode":
                group = None
                if _args(s).get("method") in UPDATE_METHODS:
                    group = {"spans": [s], "applied": False}
                    updates.append(group)
            elif group is not None:
                group["spans"].append(s)
                if s["name"] == "apply" and _args(s).get("kind") in UPDATE_KINDS:
                    group["applied"] = True
                    group["apply"] = s["dur"]
                    group["version"] = _args(s).get("version")
                if s["name"] == "rpc.encode":
                    group = None
    out = timeline.updates = []
    for u in updates:
        first = u["spans"][0]
        if not u["applied"] or not timeline.inside_window(first):
            continue
        out.append({
            "version": u["version"],
            "lo": first["ts"],
            "hi": max(s["ts"] + s["dur"] for s in u["spans"]),
            "apply": u["apply"],
            "codec": sum(s["dur"] for s in u["spans"] if s["name"] in CODEC),
            "handler": sum(s["dur"] for s in u["spans"]),
        })
    return out


def sync_own_ms(timeline):
    return median_ms([s["own"] for s in worker_syncs(timeline)])


def sync_client_ms(timeline):
    """Median per update of the worker's host work in a sync: the
    copy out, the pack and the unpack (a span inside another counted
    once)."""
    return median_ms([
        _union_s([p for p in s["parts"] if p["name"] in CLIENT_HOST])
        for s in worker_syncs(timeline) if s["rpc"] is not None
    ])


def wire_seconds(timeline):
    """Per update both sides saw: the client's round trip less the
    master's handler spans of the same update, joined on the version
    the response named (and, where two requests were answered with one
    version, on the handler lying inside the round trip)."""
    by_version = {}
    for u in master_updates(timeline):
        by_version.setdefault(u["version"], []).append(u)
    out = []
    for sync in worker_syncs(timeline):
        rpc = sync["rpc"]
        if rpc is None or _args(rpc).get("version") is None:
            continue
        lo, hi = rpc["ts"] - JOIN_SLACK_SECS, rpc["ts"] + rpc["dur"] + JOIN_SLACK_SECS
        inside = [
            u for u in by_version.get(_args(rpc)["version"], ())
            if lo <= u["lo"] and u["hi"] <= hi
        ]
        if len(inside) == 1:
            out.append(max(0.0, rpc["dur"] - inside[0]["handler"]))
    return out


def log_sync_parts(timeline):
    """Log the parts of one sync beside the whole: their medians, and
    over the syncs the share of its own work (`sync_own_ms`) that a
    sync's parts cover."""
    syncs = worker_syncs(timeline)
    if not syncs:
        say("no sync began inside the window")
        return
    names = sorted({p["name"] for s in syncs for p in s["parts"]})
    medians = {
        n: median_ms([
            sum(p["dur"] for p in s["parts"] if p["name"] == n) for s in syncs
        ]) for n in names
    }
    # a sync whose round trip the kill kept out of the file is left out
    cover = [
        _union_s(s["parts"]) / s["own"] for s in syncs
        if s["own"] > 0 and s["rpc"] is not None
    ]
    wholes = [s["whole"]["dur"] for s in syncs]
    if window_mode(timeline.run):
        queued = [w - s["own"] for w, s in zip(wholes, syncs)]
        beside = (f"queued behind earlier syncs {median_ms(queued):.2f}, "
                  f"spawn to settled {median_ms(wholes):.2f}")
    else:
        beside = f"the step's whole compute phase {median_ms(wholes):.2f}"
    wire = wire_seconds(timeline)
    master = master_updates(timeline)
    say(f"one sync of {len(syncs)}, medians in ms: "
        + ", ".join(f"{n} {v:.2f}" for n, v in medians.items())
        + f"; own work (sync_own_ms) {sync_own_ms(timeline):.2f}, {beside}")
    if cover:
        say(f"per sync the parts cover {statistics.median(cover):.4f} of "
            f"its own work (min {min(cover):.4f}, max {max(cover):.4f}, "
            f"{len(cover)} syncs)")
    say(f"the master's side of {len(master)} applied update(s), medians in "
        f"ms: handler {median_ms([u['handler'] for u in master]):.2f}, "
        f"apply {median_ms([u['apply'] for u in master]):.2f}, codec "
        f"{median_ms([u['codec'] for u in master]):.2f}; the wire "
        f"(round trip less handler, {len(wire)} joined on version) "
        f"{median_ms(wire):.2f}")


# ------------------------------------------------------------------ set-up


def setup_boot_s(timeline):
    """Master's process start to the end of the first worker's
    `setup.backend_init`."""
    starts = [s["ts"] for s in timeline.master if s["name"] == "setup.imports"]
    ends = [
        s["ts"] + s["dur"] for s in timeline.worker_spans()
        if s["name"] == "setup.backend_init"
    ]
    if not starts or not ends:
        return 0.0
    return max(0.0, min(ends) - min(starts))


def setup_sum_s(timeline, name):
    """Seconds in the workers' `name` spans that began before the
    window opened, summed over the workers."""
    spans = timeline.before_window(timeline.worker_spans(), name)
    return sum(s["dur"] for s in spans)


# -------------------------------------------------------------------- idle


def _innermost(spans, lo, hi):
    """[(start, end, name)]: [lo, hi) cut where a span of `spans`
    opens or closes, each piece named for the span opened last among
    those open in it (None where none is)."""
    spans = [s for s in spans if s[1] > lo and s[0] < hi]
    edges = sorted({lo, hi} | {
        t for s in spans for t in (s[0], s[1]) if lo < t < hi
    })
    pieces = []
    for a, b in zip(edges, edges[1:]):
        middle = (a + b) / 2
        open_ = [s for s in spans if s[0] <= middle < s[1]]
        name = max(open_, key=lambda s: (s[0], -s[1]))[2] if open_ else None
        pieces.append((a, b, name))
    return pieces


def _overlap(intervals, pieces, names):
    """Seconds of `intervals` that lie in pieces named in `names`."""
    total = 0.0
    for a, b in intervals:
        for pa, pb, name in pieces:
            if name in names:
                total += max(0.0, min(b, pb) - max(a, pa))
    return total


def _slice_and_origin(planes, info):
    """((lo, hi) of the probe's slice in the trace's ns, the
    `time.time()` of the trace's 0). Where the trace carries the
    slice's host annotation, that began at the probe's `t0`; a
    device-only trace starts its clock at `asked` and is cut at the
    bounds the probe kept."""
    try:
        lo, hi = trace_reduce.find_slice(planes)
        return (lo, hi), info["t0"] - lo / 1e9
    except trace_reduce.TraceError:
        hint = (info["t0"] - info["asked"], info["t1"] - info["asked"])
        return trace_reduce.find_slice(planes, hint), info["asked"]


def idle_split(timeline):
    """{"input", "stage", "sync", "other"}: share (%) of the traced
    slice in which no operation ran on the device, by the step-loop
    phase open on the worker's main thread then; mean over the cell's
    chips. The four sum to 100 x (1 - busy_s / window_s) of the same
    traces, which is `device_idle_pct`."""
    run = timeline.run
    plane_prefix, line_prefixes = trace_reduce.SELECTORS[run["platform"]]
    probe_dir = os.path.join(timeline.run_dir, "probe")
    seconds = dict.fromkeys((*IDLE_KEYS, "other"), 0.0)
    chips, windows = 0, []
    for path in sorted(glob.glob(os.path.join(probe_dir, "*.json"))):
        with open(path) as f:
            record = json.load(f)
        info = record.get("trace") or {}
        if info.get("state") != "written":
            continue
        planes = trace_reduce.load(trace_reduce.find_xplane(info["dir"]))
        (lo, hi), origin = _slice_and_origin(planes, info)
        windows.append((hi - lo) / 1e9)
        # the main thread's phases on the trace's clock, in ns
        phases = [
            ((s["ts"] - origin) * 1e9, (s["ts"] + s["dur"] - origin) * 1e9,
             s["name"])
            for s in timeline.worker_spans()
            if s.get("pid") == record["pid"]
            and _args(s).get("thread") == MAIN_THREAD
            and s["name"] in INPUT + STAGE + SYNC + OTHER
        ]
        pieces = _innermost(phases, lo, hi)
        modules = []
        for plane, lines in planes:
            if not plane.startswith(plane_prefix):
                continue
            modules += [
                e for line, events in lines
                if line == trace_reduce.MODULES_LINE for e in events
            ]
            busy = trace_reduce.union(
                (max(s, lo), min(e, hi))
                for line, events in lines if line.startswith(line_prefixes)
                for _n, s, e in events
            )
            if not busy:
                continue
            chips += 1
            edges = [lo] + [t for pair in busy for t in pair] + [hi]
            idle = [
                (a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a
            ]
            by = {
                key: _overlap(idle, pieces, names) / 1e9
                for key, names in IDLE_KEYS.items()
            }
            by["other"] = sum(b - a for a, b in idle) / 1e9 - sum(by.values())
            for key, value in by.items():
                seconds[key] += value
            for name in sorted({p[2] for p in pieces}, key=str):
                held = _overlap(idle, pieces, (name,)) / 1e9
                if held > 0:
                    say(f"idle on {plane} of pid {record['pid']} under "
                        f"{name or 'no phase'}: {held:.4f}s of "
                        f"{(hi - lo) / 1e9:.4f}s")
        _clock_join(timeline, record["pid"], origin, modules, lo, hi)
    if not chips:
        raise TimelineError(
            f"no probe under {probe_dir} wrote a trace with operations "
            "inside its slice"
        )
    window = sum(windows) / len(windows)
    return {k: 100.0 * v / chips / window for k, v in seconds.items()}


def _clock_join(timeline, pid, origin, modules, lo, hi):
    """Log how well the two clocks join: the device finishing a program
    (an `XLA Modules` event's end, at the trace's origin + its time)
    against the host seeing it finished (`worker.delta_wait`'s end)."""
    ends = sorted(origin + e / 1e9 for _n, s, e in modules if lo <= e <= hi)
    waits = [
        s["ts"] + s["dur"] for s in timeline.worker_spans()
        if s.get("pid") == pid and s["name"] == "worker.delta_wait"
    ]
    seen = [
        min((w - e for w in waits if w >= e - 0.05), default=None, key=abs)
        for e in ends
    ]
    seen = [d for d in seen if d is not None and abs(d) < 1.0]
    if not seen:
        # a sync that queues behind others reaches its wait for the
        # device seconds after the device was done: the other end then
        starts = sorted(origin + s / 1e9 for _n, s, e in modules if lo <= s <= hi)
        lead = [
            min((m - s["ts"] for m in starts if m >= s["ts"]), default=None)
            for s in timeline.worker_spans()
            if s.get("pid") == pid and s["name"] == "compute"
        ]
        lead = [d for d in lead if d is not None and d < 1.0]
        say(f"clock join, pid {pid}: none of {len(ends)} program ends in "
            "the slice has a worker.delta_wait ending near it; a program "
            "started " + (f"{min(lead) * 1e3:.1f}ms" if lead else "(none)")
            + " after its compute phase opened (stack and dispatch "
            f"included; {len(lead)} matched)")
        return
    say(f"clock join, pid {pid}: the host saw a program finished "
        f"{statistics.median(seen) * 1e3:.1f}ms (median; range "
        f"{min(seen) * 1e3:.1f} to {max(seen) * 1e3:.1f}ms) after the "
        f"trace says it ended ({len(seen)} of {len(ends)} program ends "
        "matched)")


def idle(run, reader_file, key):
    timeline = load(run, reader_file)
    if timeline.idle is None:
        timeline.idle = idle_split(timeline)
        say("device idle by main-thread phase: " + ", ".join(
            f"{k} {v:.3f}%" for k, v in timeline.idle.items()
        ) + f"; sum {sum(timeline.idle.values()):.3f}%")
    return timeline.idle[key]
