"""Run one cell of `BENCHMARK.json` and print its result line.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A parent that never imports jax (every chip belongs to a worker of the
job it starts). It
1. sets up: finds the cell's files by name, writes or reuses the
   seeded RecordIO data, starts the cell's `master.main` job and waits
   until every incumbent worker has completed the mix's set-up tasks
   (two, unless the mix says otherwise; `setup_s` runs from the start
   of this process to that moment);
2. measures for `--seconds`: polls `GetSchedStats` every 0.1 s and
   tails the master's metrics sink and the worker logs;
3. stops the job, checks it, and prints — last — one JSON object:
   the cell's end-to-end metrics (`--trace 0`) or its per-layer metrics
   (`--trace 1`) and the `device` block taken inside the workers.
A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result. Progress goes to stderr.
"""

import argparse
import json
import math
import os
import shutil
import statistics
import sys
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import (  # noqa: E402
    data,
    flops,
    manifest as manifest_lib,
    peaks,
    trace_reduce,
    validate,
)
from benchmark.harness.job import Job, JobFailure, cpu_seconds  # noqa: E402

SETUP_DEADLINE_SECS = 1100  # a cell's first run compiles
POLL_SECS = 0.1
TRACE_SLICE_SECS = 4.0  # the same in every cell
EDGE_GAPS = 1.5  # median update gaps a window's edge may lack an update for
CLOCK_SLACK_SECS = 0.25  # the sink's `ts` against the polls it was read between
RUNS = ".bench_runs"


class BenchFailure(RuntimeError):
    pass


def say(msg):
    print(f"bench[{time.monotonic() - T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_reader(name, root):
    return manifest_lib.load_module(manifest_lib.reader_file(name, root)).read


# ------------------------------------------------------------------ phases


def wait_for_setup(job, platform):
    """Until every incumbent has completed the mix's set-up tasks;
    checks each boot line's platform on the way, and says where the
    time went. -> the workers' boot records."""
    marks = {}

    def mark(name):
        marks.setdefault(name, time.monotonic() - T0)

    while True:
        if time.monotonic() - T0 > SETUP_DEADLINE_SECS:
            raise BenchFailure("set-up ran past its deadline")
        time.sleep(POLL_SECS)
        if job.stats() is not None:  # raises when the master is gone
            mark("master answers")
        if job.master_holds_tpu():
            raise BenchFailure("the master mapped libtpu")
        logs = job.worker_logs()
        for wid, log in logs.items():
            if log["boot"] and log["boot"]["platform"] != platform:
                raise BenchFailure(
                    f"worker {wid} booted on {log['boot']['platform']!r}, "
                    f"this run needs {platform!r}"
                )
        done = [
            len(logs.get(w, {"done": ()})["done"]) for w in range(job.workers)
        ]
        if all(log["boot"] for log in logs.values()) and len(logs) >= job.workers:
            mark("every worker booted")
        if min(done) >= 1:
            mark("first task done")
        if min(done) >= job.setup_tasks:
            mark("set-up tasks done")
            say("set-up: " + ", ".join(f"{k} {v:.1f}s" for k, v in marks.items()))
            job.worker_pids()
            return logs


def measure(job, seconds):
    """The window: -> {"snaps", "faults", wall and cpu marks}."""
    faults, snaps = [], []
    cpu0 = cpu_seconds(job.master.pid)
    wall0, mono0 = time.time(), time.monotonic()
    while True:
        now = time.monotonic() - mono0
        if now >= seconds:
            break
        stats = job.stats()
        if stats is not None:
            ex, good = stats["exactness"], stats["goodput"]
            if ex["version"] != ex["init_version"] + ex["applied_update_steps"]:
                faults.append(f"inexact at {now:.1f}s: {ex}")
            snaps.append({
                "t": time.monotonic() - mono0,
                "wall": time.time(),
                "completed": good["completed_records"],
                "recomputed": good["recomputed_records"],
                "version": ex["version"] - ex["init_version"],
                "fractions": stats["phases"].get("fractions"),
                "relaunches": stats["workers"]["relaunches"],
            })
        if job.master_holds_tpu():
            faults.append("the master mapped libtpu")
        job.new_events()
        time.sleep(max(0.0, POLL_SECS - ((time.monotonic() - mono0) - now)))
    cpu1 = cpu_seconds(job.master.pid)
    job.new_events()
    return {
        "snaps": snaps, "faults": faults,
        "wall0": wall0, "wall1": time.time(),
        "window_s": time.monotonic() - mono0,
        "master_cpu_s": (cpu1 - cpu0) if None not in (cpu0, cpu1) else None,
    }


def final_stats(job, patience=30.0):
    """The master's answer once the window is over, asked again while
    it gives none: a poll waits behind a 649 MB apply for 3 s on a quiet
    host and one call gives up after 5, which says the master is busy,
    not that it is gone (a master that has exited raises)."""
    deadline = time.monotonic() + patience
    while True:
        stats = job.stats()
        if stats is not None or time.monotonic() > deadline:
            return stats
        time.sleep(POLL_SECS)


def applied_updates(events):
    """[(ts, version)] of the updates the master applied, by its own
    clock: one `train/loss` line of its sink for each, whose `step` is
    the model version the update produced (a version is one minibatch
    trained and applied: a window of 8 local steps advances it by 8)."""
    return sorted(
        (e["ts"], e["step"]) for e in events if e["tag"] == "train/loss"
    )


def clock_faults(events):
    """The master stamps each update with the host's clock; this
    process read that line between two polls it timed itself. A `ts`
    outside them (by more than `CLOCK_SLACK_SECS`) means the stamps
    are not the times the updates landed, and the run faults: the
    program's stamp only places an update inside the benchmark's own
    poll interval, which is 0.1 s wide or, behind a 649 MB apply, 3 s."""
    off = [
        e for e in events
        if e["tag"] == "train/loss" and not (
            (e["seen"][0] or 0.0) - CLOCK_SLACK_SECS
            <= e["ts"] <= e["seen"][1] + CLOCK_SLACK_SECS
        )
    ]
    return [
        f"{len(off)} update(s) stamped outside the polls they were read "
        f"between, the first {off[0]['ts']:.3f} in {off[0]['seen']}"
    ] if off else []


def update_span(events, window):
    """The updates a rate is taken between: the last one applied before
    the window opened (the one that ended set-up: the window opens
    within a poll of it) and every one applied inside the window."""
    updates = applied_updates(events)
    before = [u for u in updates if u[0] <= window["wall0"]]
    return before[-1:] + [
        u for u in updates if window["wall0"] < u[0] <= window["wall1"]
    ]


def goodput_span(events, window, minibatch, recomputed=0):
    """-> (records, seconds, faults, note): the samples trained and
    applied over the window, and the time they took.

    Work is counted where the master applies it (`version` x minibatch,
    which `correct` holds to `completed_records`), not where a task of
    4096 records ends. The span runs from the first to the last update
    of `update_span`, both by the master's clock, so that it holds
    whole update periods only: a window of 45 s holds 9 periods of the
    slowest cell, and a count over the bare window would jump by a
    ninth where a period crosses its edge. What that leaves out is an
    edge shorter than a period at the window's end — and no more: when
    the window ends more than `EDGE_GAPS` median gaps after the last
    update, the job has stopped or stalled, and the span runs on to the
    window's end, so that the rate pays for all of that time (updates
    that stop halfway through the window halve it). The same at the
    window's start is a fault: set-up ends on an update. `recomputed`
    (records trained a second time after a requeue, first to last
    poll) is subtracted."""
    span = update_span(events, window)
    if len(span) < 3:
        raise BenchFailure(
            f"{len(span)} update(s) to take a rate between: none "
            f"({span}, window {window['wall0']} .. {window['wall1']})"
        )
    gap = statistics.median(b[0] - a[0] for a, b in zip(span, span[1:]))
    head = abs(window["wall0"] - span[0][0])
    tail = window["wall1"] - span[-1][0]
    faults = [
        f"no update for {head:.2f}s at the window's start, where the "
        f"median gap between updates is {gap:.2f}s"
    ] if head > EDGE_GAPS * gap else []
    end, note = span[-1][0], ""
    if tail > EDGE_GAPS * gap:
        end = window["wall1"]
        note = (f"STALL: no update for the window's last {tail:.2f}s "
                f"(median gap {gap:.2f}s): that time is in the rate")
    records = (span[-1][1] - span[0][1]) * minibatch - recomputed
    return records, end - span[0][0], faults, note


def half_rates(events, window, minibatch):
    """The rate in each half of the span, for the log: whether a run's
    halves differ as its runs do says whether a longer window would
    steady the metric."""
    span = update_span(events, window)
    middle = (span[0][0] + span[-1][0]) / 2
    cut = min(range(len(span)), key=lambda i: abs(span[i][0] - middle))
    if cut in (0, len(span) - 1):
        return "too few updates for halves"
    first, mid, last = span[0], span[cut], span[-1]
    return "halves {:.2f} and {:.2f} records/s".format(
        (mid[1] - first[1]) * minibatch / (mid[0] - first[0]),
        (last[1] - mid[1]) * minibatch / (last[0] - mid[0]),
    )


def window_updates(events, window):
    """The `train/loss` events the master wrote inside the window."""
    return [
        e for e in events
        if e["tag"] == "train/loss"
        and window["wall0"] <= e["ts"] <= window["wall1"]
    ]


def check_losses(events, window, sizes):
    """-> (faults, note): every loss of the window finite, and its end
    at most `last_over_first_at_most` times its start. The
    configuration's file states that ratio under `loss_check`, with
    the runs it was measured from; a file that states none is held to
    1. As a rule the start is the median of the window's first fifth
    and the end the median of its last fifth, a fifth never fewer than
    three updates: the data are learnable, so the loss falls all
    through a window, and by the same share for a seed (ResNet-50).

    A file that states `untrained_loss` is held to that instead: the
    start is the loss of the model before its first step (ln of the
    vocabulary) and the end the lowest loss of the window's second
    half. That is for a configuration whose loss falls by most of its
    height within some tens of steps, and not the same tens from run
    to run (the LM: between steps 50 and 110 of a window that spans 40
    to 112, in two runs of one seed): a fall inside the window cannot
    be asked of every run, the learning that every run shows can."""
    losses = [e["value"] for e in window_updates(events, window)]
    if len(losses) < 3:
        return [f"only {len(losses)} train/loss events in the window"], ""
    faults = []
    if not all(math.isfinite(v) for v in losses):
        faults.append("a train/loss is not finite")
    check = sizes.get("loss_check", {})
    fifth = max(3, len(losses) // 5)
    first = statistics.median(losses[:fifth])
    last = statistics.median(losses[-fifth:])
    note = f"loss {first:.4f} -> {last:.4f} over {len(losses)} updates"
    if "untrained_loss" in check:
        first, last = check["untrained_loss"], min(losses[len(losses) // 2:])
        note += f", its second half's lowest {last:.4f} of {first} untrained"
    most = check.get("last_over_first_at_most", 1.0)
    if not last <= first * most:
        faults.append(f"{note}: x{last / first:.3f} where the "
                      f"configuration allows at most x{most}")
    return faults, f"{note} (x{last / first:.3f})"


def update_gaps_ms(events, window):
    ts = sorted(e["ts"] for e in window_updates(events, window))
    return [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]


def device_block(job, logs, platform, chips, trace):
    """-> (device, reduction | None) from the workers' boot lines, the
    probe's records and, in a traced run, the probe's traces."""
    boots = [logs[w]["boot"] for w in range(job.workers)]
    kinds = {b["device_kind"] for b in boots}
    held = [c for b in boots for c in b["chips"]]
    if len(kinds) != 1:
        raise BenchFailure(f"workers on different devices: {kinds}")
    if platform == "tpu" and (len(held) != chips or len(set(held)) != chips):
        raise BenchFailure(
            f"the cell asks for {chips} chip(s), its workers held {held}"
        )
    records = job.probe_records()
    peak = max((r["memory_peak_bytes"] for r in records.values()), default=0)
    device = {
        "platform": platform, "kind": kinds.pop(), "count": len(held),
        "memory_peak_bytes": peak,
    }
    if not trace:
        return device, None
    reductions = []
    for record in records.values():
        info = record.get("trace") or {}
        if info.get("state") != "written":
            say(f"worker {record['worker_id']}: no trace ({info})")
            continue
        planes = trace_reduce.load(trace_reduce.find_xplane(info["dir"]))
        hint = (info["t0"] - info["asked"], info["t1"] - info["asked"])
        reductions.append(trace_reduce.reduce(planes, platform, hint))
    merged = trace_reduce.merge(reductions)
    device["window_s"] = merged["window_s"]
    device["busy_s"] = merged["busy_s"]
    return device, merged


# -------------------------------------------------------------------- a run


def run_cell(workload, seed, seconds, trace, root=ROOT, platform="tpu"):
    """-> the result object. `platform="cpu"` is the sandbox rehearsal
    (tests only): same path, no device metric."""
    manifest = manifest_lib.load(root)
    resolved = manifest_lib.resolve(manifest, workload, root)
    cell, sizes, mix = resolved["cell"], resolved["sizes"], resolved["mix"]
    chips = cell["chips"]
    data_dir = data.ensure(root, sizes, resolved["config_dir"], seed)
    run_dir = os.path.join(root, RUNS, f"{workload}-s{seed}-t{int(trace)}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    say(f"{workload}: data {data_dir}, run dir {run_dir}")

    job = Job(
        root, run_dir, resolved, data_dir,
        trace_secs=TRACE_SLICE_SECS if trace else 0.0,
    )
    try:
        job.start()
        say(f"master pid {job.master.pid}: {' '.join(job.argv[3:])}")
        logs = wait_for_setup(job, platform)
        setup_s = time.monotonic() - T0
        say(f"set-up done in {setup_s:.1f}s; measuring {seconds}s")
        if trace:
            job.drop_trace_latch()
        window = measure(job, seconds)
        snaps = window["snaps"]
        records, span, rate_faults, stall = goodput_span(
            job.events, window, job.minibatch,
            snaps[-1]["recomputed"] - snaps[0]["recomputed"],
        )
        final = final_stats(job)
        if trace:  # the slice is long over; give a slow writer a moment
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and any(
                (r.get("trace") or {}).get("state")
                in (None, "starting", "tracing", "stopping")
                for r in job.probe_records().values()
            ):
                time.sleep(0.2)
        logs = job.worker_logs()
        dropped = job.dropped_tasks()
    except (JobFailure, BenchFailure):
        say(job.tail())
        say(f"last RPC error: {job.last_rpc_error}")
        raise
    finally:
        job.stop()

    faults = window["faults"] + rate_faults + clock_faults(job.events)
    if final is None:
        faults.append("the master did not answer after the window")
    else:
        ex, good = final["exactness"], final["goodput"]
        version = ex["version"] - ex["init_version"]
        in_flight = mix["workers"] * job.per_task
        if ex["version"] != ex["init_version"] + ex["applied_update_steps"]:
            faults.append(f"inexact at the end: {ex}")
        if abs(version * job.minibatch - good["completed_records"]) > in_flight:
            faults.append(
                f"version {version} x minibatch {job.minibatch} is not "
                f"within {in_flight} of {good['completed_records']} completed"
            )
    if dropped:
        faults.append(f"{dropped} task(s) dropped after their retries")
    loss_faults, loss_note = check_losses(job.events, window, sizes)
    faults += loss_faults
    for wid, log in logs.items():
        if log["boot"] and log["boot"]["platform"] != platform:
            faults.append(f"worker {wid} ran on {log['boot']['platform']}")

    device, reduction = device_block(job, logs, platform, chips, trace)
    gaps = update_gaps_ms(job.events, window)
    first, last = snaps[0], snaps[-1]
    tasks = (last["completed"] - first["completed"]) // job.per_task
    say(f"{len(snaps)} polls, the longest apart "
        f"{max(b['t'] - a['t'] for a, b in zip(snaps, snaps[1:])):.2f}s")
    whole = (last["completed"] - last["recomputed"]) - (
        first["completed"] - first["recomputed"]
    )
    halves = half_rates(job.events, window, job.minibatch)
    say(f"{loss_note}; {tasks} tasks, {records} records over {span:.2f}s "
        f"of the {window['window_s']:.2f}s window ({halves}); "
        f"by completed tasks over the bare window: {whole} records")
    say("every update's loss, set-up | window: " + " | ".join(
        " ".join(f"{e['value']:.3f}" for e in job.events
                 if e["tag"] == "train/loss"
                 and (e["ts"] > window["wall0"]) == inside)
        for inside in (False, True)
    ))
    if stall:  # not a fault, but the next reader wants to know why
        say(f"{stall}\n{job.tail(1500)}")
    for fault in faults:
        say(f"FAULT: {fault}")

    # what a metric's reader gets (benchmark/layer_metrics/__init__.py)
    run = {
        "platform": platform, "chips": chips, "sizes": sizes, "mix": mix,
        "window": window, "snaps": snaps,
        "goodput_records": records, "goodput_span_s": span,
        "setup_s": setup_s, "update_gaps_ms": gaps,
        "trace": reduction,
        "flops_per_sample": flops.flops_per_sample(
            sizes, resolved["config_dir"]
        ),
        "peak_flops_per_s": peaks.peak(device["kind"])
        if platform == "tpu" else None,
    }
    kind = "per_layer" if trace else "end_to_end"
    wanted = manifest_lib.cell_metrics(manifest, workload, kind)
    metrics = {}
    for name, entry in wanted.items():
        value = (
            load_reader(name, root)(run) if trace else END_TO_END[name](run)
        )
        if value is not None:  # a reader that finds nothing says nothing
            metrics[name] = {"value": value, "unit": entry["unit"]}
    result = {
        "correct": not faults,
        "attempted": int(tasks),
        "failed": dropped,
        "metrics": metrics,
        "device": device,
    }
    if reduction:
        result["breakdown"] = {
            "device_ops": reduction["device_ops"],
            "idle_gaps": reduction["idle_gaps"],
        }
    if faults:
        result["faults"] = faults[:10]  # the driver ignores other keys
    else:
        shutil.rmtree(run_dir)  # logs and traces outlive only a fault
    return result, {n: e["unit"] for n, e in wanted.items()}


# end-to-end metrics are the benchmark's own readings (host clock)
END_TO_END = {
    "goodput": lambda run: run["goodput_records"]
    / run["goodput_span_s"] / run["chips"],
    "setup_s": lambda run: run["setup_s"],
}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        raise SystemExit(
            "benchmark: JAX_PLATFORMS=cpu — this needs a TPU; nothing "
            "is measured on the CPU"
        )
    result, expected = run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace)
    )
    line = json.dumps(result)
    faults = validate.check_line(line, expected, bool(args.trace))
    if faults:
        say(f"the result line fails the contract: {faults}\n{line}")
        raise SystemExit(4)
    print(line, flush=True)


if __name__ == "__main__":
    main()
