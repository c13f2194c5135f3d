"""The in-worker probe: where the result line's `device` block comes from.

The process that runs the benchmark never holds the chip — every chip
belongs to a `python -m elasticdl_tpu.worker.main` child of the master
— so peak memory and the device trace can only be taken inside a
worker, and the program has no hook that reports either for a bounded
window. Every configuration's `zoo.py` imports this module and calls
`start_if_worker()`. It does nothing unless `__main__` is the worker
entry point AND the benchmark's probe directory is in the environment:
the master loads the zoo module too and must stay off the chip.

In a worker, one daemon thread
(a) once a second writes the fullest local device's peak memory
    (`peak_bytes_in_use` + `peak_bytes_reserved`) to `<dir>/<pid>.json`;
(b) in a traced run, when the parent drops `<dir>/trace.latch` at the
    start of the measured window, records a `jax.profiler` trace of a
    fixed slice under `<dir>/trace-<pid>/`, and keeps the slice's
    bounds on the trace's clock for the reduction to clip to.
A worker that boots after the latch's moment (a replacement) records
no trace.
"""

import json
import os
import sys
import threading
import time

ENV_DIR = "EDLBENCH_PROBE_DIR"
ENV_TRACE_SECS = "EDLBENCH_PROBE_TRACE_SECS"  # "" or 0: an untraced run
WORKER_MAIN = "elasticdl_tpu.worker.main"
LATCH = "trace.latch"
SLICE_EVENT = "edlbench_probe_slice"
LATCH_GRACE_SECS = 2.0
_started = None


def _write(path, record):
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(record, f)
    os.replace(tmp, path)  # the parent never reads half a file


def _worker_id():
    argv = sys.argv
    if "--worker_id" in argv[:-1]:
        return int(argv[argv.index("--worker_id") + 1])
    return -1


def _peak_bytes(devices):
    """The fullest device's peak: buffers (`peak_bytes_in_use`) plus
    what the runtime set aside for compiled programs' temporaries
    (`peak_bytes_reserved`: on the v5e the window program's 9 GB of
    scratch shows there and nowhere else — PERF.md, PR 23)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}  # the CPU backend reports none
        peak = max(
            peak,
            int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)),
        )
    return peak


def _trace_slice(probe_dir, seconds, on_tpu, note):
    """Record the slice; `note(state)` leaves each step in the probe's
    record, so a trace that never comes back says where it stopped.

    On the TPU the host tracer is off: with it on (level 1 or 2),
    `stop_trace` did not return within 200 s inside a training worker,
    three runs of three, and returned in 10 s with it off (PERF.md, PR
    23). The slice annotation is then missing from the trace, so the
    slice's bounds are also kept as seconds since `start_trace` was
    called, which is where the trace's clock starts."""
    import jax

    trace_dir = os.path.join(probe_dir, f"trace-{os.getpid()}")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0 if on_tpu else 2  # XLA:CPU ops are host spans
    asked = time.time()
    note({"state": "starting", "asked": asked})
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.time()
    note({"state": "tracing", "asked": asked, "t0": t0})
    try:
        with jax.profiler.TraceAnnotation(SLICE_EVENT):
            time.sleep(seconds)
    finally:
        t1 = time.time()
        note({"state": "stopping", "asked": asked, "t0": t0, "t1": t1})
        jax.profiler.stop_trace()
    return {"state": "written", "dir": trace_dir, "asked": asked, "t0": t0,
            "t1": t1, "written": time.time()}


def _loop(probe_dir, trace_secs):
    import jax

    devices = jax.local_devices()
    record = {
        "pid": os.getpid(),
        "worker_id": _worker_id(),
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "local_devices": len(devices),
        "memory_peak_bytes": 0,
        "trace": None,
    }
    path = os.path.join(probe_dir, f"{os.getpid()}.json")
    latch = os.path.join(probe_dir, LATCH)
    next_write = 0.0
    while True:
        now = time.time()
        if now >= next_write:
            record["memory_peak_bytes"] = _peak_bytes(devices)
            record["t"] = now
            _write(path, record)
            next_write = now + 1.0
        if trace_secs > 0 and record["trace"] is None and os.path.exists(latch):
            try:
                with open(latch) as f:
                    due = float(f.read().strip() or 0)
            except (OSError, ValueError):
                due = 0.0  # half-written: look again in a moment
            if due and now - due > LATCH_GRACE_SECS:
                record["trace"] = {"state": "skipped: booted after the latch"}
            elif due:

                def note(state):
                    record["trace"] = state
                    _write(path, record)

                try:
                    note(_trace_slice(
                        probe_dir, trace_secs, record["platform"] == "tpu",
                        note,
                    ))
                except Exception as e:  # the worker must train on
                    note({"state": f"failed: {e!r}"})
                next_write = 0.0
                continue
        time.sleep(0.05)


def start_if_worker():
    """Start the probe thread — only inside a benchmarked worker."""
    global _started
    main = sys.modules.get("__main__")
    spec = getattr(main, "__spec__", None)
    probe_dir = os.environ.get(ENV_DIR)
    if _started or not probe_dir or getattr(spec, "name", None) != WORKER_MAIN:
        return None
    trace_secs = float(os.environ.get(ENV_TRACE_SECS) or 0)
    import jax  # noqa: F401  here, not first in the thread: a worker has
    # it loaded already, and two threads importing it at once collide
    _started = threading.Thread(
        target=_loop, args=(probe_dir, trace_secs), daemon=True,
        name="edlbench-probe",
    )
    _started.start()
    return _started
