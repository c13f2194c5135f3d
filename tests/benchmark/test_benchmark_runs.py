"""The whole-window readers (`benchmark/layer_metrics/_runs.py`) on span
files written by hand, so every answer is known.

The serial window cell: five update periods of 1000 ms, period k
beginning at `b` = 100 + 1000 k ms from the window's start. The run
`seq` k + 1 is asked for at `b` and seen ready at `b` + 700, under the
step loop's `compute` [b - 20, b + 720) and its `worker.window_wait`
[b + 5, b + 700); then `sync_wait` [b + 720, b + 950) and `get_batch`
[b + 950, b + 980). On the sync's thread `worker.d2h` [b + 730, b + 800)
and the round trip [b + 780, b + 930), inside which the master's `apply`
of the same version [b + 850, b + 900). So the gap after a run is
300 ms, 230 of it the sync's: 50 of the copy alone, 50 of `apply`, 100
of the rest of the round trip, 30 of none of these; from the first run's
start to the last one's end the device stands still 1200 of 4700 ms.
"""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

import xplane_fixture  # noqa: E402

from benchmark.harness import manifest  # noqa: E402
from benchmark.layer_metrics import _runs, _timeline  # noqa: E402

T0 = 1_790_000_000.0
WALL0, WALL1 = T0, T0 + 45.0
PID, MASTER_PID = 4242, 4100
READERS = ("window_exposed_pct", "exposed_sync_ms", "window_device_ms",
           "update_period_max_over_median", "window_resident_gb")
PERIODS, PERIOD_MS = 5, 1000


def span(name, start_ms, end_ms, thread="MainThread", pid=PID, tid=1, **args):
    return {
        "name": name, "cat": "phase", "ts": T0 + start_ms / 1e3,
        "dur": (end_ms - start_ms) / 1e3, "pid": pid, "tid": tid,
        "args": {"thread": thread, **args},
    }


def window_job(stalled=None, stall_ms=2000):
    """-> (worker's spans, master's). `stalled`: the period whose round
    trip stands still for `stall_ms` before the master applies."""
    worker, master = [], [span("setup.imports", -9e4, -8e4, pid=MASTER_PID)]
    shift = 0
    for k in range(PERIODS):
        b = 100 + PERIOD_MS * k + shift
        late = stall_ms if k == stalled else 0
        seq, sync = k + 1, dict(thread=f"Thread-{k + 3}", tid=2 + k % 2)
        worker += [
            span("worker.device_run", b, b + 700, program="jit_window",
                 steps=16, seq=seq, asked=T0 + b / 1e3, queued_ms=0.0,
                 bytes_in_use=int(6e9 + k * 1e8), bytes_reserved=int(8e9)),
            span("compute", b - 20, b + 720, steps=16),
            span("worker.window_wait", b + 5, b + 700, seq=seq),
            span("worker.sync_spawn", b + 710, b + 719, seq=seq),
            span("sync_wait", b + 720, b + 950 + late),
            span("get_batch", b + 950 + late, b + 980 + late),
            span("worker.window_sync", b + 710, b + 950 + late, seq=seq,
                 steps=16, bytes=1024, **sync),
            span("worker.d2h", b + 730, b + 800, seq=seq, **sync),
            span("rpc.client.ReportLocalUpdate", b + 780, b + 930 + late,
                 version=16 * seq, **sync),
            span("worker.flush_reports", b + 930 + late, b + 940 + late,
                 seq=seq, **sync),
        ]
        handler = dict(pid=MASTER_PID, tid=100, thread="uds-0",
                       version=16 * seq)
        master += [
            span("rpc.decode", b + 840 + late, b + 850 + late,
                 method="ReportLocalUpdate", **handler),
            span("apply", b + 850 + late, b + 900 + late,
                 kind="local_update", **handler),
            span("rpc.encode", b + 900 + late, b + 905 + late,
                 method="ReportLocalUpdate", **handler),
        ]
        if late:
            master += [
                span("proc.stall", b + 800, b + 800 + late - 100,
                     thread="edl-span-file", pid=MASTER_PID, tid=7,
                     late_ms=late - 100.0, role="master"),
                span("rpc.server.slow", b + 790, b + 906 + late,
                     pid=MASTER_PID, tid=100, thread="uds-0",
                     method="ReportLocalUpdate", queued_ms=50.0 + late,
                     handled_ms=60.0),
                # one outside the period says nothing of it
                span("proc.gc", b - 5000, b - 4900, pid=MASTER_PID, tid=100,
                     thread="uds-0", generation=2),
            ]
        shift += late
    return worker, master


def step_job():
    """Per-step mode: a step every 200 ms; `compute` [b - 10, b + 190)
    holds `report_gradient` [b + 20, b + 185), in it the wait for the
    step until b + 100, the copy [b + 100, b + 110) and the round trip
    [b + 110, b + 180) with the master's `apply` [b + 130, b + 150)."""
    worker, master = [], [span("setup.imports", -9e4, -8e4, pid=MASTER_PID)]
    for k in range(7):
        b = 50 + 200 * k
        worker += [
            span("worker.device_run", b, b + 100, program="jit_step", steps=1,
                 seq=k + 1, asked=T0 + b / 1e3, queued_ms=0.0,
                 bytes_in_use=int(2e9), bytes_reserved=int(9e9 + k)),
            span("compute", b - 10, b + 190, steps=1),
            span("report_gradient", b + 20, b + 185),
            span("worker.delta_wait", b + 21, b + 100),
            span("worker.d2h", b + 100, b + 110),
            span("rpc.client.ReportGradient", b + 110, b + 180, version=k + 1),
        ]
        master.append(span("apply", b + 130, b + 150, pid=MASTER_PID, tid=100,
                           thread="uds-0", kind="gradient", version=k + 1))
    return worker, master


def write_run(root, worker, master, name="cell-s7-t1"):
    run_dir = os.path.join(root, ".bench_runs", name)
    for d in ("probe", "logs", "tb"):
        os.makedirs(os.path.join(run_dir, d))
    with open(os.path.join(run_dir, "probe", "trace.latch"), "w") as f:
        f.write(repr(WALL0))
    for path, spans in (("logs/worker-0.spans.jsonl", worker),
                        ("tb/master.spans.jsonl", master)):
        if spans is not None:
            with open(os.path.join(run_dir, path), "w") as f:
                f.writelines(
                    s if isinstance(s, str) else json.dumps(s) + "\n"
                    for s in spans
                )
    return run_dir


def make_run(local_updates=16, trace=None):
    return {
        "platform": "tpu", "chips": 1, "trace": trace,
        "window": {"wall0": WALL0, "wall1": WALL1, "window_s": 45.0},
        "mix": {"workers": 1, "master_flags": {"local_updates": local_updates}},
    }


@pytest.fixture
def checkout(tmp_path):
    """A checkout's root with the readers in it, as `run.py` loads them."""
    root = str(tmp_path / "checkout")
    shutil.copytree(
        os.path.join(ROOT, "benchmark", "layer_metrics"),
        os.path.join(root, "benchmark", "layer_metrics"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    _timeline._cache.clear()
    _runs._cache.clear()
    return root


def read(root, name, run):
    return manifest.load_module(manifest.reader_file(name, root)).read(run)


def ms(value):
    """Wall-clock doubles near 1.79e9 resolve 0.24 us."""
    return pytest.approx(value, abs=2e-3)


KNOWN = {
    "window_exposed_pct": 100 * 1200 / 4700,
    "exposed_sync_ms": 230.0,
    "window_device_ms": 700.0,
    "update_period_max_over_median": 1.0,
    "window_resident_gb": 14.4,
}


@pytest.mark.parametrize("name", READERS)
def test_a_reader_on_the_serial_window_job_gives_its_known_number(
    checkout, name
):
    write_run(checkout, *window_job())
    assert read(checkout, name, make_run()) == ms(KNOWN[name])


def test_the_exposed_sync_s_parts_sum_to_it_each_moment_charged_once(
    checkout, capfd
):
    write_run(checkout, *window_job())
    assert read(checkout, "exposed_sync_ms", make_run()) == ms(230.0)
    (line,) = [l for l in capfd.readouterr().err.splitlines()
               if "exposed sync of the median update" in l]
    assert "230.00ms = d2h alone 50.00 + the master's apply 50.00 + the " \
        "rest of the round trip 100.00 + none of these 30.00" in line
    assert "of 4:" in line  # the last run has none after it
    runs = _runs.load(make_run(), manifest.reader_file("exposed_sync_ms", checkout))
    for parts in _runs.exposed_syncs(runs):
        assert sum(parts.values()) * 1e3 == ms(230.0)


def test_a_moment_under_the_window_wait_is_the_device_s_never_the_sync_s(
    checkout
):
    """A run seen ready late (the host was held up inside the wait):
    its gap is shorter, and no moment of it moves to the sync."""
    worker, master = window_job()
    for s in worker:
        if s["name"] in ("worker.device_run", "worker.window_wait") and (
            s["args"]["seq"] == 2
        ):
            s["dur"] += 0.1  # into `sync_wait`'s first 80 ms
    write_run(checkout, worker, master)
    runs = _runs.load(make_run(), manifest.reader_file("exposed_sync_ms", checkout))
    parts = _runs.exposed_syncs(runs)[1]
    # the wait is innermost until b + 800: sync_wait's [720, 800) is the
    # device's, and with it the copy alone
    assert sum(parts.values()) * 1e3 == ms(150.0)
    assert parts["d2h alone"] == 0.0


def test_a_queued_run_leaves_no_gap_and_no_exposed_sync(checkout):
    """The overlapped chain: each run asked for while the one before
    it ran, so `ts` is that one's end and nothing is exposed."""
    worker, master = [], [span("setup.imports", -9e4, -8e4, pid=MASTER_PID)]
    for k in range(6):
        b = 100 + 500 * k
        worker += [
            span("worker.device_run", b, b + 500, program="jit_window",
                 steps=8, seq=k + 1, asked=T0 + (b - 300) / 1e3,
                 queued_ms=300.0, thread="edl-device-runs", tid=9),
            span("compute", b - 320, b - 250, steps=8),
            span("worker.window_sync", b - 260, b + 600, seq=k + 1,
                 thread="Thread-3", tid=3, steps=8, bytes=8),
        ]
        master.append(span("apply", b + 550, b + 560, pid=MASTER_PID, tid=100,
                           thread="uds-0", kind="local_update",
                           version=8 * (k + 1)))
    write_run(checkout, worker, master)
    run = make_run(local_updates=8)
    assert read(checkout, "window_exposed_pct", run) == ms(0.0)
    assert read(checkout, "exposed_sync_ms", run) == 0.0
    assert read(checkout, "window_device_ms", run) == ms(500.0)
    # the CPU's runs carry no memory
    assert read(checkout, "window_resident_gb", run) == 0.0


def test_per_step_every_run_ends_an_update_period(checkout):
    write_run(checkout, *step_job())
    run = make_run(local_updates=0)
    assert read(checkout, "window_device_ms", run) == ms(100.0)
    # the gap [b + 100, b + 200): `report_gradient` holds 85 ms of it
    assert read(checkout, "exposed_sync_ms", run) == ms(85.0)
    runs = _runs.load(run, manifest.reader_file("exposed_sync_ms", checkout))
    (parts,) = {tuple(round(v * 1e3, 2) for v in p.values())
                for p in _runs.exposed_syncs(runs)}
    assert parts == (10.0, 20.0, 50.0, 5.0)
    assert read(checkout, "window_exposed_pct", run) == ms(100 * 600 / 1300)
    assert read(checkout, "window_resident_gb", run) == ms(11.000000006)
    assert read(checkout, "update_period_max_over_median", run) == ms(1.0)


def test_a_stalled_period_is_named_with_what_held_the_master_up(
    checkout, capfd
):
    write_run(checkout, *window_job(stalled=2))
    run = make_run()
    assert read(checkout, "update_period_max_over_median", run) == ms(3.0)
    err = capfd.readouterr().err.splitlines()
    (line,) = [l for l in err if "update period" in l and "took" in l]
    assert "update period 2 of 4" in line and "took 3.000s" in line
    assert "median is 1.000s" in line and "excess 2.000s" in line
    assert "exposed under sync_wait +2.000s" in line
    inside = [l for l in err if "inside it" in l]
    assert len(inside) == 2
    assert "master: rpc.server.slow" in inside[0]
    assert "'queued_ms': 2050.0" in inside[0]
    assert "master: proc.stall" in inside[1] and "'late_ms': 1900.0" in inside[1]
    # the other readers take the stall as it falls
    assert read(checkout, "exposed_sync_ms", run) == ms(230.0)
    assert read(checkout, "window_exposed_pct", run) == ms(100 * 3200 / 6700)


def test_a_steady_window_names_no_period(checkout, capfd):
    write_run(checkout, *window_job())
    assert read(checkout, "update_period_max_over_median", make_run()) == ms(1.0)
    assert "took" not in capfd.readouterr().err


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_device_runs_reads_zero_and_one_line_says_so(
    checkout, capfd, name
):
    """The parent commit: its span files are whole and hold none of
    PR 54's spans (its `apply` spans alone would give the periods)."""
    worker, master = window_job()
    write_run(checkout, [s for s in worker if s["name"] not in (
        "worker.device_run", "worker.window_wait")], master)
    run = make_run()
    assert read(checkout, name, run) == 0.0
    for other in READERS:
        assert read(checkout, other, run) == 0.0
    said = [l for l in capfd.readouterr().err.splitlines()
            if "no worker.device_run span" in l]
    assert len(said) == 1 and all(n in said[0] for n in READERS)


def test_a_program_without_a_timeline_reads_zero(checkout, capfd):
    write_run(checkout, None, None)
    run = make_run()
    assert [read(checkout, n, run) for n in READERS] == [0.0] * 5
    assert "no worker.device_run span" in capfd.readouterr().err


@pytest.mark.parametrize("name", READERS)
def test_a_broken_span_file_raises(checkout, name):
    worker, master = window_job()
    write_run(checkout, worker[:3] + ["{not a span\n"] + worker[3:], master)
    with pytest.raises(_timeline.TimelineError, match="is no span"):
        read(checkout, name, make_run())


def test_a_line_the_kill_cut_short_is_left_out(checkout):
    worker, master = window_job()
    write_run(checkout, worker + [json.dumps(worker[-1])[:40]], master)
    assert read(checkout, "window_device_ms", make_run()) == ms(700.0)


def test_the_runs_are_laid_beside_the_device_trace(checkout, capfd):
    """Every `jit_window` event whole inside the probe's slice against
    the run that ends nearest its end (device-only trace: its clock
    starts at the probe's `asked`)."""
    worker, master = window_job()
    run_dir = write_run(checkout, worker, master)
    profile = os.path.join(run_dir, "probe", f"trace-{PID}", "plugins",
                           "profile", "2026_10_03")
    os.makedirs(profile)
    # the trace's clock starts at T0: two windows whole inside the slice
    # [50, 2050) ms, begun 2 ms after their span's ts and seen ended
    # 1 ms late; before them the tail of the window the trace began in
    # and after them the head of the one it ended in, both cut to the
    # trace's edges (the line's first and last); another program
    plane = xplane_fixture._plane(1, "/device:TPU:0", [
        ("XLA Modules", [("jit_window(7)", 50_100, 90_000),
                         ("jit_window(7)", 102_000, 799_000),
                         ("jit_join(9)", 799_100, 799_900),
                         ("jit_window(7)", 1_102_000, 1_799_000),
                         ("jit_window(7)", 2_000_000, 2_049_000)]),
        ("XLA Ops", [("fusion.1", 102_000, 799_000)]),
    ])
    with open(os.path.join(profile, "host.xplane.pb"), "wb") as f:
        f.write(xplane_fixture._bytes(1, plane))
    with open(os.path.join(run_dir, "probe", f"{PID}.json"), "w") as f:
        json.dump({"pid": PID, "worker_id": 0, "trace": {
            "state": "written",
            "dir": os.path.join(run_dir, "probe", f"trace-{PID}"),
            "asked": T0, "t0": T0 + 0.05, "t1": T0 + 2.05,
        }}, f)
    run = make_run(trace={"window_s": 2.0, "busy_s": 0.7})
    assert read(checkout, "window_device_ms", run) == ms(700.0)
    (line,) = [l for l in capfd.readouterr().err.splitlines()
               if "device runs against the trace" in l]
    assert "2 of 2 jit_step/jit_window events" in line and "(0 have none)" in line
    assert "saw the end 1.00ms after" in line
    assert "began 2.00ms after" in line


def test_the_manifest_lists_the_five_by_name_for_every_cell():
    """Found by name, wherever later PRs append theirs."""
    committed = manifest.load(ROOT)
    assert manifest.lint(committed, ROOT) == []
    by_name = {m["name"]: m for m in committed["per_layer"]}
    layers = {
        "window_exposed_pct": ("%", "worker step"),
        "exposed_sync_ms": ("ms", "sync"),
        "window_device_ms": ("ms", "worker step"),
        "update_period_max_over_median": ("ratio", "PS apply and task dispatch"),
        "window_resident_gb": ("GB", "worker step"),
    }
    assert set(layers) == set(READERS)
    for name, (unit, layer) in layers.items():
        entry = by_name[name]
        assert entry == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": layer, "moves": "goodput",
        }
        assert os.path.isfile(manifest.reader_file(name, ROOT))
    for cell in committed["workloads"]:
        reported = manifest.cell_metrics(committed, cell["name"], "per_layer")
        assert set(READERS) <= set(reported)
