"""The timeline readers (`benchmark/layer_metrics/_timeline.py`) on a
recorded fixture: span files written by hand beside the committed
`recorded.xplane.pb`, so every answer is known.

The trace (xplane_fixture.py): slice [1000, 11000) us; chip 0 idle in
[3000, 4000) and [8000, 10000) us, chip 1 in [1000, 2000) and
[5000, 11000) us: 10000 us of idle over two chips of a 10000 us slice,
`device_idle_pct` 50. The worker's main thread, on the same clock
(trace time t is wall-clock `ASKED + t`): `task_other` [0, 20000) us
holding `sync_wait` [500, 3500), `compute` [3500, 8000) and `get_batch`
[8000, 10000). So of two chips' slice: input 2000 + 2000 us = 20 %,
stage (`compute`) 500 + 3000 = 17.5 %, sync 500 + 1000 = 7.5 %, the
rest (`task_other`, chip 1 from 10000) 1000 us = 5 %.
"""

import json
import math
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

import xplane_fixture  # noqa: E402

from benchmark.harness import manifest, trace_reduce  # noqa: E402
from benchmark.layer_metrics import _timeline  # noqa: E402

ASKED = 1_790_000_000.0
WALL0, WALL1 = ASKED + 0.0005, ASKED + 45.0
PID, MASTER_PID = 4242, 4100
IDLE = ("idle_input_pct", "idle_stage_pct", "idle_sync_pct", "idle_other_pct")
NEW = IDLE + (
    "sync_own_ms", "sync_client_ms", "sync_wire_ms", "apply_ms",
    "master_codec_ms", "setup_boot_s", "setup_init_s", "setup_programs_s",
)
# three syncs (ms): after SPAWN_MS on the step loop, on the sync thread
# and back to back: queued, device wait, d2h, pack, round trip, unpack,
# flush
SPAWN_MS = 0.1
SYNCS = (
    (0.0, 0.2, 0.3, 0.5, 2.5, 0.1, 0.3),   # own 4.0 with the spawn
    (3.0, 0.1, 0.4, 0.6, 3.4, 0.2, 0.2),   # own 5.0, queued 3
    (9.0, 0.5, 1.0, 1.5, 5.0, 0.5, 0.4),   # own 9.0, queued 9
)
SYNC_PARTS = ("worker.chain_wait", "worker.delta_wait", "worker.d2h",
              "rpc.client.encode", "rpc.client.ReportLocalUpdate",
              "rpc.client.decode", "worker.flush_reports")
# the master's side of the same updates (ms): rpc.decode, apply_wait,
# grad_decode, apply, model_encode, rpc.encode; handler 2.0, 2.9, 3.0
UPDATES = (
    (0.3, 0.1, 0.2, 1.0, 0.0, 0.4),
    (0.4, 0.1, 0.4, 1.5, 0.0, 0.5),
    (0.2, 0.1, 0.3, 2.0, 0.1, 0.3),
)
HANDLER = ("rpc.decode", "apply_wait", "grad_decode", "apply",
           "model_encode", "rpc.encode")


def span(name, start_us, end_us, thread="MainThread", pid=PID, tid=1, **args):
    return {
        "name": name, "cat": "phase", "ts": ASKED + start_us / 1e6,
        "dur": (end_us - start_us) / 1e6, "pid": pid, "tid": tid,
        "args": {"thread": thread, **args},
    }


def sync_start(i):
    return 1000 + 40000 * i


def rpc_start(i):
    return sync_start(i) + 1000 * (SPAWN_MS + sum(SYNCS[i][:4]))


def worker_spans():
    spans = [
        # set-up, before the window opens (negative trace time)
        span("setup.imports", -60e6, -50e6),
        span("setup.backend_init", -50e6, -40e6),
        span("setup.model_init", -30e6, -27e6, how="init"),
        span("setup.model_init", -27e6, -25e6, how="pull"),
        span("setup.program", -20e6, -16e6, program="jit_window",
             cache_hit=True),
        span("setup.program", -15e6, -14.5e6, program="jit_subtract",
             cache_hit=True),
        # the step loop over the traced slice
        span("task_other", 0, 20000),
        span("sync_wait", 500, 3500),
        span("worker.sync_exposed", 600, 3400, reason="backpressure"),
        span("compute", 3500, 8000, steps=8),
        span("get_batch", 8000, 10000),
    ]
    for i, parts in enumerate(SYNCS):
        start = sync_start(i)
        t = start + SPAWN_MS * 1000
        for name, ms in zip(SYNC_PARTS, parts):
            args = {"version": 8 * (i + 1)} if "Report" in name else {}
            if ms:
                spans.append(span(name, t, t + ms * 1000, thread="Thread-7",
                                  tid=2, **args))
            t += ms * 1000
        spans.append(span("worker.window_sync", start, t, thread="Thread-7",
                          tid=2, steps=8, bytes=1024))
        # the step loop's own part, from the sync's own start
        spans.append(span("worker.sync_spawn", start, start + SPAWN_MS * 1000))
    return spans


def master_spans(updates=UPDATES, versions=(8, 16, 24)):
    """Each update's handler spans back to back on one handler thread,
    100 us into the client's round trip of the same version."""
    spans = [span("setup.imports", -100e6, -95e6, pid=MASTER_PID)]
    for i, (parts, version) in enumerate(zip(updates, versions)):
        t = rpc_start(i) + 100
        for name, ms in zip(HANDLER, parts):
            if ms:
                spans.append(span(
                    name, t, t + ms * 1000, thread=f"grpc-{i}",
                    pid=MASTER_PID, tid=100 + i, kind="local_update",
                    method="ReportLocalUpdate", version=version,
                ))
            t += ms * 1000
    # a model pull is on the timeline too, and is no update
    t = 200000
    spans.append(span("rpc.decode", t, t + 100, pid=MASTER_PID, tid=99,
                      method="GetModel", version=24))
    spans.append(span("apply_wait", t + 100, t + 150, pid=MASTER_PID, tid=99,
                      kind="get_model", version=24))
    spans.append(span("model_encode", t + 150, t + 900, pid=MASTER_PID,
                      tid=99, kind="get_model", version=24))
    spans.append(span("rpc.encode", t + 900, t + 1900, pid=MASTER_PID, tid=99,
                      method="GetModel", version=24))
    return spans


def write_run(root, name="resnet50-224.window-1w-s7-t1", latch=WALL0,
              workers=None, master=None):
    run_dir = os.path.join(root, ".bench_runs", name)
    profile = os.path.join(run_dir, "probe", f"trace-{PID}", "plugins",
                           "profile", "2026_09_27")
    for d in (profile, os.path.join(run_dir, "logs"), os.path.join(run_dir, "tb")):
        os.makedirs(d)
    shutil.copy(xplane_fixture.PATH, os.path.join(profile, "host.xplane.pb"))
    with open(os.path.join(run_dir, "probe", "trace.latch"), "w") as f:
        f.write(repr(latch))
    with open(os.path.join(run_dir, "probe", f"{PID}.json"), "w") as f:
        json.dump({"pid": PID, "worker_id": 0, "trace": {
            "state": "written",
            "dir": os.path.join(run_dir, "probe", f"trace-{PID}"),
            "asked": ASKED, "t0": ASKED + 0.001, "t1": ASKED + 0.011,
        }}, f)
    for path, spans in (
        (os.path.join(run_dir, "logs", "worker-0.spans.jsonl"),
         worker_spans() if workers is None else workers),
        (os.path.join(run_dir, "tb", "master.spans.jsonl"),
         master_spans() if master is None else master),
    ):
        if spans is not False:
            with open(path, "w") as f:
                f.writelines(json.dumps(s) + "\n" for s in spans)
    return run_dir


def make_run(local_updates=8):
    reduction = trace_reduce.reduce(trace_reduce.load(xplane_fixture.PATH))
    return {
        "platform": "tpu", "chips": 2, "trace": reduction,
        "window": {"wall0": WALL0, "wall1": WALL1, "window_s": 45.0},
        "mix": {"workers": 1, "master_flags": {"local_updates": local_updates}},
    }


@pytest.fixture
def checkout(tmp_path):
    """A checkout's root with the readers in it, as `run.py` loads them."""
    root = str(tmp_path / "checkout")
    os.makedirs(os.path.join(root, "benchmark"))
    shutil.copytree(
        os.path.join(ROOT, "benchmark", "layer_metrics"),
        os.path.join(root, "benchmark", "layer_metrics"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    _timeline._cache.clear()
    return root


def read(root, name, run):
    return manifest.load_module(manifest.reader_file(name, root)).read(run)


def ms(value):
    """Wall-clock doubles near 1.79e9 resolve 0.24 us."""
    return pytest.approx(value, abs=2e-3)


def device_idle_pct(run):
    return 100.0 * (1.0 - run["trace"]["busy_s"] / run["trace"]["window_s"])


def test_idle_shares_are_known_and_sum_to_device_idle_pct(checkout):
    write_run(checkout)
    run = make_run()
    shares = [read(checkout, n, run) for n in IDLE]
    # wall-clock doubles near 1.79e9 resolve 0.2 us of a 10000 us slice
    assert shares == pytest.approx([20.0, 17.5, 7.5, 5.0], abs=0.01)
    assert device_idle_pct(run) == pytest.approx(50.0)
    assert abs(sum(shares) - device_idle_pct(run)) < 0.5


def test_a_device_only_trace_is_dated_by_the_probe_s_asked(checkout):
    """The v5e's traces carry no host annotation: the slice is cut at
    the bounds the probe kept and the clock starts at `asked`."""
    run_dir = write_run(checkout)
    xplane = os.path.join(run_dir, "probe", f"trace-{PID}", "plugins",
                          "profile", "2026_09_27", "host.xplane.pb")
    with open(xplane, "wb") as f:
        f.write(xplane_fixture.build(with_slice=False))
    shares = [read(checkout, n, make_run()) for n in IDLE]
    assert shares == pytest.approx([20.0, 17.5, 7.5, 5.0], abs=0.01)


def test_the_latch_picks_this_run_of_two_and_raises_on_none(checkout):
    mine = write_run(checkout)
    write_run(checkout, name="lm-dense-160m.window-1w-s1-t1",
              latch=WALL0 - 300.0, workers=[])  # an older, faulted run
    reader = manifest.reader_file("sync_own_ms", checkout)
    assert _timeline.find_run_dir(make_run(), reader) == mine
    later = make_run()
    later["window"]["wall0"] += 50.0
    with pytest.raises(_timeline.TimelineError, match="0 run directories"):
        _timeline.find_run_dir(later, reader)
    write_run(checkout, name="twin", latch=WALL0 + 0.4)
    with pytest.raises(_timeline.TimelineError, match="2 run directories"):
        _timeline.find_run_dir(make_run(), reader)


def test_every_new_reader_gives_a_known_finite_number(checkout):
    write_run(checkout)
    run = make_run()
    got = {n: read(checkout, n, run) for n in NEW}
    assert all(math.isfinite(v) for v in got.values())
    # a sync less what it queued behind: 4, 5, 9 ms (the wholes 4, 8, 18)
    assert got["sync_own_ms"] == ms(5.0)
    # d2h + pack + unpack: 0.9, 1.2, 3.0 ms
    assert got["sync_client_ms"] == ms(1.2)
    # round trip less the handler: 2.5 - 2.0, 3.4 - 2.9, 5.0 - 3.0
    assert got["sync_wire_ms"] == ms(0.5)
    assert got["apply_ms"] == ms(1.5)
    # rpc.decode + grad_decode + model_encode + rpc.encode: 0.9, 1.3, 0.9
    assert got["master_codec_ms"] == ms(0.9)
    # master's start at -100 s, the worker's backend up at -40 s
    assert got["setup_boot_s"] == pytest.approx(60.0)
    assert got["setup_init_s"] == pytest.approx(5.0)
    assert got["setup_programs_s"] == pytest.approx(4.5)


def test_per_sync_the_parts_cover_its_own_work(checkout):
    write_run(checkout)
    timeline = _timeline.load(
        make_run(), manifest.reader_file("sync_own_ms", checkout)
    )
    for sync in _timeline.worker_syncs(timeline):
        assert _timeline._union_s(sync["parts"]) * 1e3 == ms(sync["own"] * 1e3)
        assert sync["rpc"]["name"] == "rpc.client.ReportLocalUpdate"
        assert [p["ts"] for p in sync["parts"]
                if p["name"] == "worker.sync_spawn"] == [sync["whole"]["ts"]]


def test_the_wire_joins_on_version_and_leaves_out_what_one_side_saw(checkout):
    # the master never saw version 16; it saw a 32 no client span names
    write_run(checkout, master=master_spans(versions=(8, 32, 24)))
    run = make_run()
    timeline = _timeline.load(run, manifest.reader_file("sync_wire_ms", checkout))
    wire = _timeline.wire_seconds(timeline)
    assert [round(w * 1e3, 3) for w in wire] == [0.5, 2.0]
    assert read(checkout, "sync_wire_ms", run) == ms(1.25)
    # the other metrics still count all three updates on each side
    assert len(_timeline.master_updates(timeline)) == 3
    assert read(checkout, "sync_own_ms", run) == ms(5.0)


def test_a_child_of_the_client_s_host_work_is_counted_once(checkout):
    """`worker.d2h` inside a `worker.encode` (or beside it): the union."""
    spans = worker_spans()
    start = sync_start(0) + 300  # sync 0's d2h: 0.3 ms from here
    spans.append(span("worker.encode", start - 50, start + 450,
                      thread="Thread-7", tid=2))
    write_run(checkout, workers=spans)
    timeline = _timeline.load(
        make_run(), manifest.reader_file("sync_client_ms", checkout)
    )
    first = _timeline.worker_syncs(timeline)[0]
    host = [p for p in first["parts"] if p["name"] in _timeline.CLIENT_HOST]
    # encode 0.5 (holding d2h 0.3) + pack 0.5 (its first 0.15 inside the
    # encode span) + unpack 0.1
    assert _timeline._union_s(host) * 1e3 == ms(0.95)


def test_a_name_the_files_lack_reads_zero(checkout):
    write_run(checkout, workers=[s for s in worker_spans()
                                 if s["name"] == "task_other"],
              master=[s for s in master_spans() if s["name"] != "apply"])
    run = make_run()
    got = {n: read(checkout, n, run) for n in NEW}
    assert got["idle_input_pct"] == got["idle_sync_pct"] == 0.0
    assert got["idle_stage_pct"] == 0.0
    assert got["idle_other_pct"] == pytest.approx(50.0, abs=0.01)
    for name in NEW[4:]:
        assert got[name] == 0.0, name


@pytest.mark.parametrize("missing", ["worker", "master"])
def test_one_process_s_span_file_without_the_other_s_raises(checkout, missing):
    write_run(checkout, **{
        "workers" if missing == "worker" else "master": False
    })
    with pytest.raises(_timeline.TimelineError, match="span files are missing"):
        read(checkout, "sync_own_ms", make_run())


def test_a_span_file_that_cannot_be_parsed_raises(checkout):
    run_dir = write_run(checkout)
    path = os.path.join(run_dir, "logs", "worker-0.spans.jsonl")
    with open(path) as f:
        lines = f.readlines()
    with open(path, "w") as f:
        f.writelines(lines[:3] + ["not a span\n"] + lines[3:])
    with pytest.raises(_timeline.TimelineError, match="is no span"):
        read(checkout, "sync_own_ms", make_run())


def test_a_last_line_the_kill_cut_short_is_left_out(checkout):
    run_dir = write_run(checkout)
    with open(os.path.join(run_dir, "logs", "worker-0.spans.jsonl"), "a") as f:
        f.write('{"name": "compute", "ts": 17900')
    assert read(checkout, "sync_own_ms", make_run()) == ms(5.0)


def test_a_program_without_a_timeline_reads_zero_and_all_idle_is_other(
    checkout, capsys
):
    """A parent commit under these benchmark files has `logs/` and
    `tb/` and no span file in them: the readers must not raise there
    (the driver's traced runs of the parent)."""
    write_run(checkout, workers=False, master=False)
    run = make_run()
    got = {n: read(checkout, n, run) for n in NEW}
    assert got.pop("idle_other_pct") == pytest.approx(
        device_idle_pct(run), abs=0.01
    )
    assert set(got.values()) == {0.0}
    said = capsys.readouterr().err
    assert said.count("writes no phase timeline") == 1


def test_per_step_sync_is_the_step_s_report_gradient_and_get_model(checkout):
    steps, master = [], [span("setup.imports", -100e6, -95e6, pid=MASTER_PID)]
    for i, (report_ms, pull_ms) in enumerate(((10, 0), (12, 3), (30, 0))):
        t = 1000 + 100000 * i
        steps.append(span("compute", t, t + 80000, steps=1))
        steps.append(span("report_gradient", t + 5000, t + 5000 + report_ms * 1000))
        steps.append(span("worker.d2h", t + 5000, t + 6000))
        steps.append(span("rpc.client.ReportGradient", t + 6500,
                          t + 4500 + report_ms * 1000, version=i + 1))
        if pull_ms:
            steps.append(span("get_model", t + 50000, t + 50000 + pull_ms * 1000))
        # the master's handler takes 2 ms of every round trip
        for name, a, b in (("rpc.decode", 7000, 7500), ("apply", 7500, 8500),
                           ("rpc.encode", 8500, 9000)):
            master.append(span(
                name, t + a, t + b, pid=MASTER_PID, tid=100, kind="gradient",
                method="ReportGradient", version=i + 1,
            ))
    write_run(checkout, workers=steps, master=master)
    run = make_run(local_updates=0)
    assert read(checkout, "sync_own_ms", run) == ms(15.0)
    assert read(checkout, "sync_client_ms", run) == ms(1.0)
    # round trips of 8, 10 and 28 ms less 2 ms of handler
    assert read(checkout, "sync_wire_ms", run) == ms(8.0)


def test_master_updates_groups_by_handler_thread_and_skips_pulls(checkout):
    write_run(checkout)
    updates = _timeline.master_updates(
        _timeline.load(make_run(), manifest.reader_file("apply_ms", checkout))
    )
    assert [u["version"] for u in updates] == [8, 16, 24]
    assert [round(u["apply"] * 1e3, 3) for u in updates] == [1.0, 1.5, 2.0]
    assert [round(u["handler"] * 1e3, 3) for u in updates] == [2.0, 2.9, 3.0]


def test_a_report_that_only_accumulates_is_no_update_and_decode_is_codec(checkout):
    """`--grads_to_wait 2`: the first report of a pair joins the sum
    (`apply` of kind `accumulate`), the second applies; `grad_decode`
    counts with the codec, not with the apply."""
    spans = [span("setup.imports", -100e6, -95e6, pid=MASTER_PID)]
    for i, kind in enumerate(("accumulate", "gradient") * 2):
        t = 2000 + 10000 * i
        for name, start, end in (
            ("rpc.decode", 0, 1000), ("apply_wait", 1000, 1100),
            ("grad_decode", 1100, 1600), ("apply", 1600, 1600 + 2000 * (i + 1)),
        ):
            spans.append(span(name, t + start, t + end, thread="grpc-0",
                              pid=MASTER_PID, tid=100, kind=kind,
                              method="ReportGradient", version=i // 2))
    write_run(checkout, master=spans)
    run = make_run(local_updates=0)
    updates = _timeline.master_updates(
        _timeline.load(run, manifest.reader_file("apply_ms", checkout))
    )
    assert [round(u["apply"] * 1e3, 3) for u in updates] == [4.0, 8.0]
    assert read(checkout, "apply_ms", run) == ms(6.0)
    assert read(checkout, "master_codec_ms", run) == ms(1.5)


def test_innermost_takes_the_span_opened_last():
    pieces = _timeline._innermost(
        [(0, 100, "task_other"), (10, 40, "sync_wait"), (40, 90, "compute")],
        5, 95,
    )
    assert pieces == [
        (5, 10, "task_other"), (10, 40, "sync_wait"), (40, 90, "compute"),
        (90, 95, "task_other"),
    ]
    assert _timeline._overlap([(0, 50)], pieces, ("sync_wait",)) == 30


def test_the_committed_manifest_appends_twelve_entries_and_lints_clean():
    committed = manifest.load(ROOT)
    assert manifest.lint(committed, ROOT) == []
    tail = committed["per_layer"][-12:]
    assert tuple(m["name"] for m in tail) == NEW
    ends = {m["name"] for m in committed["end_to_end"]}
    for m in tail:
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] in ends
        assert "workloads" not in m  # every cell, the rehearsal's too
        assert os.path.isfile(manifest.reader_file(m["name"], ROOT))
    # seventeen in the window cells, eighteen per step
    counts = {
        cell["name"]: len(manifest.cell_metrics(committed, cell["name"], "per_layer"))
        for cell in committed["workloads"]
    }
    assert sorted(counts.values()) == [17, 17, 18]
