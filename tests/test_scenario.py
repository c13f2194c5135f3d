"""Churn-harness tests (chaos/scenario.py): strict trace parsing,
deterministic scheduling, and goodput arithmetic.

The tier-1 portion never boots a fleet: parsing and scheduling are
pure, and the dispatcher-accounting tests drive a real TaskDispatcher
in-process, and the entry point's tests stub the runner to a canned
report. The full trace replays are e2e-marked (and run in CI's
churn-scenario job via `python -m elasticdl_tpu.chaos <trace>`)."""

import copy
import json
import tempfile

import pytest

from elasticdl_tpu.chaos.__main__ import main as chaos_main
from elasticdl_tpu.chaos.scenario import (
    JobRun,
    JobSpec,
    ScenarioRunner,
    ScenarioScheduler,
    TraceError,
    compute_goodput,
    list_traces,
    load_trace,
    parse_trace,
)
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher


def _trace(**overrides):
    base = {
        "name": "t",
        "seed": 3,
        "jobs": [{"tag": "main", "records": 1024, "workers": 2}],
        "events": [
            {"at_progress": 0.5, "action": "kill", "fraction": 0.5}
        ],
    }
    base.update(overrides)
    return base


# -- parsing ------------------------------------------------------------------


def test_packaged_traces_all_parse():
    names = list_traces()
    assert set(names) >= {
        "preemption-storm",
        "flash-crowd",
        "bimodal-stragglers",
        "rolling-node-failure",
        "master-failover-drain",
        "master-failover-sigkill",
    }
    for name in names:
        trace = load_trace(name)
        assert trace.jobs and trace.events, name


def test_unknown_trace_name_is_loud():
    with pytest.raises(TraceError, match="unknown trace"):
        load_trace("no-such-trace")


def test_invalid_json_file_is_loud(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text("{not json")
    with pytest.raises(TraceError, match="not valid JSON"):
        load_trace(str(path))


@pytest.mark.parametrize(
    "mutation, message",
    [
        ({"bogus_key": 1}, "unknown keys"),
        ({"jobs": []}, "at least one job"),
        (
            {"jobs": [{"tag": "a", "records": 1024},
                      {"tag": "a", "records": 1024}]},
            "duplicate job tags",
        ),
        (
            {"jobs": [{"tag": "main", "records": 1000}]},
            "positive multiple",
        ),
        (
            {"jobs": [{"tag": "main", "records": 1024, "num_agg": 2}]},
            "num_agg requires num_ps",
        ),
        (
            {"jobs": [{"tag": "main", "records": 1024,
                       "deferred": True}]},
            "cannot be deferred",
        ),
        (
            {"events": [{"action": "nuke", "at_progress": 0.5}]},
            "unknown action",
        ),
        ({"events": [{"action": "kill", "fraction": 0.5}]}, "exactly one"),
        (
            {"events": [{"action": "kill", "fraction": 0.5,
                         "at_progress": 0.5, "at_elapsed": 1.0}]},
            "exactly one",
        ),
        (
            {"events": [{"action": "kill", "at_progress": 0.5}]},
            "fraction>0 or an explicit count",
        ),
        (
            {"events": [{"action": "kill", "fraction": 0.5,
                         "at_progress": 0.5, "job": "ghost"}]},
            "unknown job",
        ),
        (
            {"events": [{"action": "spawn_job", "at_progress": 0.5,
                         "spawn": "ghost"}]},
            "spawn_job needs spawn",
        ),
        (
            {"events": [{"action": "spawn_job", "at_progress": 0.5,
                         "spawn": "main"}]},
            "must be declared deferred",
        ),
        (
            {"events": [{"action": "chaos_arm", "at_progress": 0.5,
                         "latch": "ghost"}]},
            "not an armed_file",
        ),
        (
            {"events": [{"action": "kill_host", "at_progress": 0.5,
                         "host": 0}]},
            "out of range",
        ),
        (
            {"jobs": [{"tag": "main", "records": 1024, "num_ps": 1,
                       "master_standby": True}],
             "events": [{"action": "kill_master", "at_progress": 0.5,
                         "mode": "vaporize"}]},
            "mode 'sigkill' or 'handoff'",
        ),
        (
            {"jobs": [{"tag": "main", "records": 1024, "num_ps": 1}],
             "events": [{"action": "kill_master", "at_progress": 0.5,
                         "mode": "sigkill"}]},
            "must declare master_standby",
        ),
        (
            {"jobs": [{"tag": "main", "records": 1024,
                       "master_standby": True}]},
            "master_standby requires num_ps",
        ),
        ({"expect": {"min_unicorns": 1}}, "unknown keys"),
        (
            {"chaos": {"faults": [{"kind": "meteor"}]}},
            "unknown fault kind",
        ),
        (
            {"chaos": {"faults": [{"kind": "drop",
                                   "armed_file": "/tmp/abs"}]}},
            "bare latch name",
        ),
    ],
)
def test_malformed_traces_raise(mutation, message):
    with pytest.raises(TraceError, match=message):
        parse_trace(_trace(**mutation))


def test_kill_master_trace_parses_and_caps_at_one_per_job():
    raw = _trace(
        jobs=[{"tag": "main", "records": 1024, "num_ps": 1,
               "master_standby": True}],
        events=[{"action": "kill_master", "at_progress": 0.5,
                 "mode": "handoff"}],
        gap_explained_tolerance=0.01,
    )
    trace = parse_trace(raw)
    assert trace.jobs[0].master_standby
    assert trace.events[0].mode == "handoff"
    assert trace.gap_explained_tolerance == 0.01
    # a second kill has no standby left waiting to adopt
    raw["events"].append(
        {"action": "kill_master", "at_progress": 0.8, "mode": "sigkill"}
    )
    with pytest.raises(TraceError, match="at most one per job"):
        parse_trace(raw)
    # tolerance is optional and defaults to None (no assertion armed)
    assert parse_trace(_trace()).gap_explained_tolerance is None


def test_deferred_job_needs_exactly_one_spawn():
    raw = _trace(
        jobs=[
            {"tag": "main", "records": 1024},
            {"tag": "burst", "records": 512, "deferred": True},
        ],
        events=[],
    )
    with pytest.raises(TraceError, match="exactly one spawn_job"):
        parse_trace(raw)


# -- deterministic scheduling -------------------------------------------------


def test_same_seed_byte_identical_timeline():
    """The determinism contract: driven against a scripted fake fleet
    (fixed pool states per step), two schedulers with the same seed
    produce byte-identical canonical timelines; a different seed
    reshuffles the victim picks."""
    trace = load_trace("preemption-storm")
    script = [
        ([0, 1, 2, 3], 2),
        ([0, 2, 4, 5], 2),
        ([4, 5, 6], 1),
        ([6, 7, 8, 9, 10], 3),
    ]

    def drive(seed=None):
        s = ScenarioScheduler(trace, seed=seed)
        for pool, count in script:
            victims = s.pick_victims(pool, count)
            s.record("kill", "main", victims=victims, alive=len(pool))
        return s.timeline

    a, b = drive(), drive()
    assert a == b, "same seed must replay byte-identically"
    assert "\n".join(a) == "\n".join(b)
    c = drive(seed=trace.seed + 1)
    assert a != c, "a different seed must reshuffle the picks"
    # canonical form: sorted keys, no whitespace, no wall-clock fields
    for line in a:
        entry = json.loads(line)
        assert list(entry) == sorted(entry)
        assert "time" not in entry and "ts" not in entry


def test_pick_victims_is_order_insensitive_and_bounded():
    trace = parse_trace(_trace())
    a = ScenarioScheduler(trace)
    b = ScenarioScheduler(trace)
    assert a.pick_victims([3, 1, 2, 0], 2) == b.pick_victims(
        [0, 1, 2, 3], 2
    )
    s = ScenarioScheduler(trace)
    assert s.pick_victims([], 2) == []
    assert sorted(s.pick_victims([7, 8], 5)) == [7, 8]


def test_due_events_fire_in_declaration_order():
    raw = _trace(
        events=[
            {"at_progress": 0.5, "action": "drain", "count": 1},
            {"at_records": 100, "action": "scale_up", "count": 1},
            {"at_elapsed": 99.0, "action": "kill", "fraction": 0.5},
        ]
    )
    s = ScenarioScheduler(parse_trace(raw))
    totals = {"main": 1024}
    assert s.due_events(lambda tag: 0, totals, 0.0) == []
    assert s.pending() == 3
    due = s.due_events(lambda tag: 600, totals, 1.0)
    assert [e.action for e in due] == ["drain", "scale_up"]
    assert s.pending() == 1
    due = s.due_events(lambda tag: 600, totals, 100.0)
    assert [e.action for e in due] == ["kill"]
    assert s.pending() == 0


def test_kill_count_from_fraction_and_count():
    raw = _trace(
        events=[
            {"at_progress": 0.1, "action": "kill", "fraction": 0.5},
            {"at_progress": 0.2, "action": "kill", "count": 3},
        ]
    )
    trace = parse_trace(raw)
    s = ScenarioScheduler(trace)
    frac_ev, count_ev = trace.events
    assert s.kill_count(4, frac_ev) == 2
    assert s.kill_count(1, frac_ev) == 1  # floor of one victim
    assert s.kill_count(0, frac_ev) == 0
    assert s.kill_count(2, count_ev) == 2  # clamped to the pool


# -- goodput arithmetic -------------------------------------------------------


def test_goodput_gap_is_exactly_the_recompute_rate():
    g = compute_goodput(
        {
            "completed_records": 2048,
            "recomputed_records": 256,
            "drain_flushed_records": 128,
        },
        elapsed=16.0,
    )
    assert g["raw_images_per_sec"] == 128.0
    assert g["goodput_images_per_sec"] == 112.0
    # the defining identity: the raw-vs-goodput gap IS the recompute
    # rate, record for record
    assert g["gap_images_per_sec"] == pytest.approx(
        g["gap_from_recompute_images_per_sec"]
    )
    assert g["gap_explained"] == pytest.approx(1.0)


def test_goodput_drain_flush_never_subtracts():
    base = {"completed_records": 1024, "recomputed_records": 0}
    no_drain = compute_goodput(dict(base), 8.0)
    with_drain = compute_goodput(
        {**base, "drain_flushed_records": 512}, 8.0
    )
    assert (
        with_drain["goodput_images_per_sec"]
        == no_drain["goodput_images_per_sec"]
        == no_drain["raw_images_per_sec"]
    )
    assert with_drain["gap_images_per_sec"] == 0.0
    assert with_drain["gap_explained"] is None
    assert with_drain["drain_flushed_records"] == 512


def test_goodput_recompute_exceeding_completed_clamps_at_zero():
    # recompute is charged per PRIOR dispatch at success, so a job
    # whose tasks averaged >= 2 failed dispatches each (worker-death
    # requeue + master-cutover requeue_doing) legitimately recomputes
    # more records than it has — useful throughput floors at zero
    # while the UNCLAMPED gap keeps the recompute identity exact
    g = compute_goodput(
        {"completed_records": 10, "recomputed_records": 15}, 1.0
    )
    assert g["goodput_images_per_sec"] == 0.0
    assert g["goodput_fraction"] == 0.0
    assert g["raw_images_per_sec"] == pytest.approx(10.0)
    assert g["gap_images_per_sec"] == pytest.approx(15.0)
    assert g["gap_from_recompute_images_per_sec"] == pytest.approx(15.0)
    assert g["gap_explained"] == pytest.approx(1.0)


# -- dispatcher accounting ----------------------------------------------------


def _dispatcher(records=64):
    # `records` records in one shard, 16 per task
    return TaskDispatcher({"f": records}, {}, {}, 16, 1)


def test_requeued_and_retrained_subtract_exactly():
    d = _dispatcher(records=16)  # single task: the requeue comes back
    t = d.get(0)
    assert d.report(t.task_id, False, worker_id=0)  # fail -> requeue
    g = d.goodput_stats()
    assert g["requeued_records"] == 16
    assert g["recomputed_records"] == 0  # not yet retrained
    t2 = d.get(1)
    assert t2.task_id == t.task_id  # the requeued shard comes back
    assert d.report(t2.task_id, True, worker_id=1)
    g = d.goodput_stats()
    # retrained once: exactly one task's records charged, no more
    assert g["recomputed_records"] == 16
    assert g["completed_records"] == 16
    gp = compute_goodput(g, elapsed=2.0)
    assert gp["goodput_images_per_sec"] == 0.0  # all of it was re-work
    assert gp["raw_images_per_sec"] == 8.0


def test_preemption_requeue_counts_once_per_task():
    d = _dispatcher(records=32)  # exactly the two in-flight tasks
    a, b = d.get(0), d.get(0)
    d.recover_tasks(0)  # the worker died with two tasks in flight
    g = d.goodput_stats()
    assert g["preempted_task_requeues"] == 2
    assert g["requeued_records"] == 32
    assert g["recomputed_records"] == 0
    for _ in range(2):
        t = d.get(1)
        assert t.task_id in (a.task_id, b.task_id)
        d.report(t.task_id, True, worker_id=1)
    g = d.goodput_stats()
    assert g["recomputed_records"] == 32  # both shards retrained once


def test_first_dispatch_success_charges_nothing():
    d = _dispatcher()
    t = d.get(0)
    d.report(t.task_id, True, worker_id=0)
    g = d.goodput_stats()
    assert g["completed_records"] == 16
    assert g["recomputed_records"] == 0
    assert g["requeued_records"] == 0


def test_drain_flush_counted_once_never_into_recompute():
    d = _dispatcher()
    d.set_draining_fn(lambda wid: wid == 0)  # worker 0 is mid-drain
    t = d.get(0)
    d.report(t.task_id, True, worker_id=0)  # the drain flush
    t2 = d.get(1)
    d.report(t2.task_id, True, worker_id=1)  # ordinary completion
    g = d.goodput_stats()
    assert g["drain_flushed_records"] == 16  # only worker 0's task
    assert g["completed_records"] == 32  # flush counted ONCE, in here
    assert g["recomputed_records"] == 0  # and never as re-work
    gp = compute_goodput(g, elapsed=1.0)
    assert gp["goodput_images_per_sec"] == gp["raw_images_per_sec"]


def test_double_fault_on_same_task_charges_both_retrains():
    d = _dispatcher(records=16)  # single task hit by both faults
    t = d.get(0)
    d.report(t.task_id, False, worker_id=0)
    t = d.get(1)
    d.recover_tasks(1)
    t = d.get(2)
    d.report(t.task_id, True, worker_id=2)
    g = d.goodput_stats()
    assert g["requeued_records"] == 32  # two requeues of 16
    assert g["recomputed_records"] == 32  # two wasted dispatches


# -- teardown lifecycle (regressions) -----------------------------------------


class _StubRun:
    """Stands in for a booted JobRun in runner._jobs."""

    def __init__(self, fail=False):
        self.fail = fail
        self.stopped = False

    def stop(self):
        self.stopped = True
        if self.fail:
            raise RuntimeError("teardown broke")


def test_stop_all_isolates_per_job_failures(tmp_path):
    # regression: the finally sweep used to call stop() in a plain
    # loop — job A's raising stop() stranded the Popen fleets of every
    # job after it in the dict. All jobs must be stopped, and the
    # first error must still propagate (a broken teardown is itself a
    # scenario failure).
    runner = ScenarioRunner(
        parse_trace(_trace()), run_dir=str(tmp_path)
    )
    a, b, c = _StubRun(fail=True), _StubRun(), _StubRun(fail=True)
    runner._jobs = {"a": a, "b": b, "c": c}
    with pytest.raises(RuntimeError, match="teardown broke"):
        runner._stop_all()
    assert a.stopped and b.stopped and c.stopped


class _StubStoppable:
    def __init__(self):
        self.stopped = False

    def stop(self):
        self.stopped = True


def test_jobrun_failed_boot_tears_down_partial_state(tmp_path):
    # regression: a raise mid-_start_inner (bad spec args, shard spawn
    # failure) left a half-booted job the runner never records in
    # _jobs — the finally sweep missed it and the RPC server plus any
    # already-spawned worker Popens leaked past the process exit
    run = JobRun(
        JobSpec(tag="t", records=64),
        run_dir=str(tmp_path),
        worker_env={},
    )
    server = _StubStoppable()
    backend = _StubStoppable()

    def boots_then_raises():
        run.server = server
        run.backend = backend
        raise RuntimeError("shard spawn failed")

    run._start_inner = boots_then_raises
    with pytest.raises(RuntimeError, match="shard spawn failed"):
        run.start()
    assert server.stopped and backend.stopped


def test_jobrun_stop_is_safe_on_unbooted_run(tmp_path):
    # stop() against a run whose _start_inner never got anywhere must
    # be a no-op, not an AttributeError — start()'s cleanup path and
    # the runner sweep both rely on it
    run = JobRun(
        JobSpec(tag="t", records=64),
        run_dir=str(tmp_path),
        worker_env={},
    )
    run.stop()


# -- the entry point: python -m elasticdl_tpu.chaos ---------------------------

NO_FAILOVER = "no kill_master event"
REPORT = {
    "metric": "churn_scenario",
    "trace": "t",
    "scale": 1.0,
    "retention": 0.9,
    "baseline_images_per_sec": 100.0,
    "jobs": {
        "main": {
            "goodput": {"goodput_fraction": 0.5, "gap_explained": 1.0},
            "relaunches": 2,
        }
    },
    "events": [],
}


@pytest.fixture
def replay(monkeypatch, tmp_path, capsys):
    """Run the entry with `ScenarioRunner.run` stubbed to `report`:
    -> (exit code, the parsed stdout lines, the runners that ran)."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))

    def run(argv, report=REPORT):
        ran = []

        def fake_run(runner):
            ran.append(runner)
            return copy.deepcopy(report)

        monkeypatch.setattr(ScenarioRunner, "run", fake_run)
        rc = chaos_main(argv)
        out = capsys.readouterr().out.splitlines()
        return rc, [json.loads(line) for line in out], ran

    return run


def test_entry_unknown_trace_exits_nonzero_naming_the_packaged(capsys):
    with pytest.raises(SystemExit) as exc:
        chaos_main(["no-such-trace"])
    assert exc.value.code != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    for name in list_traces():
        assert name in captured.err


def test_entry_list_prints_exactly_the_packaged_traces(capsys):
    assert chaos_main(["--list"]) == 0
    assert capsys.readouterr().out.splitlines() == list_traces()


def test_entry_refuses_a_gap_the_recompute_counter_leaves_open(replay):
    report = copy.deepcopy(REPORT)
    report["jobs"]["main"]["goodput"]["gap_explained"] = 0.97
    with pytest.raises(AssertionError, match="not explained.*0.97"):
        replay(["preemption-storm"], report)


@pytest.mark.parametrize(
    "trace, top, job, goodput, expected",
    [
        # no master died: both headline fields null, and saying why
        (
            "preemption-storm", {}, {}, {},
            {
                "time_to_adopt_secs": None,
                "failover_mode": None,
                "time_to_adopt_secs_skipped_reason": NO_FAILOVER,
                "failover_mode_skipped_reason": NO_FAILOVER,
            },
        ),
        # the anchor job's failover is hoisted to the top level
        (
            "master-failover-drain", {},
            {"master_failover": {"time_to_adopt_secs": 1.25, "mode": "drain"}},
            {},
            {"time_to_adopt_secs": 1.25, "failover_mode": "drain"},
        ),
        # no fault-free twin, nothing recomputed: nulls with reasons
        (
            "flash-crowd", {"retention": None}, {}, {"gap_explained": None},
            {
                "retention": None,
                "retention_skipped_reason": "baseline=false",
                "gap_explained": None,
                "gap_explained_skipped_reason": "zero records were recomputed",
            },
        ),
    ],
    ids=["no-failover", "failover-hoisted", "no-baseline-no-gap"],
)
def test_entry_prints_one_json_line_whose_nulls_say_why(
    replay, trace, top, job, goodput, expected
):
    report = {**copy.deepcopy(REPORT), **top}
    anchor = load_trace(trace).jobs[0].tag
    report["jobs"][anchor] = {**report["jobs"].pop("main"), **job}
    report["jobs"][anchor]["goodput"].update(goodput)
    rc, lines, _ = replay([trace], report)
    assert rc == 0 and len(lines) == 1
    (line,) = lines
    # the report goes out whole, with the hoisted pair beside it
    assert set(line) >= set(REPORT) | {"time_to_adopt_secs", "failover_mode"}
    flat = {**line, **line["jobs"][anchor]["goodput"]}
    for key, value in expected.items():
        if key.endswith("_skipped_reason"):
            assert value in flat[key], (key, flat[key])
        else:
            assert flat[key] == value, key
    # a field that has a value has no reason beside it
    assert not [
        k for k in flat
        if k.endswith("_skipped_reason")
        and flat[k[: -len("_skipped_reason")]] is not None
    ]


@pytest.mark.parametrize(
    "argv, scale",
    [(["preemption-storm"], 1.0), (["preemption-storm", "--scale", "0.5"], 0.5)],
    ids=["default", "half"],
)
def test_entry_scale_reaches_the_runner(replay, argv, scale):
    rc, _, (runner,) = replay(argv)
    assert rc == 0
    assert runner.scale == scale
    assert runner.trace.name == "preemption-storm"


# -- e2e: one real scenario replay -------------------------------------------


@pytest.mark.e2e
@pytest.mark.chaos
@pytest.mark.slow
def test_preemption_storm_scenario_end_to_end(tmp_path, monkeypatch):
    """Replays the preemption-storm trace (scaled down) against a real
    ProcessBackend fleet: exact versions at every probe, zero dropped
    tasks, goodput gap explained by the recompute counter, and
    retention vs the fault-free baseline twin reported."""
    from elasticdl_tpu.chaos.scenario import ScenarioRunner

    monkeypatch.setenv("EDL_FLIGHT_DIR", str(tmp_path / "flight"))
    trace = load_trace("preemption-storm")
    report = ScenarioRunner(
        trace, scale=0.5, run_dir=str(tmp_path / "run")
    ).run()
    main = report["jobs"]["main"]
    assert main["versions"] == [main["expected_version"]]
    assert main["exactness_probes"] >= 1
    assert main["relaunches"] >= 1
    assert report["retention"] is not None
    kills = [e for e in report["events"] if e["action"] == "kill"]
    assert len(kills) == 3
    g = main["goodput"]
    if g["gap_explained"] is not None:
        assert abs(g["gap_explained"] - 1.0) <= 0.01
