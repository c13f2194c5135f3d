"""The arithmetic of a run, piece by piece, with no job started."""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import data, job as job_lib  # noqa: E402


WINDOW45 = {"wall0": 1000.0, "wall1": 1045.0}


def updates(times, per=8, start=16):
    """`train/loss` lines as the master's sink writes them: one for each
    update applied, `step` the version it produced."""
    return [{"tag": "train/loss", "ts": 1000.0 + t, "value": 1.0,
             "step": start + per * i} for i, t in enumerate(times)]


def every(period, until, first=-0.1):
    times, t = [], first
    while t <= until:
        times.append(t)
        t += period
    return times


def test_goodput_runs_from_the_update_that_ended_setup_to_the_last_in_the_window():
    applied = updates(every(5.0, 60.0))  # -0.1, 4.9 .. 44.9, then outside
    applied.append({"tag": "eval/acc", "ts": 1003.0, "value": 1.0, "step": 9})
    records, seconds, faults, stall = bench_run.goodput_span(applied, WINDOW45, 2)
    assert (records, seconds, faults, stall) == (
        9 * 8 * 2, pytest.approx(45.0), [], "")


def test_updates_before_the_one_that_ended_setup_are_not_counted():
    applied = updates(every(5.0, 44.0, first=-10.1))
    records, seconds, faults, _ = bench_run.goodput_span(applied, WINDOW45, 2)
    assert (records, seconds, faults) == (8 * 8 * 2, pytest.approx(40.0), [])


def test_goodput_subtracts_what_was_trained_twice():
    applied = updates(every(5.0, 44.95))
    assert bench_run.goodput_span(applied, WINDOW45, 2, recomputed=32)[0] == (
        9 * 8 * 2 - 32
    )


@pytest.mark.parametrize(
    "stop,share",
    [(44.95, 1.0), (44.0, 1.0), (40.0, 1.0), (38.0, 7 / 9), (22.5, 4 / 9),
     (12.0, 2 / 9)],
)
def test_a_job_that_stops_before_the_window_ends_does_not_keep_its_rate(
    stop, share
):
    # a worker that hangs at `stop`: records and span would shrink
    # together and the rate not move, so the span runs on to the
    # window's end — updates that stop halfway halve the rate
    steady = 8 * 2 / 5.0
    records, seconds, faults, stall = bench_run.goodput_span(
        updates(every(5.0, stop)), WINDOW45, 2
    )
    assert faults == [] and bool(stall) == (share < 1.0)
    assert records / seconds == pytest.approx(steady * share, rel=0.01)


def test_a_job_that_stalls_as_the_window_opens_faults_too():
    applied = updates([-0.1] + [10.0 + t for t in every(5.0, 34.9, first=0.0)])
    _records, seconds, faults, stall = bench_run.goodput_span(
        applied, WINDOW45, 2
    )
    # one long gap inside the span is in the rate already
    assert (seconds, faults, stall) == (pytest.approx(40.1), [], "")
    late = updates([-9.0] + every(5.0, 44.9, first=1.0))
    faults = bench_run.goodput_span(late, WINDOW45, 2)[2]
    assert len(faults) == 1 and "window's start" in faults[0]


def test_a_slow_second_half_lowers_the_rate_and_the_halves_show_it():
    times = every(1.0, 22.0) + [21.9 + 2.0 * i for i in range(1, 12)]
    applied = updates(times)
    records, seconds, faults, stall = bench_run.goodput_span(
        applied, WINDOW45, 256
    )
    assert faults == [] and not stall
    steady = bench_run.goodput_span(updates(every(1.0, 44.95)), WINDOW45, 256)
    assert records / seconds < 0.8 * steady[0] / steady[1]
    assert bench_run.half_rates(applied, WINDOW45, 256) == (
        "halves 2048.00 and 1024.00 records/s"
    )


@pytest.mark.parametrize("n", [0, 1, 2])
def test_a_window_with_too_few_updates_gives_no_rate(n):
    with pytest.raises(bench_run.BenchFailure, match="none"):
        bench_run.goodput_span(updates(every(20.0, 20.0 * n - 1)), WINDOW45, 2)


def events(values, t0=100.0, dt=0.5):
    return [{"tag": "train/loss", "value": v, "step": i, "ts": t0 + i * dt}
            for i, v in enumerate(values)]


WINDOW = {"wall0": 100.0, "wall1": 110.0}


@pytest.mark.parametrize(
    "values,most,word",
    [
        ([5.0, 4.0, 3.0, 2.5, 2.0, 1.5, 1.2, 1.0, 0.9, 0.8], 0.9, None),
        ([5.0, 4.9, 4.9, 4.8, 4.8, 4.8, 4.7, 4.7, 4.7, 4.6], 0.9, "at most x0.9"),
        ([0.5] * 10, None, None),  # no ratio stated: held to 1
        ([1.0, 1.0, 1.0, 1.0, 1.0, 1.1, 1.1, 1.1, 1.1, 1.1], None, "at most x1.0"),
        ([11.0, 11.4, 10.9, 11.6, 11.2, 11.1, 11.3, 11.0, 11.5, 11.8], 0.9,
         "at most x0.9"),  # hovering at chance is not learning
        ([5.0, 4.0, float("nan"), 2.5, 2.0, 1.5, 1.2, 1.0, 0.9, 0.8], 0.9,
         "finite"),
        ([5.0, 4.0], 0.9, "only 2"),
        # nine updates, the last one a spike: three are read at each end
        ([6.0, 4.5, 3.0, 2.0, 1.0, 0.5, 0.2, 0.1, 4.9], 0.8, None),
        ([6.0, 4.5, 3.0, 2.0, 1.0, 0.5, 5.5, 0.1, 4.9], 0.8, "at most x0.8"),
    ],
)
def test_the_loss_check(values, most, word):
    sizes = {"loss_check": {"last_over_first_at_most": most}} if most else {}
    faults, _note = bench_run.check_losses(events(values), WINDOW, sizes)
    if word is None:
        assert faults == []
    else:
        assert any(word in f for f in faults), faults


UNTRAINED = {"untrained_loss": 10.83, "last_over_first_at_most": 0.75}


@pytest.mark.parametrize(
    "values,word",
    [
        # the fall comes early, late, or after the window: all learned
        ([6.0, 4.5, 3.0, 2.0, 1.0, 0.5, 0.2, 0.1, 0.1], None),
        ([7.0, 7.1, 6.9, 6.8, 6.9, 6.6, 6.3, 5.8, 4.9], None),
        ([7.7, 7.6, 7.8, 7.5, 7.7, 7.6, 7.9, 7.6, 7.7], None),
        # single updates that spike do not decide it
        ([6.0, 4.5, 3.0, 2.0, 1.0, 0.5, 9.5, 0.1, 8.9], None),
        # never learned which ids occur; diverged; learned, then lost it
        ([10.8, 10.9, 10.7, 10.8, 10.8, 10.9, 10.7, 10.8, 10.8],
         "at most x0.75"),
        ([7.7, 7.6, 9.0, 11.0, 13.5, 13.0, 13.6, 13.5, 13.4],
         "at most x0.75"),
        ([6.0, 5.5, 0.2, 0.1, 9.8, 9.0, 9.5, 9.1, 9.2], "at most x0.75"),
        ([6.0, 6.1, 5.9, 6.2, 5.8, 6.0, float("inf"), 0.1, 0.1], "finite"),
    ],
)
def test_a_loss_held_to_the_untrained_models(values, word):
    faults, note = bench_run.check_losses(
        events(values), WINDOW, {"loss_check": UNTRAINED}
    )
    assert "untrained" in note
    assert (faults == []) if word is None else any(word in f for f in faults)


class _BusyMaster:
    def __init__(self, answers):
        self.answers = list(answers)

    def stats(self):
        return self.answers.pop(0) if len(self.answers) > 1 else self.answers[0]


@pytest.mark.parametrize(
    "answers,patience,got",
    [([None, None, {"v": 1}], 5.0, {"v": 1}), ([{"v": 2}], 5.0, {"v": 2}),
     ([None], 0.3, None)],
)
def test_the_last_poll_waits_for_a_busy_master_but_not_for_ever(
    answers, patience, got
):
    assert bench_run.final_stats(_BusyMaster(answers), patience) == got


def test_losses_outside_the_window_are_not_read():
    early = events([float("nan")] * 3, t0=10.0)
    good = events([5.0, 4.0, 3.0, 2.0, 1.0])
    assert bench_run.check_losses(early + good, WINDOW, {})[0] == []
    assert bench_run.update_gaps_ms(early + good, WINDOW) == [500.0] * 4


MASTER_LOG = """\
2026-09-27 01:00:00,100 INFO [MainProcess] pod_backend:183 : Started worker 0 (pid 4242) on chips [0]
2026-09-27 01:00:31,000 INFO [MainProcess] pod_backend:183 : Started worker 1 (pid 4343) on chips [0]
2026-09-27 01:00:40,000 ERROR [MainProcess] task_dispatcher:372 : Task 9 failed 3 times, dropping (poison task)
"""
WORKER_LOG = """\
2026-09-27 01:00:12,250 INFO [MainProcess] main:151 : Worker 0 boot: platform=tpu device_kind=TPU v5 lite chips=[0]
2026-09-27 01:00:20,500 INFO [MainProcess] worker:2197 : Worker 0 task 1 done (last loss 6.9000, v8) [compute=1s]
2026-09-27 01:00:21,750 INFO [MainProcess] worker:2197 : Worker 0 task 2 done (last loss 6.5000, v16) [compute=2s]
"""


def test_the_job_driver_reads_the_programs_log_lines(tmp_path):
    j = job_lib.Job.__new__(job_lib.Job)
    j.log_dir = str(tmp_path / "logs")
    j.master_log = str(tmp_path / "master.log")
    os.makedirs(j.log_dir)
    (tmp_path / "master.log").write_text(MASTER_LOG)
    (tmp_path / "logs" / "worker-0.log").write_text(WORKER_LOG)
    logs = j.worker_logs()
    boot = logs[0]["boot"]
    assert (boot["platform"], boot["device_kind"], boot["chips"]) == (
        "tpu", "TPU v5 lite", [0],
    )
    assert logs[0]["done"][1] - logs[0]["done"][0] == pytest.approx(1.25)
    assert logs[0]["done"][0] - boot["at"] == pytest.approx(8.25)
    assert j.dropped_tasks() == 1


RESOLVED = {
    "config_dir": os.path.join(ROOT, "benchmark", "configs", "x"),
    "sizes": {"minibatch_per_chip": 256},
    "mix": {"workers": 1, "master_flags": {"local_updates": 8}},
}


@pytest.mark.parametrize(
    "sizes,mix,per_task,setup_tasks",
    [({}, {}, 4096, 2), ({"records_per_task": 32}, {}, 32, 2),
     ({}, {"setup_tasks": 1}, 4096, 1)],
)
def test_the_job_runs_at_the_programs_defaults_unless_a_file_says_otherwise(
    tmp_path, sizes, mix, per_task, setup_tasks
):
    resolved = {**RESOLVED, "sizes": {**RESOLVED["sizes"], **sizes},
                "mix": {**RESOLVED["mix"], **mix}}
    j = job_lib.Job(ROOT, str(tmp_path), resolved, "/data")
    assert (j.per_task, j.setup_tasks) == (per_task, setup_tasks)
    flag = "--records_per_task"
    assert (flag in j.argv) == bool(sizes)
    if sizes:
        assert j.argv[j.argv.index(flag) + 1] == "32"
    assert j.argv[j.argv.index("--local_updates") + 1] == "8"


def test_the_sink_is_tailed_line_by_line(tmp_path):
    j = job_lib.Job.__new__(job_lib.Job)
    j.events_file = str(tmp_path / "events.jsonl")
    j._events_pos, j._read_before, j.events = 0, None, []
    assert j.new_events() == []
    line = json.dumps({"tag": "train/loss", "value": 1.0, "step": 1, "ts": 5.0})
    with open(j.events_file, "w") as f:
        f.write(line + "\n" + line[:20])  # the second is half written
    first_read = time.time()
    assert len(j.new_events()) == 1
    with open(j.events_file, "a") as f:
        f.write(line[20:] + "\n")
    assert len(j.new_events()) == 1 and len(j.events) == 2
    # each line knows the polls it was read between, by this clock
    (lo1, hi1), (lo2, hi2) = (e["seen"] for e in j.events)
    assert lo1 <= first_read <= hi1 and hi1 >= lo2 >= first_read and hi2 >= lo2


@pytest.mark.parametrize(
    "ts,seen,ok",
    [(100.05, (100.0, 100.1), True), (100.3, (100.0, 100.1), True),
     (100.5, (100.0, 100.1), False), (99.5, (100.0, 103.0), False),
     (102.9, (100.0, 103.0), True), (50.0, (None, 100.0), True),
     (100.4, (None, 100.0), False)],
)
def test_an_update_stamped_outside_the_polls_it_was_read_between_faults(
    ts, seen, ok
):
    line = {"tag": "train/loss", "value": 1.0, "step": 8, "ts": ts,
            "seen": seen}
    other = {"tag": "eval/acc", "value": 1.0, "step": 8, "ts": 0.0,
             "seen": (100.0, 100.1)}
    faults = bench_run.clock_faults([line, other])
    assert (faults == []) == ok


@pytest.mark.parametrize(
    "spec",
    [
        {"kind": "image", "shape": [8, 8, 3], "classes": 1000, "records": 40,
         "shards": 3},
        {"kind": "tokens", "seq_len": 16, "alphabet": 512, "records": 12},
    ],
)
def test_data_is_a_function_of_the_seed_and_learnable(tmp_path, spec):
    from elasticdl_tpu.data.recordio import RecordIOReader

    seed = 2**31 + 11  # the driver's seeds pass 32 signed bits
    sizes = {"data": spec}
    first = data.ensure(str(tmp_path / "a"), sizes, "", seed)
    again = data.ensure(str(tmp_path / "b"), sizes, "", seed)
    other = data.ensure(str(tmp_path / "a"), sizes, "", seed + 1)
    read = lambda d: open(os.path.join(d, "train.rio"), "rb").read()  # noqa: E731
    assert read(first) == read(again) != read(other)
    assert data.ensure(str(tmp_path / "a"), sizes, "", seed) == first  # reused
    with RecordIOReader(os.path.join(first, "train.rio")) as r:
        records = list(r.read_range(0, spec["records"]))
    assert len(records) == spec["records"]
    if spec["kind"] == "image":
        labels = [int(np.frombuffer(x, np.int64, 1)[0]) for x in records]
        means = [np.frombuffer(x, np.uint8, offset=8).mean() for x in records]
        assert 0 <= min(labels) and max(labels) < 1000
        assert np.corrcoef(labels, means)[0, 1] > 0.9  # the mean tells the class
    else:
        toks = np.stack([np.frombuffer(x, np.int32) for x in records])
        strides = (toks[:, 1:] - toks[:, :-1]) % spec["alphabet"]
        assert (strides == strides[:, :1]).all() and set(strides[:, 0]) <= {1, 2, 3}
        assert 0 <= toks.min() and toks.max() < spec["alphabet"]
    files = sorted(os.listdir(first))
    assert len(files) == spec.get("shards", 1) and files[-1] == "train.rio"
    assert all(  # the further shards are links to the one written
        os.path.samefile(os.path.join(first, f), os.path.join(first, "train.rio"))
        for f in files
    )


def test_without_a_tpu_the_command_prints_no_result(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "resnet50-224.window-1w", "--seed", "1",
                        "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
