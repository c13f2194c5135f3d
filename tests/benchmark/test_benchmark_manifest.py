"""The committed manifest keeps the contract's rules, and a new cell is
new files and new entries only."""

import copy
import filecmp
import json
import os
import sys

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

import bench_sandbox  # noqa: E402

from benchmark.harness import manifest as manifest_lib  # noqa: E402


@pytest.fixture(scope="module")
def manifest():
    return manifest_lib.load(ROOT)


def test_the_committed_manifest_has_no_fault(manifest):
    assert manifest_lib.lint(manifest, ROOT) == []


def test_the_file_is_small_and_the_budget_of_a_full_check_fits(manifest):
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # 2 + 14 x 24 runs of run_seconds + 60 s, 24 x 180 s to compile, 1200 spare
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_cell_resolves_to_files_that_exist(manifest):
    for cell in manifest["workloads"]:
        resolved = manifest_lib.resolve(manifest, cell["name"], ROOT)
        assert os.path.isfile(os.path.join(resolved["config_dir"], "zoo.py"))
        assert resolved["sizes"]["minibatch_per_chip"] >= 1
        assert resolved["mix"]["workers"] * 1 == cell["chips"]
        for name in manifest_lib.cell_metrics(manifest, cell["name"], "per_layer"):
            assert os.path.isfile(manifest_lib.reader_file(name, ROOT))


def test_every_configuration_has_a_cell_and_at_most_a_quarter_take_four_chips(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_moves_names_an_end_to_end_metric_each_of_its_cells_reports(manifest):
    for metric in manifest["per_layer"]:
        cells = metric.get(
            "workloads", [w["name"] for w in manifest["workloads"]]
        )
        for cell in cells:
            ends = manifest_lib.cell_metrics(manifest, cell, "end_to_end")
            assert metric["moves"] in ends, (metric["name"], cell)


def mutate(manifest, fn):
    out = copy.deepcopy(manifest)
    fn(out)
    return out


@pytest.mark.parametrize(
    "change,word",
    [
        (lambda m: m.update(extra=1), "keys"),
        (lambda m: m["workloads"][0].update(name="has space"), "allowed"),
        (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
        (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
        (lambda m: m["end_to_end"][0].update(why="because"), "keys"),
        (lambda m: m["end_to_end"][0].update(source="program_counter"), "reads from the program"),
        (lambda m: m["per_layer"][0].update(moves="nothing"), "moves unknown"),
        (lambda m: (m["end_to_end"].append({
            "name": "one_cell_only", "unit": "ms", "better": "lower",
            "bound": 0.05, "source": "host_clock",
            "workloads": [m["workloads"][-1]["name"]]}),
            m["per_layer"][0].update(moves="one_cell_only")), "does not report"),
        (lambda m: m["per_layer"][0].update(name="no_such_reader"), "layer_metrics"),
        (lambda m: m["workloads"][0].update(traffic="no-such-mix"), "traffic"),
        (lambda m: m["workloads"][0].update(chips=2), "chips"),
        (lambda m: [w.update(chips=4) for w in m["workloads"]], "ask for 4"),
        (lambda m: m["workloads"].append(dict(m["workloads"][0], name="twin")), "twice"),
        (lambda m: m["configs"].append(dict(m["configs"][0], name="idle", file="benchmark/configs/idle/config.json")), "does not exist"),
        (lambda m: m["configs"][0].update(file="elasticdl_tpu/x.json"), "under paths"),
        (lambda m: m.update(run_seconds=52), "run_seconds"),
        (lambda m: m.update(command=["python3", "../run.py"]), "out of the repo"),
        (lambda m: m["end_to_end"].pop(), "setup_s"),
        (lambda m: m["workloads"][0].update(why="x" * 201), "why"),
    ],
)
def test_lint_names_each_breach(manifest, change, word):
    faults = manifest_lib.lint(mutate(manifest, change), ROOT)
    assert faults and any(word in f for f in faults), faults


def test_a_new_cell_is_new_files_and_new_entries_only(tmp_path, manifest):
    root = bench_sandbox.copy_benchmark(tmp_path)
    cell = bench_sandbox.add_tiny_cell(root)
    grown = manifest_lib.load(root)
    assert manifest_lib.lint(grown, root) == []
    resolved = manifest_lib.resolve(grown, cell, root)
    assert resolved["sizes"]["hidden_size"] == 32
    assert resolved["mix"]["master_flags"]["local_updates"] == 2
    assert "tiny_tasks" in manifest_lib.cell_metrics(grown, cell, "per_layer")
    assert "tiny_tasks" not in manifest_lib.cell_metrics(
        grown, manifest["workloads"][0]["name"], "per_layer"
    )
    # every file the benchmark had is byte for byte what it was
    for folder, _dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in folder:
            continue
        for name in files:
            if name.endswith(".pyc"):
                continue
            old = os.path.join(folder, name)
            new = os.path.join(root, os.path.relpath(old, ROOT))
            assert filecmp.cmp(old, new, shallow=False), old
    # and the entries it had are the entries it has
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert grown[key][: len(manifest[key])] == manifest[key]


def test_the_reader_of_a_new_metric_is_found_by_name(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from benchmark import run as bench_run

    root = bench_sandbox.copy_benchmark(tmp_path)
    bench_sandbox.add_tiny_cell(root)
    read = bench_run.load_reader("tiny_tasks", root)
    run = {"snaps": [{"completed": 8}, {"completed": 40}]}
    assert read(run) == 32.0


def test_config_files_state_their_source_and_departures():
    for config in ("resnet50-224", "lm-dense-160m"):
        with open(os.path.join(ROOT, "benchmark", "configs", config, "config.json")) as f:
            sizes = json.load(f)
        assert sizes["source"] and sizes["assumed"]
        assert sizes["minibatch_per_chip"] & (sizes["minibatch_per_chip"] - 1) == 0
