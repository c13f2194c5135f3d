"""`BENCHMARK.json` read, checked, and resolved to a cell's files.

The harness holds no list of configurations, mixes or metrics: a cell
names them and `resolve` finds `configs/<config>/config.json` (with its
`zoo.py`), `traffic/<traffic>.json` and `layer_metrics/<metric>.py`
under the benchmark's directory. `lint` is the contract's rules for
the manifest, as far as they can be checked without the driver; the
tests run it on the committed file.
"""

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
BENCH_DIR = "benchmark"

KEYS = (
    "command", "paths", "run_seconds", "configs", "workloads",
    "end_to_end", "per_layer",
)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MAX_BOUND = 0.1


def load(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return (
        isinstance(text, str)
        and 1 <= len(text) <= 200
        and "\n" not in text
        and "\t" not in text
    )


def cell_metrics(manifest, workload, kind):
    """{name: entry} of the `kind` ("end_to_end" | "per_layer") metrics
    the cell reports: those with no `workloads` key, or that list it."""
    return {
        m["name"]: m
        for m in manifest[kind]
        if "workloads" not in m or workload in m["workloads"]
    }


def resolve(manifest, workload, root=ROOT):
    """The cell's entry and files: {"cell", "config", "config_dir",
    "sizes", "mix"} — raises KeyError / FileNotFoundError on a name
    the manifest or the tree does not have."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(
            f"no workload {workload!r} in BENCHMARK.json (has {sorted(cells)})"
        )
    cell = cells[workload]
    config = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(root, config["file"])) as f:
        sizes = json.load(f)
    mix_file = os.path.join(
        root, BENCH_DIR, "traffic", cell["traffic"] + ".json"
    )
    with open(mix_file) as f:
        mix = json.load(f)
    return {
        "cell": cell,
        "config": config,
        "config_dir": os.path.dirname(os.path.join(root, config["file"])),
        "sizes": sizes,
        "mix": mix,
    }


def load_module(path):
    """The Python file at `path` as a module of its own (a metric's
    reader, or arithmetic a configuration brings beside its sizes)."""
    name = "edlbench_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader_file(metric, root=ROOT):
    return os.path.join(root, BENCH_DIR, "layer_metrics", metric + ".py")


def lint(manifest, root=ROOT):
    """-> list of faults against the contract's rules for the file."""
    faults = []
    if sorted(manifest) != sorted(KEYS):
        return [f"keys are {sorted(manifest)}, want exactly {sorted(KEYS)}"]
    say = faults.append

    command, paths = manifest["command"], manifest["paths"]
    if not (1 <= len(command) <= 32 and all(_line(w) for w in command)):
        say("command is not 1 to 32 words of 1 to 200 characters")
    if not (1 <= len(paths) <= 16 and all(PATH.match(p) for p in paths)):
        say("paths is not 1 to 16 relative directories")
    for word in command + paths:
        if word.startswith("/") or ".." in word.split("/"):
            say(f"{word!r} leads out of the repo")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        say("run_seconds is not a whole number from 1 to 51")

    def named(entries, what, keys, optional=()):
        seen = set()
        for e in entries:
            extra = set(e) - set(keys) - set(optional)
            missing = set(keys) - set(e)
            if extra or missing:
                say(f"{what} {e.get('name')!r}: keys {sorted(e)}")
            name = e.get("name", "")
            if not NAME.match(name):
                say(f"{what} name {name!r} is outside the allowed characters")
            if name in seen:
                say(f"{what} name {name!r} appears twice")
            seen.add(name)
        return seen

    configs = named(
        manifest["configs"], "config",
        ("name", "source", "file", "reduced", "why"),
    )
    if not 1 <= len(manifest["configs"]) <= 24:
        say("configs is not 1 to 24 entries")
    files = set()
    for c in manifest["configs"]:
        if not (_line(c.get("source")) and _line(c.get("why"))):
            say(f"config {c['name']!r}: source or why is not one short line")
        f = c.get("file", "")
        if not PATH.match(f) or not any(
            f.startswith(p.rstrip("/") + "/") for p in paths
        ):
            say(f"config {c['name']!r}: file {f!r} is not under paths")
        elif not os.path.isfile(os.path.join(root, f)):
            say(f"config {c['name']!r}: file {f!r} does not exist")
        elif not os.path.isfile(
            os.path.join(root, os.path.dirname(f), "zoo.py")
        ):
            say(f"config {c['name']!r}: no zoo.py beside {f!r}")
        if f in files:
            say(f"config file {f!r} serves two configurations")
        files.add(f)
        reduced = c.get("reduced", [])
        if len(reduced) > 16 or not all(NAME.match(k) for k in reduced):
            say(f"config {c['name']!r}: reduced is not <= 16 names")

    cells = named(
        manifest["workloads"], "workload",
        ("name", "config", "traffic", "chips", "why"),
    )
    if not 1 <= len(manifest["workloads"]) <= 24:
        say("workloads is not 1 to 24 entries")
    pairs = set()
    for w in manifest["workloads"]:
        if w.get("config") not in configs:
            say(f"workload {w['name']!r}: unknown config {w.get('config')!r}")
        if not NAME.match(w.get("traffic", "")):
            say(f"workload {w['name']!r}: traffic name not allowed")
        elif not os.path.isfile(
            os.path.join(root, BENCH_DIR, "traffic", w["traffic"] + ".json")
        ):
            say(f"workload {w['name']!r}: no traffic/{w['traffic']}.json")
        if w.get("chips") not in (1, 4):
            say(f"workload {w['name']!r}: chips is not 1 or 4")
        if not _line(w.get("why")):
            say(f"workload {w['name']!r}: why is not one line of <= 200")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            say(f"the pair {pair} appears twice")
        pairs.add(pair)
    unused = configs - {w.get("config") for w in manifest["workloads"]}
    if unused:
        say(f"configurations without a cell: {sorted(unused)}")
    four = sum(1 for w in manifest["workloads"] if w.get("chips") == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        say(f"{four} of {len(manifest['workloads'])} cells ask for 4 chips")

    e2e = named(
        manifest["end_to_end"], "end-to-end metric",
        ("name", "unit", "better", "bound", "source"), ("workloads",),
    )
    layer = named(
        manifest["per_layer"], "per-layer metric",
        ("name", "unit", "better", "source", "layer", "moves"),
        ("workloads",),
    )
    if not 1 <= len(manifest["end_to_end"]) <= 16:
        say("end_to_end is not 1 to 16 metrics")
    if not 1 <= len(manifest["per_layer"]) <= 128:
        say("per_layer is not 1 to 128 metrics")
    if e2e & layer:
        say(f"metric names used twice: {sorted(e2e & layer)}")
    if "setup_s" not in e2e:
        say("no end-to-end metric setup_s")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(m.get("unit", "")):
            say(f"metric {m['name']!r}: unit {m.get('unit')!r} not allowed")
        if m.get("better") not in ("lower", "higher"):
            say(f"metric {m['name']!r}: better is not lower or higher")
        if m.get("source") not in SOURCES:
            say(f"metric {m['name']!r}: unknown source {m.get('source')!r}")
        for w in m.get("workloads", ()):
            if w not in cells:
                say(f"metric {m['name']!r} lists unknown workload {w!r}")
    for m in manifest["end_to_end"]:
        bound = m.get("bound")
        if not (isinstance(bound, (int, float)) and 0.01 <= bound <= MAX_BOUND):
            say(f"metric {m['name']!r}: bound {bound!r} not in [0.01, 0.1]")
        if m.get("source") not in ("host_clock", "device_trace"):
            say(f"end-to-end metric {m['name']!r} reads from the program")
    for m in manifest["per_layer"]:
        if not _line(m.get("layer")):
            say(f"metric {m['name']!r}: layer is not one short line")
        if not os.path.isfile(reader_file(m["name"], root)):
            say(f"metric {m['name']!r}: no layer_metrics/{m['name']}.py")
        moves = m.get("moves")
        if moves not in e2e:
            say(f"metric {m['name']!r} moves unknown metric {moves!r}")
            continue
        for w in m.get("workloads", sorted(cells)):
            if moves not in cell_metrics(manifest, w, "end_to_end"):
                say(f"metric {m['name']!r} moves {moves!r}, which cell "
                    f"{w!r} does not report")
    for w in sorted(cells):
        ends = cell_metrics(manifest, w, "end_to_end")
        if "setup_s" not in ends or len(ends) < 2:
            say(f"cell {w!r} lacks setup_s and one other end-to-end metric")
        if not cell_metrics(manifest, w, "per_layer"):
            say(f"cell {w!r} reports no per-layer metric")
    return faults
