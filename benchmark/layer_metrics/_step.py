"""Shared by the readers of the step's own time (`step_forward_pct`,
`step_recompute_pct`, `step_backward_pct`, `step_optimizer_pct`,
`step_unnamed_pct`, `lm_attention_pct`, `lm_mlp_pct`, `lm_head_pct`,
`resnet_conv_pct`, `resnet_norm_pct`, `program_temp_gb`): every moment
of the probe's traced slice in which the device ran is charged to one
phase of the step and, where its path names one, to a block of the
model, from what the compiled programs say of themselves.

The worker writes `<run>/logs/worker-<id>.hlo_scopes.json` for every
jitted program of its training path (`elasticdl_tpu/obs/hlo_scopes.py`:
`programs: {<program>: {"instructions": {name: op_name}, "memory",
"stale"}}`, and `program`, the one that trains: the window, or the step
in per-step mode). The trace names an operation by its instruction
alone and instruction names repeat across programs (`fusion.1` of
`jit_window` is not `fusion.1` of `jit_subtract`), so an operation
belongs to the event of the `XLA Modules` line it lies inside, and the
join is on (program, instruction). `_scopes.py`'s `instruction` and
`_passes` and `trace_reduce`'s lines are used as they are.

A moment belongs to the innermost operation running then: a leaf
(`_scopes.leaves`) is charged whole, and a `while` the time between
its body's operations, so the phases sum to the busy time
`device_idle_pct` has, the union of the line's events. An operation of
the program that trains is in exactly one phase, by its `op_name` as
jax 0.9.0 writes it (a fusion carries its root's):

- under `optimizer`                                     -> optimizer
- `.../transpose(jvp(...))/.../rematted_computation/...` -> recompute
- any other `transpose(jvp(...))`                        -> backward
- `jvp(...)` without `transpose`                         -> forward
- no `op_name`, or none of these                         -> unnamed

and an operation of any other program (`jit_copy`, `jit_subtract`, a
`jit_step` beside the window) is `other`. The blocks are read from the
same paths, whatever the phase (`BLOCKS`).

Off the TPU or untraced: None, and the metric is left out. In a traced
run on the TPU every reader gives a number, because `run.py` refuses a
traced line that lacks one of its cell's metrics (`validate.
check_line`, exit 4) and the driver runs the parent commit under these
files: where there is nothing to read — no map (a parent commit), a map
without `memory` (one written by `hlo_scopes.write`), or a map marked
`stale` (the compile cache served an executable compiled from other
source: its names are not this program's) — the reader gives 0, as
`_timeline.py`'s do for a program without a timeline, and the `step:`
line says that nothing was read and why. A 0 in every share of a cell
is that, never a measurement: the phases of a program that ran sum to
100 less `other`.
"""

import bisect
import glob
import json
import os
import re
import sys

from benchmark.harness import trace_reduce
from benchmark.layer_metrics import _scopes, _timeline

PHASES = ("forward", "recompute", "backward", "optimizer", "unnamed", "other")
# block -> the pattern a whole word of the path matches
BLOCKS = {
    "attention": re.compile(r"attention$"),
    "mlp": re.compile(r"mlp$"),
    "head": re.compile(r"(embed|head)$"),
    "conv": re.compile(r"Conv_\d+$"),
    "norm": re.compile(r"BatchNorm_\d+$"),
}
_cache = {}


def say(msg):
    print(f"step: {msg}", file=sys.stderr, flush=True)


def phase(path):
    """The phase of an operation of the training program whose
    `op_name` is `path` (None: the map has none for it)."""
    if path is None:
        return "unnamed"
    if _scopes._passes(path, ("optimizer",)):
        return "optimizer"
    if "transpose(jvp(" in path:
        if _scopes._passes(path, ("rematted_computation",)):
            return "recompute"
        return "backward"
    return "forward" if "jvp(" in path else "unnamed"


def blocks(path):
    """The blocks `path` names: a scope is a whole word of it."""
    if path is None:
        return ()
    words = [w for w in re.split(r"[/()]", path) if w]
    return tuple(
        block for block, pattern in BLOCKS.items()
        if any(pattern.match(w) for w in words)
    )


def exclusive(events):
    """{(name, start, end): ns in which it was the innermost event
    running} of one device's operations, which nest (an operation
    runs inside its `while`). The values sum to the union."""
    own = {}
    stack, cursor = [], 0
    for event in sorted(events, key=lambda e: (e[1], -e[2])):
        start = event[1]
        while stack and stack[-1][2] <= start:
            done = stack.pop()
            if done[2] > cursor:
                own[done] = own.get(done, 0) + done[2] - cursor
                cursor = done[2]
        if stack and start > cursor:
            own[stack[-1]] = own.get(stack[-1], 0) + start - cursor
        cursor = max(cursor, start)
        stack.append(event)
    while stack:
        done = stack.pop()
        if done[2] > cursor:
            own[done] = own.get(done, 0) + done[2] - cursor
            cursor = done[2]
    return own


def program_of(modules):
    """-> f(ns) = the program whose run holds that moment (None:
    none does), from the `XLA Modules` line's events."""
    runs = sorted((s, e, name.split("(", 1)[0]) for name, s, e in modules)
    starts = [s for s, _e, _n in runs]

    def at(t):
        i = bisect.bisect_right(starts, t) - 1
        return runs[i][2] if i >= 0 and t < runs[i][1] else None

    return at


def plane_seconds(lines, scope_map, lo, hi, line_prefixes):
    """One device plane inside [lo, hi) ns -> {"busy", "phases",
    "blocks", "programs": {program: s}, "unnamed": {instruction: s}},
    seconds each."""
    trains = scope_map["program"]
    # a map of one program alone (`hlo_scopes.write`) is that program's
    instructions = (scope_map.get("programs") or {}).get(
        trains, scope_map
    )["instructions"]
    at = program_of(
        e for line, evs in lines if line == trace_reduce.MODULES_LINE
        for e in evs
    )
    events = [
        (name, max(s, lo), min(e, hi))
        for line, evs in lines if line.startswith(line_prefixes)
        for name, s, e in evs if min(e, hi) > max(s, lo)
    ]
    found = {
        "busy": 0.0, "phases": dict.fromkeys(PHASES, 0.0),
        "blocks": dict.fromkeys(BLOCKS, 0.0), "programs": {}, "unnamed": {},
    }
    member = {}  # instruction of the program that trains -> (phase, blocks)
    for (name, start, _end), ns in exclusive(events).items():
        seconds = ns / 1e9
        found["busy"] += seconds
        program = at(start)
        name = _scopes.instruction(name)
        seen = program or "no program"
        found["programs"][seen] = found["programs"].get(seen, 0.0) + seconds
        if program != trains:
            found["phases"]["other"] += seconds
            continue
        if name not in member:
            path = instructions.get(name)
            member[name] = (phase(path), blocks(path))
        in_phase, in_blocks = member[name]
        found["phases"][in_phase] += seconds
        for block in in_blocks:
            found["blocks"][block] += seconds
        if in_phase == "unnamed":
            found["unnamed"][name] = found["unnamed"].get(name, 0.0) + seconds
    return found


def _add(total, part):
    for key, value in part.items():
        if isinstance(value, dict):
            _add(total.setdefault(key, {}), value)
        else:
            total[key] = total.get(key, 0.0) + value


def load_maps(run_dir):
    """{worker id: its map}; {} where there is none or one is stale,
    and says why nothing will be read."""
    maps = {}
    for path in glob.glob(os.path.join(run_dir, "logs", "worker-*.hlo_scopes.json")):
        with open(path) as f:
            maps[int(re.search(r"worker-(\d+)\.", path).group(1))] = json.load(f)
    if not maps:
        say(f"no worker-*.hlo_scopes.json under {run_dir}/logs: the program "
            "under test maps no instruction to a scope; nothing to read")
    for wid, scope_map in maps.items():
        trains = scope_map["program"]
        record = (scope_map.get("programs") or {}).get(trains, {})
        if record.get("stale"):
            say(f"worker {wid}: the map of {trains} is stale (its executable "
                f"lacks {record.get('missing')}, which this source names: "
                "the compile cache served one compiled from other source); "
                "nothing is read from it")
            return {}
    return maps


def nothing():
    """What `read` gives where there is nothing to read: every share
    and the temporaries read 0."""
    return {
        "busy": 0.0, "phases": dict.fromkeys(PHASES, 0.0),
        "blocks": dict.fromkeys(BLOCKS, 0.0), "programs": {}, "unnamed": {},
        "temp_bytes": None,
    }


def read(run, reader_file):
    """{"busy", "phases", "blocks", "programs", "unnamed", "temp_bytes"}
    summed over the run's traced workers (`temp_bytes`: the most of
    any worker's training program; None: no map states it); `nothing()`
    where there is nothing to read; None off the TPU or untraced."""
    if run["platform"] != "tpu" or not run["trace"]:
        return None
    key = run["window"]["wall0"]
    if key in _cache:
        return _cache[key]
    _cache.clear()
    total = _cache[key] = nothing()
    run_dir = _timeline.find_run_dir(run, reader_file)
    maps = load_maps(run_dir)
    if not maps:
        say("nothing read: every metric of this reader reads 0")
        return total
    _plane_prefix, line_prefixes = trace_reduce.SELECTORS[run["platform"]]
    for scope_map in maps.values():
        memory = (scope_map.get("programs") or {}).get(
            scope_map["program"], {}
        ).get("memory") or {}
        if "temp" in memory:
            total["temp_bytes"] = max(total["temp_bytes"] or 0, memory["temp"])
    for path in sorted(glob.glob(os.path.join(run_dir, "probe", "*.json"))):
        with open(path) as f:
            record = json.load(f)
        info = record.get("trace") or {}
        scope_map = maps.get(record.get("worker_id"))
        if info.get("state") != "written" or scope_map is None:
            continue
        planes = trace_reduce.load(trace_reduce.find_xplane(info["dir"]))
        (lo, hi), _origin = _timeline._slice_and_origin(planes, info)
        for plane, lines in planes:
            if plane.startswith(_plane_prefix):
                _add(total, plane_seconds(
                    lines, scope_map, lo, hi, line_prefixes
                ))
    say(f"busy {total['busy']:.4f}s = " + " + ".join(
        f"{name} {total['phases'][name]:.4f}" for name in PHASES
    ) + "; blocks: " + ", ".join(
        f"{name} {seconds:.4f}" for name, seconds in total["blocks"].items()
    ))
    say("programs seen: " + ", ".join(
        f"{name} {seconds:.4f}s" for name, seconds in
        sorted(total["programs"].items(), key=lambda kv: -kv[1])
    ) + f"; {sorted({m['program'] for m in maps.values()})} trains, temp "
        + (f"{total['temp_bytes']} B" if total["temp_bytes"] is not None
           else "not in the map: `program_temp_gb` reads 0"))
    say("longest unnamed: " + ", ".join(
        f"{name} {seconds:.4f}s" for name, seconds in
        sorted(total["unnamed"].items(), key=lambda kv: -kv[1])[:10]
    ))
    return total


def _pct(found, table, name):
    if found is None:
        return None
    return 100.0 * found[table][name] / found["busy"] if found["busy"] else 0.0


def phase_pct(run, reader_file, name):
    return _pct(read(run, reader_file), "phases", name)


def block_pct(run, reader_file, name):
    return _pct(read(run, reader_file), "blocks", name)


def temp_gb(run, reader_file):
    found = read(run, reader_file)
    return None if found is None else (found["temp_bytes"] or 0) / 1e9
