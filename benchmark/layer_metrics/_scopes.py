"""Shared by the scope readers (`loop_stack_pct`, `loop_attention_pct`,
`exit_head_pct`): the share of the probe's traced slice's device-busy
time spent in operations traced under a `jax.named_scope`.

The v5e's device trace names an operation by its HLO instruction's
text and carries no scope (looked at on the chip, PR 27), so the join
is on the instruction's name: a worker asked to (`EDL_HLO_SCOPES=1`,
which the configuration's `zoo.py` sets in a traced run) writes
`<run>/logs/worker-<id>.hlo_scopes.json`, every instruction of the
window program with its `op_name` path
(`elasticdl_tpu/obs/hlo_scopes.py`). Which scopes a share looks for is
decided here alone (`SHARES`, `_passes`). The run's directory is found
as `_timeline.py` finds it, by the probe's latch.

Only leaf operations are summed: a `while` and the operations of its
body both appear on the `XLA Ops` line, one inside the other, and the
window program is a `while` over steps around `while`s over passes and
layers. A leaf is an event no other event of its line lies inside.
Busy time is the union of the line's events inside the slice, the
denominator `device_idle_pct` has.

No map under the run's directory (a program that writes none: a parent
commit, another configuration), no trace, or a run off the TPU: every
share is None and the metric is left out of the line.
"""

import glob
import json
import os
import re
import sys

from benchmark.harness import trace_reduce
from benchmark.layer_metrics import _timeline

# share name -> the scopes an operation's path must pass through, all
SHARES = {
    "stack": ("looped_stack",),
    "attention": ("looped_stack", "attention"),
    "exit_heads": ("exit_heads",),
}
_cache = {}


def say(msg):
    print(f"scopes: {msg}", file=sys.stderr, flush=True)


def instruction(event_name):
    """`%fusion.1 = bf16[...] fusion(...)` -> `fusion.1`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def leaves(events):
    """The events of one line that hold no other: [(name, start, end)].
    Events nest properly (an operation runs inside its `while`)."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    return [
        e for e, after in zip(ordered, ordered[1:] + [None])
        if after is None or after[1] >= e[2] or e[2] <= e[1]
    ]


def _passes(path, scopes):
    """Whether an `op_name` path runs through every scope of `scopes`:
    `jit(window)/while/body/jvp(looped_stack)/.../attention/dot_general`
    does through `looped_stack` and `attention` (a scope shows bare,
    or inside `jvp(...)`, `transpose(jvp(...))` and the like)."""
    parts = set(re.split(r"[/()]", path))
    return all(scope in parts for scope in scopes)


def plane_shares(lines, instructions, lo, hi, line_prefixes):
    """({share: seconds}, busy seconds) of one device plane's lines
    inside [lo, hi) ns."""
    events = [
        e for line, evs in lines if line.startswith(line_prefixes) for e in evs
    ]
    busy = sum(
        e - s for s, e in trace_reduce.union(
            (max(s, lo), min(e, hi)) for _n, s, e in events
        )
    ) / 1e9
    seconds = dict.fromkeys(SHARES, 0.0)
    member = {}  # instruction -> the shares its path passes through
    for name, start, end in leaves(events):
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        name = instruction(name)
        if name not in member:
            path = instructions.get(name)
            member[name] = () if path is None else tuple(
                share for share, scopes in SHARES.items()
                if _passes(path, scopes)
            )
        for share in member[name]:
            seconds[share] += (end - start) / 1e9
    return seconds, busy


def shares(run, reader_file):
    """{share: % of the slice's device-busy time} or None."""
    if run["platform"] != "tpu" or not run["trace"]:
        return None
    key = run["window"]["wall0"]
    if key in _cache:
        return _cache[key]
    _cache.clear()
    _cache[key] = None
    run_dir = _timeline.find_run_dir(run, reader_file)
    maps = {}
    for path in glob.glob(os.path.join(run_dir, "logs", "worker-*.hlo_scopes.json")):
        with open(path) as f:
            maps[int(re.search(r"worker-(\d+)\.", path).group(1))] = json.load(f)
    if not maps:
        say(f"no worker-*.hlo_scopes.json under {run_dir}/logs: the program "
            "under test maps no instruction to a scope; nothing to read")
        return None
    _plane_prefix, line_prefixes = trace_reduce.SELECTORS[run["platform"]]
    seconds, busy = dict.fromkeys(SHARES, 0.0), 0.0
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
            if not plane.startswith(_plane_prefix):
                continue
            by, plane_busy = plane_shares(
                lines, scope_map["instructions"], lo, hi, line_prefixes
            )
            busy += plane_busy
            for share, value in by.items():
                seconds[share] += value
            say(f"{plane} of worker {record['worker_id']}: busy "
                f"{plane_busy:.4f}s of {(hi - lo) / 1e9:.4f}s; leaf operations "
                + ", ".join(f"under {'/'.join(SHARES[k])} {v:.4f}s"
                            for k, v in by.items())
                + f" ({len(scope_map['instructions'])} instructions of "
                f"{scope_map['program']} named)")
    if busy <= 0:
        return None
    _cache[key] = {k: 100.0 * v / busy for k, v in seconds.items()}
    return _cache[key]


def read(run, reader_file, share):
    found = shares(run, reader_file)
    return None if found is None else found[share]
