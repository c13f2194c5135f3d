"""Shared by the readers of the `kimi-linear-48b-a3b` cell (`kda_pct`,
`kda_scan_pct`, `kda_scan_roofline_pct`, `hybrid_mla_pct`,
`hybrid_moe_pct`; `hybrid_expert_load_max_over_mean` is `_moe.py`'s
reading as it is).

The shares are `_moe.py`'s walk over the probe's traced slice, called
as it is with this cell's table of scopes in place of its own
(`trace_seconds`): leaf operations whose `op_name` passes through
`kda`, `kda` and `scan`, `mla`, or `moe`, as a share of the slice's
device-busy time, the compiler's grouped matmuls (`ragged-dot-*`, their
scope lost) by their name. What this file adds is the count of the
passes over the chunks and the roofline share made from it.

`kda_scan_roofline_pct` divides the work the recurrence needed in the
slice by the time of all leaf operations under `kda/scan` there. Work:
every pass over the chunks is a `while` whose `op_name` runs through
`kda`, `scan` and `state`; one under `transpose(...)` and not under
`rematted_computation` is a backward pass of one layer, credited two
forward passes of `flops.py`'s chunked form over the minibatch's tokens
(the recomputation of its chunks inside it is not credited); any other
is a forward pass (the first, or the layer's recomputation: counted
because it ran), credited one. A pass the slice's edge cuts
counts by the part inside. The roof is min(peak FLOP/s, HBM bytes/s x
the form's intensity), both of `harness/peaks.py`; `flops.py`'s bytes
are the recurrence's inputs and output once, the state never leaving
the chip, so the share stays under 100 whatever implements the scan.

No trace, no map, a run off the TPU, or a program without these scopes
(a parent commit): None, and the metric is left out.

For the next `benchmark` PR (only it may lengthen an accepted metric's
`workloads`): append this cell to `mla_attention_pct`,
`expert_load_max_over_mean`, `moe_experts_pct`, `moe_route_pct`,
`step_*_pct` and `program_temp_gb`, delete the three `hybrid_*` twins
and the `mla` and `experts` rows below, and give `_moe.py`'s walk its
table as an argument.
"""

import contextlib
import os
import re

from benchmark.harness import peaks
from benchmark.harness.manifest import load_module
from benchmark.layer_metrics import _moe, _scopes

# `_moe.py` puts the grouped matmuls under the share it calls
# "experts": in this table that key is the whole of `moe`
SHARES = {
    "kda": ("kda",),
    "kda_scan": ("kda", "scan"),
    "mla": ("mla",),
    "experts": ("moe",),
}
PASS = ("kda", "scan", "state")  # the `while` over a layer's chunks
_cache = {}


@contextlib.contextmanager
def _in_place_of(module, **others):
    """`module`'s attributes replaced by `others` while the block runs."""
    kept = {name: getattr(module, name) for name in others}
    for name, other in others.items():
        setattr(module, name, other)
    try:
        yield
    finally:
        for name, value in kept.items():
            setattr(module, name, value)


def passes(lines, instructions, lo, hi, line_prefixes):
    """(forward, backward) passes over the chunks that one device
    plane's lines hold inside [lo, hi) ns."""
    forward = backward = 0.0
    for line, events in lines:
        if not line.startswith(line_prefixes):
            continue
        for name, start, end in events:
            name = _scopes.instruction(name)
            path = instructions.get(name)
            if not name.startswith("while") or path is None:
                continue
            if not _scopes._passes(path, PASS):
                continue
            part = (min(end, hi) - max(start, lo)) / max(end - start, 1)
            if part <= 0:
                continue
            scopes = re.split(r"[/()]", path)
            if "transpose" in scopes and "rematted_computation" not in scopes:
                backward += part
            else:
                forward += part
    return forward, backward


def trace_seconds(run, reader_file):
    """`_moe.trace_seconds` with `SHARES` for its table, and beside
    its {"seconds", "busy", "kernels", "kind"}: "forward" and
    "backward", the passes over the chunks; or None."""
    if run["platform"] != "tpu" or not run["trace"]:
        return None
    key = run["window"]["wall0"]
    if key in _cache:
        return _cache[key]
    _cache.clear()
    counted = {"forward": 0.0, "backward": 0.0}
    walk = _moe.plane_seconds

    def walk_and_count(lines, instructions, lo, hi, line_prefixes):
        forward, backward = passes(lines, instructions, lo, hi, line_prefixes)
        counted["forward"] += forward
        counted["backward"] += backward
        return walk(lines, instructions, lo, hi, line_prefixes)

    _moe._cache.clear()  # what it keeps was read with another table
    with _in_place_of(_moe, SHARES=SHARES, plane_seconds=walk_and_count):
        found = _moe.trace_seconds(run, reader_file)
    _moe._cache.clear()
    if found is not None and not found["seconds"]["kda"]:
        found = None  # a program without these scopes
    if found is not None:
        found = {**found, **counted}
        _scopes.say(
            f"passes over the chunks: {counted['forward']:.1f} forward, "
            f"{counted['backward']:.1f} backward"
        )
    _cache[key] = found
    return found


def share(run, reader_file, name):
    found = trace_seconds(run, reader_file)
    return None if found is None else (
        100.0 * found["seconds"][name] / found["busy"]
    )


def roofline_pct(found, tokens, sizes, flops_module, peak_flops, peak_bytes):
    """100 x (the credited passes' FLOPs over the seconds under
    kda/scan) over the roof of one pass."""
    seconds = found["seconds"]["kda_scan"]
    passes = found["forward"] + 2.0 * found["backward"]
    if seconds <= 0 or passes <= 0:
        return None
    one = flops_module.kda_scan_flops(tokens, sizes)
    intensity = one / flops_module.kda_scan_bytes(tokens, sizes)
    roof = min(peak_flops, peak_bytes * intensity)
    _scopes.say(
        f"kda scan: {found['forward']:.1f} forward and {found['backward']:.1f} "
        f"backward passes of {tokens} tokens ({one / 1e9:.2f} GFLOP a forward "
        f"pass, {intensity:.0f} FLOP/B) in {seconds:.4f}s; roof "
        f"{roof / 1e12:.1f} TFLOP/s"
    )
    return 100.0 * passes * one / seconds / roof


def scan_roofline(run, reader_file):
    found = trace_seconds(run, reader_file)
    if found is None:
        return None
    sizes = run["sizes"]
    config_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(reader_file))),
        "configs", sizes["name"],
    )
    return roofline_pct(
        found, sizes["minibatch_per_chip"] * sizes["seq_len"], sizes,
        load_module(os.path.join(config_dir, "flops.py")),
        peaks.peak(found["kind"]), peaks.peak(found["kind"], "hbm_bytes_per_s"),
    )
