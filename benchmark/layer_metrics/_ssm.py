"""Shared by the readers of the `nemotron-3-nano-30b-a3b` cell
(`ssm_pct`, `ssm_scan_pct`, `ssm_scan_roofline_pct`,
`nano_attention_pct`, `nano_moe_pct`, `relu2_experts_roofline_pct`;
`nano_expert_load_max_over_mean` is `_moe.py`'s reading as it is).

The shares are `_moe.py`'s walk over the probe's traced slice, called
as it is with this cell's table of scopes in place of its own (the way
`_gdn.py` borrows it, with `_hybrid.py`'s swap): leaf operations whose
`op_name` passes through `mamba2`, `mamba2` and `scan`, `attention`
(the Pallas kernels keep their path: `.../attention/pallas_call`), or
`moe`, as a share of the slice's device-busy time. The compiler's
grouped matmuls (`ragged-dot-*`, their scope lost) count by their name
under `moe` and `moe/experts` (`_shortconv.shares_of`).

`ssm_scan_roofline_pct` is `kda_scan_roofline_pct`'s rule, with
another count of the passes: this scan's pass between chunks is one
product and no `while`, so there is no loop to count. A pass is
counted from the scan's own operations instead. The program names the
RUN a state-space layer belongs to (`mamba2/run<i>/scan/...`), and the
compiler lays each run's scan down up to three times: in the forward
pass, in the layer's recomputation (`rematted_computation`) and
transposed (`transpose(...)`, not under `rematted_computation`: the
backward pass). Every instruction of one such copy runs once a pass of
that copy, so a copy's passes inside the slice are the MEDIAN over its
instructions of their events there (an event the slice's edge cuts by
the part inside; the median, so that an instruction the compiler put
inside a loop of its own, or shares between copies, moves nothing). A
backward pass is credited two forward passes of `flops.py`'s chunked
form over the minibatch's tokens, any other one (the first, or the
recomputation: counted because it ran), over the time of every leaf
operation under `mamba2/scan`, against min(peak FLOP/s, HBM bytes/s x
the form's intensity). The form's bytes are the recurrence's inputs and
output once, so the share stays under 100 whatever implements the scan.

`relu2_experts_roofline_pct` is `_moe.roofline_pct` with this
configuration's `flops.py`: every `ragged-dot-none` event of the slice
is one grouped matmul over the rows the router really sent to the held
experts (`expert_tokens` of `worker.window_stats`), 2 x rows x 2688 x
1856 FLOPs (two such a pass of an expert block, not three: no gate),
over the time of all leaf operations under `moe/experts`.

No trace, no map, no span, a run off the TPU, or a program without
these scopes (a parent commit): None, and the metric is left out.
"""

import os
import re
import statistics

from benchmark.harness import peaks
from benchmark.harness.manifest import load_module
from benchmark.layer_metrics import _hybrid, _moe, _scopes, _shortconv

SHARES = {
    "ssm": ("mamba2",),
    "ssm_scan": ("mamba2", "scan"),
    "attention": ("attention",),
    "moe": ("moe",),
    "experts": ("moe", "experts"),  # what `_moe.roofline_pct` divides by
}
SCAN = SHARES["ssm_scan"]
_RUN = re.compile(r"run\d+$")
_cache = {}


def scan_passes(lines, instructions, lo, hi, line_prefixes):
    """(forward, backward) passes of the state-space scan that one
    device plane's lines hold inside [lo, hi) ns."""
    events = [
        e for line, evs in lines if line.startswith(line_prefixes) for e in evs
    ]
    copies = {}  # (run, transposed, recomputed) -> {instruction: events}
    for name, start, end in _scopes.leaves(events):
        inside = min(end, hi) - max(start, lo)
        name = _scopes.instruction(name)
        path = instructions.get(name)
        if inside <= 0 or path is None or not _scopes._passes(path, SCAN):
            continue
        scopes = re.split(r"[/()]", path)
        copy = (
            next((s for s in scopes if _RUN.match(s)), ""),
            "transpose" in scopes, "rematted_computation" in scopes,
        )
        counts = copies.setdefault(copy, {})
        counts[name] = counts.get(name, 0.0) + inside / max(end - start, 1)
    forward = backward = 0.0
    for (_run, transposed, recomputed), counts in copies.items():
        passes = statistics.median(counts.values())
        if transposed and not recomputed:
            backward += passes
        else:
            forward += passes
    return forward, backward


def trace_seconds(run, reader_file):
    """`_moe.trace_seconds` with `SHARES` for its table, and beside
    its {"seconds", "busy", "kernels", "kind"}: "forward" and
    "backward", the scan's passes; or None."""
    if run["platform"] != "tpu" or not run["trace"]:
        return None
    key = run["window"]["wall0"]
    if key in _cache:
        return _cache[key]
    _cache.clear()
    counted = {"forward": 0.0, "backward": 0.0}
    walk = _moe.plane_seconds

    def walk_and_count(lines, instructions, lo, hi, line_prefixes):
        forward, backward = scan_passes(
            lines, instructions, lo, hi, line_prefixes
        )
        counted["forward"] += forward
        counted["backward"] += backward
        return walk(lines, instructions, lo, hi, line_prefixes)

    _moe._cache.clear()  # what it keeps was read with another table
    with _hybrid._in_place_of(
        _moe, SHARES=SHARES, shares_of=_shortconv.shares_of,
        plane_seconds=walk_and_count,
    ):
        found = _moe.trace_seconds(run, reader_file)
    _moe._cache.clear()
    if found is not None and not found["seconds"]["ssm"]:
        found = None  # a program without these scopes
    if found is not None:
        found = {**found, **counted}
        _scopes.say(
            f"passes of the state-space scan: {counted['forward']:.1f} "
            f"forward, {counted['backward']:.1f} backward"
        )
    _cache[key] = found
    return found


def share(run, reader_file, name):
    found = trace_seconds(run, reader_file)
    return None if found is None else (
        100.0 * found["seconds"][name] / found["busy"]
    )


def _flops_module(run, reader_file):
    return load_module(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(reader_file))),
        "configs", run["sizes"]["name"], "flops.py",
    ))


def scan_roofline_pct(found, tokens, sizes, flops_module, peak_flops,
                      peak_bytes):
    """100 x (the credited passes' FLOPs over the seconds under
    mamba2/scan) over the roof of one pass."""
    seconds = found["seconds"]["ssm_scan"]
    passes = found["forward"] + 2.0 * found["backward"]
    if seconds <= 0 or passes <= 0:
        return None
    one = flops_module.ssm_scan_flops(tokens, sizes)
    intensity = one / flops_module.ssm_scan_bytes(tokens, sizes)
    roof = min(peak_flops, peak_bytes * intensity)
    _scopes.say(
        f"ssm scan: {found['forward']:.1f} forward and "
        f"{found['backward']:.1f} backward passes of {tokens} tokens "
        f"({one / 1e9:.2f} GFLOP a forward pass, {intensity:.0f} FLOP/B) in "
        f"{seconds:.4f}s; roof {roof / 1e12:.1f} TFLOP/s"
    )
    return 100.0 * passes * one / seconds / roof


def scan_roofline(run, reader_file):
    found = trace_seconds(run, reader_file)
    if found is None:
        return None
    sizes = run["sizes"]
    return scan_roofline_pct(
        found, sizes["minibatch_per_chip"] * sizes["seq_len"], sizes,
        _flops_module(run, reader_file),
        peaks.peak(found["kind"]), peaks.peak(found["kind"], "hbm_bytes_per_s"),
    )


def experts_roofline(run, reader_file):
    found = trace_seconds(run, reader_file)
    if found is None:
        return None
    # `_moe.experts_roofline` on the slice as this table read it
    with _hybrid._in_place_of(_moe, trace_seconds=lambda *_: found):
        return _moe.experts_roofline(run, reader_file)
