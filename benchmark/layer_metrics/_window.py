"""Shared by the readers of the `laguna-xs2` cell (`swa_attention_pct`,
`global_attention_pct`, `swa_roofline_pct`, `window_moe_pct`,
`attn_gate_mean`).

The shares are `_moe.py`'s walk over the probe's traced slice, called
as it is with this cell's table of scopes in place of its own (the way
`_shortconv.py` borrows it, with `_hybrid.py`'s swap): leaf operations
whose `op_name` passes through `attention` and `swa`, `attention` and
`global` (the Pallas kernels keep their path:
`.../attention/swa/pallas_call`), or `moe`, as a share of the slice's
device-busy time. The compiler's grouped matmuls (`ragged-dot-*`, their
scope lost) count by their name under `moe`.

`swa_roofline_pct` holds the banded kernels to their roofline. Its
calls are the leaf operations whose `op_name` passes through
`attention` and `swa` and ends in `pallas_call` AND whose instruction is
named after the scope (`swa.39`: the compiler names a Mosaic call so,
and leaves the small reductions and copies it sets round one, which
inherit the call's `op_name`, their own names, `reduce.526`,
`copy.1282`: seen on the chip, PR 48): one under
`transpose(...)` and not under `rematted_computation` is a backward
kernel (dq or dk+dv: half a backward pair), any other a forward one
(the first, or the layer's recomputation: counted because it ran); a
call the slice's edge cuts counts by the part inside. Each is credited
`flops.py`'s operations over THE BAND'S VISIBLE PAIRS, not the tiles it
ran, and the bytes of its arrays once; the least time the chip could
take for them, max(operations / 197 TFLOP/s, bytes / 819 GB/s) a call
(`harness/peaks.py`), over the time those calls took. Counted so, it
cannot pass 100.

`attn_gate_mean` is the program's own: the mean of `attn_gate_mean`
over the window's `worker.window_stats` spans.

No trace, no map, no span, a run off the TPU, or a program without
these scopes (a parent commit): None, and the metric is left out.
"""

import os
import re
import statistics

from benchmark.harness import peaks
from benchmark.harness.manifest import load_module
from benchmark.layer_metrics import _hybrid, _moe, _scopes, _timeline

SHARES = {
    "swa": ("attention", "swa"),
    "global": ("attention", "global"),
    "moe": ("moe",),
}
KERNEL = "pallas_call"  # the last word of a Mosaic call's `op_name`
_cache = {}
_by_scope = _moe.shares_of


def shares_of(name, path):
    """`_moe.shares_of` by this table, a grouped matmul under `moe`."""
    if name.startswith(_moe.KERNEL_FAMILY):
        return ("moe",)
    return _by_scope(name, path)


def banded_calls(lines, instructions, lo, hi, line_prefixes):
    """(seconds, forward calls, backward calls) of the banded kernels
    that one device plane's lines hold inside [lo, hi) ns."""
    events = [
        e for line, evs in lines if line.startswith(line_prefixes) for e in evs
    ]
    seconds = forward = backward = 0.0
    for name, start, end in _scopes.leaves(events):
        inside = min(end, hi) - max(start, lo)
        instruction = _scopes.instruction(name)
        path = instructions.get(instruction)
        if inside <= 0 or path is None or not path.endswith(KERNEL):
            continue
        if instruction.split(".")[0] != SHARES["swa"][-1]:
            continue  # a reduction or copy the compiler set round a call
        if not _scopes._passes(path, SHARES["swa"]):
            continue
        seconds += inside / 1e9
        part = inside / max(end - start, 1)
        scopes = re.split(r"[/()]", path)
        if "transpose" in scopes and "rematted_computation" not in scopes:
            backward += part
        else:
            forward += part
    return seconds, forward, backward


def trace_seconds(run, reader_file):
    """`_moe.trace_seconds` with `SHARES` for its table, and beside
    its {"seconds", "busy", "kind"}: "kernel_seconds", "forward" and
    "backward", the banded kernels' time and calls; or None."""
    if run["platform"] != "tpu" or not run["trace"]:
        return None
    key = run["window"]["wall0"]
    if key in _cache:
        return _cache[key]
    _cache.clear()
    counted = {"kernel_seconds": 0.0, "forward": 0.0, "backward": 0.0}
    walk = _moe.plane_seconds

    def walk_and_count(lines, instructions, lo, hi, line_prefixes):
        found = banded_calls(lines, instructions, lo, hi, line_prefixes)
        for name, value in zip(counted, found):
            counted[name] += value
        return walk(lines, instructions, lo, hi, line_prefixes)

    _moe._cache.clear()  # what it keeps was read with another table
    with _hybrid._in_place_of(
        _moe, SHARES=SHARES, shares_of=shares_of, plane_seconds=walk_and_count
    ):
        found = _moe.trace_seconds(run, reader_file)
    _moe._cache.clear()
    if found is not None and not found["seconds"]["swa"]:
        found = None  # a program without these scopes
    if found is not None:
        found = {**found, **counted}
        _scopes.say(
            f"banded kernels: {counted['forward']:.1f} forward and "
            f"{counted['backward']:.1f} backward calls in "
            f"{counted['kernel_seconds']:.4f}s"
        )
    _cache[key] = found
    return found


def share(run, reader_file, name):
    found = trace_seconds(run, reader_file)
    return None if found is None else (
        100.0 * found["seconds"][name] / found["busy"]
    )


def roofline_pct(found, sizes, flops_module, peak_flops, peak_bytes):
    """100 x the least time the chip could take for the banded calls
    the slice ran over the time they took."""
    seconds = found["kernel_seconds"]
    if seconds <= 0 or found["forward"] + found["backward"] <= 0:
        return None
    sequences = sizes["minibatch_per_chip"]
    f = flops_module
    # a backward call is half a pair of dq (5 arrays) and dk+dv (6)
    kinds = (
        (found["forward"], f.FORWARD_PRODUCTS, 4),
        (found["backward"], f.BACKWARD_PRODUCTS / 2, (5 + 6) / 2),
    )
    least = sum(
        calls * max(
            f.swa_call_flops(sizes, products, sequences) / peak_flops,
            f.swa_call_bytes(sizes, tensors, sequences) / peak_bytes,
        )
        for calls, products, tensors in kinds
    )
    _scopes.say(
        f"banded kernels: {found['forward']:.1f} forward calls of "
        f"{f.swa_call_flops(sizes, f.FORWARD_PRODUCTS, sequences) / 1e9:.1f} "
        f"GFLOP and {found['backward']:.1f} backward calls of "
        f"{f.swa_call_flops(sizes, f.BACKWARD_PRODUCTS / 2, sequences) / 1e9:.1f}"
        f" in {seconds:.4f}s; the roof asks {least:.4f}s"
    )
    return 100.0 * least / seconds


def swa_roofline(run, reader_file):
    found = trace_seconds(run, reader_file)
    if found is None:
        return None
    config_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(reader_file))),
        "configs", run["sizes"]["name"],
    )
    return roofline_pct(
        found, run["sizes"],
        load_module(os.path.join(config_dir, "flops.py")),
        peaks.peak(found["kind"]), peaks.peak(found["kind"], "hbm_bytes_per_s"),
    )


def gate_mean(run, reader_file):
    """The mean `attn_gate_mean` of the window's `worker.window_stats`
    spans, or None where no span carries one."""
    timeline = _timeline.load(run, reader_file)
    spans = timeline.in_window(timeline.worker_spans(), _moe.STATS_SPAN)
    means = [
        _timeline._args(s)["attn_gate_mean"] for s in spans
        if "attn_gate_mean" in _timeline._args(s)
    ]
    return statistics.fmean(means) if means else None
