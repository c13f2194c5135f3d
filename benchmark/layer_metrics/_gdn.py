"""Shared by the readers of the `qwen3-next-80b-a3b` cell (`gdn_pct`,
`gdn_scan_pct`, `gdn_scan_roofline_pct`, `gated_attention_pct`,
`attn256_roofline_pct`, `next_moe_pct`;
`next_expert_load_max_over_mean` is `_moe.py`'s reading as it is).

The shares are `_moe.py`'s walk over the probe's traced slice, called
as it is with this cell's table of scopes in place of its own (the way
`_window.py` borrows it, with `_hybrid.py`'s swap): leaf operations
whose `op_name` passes through `gdn`, `gdn` and `scan`, `attention`
(the Pallas kernels keep their path: `.../attention/pallas_call`), or
`moe`, as a share of the slice's device-busy time. The compiler's
grouped matmuls (`ragged-dot-*`, their scope lost) count by their name
under `moe`.

`gdn_scan_roofline_pct` is `kda_scan_roofline_pct`'s rule on this
cell's scopes: every pass over the chunks is a `while` whose `op_name`
runs through `gdn`, `scan` and `state` (`_hybrid.passes` with that
path); one under `transpose(...)` and not under `rematted_computation`
is a backward pass of one layer, credited two forward passes of
`flops.py`'s SCALAR chunked form over the minibatch's tokens; any other
is a forward pass (the first, or the layer's recomputation: counted
because it ran), credited one; over the time of every leaf operation
under `gdn/scan`, against min(peak FLOP/s, HBM bytes/s x the form's
intensity). The form's bytes are the recurrence's inputs and output
once, so the share stays under 100 whatever implements the scan.

`attn256_roofline_pct` is `swa_roofline_pct`'s rule on the causal
calls at heads of 256: the leaf operations whose `op_name` passes
through `attention` and ends in `pallas_call` and whose instruction is
named after the scope (`attention.<n>`); forward and recomputed calls
credited 2 products of 256 a VISIBLE pair and head, a backward kernel
3.5 (dq 3, dk+dv 4), over the triangle's pairs, not the tiles run, and
their arrays' bytes once; the least time the chip could take,
max(FLOPs / peak, bytes / bandwidth) a call, over the time they took.

No trace, no map, a run off the TPU, or a program without these scopes
(a parent commit): None, and the metric is left out.
"""

import os

from benchmark.harness import peaks
from benchmark.harness.manifest import load_module
from benchmark.layer_metrics import _hybrid, _moe, _scopes, _window

SHARES = {
    "gdn": ("gdn",),
    "gdn_scan": ("gdn", "scan"),
    "attention": ("attention",),
    "moe": ("moe",),
}
PASS = ("gdn", "scan", "state")  # the `while` over a layer's chunks
_cache = {}


def trace_seconds(run, reader_file):
    """`_moe.trace_seconds` with `SHARES` for its table, and beside
    its {"seconds", "busy", "kind"}: "forward" and "backward", the
    passes over the chunks, and "kernel_seconds", "kernel_forward",
    "kernel_backward", the causal kernels' time and calls; or None."""
    if run["platform"] != "tpu" or not run["trace"]:
        return None
    key = run["window"]["wall0"]
    if key in _cache:
        return _cache[key]
    _cache.clear()
    counted = dict.fromkeys(
        ("forward", "backward", "kernel_seconds", "kernel_forward",
         "kernel_backward"), 0.0,
    )
    walk = _moe.plane_seconds

    def walk_and_count(lines, instructions, lo, hi, line_prefixes):
        with _hybrid._in_place_of(_hybrid, PASS=PASS):
            passes = _hybrid.passes(lines, instructions, lo, hi, line_prefixes)
        with _hybrid._in_place_of(
            _window, SHARES={"swa": SHARES["attention"]}
        ):
            calls = _window.banded_calls(
                lines, instructions, lo, hi, line_prefixes
            )
        for name, value in zip(counted, (*passes, *calls)):
            counted[name] += value
        return walk(lines, instructions, lo, hi, line_prefixes)

    _moe._cache.clear()  # what it keeps was read with another table
    with _hybrid._in_place_of(
        _moe, SHARES=SHARES, shares_of=_window.shares_of,
        plane_seconds=walk_and_count,
    ):
        found = _moe.trace_seconds(run, reader_file)
    _moe._cache.clear()
    if found is not None and not found["seconds"]["gdn"]:
        found = None  # a program without these scopes
    if found is not None:
        found = {**found, **counted}
        _scopes.say(
            f"passes over the chunks: {counted['forward']:.1f} forward, "
            f"{counted['backward']:.1f} backward; causal kernels at 256: "
            f"{counted['kernel_forward']:.1f} forward and "
            f"{counted['kernel_backward']:.1f} backward calls in "
            f"{counted['kernel_seconds']:.4f}s"
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
    gdn/scan) over the roof of one pass."""
    seconds = found["seconds"]["gdn_scan"]
    passes = found["forward"] + 2.0 * found["backward"]
    if seconds <= 0 or passes <= 0:
        return None
    one = flops_module.gdn_scan_flops(tokens, sizes)
    intensity = one / flops_module.gdn_scan_bytes(tokens, sizes)
    roof = min(peak_flops, peak_bytes * intensity)
    _scopes.say(
        f"gdn scan: {found['forward']:.1f} forward and "
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


def attention_roofline_pct(found, sizes, flops_module, peak_flops,
                           peak_bytes):
    """100 x the least time the chip could take for the causal calls
    the slice ran over the time they took."""
    seconds = found["kernel_seconds"]
    forward, backward = found["kernel_forward"], found["kernel_backward"]
    if seconds <= 0 or forward + backward <= 0:
        return None
    sequences = sizes["minibatch_per_chip"]
    f = flops_module
    # a backward call is half a pair of dq (5 arrays) and dk+dv (6)
    kinds = (
        (forward, f.FORWARD_PRODUCTS, 4),
        (backward, f.BACKWARD_PRODUCTS / 2, (5 + 6) / 2),
    )
    least = sum(
        calls * max(
            f.attention_call_flops(sizes, products, sequences) / peak_flops,
            f.attention_call_bytes(sizes, tensors, sequences) / peak_bytes,
        )
        for calls, products, tensors in kinds
    )
    _scopes.say(
        f"causal kernels at 256: {forward:.1f} forward calls of "
        f"{f.attention_call_flops(sizes, f.FORWARD_PRODUCTS, sequences) / 1e9:.1f}"
        f" GFLOP and {backward:.1f} backward calls of "
        f"{f.attention_call_flops(sizes, f.BACKWARD_PRODUCTS / 2, sequences) / 1e9:.1f}"
        f" in {seconds:.4f}s; the roof asks {least:.4f}s"
    )
    return 100.0 * least / seconds


def attention_roofline(run, reader_file):
    found = trace_seconds(run, reader_file)
    if found is None:
        return None
    return attention_roofline_pct(
        found, run["sizes"], _flops_module(run, reader_file),
        peaks.peak(found["kind"]), peaks.peak(found["kind"], "hbm_bytes_per_s"),
    )
