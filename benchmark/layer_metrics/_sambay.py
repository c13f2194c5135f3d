"""Shared by the readers of the `phi-4-mini-flash-reasoning` cell
(`selscan_pct`, `sambay_mamba_pct`, `sambay_gmu_pct`,
`sambay_attention_pct`, `sambay_cross_pct`, `selscan_roofline_pct`).

The shares are `_moe.py`'s walk over the probe's traced slice, called
as it is with this cell's table of scopes in place of its own (the way
`_ssm.py` borrows it): leaf operations whose `op_name` passes through
`mamba1`, `mamba1` and `scan`, `gmu`, `attention` (the Pallas kernels
keep their path: `.../attention/global/pallas_call`), or `attention`
and `cross`, as a share of the slice's device-busy time.

`selscan_roofline_pct` is `ssm_scan_roofline_pct`'s rule with another
yardstick for the passes, one that does not look at what implements
the scan (a kernel is one instruction a pass, a `lax.scan` over chunks
runs its body's instructions once a CHUNK): a pass of the recurrence is
a pass of its layer, and that is counted from the layer's first
projection, the operations under `mamba1/in_proj` (`_ssm.scan_passes`,
told that scope): the compiler lays them down in the forward pass, in
the layer's recomputation (`rematted_computation`) and transposed (the
backward pass), once a step each whatever follows, and a copy's passes
inside the slice are the median over its instructions of their events
there. A backward pass is credited two forward passes of `flops.py`'s
recurrence AS WRITTEN over the minibatch's tokens (three
multiply-accumulates a token, channel and state column), any other
one, over the time of every leaf operation under `mamba1/scan`, against
min(peak FLOP/s, HBM bytes/s x the recurrence's intensity). Its bytes
are x, dt, B and C read once and y written once, never the [T, inner,
state] states, so the share stays under 100 whatever implements the
scan; the vector unit cannot reach that roof (six operations and an
exponential a state entry against ten bytes a channel), and the share
says how far under it stays.

No trace, no map, no span, a run off the TPU, or a program without
these scopes (a parent commit): None, and the metric is left out.
"""

from benchmark.harness import peaks
from benchmark.layer_metrics import _hybrid, _moe, _scopes, _ssm

SHARES = {
    "mamba": ("mamba1",),
    "selscan": ("mamba1", "scan"),
    "gmu": ("gmu",),
    "attention": ("attention",),
    "cross": ("attention", "cross"),
}
LAYER_PASS = ("mamba1", "in_proj")  # one projection a pass of the layer
_cache = {}


def trace_seconds(run, reader_file):
    """`_moe.trace_seconds` with `SHARES` for its table, and beside
    its {"seconds", "busy", "kernels", "kind"}: "forward" and
    "backward", the Mamba-1 layers' passes; or None."""
    if run["platform"] != "tpu" or not run["trace"]:
        return None
    key = run["window"]["wall0"]
    if key in _cache:
        return _cache[key]
    _cache.clear()
    counted = {"forward": 0.0, "backward": 0.0}
    walk = _moe.plane_seconds

    def walk_and_count(lines, instructions, lo, hi, line_prefixes):
        with _hybrid._in_place_of(_ssm, SCAN=LAYER_PASS):
            forward, backward = _ssm.scan_passes(
                lines, instructions, lo, hi, line_prefixes
            )
        counted["forward"] += forward
        counted["backward"] += backward
        return walk(lines, instructions, lo, hi, line_prefixes)

    _moe._cache.clear()  # what it keeps was read with another table
    with _hybrid._in_place_of(
        _moe, SHARES=SHARES, plane_seconds=walk_and_count
    ):
        found = _moe.trace_seconds(run, reader_file)
    _moe._cache.clear()
    if found is not None and not found["seconds"]["mamba"]:
        found = None  # a program without these scopes
    if found is not None:
        found = {**found, **counted}
        _scopes.say(
            f"passes of the Mamba-1 layers: {counted['forward']:.1f} "
            f"forward, {counted['backward']:.1f} backward"
        )
    _cache[key] = found
    return found


def share(run, reader_file, name):
    found = trace_seconds(run, reader_file)
    return None if found is None else (
        100.0 * found["seconds"][name] / found["busy"]
    )


def scan_roofline_pct(found, tokens, sizes, flops_module, peak_flops,
                      peak_bytes):
    """100 x (the credited passes' FLOPs over the seconds under
    mamba1/scan) over the roof of one pass."""
    seconds = found["seconds"]["selscan"]
    passes = found["forward"] + 2.0 * found["backward"]
    if seconds <= 0 or passes <= 0:
        return None
    one = flops_module.selscan_flops(tokens, sizes)
    intensity = one / flops_module.selscan_bytes(tokens, sizes)
    roof = min(peak_flops, peak_bytes * intensity)
    _scopes.say(
        f"selective scan: {found['forward']:.1f} forward and "
        f"{found['backward']:.1f} backward passes of {tokens} tokens "
        f"({one / 1e9:.2f} GFLOP a forward pass, {intensity:.1f} FLOP/B) in "
        f"{seconds:.4f}s; roof {roof / 1e12:.2f} TFLOP/s"
    )
    return 100.0 * passes * one / seconds / roof


def scan_roofline(run, reader_file):
    found = trace_seconds(run, reader_file)
    if found is None:
        return None
    sizes = run["sizes"]
    return scan_roofline_pct(
        found, sizes["minibatch_per_chip"] * sizes["seq_len"], sizes,
        _ssm._flops_module(run, reader_file), peaks.peak(found["kind"]),
        peaks.peak(found["kind"], "hbm_bytes_per_s"),
    )
