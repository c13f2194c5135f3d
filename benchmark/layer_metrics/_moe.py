"""Shared by the readers of the `deepseek-v2-lite` cell
(`moe_experts_pct`, `moe_route_pct`, `mla_attention_pct`,
`experts_roofline_pct`, `expert_load_max_over_mean`).

The three shares are `_scopes.py`'s reading with other scopes: leaf
operations of the probe's traced slice whose `op_name` passes through
`moe` and `experts`, `moe` and `route`, or `mla`, as a share of the
slice's device-busy time; the join, the leaves and the slice are
`_scopes.py`'s own functions. One thing is added: the v5e compiler
turns each `lax.ragged_dot` into a grouped-matmul kernel whose
instruction is named `ragged-dot-none[.n]` (with a small
`ragged-dot-metadata[.n]` before it) and whose `op_name` is that name,
the scope lost. The program's only ragged dots are the expert layer's,
so an instruction of that name counts under `moe/experts`.

`experts_roofline_pct` divides the work those kernels did in the slice
by the time of all leaf operations under `moe/experts` there (the
kernels and the SiLU-and-multiply between them). Work: every
`ragged-dot-none` event of the slice is one grouped matmul over the
rows the router really sent to the held experts, 2 x rows x 2048 x
1408 FLOPs (`flops.py` beside the configuration), whichever of a
layer's twelve it is — forward, recomputed forward and the two
backward products are counted as often as they ran, and a recomputed
forward is counted because it ran. Rows: the mean over the window's
`worker.window_stats` spans and over the layers of the sum of
`expert_tokens`. The roof is min(peak FLOP/s, HBM bytes/s x the
matmul's intensity), both of `harness/peaks.py`.

No trace, no map, no span, a run off the TPU, or a program without
these scopes (a parent commit): None, and the metric is left out.
"""

import glob
import json
import os
import re
import statistics

from benchmark.harness import peaks, trace_reduce
from benchmark.harness.manifest import load_module
from benchmark.layer_metrics import _scopes, _timeline

SHARES = {
    "experts": ("moe", "experts"),
    "route": ("moe", "route"),
    "mla": ("mla",),
}
KERNEL = "ragged-dot-none"  # one grouped matmul
KERNEL_FAMILY = "ragged-dot-"  # and the metadata call before it
STATS_SPAN = "worker.window_stats"
_cache = {}


def shares_of(name, path):
    """The shares the instruction `name` with `op_name` `path` (None:
    not in the map) counts under."""
    if name.startswith(KERNEL_FAMILY):
        return ("experts",)
    if path is None:
        return ()
    return tuple(
        share for share, scopes in SHARES.items()
        if _scopes._passes(path, scopes)
    )


def plane_seconds(lines, instructions, lo, hi, line_prefixes):
    """({share: seconds}, busy seconds, grouped matmuls run) of one
    device plane's lines inside [lo, hi) ns."""
    events = [
        e for line, evs in lines if line.startswith(line_prefixes) for e in evs
    ]
    busy = sum(
        e - s for s, e in trace_reduce.union(
            (max(s, lo), min(e, hi)) for _n, s, e in events
        )
    ) / 1e9
    seconds, kernels, member = dict.fromkeys(SHARES, 0.0), 0.0, {}
    for name, start, end in _scopes.leaves(events):
        inside = min(end, hi) - max(start, lo)
        if inside <= 0:
            continue
        name = _scopes.instruction(name)
        if name not in member:
            member[name] = shares_of(name, instructions.get(name))
        for share in member[name]:
            seconds[share] += inside / 1e9
        if name.startswith(KERNEL):
            # a kernel the slice's edge cuts counts by the part inside
            kernels += inside / max(end - start, 1)
    return seconds, busy, kernels


def trace_seconds(run, reader_file):
    """{"seconds": {share: s}, "busy": s, "kernels": n, "kind": the
    device's} summed over the run's traced workers, or None."""
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
        return None
    _plane_prefix, line_prefixes = trace_reduce.SELECTORS[run["platform"]]
    total = {"seconds": dict.fromkeys(SHARES, 0.0), "busy": 0.0,
             "kernels": 0.0, "kind": None}
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
            by, busy, kernels = plane_seconds(
                lines, scope_map["instructions"], lo, hi, line_prefixes
            )
            total["busy"] += busy
            total["kernels"] += kernels
            total["kind"] = record["kind"]
            for share, value in by.items():
                total["seconds"][share] += value
            _scopes.say(
                f"{plane} of worker {record['worker_id']}: busy {busy:.4f}s; "
                + ", ".join(f"under {'/'.join(SHARES[k])} {v:.4f}s"
                            for k, v in by.items())
                + f"; {kernels:.1f} grouped matmuls"
            )
    if total["busy"] <= 0 or not any(total["seconds"].values()):
        return None  # a program without these scopes
    _cache[key] = total
    return total


def share(run, reader_file, name):
    found = trace_seconds(run, reader_file)
    return None if found is None else (
        100.0 * found["seconds"][name] / found["busy"]
    )


def expert_tokens(run, reader_file):
    """[[[tokens of each held expert] of each layer] of each
    `worker.window_stats` span of the window], or None."""
    timeline = _timeline.load(run, reader_file)
    spans = timeline.in_window(timeline.worker_spans(), STATS_SPAN)
    loads = [
        _timeline._args(s)["expert_tokens"] for s in spans
        if "expert_tokens" in _timeline._args(s)
    ]
    return loads or None


def load_max_over_mean(loads):
    """Mean over spans and layers of the fullest held expert's tokens
    over the held experts' mean (1.0: even; the count of held experts:
    one expert takes all). A layer that sent nothing here is left out."""
    ratios = [
        max(layer) / statistics.fmean(layer)
        for span in loads for layer in span if sum(layer) > 0
    ]
    return statistics.fmean(ratios) if ratios else None


def roofline_pct(found, loads, sizes, flops_module, peak_flops, peak_bytes):
    """100 x (the grouped matmuls' FLOPs over the seconds under
    moe/experts) over the roof of one such matmul."""
    seconds = found["seconds"]["experts"]
    if seconds <= 0 or found["kernels"] <= 0:
        return None
    rows = statistics.fmean(sum(layer) for span in loads for layer in span)
    if rows <= 0:
        return None
    one = flops_module.expert_matmul_flops(rows, sizes)
    intensity = one / flops_module.expert_matmul_bytes(rows, sizes)
    roof = min(peak_flops, peak_bytes * intensity)
    _scopes.say(
        f"experts: {found['kernels']:.1f} grouped matmuls of {rows:.0f} rows "
        f"({one / 1e9:.2f} GFLOP, {intensity:.0f} FLOP/B) in {seconds:.4f}s; "
        f"roof {roof / 1e12:.1f} TFLOP/s"
    )
    return 100.0 * found["kernels"] * one / seconds / roof


def experts_roofline(run, reader_file):
    found = trace_seconds(run, reader_file)
    if found is None:
        return None
    loads = expert_tokens(run, reader_file)
    if loads is None:
        return None
    config_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(reader_file))),
        "configs", run["sizes"]["name"],
    )
    return roofline_pct(
        found, loads, run["sizes"],
        load_module(os.path.join(config_dir, "flops.py")),
        peaks.peak(found["kind"]), peaks.peak(found["kind"], "hbm_bytes_per_s"),
    )

