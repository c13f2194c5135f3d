"""Shared by the readers of the `lfm2-24b-a2b` cell (`shortconv_pct`,
`shortconv_gate_pct`, `gqa_attention_pct`, `sparse_moe_pct`,
`sparse_experts_roofline_pct`; `sparse_expert_load_max_over_mean` is
`_moe.py`'s reading as it is).

The shares are `_moe.py`'s walk over the probe's traced slice, called
as it is with this cell's table of scopes in place of its own (the way
`_hybrid.py` calls it, and with `_hybrid.py`'s swap): leaf operations
whose `op_name` passes through `shortconv`, `shortconv` and
`gate_conv`, `attention` (the Pallas kernels keep their path:
`.../attention/pallas_call`), or `moe`, as a share of the slice's
device-busy time. The compiler's grouped matmuls (`ragged-dot-*`, their
scope lost) count by their name under `moe/experts`, as in `_moe.py`,
and here under the whole of `moe` too.

`sparse_experts_roofline_pct` is `_moe.roofline_pct` with this
configuration's `flops.py`: every `ragged-dot-none` event of the slice
is one grouped matmul over the rows the router really sent to the held
experts (`expert_tokens` of `worker.window_stats`), 2 x rows x 2048 x
1536 FLOPs, over the time of all leaf operations under `moe/experts`,
against min(peak FLOP/s, HBM bytes/s x the matmul's intensity).

No trace, no map, no span, a run off the TPU, or a program without
these scopes (a parent commit): None, and the metric is left out.

For the next `benchmark` PR (only it may lengthen an accepted metric's
`workloads`): append this cell to `expert_load_max_over_mean`,
`experts_roofline_pct`, `moe_experts_pct`, `moe_route_pct`,
`step_*_pct` and `program_temp_gb`, delete the three `sparse_*` twins
and the `moe` and `experts` rows below, and give `_moe.py`'s walk its
table as an argument.
"""

from benchmark.layer_metrics import _hybrid, _moe

SHARES = {
    "shortconv": ("shortconv",),
    "gate": ("shortconv", "gate_conv"),
    "attention": ("attention",),
    "moe": ("moe",),
    "experts": ("moe", "experts"),  # what `_moe.roofline_pct` divides by
}
_cache = {}
_by_scope = _moe.shares_of


def shares_of(name, path):
    """`_moe.shares_of`, and a grouped matmul under `moe` as well."""
    found = _by_scope(name, path)
    return found + ("moe",) if name.startswith(_moe.KERNEL_FAMILY) else found


def trace_seconds(run, reader_file):
    """`_moe.trace_seconds` with `SHARES` for its table, or None."""
    if run["platform"] != "tpu" or not run["trace"]:
        return None
    key = run["window"]["wall0"]
    if key in _cache:
        return _cache[key]
    _cache.clear()
    _moe._cache.clear()  # what it keeps was read with another table
    with _hybrid._in_place_of(_moe, SHARES=SHARES, shares_of=shares_of):
        found = _moe.trace_seconds(run, reader_file)
    _moe._cache.clear()
    if found is not None and not found["seconds"]["shortconv"]:
        found = None  # a program without these scopes
    _cache[key] = found
    return found


def share(run, reader_file, name):
    found = trace_seconds(run, reader_file)
    return None if found is None else (
        100.0 * found["seconds"][name] / found["busy"]
    )


def experts_roofline(run, reader_file):
    found = trace_seconds(run, reader_file)
    if found is None:
        return None
    # `_moe.experts_roofline` on the slice as this table read it
    with _hybrid._in_place_of(_moe, trace_seconds=lambda *_: found):
        return _moe.experts_roofline(run, reader_file)
