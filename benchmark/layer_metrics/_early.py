"""Shared by the readers of the `smallthinker-21b-a3b` cell
(`early_route_pct`, `band4k_attention_pct`, `nope_attention_pct`,
`early_moe_pct`, `band4k_roofline_pct`, `reglu_experts_roofline_pct`;
`early_expert_load_max_over_mean` is `_moe.py`'s reading as it is).

The shares are `_moe.py`'s walk over the probe's traced slice, called
as it is with this cell's table of scopes in place of its own (the way
`_window.py` borrows it, with `_hybrid.py`'s swap): leaf operations
whose `op_name` passes through `attention` and `swa`, `attention` and
`global` (the Pallas kernels keep their path:
`.../attention/swa/pallas_call`), or `moe`, as a share of the slice's
device-busy time. The compiler's grouped matmuls (`ragged-dot-*`, their
scope lost) count by their name under `moe` and `moe/experts`.
`early_route_pct` is the one share of TWO scopes: this model's router
forms its logits ahead of the attention, under a scope of its own,
`router`, and everything behind the product (softmax, top-6, the sort,
the moves between token order and expert order) stays under
`moe/route`; the two are read apart (the walk's `scopes:` line on
stderr gives each) and the metric is their sum.

`band4k_roofline_pct` is `swa_roofline_pct`'s rule (`_window.py`:
`banded_calls`, `roofline_pct`) with this configuration's `flops.py`:
the Mosaic calls under `attention/swa`, each credited the operations
over the band's VISIBLE pairs at a window of 4096 and its arrays'
bytes once, the least time the chip could take over the time they
took. Counted so, it cannot pass 100.

`reglu_experts_roofline_pct` is `_moe.roofline_pct` with this
configuration's `flops.py`: every `ragged-dot-none` event of the slice
is one grouped matmul over the rows the router really sent to the held
experts (`expert_tokens` of `worker.window_stats`), 2 x rows x 2560 x
768 FLOPs, over the time of all leaf operations under `moe/experts`.

No trace, no map, no span, a run off the TPU, or a program without
these scopes (a parent commit): None, and the metric is left out.
"""

from benchmark.layer_metrics import _hybrid, _moe, _window

SHARES = {
    "router": ("router",),  # the logits, formed in front of the attention
    "route": ("moe", "route"),
    "swa": _window.SHARES["swa"],
    "global": _window.SHARES["global"],
    "moe": ("moe",),
    "experts": ("moe", "experts"),  # what `_moe.roofline_pct` divides by
}
_cache = {}
_by_scope = _moe.shares_of


def shares_of(name, path):
    """`_moe.shares_of` by this table, a grouped matmul under `moe` and
    `moe/experts`."""
    if name.startswith(_moe.KERNEL_FAMILY):
        return ("moe", "experts")
    return _by_scope(name, path)


def trace_seconds(run, reader_file):
    """`_window.trace_seconds` (`_moe.py`'s walk, and the banded
    kernels' time and calls counted beside it) with `SHARES` and
    `shares_of` for its table; or None."""
    if run["platform"] != "tpu" or not run["trace"]:
        return None
    key = run["window"]["wall0"]
    if key not in _cache:
        _cache.clear()
        _window._cache.clear()  # what it keeps was read with another table
        with _hybrid._in_place_of(_window, SHARES=SHARES, shares_of=shares_of):
            _cache[key] = _window.trace_seconds(run, reader_file)
        _window._cache.clear()
    return _cache[key]


def share(run, reader_file, *names):
    """The slice's device-busy share under `names`' scopes, summed
    (scopes that no path passes through together)."""
    found = trace_seconds(run, reader_file)
    return None if found is None else 100.0 * sum(
        found["seconds"][name] for name in names
    ) / found["busy"]


def _as_read_here(module, reader, run, reader_file):
    """`module.<reader>` on the slice as this table read it."""
    found = trace_seconds(run, reader_file)
    if found is None:
        return None
    with _hybrid._in_place_of(module, trace_seconds=lambda *_: found):
        return getattr(module, reader)(run, reader_file)


def band_roofline(run, reader_file):
    return _as_read_here(_window, "swa_roofline", run, reader_file)


def experts_roofline(run, reader_file):
    return _as_read_here(_moe, "experts_roofline", run, reader_file)
