"""Share of the probe's traced slice's device-busy time in leaf
operations under `attention/global`: the two full-attention layers'
projections (q and o 6144 wide), the half-head YaRN rotation, the
widening of 8 key-value heads to 48, the causal Pallas kernels (by
their `op_name`), the per-head gate and the output projection, all
phases (see `_window.py`)."""

from benchmark.layer_metrics import _window


def read(run):
    return _window.share(run, __file__, "global")
