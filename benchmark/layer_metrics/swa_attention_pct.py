"""Share of the probe's traced slice's device-busy time in leaf
operations under `attention/swa`: the three window-512 layers'
projections (q and o 8192 wide), the rotation of the whole head, the
widening of 8 key-value heads to 64, the banded Pallas kernels (by
their `op_name`), the per-head gate and the output projection, all
phases (see `_window.py`)."""

from benchmark.layer_metrics import _window


def read(run):
    return _window.share(run, __file__, "swa")
