"""Share of the probe's traced slice's device-busy time in leaf
operations under `attention/swa`: the three window-4096 layers'
projections (q and o 3584 wide), the rotation of the whole head at
theta 1.5e6, the widening of 4 key-value heads to 28, the banded Pallas
kernels at 16,384 tokens (by their `op_name`) and the output
projection, all phases (see `_early.py`)."""

from benchmark.layer_metrics import _early


def read(run):
    return _early.share(run, __file__, "swa")
