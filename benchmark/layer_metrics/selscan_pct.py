"""Share of the probe's traced slice's device-busy time in leaf
operations under `mamba1/scan`: the selective scan's kernels (or
whatever implements it) and D x, all phases (see `_sambay.py`)."""

from benchmark.layer_metrics import _sambay


def read(run):
    return _sambay.share(run, __file__, "selscan")
