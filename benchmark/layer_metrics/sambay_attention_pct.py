"""Share of the probe's traced slice's device-busy time in leaf
operations under `attention`: the windowed, the full and the cross
layer's projections, the Pallas kernels at queries and keys of 64
over values of 128 (by their `op_name`), the subtraction of the two
maps and the pair norm, all phases (see `_sambay.py`)."""

from benchmark.layer_metrics import _sambay


def read(run):
    return _sambay.share(run, __file__, "attention")
