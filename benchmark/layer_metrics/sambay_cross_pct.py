"""Share of the probe's traced slice's device-busy time in leaf
operations under `attention/cross`: the cross layer's two projections
and its kernels over another layer's keys and values, all phases
(see `_sambay.py`)."""

from benchmark.layer_metrics import _sambay


def read(run):
    return _sambay.share(run, __file__, "cross")
