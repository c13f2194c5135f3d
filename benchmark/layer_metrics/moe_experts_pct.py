"""Share of the probe's traced slice's device-busy time in leaf
operations under `moe/experts`: the grouped matmuls over the rows
routed to the experts held here (gate, up, down; forward, recomputed
and backward) and the SiLU-and-multiply between them (see `_moe.py`)."""

from benchmark.layer_metrics import _moe


def read(run):
    return _moe.share(run, __file__, "experts")
