"""Share of the probe's traced slice's device-busy time in leaf
operations under `gmu`: the gated memory unit's two projections and
its gate on the memory, all phases (see `_sambay.py`)."""

from benchmark.layer_metrics import _sambay


def read(run):
    return _sambay.share(run, __file__, "gmu")
