"""Share of the probe's traced slice's device-busy time in leaf
operations under `moe/route`: the float32 router, its softmax and
top-6, the sort of the assignments by expert, the gathers into expert
order and back, the weighted sum and the balance term — what a
dropless layer pays around its matmuls (see `_moe.py`)."""

from benchmark.layer_metrics import _moe


def read(run):
    return _moe.share(run, __file__, "route")
