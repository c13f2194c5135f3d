"""Share of the probe's traced slice's device-busy time in leaf
operations under `router` or `moe/route`: the float32 product of the
layer's INPUT with the router's 64 columns, formed ahead of the
attention, and behind the attention its softmax and top-6, the sort of
the assignments by expert, the gathers into expert order and back and
the weighted sum — what a dropless layer whose router runs early pays
around its matmuls, all phases (see `_early.py`)."""

from benchmark.layer_metrics import _early


def read(run):
    return _early.share(run, __file__, "router", "route")
