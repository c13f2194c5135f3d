"""Share of the probe's traced slice's device-busy time in leaf
operations under `moe`: the sigmoid router, the sort and gathers and the
grouped matmuls of the 8 held experts (`ragged-dot-*`, counted here
though the compiler drops their scope), in the four expert layers; this
layer has no shared expert (see `_shortconv.py`)."""

from benchmark.layer_metrics import _shortconv


def read(run):
    return _shortconv.share(run, __file__, "moe")
