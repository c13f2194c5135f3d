"""Share of the probe's traced slice's device-busy time in leaf
operations under `moe`: the sigmoid router, the sort and gathers, the
grouped matmuls of the 8 held experts (`ragged-dot-*`, counted here
though the compiler drops their scope) and the shared expert, in the
four expert layers (see `_hybrid.py`)."""

from benchmark.layer_metrics import _hybrid


def read(run):
    return _hybrid.share(run, __file__, "experts")
