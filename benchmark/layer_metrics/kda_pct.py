"""Share of the probe's traced slice's device-busy time in leaf
operations under `kda`: Kimi Delta Attention's projections, its
convolutions, decay and gates, the chunked recurrence and the gated
output, in the four KDA layers, all phases (see `_hybrid.py`)."""

from benchmark.layer_metrics import _hybrid


def read(run):
    return _hybrid.share(run, __file__, "kda")
