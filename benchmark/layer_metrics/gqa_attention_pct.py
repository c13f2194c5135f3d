"""Share of the probe's traced slice's device-busy time in leaf
operations under `attention`: the one grouped-query layer's norm,
projections, the norms of queries and keys, rotary, the widening of 8
key-value heads to 32, the Pallas kernels (by their `op_name`) and the
output projection, all phases (see `_shortconv.py`)."""

from benchmark.layer_metrics import _shortconv


def read(run):
    return _shortconv.share(run, __file__, "attention")
