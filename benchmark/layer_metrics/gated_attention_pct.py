"""Share of the probe's traced slice's device-busy time in leaf
operations under `attention`: the one gated attention layer's
projections, norms, rotation, the Pallas kernels at heads of 256 (by
their `op_name`) and the channel gate, all phases (see `_gdn.py`)."""

from benchmark.layer_metrics import _gdn


def read(run):
    return _gdn.share(run, __file__, "attention")
