"""Share of the probe's traced slice's device-busy time in leaf
operations under `mamba1`: the Mamba-1 layer's projections,
convolution, step, scan, gate and output projection, all phases (see
`_sambay.py`)."""

from benchmark.layer_metrics import _sambay


def read(run):
    return _sambay.share(run, __file__, "mamba")
