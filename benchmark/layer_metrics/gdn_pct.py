"""Share of the probe's traced slice's device-busy time in leaf
operations under `gdn`: the three Gated DeltaNet layers' projections,
convolution, gates, scan and output norm and gate, all phases (see
`_gdn.py`)."""

from benchmark.layer_metrics import _gdn


def read(run):
    return _gdn.share(run, __file__, "gdn")
