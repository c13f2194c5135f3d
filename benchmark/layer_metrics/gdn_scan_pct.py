"""Share of the probe's traced slice's device-busy time in leaf
operations under `gdn/scan`: the scalar-decay `intra` stage and the
pass over the chunks of the three Gated DeltaNet layers, all phases
(see `_gdn.py`)."""

from benchmark.layer_metrics import _gdn


def read(run):
    return _gdn.share(run, __file__, "gdn_scan")
