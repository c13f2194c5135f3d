"""Share of the probe's traced slice's device-busy time in leaf
operations under `shortconv`: the double-gated short convolution's
input projection, its two gatings and three taps, and its output
projection, in the four conv layers, all phases (see `_shortconv.py`)."""

from benchmark.layer_metrics import _shortconv


def read(run):
    return _shortconv.share(run, __file__, "shortconv")
