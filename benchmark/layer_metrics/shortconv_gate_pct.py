"""Share of the probe's traced slice's device-busy time in leaf
operations under `shortconv/gate_conv`: b * u, the three depthwise taps
and c * y, pure memory traffic between the mixer's two matmuls — the
stage a fused kernel would take (see `_shortconv.py`)."""

from benchmark.layer_metrics import _shortconv


def read(run):
    return _shortconv.share(run, __file__, "gate")
