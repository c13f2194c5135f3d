"""Share of the probe's traced slice's device-busy time in leaf
operations under `moe`: the softmax router over 512 outputs, the sort
and gathers, the grouped matmuls of the 8 held experts of 512
(`ragged-dot-*`, counted here though the compiler drops their scope)
and the gated shared expert, in the four expert layers (see
`_gdn.py`)."""

from benchmark.layer_metrics import _gdn


def read(run):
    return _gdn.share(run, __file__, "moe")
