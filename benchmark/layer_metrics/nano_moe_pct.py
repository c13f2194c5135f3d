"""Share of the probe's traced slice's device-busy time in leaf
operations under `moe`: the sigmoid router over 128 outputs, the sort
and gathers, the grouped matmuls of the 8 held squared-ReLU experts of
128 (`ragged-dot-*`, counted here though the compiler drops their
scope) and the shared expert, in the three expert blocks (see
`_ssm.py`)."""

from benchmark.layer_metrics import _ssm


def read(run):
    return _ssm.share(run, __file__, "moe")
