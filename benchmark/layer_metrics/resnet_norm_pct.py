"""Share of the probe's traced slice's device-busy time in operations of the
program that trains under one of flax's `BatchNorm_<n>` modules,
forward and backward (a fusion counts where its root lies: a norm
fused into the ReLU after it is the bottleneck's, not the norm's) (see `_step.py`)."""

from benchmark.layer_metrics import _step


def read(run):
    return _step.block_pct(run, __file__, "norm")
