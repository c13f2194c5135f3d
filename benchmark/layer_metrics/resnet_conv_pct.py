"""Share of the probe's traced slice's device-busy time in operations of the
program that trains under one of flax's `Conv_<n>` modules, forward
and backward (a fusion counts where its root lies) (see `_step.py`)."""

from benchmark.layer_metrics import _step


def read(run):
    return _step.block_pct(run, __file__, "conv")
