"""Share of the probe's traced slice's device-busy time in leaf
operations under `attention`: the one attention block's projections
and the Pallas kernels at 32 heads of 128 fed by 2 key-value heads
widened sixteenfold (by their `op_name`), all phases; nothing turns
(see `_ssm.py`)."""

from benchmark.layer_metrics import _ssm


def read(run):
    return _ssm.share(run, __file__, "attention")
