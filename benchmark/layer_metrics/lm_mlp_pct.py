"""Share of the probe's traced slice's device-busy time in operations of the
program that trains under the dense LM's `mlp` scope (norm, the two
matmuls, GELU and the residual add), forward and backward (see `_step.py`)."""

from benchmark.layer_metrics import _step


def read(run):
    return _step.block_pct(run, __file__, "mlp")
