"""Share of the probe's traced slice's device-busy time in leaf
operations under `attention/global`: the one full-attention layer's
projections, the widening of 4 key-value heads to 28, the causal Pallas
kernels over the whole triangle of 16,384 tokens (by their `op_name`)
and the output projection, all phases; nothing turns there, so no
`rope` scope lies under it (see `_early.py`)."""

from benchmark.layer_metrics import _early


def read(run):
    return _early.share(run, __file__, "global")
