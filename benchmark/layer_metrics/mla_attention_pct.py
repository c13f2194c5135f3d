"""Share of the probe's traced slice's device-busy time in leaf
operations under `mla`: latent attention's projections, the latent's
norm, the rotary turn and XLA's materialised causal attention over
192-wide keys and 128-wide values, in all five layers (see `_moe.py`)."""

from benchmark.layer_metrics import _moe


def read(run):
    return _moe.share(run, __file__, "mla")
