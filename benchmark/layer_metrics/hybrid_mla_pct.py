"""Share of the probe's traced slice's device-busy time in leaf
operations under `mla`: the one latent-attention layer of the hybrid
stack, nothing rotated, XLA's materialised causal attention over
192-wide keys and 128-wide values (see `_hybrid.py`)."""

from benchmark.layer_metrics import _hybrid


def read(run):
    return _hybrid.share(run, __file__, "mla")
