"""Share of the probe's traced slice's device-busy time in leaf
operations under `looped_stack/.../attention`: the q, k, v and output
projections, the rotary turn and XLA's materialised causal attention
of every layer application — the number that says when the Pallas
kernels should take over (see `_scopes.py`)."""

from benchmark.layer_metrics import _scopes


def read(run):
    return _scopes.read(run, __file__, "attention")
