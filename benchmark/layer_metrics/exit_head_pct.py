"""Share of the probe's traced slice's device-busy time in leaf
operations under the `exit_heads` scope: the four projections onto the
vocabulary slice, the gate and the exit objective (see `_scopes.py`)."""

from benchmark.layer_metrics import _scopes


def read(run):
    return _scopes.read(run, __file__, "exit_heads")
