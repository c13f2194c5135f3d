"""Share of the probe's traced slice's device-busy time in leaf
operations under the `looped_stack` scope: the `total_ut_steps` x
`num_hidden_layers` layer applications of the looped LM, forward,
recomputed and backward (see `_scopes.py`)."""

from benchmark.layer_metrics import _scopes


def read(run):
    return _scopes.read(run, __file__, "stack")
