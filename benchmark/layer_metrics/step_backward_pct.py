"""Share of the probe's traced slice's device-busy time in
the backward pass of the program that trains: operations under
`transpose(jvp(...))`, the recomputed forward excepted (see `_step.py`)."""

from benchmark.layer_metrics import _step


def read(run):
    return _step.phase_pct(run, __file__, "backward")
