"""Share of the probe's traced slice's device-busy time in
the optimizer's update of the program that trains: operations
under the worker's `optimizer` scope (`tx.update` and the add; 0 in
per-step mode, whose optimizer is the master's: `apply_ms`) (see `_step.py`)."""

from benchmark.layer_metrics import _step


def read(run):
    return _step.phase_pct(run, __file__, "optimizer")
