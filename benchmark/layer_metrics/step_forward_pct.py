"""Share of the probe's traced slice's device-busy time in
the forward pass of the program that trains: operations whose
`op_name` lies under `jvp(...)` and not under `transpose(jvp(...))` (see `_step.py`)."""

from benchmark.layer_metrics import _step


def read(run):
    return _step.phase_pct(run, __file__, "forward")
