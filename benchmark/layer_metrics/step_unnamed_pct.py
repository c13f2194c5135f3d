"""Share of the probe's traced slice's device-busy time in
operations of the program that trains which no phase claims: an
instruction without an `op_name`, or one under neither `jvp(...)` nor
`optimizer` (the window's own loop, a kernel that lost its name);
the `step:` line names the ten longest (see `_step.py`)."""

from benchmark.layer_metrics import _step


def read(run):
    return _step.phase_pct(run, __file__, "unnamed")
