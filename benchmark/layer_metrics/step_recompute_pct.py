"""Share of the probe's traced slice's device-busy time in
the recomputation the backward pass asks for: operations of the
program that trains under `transpose(jvp(...))` and `rematted_computation`
(a layer application run again under `jax.checkpoint`) (see `_step.py`)."""

from benchmark.layer_metrics import _step


def read(run):
    return _step.phase_pct(run, __file__, "recompute")
