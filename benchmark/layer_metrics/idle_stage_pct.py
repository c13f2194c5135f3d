"""Share of the probe's traced slice in which no operation ran on the
device AND the innermost step-loop phase open on the worker's main
thread was `compute`: the host stacking the window's batches and the
dispatch that copies them in, while the device has nothing to run (see
`_timeline.py`). One of the four shares `device_idle_pct` splits into."""

from benchmark.layer_metrics import _timeline


def read(run):
    return _timeline.idle(run, __file__, "stage")
