"""Median length of the `worker.device_run` spans that end an update
period (per step: of a step): how long the device took over a window,
as the host saw it (see `_runs.py`). 0.0 where the program writes no
such span."""

from benchmark.layer_metrics import _runs


def read(run):
    runs = _runs.load(run, __file__)
    return _runs.window_device_ms(runs) if runs else 0.0
