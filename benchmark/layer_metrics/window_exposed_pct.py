"""Of the time from the first `worker.device_run` that starts in the
timed window to the last that ends in it, the share in which no run was
in flight: the whole-window twin of `device_idle_pct`, from the
program's own spans (see `_runs.py`). 0.0 where the program writes no
such span."""

from benchmark.layer_metrics import _runs


def read(run):
    runs = _runs.load(run, __file__)
    return _runs.window_exposed_pct(runs) if runs else 0.0
