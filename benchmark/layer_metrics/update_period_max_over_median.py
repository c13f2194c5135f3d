"""The longest gap between consecutive updates the master applied in
the window (its `apply` spans) over the median gap; over 1.5 the period
is taken apart on stderr, with every `proc.stall`, `proc.gc` and
`rpc.server.slow` of either process inside it (see `_runs.py`). 0.0
where the program writes no `worker.device_run`."""

from benchmark.layer_metrics import _runs


def read(run):
    runs = _runs.load(run, __file__)
    return _runs.update_period_max_over_median(runs) if runs else 0.0
