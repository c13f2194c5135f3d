"""Share of the probe's traced slice in which no operation ran on the
device AND the innermost step-loop phase open on the worker's main
thread was `sync_wait`, `report_gradient`, `get_model` or `rebase`: the
device waiting for the sync (see `_timeline.py`). One of the four
shares `device_idle_pct` splits into."""

from benchmark.layer_metrics import _timeline


def read(run):
    return _timeline.idle(run, __file__, "sync")
