"""Share of the probe's traced slice in which no operation ran on the
device AND the innermost step-loop phase open on the worker's main
thread was `get_batch` or `read_records`: the device waiting for input
(see `_timeline.py`). With `idle_stage_pct`, `idle_sync_pct` and
`idle_other_pct` it sums to `device_idle_pct` of the same run."""

from benchmark.layer_metrics import _timeline


def read(run):
    return _timeline.idle(run, __file__, "input")
