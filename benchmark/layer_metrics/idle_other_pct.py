"""Share of the probe's traced slice in which no operation ran on the
device and the worker's main thread was in none of the input, `compute`
or sync phases: `get_task`, `task_other`, `wait_poll`, `device_wait`,
or between phases (see `_timeline.py`). The rest of `device_idle_pct`;
all of it where the program writes no timeline."""

from benchmark.layer_metrics import _timeline


def read(run):
    return _timeline.idle(run, __file__, "other")
