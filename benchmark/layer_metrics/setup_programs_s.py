"""Seconds the workers spent in `setup.program` before the window
opened, summed over them: the first call of each jitted program —
trace, lower, compile or load from the compile cache, dispatch (see
`_timeline.py`)."""

from benchmark.layer_metrics import _timeline


def read(run):
    timeline = _timeline.load(run, __file__)
    return _timeline.setup_sum_s(timeline, "setup.program")
