"""Seconds the workers spent in `setup.model_init` before the window
opened, summed over them: the model initialised on the host (`how:
init`), reported to the master (`report`) and pulled back (`pull`)
(see `_timeline.py`)."""

from benchmark.layer_metrics import _timeline


def read(run):
    timeline = _timeline.load(run, __file__)
    return _timeline.setup_sum_s(timeline, "setup.model_init")
