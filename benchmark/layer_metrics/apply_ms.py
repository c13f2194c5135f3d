"""Median `apply` span of the master over the updates it applied in
the window: the delta added to the model, or the `PSOptimizer` step, on
the handler's thread under the model lock; a gradient that only joined
the sum (`kind: accumulate`) is no update (see `_timeline.py`)."""

from benchmark.layer_metrics import _timeline


def read(run):
    updates = _timeline.master_updates(_timeline.load(run, __file__))
    return _timeline.median_ms([u["apply"] for u in updates])
