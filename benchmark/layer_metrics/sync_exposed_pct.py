"""Share of the workers' host time spent in model sync that nothing
hid: the `sync_wait`, `report_gradient`, `get_model` and `rebase`
phases (see `_phases.py`)."""

from benchmark.layer_metrics._phases import share


def read(run):
    return share(
        run, ("sync_wait", "report_gradient", "get_model", "rebase")
    )
