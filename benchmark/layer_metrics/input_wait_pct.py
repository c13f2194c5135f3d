"""Share of the workers' host time spent fetching and decoding input:
the `get_batch` and `read_records` phases (see `_phases.py`)."""

from benchmark.layer_metrics._phases import share


def read(run):
    return share(run, ("get_batch", "read_records"))
