"""Median over the window's updates of the gap between the
`worker.device_run` that ends an update period and the run after it, as
far as the step loop's innermost phase charges it to the sync
(`_timeline.SYNC`; a moment under `worker.window_wait` is the device's).
Its four parts go to stderr (see `_runs.py`). 0.0 where the program
writes no such span."""

from benchmark.layer_metrics import _runs


def read(run):
    runs = _runs.load(run, __file__)
    return _runs.exposed_sync_ms(runs) if runs else 0.0
