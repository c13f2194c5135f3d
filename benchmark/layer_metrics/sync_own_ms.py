"""Median over the window's updates of one sync's own work, as the
worker spent it: a `worker.window_sync` span (spawn on the step loop to
settled on the sync thread) less the `worker.chain_wait` it spent
queued behind the syncs before it; in per-step mode the
`report_gradient` and `get_model` phases of one step (see
`_timeline.py`)."""

from benchmark.layer_metrics import _timeline


def read(run):
    return _timeline.sync_own_ms(_timeline.load(run, __file__))
