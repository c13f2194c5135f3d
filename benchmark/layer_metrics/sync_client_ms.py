"""Median per update of the worker's host work in a sync: the copy out
of the device (`worker.d2h`), `worker.quantize` / `worker.encode` where
the wire form is compressed, and `rpc.client.encode` and
`rpc.client.decode` of the update's RPC; a span inside another is
counted once (see `_timeline.py`)."""

from benchmark.layer_metrics import _timeline


def read(run):
    return _timeline.sync_client_ms(_timeline.load(run, __file__))
