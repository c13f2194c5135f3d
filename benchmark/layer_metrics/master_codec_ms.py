"""Median per update applied in the window of the master's time in
the codec: `rpc.decode` of the request, `grad_decode` (the update's
wire form to an f32 tree), `model_encode` (the model raveled for the
way down, where one goes) and `rpc.encode` of the response, on the
handler's thread (see `_timeline.py`)."""

from benchmark.layer_metrics import _timeline


def read(run):
    updates = _timeline.master_updates(_timeline.load(run, __file__))
    return _timeline.median_ms([u["codec"] for u in updates])
