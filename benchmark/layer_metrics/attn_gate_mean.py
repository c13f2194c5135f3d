"""The mean of the attention's per-head output gate sigmoid(x . w_h)
over heads, tokens and layers of a window's last step, averaged over
the window's `worker.window_stats` spans: 0.5 untrained; a drift to 0
or 1 says the gate saturates (see `_window.py`). A health reading: the
manifest wants a `better` and has `higher`, which carries no meaning
here, and the reading moves no rate."""

from benchmark.layer_metrics import _window


def read(run):
    return _window.gate_mean(run, __file__)
