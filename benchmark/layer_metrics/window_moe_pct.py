"""Share of the probe's traced slice's device-busy time in leaf
operations under `moe`: the softmax router, the sort and gathers, the
grouped matmuls of the 16 held experts of 512 (`ragged-dot-*`, counted
here though the compiler drops their scope) and the shared expert, in
the four expert layers (see `_window.py`)."""

from benchmark.layer_metrics import _window


def read(run):
    return _window.share(run, __file__, "moe")
