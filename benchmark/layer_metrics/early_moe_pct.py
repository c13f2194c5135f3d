"""Share of the probe's traced slice's device-busy time in leaf
operations under `moe`: the routing behind the early router's product,
the grouped matmuls of the 8 held gated-ReLU experts of 64
(`ragged-dot-*`, counted here though the compiler drops their scope)
and the ReLU-and-multiply between them, in the four expert layers; the
router's own product lies under `router` and is `early_route_pct`'s
(see `_early.py`)."""

from benchmark.layer_metrics import _early


def read(run):
    return _early.share(run, __file__, "moe")
