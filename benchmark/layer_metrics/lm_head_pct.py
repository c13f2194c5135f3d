"""Share of the probe's traced slice's device-busy time in operations of the
program that trains under the LM's `embed` and `head` scopes (the
embedding's gather and scatter-add, the final norm, the logits and
the loss), forward and backward (see `_step.py`)."""

from benchmark.layer_metrics import _step


def read(run):
    return _step.block_pct(run, __file__, "head")
