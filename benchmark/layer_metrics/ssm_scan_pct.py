"""Share of the probe's traced slice's device-busy time in leaf
operations under `mamba2/scan`: the step and the decay, the chunks'
own outputs (`intra`), what they add to the state and the pass between
chunks (`state`), the carried state's part and D x (`out`), all phases
(see `_ssm.py`)."""

from benchmark.layer_metrics import _ssm


def read(run):
    return _ssm.share(run, __file__, "ssm_scan")
