"""Share of the probe's traced slice's device-busy time in leaf
operations under `mamba2`: the three Mamba-2 blocks' projection,
convolution, scan, gate and grouped norm and output projection, all
phases (see `_ssm.py`)."""

from benchmark.layer_metrics import _ssm


def read(run):
    return _ssm.share(run, __file__, "ssm")
