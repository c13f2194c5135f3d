"""The state-space scan against its roofline: the FLOPs of the chunked
form (`configs/nemotron-3-nano-30b-a3b/flops.py`, chunks of 128
whatever implements it) for the passes of the scan the traced slice
ran, over the device time of every leaf operation under `mamba2/scan`,
as a share of min(197 TFLOP/s, 819 GB/s x the form's intensity) (see
`_ssm.py`)."""

from benchmark.layer_metrics import _ssm


def read(run):
    return _ssm.scan_roofline(run, __file__)
