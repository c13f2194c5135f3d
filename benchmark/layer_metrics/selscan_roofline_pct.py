"""The selective scan against its roofline: the FLOPs of the
recurrence as written (`configs/phi-4-mini-flash-reasoning/flops.py`,
whatever implements it) for the passes the traced slice ran, over the
device time of every leaf operation under `mamba1/scan`, as a share of
min(197 TFLOP/s, 819 GB/s x the recurrence's intensity) (see
`_sambay.py`)."""

from benchmark.layer_metrics import _sambay


def read(run):
    return _sambay.scan_roofline(run, __file__)
