"""The scalar-decay delta rule against its roofline: the FLOPs of the
chunked form under one decay a head (`configs/qwen3-next-80b-a3b/
flops.py`, chunks of 64 whatever implements it) for the passes over the
chunks the traced slice ran, over the device time of every leaf
operation under `gdn/scan`, as a share of min(197 TFLOP/s, 819 GB/s x
the form's intensity) (see `_gdn.py`)."""

from benchmark.layer_metrics import _gdn


def read(run):
    return _gdn.scan_roofline(run, __file__)
