"""The KDA recurrence against its roofline: the FLOPs of the chunked
form (`configs/kimi-linear-48b-a3b/flops.py`, chunks of 64 whatever
implements it) for the passes over the chunks the traced slice ran,
over the device time of every leaf operation under `kda/scan`, as a
share of min(197 TFLOP/s, 819 GB/s x the form's intensity) (see
`_hybrid.py`)."""

from benchmark.layer_metrics import _hybrid


def read(run):
    return _hybrid.scan_roofline(run, __file__)
