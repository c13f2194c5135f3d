"""The banded attention kernels under a window of 4096 at 16,384 tokens
against their roofline: the calls the traced slice ran under
`attention/swa`, each credited `configs/smallthinker-21b-a3b/flops.py`'s
operations over the band's visible pairs (not the tiles it ran) and its
arrays' bytes once, the least time the chip could take for them (197
TFLOP/s, 819 GB/s) over the time they took (`swa_roofline_pct`'s rule;
see `_early.py`)."""

from benchmark.layer_metrics import _early


def read(run):
    return _early.band_roofline(run, __file__)
