"""The banded attention kernels against their roofline: the calls the
traced slice ran under `attention/swa`, each credited
`configs/laguna-xs2/flops.py`'s operations over the band's visible
pairs (not the tiles it ran) and its arrays' bytes once, the least time
the chip could take for them (197 TFLOP/s, 819 GB/s) over the time they
took (see `_window.py`)."""

from benchmark.layer_metrics import _window


def read(run):
    return _window.swa_roofline(run, __file__)
