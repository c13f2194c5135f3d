"""The causal attention kernels at heads of 256 against their roofline:
the calls the traced slice ran under `attention`, each credited
`configs/qwen3-next-80b-a3b/flops.py`'s operations over the triangle's
visible pairs (not the tiles it ran) and its arrays' bytes once, the
least time the chip could take for them (197 TFLOP/s, 819 GB/s) over
the time they took (see `_gdn.py`)."""

from benchmark.layer_metrics import _gdn


def read(run):
    return _gdn.attention_roofline(run, __file__)
