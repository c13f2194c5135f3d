"""The expert layer's grouped matmuls against their roofline: the
FLOPs the `ragged-dot` kernels of the traced slice executed on the rows
really routed (from `expert_tokens` of `worker.window_stats`) over the
device time of every leaf operation under `moe/experts`, as a share of
min(197 TFLOP/s, 819 GB/s x the matmul's intensity) (see `_moe.py`;
operations and bytes: `configs/deepseek-v2-lite/flops.py`)."""

from benchmark.layer_metrics import _moe


def read(run):
    return _moe.experts_roofline(run, __file__)
