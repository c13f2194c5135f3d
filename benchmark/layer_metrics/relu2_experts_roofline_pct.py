"""The squared-ReLU experts' grouped matmuls against their roofline:
`experts_roofline_pct`'s rule with this configuration's sizes — the
FLOPs the `ragged-dot` kernels of the traced slice executed on the rows
really routed (from `expert_tokens` of `worker.window_stats`), 2 x rows
x 2688 x 1856 a matmul, two a pass of an expert block, over the device
time of every leaf operation under `moe/experts`, as a share of min(197
TFLOP/s, 819 GB/s x the matmul's intensity) (see `_ssm.py`; operations
and bytes: `configs/nemotron-3-nano-30b-a3b/flops.py`)."""

from benchmark.layer_metrics import _ssm


def read(run):
    return _ssm.experts_roofline(run, __file__)
