"""The gated-ReLU experts' grouped matmuls against their roofline:
`experts_roofline_pct`'s rule with this configuration's sizes — the
FLOPs the `ragged-dot` kernels of the traced slice executed on the rows
really routed (from `expert_tokens` of `worker.window_stats`), 2 x rows
x 2560 x 768 a matmul, three a pass of an expert layer, over the device
time of every leaf operation under `moe/experts`, as a share of min(197
TFLOP/s, 819 GB/s x the matmul's intensity) (see `_early.py`;
operations and bytes: `configs/smallthinker-21b-a3b/flops.py`)."""

from benchmark.layer_metrics import _early


def read(run):
    return _early.experts_roofline(run, __file__)
