"""The expert layer's grouped matmuls against their roofline:
`experts_roofline_pct`'s rule with this configuration's sizes — the
FLOPs the `ragged-dot` kernels of the traced slice executed on the rows
really routed (from `expert_tokens` of `worker.window_stats`), 2 x rows
x 2048 x 1536 a matmul, over the device time of every leaf operation
under `moe/experts`, as a share of min(197 TFLOP/s, 819 GB/s x the
matmul's intensity) (see `_shortconv.py`; operations and bytes:
`configs/lfm2-24b-a2b/flops.py`)."""

from benchmark.layer_metrics import _shortconv


def read(run):
    return _shortconv.experts_roofline(run, __file__)
