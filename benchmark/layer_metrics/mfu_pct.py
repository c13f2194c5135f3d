"""Model FLOP/s utilization over the measured window: FLOPs a sample
requires (forward + backward from shapes, `harness/flops.py`) x the
samples trained and applied (recomputed ones subtracted), over the
span they took, the cell's chips and the chip's published bf16 peak
(`harness/peaks.py`). The same records and span as `goodput`, and so
held to the window's edges by the same fault (`run.goodput_span`)."""


def read(run):
    if run["platform"] != "tpu":
        return None
    rate = run["goodput_records"] / run["goodput_span_s"] / run["chips"]
    return 100.0 * rate * run["flops_per_sample"] / run["peak_flops_per_s"]
