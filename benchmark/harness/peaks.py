"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` jax reports. A device that is not here is an error, not
a default (copied from `bench.PEAK_BF16_TFLOPS`, which a later PR may
delete).

TPU v5e — Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peak(device_kind, what="bf16_flops_per_s"):
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}: add it to "
            "benchmark/harness/peaks.py with its source"
        )
    return PEAKS[device_kind][what]
