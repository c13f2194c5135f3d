"""The largest `bytes_in_use` + `bytes_reserved` (GB of 1e9 bytes) that
any `jit_window` run of the window carries (per step: `jit_step`): both
from one `memory_stats()` read right after the call was asked for, so
of one moment, where `memory_peak_bytes` adds two peaks of two (see
`_runs.py`). 0.0 where no run carries them."""

from benchmark.layer_metrics import _runs


def read(run):
    runs = _runs.load(run, __file__)
    return _runs.window_resident_gb(runs) if runs else 0.0
