"""`run.py` as functions, end to end on the CPU at a tiny size: a real
`master.main` job with process workers, the probe inside them, the
trace reduced, the line validated. Platform `cpu`, so never a device
metric. Slow (real jax boots), so outside tier-1's count."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

import bench_sandbox  # noqa: E402

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import validate  # noqa: E402

pytestmark = [pytest.mark.slow, pytest.mark.e2e]
DEVICE_ONLY = ("device_idle_pct", "mfu_pct")


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    root = bench_sandbox.copy_benchmark(tmp_path)
    # the job's processes import the program from the repo and the
    # benchmark from the copy (Job puts the copy first)
    monkeypatch.setenv("PYTHONPATH", ROOT)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    return root


@pytest.mark.parametrize("trace", [True, False])
def test_one_worker_cell(checkout, trace):
    cell = bench_sandbox.add_tiny_cell(checkout)
    result, expected = bench_run.run_cell(
        cell, 2**31 + 7, 8.0, trace, root=checkout, platform="cpu"
    )
    faults = validate.check_line(
        json.dumps(result), expected, trace, allow_missing=DEVICE_ONLY
    )
    # the CPU backend reports no memory: the one fault a rehearsal has
    assert faults == ["device.memory_peak_bytes is not a whole number > 0"]
    assert result["correct"], result.get("faults")
    assert result["device"]["platform"] == "cpu"
    assert not set(result["metrics"]) & set(DEVICE_ONLY)
    if trace:
        assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
        assert result["metrics"]["tiny_tasks"]["value"] > 0
        assert result["breakdown"]["device_ops"]
    else:
        assert result["metrics"]["goodput"]["value"] > 0
        assert result["metrics"]["setup_s"]["value"] > 1
