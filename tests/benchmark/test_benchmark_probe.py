"""The in-worker probe stays inert everywhere but in a benchmarked
worker, and in one it reports what the device block needs."""

import json
import os
import subprocess
import sys
import time
import types

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

from benchmark.harness import probe  # noqa: E402


def test_inert_in_a_process_whose_main_is_not_the_worker(tmp_path, monkeypatch):
    monkeypatch.setenv(probe.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(probe.ENV_TRACE_SECS, "1")
    assert probe.start_if_worker() is None  # __main__ is pytest
    fake = types.ModuleType("__main__")
    fake.__spec__ = types.SimpleNamespace(name="elasticdl_tpu.master.main")
    monkeypatch.setitem(sys.modules, "__main__", fake)
    assert probe.start_if_worker() is None  # the master loads the zoo too
    assert os.listdir(tmp_path) == []


def test_inert_in_a_worker_without_the_probe_directory(monkeypatch):
    monkeypatch.delenv(probe.ENV_DIR, raising=False)
    fake = types.ModuleType("__main__")
    fake.__spec__ = types.SimpleNamespace(name=probe.WORKER_MAIN)
    monkeypatch.setitem(sys.modules, "__main__", fake)
    assert probe.start_if_worker() is None


def test_the_zoo_modules_call_the_probe_and_nothing_starts_here():
    for config in ("resnet50-224", "lm-dense-160m"):
        path = os.path.join(ROOT, "benchmark", "configs", config, "zoo.py")
        with open(path) as f:
            assert "probe.start_if_worker()" in f.read()
    assert probe._started is None


_FAKE_WORKER = """
import os, sys, time, types
sys.path.insert(0, {root!r})
main = sys.modules["__main__"]
main.__spec__ = types.SimpleNamespace(name="elasticdl_tpu.worker.main")
sys.argv = ["worker", "--worker_id", "7"]
from benchmark.harness import probe
assert probe.start_if_worker() is not None
assert probe.start_if_worker() is None  # once
import jax.numpy as jnp
deadline = time.time() + 90
while time.time() < deadline and not os.path.exists(sys.argv[0] + ".stop"):
    (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    done = [n for n in os.listdir({dir!r}) if n.endswith(".json")]
    if done:
        import json
        rec = json.load(open(os.path.join({dir!r}, done[0])))
        if rec["trace"] and rec["trace"]["state"] not in ("starting", "tracing", "stopping"):
            break
"""


def test_in_a_worker_it_writes_peak_memory_and_traces_the_slice(tmp_path):
    """A process posing as the worker entry point, on the CPU: the
    record appears, the latch starts one trace, and the trace holds
    the slice annotation the reduction clips to."""
    from benchmark.harness import trace_reduce

    probe_dir = tmp_path / "probe"
    probe_dir.mkdir()
    env = {
        **os.environ, "JAX_PLATFORMS": "cpu",
        probe.ENV_DIR: str(probe_dir), probe.ENV_TRACE_SECS: "0.3",
    }
    code = _FAKE_WORKER.format(root=ROOT, dir=str(probe_dir))
    worker = subprocess.Popen([sys.executable, "-c", code], env=env)
    try:
        # the latch drops once the probe's record is there, as the window
        # opens long after a worker's boot: no race with a slow import
        deadline = time.time() + 200
        while time.time() < deadline and not [
            n for n in os.listdir(probe_dir) if n.endswith(".json")
        ]:
            assert worker.poll() is None, "the fake worker exited early"
            time.sleep(0.05)
        (probe_dir / probe.LATCH).write_text(repr(time.time()))
        assert worker.wait(timeout=240) == 0
    finally:
        worker.kill()
        worker.wait()
    records = [n for n in os.listdir(probe_dir) if n.endswith(".json")]
    assert len(records) == 1
    with open(probe_dir / records[0]) as f:
        record = json.load(f)
    assert record["worker_id"] == 7 and record["platform"] == "cpu"
    assert record["memory_peak_bytes"] >= 0
    assert record["trace"]["t1"] - record["trace"]["t0"] >= 0.3
    planes = trace_reduce.load(
        trace_reduce.find_xplane(record["trace"]["dir"])
    )
    lo, hi = trace_reduce.find_slice(planes)
    assert hi - lo >= 0.29e9  # a loaded host may oversleep, never undersleep


def test_a_worker_that_boots_after_the_latch_records_no_trace(tmp_path):
    probe_dir = tmp_path / "probe"
    probe_dir.mkdir()
    (probe_dir / probe.LATCH).write_text(repr(time.time() - 60))
    env = {
        **os.environ, "JAX_PLATFORMS": "cpu",
        probe.ENV_DIR: str(probe_dir), probe.ENV_TRACE_SECS: "0.3",
    }
    code = _FAKE_WORKER.format(root=ROOT, dir=str(probe_dir))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=240)
    (name,) = [n for n in os.listdir(probe_dir) if n.endswith(".json")]
    with open(probe_dir / name) as f:
        assert "skipped" in json.load(f)["trace"]["state"]
    assert not [n for n in os.listdir(probe_dir) if n.startswith("trace-")]
