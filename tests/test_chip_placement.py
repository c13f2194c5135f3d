"""Who holds the chip, and where the compile cache lives.

The master and the shards stay off the TPU, each worker process gets
its own chips, nothing computes on the CPU by accident, and the
persistent compile cache sits at one path that can be placed from
outside (common/device.py, common/args.py, cluster/pod_backend.py).
"""

import itertools
import json
import os
import subprocess
import sys
import tempfile
import types

import pytest

from elasticdl_tpu.cluster import pod_backend
from elasticdl_tpu.common import args as args_mod
from elasticdl_tpu.common import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -- compile cache resolver ---------------------------------------------------


def _args(**kw):
    return types.SimpleNamespace(
        **{"compile_cache_dir": "auto", "worker_backend": "process", **kw}
    )


def test_cache_from_outside_is_never_overridden(monkeypatch):
    monkeypatch.setenv(args_mod.ENV_COMPILE_CACHE_DIR, "/some/dir")
    for flag in ("auto", "", "/elsewhere"):
        envs = args_mod.resolve_compile_cache_envs(_args(compile_cache_dir=flag))
        assert args_mod.ENV_COMPILE_CACHE_DIR not in envs
        assert envs["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"


def test_default_cache_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(args_mod.ENV_COMPILE_CACHE_DIR, raising=False)
    first = args_mod.resolve_compile_cache_envs(_args())
    again = args_mod.resolve_compile_cache_envs(_args())
    assert first == again == args_mod.resolve_compile_cache_envs()
    path = first[args_mod.ENV_COMPILE_CACHE_DIR]
    assert path == os.path.join(REPO, ".jax_cache")
    assert not path.startswith(tempfile.gettempdir())
    assert first["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"


def test_cache_flag_arms(monkeypatch):
    monkeypatch.delenv(args_mod.ENV_COMPILE_CACHE_DIR, raising=False)
    resolve = args_mod.resolve_compile_cache_envs
    assert resolve(_args(compile_cache_dir="")) == {}
    assert resolve(_args(compile_cache_dir="/mnt/x"))[
        args_mod.ENV_COMPILE_CACHE_DIR
    ] == "/mnt/x"
    # pods need a shared volume: auto is off on k8s
    assert resolve(_args(worker_backend="k8s")) == {}


# -- one process for each chip ------------------------------------------------


def test_chip_shares_are_even_and_disjoint():
    chips = [0, 1, 2, 3]
    assert device.chip_shares(chips, 4) == [(0,), (1,), (2,), (3,)]
    assert device.chip_shares(chips, 2) == [(0, 1), (2, 3)]
    assert device.chip_shares(chips, 1) == [(0, 1, 2, 3)]
    assert device.chip_shares(chips, 3) == [(0,), (1,), (2,)]
    with pytest.raises(ValueError, match="need a chip each"):
        device.chip_shares(chips, 5)  # e.g. 4 workers + a warm standby
    with pytest.raises(ValueError, match="need a chip each"):
        device.chip_shares([0], 2)
    assert device.chip_shares(list(range(8)), 1) == [tuple(range(8))]


def test_free_share_and_chip_env():
    shares = device.chip_shares([0, 1, 2, 3], 4)
    assert device.free_share(shares, []) == (0,)
    assert device.free_share(shares, [(0,), (1,), (3,)]) == (2,)
    with pytest.raises(RuntimeError, match="no free chip"):
        device.free_share(shares, shares)
    assert device.chip_env((2,), 4) == {
        "TPU_VISIBLE_CHIPS": "2",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
    # the whole host is libtpu's default: nothing to stamp
    assert device.chip_env((0, 1, 2, 3), 4) == {}
    assert device.chip_env((0,), 1) == {}


class _FakeProc:
    _pids = itertools.count(1000)

    def __init__(self, cmd, env=None, **_kw):
        self.env = env
        self.pid = next(self._pids)
        self.returncode = None

    def poll(self):
        return self.returncode

    def terminate(self):
        self.returncode = -15

    kill = terminate

    def wait(self, timeout=None):
        return self.returncode


@pytest.fixture
def fake_popen(monkeypatch):
    spawned = []

    def popen(cmd, **kw):
        spawned.append(_FakeProc(cmd, **kw))
        return spawned[-1]

    monkeypatch.setattr(pod_backend.subprocess, "Popen", popen)
    return spawned


def test_backend_gives_each_worker_its_own_chip(monkeypatch, fake_popen):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    backend = pod_backend.ProcessBackend(
        chip_shares=device.chip_shares([0, 1, 2, 3], 4)
    )
    try:
        for wid in range(4):
            backend.start_worker(wid, [], {})
        assert [p.env["TPU_VISIBLE_CHIPS"] for p in fake_popen] == list("0123")
        with pytest.raises(RuntimeError, match="no free chip"):
            backend.start_worker(4, [], {})
        # worker 2 is preempted: its replacement (a fresh id) takes the
        # chip it released, and nobody else's
        fake_popen[2].returncode = -9
        backend.start_worker(5, [], {})
        assert fake_popen[-1].env["TPU_VISIBLE_CHIPS"] == "2"
    finally:
        backend.stop()


def test_backend_stamps_nothing_for_cpu_workers(monkeypatch, fake_popen):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    for name in device.chip_env((0,), 4):
        monkeypatch.delenv(name, raising=False)
    backend = pod_backend.ProcessBackend(
        chip_shares=device.chip_shares([0, 1, 2, 3], 4)
    )
    plain = pod_backend.ProcessBackend()
    try:
        backend.start_worker(0, [], {"JAX_PLATFORMS": "cpu"})
        plain.start_worker(0, [], {})
        stamped = set(device.chip_env((0,), 4))
        for proc in fake_popen:
            assert not stamped & set(proc.env)
    finally:
        backend.stop()
        plain.stop()


# -- nobody computes on the wrong device --------------------------------------


def test_master_pins_the_cpu_whatever_the_environment_says(monkeypatch, capsys):
    import jax

    from elasticdl_tpu.master import main as master_main

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    jax.config.update("jax_platforms", "tpu")
    try:
        with pytest.raises(SystemExit):
            master_main.main(["--help"])  # argparse exits after the pin
        assert jax.config.jax_platforms == "cpu"
    finally:
        jax.config.update("jax_platforms", "cpu")
    capsys.readouterr()


def test_no_chip_and_no_cpu_request_exits(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="refusing to compute on the CPU"):
        device.require_device("worker 0")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    report = device.require_device("worker 0")
    assert report["platform"] == "cpu" and report["chips"]


def test_compiled_kernels_refuse_the_cpu():
    import jax.numpy as jnp

    from elasticdl_tpu.ops.flash_attention import BLOCK, flash_attention

    q = jnp.zeros((1, BLOCK, 1, 64), jnp.bfloat16)
    with pytest.raises(RuntimeError, match="TPU only"):
        flash_attention(q, q, q)


# -- the smoke itself, cut down ------------------------------------------------
# (outside tier-1, whose `-m "not slow"` replaces pytest.ini's "not e2e")


@pytest.mark.e2e
@pytest.mark.slow
def test_chip_smoke_fails_without_a_chip_and_passes_cut_down_on_the_cpu():
    smoke = [sys.executable, os.path.join(REPO, "chip_smoke.py")]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    no_chip = subprocess.run(
        smoke, env=env, capture_output=True, text=True, timeout=300
    )
    assert no_chip.returncode != 0 and no_chip.stdout == ""
    assert "needs a TPU" in no_chip.stderr
    cut_down = subprocess.run(
        smoke + ["--cpu"], env=env, capture_output=True, text=True,
        timeout=1200,
    )
    assert cut_down.returncode == 0, cut_down.stderr[-4000:]
    record, verdict = map(json.loads, cut_down.stdout.strip().splitlines())
    # the last line is the driver's contract: these keys and no others
    assert verdict == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    for job in ("window_job", "per_step_job"):
        assert record["phases"][job]["exit_code"] == 0
        assert not record["phases"][job]["master_held_tpu"]
    assert record["phases"]["window_job"]["killed_worker"] is not None
