"""What the worker writes of its compiled programs
(`worker-<id>.hlo_scopes.json`, `setup.scope_map`): every jitted
program of the training path, in window and in per-step mode; names
that are this source's, or `stale`; scopes that change no code."""

import contextlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from elasticdl_tpu.master.ps_optimizer import PSOptimizer
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.obs import hlo_scopes, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_recorder():
    trace.configure(0.0)
    trace.RECORDER.clear()
    yield
    trace.RECORDER.clear()
    trace.configure(None)


def _spans(name):
    return [s for s in trace.RECORDER.snapshot() if s["name"] == name]


def _train(tmp_path, local_updates, records=128):
    """A real Worker against a real servicer, in process."""
    from elasticdl_tpu.api.model_spec_helpers import spec_from_module
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.testing import InProcessMaster, write_linear_records
    from elasticdl_tpu.worker.worker import Worker
    from tests.fixtures import linear_module

    path = str(tmp_path / "train.rio")
    write_linear_records(path, records, noise=0.05)
    dispatcher = TaskDispatcher({path: records}, {}, {}, 64, 1)
    servicer = MasterServicer(
        grads_to_wait=1,
        optimizer=PSOptimizer(linear_module.optimizer()),
        task_dispatcher=dispatcher,
    )
    worker = Worker(0, InProcessMaster(servicer),
                    spec_from_module(linear_module),
                    minibatch_size=16, local_updates=local_updates)
    worker.run()
    worker.close()
    assert dispatcher.finished()


@pytest.mark.parametrize("local_updates, trains", [
    (2, "jit_window"), (0, "jit_step"),
], ids=["window", "perstep"])
def test_a_job_leaves_the_map_of_every_program_it_dispatched(
    tmp_path, monkeypatch, local_updates, trains
):
    logs = tmp_path / "logs"
    logs.mkdir()
    monkeypatch.setenv("EDL_WORKER_LOG_DIR", str(logs))
    _train(tmp_path, local_updates)
    with open(logs / "worker-0.hlo_scopes.json") as f:
        record = json.load(f)
    # every jitted callable whose first call got a `setup.program`
    # span; the eager operations have no callable to lower, and no map
    dispatched = {s["args"]["program"] for s in _spans("setup.program")}
    eager = {"jit_copy", "jit_subtract"}
    assert set(record["programs"]) == dispatched - eager
    assert trains in dispatched and record["program"] == trains
    assert record["instructions"] == record["programs"][trains]["instructions"]
    for name, program in record["programs"].items():
        assert program["instructions"], name
        assert sorted(program["memory"]) == sorted(hlo_scopes.MEMORY_FIELDS)
        assert all(type(v) is int for v in program["memory"].values())
        assert program["stale"] is False and program["missing"] == []
        assert program["count"] >= len(program["instructions"])
    # one span a program, with what the map cost and whether it holds
    spans = {s["args"]["program"]: s["args"] for s in _spans("setup.scope_map")}
    assert set(spans) == set(record["programs"])
    assert len(_spans("setup.scope_map")) == len(spans)
    for name, args in spans.items():
        program = record["programs"][name]
        assert args["instructions"] == program["count"]
        assert args["named"] == len(program["instructions"])
        assert args["temp_bytes"] == program["memory"]["temp"]
        assert args["argument_bytes"] == program["memory"]["argument"]
        assert args["stale"] is False and "missing" not in args
    # the optimizer runs in the worker's program in window mode alone
    paths = record["instructions"].values()
    assert any("/optimizer/" in p for p in paths) == bool(local_updates)


_STALE_SCRIPT = """
import contextlib, json, os, sys
import jax, jax.numpy as jnp
from elasticdl_tpu.common.timing import PhaseTimers
from elasticdl_tpu.worker.worker import Worker

scoped = sys.argv[1] == "scoped"

def window(x):
    with jax.named_scope("attention") if scoped else contextlib.nullcontext():
        return jnp.tanh(x @ x) * 2.0

worker = Worker.__new__(Worker)
worker._id = 0
worker.timers = PhaseTimers()
program = jax.jit(window)
args = (jnp.ones((8, 8)),)
with worker._first_call(program, args):
    program(*args)
with open(os.path.join(os.environ["EDL_WORKER_LOG_DIR"],
                       "worker-0.hlo_scopes.json")) as f:
    print(json.dumps(json.load(f)["programs"]["jit_window"]))
"""


def _compile_in_a_process(tmp_path, cache_envs, scoped):
    logs = tmp_path / f"logs-{scoped}"
    logs.mkdir()
    env = {
        **os.environ, **cache_envs, "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO, "EDL_WORKER_LOG_DIR": str(logs),
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
    }
    done = subprocess.run(
        [sys.executable, "-c", _STALE_SCRIPT, scoped],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("as_configured", [False, True], ids=[
    "a_cache_that_leaves_names_out_of_its_key", "the_worker_s_cache",
])
def test_a_cache_that_outlives_an_edit_never_passes_old_names_off_as_new(
    tmp_path, monkeypatch, as_configured
):
    """One persisted cache directory, a program compiled without and
    then with a scope. jax's default key leaves metadata out, so the
    second compile is a hit that carries the first one's names: the map
    says `stale`. With the worker's own cache settings
    (`resolve_compile_cache_envs`) the edited program compiles once
    more and carries the scope."""
    from elasticdl_tpu.common import args as args_mod

    cache = str(tmp_path / "cache")
    monkeypatch.setenv(args_mod.ENV_COMPILE_CACHE_DIR, cache)
    envs = {args_mod.ENV_COMPILE_CACHE_DIR: cache}
    if as_configured:
        envs.update(args_mod.resolve_compile_cache_envs())
        assert envs["JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"] == "1"
    else:
        envs["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    before = _compile_in_a_process(tmp_path, envs, "plain")
    assert before["stale"] is False
    assert os.listdir(cache)  # the first compile was kept
    after = _compile_in_a_process(tmp_path, envs, "scoped")
    named = any("attention" in p for p in after["instructions"].values())
    assert named or after["stale"], "old names passed off as this source's"
    assert named == as_configured
    assert after["stale"] == (not as_configured)
    if after["stale"]:
        assert after["missing"] == ["attention"]
    # scopes are metadata: the same code either way
    assert after["count"] == before["count"]


def _lm_window_record(monkeypatch, scoped):
    """`describe` of the tiny dense LM's window program, as the worker
    builds it; without `scoped`, every `jax.named_scope` is a no-op."""
    import jax

    from elasticdl_tpu.api.model_spec_helpers import spec_from_module
    from elasticdl_tpu.models import transformer_lm_zoo
    from elasticdl_tpu.testing import InProcessMaster
    from elasticdl_tpu.worker.worker import Worker

    if not scoped:
        monkeypatch.setattr(
            jax, "named_scope", lambda _name: contextlib.nullcontext()
        )
    spec = spec_from_module(transformer_lm_zoo)
    servicer = MasterServicer(
        grads_to_wait=1, optimizer=PSOptimizer(spec.optimizer())
    )
    worker = Worker(0, InProcessMaster(servicer), spec, minibatch_size=2,
                    local_updates=2)
    tokens = np.zeros((2, 2, 8), np.int32)
    worker._lazy_init_model(tokens[0])
    window = worker._build_local_window_fn()
    opt_state = spec.optimizer().init(worker._flat)
    lowered = window.lower(worker._flat, opt_state, worker._aux, tokens, tokens)
    return hlo_scopes.describe(lowered, lowered.compile())


def test_the_dense_lm_s_program_names_its_blocks_and_is_the_unscoped_one(
    monkeypatch,
):
    scoped = _lm_window_record(monkeypatch, scoped=True)
    named = hlo_scopes.scopes(scoped["instructions"].values())
    assert {"attention", "mlp", "embed", "head", "optimizer"} <= named
    assert scoped["stale"] is False
    bare = _lm_window_record(monkeypatch, scoped=False)
    assert not {"attention", "mlp", "embed", "head", "optimizer"} & (
        hlo_scopes.scopes(bare["instructions"].values())
    )
    # scopes are metadata: not an instruction more or less, named or not
    assert scoped["count"] == bare["count"]
    assert len(scoped["instructions"]) == len(bare["instructions"])
    assert scoped["memory"] == bare["memory"]


# the v5e compiler's text round a Pallas kernel, cut to what is read:
# a forward call inside a scanned layer, its two backward calls, a
# kernel outside every scope, and a plain fusion that is no kernel
_KERNEL_HLO = """\
HloModule jit_window

%fused_computation (p: bf16[24,2048,64]) -> bf16[24,2048,64] {
  ROOT %multiply.1 = bf16[24,2048,64]{2,1,0} multiply(%p, %p), metadata={op_name="jit(window)/while/body/jvp(attention)/mul"}
}

ENTRY %main {
  %fusion.7 = bf16[24,2048,64]{2,1,0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(window)/while/body/jvp(attention)/mul"}
  %attention.30 = (bf16[24,2048,64]{2,1,0:T(8,128)(2,1)}, f32[24,2048,1]{2,1,0:T(8,128)}) custom-call(%fusion.7, %k, %v), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[24,2048,64]{2,1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(window)/while/body/jvp(attention)/pallas_call" stack_frame_id=10}, backend_config={"custom_call_config": {"body": "TUxJUg=="}}
  %attention.31 = bf16[24,2048,64]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(window)/while/body/transpose(jvp(attention))/pallas_call" stack_frame_id=2}
  %attention.32 = (bf16[24,2048,64]{2,1,0}, bf16[24,2048,64]{2,1,0}) custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(window)/while/body/transpose(jvp(attention))/pallas_call" stack_frame_id=2}
  %pallas_call.4 = f32[8,128]{1,0} custom-call(%y), custom_call_target="tpu_custom_call", metadata={op_name="jit(window)/jit(helper)/pallas_call"}
  ROOT %sort.2 = f32[8]{0} custom-call(%z), custom_call_target="SomeOtherTarget", metadata={op_name="jit(window)/optimizer/sort"}
}
"""


def test_the_map_counts_a_program_s_pallas_kernels_by_their_scope(
    monkeypatch,
):
    """`kernels`: Mosaic's custom calls under the innermost scope of
    their `op_name`, `jvp(...)` and `transpose(...)` looked through;
    other custom calls and plain fusions are not kernels; a program
    without one reads none (the dense LM's window on the CPU, where
    `attention` never takes the kernels)."""
    assert hlo_scopes.kernels(_KERNEL_HLO) == {"attention": 3, "": 1}
    assert hlo_scopes.op_names(_KERNEL_HLO)["attention.30"].endswith(
        "jvp(attention)/pallas_call"
    )
    assert hlo_scopes.kernels("HloModule empty\n") == {}
    record = _lm_window_record(monkeypatch, scoped=True)
    assert record["kernels"] == {}
    assert sum(record["kernels"].values()) == 0
