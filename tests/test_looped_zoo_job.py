"""A looped LM (the Ouro block at a tiny size: SwiGLU, four norms a
layer, rotary base 1e6, the stack run three times with an exit gate and
the three-exit loss) trains through `master.main` with process workers,
in window mode and in per-step mode, by the worker's own programs, and
ends at the exact version. Beside `tests/test_transformer_zoo_job.py`,
whose dense and MoE jobs run the same adapter."""

import glob
import json
import math
import os

import jax.numpy as jnp
import pytest

from elasticdl_tpu.master.checkpoint import load_model_file
from elasticdl_tpu.master.main import main as master_main
from elasticdl_tpu.models import transformer_lm_zoo as zoo
from elasticdl_tpu.models.record_codec import write_learnable_token_records

MODELS_DIR = os.path.join(
    os.path.dirname(__file__), "..", "elasticdl_tpu", "models"
)
VOCAB, SEQ, RECORDS, MINIBATCH, EPOCHS = 64, 24, 256, 32, 3
LOOPED = "vocab=64,n_loops=3,mlp='swiglu',sandwich_norm=True,rope_base=1000000.0"


def _final(ckpt_path, data_path):
    from elasticdl_tpu.data.recordio import RecordIOReader

    model = load_model_file(ckpt_path)
    with RecordIOReader(data_path) as r:
        records = list(r.read_range(0, 64))
    feats, labels = zoo.dataset_fn(records, "training")
    lm = zoo.custom_model(
        vocab=VOCAB, n_loops=3, mlp="swiglu", sandwich_norm=True,
        rope_base=1e6,
    )
    outputs = lm.apply({"params": model.params}, jnp.asarray(feats))
    return model, outputs, float(zoo.loss(outputs, jnp.asarray(labels)))


@pytest.mark.parametrize("local_updates", [4, 0], ids=["window", "perstep"])
def test_looped_lm_trains_through_master_main(tmp_path, monkeypatch, local_updates):
    tmp = str(tmp_path)
    data = os.path.join(tmp, "tokens.rio")
    write_learnable_token_records(data, RECORDS, SEQ, VOCAB, seed=2)
    output = os.path.join(tmp, "final.ckpt")
    logs = os.path.join(tmp, "logs")
    monkeypatch.setenv("EDL_WORKER_LOG_DIR", logs)
    rc = master_main(
        [
            "--model_zoo", MODELS_DIR,
            "--model_def", "transformer_lm_zoo.custom_model",
            "--model_params", LOOPED,
            "--minibatch_size", str(MINIBATCH),
            "--training_data_dir", data,
            "--records_per_task", "128",
            "--num_epochs", str(EPOCHS),
            "--grads_to_wait", "1",
            "--local_updates", str(local_updates),
            "--num_workers", "1",
            "--worker_backend", "process",
            "--output", output,
        ]
    )
    assert rc == 0
    model, outputs, final = _final(output, data)
    # one version a minibatch trained and applied, none twice, none lost
    assert model.version == EPOCHS * RECORDS // MINIBATCH
    assert outputs.logits.shape[0] == outputs.gates.shape[0] == 3
    # untrained: ln 64 - 0.1 H(q) = 4.1; the sequences are deterministic
    assert final < 0.5 * math.log(VOCAB), f"loss {final:.3f} did not fall"
    if not local_updates:
        return
    # the window program's scope map and the exit distribution's span
    with open(os.path.join(logs, "worker-0.hlo_scopes.json")) as f:
        scopes = json.load(f)
    assert scopes["program"] == "jit_window"
    paths = scopes["instructions"].values()
    for want in ("looped_stack", "attention", "mlp", "exit_heads"):
        assert any(want in p for p in paths), want
    spans = []
    for path in glob.glob(os.path.join(logs, "worker-0.spans.jsonl")):
        with open(path) as f:
            spans += [json.loads(line) for line in f if line.strip()]
    stats = [s for s in spans if s["name"] == "worker.window_stats"]
    assert stats, sorted({s["name"] for s in spans})
    q = stats[-1]["args"]["exit_q"]
    assert len(q) == 3 and sum(q) == pytest.approx(1.0, abs=1e-4)
    assert stats[-1]["args"]["expected_exit"] == pytest.approx(
        sum((t + 1) * v for t, v in enumerate(q)), abs=1e-4
    )


def test_the_scope_map_is_written_where_asked_and_costs_no_compile(
    tmp_path, monkeypatch
):
    """A log directory alone makes the worker write it, on the way out
    of the program's first call; the text and the memory analysis come
    from the executable of that call, donated arguments and all: jax's
    own counters see no second lowering and no second compile."""
    import jax
    from jax import monitoring

    from elasticdl_tpu.common.timing import PhaseTimers
    from elasticdl_tpu.obs import trace
    from elasticdl_tpu.worker.worker import Worker

    worker = Worker.__new__(Worker)
    worker._id = 0
    worker.timers = PhaseTimers(sink=trace.record_phase)

    def window(x):
        with jax.named_scope("looped_stack"):
            return jnp.tanh(x) * 2.0

    program = jax.jit(window, donate_argnums=(0,))
    monkeypatch.delenv("EDL_WORKER_LOG_DIR", raising=False)
    worker._write_scope_map(program, (jnp.ones((4,)),))
    assert os.listdir(tmp_path) == []  # no directory, no file
    monkeypatch.setenv("EDL_WORKER_LOG_DIR", str(tmp_path))
    seen = []

    def listener(event, _seconds, **_kw):
        seen.append(event)

    args = (jnp.ones((4,)),)
    trace.RECORDER.clear()
    monitoring.register_event_duration_secs_listener(listener)
    try:
        with worker._first_call(program, args):
            program(*args)
            assert args[0].is_deleted()
            called = list(seen)
    finally:
        monitoring.unregister_event_duration_listener(listener)
    assert [e for e in called if "backend_compile" in e], called
    later = seen[len(called):]  # what the map's writing added: nothing
    assert not [e for e in later if "mlir" in e or "backend_compile" in e], later
    with worker._first_call(program, args):
        pass  # a later call: no span, no second map
    with open(tmp_path / "worker-0.hlo_scopes.json") as f:
        record = json.load(f)
    assert record["program"] == "jit_window"
    assert any("looped_stack" in p for p in record["instructions"].values())
    assert record["programs"]["jit_window"]["instructions"] == record["instructions"]
    spans = [s for s in trace.RECORDER.snapshot() if s["name"].startswith("setup.")]
    assert [s["name"] for s in spans] == ["setup.program", "setup.scope_map"]
    program_span, map_span = spans
    # outside `setup.program`, so `setup_programs_s` does not move
    assert map_span["ts"] >= program_span["ts"] + program_span["dur"] - 1e-6
    assert map_span["args"]["program"] == "jit_window"
    assert map_span["args"]["stale"] is False
    assert 0 < map_span["args"]["named"] <= map_span["args"]["instructions"]
    trace.RECORDER.clear()


@pytest.mark.parametrize("setting", [
    {"mlp": "swiglu"}, {"sandwich_norm": True}, {"n_loops": 4},
])
def test_the_mesh_path_refuses_the_new_settings_by_name(setting):
    from elasticdl_tpu.models import transformer_lm as lm

    cfg = lm.TransformerConfig(**setting)
    with pytest.raises(NotImplementedError, match="plain_forward"):
        lm.param_partition_specs(cfg)
    with pytest.raises(NotImplementedError, match="mesh path"):
        lm.build_train_step(cfg, lm.make_mesh_for(2), zoo.optimizer())
    # the settings the mesh path does take are passed through
    lm.param_partition_specs(lm.TransformerConfig(rope_base=1e6, norm_eps=1e-5))
