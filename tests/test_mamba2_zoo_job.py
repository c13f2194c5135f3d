"""The state-space expert LM (Nemotron-3-Nano's blocks at a tiny size:
three Mamba-2 layers, one of them a mixer ALONE, and one attention
layer that turns nothing, the other three with sigmoid top-3
squared-ReLU expert layers that hold 4 of their 16 experts and a shared
expert) trains through `master.main` with a process worker on the
serial chain (`--overlap_sync off`, its cell's mix), by the worker's
own window program, two windows a task, and ends at the exact version.
Beside `tests/test_gdn_zoo_job.py`, whose job runs the same adapter."""

import glob
import json
import math
import os

import jax.numpy as jnp
import pytest

from elasticdl_tpu.master.checkpoint import load_model_file
from elasticdl_tpu.master.main import main as master_main
from elasticdl_tpu.models.record_codec import write_learnable_token_records

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
VOCAB, SEQ, RECORDS, MINIBATCH, EPOCHS = 61, 24, 256, 32, 12


def test_mamba2_lm_trains_through_master_main_on_the_serial_chain(
    tmp_path, monkeypatch
):
    import sys

    sys.path.insert(0, FIXTURES)
    import mamba2_lm_tiny as zoo
    from elasticdl_tpu.data.recordio import RecordIOReader

    tmp = str(tmp_path)
    data = os.path.join(tmp, "tokens.rio")
    write_learnable_token_records(data, RECORDS, SEQ, VOCAB, seed=2)
    output = os.path.join(tmp, "final.ckpt")
    logs = os.path.join(tmp, "logs")
    monkeypatch.setenv("EDL_WORKER_LOG_DIR", logs)
    rc = master_main(
        [
            "--model_zoo", FIXTURES,
            "--model_def", "mamba2_lm_tiny.custom_model",
            "--minibatch_size", str(MINIBATCH),
            "--training_data_dir", data,
            "--records_per_task", "128",
            "--num_epochs", str(EPOCHS),
            "--grads_to_wait", "1",
            "--local_updates", "2",  # two windows a task of four steps
            "--overlap_sync", "off",
            "--num_workers", "1",
            "--worker_backend", "process",
            "--output", output,
        ]
    )
    assert rc == 0
    model = load_model_file(output)
    # version == init + applied: every minibatch trained and applied once
    assert model.version == EPOCHS * RECORDS // MINIBATCH
    assert sorted(model.params) == [
        "embed", "head", "ln_f", "ssm_decay", "stack",
    ]
    with RecordIOReader(data) as r:
        feats, labels = zoo.dataset_fn(list(r.read_range(0, 64)), "training")
    outputs = zoo.custom_model().apply(
        {"params": model.params}, jnp.asarray(feats)
    )
    final = float(zoo.loss(outputs, jnp.asarray(labels)))
    assert final < 0.6 * math.log(VOCAB), f"loss {final:.3f} did not fall"
    # each run kept its own shapes through the flat vector: two paired
    # Mamba-2 layers, the bare one, the attention layer
    paired, bare, attention = model.params["stack"]
    assert jnp.asarray(paired["in_proj"]).shape == (2, 32, 132)
    assert jnp.asarray(paired["eu"]).shape == (2, 4, 32, 12)
    assert jnp.asarray(bare["in_proj"]).shape == (1, 32, 132)
    assert "ln2" not in bare and "router" not in bare
    assert jnp.asarray(attention["wk"]).shape == (1, 32, 16)
    assert jnp.asarray(model.params["ssm_decay"]).shape == (3 * 3 * 4,)
    # the selection bias is a leaf no gradient reaches: still zero
    assert float(jnp.max(jnp.abs(jnp.asarray(paired["router_bias"])))) == 0.0
    # the window program's scope map and the layers' span
    with open(os.path.join(logs, "worker-0.hlo_scopes.json")) as f:
        scopes = json.load(f)
    assert scopes["program"] == "jit_window"
    paths = list(scopes["instructions"].values())
    for want in ("mamba2/run0/in_proj", "mamba2/run0/conv",
                 "mamba2/run0/scan/intra", "mamba2/run0/scan/state",
                 "mamba2/run0/scan/out", "mamba2/run0/gate_norm",
                 "mamba2/run0/out_proj", "mamba2/run1/scan/state",
                 "attention", "moe/route", "moe/shared", "head"):
        assert any(want in p for p in paths), want
    # a bare block's scope is its mixer's alone: no `mlp` anywhere
    assert not any("/mlp/" in p or "gdn" in p or "rope" in p for p in paths)
    spans = []
    for path in glob.glob(os.path.join(logs, "worker-0.spans.jsonl")):
        with open(path) as f:
            spans += [json.loads(line) for line in f if line.strip()]
    stats = [s for s in spans if s["name"] == "worker.window_stats"]
    assert len(stats) >= 2, sorted({s["name"] for s in spans})
    args = stats[-1]["args"]
    tokens = args["expert_tokens"]
    assert len(tokens) == 3 and all(len(layer) == 4 for layer in tokens)
    routed = MINIBATCH * SEQ * 3  # assignments a layer
    assert args["held_share"] == pytest.approx(
        sum(map(sum, tokens)) / (3 * routed), abs=1e-4
    )
    assert 0.0 < args["router_entropy"] <= math.log(16) + 1e-4
    assert args["router_bias_absmax"] == 0.0
    assert args["ssm_log_decay_min"] < 0.0
    assert 0.0 < args["ssm_dt_mean"] < 1.0
    programs = {s["args"].get("program") for s in spans
                if s["name"] == "setup.program"}
    assert {"jit_window", "jit_subtract", "jit_copy"} <= programs
    # off the TPU no layer reaches a kernel
    maps = [s["args"] for s in spans if s["name"] == "setup.scope_map"
            and s["args"].get("program") == "jit_window"]
    assert maps and maps[0]["kernels"] == {}
