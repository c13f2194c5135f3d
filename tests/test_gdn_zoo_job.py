"""The scalar-decay delta-rule LM (Qwen3-Next's block at a tiny size:
three Gated DeltaNet layers, 2 key heads under 4 value heads, and one
gated attention layer, 4 query heads over 1 key-value head with a gate
per output channel, each of the four with softmax top-3 expert layers
that hold 4 of their 16 experts and a shared expert behind a gate)
trains through `master.main` with a process worker on the serial chain
(`--overlap_sync off`, its cell's mix), by the worker's own window
program, two windows a task, and ends at the exact version. Beside
`tests/test_hybrid_zoo_job.py`, whose job runs the same adapter."""

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
VOCAB, SEQ, RECORDS, MINIBATCH, EPOCHS = 64, 24, 256, 32, 12


def test_gdn_lm_trains_through_master_main_on_the_serial_chain(
    tmp_path, monkeypatch
):
    import sys

    sys.path.insert(0, FIXTURES)
    import gdn_lm_tiny as zoo
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
            "--model_def", "gdn_lm_tiny.custom_model",
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
        "embed", "gdn_decay", "head", "ln_f", "stack",
    ]
    with RecordIOReader(data) as r:
        feats, labels = zoo.dataset_fn(list(r.read_range(0, 64)), "training")
    outputs = zoo.custom_model().apply(
        {"params": model.params}, jnp.asarray(feats)
    )
    final = float(zoo.loss(outputs, jnp.asarray(labels)))
    assert final < 0.6 * math.log(VOCAB), f"loss {final:.3f} did not fall"
    # each kind of layer kept its own shapes through the flat vector
    linear, full = model.params["stack"]
    assert jnp.asarray(linear["wqkvz"]).shape == (3, 64, 192)
    assert jnp.asarray(linear["wba"]).shape == (3, 8, 64)
    assert jnp.asarray(linear["sgate"]).shape == (3, 1, 64)
    assert jnp.asarray(full["wq"]).shape == (1, 64, 256)
    assert jnp.asarray(model.params["gdn_decay"]).shape == (2 * 3 * 4,)
    # the window program's scope map and the layers' span
    with open(os.path.join(logs, "worker-0.hlo_scopes.json")) as f:
        scopes = json.load(f)
    assert scopes["program"] == "jit_window"
    paths = list(scopes["instructions"].values())
    for want in ("gdn/conv", "gdn/gates", "gdn/scan/intra", "gdn/scan/state",
                 "gdn/out", "attention/gate", "moe/route", "moe/shared/gate",
                 "head"):
        assert any(want in p for p in paths), want
    assert not any("kda" in p for p in paths)
    spans = []
    for path in glob.glob(os.path.join(logs, "worker-0.spans.jsonl")):
        with open(path) as f:
            spans += [json.loads(line) for line in f if line.strip()]
    stats = [s for s in spans if s["name"] == "worker.window_stats"]
    assert len(stats) >= 2, sorted({s["name"] for s in spans})
    args = stats[-1]["args"]
    tokens = args["expert_tokens"]
    assert len(tokens) == 4 and all(len(layer) == 4 for layer in tokens)
    routed = MINIBATCH * SEQ * 3  # assignments a layer
    assert args["held_share"] == pytest.approx(
        sum(map(sum, tokens)) / (4 * routed), abs=1e-4
    )
    assert 0.0 < args["router_entropy"] <= math.log(16) + 1e-4
    assert args["gdn_log_decay_min"] < 0.0
    for name in ("gdn_beta_mean", "shared_gate_mean", "attn_gate_mean"):
        assert 0.05 < args[name] < 0.95, name
    programs = {s["args"].get("program") for s in spans
                if s["name"] == "setup.program"}
    assert {"jit_window", "jit_subtract", "jit_copy"} <= programs
    # off the TPU no layer reaches a kernel
    maps = [s["args"] for s in spans if s["name"] == "setup.scope_map"
            and s["args"].get("program") == "jit_window"]
    assert maps and maps[0]["kernels"] == {}
