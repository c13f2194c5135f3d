"""The decoder-hybrid-decoder LM (Phi-4-mini-flash's layers at a tiny
size, UNCUT: Mamba-1 and windowed differential attention in turn, a
Mamba-1 that gives the memory, full differential attention that gives
the keys and values, then gated memory units and cross attention that
read them, a dense gated MLP in every layer) trains through
`master.main` with a process worker on the serial chain
(`--overlap_sync off`, its cell's mix), by the worker's own window
program, two windows a task, and ends at the exact version. Beside
`tests/test_mamba2_zoo_job.py`, whose job runs the same adapter."""

import glob
import json
import math
import os

import jax.numpy as jnp

from elasticdl_tpu.master.checkpoint import load_model_file
from elasticdl_tpu.master.main import main as master_main
from elasticdl_tpu.models.record_codec import write_learnable_token_records

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
VOCAB, SEQ, RECORDS, MINIBATCH, EPOCHS = 61, 24, 256, 32, 12


def test_sambay_lm_trains_through_master_main_on_the_serial_chain(
    tmp_path, monkeypatch
):
    import sys

    sys.path.insert(0, FIXTURES)
    import sambay_lm_tiny as zoo
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
            "--model_def", "sambay_lm_tiny.custom_model",
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
    # tied embeddings: no head; LayerNorms: a bias beside the weight
    assert sorted(model.params) == ["embed", "ln_f", "ln_f_bias", "stack"]
    with RecordIOReader(data) as r:
        feats, labels = zoo.dataset_fn(list(r.read_range(0, 64)), "training")
    outputs = zoo.custom_model().apply(
        {"params": model.params}, jnp.asarray(feats)
    )
    final = float(zoo.loss(outputs, jnp.asarray(labels)))
    assert final < 0.6 * math.log(VOCAB), f"loss {final:.3f} did not fall"
    # twelve runs of one layer, each with its own leaves through the
    # flat vector
    stack = model.params["stack"]
    assert len(stack) == 12
    mamba, swa, full, gmu, cross = (stack[i] for i in (6, 5, 7, 8, 9))
    assert jnp.asarray(mamba["a_log"]).shape == (1, 4, 64)  # [state, inner]
    assert jnp.asarray(mamba["x_proj"]).shape == (1, 64, 2 + 2 * 4)
    assert jnp.asarray(swa["diff"]).shape == (1, 6 * 8)
    assert jnp.asarray(full["wk"]).shape == (1, 32, 16)
    assert sorted(gmu) == ["ln1", "ln1_bias", "ln2", "ln2_bias", "w1", "w2",
                           "wd", "wg", "wu"]
    assert "wk" not in cross and "wv" not in cross and "bk" not in cross
    # the window program's scope map and the layers' span
    with open(os.path.join(logs, "worker-0.hlo_scopes.json")) as f:
        scopes = json.load(f)
    assert scopes["program"] == "jit_window"
    paths = list(scopes["instructions"].values())
    for want in ("mamba1/run0/in_proj", "mamba1/run0/conv", "mamba1/run0/step",
                 "mamba1/run0/scan", "mamba1/run0/gate", "mamba1/run0/out_proj",
                 "mamba1/run6/scan", "gmu/in_proj", "gmu/gate", "gmu/out_proj",
                 "attention/swa", "attention/global", "attention/cross",
                 "attention/swa/diff", "attention/global/diff",
                 "attention/cross/diff", "mlp", "head"):
        assert any(want in p for p in paths), want
    assert not any("moe" in p or "rope" in p for p in paths)
    spans = []
    for path in glob.glob(os.path.join(logs, "worker-0.spans.jsonl")):
        with open(path) as f:
            spans += [json.loads(line) for line in f if line.strip()]
    stats = [s for s in spans if s["name"] == "worker.window_stats"]
    assert len(stats) >= 2, sorted({s["name"] for s in spans})
    args = stats[-1]["args"]
    assert args["ssm1_log_decay_min"] < 0.0
    assert 0.0 < args["ssm1_dt_mean"] < 1.0
    assert 0.0 < args["diff_lambda_mean"] < 1.5
    assert args["gmu_gate_absmax"] > 0.0
    programs = {s["args"].get("program") for s in spans
                if s["name"] == "setup.program"}
    assert {"jit_window", "jit_subtract", "jit_copy"} <= programs
    # off the TPU no layer reaches a kernel
    maps = [s["args"] for s in spans if s["name"] == "setup.scope_map"
            and s["args"].get("program") == "jit_window"]
    assert maps and maps[0]["kernels"] == {}
