"""The short-convolution LM (LFM2's block at a tiny size: the
double-gated convolution in four layers of five, grouped-query
attention with normed queries and keys in the fifth, sigmoid top-3
expert layers that hold 4 of their 16 experts and have no shared
expert, the head tied to the embedding) trains through `master.main`
with a process worker on the serial chain (`--overlap_sync off`, its
cell's mix), by the worker's own window program, two windows a task,
and ends at the exact version. Beside `tests/test_hybrid_zoo_job.py`,
whose job runs the same adapter."""

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


def test_shortconv_lm_trains_through_master_main_on_the_serial_chain(
    tmp_path, monkeypatch
):
    import sys

    sys.path.insert(0, FIXTURES)
    import shortconv_lm_tiny as zoo
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
            "--model_def", "shortconv_lm_tiny.custom_model",
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
    assert sorted(model.params) == ["embed", "ln_f", "stack"]  # no head
    with RecordIOReader(data) as r:
        feats, labels = zoo.dataset_fn(list(r.read_range(0, 64)), "training")
    outputs = zoo.custom_model().apply(
        {"params": model.params}, jnp.asarray(feats)
    )
    final = float(zoo.loss(outputs, jnp.asarray(labels)))
    assert final < 0.6 * math.log(VOCAB), f"loss {final:.3f} did not fall"
    # the selection bias is a leaf no gradient reaches: it stays zero;
    # a layer without a shared expert brought no leaf for one
    for run in model.params["stack"]:
        assert not {"sg", "su", "sd"} & set(run)
        if "router_bias" in run:
            assert not jnp.any(jnp.asarray(run["router_bias"]))
    # the window program's scope map and the layers' span
    with open(os.path.join(logs, "worker-0.hlo_scopes.json")) as f:
        scopes = json.load(f)
    assert scopes["program"] == "jit_window"
    paths = list(scopes["instructions"].values())
    for want in ("conv/shortconv/in_proj", "conv/shortconv/gate_conv",
                 "conv/shortconv/out_proj", "attention", "moe/route",
                 "moe/cond/branch_0_fun/experts",
                 "moe/cond/branch_3_fun/experts", "mlp", "head"):
        # (the experts run inside the switch over the ladder's rungs)
        assert any(want in p for p in paths), want
    assert not any("moe/shared" in p for p in paths)
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
    # the sorted buffer each layer took: a rung of the ladder that holds
    # the rows that came, the mean over the layers; how many took the top
    from elasticdl_tpu.parallel.moe import route_rungs

    rungs = route_rungs(MINIBATCH * SEQ, 3, 4)
    assert len(rungs) == 4 and rungs[-1] == routed
    came = [sum(layer) for layer in tokens]
    taken = [min(r for r in rungs if r >= rows) for rows in came]
    assert args["route_rows"] == pytest.approx(sum(taken) / len(taken))
    assert args["route_full"] == sum(r == rungs[-1] for r in taken)
    assert args["shortconv_gate_absmax"] > 0.0
    assert args["router_bias_absmax"] == 0.0
    programs = {s["args"].get("program") for s in spans
                if s["name"] == "setup.program"}
    assert {"jit_window", "jit_subtract", "jit_copy"} <= programs
