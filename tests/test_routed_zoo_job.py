"""The routed LM (the DeepSeek-V2 block at a tiny size: latent
attention, a dense layer, then top-3 dropless expert layers that hold 4
of their 16 experts) trains through `master.main` with a process
worker on the serial chain (`--overlap_sync off`, the new cell's mix),
by the worker's own window program, and ends at the exact version.
Beside `tests/test_looped_zoo_job.py`, whose jobs run the same
adapter."""

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
VOCAB, SEQ, RECORDS, MINIBATCH, EPOCHS = 64, 24, 256, 32, 3


def test_routed_lm_trains_through_master_main_on_the_serial_chain(
    tmp_path, monkeypatch
):
    import sys

    sys.path.insert(0, FIXTURES)
    import routed_lm_tiny as zoo
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
            "--model_def", "routed_lm_tiny.custom_model",
            "--minibatch_size", str(MINIBATCH),
            "--training_data_dir", data,
            "--records_per_task", "128",
            "--num_epochs", str(EPOCHS),
            "--grads_to_wait", "1",
            "--local_updates", "4",
            "--overlap_sync", "off",
            "--num_workers", "1",
            "--worker_backend", "process",
            "--output", output,
        ]
    )
    assert rc == 0
    model = load_model_file(output)
    assert model.version == EPOCHS * RECORDS // MINIBATCH
    with RecordIOReader(data) as r:
        feats, labels = zoo.dataset_fn(list(r.read_range(0, 64)), "training")
    outputs = zoo.custom_model().apply(
        {"params": model.params}, jnp.asarray(feats)
    )
    final = float(zoo.loss(outputs, jnp.asarray(labels)))
    assert final < 0.6 * math.log(VOCAB), f"loss {final:.3f} did not fall"
    # the window program's scope map and the routers' span
    with open(os.path.join(logs, "worker-0.hlo_scopes.json")) as f:
        scopes = json.load(f)
    assert scopes["program"] == "jit_window"
    paths = list(scopes["instructions"].values())
    for want in ("mla", "moe/route", "moe/cond/branch_0_fun/experts",
                 "moe/cond/branch_3_fun/experts", "moe/shared", "mlp"):
        # (the experts run inside the switch over the ladder's rungs)
        assert any(want in p for p in paths), want
    spans = []
    for path in glob.glob(os.path.join(logs, "worker-0.spans.jsonl")):
        with open(path) as f:
            spans += [json.loads(line) for line in f if line.strip()]
    stats = [s for s in spans if s["name"] == "worker.window_stats"]
    assert stats, sorted({s["name"] for s in spans})
    args = stats[-1]["args"]
    tokens = args["expert_tokens"]
    assert len(tokens) == 2 and all(len(layer) == 4 for layer in tokens)
    routed = MINIBATCH * SEQ * 3  # assignments a layer
    assert args["held_share"] == pytest.approx(
        sum(map(sum, tokens)) / (2 * routed), abs=1e-4
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


@pytest.mark.parametrize("setting", [
    {"attention": "mla"}, {"n_dense_layers": 1}, {"moe_top_k": 2, "n_experts": 4},
])
def test_the_mesh_path_refuses_the_routed_settings_by_name(setting):
    from elasticdl_tpu.models import transformer_lm as lm
    from elasticdl_tpu.models import transformer_lm_zoo as zoo

    cfg = lm.TransformerConfig(**setting)
    with pytest.raises(NotImplementedError, match="plain_forward"):
        lm.param_partition_specs(cfg)
    with pytest.raises(NotImplementedError, match="mesh path"):
        lm.build_train_step(cfg, lm.make_mesh_for(2), zoo.optimizer())


def test_the_routed_stack_is_built_whole_or_not_at_all():
    import numpy as np

    from elasticdl_tpu.models import transformer_lm as lm

    with pytest.raises(NotImplementedError, match="latent attention"):
        lm.init_params(
            np.random.default_rng(0), lm.TransformerConfig(attention="mla")
        )


def test_the_single_device_loss_builder_takes_the_routed_stack():
    """`build_loss_fn` on a one-device mesh is `plain_forward` plus the
    weighted balance term, the zoo adapter's loss."""
    import sys

    import jax
    import numpy as np

    sys.path.insert(0, FIXTURES)
    import routed_lm_tiny as zoo
    from elasticdl_tpu.models import transformer_lm as lm

    model = zoo.custom_model()
    params = model.init(jax.random.PRNGKey(0), None)["params"]
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, size=(2, 17)), jnp.int32
    )
    got = lm.build_loss_fn(model.cfg, lm.make_mesh_for(1))(params, tokens)
    want = zoo.loss(
        model.apply({"params": params}, tokens[:, :-1]), tokens[:, 1:]
    )
    assert float(got) == pytest.approx(float(want), rel=1e-6)
