"""The early-router LM (SmallThinker's block at a tiny size: a full
layer that turns nothing, three window-8 layers whose whole head turns,
a group of 7 query heads on one key-value head, every layer an expert
layer of gated-ReLU experts whose router reads the layer's INPUT ahead
of the attention), float32 on the CPU: the program against the
configuration's plain reference
(`benchmark/configs/smallthinker-21b-a3b/reference.py`), loss, logits,
loads and every gradient leaf; the eight shares of a layer adding up to
the uncut reference layer; a router that does not see its layer's
attention; rotation by kind of layer; the window's edge; the blocks a
changed part makes; what the mesh path refuses; and a zoo job of two
windows a task through `master.main` and a process worker on the
serial chain, as `tests/test_window_zoo_job.py`.

Tolerance: both sides are float32 with the same mathematics in another
order, so they agree to accumulated rounding: a relative 2e-4 of the
largest value, the other configurations' tolerance."""

import glob
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "fixtures")
for path in (ROOT, FIXTURES):
    if path not in sys.path:
        sys.path.insert(0, path)

import early_router_lm_tiny as zoo  # noqa: E402
from benchmark.harness.manifest import load_module  # noqa: E402
from elasticdl_tpu.common.constants import WINDOW_STATS  # noqa: E402
from elasticdl_tpu.models import transformer_lm as lm  # noqa: E402
from elasticdl_tpu.ops import flash_attention  # noqa: E402
from elasticdl_tpu.parallel import moe  # noqa: E402

TOLERANCE = 2e-4
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "smallthinker-21b-a3b")
REF = load_module(os.path.join(CONFIG_DIR, "reference.py"))


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def close(a, b, tolerance=TOLERANCE, floor=1e-6):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tolerance * max(np.max(np.abs(b)), floor)


def seeded(length, seed=3, **overrides):
    """-> (params, program, reference): the tiny model's loss, logits
    and stats, and the reference's, as functions of the parameters."""
    model = zoo.custom_model(**overrides)
    variables = model.init(jax.random.PRNGKey(seed), None)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    # norms away from their initial ones, so that a weight left out
    # (or a norm the router should not have read) would show
    rng = np.random.default_rng(seed)
    for run in params["stack"]:
        for name in ("ln1", "ln2"):
            run[name] = run[name] + jnp.asarray(
                rng.normal(size=run[name].shape) * 0.2, jnp.float32
            )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, length + 1), 0, 64)
    x, y = tokens[:, :-1], tokens[:, 1:]

    def program(p):
        out, state = model.apply(
            {"params": p, WINDOW_STATS: variables[WINDOW_STATS]}, x,
            mutable=[WINDOW_STATS],
        )
        return zoo.loss(out, y), (out[0], state[WINDOW_STATS])

    def reference(p, **sizes):
        sizes = {**zoo.REFERENCE_SIZES, **sizes}
        value, loads = REF.parts(p, x, y, sizes)
        return value, (REF.logits_of(p, x, sizes), loads)

    return params, program, reference


# ------------------------------------------------ program and reference


@pytest.mark.parametrize("length", [48, 2, 41])
def test_the_program_s_logits_loss_and_loads_are_the_reference_s(length):
    params, program, reference = seeded(length)
    got, (logits, stats) = jax.jit(program)(params)
    want, (ref_logits, loads) = jax.jit(reference)(params)
    assert close(logits, ref_logits)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    assert np.array_equal(
        np.asarray(stats["expert_tokens"]), np.asarray(loads)[:, 4:8]
    )
    assert stats["expert_tokens"].shape == (4, 4)  # every layer routes
    assert sorted(stats) == [
        "expert_tokens", "held_share", "route_full", "route_rows",
        "router_entropy",
    ]


@pytest.mark.parametrize("length", [48, 41])
def test_every_leaf_s_gradient_is_the_reference_s(length):
    params, program, reference = seeded(length)
    got = jax.jit(jax.grad(lambda p: program(p)[0]))(params)
    want = jax.jit(jax.grad(lambda p: reference(p)[0]))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    # embed, head, ln_f; two runs of 6 + router + three expert leaves
    assert len(flat_got) == len(flat_want) == 3 + 10 + 10
    for (path, a), b in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        assert np.any(np.asarray(b)), name
        assert close(a, b), name


@pytest.mark.parametrize("control, setting", [
    ("late_router", {"early_router": False}),
    ("full_layer_turned", {"rope_mixers": None}),
    ("nothing_turned", {"rope": False}),
    ("silu_gate", {"mlp": "swiglu"}),
    ("no_window", {"swa_window": 4096}),
    ("a_key_more", {"swa_window": 9}),
    ("unnormalised", {"moe_renormalize": False}),
])
def test_a_block_that_changes_a_part_is_not_the_reference(control, setting):
    params, _program, reference = seeded(48)
    other = zoo.custom_model(**setting)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 49), 0, 64)
    logits, _aux = other.apply({"params": params}, tokens[:, :-1])
    assert not close(logits, reference(params)[1][0], 1e-2)


@pytest.mark.parametrize("reads, setting", [
    ("ln2", {"early_router": False}),  # the usual placement
])
def test_the_usual_placement_is_the_reference_told_so(reads, setting):
    """The same program with `early_router` off is the reference whose
    router reads ln2(h): the field moves the router and nothing else."""
    params, program, reference = seeded(41, **setting)
    got, (logits, stats) = jax.jit(program)(params)
    want, (ref_logits, loads) = jax.jit(
        lambda p: reference(p, router_reads=reads)
    )(params)
    assert close(logits, ref_logits)
    assert np.array_equal(
        np.asarray(stats["expert_tokens"]), np.asarray(loads)[:, 4:8]
    )
    # and the other reading of "before attention" is a third block
    _v, (third, _l) = jax.jit(lambda p: reference(p, router_reads="ln1"))(params)
    assert not close(third, ref_logits, 1e-2)
    assert not close(third, reference(params)[1][0], 1e-2)


# --------------------------------------- the router and the attention


def chosen_experts(params, early, length=40):
    """Every layer's loads over ALL experts (the reference's count of
    what the program's router chose is held by the tests above; here the
    program alone, held = all 16)."""
    model = zoo.custom_model(early_router=early, held_experts=(0, 16))
    variables = model.init(jax.random.PRNGKey(3), None)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, length), 0, 64)
    _out, state = model.apply(
        {"params": params, WINDOW_STATS: variables[WINDOW_STATS]}, tokens,
        mutable=[WINDOW_STATS],
    )
    return np.asarray(state[WINDOW_STATS]["expert_tokens"])


def test_the_early_router_does_not_see_its_layer_s_attention():
    """Perturb `wq` and `wo` of the LAST layer alone: its input is what
    it was, so an early router chooses as it did; a router on ln2(h),
    behind the attention, chooses otherwise."""
    model = zoo.custom_model(held_experts=(0, 16))
    params = jax.tree_util.tree_map(
        jnp.asarray, model.init(jax.random.PRNGKey(3), None)["params"]
    )
    moved = jax.tree_util.tree_map(lambda a: a, params)
    rng = np.random.default_rng(0)
    sliding = dict(moved["stack"][1])
    for name in ("wq", "wo"):
        bump = np.zeros(sliding[name].shape, np.float32)
        bump[-1] = rng.normal(size=bump.shape[1:]) * 0.5
        sliding[name] = sliding[name] + jnp.asarray(bump)
    moved["stack"] = [moved["stack"][0], sliding]
    for early, same in ((True, True), (False, False)):
        before = chosen_experts(params, early)
        after = chosen_experts(moved, early)
        assert np.array_equal(before[:-1], after[:-1])  # layers in front
        assert np.array_equal(before[-1], after[-1]) == same, early


def test_the_router_scope_lies_in_front_of_the_attention_scope():
    model = zoo.custom_model()
    variables = model.init(jax.random.PRNGKey(0), None)
    tokens = jnp.zeros((1, 12), jnp.int32)
    text = jax.jit(
        lambda p: model.apply({**variables, "params": p}, tokens)[0]
    ).lower(variables["params"]).as_text(debug_info=True)
    for want in ("/router/", "attention/swa/rope/", "attention/global/",
                 "moe/route", "moe/experts"):
        assert want in text, want
    assert "attention/global/rope" not in text  # the full layer turns nothing
    assert "moe/shared" not in text and "/mlp/" not in text
    late = zoo.custom_model(early_router=False)
    text = jax.jit(
        lambda p: late.apply({**variables, "params": p}, tokens)[0]
    ).lower(variables["params"]).as_text(debug_info=True)
    assert "/router/" not in text


# ------------------------------------------------ rotation by kind


def attention_layer(mixer, positions, seed=4, **overrides):
    cfg = zoo.custom_model(**overrides).cfg
    rng = np.random.default_rng(seed)
    heads = cfg.attention_shape(mixer).heads

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]), jnp.float32)

    lp = {"wq": draw(64, heads * 16), "wk": draw(64, 16), "wv": draw(64, 16),
          "wo": draw(heads * 16, 64)}
    x = jnp.asarray(rng.normal(size=(2, 16, 64)), jnp.float32)
    return lm._attend(cfg, lp, x, jnp.asarray(positions), mixer)[0]


def test_a_full_layer_is_the_same_under_shifted_positions_and_is_rope_false():
    at_zero = attention_layer("mha", np.arange(16))
    shifted = attention_layer("mha", np.arange(16) + 1000)
    assert np.array_equal(np.asarray(at_zero), np.asarray(shifted))
    # nothing turned at all: the unturned "mha" of `rope=False`
    unturned = attention_layer("mha", np.arange(16), rope=False)
    assert np.array_equal(np.asarray(at_zero), np.asarray(unturned))
    # under one global switch the full layer would turn
    turned = attention_layer("mha", np.arange(16), rope_mixers=None)
    assert not close(turned, at_zero, 1e-2)
    # a windowed layer turns, and turns relatively: a shift of every
    # position changes no score
    sliding = attention_layer("swa", np.arange(16))
    assert close(sliding, attention_layer("swa", np.arange(16) + 1000), 1e-3)
    assert not close(sliding, attention_layer("swa", np.arange(16), rope=False), 1e-2)


def test_attention_shape_says_which_kind_turns():
    cfg = zoo.custom_model().cfg
    assert cfg.attention_shape("swa").turns and not cfg.attention_shape("mha").turns
    assert cfg.attention_shape("swa") == lm.AttentionShape(
        7, 8, 1500000.0, None, None, 1.0, "swa", True
    )
    everything = zoo.custom_model(rope_mixers=None).cfg
    assert everything.attention_shape("mha").turns
    nothing = zoo.custom_model(rope=False).cfg
    assert not nothing.attention_shape("swa").turns
    with pytest.raises(ValueError, match="rope_mixers"):
        lm.init_params(
            np.random.default_rng(0), zoo.custom_model(rope_mixers=("kda",)).cfg
        )
    with pytest.raises(ValueError, match="early_router"):
        lm.init_params(np.random.default_rng(0), lm.TransformerConfig(
            layer_types=("mha", "swa"), n_layers=2, mlp="swiglu",
            swa_heads=8, swa_window=4, early_router=True,
        ))


def test_a_window_of_4_at_length_16_masks_0_le_t_minus_u_lt_4():
    """Values one-hot by position: a query's output IS its attention
    row. The seven query heads all read the one key-value head."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 16, 7, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 16, 1, 16)), jnp.float32)
    v = jnp.eye(16, dtype=jnp.float32)[None, :, None, :]
    rows = np.asarray(flash_attention.attention(q, k, v, window=4))
    t, u = np.arange(16)[:, None], np.arange(16)[None, :]
    seen = (0 <= t - u) & (t - u < 4)
    for head in range(7):
        assert np.array_equal(rows[0, :, head] > 0, seen), head
        np.testing.assert_allclose(rows[0, :, head].sum(-1), 1.0, rtol=1e-6)


# ------------------------------------------------ the shares of a layer


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_reference_layer():
    """The guide's section 4: the eight chips of the deployment hold
    experts 0-7 ... 56-63 of 64, each computes the whole attention and
    the whole router alike and its own experts' part; the parts, with
    what every chip computes alike (h, the stream behind the attention)
    counted once, are the uncut reference's layer."""
    d, f, experts, k = 32, 12, 64, 6
    rng = np.random.default_rng(11)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]), jnp.float32)

    sizes = dict(heads=7, kv_heads=1, head_dim=8, rope_base=1500000.0,
                 eps=1e-6, top_k=k, held=(0, experts), router_reads="input")
    kind = dict(window=8, turns=True)
    lp = {
        "ln1": 1 + 0.2 * draw(1, d)[0], "ln2": 1 + 0.2 * draw(1, d)[0],
        "wq": draw(d, 56), "wk": draw(d, 8), "wv": draw(d, 8), "wo": draw(56, d),
        "router": draw(d, experts), "eg": draw(experts, d, f),
        "eu": draw(experts, d, f), "ed": draw(experts, f, d),
    }
    x = jnp.asarray(rng.normal(size=(2, 24, d)), jnp.float32)
    uncut, loads = REF.layer(lp, x, kind, sizes)
    assert float(jnp.sum(loads)) == 2 * 24 * k
    # what every chip computes alike: the stream behind the attention
    h = x + REF.attention_mixer(lp, REF._rms_norm(x, lp["ln1"], 1e-6), kind, sizes)
    u = REF._rms_norm(h, lp["ln2"], 1e-6)
    logits = moe.router_logits(x.reshape(-1, d), lp["router"])
    parts, seen = 0.0, 0.0
    for first in range(0, experts, 8):
        held = slice(first, first + 8)
        part, term, share = moe.moe_topk_held(
            u, lp["router"],
            (lp["eg"][held], lp["eu"][held], lp["ed"][held]), None,
            top_k=k, held=(first, 8), renormalize=True, balance=False,
            kind="reglu", logits=logits,
        )
        assert float(term) == 0.0
        assert np.array_equal(
            np.asarray(share["expert_tokens"]), np.asarray(loads)[held]
        )
        cut, _ = REF.layer(
            {**lp, "eg": lp["eg"][held], "eu": lp["eu"][held],
             "ed": lp["ed"][held]}, x, kind, sizes, held=(first, 8),
        )
        assert close(h + part, cut, 1e-5)
        parts = parts + part
        seen += float(jnp.sum(share["expert_tokens"]))
    assert seen == 2 * 24 * k
    assert close(h + parts, uncut, 1e-5)
    # h counted eight times is not the layer
    assert not close(8 * h + parts, uncut, 1e-2)


# ----------------------------------------------------------- what stays


def test_the_stack_is_two_runs_and_the_mesh_path_refuses_the_new_fields():
    cfg = zoo.custom_model().cfg
    assert cfg.mixed and cfg.runs == (("mha", True, 1), ("swa", True, 3))
    params = zoo.custom_model().init(jax.random.PRNGKey(0), None)["params"]
    full, sliding = params["stack"]
    assert sorted(full) == sorted(sliding) == [
        "ed", "eg", "eu", "ln1", "ln2", "router", "wk", "wo", "wq", "wv",
    ]
    assert sliding["wq"].shape == (3, 64, 7 * 16)
    assert sliding["wk"].shape == (3, 64, 16)
    assert sliding["eg"].shape == (3, 4, 64, 24)
    assert sliding["router"].shape == (3, 64, 16)
    for setting in (dict(early_router=True), dict(rope_mixers=("mha",)),
                    dict(mlp="reglu")):
        with pytest.raises(NotImplementedError, match="early_router"):
            lm.param_partition_specs(lm.TransformerConfig(**setting))
    with pytest.raises(NotImplementedError, match="no dense layer"):
        lm.init_params(
            np.random.default_rng(0), zoo.custom_model(n_dense_layers=1).cfg
        )


# ------------------------------------------------------------ the zoo job

VOCAB, SEQ, RECORDS, MINIBATCH, EPOCHS = 64, 24, 256, 32, 12


def test_early_router_lm_trains_through_master_main_on_the_serial_chain(
    tmp_path, monkeypatch
):
    from elasticdl_tpu.data.recordio import RecordIOReader
    from elasticdl_tpu.master.checkpoint import load_model_file
    from elasticdl_tpu.master.main import main as master_main
    from elasticdl_tpu.models.record_codec import write_learnable_token_records

    tmp = str(tmp_path)
    data = os.path.join(tmp, "tokens.rio")
    write_learnable_token_records(data, RECORDS, SEQ, VOCAB, seed=2)
    output = os.path.join(tmp, "final.ckpt")
    logs = os.path.join(tmp, "logs")
    monkeypatch.setenv("EDL_WORKER_LOG_DIR", logs)
    rc = master_main(
        [
            "--model_zoo", FIXTURES,
            "--model_def", "early_router_lm_tiny.custom_model",
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
    assert sorted(model.params) == ["embed", "head", "ln_f", "stack"]
    with RecordIOReader(data) as r:
        feats, labels = zoo.dataset_fn(list(r.read_range(0, 64)), "training")
    outputs = zoo.custom_model().apply(
        {"params": model.params}, jnp.asarray(feats)
    )
    final = float(zoo.loss(outputs, jnp.asarray(labels)))
    assert final < 0.6 * math.log(VOCAB), f"loss {final:.3f} did not fall"
    full, sliding = model.params["stack"]
    assert jnp.asarray(full["wq"]).shape == (1, 64, 112)
    assert jnp.asarray(sliding["eg"]).shape == (3, 4, 64, 24)
    # the window program's scope map and the layers' span
    with open(os.path.join(logs, "worker-0.hlo_scopes.json")) as f:
        scopes = json.load(f)
    assert scopes["program"] == "jit_window"
    paths = list(scopes["instructions"].values())
    for want in ("/router/", "attention/swa/", "attention/global/",
                 "attention/swa/rope/", "moe/route", "moe/", "head"):
        assert any(want in p for p in paths), want
    assert not any("attention/global/rope" in p for p in paths)
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
    assert args["route_rows"] > 0
    maps = [s["args"] for s in spans if s["name"] == "setup.scope_map"
            and s["args"].get("program") == "jit_window"]
    assert maps and maps[0]["kernels"] == {}
