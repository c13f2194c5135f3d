"""The decoder-hybrid-decoder LM (`layer_types` of "mamba1", "swa",
"mha", "gmu" and "cross"; differential attention; LayerNorms; a dense
gated MLP in every layer) against the plain reference of the
`phi-4-mini-flash-reasoning` configuration, on the CPU at a small size
and UNCUT (12 published layers, so two "gmu" and two "cross" layers sum
their cotangents into the memory and the shared keys and values): loss,
logits and every gradient leaf; a cut of it (lambda_init by the
PUBLISHED index); the walk from the published depth to the program's
layers; the comparison's controls, each forced outside the agreement;
what the stack refuses; and an existing cell's tiny program traced as
it was."""

import hashlib
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models import transformer_lm as lm
from elasticdl_tpu.models import transformer_lm_zoo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests"))
from fixtures import sambay_lm_tiny as tiny  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs", "phi-4-mini-flash-reasoning")


def _load(name):
    from benchmark.harness.manifest import load_module

    return load_module(os.path.join(CONFIG, name))


@pytest.fixture(scope="module")
def ref():
    return _load("reference.py")


@pytest.fixture(scope="module")
def compare():
    return _load("compare.py")


def _sizes(held):
    s = tiny.SIZES
    return dict(
        published_layers=tiny.PUBLISHED_LAYERS, held=held, heads=s["n_heads"],
        kv_heads=s["n_kv_heads"], head_dim=s["head_width"],
        window=s["swa_window"], eps=s["norm_eps"], inner=s["ssm1_inner"],
        state=s["ssm1_state"], dt_rank=s["ssm1_dt_rank"],
    )


def _case(held=None, seed=0, **overrides):
    """A model, its weights with every zero and one of the initialiser
    moved off it, and a batch."""
    model = tiny.custom_model(held, **overrides)
    variables = model.init(jax.random.PRNGKey(seed), None)
    rng = np.random.default_rng(seed + 1)
    params = jax.tree_util.tree_map(
        lambda a: (a + 0.1 * rng.standard_normal(a.shape)).astype(np.float32),
        variables["params"],
    )
    tokens = jnp.asarray(rng.integers(0, tiny.SIZES["vocab"], (2, 24)), jnp.int32)
    return model, variables, params, tokens, jnp.roll(tokens, -1, axis=1)


def _program(model, variables, tokens, targets):
    def loss(params):
        out, aux = model.apply(
            {**variables, "params": params}, tokens, mutable=True
        )
        return tiny.loss(out, targets), (out, aux)

    return jax.jit(jax.value_and_grad(loss, has_aux=True))


def _worst_leaf(got, want):
    """The worst leaf's largest error over the larger of its own
    largest entry and a thousandth of the whole gradient's (a key
    bias's true gradient is zero under a softmax: rounding on both
    sides)."""
    floor = 1e-3 * max(
        float(jnp.max(jnp.abs(w))) for w in jax.tree_util.tree_leaves(want)
    )
    errors = jax.tree_util.tree_map(
        lambda g, w: float(jnp.max(jnp.abs(g - w)))
        / max(float(jnp.max(jnp.abs(w))), floor),
        got, want,
    )
    flat = jax.tree_util.tree_flatten_with_path(errors)[0]
    path, worst = max(flat, key=lambda item: item[1])
    return worst, jax.tree_util.keystr(path)


@pytest.mark.parametrize("held", [None, (5, 5), (6, 4)], ids=str)
def test_the_program_holds_to_the_reference(ref, held):
    """None: all 12 layers. (5, 5): published layers 5-9, the cell's
    cut in small (windowed, memory, keys and values, one reader each),
    whose lambda_init comes from the published index and not from the
    place in the cut. (6, 4): a cut that starts at the memory layer."""
    model, variables, params, tokens, targets = _case(held)
    assert len(model.cfg.runs) == len(model.cfg.mixers)  # a run a layer
    (loss, (logits, aux)), grads = _program(
        model, variables, tokens, targets
    )(params)
    sizes = _sizes(held or (0, tiny.PUBLISHED_LAYERS))
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, targets, sizes)
    ))(params)
    want_logits = ref.logits_of(params, tokens, sizes)
    assert abs(float(loss) - float(want_loss)) < 2e-6 * abs(float(want_loss))
    np.testing.assert_allclose(logits, want_logits, atol=5e-5, rtol=1e-4)
    assert jax.tree_util.tree_structure(grads) == (
        jax.tree_util.tree_structure(want)
    )
    worst, where = _worst_leaf(grads, want)
    assert worst < 5e-4, (worst, where)
    stats = aux["window_stats"]
    assert sorted(stats) == [
        "diff_lambda_mean", "gmu_gate_absmax", "ssm1_dt_mean",
        "ssm1_log_decay_min",
    ]
    assert float(stats["ssm1_log_decay_min"]) < 0 < float(stats["ssm1_dt_mean"])


def test_the_walk_reproduces_the_published_table(ref):
    layers = transformer_lm_zoo.sambay_layers(32)
    kinds = layers["layer_types"]
    assert {k: kinds.count(k) for k in set(kinds)} == {
        "mamba1": 9, "swa": 8, "mha": 1, "gmu": 7, "cross": 7,
    }
    assert (layers["memory_layer"], layers["kv_layer"]) == (16, 17)
    assert layers["diff_depths"] == tuple(range(32))
    names = {"mamba": "mamba1", "memory_mamba": "mamba1", "sliding": "swa",
             "full": "mha", "gmu": "gmu", "cross": "cross"}
    assert kinds == tuple(names[ref.kind_of(i, 32)] for i in range(32))
    assert ref.kind_of(16, 32) == "memory_mamba"
    # the cell's cut: published layers 15-19, indexes among the held
    cut = transformer_lm_zoo.sambay_layers(32, (15, 5))
    assert cut == dict(
        layer_types=("swa", "mamba1", "mha", "gmu", "cross"),
        diff_depths=(15, 16, 17, 18, 19), memory_layer=1, kv_layer=2,
    )
    # a cut that holds neither feeder names none
    tail = transformer_lm_zoo.sambay_layers(32, (18, 4))
    assert (tail["memory_layer"], tail["kv_layer"]) == (None, None)
    with pytest.raises(ValueError, match="N / 2 even"):
        transformer_lm_zoo.sambay_layers(30)


@pytest.mark.parametrize("depth", [1, 15, 17, 19, 31])
def test_lambda_init_follows_the_published_index(ref, depth):
    want = 0.8 - 0.6 * np.exp(-0.3 * depth)
    assert lm.diff_lambda_init(depth) == pytest.approx(want, rel=1e-12)
    assert ref.lambda_init(depth) == pytest.approx(want, rel=1e-12)


def test_a_reader_without_its_feeder_is_refused():
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="no earlier 'mamba1' layer"):
        lm.init_params(rng, tiny.custom_model((8, 4)).cfg)  # gmu, cross, ..
    with pytest.raises(ValueError, match="no earlier 'mha' layer"):
        lm.init_params(rng, tiny.custom_model(
            layer_types=("mamba1", "gmu", "cross"), n_layers=3,
            diff_depths=(6, 8, 9), memory_layer=0, kv_layer=None,
        ).cfg)
    with pytest.raises(ValueError, match="is no 'mamba1' layer"):
        lm.init_params(rng, tiny.custom_model(memory_layer=7).cfg)
    with pytest.raises(ValueError, match="diff_depths"):
        lm.init_params(rng, tiny.custom_model(diff_depths=(0, 1)).cfg)
    for setting in (dict(ssm1_inner=8), dict(diff_attention=True),
                    dict(norm="layer"), dict(attn_bias=True),
                    dict(memory_layer=0), dict(kv_layer=0)):
        with pytest.raises(NotImplementedError, match="mamba1"):
            lm.param_partition_specs(lm.TransformerConfig(**setting))


def test_narrow_leaves_lie_where_the_compiler_reads_them_whole():
    """No leaf ends in the state's 16 columns or a head's 64: `a_log`
    is [state, inner] and a layer's lambda vectors and pair norm's
    weight are one leaf."""
    params = lm.init_params(np.random.default_rng(0), tiny.custom_model().cfg)
    mamba, attention = params["stack"][0], params["stack"][1]
    s = tiny.SIZES
    assert mamba["a_log"].shape == (1, s["ssm1_state"], s["ssm1_inner"])
    np.testing.assert_allclose(
        np.exp(mamba["a_log"][0, :, 0]), np.arange(1, s["ssm1_state"] + 1),
        rtol=1e-6,
    )
    assert attention["diff"].shape == (1, 6 * s["head_width"])
    assert not any(
        name.startswith("lambda") for run in params["stack"] for name in run
    )


@pytest.fixture(scope="module")
def agreement(ref):
    """The uncut float32 program's case, the reference's logits and
    loss for it, and how far the program itself lies from them."""
    model, variables, params, tokens, targets = _case(seed=3)
    sizes = _sizes((0, tiny.PUBLISHED_LAYERS))
    want = np.asarray(ref.logits_of(params, tokens, sizes))
    return params, tokens, targets, want


def _logits_rel(compare, name, agreement, **overrides):
    params, tokens, targets, want = agreement
    model = tiny.custom_model(**overrides)
    variables = {"params": params}
    with compare.control(name):
        got = jax.jit(lambda p: model.apply({**variables, "params": p}, tokens))(
            params
        )
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


def test_the_controls_each_fall_outside_the_agreement(compare, agreement):
    """The program agrees with the reference to rounding; each of the
    comparison's controls (`compare.py`: a swap or a model override)
    moves the logits a thousand times further."""
    own = _logits_rel(compare, "timed", agreement)
    assert own < 2e-5, own
    assert set(compare.CONTROLS) == set(compare.SWAPS) | set(compare.OVERRIDES)
    for name in compare.CONTROLS:
        off = _logits_rel(
            compare, name, agreement, **compare.OVERRIDES.get(name, {})
        )
        # bfloat16's decay is a rounding, the others are other models
        assert off > (1e-3 if name == "bf16_decay" else 0.02), (name, off)


# The tiny program of the state-space expert cell, traced on the parent
# of this change (commit 6a8a526): the same digest, so `cfg.runs`'
# break at a giving layer, the `ys` that carry what a run hands on, the
# norm's choice and `attend`'s third result changed nothing it runs.
# (`test_mamba2_lm.py` pins the five other routed fixtures.)
TRACED = {"mamba2_lm_tiny": "2e9631248a6c7813"}


def _digest(fixture):
    module = importlib.import_module("fixtures." + fixture)
    model = module.custom_model()
    variables = model.init(jax.random.PRNGKey(0), None)
    tokens = jnp.zeros((2, 32), jnp.int32)

    def loss(p):
        out, _ = model.apply({**variables, "params": p}, tokens, mutable=True)
        return module.loss(out, tokens)

    text = str(jax.make_jaxpr(jax.value_and_grad(loss))(variables["params"]))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("fixture", sorted(TRACED))
def test_an_existing_cells_tiny_program_traces_as_it_did(fixture):
    assert _digest(fixture) == TRACED[fixture]
