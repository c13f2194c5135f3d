"""The looped LM (`ouro-2.6b`): its zoo module against its plain
reference on seeded random weights at a small size, float32 on the
CPU, and what ties the configuration's cuts to the model.

Tolerance: both sides are float32 with the same mathematics in another
order (an outer scan over passes of a scan over layers with
rematerialization, against two Python loops; log-sigmoid sums against
products of probabilities), so they agree to accumulated rounding — a
relative 2e-4 of the largest value, `test_benchmark_reference.py`'s,
far tighter than bfloat16's 4e-3: a lower precision or a left-out term
(the entropy, a norm of the sandwich, the final norm between passes, a
pass) on either side fails."""

import json
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)
TOLERANCE = 2e-4
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "ouro-2.6b")

from benchmark.harness import flops, manifest as manifest_lib  # noqa: E402
from benchmark.harness.manifest import load_module  # noqa: E402


def load(name):
    return load_module(os.path.join(CONFIG_DIR, name + ".py"))


def close(a, b, tolerance=TOLERANCE):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tolerance * max(np.max(np.abs(b)), 1e-6)


def small(zoo, layers, passes, vocab=97, **overrides):
    import jax.numpy as jnp

    return zoo.custom_model(
        dtype=jnp.float32, vocab=vocab, d_model=48, n_heads=4, d_ff=80,
        n_layers=layers, n_loops=passes, **overrides,
    )


def seeded(model, seed=3, vocab=97, length=33):
    """Weights from a seed with every leaf filled (the gate's bias and
    the norms' scales start at 0 and 1: a test that leaves them there
    cannot see a term they multiply away) and one batch of tokens."""
    import jax
    import jax.numpy as jnp

    params = model.init(jax.random.PRNGKey(seed), None)["params"]
    rng = np.random.default_rng(seed + 1)
    leaves, tree = jax.tree_util.tree_flatten(params)
    params = jax.tree_util.tree_unflatten(tree, [
        jnp.asarray(l + rng.normal(0, 0.1, l.shape), jnp.float32)
        for l in leaves
    ])
    tokens = jnp.asarray(rng.integers(0, vocab, size=(2, length)), jnp.int32)
    return params, tokens[:, :-1], tokens[:, 1:]


def test_every_exit_the_gates_q_and_the_loss_match_the_reference():
    import jax

    from elasticdl_tpu.models.transformer_lm import exit_distribution

    zoo, ref = load("zoo"), load("reference")
    model = small(zoo, layers=2, passes=3)
    params, inputs, targets = seeded(model)
    out = jax.jit(lambda p: model.apply({"params": p}, inputs))(params)
    logits, gates = jax.jit(lambda p: ref.forward(p, inputs, 4, 3))(params)
    assert out.logits.shape == (3, 2, 32, 97)
    for t in range(3):  # every exit, not their mean
        assert close(out.logits[t], logits[t]), t
    assert close(jax.nn.sigmoid(out.gates), gates)
    q = exit_distribution(out.gates)[0]
    assert close(q, ref.exit_distribution(gates))
    assert np.allclose(np.sum(q, axis=0), 1.0, atol=1e-6)
    value, ce, mean_q = ref.parts(params, inputs, targets, 4, 3, beta=0.1)
    assert float(zoo.loss(out, targets)) == pytest.approx(
        float(value), rel=TOLERANCE
    )
    # the entropy term is in it: without it the loss is another number
    no_entropy = float(ref.loss(params, inputs, targets, 4, 3, beta=0.0))
    assert abs(no_entropy - float(value)) > 50 * TOLERANCE * abs(float(value))
    # the collection the worker's span reads: the step's mean q
    _out, state = model.apply(
        {"params": params, "window_stats": {}}, inputs,
        mutable=["window_stats"],
    )
    stats = state["window_stats"]
    assert close(stats["exit_q"], mean_q)
    assert float(stats["expected_exit"]) == pytest.approx(
        float(np.sum(np.arange(1, 4) * np.asarray(mean_q))), rel=1e-5
    )


SLOW = pytest.mark.slow  # the larger gradient compiles for a while on the CPU


@pytest.mark.parametrize(
    "layers,passes", [(1, 2), pytest.param(2, 4, marks=SLOW)]
)
def test_gradients_match_the_reference(layers, passes):
    import jax

    zoo, ref = load("zoo"), load("reference")
    model = small(zoo, layers, passes)
    params, inputs, targets = seeded(model)

    def zoo_loss(p):
        return zoo.loss(model.apply({"params": p}, inputs), targets)

    value, grads = jax.jit(jax.value_and_grad(zoo_loss))(params)
    ref_value, ref_grads = ref.loss_and_grads(params, inputs, targets, 4, passes)
    assert float(value) == pytest.approx(float(ref_value), rel=TOLERANCE)
    ours = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), r in zip(ours, jax.tree_util.tree_leaves(ref_grads)):
        assert close(g, r), jax.tree_util.keystr(path)


def test_compare_py_reads_the_gradient_out_of_the_worker_s_own_step():
    """`compare.py` holds against the reference what `Worker`'s own
    builder jits (`_build_local_step`), with the optimizer swapped for
    one whose state is the gradient: that state equals the reference's
    gradient, the loss its loss, and the `window_stats` the step leaves
    (the `mutable` path) its mean exit distribution."""
    import jax
    from jax.flatten_util import ravel_pytree

    zoo, ref, compare = load("zoo"), load("reference"), load("compare")
    model = small(zoo, layers=1, passes=2)
    params, inputs, targets = seeded(model)
    variables = {**model.init(jax.random.PRNGKey(0), None), "params": params}
    step = compare.WorkerStep(zoo, model, variables)
    got = step(ravel_pytree(params)[0], inputs, targets)
    value, ce, mean_q = ref.parts(params, inputs, targets, 4, 2, beta=0.1)
    _value, grads = ref.loss_and_grads(params, inputs, targets, 4, 2)
    assert got["loss"] == pytest.approx(float(value), rel=TOLERANCE)
    assert close(got["ce"], ce) and close(got["q"], mean_q)
    assert close(got["grad"], ravel_pytree(grads)[0])


def test_a_shared_leaf_s_gradient_is_the_sum_over_its_passes():
    """The mechanism: one set of layer weights used T times. With T
    untied copies of the stack set equal, the copies' gradients add up
    to the shared stack's, leaf by leaf (zoo and reference alike)."""
    import jax
    import jax.numpy as jnp

    zoo, ref = load("zoo"), load("reference")
    passes = 3
    model = small(zoo, layers=1, passes=passes)
    params, inputs, targets = seeded(model)

    def untied(copies):
        p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
        h, exits = p["embed"][inputs], []
        for layers in copies:  # pass t runs copy t
            h = ref.one_pass(p, layers, h, 4)
            exits.append(ref.exit_of(p, h))
        return ref.objective(
            jnp.stack([e[0] for e in exits]), jnp.stack([e[1] for e in exits]),
            targets,
        )[0]

    per_copy = jax.grad(untied)([params["layers"]] * passes)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_copy)
    shared = jax.grad(
        lambda p: zoo.loss(model.apply({"params": p}, inputs), targets)
    )(params)["layers"]
    ref_shared = ref.loss_and_grads(params, inputs, targets, 4, passes)[1]["layers"]
    for name in summed:
        assert close(shared[name], summed[name]), name
        assert close(ref_shared[name], summed[name]), name
        # and no single pass gives it
        assert not close(per_copy[0][name], summed[name], 1e-2), name


def test_rotary_angles_are_float32_at_position_2047_with_base_1e6():
    """bfloat16 holds no odd number above 256: an angle computed in the
    compute dtype turns position 2047 as 2048, a whole radian off in
    the fastest pair. Against float64, within bfloat16's rounding of
    the rotated values alone."""
    import jax.numpy as jnp

    from elasticdl_tpu.models.transformer_lm import _rope

    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 2048, 2, 128))
    half = 64
    freqs = 1.0 / 1e6 ** (np.arange(half) / half)
    angle = np.arange(2048)[:, None] * freqs[None, :]
    cos, sin = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]
    want = np.concatenate(
        [x[..., :half] * cos - x[..., half:] * sin,
         x[..., :half] * sin + x[..., half:] * cos], -1,
    )
    got32 = _rope(jnp.asarray(x, jnp.float32), jnp.arange(2048), 1e6)
    assert np.max(np.abs(np.asarray(got32, np.float64) - want)) < 2e-3
    got16 = _rope(jnp.asarray(x, jnp.bfloat16), jnp.arange(2048), 1e6)
    assert got16.dtype == jnp.bfloat16
    at_2047 = np.abs(np.asarray(got16, np.float64) - want)[0, 2047]
    # x and the cosines rounded to 8 bits, one product and one sum: a
    # few 2^-8 of |x| <= 4.5; a radian off would be of the order of |x|
    assert np.max(at_2047) < 0.08, np.max(at_2047)
    assert np.max(np.abs(np.asarray(got16, np.float64) - want)) < 0.1


def test_the_four_vocabulary_shares_add_up_to_the_whole_head():
    """The configuration holds one chip's share of a vocabulary-parallel
    embedding and head (12,288 of 49,152 rows). At a small size: the
    four shares' rows are the whole table's, and with the batch's ids in
    one share (as the traffic draws them) the four shares' logits side
    by side are the whole head's at every exit, the gates the same."""
    import jax
    import jax.numpy as jnp

    zoo = load("zoo")
    share, chips = 24, 4
    whole = small(zoo, layers=1, passes=2, vocab=share * chips)
    params, _, _ = seeded(whole, vocab=share * chips)
    rng = np.random.default_rng(9)
    inputs = jnp.asarray(rng.integers(0, share, size=(2, 16)), jnp.int32)
    want = whole.apply({"params": params}, inputs)
    shares = [
        {**params,
         "embed": params["embed"][k * share:(k + 1) * share],
         "head": params["head"][:, k * share:(k + 1) * share]}
        for k in range(chips)
    ]
    assert np.array_equal(
        np.concatenate([s["embed"] for s in shares]), params["embed"]
    )
    model = small(zoo, layers=1, passes=2, vocab=share)
    # every chip reads the hidden states the ids' owner embedded
    outs = [
        model.apply({"params": {**s, "embed": shares[0]["embed"]}}, inputs)
        for s in shares
    ]
    side_by_side = jnp.concatenate([o.logits for o in outs], axis=-1)
    assert side_by_side.shape == want.logits.shape
    assert close(side_by_side, want.logits, 1e-6)
    for o in outs:
        assert close(o.gates, want.gates, 1e-6)


def test_flops_are_the_hand_count():
    # per token: 16 layer applications x (4 x 2048^2 + 3 x 2048 x 5632)
    #   = 16 x 51_380_224 = 822_083_584; four exits x (2048 x 12288 + 2048)
    #   = 100_671_488; causal attention 16 x 2 x 2048 x 2049 / 2 = 67_141_632
    # -> 989_896_704 MACs, x 6 = 5_939_380_224 FLOPs a token, x 2048
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        sizes = json.load(f)
    macs = 16 * (4 * 2048**2 + 3 * 2048 * 5632) + 4 * (2048 * 12288 + 2048)
    macs += 16 * 2 * 2048 * 2049 // 2
    assert macs == 989_896_704
    got = flops.flops_per_sample(sizes, CONFIG_DIR)
    assert got == pytest.approx(6 * macs * 2048, rel=1e-12)
    assert got == pytest.approx(12.164e12, rel=1e-4)
    stack = 6 * 16 * (4 * 2048**2 + 3 * 2048 * 5632 + 2048 * 2049) * 2048
    assert 0.89 < stack / got < 0.91  # "90 % of it in the looped stack"


def test_the_configuration_states_its_source_cuts_and_sizes():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        sizes = json.load(f)
    for key in ("source", "assumed", "reduced", "published", "deployment"):
        assert sizes[key], key
    assert sizes["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert sizes["published"]["num_hidden_layers"] == 48
    assert sizes["published"]["vocab_size"] == 49152 == 4 * sizes["vocab_size"]
    # the published widths, unchanged
    assert (sizes["hidden_size"], sizes["num_attention_heads"],
            sizes["num_key_value_heads"], sizes["head_dim"],
            sizes["intermediate_size"], sizes["total_ut_steps"]) == (
        2048, 16, 16, 128, 5632, 4)
    assert sizes["rope_theta"] == 1000000 and sizes["rms_norm_eps"] == 1e-6
    assert sizes["minibatch_per_chip"] & (sizes["minibatch_per_chip"] - 1) == 0
    layer = 4 * 2048**2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert sizes["parameters"] == 4 * layer + 2 * 12288 * 2048 + 2048 + 2049
    assert sizes["parameters"] == 255_889_409
    assert sizes["published"]["parameters"] == (
        48 * layer + 2 * 49152 * 2048 + 2048 + 2049
    )
    # the loss before the first step: ln 12288 - 0.1 H(1/2, 1/4, 1/8, 1/8)
    entropy = 0.5 * math.log(2) + 0.25 * math.log(4) + 0.25 * math.log(8)
    assert math.log(12288) - 0.1 * entropy == pytest.approx(9.295, abs=1e-3)
    with open(os.path.join(CONFIG_DIR, "zoo.py")) as f:
        assert "probe.start_if_worker()" in f.read()


def test_the_zoo_module_builds_the_stated_model():
    import jax

    from elasticdl_tpu.models import transformer_lm

    zoo = load("zoo")
    cfg = zoo.custom_model().cfg
    shapes = jax.eval_shape(
        lambda: transformer_lm.init_params(np.random.default_rng(0), cfg)
    )
    count = sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes)
    )
    assert count == zoo.SIZES["parameters"]
    assert (cfg.n_loops, cfg.mlp, cfg.sandwich_norm, cfg.rope_base) == (
        4, "swiglu", True, 1e6)
    assert str(cfg.dtype) == "bfloat16" and cfg.head_dim == 128


def test_the_committed_manifest_holds_this_configuration_and_its_cell():
    """`BENCHMARK.json` as committed lints clean, the cell resolves to
    this configuration's files, and the cell reports its three shares
    beside every metric that lists no cells. Nothing here counts entries
    or reads a list from its end: a later entry, of this configuration
    or another, breaks nothing."""
    committed = manifest_lib.load(ROOT)
    assert manifest_lib.lint(committed, ROOT) == []
    cell = "ouro-2.6b.window16-1w"
    resolved = manifest_lib.resolve(committed, cell, ROOT)
    assert resolved["cell"]["chips"] == 1
    assert resolved["mix"]["master_flags"] == {
        "local_updates": 16, "grads_to_wait": 1
    }
    assert resolved["config"]["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert resolved["config"]["source"] == resolved["sizes"]["source"]
    reported = manifest_lib.cell_metrics(committed, cell, "per_layer")
    for name in ("loop_stack_pct", "loop_attention_pct", "exit_head_pct"):
        assert reported[name]["workloads"] == [cell]
        assert reported[name]["source"] == "device_trace"
        assert os.path.isfile(manifest_lib.reader_file(name, ROOT))
    assert {
        m["name"] for m in committed["per_layer"] if "workloads" not in m
    } <= set(reported)
    assert {"goodput", "setup_s"} <= set(
        manifest_lib.cell_metrics(committed, cell, "end_to_end")
    )
