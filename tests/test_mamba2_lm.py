"""The state-space expert LM (Nemotron-3-Nano's blocks at a tiny size:
`tests/fixtures/mamba2_lm_tiny.py`) against the plain reference of its
configuration (`benchmark/configs/nemotron-3-nano-30b-a3b/reference.py`,
which walks the published pattern a block at a time) on seeded weights:
the loss and every gradient leaf; controls that a forced piece must
fail; the shares of an expert-parallel block adding up to the uncut
one; the squared-ReLU experts on every rung; the blocks that are a
mixer alone; and the other configurations' tiny programs tracing to
the jaxprs they traced to before `moe_topk_held`, `_causal_conv` and
`cfg.runs` took what this model needs."""

import hashlib
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

from benchmark.harness.manifest import load_module  # noqa: E402
from elasticdl_tpu.common.constants import WINDOW_STATS  # noqa: E402
from elasticdl_tpu.models import transformer_lm as lm  # noqa: E402
from elasticdl_tpu.parallel import moe  # noqa: E402
from fixtures import mamba2_lm_tiny as tiny  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs", "nemotron-3-nano-30b-a3b")
REF = load_module(os.path.join(CONFIG, "reference.py"))


def sizes_of(cfg, **overrides):
    sizes = dict(
        ssm_heads=cfg.ssm_heads, ssm_head_dim=cfg.ssm_head_dim,
        ssm_groups=cfg.ssm_groups, ssm_state=cfg.ssm_state,
        heads=cfg.n_heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        eps=cfg.norm_eps, top_k=cfg.moe_top_k, scaling=cfg.routed_scaling,
        held=cfg.held, blocks=tiny.PATTERN,
    )
    sizes.update(overrides)
    return sizes


def seeded(seed=3, **overrides):
    """(model, variables, params with the selection bias and the
    convolution's bias off zero, tokens)."""
    model = tiny.custom_model(dtype=jnp.float32, **overrides)
    variables = model.init(jax.random.PRNGKey(seed), None)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    for run in params["stack"]:
        for name, scale in (("router_bias", 0.01), ("conv_bias", 0.3)):
            if name in run:
                run[name] = run[name] + scale * jax.random.normal(
                    next(keys), run[name].shape
                )
    tokens = jnp.asarray(
        np.random.default_rng(seed).integers(0, model.cfg.vocab, (2, 33))
    )
    return model, variables, params, tokens


def program(model, variables, tokens):
    def loss(p):
        (logits, aux), state = model.apply(
            {**variables, "params": p}, tokens[:, :-1], mutable=True
        )
        return tiny.loss((logits, aux), tokens[:, 1:]), state[WINDOW_STATS]

    return loss


def reference(model, tokens, **overrides):
    sizes = sizes_of(model.cfg, **overrides)
    return lambda p: REF.parts(p, tokens[:, :-1], tokens[:, 1:], sizes)


def test_the_program_holds_to_the_reference_loss_loads_and_every_gradient_leaf():
    model, variables, params, tokens = seeded()
    with jax.default_matmul_precision("highest"):
        (got, stats), grads = jax.value_and_grad(
            program(model, variables, tokens), has_aux=True
        )(params)
        (want, loads), wants = jax.value_and_grad(
            reference(model, tokens), has_aux=True
        )(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    first, held = model.cfg.held
    np.testing.assert_array_equal(
        stats["expert_tokens"], loads[:, first:first + held]
    )
    assert stats["expert_tokens"].shape == (3, held)  # three `E` blocks
    for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_leaves(wants),
    ):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # a leaf no gradient reaches
            assert float(jnp.max(jnp.abs(a))) == float(jnp.max(jnp.abs(b))) == 0.0
            continue
        assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) < 2e-5, name
    assert float(stats["ssm_log_decay_min"]) < 0.0
    assert 0.0 < float(stats["ssm_dt_mean"]) < 0.2
    assert float(stats["router_bias_absmax"]) > 0.0


def test_the_bfloat16_model_stays_near_the_reference():
    model, variables, params, tokens = seeded()
    timed = tiny.custom_model(dtype=jnp.bfloat16)
    got = jax.grad(lambda p: program(timed, variables, tokens)(p)[0])(params)
    a, b = ravel_pytree(got)[0], reference_gradient()
    assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 0.15


_WANT = []


def reference_gradient():
    """The reference's flat gradient at `seeded()`'s weights, once."""
    if not _WANT:
        model, _variables, params, tokens = seeded()
        with jax.default_matmul_precision("highest"):
            want = jax.grad(lambda p: reference(model, tokens)(p)[0])(params)
        _WANT.append(ravel_pytree(want)[0])
    return _WANT[0]


# the controls are `compare.py`'s own (what it swaps on the chip), all
# but `bf16_decay`, which it judges by the scan alone
COMPARE = load_module(os.path.join(CONFIG, "compare.py"))
CONTROLS = {
    name: swap for name, swap in COMPARE.SWAPS.items() if name != "bf16_decay"
}
OVERRIDES = COMPARE.OVERRIDES


@pytest.mark.parametrize("control", sorted(CONTROLS) + sorted(OVERRIDES))
def test_a_forced_piece_falls_outside_the_float32_agreement(
    control, monkeypatch
):
    """Each piece the reference states (the decay, the group a head
    reads, gate then GROUPED norm, the skip, the square, the scaling,
    no rotation, the key-value head a query reads) moves the gradient
    by far more than rounding does."""
    model, variables, params, tokens = seeded(**OVERRIDES.get(control, {}))
    if control in CONTROLS:
        monkeypatch.setattr(*CONTROLS[control])
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: program(model, variables, tokens)(p)[0])(params)
    # the reference as the configuration states it, no override
    a, b = ravel_pytree(got)[0], reference_gradient()
    assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) > 0.02, control


def test_sixteen_shares_of_an_expert_block_add_up_to_the_uncut_block():
    """One `E` block of 128 router outputs, top-6: the routed parts
    that the 16 chips' programs give (8 experts each), plus the shared
    expert counted once, are the uncut reference's block."""
    d, f, e, k, t = 32, 12, 128, 6, 64
    keys = iter(jax.random.split(jax.random.PRNGKey(9), 8))
    x = jax.random.normal(next(keys), (1, t, d))
    lp = {
        "router": jax.random.normal(next(keys), (d, e)) / d**0.5,
        "router_bias": 0.05 * jax.random.normal(next(keys), (e,)),
        "eu": jax.random.normal(next(keys), (e, d, f)) / d**0.5,
        "ed": jax.random.normal(next(keys), (e, f, d)) / f**0.5,
        "su": jax.random.normal(next(keys), (d, 2 * f)) / d**0.5,
        "sd": jax.random.normal(next(keys), (2 * f, d)) / f**0.5,
    }
    sizes = {"top_k": k, "scaling": 2.5, "held": (0, e)}
    with jax.default_matmul_precision("highest"):
        whole, loads = REF.experts(lp, x, sizes)
        parts, counts = REF.relu2_mlp(x, lp["su"], lp["sd"]), []
        for chip in range(16):
            first = 8 * chip
            mine = slice(first, first + 8)
            routed, _aux, stats = moe.moe_topk_held(
                x, lp["router"], (lp["eu"][mine], lp["ed"][mine]), None,
                top_k=k, held=(first, 8), scaling=2.5, score="sigmoid",
                bias=lp["router_bias"], renormalize=True, balance=False,
            )
            parts = parts + routed
            counts.append(stats["expert_tokens"])
    np.testing.assert_allclose(parts, whole, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(jnp.concatenate(counts), loads)
    assert float(jnp.sum(loads)) == t * k


@pytest.mark.parametrize("rung", [0, 1, 2, 3])
def test_squared_relu_experts_on_every_rung_hold_to_the_dense_sum(rung):
    """Two leaves an expert: `_held_experts` on each rung of the
    ladder, forced, against the reference's masked dense sum, the
    output and the gradients of x and both matrices."""
    d, f, e, k, t, held = 16, 8, 8, 2, 1024, (2, 4)
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 6))
    x = jax.random.normal(next(keys), (1, t, d))
    # scores that send few assignments here, so that every rung holds them
    router = jax.random.normal(next(keys), (d, e)) / d**0.5
    bias = jnp.where((jnp.arange(e) >= 2) & (jnp.arange(e) < 6), -0.4, 0.0)
    eu = jax.random.normal(next(keys), (held[1], d, f)) / d**0.5
    ed = jax.random.normal(next(keys), (held[1], f, d)) / f**0.5
    rungs = moe.route_rungs(t, k, held[1])
    assert len(rungs) == 4

    def layer(x, eu, ed):
        return jnp.sum(jnp.sin(moe.moe_topk_held(
            x, router, (eu, ed), None, top_k=k, held=held, scaling=2.5,
            score="sigmoid", bias=bias, renormalize=True, balance=False,
        )[0]))

    def dense(x, eu, ed):
        lp = {"router": router, "router_bias": bias, "eu": eu, "ed": ed}
        sizes = {"top_k": k, "scaling": 2.5, "held": held}
        return jnp.sum(jnp.sin(REF.experts(lp, x, sizes, shared=False)[0]))

    forced = lambda rungs_, sizes: jnp.int32(rung)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(dense, argnums=(0, 1, 2))(x, eu, ed)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(moe, "_rung_taken", forced)
            got = jax.value_and_grad(layer, argnums=(0, 1, 2))(x, eu, ed)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)


def test_a_convolution_with_a_bias_is_the_taps_plus_the_bias():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 9, 6))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    got = jax.nn.silu(lm._causal_conv(x, taps, bias))
    np.testing.assert_allclose(
        got, REF.short_conv(x, taps, bias), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_array_equal(
        lm._causal_conv(x, taps), lm._causal_conv(x, taps, None)
    )


def test_a_block_with_no_feed_forward_part_is_a_run_of_its_own():
    cfg = tiny.custom_model().cfg
    assert cfg.runs == (
        ("mamba2", True, 2), ("mamba2", None, 1), ("mha", True, 1)
    )
    params = lm.init_params(np.random.default_rng(0), cfg)
    paired, bare, attention = params["stack"]
    assert "ln2" in paired and "router" in paired and "eg" not in paired
    assert sorted(bare) == [
        "conv", "conv_bias", "in_proj", "ln1", "out_proj", "ssm_norm",
    ]
    assert "wq" in attention and "sg" not in attention
    # [a_log | dt_bias | D] of the three Mamba-2 layers' four heads
    decay = params["ssm_decay"].reshape(3, 3, cfg.ssm_heads)
    assert np.all((decay[0] >= 0.0) & (decay[0] <= np.log(16.0)))
    dt = np.log1p(np.exp(decay[1]))  # softplus of the bias: the step drawn
    assert np.all((dt >= 1e-4) & (dt <= 0.1 + 1e-6))
    assert np.all(decay[2] == 1.0)
    # the output projection by 1 / sqrt(52): rescale_prenorm_residual
    plain = lm.init_params(
        np.random.default_rng(0),
        tiny.custom_model(ssm_residual_blocks=0).cfg,
    )
    np.testing.assert_allclose(
        paired["out_proj"] * np.sqrt(52.0), plain["stack"][0]["out_proj"],
        rtol=1e-6,
    )


def test_the_zoo_module_walks_the_published_pattern_to_the_program_s_layers():
    zoo = load_module(os.path.join(CONFIG, "zoo.py"))
    assert zoo.layers_of("MEMEM*E") == (
        ("mamba2", "mamba2", "mamba2", "mha"), (2,)
    )
    mixers, bare = zoo.layers_of(zoo.SIZES["hybrid_override_pattern"])
    assert (mixers.count("mamba2"), mixers.count("mha")) == (23, 6)
    assert len(mixers) - len(bare) == 23  # every `E` sits behind a mixer
    with pytest.raises(ValueError, match="behind no mixer"):
        zoo.layers_of("EM")
    with pytest.raises(ValueError, match="behind no mixer"):
        zoo.layers_of("MEE")
    cfg = zoo.custom_model().cfg
    assert cfg.runs == (
        ("mamba2", True, 2), ("mamba2", None, 1), ("mha", True, 1)
    )
    assert (cfg.n_experts, cfg.held, cfg.moe_top_k) == (128, (0, 8), 6)
    assert (cfg.n_shared_experts * cfg.d_expert, cfg.rope) == (3712, False)


def test_the_refusals_name_every_mixer_and_the_new_settings():
    with pytest.raises(NotImplementedError) as raised:
        lm.init_params(
            np.random.default_rng(0),
            lm.TransformerConfig(layer_types=("mamba2",), n_layers=1),
        )
    for mixer in lm.ROUTED_MIXERS:
        assert repr(mixer) in str(raised.value)
    assert "relu2" in str(raised.value)
    with pytest.raises(NotImplementedError, match="no dense layer"):
        lm.init_params(np.random.default_rng(0), tiny.custom_model(
            n_dense_layers=1
        ).cfg)
    for setting in (dict(bare_layers=(0,)), dict(rope=False),
                    dict(ssm_heads=4), dict(mlp="relu2")):
        with pytest.raises(NotImplementedError, match="mamba2"):
            lm.param_partition_specs(lm.TransformerConfig(**setting))


# The tiny programs of the five other routed configurations, traced on
# the parent of this change (commit a0208b7): the same digests, so
# `moe_topk_held`'s tuple of leaves, `_causal_conv`'s bias and
# `cfg.runs`' third kind changed nothing they run.
TRACED = {
    "hybrid_lm_tiny": "e917706d1a0e550c",
    "shortconv_lm_tiny": "6f747560ef4c81a5",
    "window_lm_tiny": "9fd167ac7be20c53",
    "routed_lm_tiny": "dab5e0e03bc7c6bd",
    "gdn_lm_tiny": "fd6950afd1970701",
}


@pytest.mark.parametrize("fixture", sorted(TRACED))
def test_the_other_configurations_tiny_programs_trace_as_they_did(fixture):
    module = importlib.import_module("fixtures." + fixture)
    model = module.custom_model()
    variables = model.init(jax.random.PRNGKey(0), None)
    tokens = jnp.zeros((2, 32), jnp.int32)

    def loss(p):
        out, _ = model.apply({**variables, "params": p}, tokens, mutable=True)
        return module.loss(out, tokens)

    text = str(jax.make_jaxpr(jax.value_and_grad(loss))(variables["params"]))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == TRACED[fixture]
