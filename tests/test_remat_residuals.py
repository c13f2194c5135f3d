"""What a rematerialised layer keeps: the attention kernels' output and
log-sum-exp, by name, so its backward pass holds the two backward
kernels and not the forward kernel a second time.

The kernels run in the Pallas interpreter here. The dispatcher hands a
call to them on a TPU alone, so the `kernels` fixture puts the
interpreted kernels where the dispatcher's XLA path is: what a test
traces is the dispatcher itself (its widening of key-value heads
included) in front of `flash_attention`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from elasticdl_tpu.models import transformer_lm as lm
from elasticdl_tpu.ops import flash_attention as fa

L = 2 * fa.BLOCK
D_MODEL = 32


@pytest.fixture
def kernels(monkeypatch):
    def interpreted(q, k, v, causal=True, scale=None, window=None):
        return fa.flash_attention(
            q, k, v, causal, interpret=True, window=window, scale=scale
        )

    monkeypatch.setattr(fa, "reference_attention", interpreted)


def pallas_calls(fn, *args) -> int:
    """The `pallas_call`s in fn's jaxpr; a scanned or rematerialised
    body is in it once."""
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


def assert_same(a, b):
    """Bit for bit, leaf by leaf."""
    leaves = jax.tree_util.tree_leaves
    for x, y in zip(leaves(a), leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# query heads, key-value heads, (query and key | value) widths, and what
# the dispatcher is told besides
LAYER_CASES = {
    "causal": (2, 2, (32, 32), {}),
    "banded": (2, 2, (32, 32), {"window": 160}),
    "latent-192-128-scaled": (1, 1, (192, 128), {"scale": 0.11472}),
    "fewer-kv-heads": (4, 2, (16, 16), {}),
}


def _layer(case, seed=0):
    """(body, params, x): one layer's attention between its projections
    and a residual, as `plain_forward`'s body has it."""
    heads, kv_heads, (d, dv), kw = LAYER_CASES[case]
    rng = np.random.default_rng(seed)

    def leaf(*shape):
        return jnp.asarray(
            rng.standard_normal(shape) / np.sqrt(shape[0]), jnp.float32
        )

    params = {
        "wq": leaf(D_MODEL, heads * d), "wk": leaf(D_MODEL, kv_heads * d),
        "wv": leaf(D_MODEL, kv_heads * dv), "wo": leaf(heads * dv, D_MODEL),
    }
    x = leaf(1, L, D_MODEL)

    def body(params, x):
        q = (x @ params["wq"]).reshape(1, L, heads, d)
        k = (x @ params["wk"]).reshape(1, L, kv_heads, d)
        v = (x @ params["wv"]).reshape(1, L, kv_heads, dv)
        out = fa.attention(q, k, v, **kw).reshape(1, L, heads * dv)
        return x + jnp.tanh(out @ params["wo"])

    return body, params, x


def _grad(body):
    return jax.grad(lambda params, x: jnp.sum(body(params, x) ** 2), (0, 1))


@pytest.mark.parametrize("case", LAYER_CASES)
def test_a_rematerialised_layer_holds_three_kernel_calls(kernels, case):
    body, params, x = _layer(case)
    plain, full, kept = (
        _grad(wrap(body)) for wrap in (lambda f: f, jax.checkpoint, lm._remat)
    )
    assert pallas_calls(plain, params, x) == 3
    assert pallas_calls(full, params, x) == 4  # the forward kernel again
    assert pallas_calls(kept, params, x) == 3
    want = jax.jit(plain)(params, x)
    assert_same(jax.jit(full)(params, x), want)
    assert_same(jax.jit(kept)(params, x), want)


def _residuals(body, params, x):
    """What the backward pass is handed, beside the arguments."""
    return sorted(
        (str(aval), why)
        for aval, why in saved_residuals(body, params, x)
        if "from the argument" not in why
    )


def test_the_two_residuals_kept_are_the_output_and_the_log_sum_exp(kernels):
    body, params, x = _layer("fewer-kv-heads")
    assert _residuals(jax.checkpoint(body), params, x) == []
    kept = _residuals(lm._remat(body), params, x)
    assert [aval for aval, _ in kept] == [
        f"float32[1,{L},4,16]", f"float32[4,{L},1]"
    ]
    assert f"named '{fa.RESIDUAL_NAMES[1]}'" in kept[1][1]


def test_off_the_kernels_the_policy_keeps_nothing():
    """XLA's attention names nothing: the residuals and the gradients
    are full rematerialisation's."""
    body, params, x = _layer("banded")
    assert pallas_calls(_grad(lm._remat(body)), params, x) == 0
    assert _residuals(lm._remat(body), params, x) == []
    assert_same(
        jax.jit(_grad(lm._remat(body)))(params, x),
        jax.jit(_grad(jax.checkpoint(body)))(params, x),
    )


# a stack of two layers under one `lax.scan`; the looped LM's two passes
# over it under a second
MODEL_CASES = {
    "remat-stack": {"remat": True},
    "looped": {
        "n_loops": 2, "mlp": "swiglu", "sandwich_norm": True,
        "rope_base": 1e6,
    },
    "no-remat": {},
}


def _model(case):
    cfg = lm.TransformerConfig(
        vocab=32, d_model=D_MODEL, n_heads=2, d_ff=48, n_layers=2,
        **MODEL_CASES[case],
    )
    params = jax.tree_util.tree_map(
        jnp.asarray, lm.init_params(np.random.default_rng(0), cfg)
    )
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab, (1, L + 1)), jnp.int32
    )

    def loss(params):
        out, _aux = lm.plain_forward(cfg, params, tokens[:, :-1])
        if cfg.looped:
            return lm.looped_exit_loss(out, tokens[:, 1:])
        return lm.token_cross_entropy(out, tokens[:, 1:])

    return jax.value_and_grad(loss), params


@pytest.mark.parametrize("case", ["remat-stack", "looped"])
def test_a_rematerialised_stack_holds_three_kernel_calls(
    kernels, monkeypatch, case
):
    """The scanned layer body is in the jaxpr once: three calls with the
    two residuals kept, four under `jax.checkpoint` alone, and the loss
    and gradients of both are those of the stack that keeps everything."""
    step, params = _model(case)
    assert pallas_calls(step, params) == 3
    got = jax.jit(step)(params)
    for wrap, calls in ((jax.checkpoint, 4), (lambda body: body, 3)):
        monkeypatch.setattr(lm, "_remat", wrap)
        step, _ = _model(case)  # a function jax has not traced yet
        assert pallas_calls(step, params) == calls
        assert_same(jax.jit(step)(params), got)


def test_a_stack_that_keeps_everything_traces_as_it_did(kernels, monkeypatch):
    """Under no `jax.checkpoint` a name is an identity: the kernel calls
    and the numbers of a `remat=False` model are those without names."""
    step, params = _model("no-remat")
    calls, got = pallas_calls(step, params), jax.jit(step)(params)
    assert calls == 3
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    step, _ = _model("no-remat")  # a function jax has not traced yet
    assert pallas_calls(step, params) == calls
    assert_same(jax.jit(step)(params), got)


def test_the_configuration_has_no_policy_of_its_own():
    names = {f.name for f in dataclasses.fields(lm.TransformerConfig)}
    assert "remat" in names and "remat_policy" not in names
