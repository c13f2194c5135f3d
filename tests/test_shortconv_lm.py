"""The short-convolution stack (LFM2's block at a tiny size) and its
parts, float32 on the CPU: the double-gated convolution against a
recurrence a token at a time from a cache of three rows, grouped-query
attention with normed queries and keys against head-by-head loops, the
program against the configuration's plain reference
(`benchmark/configs/lfm2-24b-a2b/reference.py`), the shares of an
expert layer WITHOUT a shared expert against the uncut layer, the tied
embedding's gradient, and what the change leaves as it was: a call of
`attention()` with equal heads, Kimi's tiny program.

Tolerance: both sides are float32 with the same mathematics in another
order, so they agree to accumulated rounding: a relative 2e-4 of the
largest value, the other configurations' tolerance."""

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

from benchmark.harness.manifest import load_module  # noqa: E402
from elasticdl_tpu.common.constants import WINDOW_STATS  # noqa: E402
from elasticdl_tpu.models import transformer_lm as lm  # noqa: E402
from elasticdl_tpu.ops import flash_attention  # noqa: E402
from elasticdl_tpu.parallel import moe  # noqa: E402

TOLERANCE = 2e-4
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b")


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def close(a, b, tolerance=TOLERANCE, floor=1e-6):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tolerance * max(np.max(np.abs(b)), floor)


def draws(seed, *shapes):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=s), jnp.float32) for s in shapes]


# ------------------------------------------------------- the convolution


def conv_leaves(seed=0, d=16, taps=3):
    in_proj, conv, out_proj = draws(seed, (d, 3 * d), (taps, d), (d, d))
    return {"in_proj": in_proj / 4, "conv": conv, "out_proj": out_proj / 4}


def conv_a_token_at_a_time(lp, x):
    """The mixer as a decoder runs it: a cache of the last three rows
    of b * u, numpy, one token and one sequence at a time."""
    lp = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    x = np.asarray(x, np.float64)
    d, taps = x.shape[-1], lp["conv"].shape[0]
    out = np.zeros_like(x)
    for s in range(x.shape[0]):
        cache = np.zeros((taps, d))
        for t in range(x.shape[1]):
            bcu = x[s, t] @ lp["in_proj"]
            b, c, u = bcu[:d], bcu[d:2 * d], bcu[2 * d:]
            cache = np.concatenate([cache[1:], (b * u)[None]])
            out[s, t] = (c * np.sum(cache * lp["conv"], axis=0)) @ lp["out_proj"]
    return out


@pytest.mark.parametrize("length", [1, 2, 3, 17])
@pytest.mark.parametrize("taps", [3, 4])
def test_the_conv_mixer_is_the_recurrence_from_a_cache_of_its_taps(length, taps):
    lp = conv_leaves(taps=taps)
    (x,) = draws(1, (2, length, 16))
    got, absmax = lm._conv(lm.TransformerConfig(conv_taps=taps), lp, x)
    assert close(got, conv_a_token_at_a_time(lp, x), 1e-5)
    assert float(absmax) > 0


def test_the_conv_mixer_s_gradients_are_the_recurrence_s():
    ref = load_module(os.path.join(CONFIG_DIR, "reference.py"))
    lp = conv_leaves()
    x, weight = draws(2, (2, 17, 16), (2, 17, 16))

    def through(f):
        return jax.grad(
            lambda lp, x: jnp.sum(f(lp, x) * weight), argnums=(0, 1)
        )(lp, x)

    got = through(lambda lp, x: lm._conv(lm.TransformerConfig(), lp, x)[0])
    want = through(ref.conv_mixer)  # `lax.scan` over the tokens
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.any(np.asarray(b)) and close(a, b, 1e-5)


def test_the_taps_have_no_activation_and_kimi_s_convolution_keeps_its_own():
    (x, taps) = draws(3, (2, 9, 8), (4, 8))
    bare = lm._causal_conv(x, taps)
    want = np.zeros((2, 9, 8))
    for t in range(9):
        for i in range(4):
            if t - 3 + i >= 0:
                want[:, t] += np.asarray(x)[:, t - 3 + i] * np.asarray(taps)[i]
    assert close(bare, want, 1e-6)
    assert float(jnp.min(bare)) < -0.5  # a SiLU would have cut it off at -0.28


def test_the_gate_s_largest_magnitude_is_reported_over_the_conv_layers():
    import shortconv_lm_tiny as zoo

    model = zoo.custom_model()
    variables = model.init(jax.random.PRNGKey(0), None)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 20), 0, 64)
    _out, state = model.apply(variables, tokens, mutable=[WINDOW_STATS])
    stats = state[WINDOW_STATS]
    assert sorted(stats) == [
        "expert_tokens", "held_share", "route_full", "route_rows",
        "router_bias_absmax", "router_entropy", "shortconv_gate_absmax",
    ]
    assert stats["expert_tokens"].shape == (4, 4)
    # 40 tokens, top-3 with 4 experts held: at most 120 rows, less than
    # a row tile, so the ladder is the full buffer alone in all 4 layers
    assert float(stats["route_rows"]) == 120.0
    assert float(stats["route_full"]) == 4.0
    # a layer whose output projection is scaled up does not move it; one
    # whose gates are does
    louder = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    louder["stack"][2]["in_proj"] = louder["stack"][2]["in_proj"] * 3.0
    _out, loud = model.apply(
        {**variables, "params": louder}, tokens, mutable=[WINDOW_STATS]
    )
    assert float(loud[WINDOW_STATS]["shortconv_gate_absmax"]) > 3 * float(
        stats["shortconv_gate_absmax"]
    )


# --------------------------------------------------- grouped-query attention


def attention_by_loops(q, k, v, group_of):
    """Causal attention head by head, numpy float64: query head i reads
    key-value head `group_of(i)`."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    b, length, heads, d = q.shape
    out = np.zeros_like(q)
    for s in range(b):
        for i in range(heads):
            j = group_of(i)
            for t in range(length):
                scores = k[s, :t + 1, j] @ q[s, t, i] / np.sqrt(d)
                p = np.exp(scores - scores.max())
                out[s, t, i] = (p / p.sum()) @ v[s, :t + 1, j]
    return out


@pytest.mark.parametrize("kv_heads", [1, 2, 4, 8])
def test_attention_takes_fewer_key_value_heads_group_blocked(kv_heads):
    q, k, v = draws(4, (2, 11, 8, 6), (2, 11, kv_heads, 6), (2, 11, kv_heads, 6))
    group = 8 // kv_heads
    got = flash_attention.attention(q, k, v)
    assert close(got, attention_by_loops(q, k, v, lambda i: i // group), 1e-5)
    if 1 < kv_heads < 8:  # the other reading of a group is another result
        assert not close(
            got, attention_by_loops(q, k, v, lambda i: i % kv_heads), 1e-2
        )


def test_a_group_s_gradient_is_the_sum_over_its_query_heads():
    q, k, v, weight = draws(5, (1, 7, 4, 6), (1, 7, 2, 6), (1, 7, 2, 6), (1, 7, 4, 6))

    def loss(attend, q, k, v):
        return jnp.sum(attend(q, k, v) * weight)

    got = jax.grad(
        lambda *a: loss(flash_attention.attention, *a), argnums=(0, 1, 2)
    )(q, k, v)
    wide = jax.grad(
        lambda *a: loss(flash_attention.reference_attention, *a), argnums=(0, 1, 2)
    )(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2))
    assert close(got[0], wide[0], 1e-6)
    for a, b in zip(got[1:], wide[1:]):
        assert close(a, b.reshape(1, 7, 2, 2, 6).sum(axis=3), 1e-6)


def test_heads_that_are_not_whole_groups_are_refused():
    q, k, v = draws(6, (1, 4, 6, 4), (1, 4, 4, 4), (1, 4, 4, 4))
    with pytest.raises(ValueError, match="not whole groups"):
        flash_attention.attention(q, k, v)


def test_attention_with_equal_heads_is_traced_as_it_was():
    """The dispatcher widens only a call with fewer key-value heads:
    the dense LM's and the looped LM's calls trace to the jaxpr of the
    path they took before, with no `repeat`'s gather or broadcast."""
    q, k, v = draws(7, (2, 16, 4, 8), (2, 16, 4, 8), (2, 16, 4, 8))
    now = str(jax.make_jaxpr(flash_attention.attention)(q, k, v))
    before = str(jax.make_jaxpr(flash_attention.reference_attention)(q, k, v))
    assert now == before
    grouped = str(jax.make_jaxpr(flash_attention.attention)(q, k[:, :, :2], v[:, :, :2]))
    assert grouped != before


def test_queries_and_keys_are_normed_per_head_before_the_rotation():
    """The attention layer of the tiny model against head-by-head loops
    with the norm and the rotation written out in numpy."""
    import shortconv_lm_tiny as zoo

    ref = load_module(os.path.join(CONFIG_DIR, "reference.py"))
    cfg = zoo.custom_model().cfg
    d, hd = cfg.d_model, cfg.head_dim
    wq, wk, wv, wo, qn, kn, x = draws(
        8, (d, 4 * hd), (d, 2 * hd), (d, 2 * hd), (4 * hd, d), (hd,), (hd,),
        (2, 9, d),
    )
    lp = {"wq": wq / 7, "wk": wk / 7, "wv": wv / 7, "wo": wo / 7,
          "q_norm": 1 + qn / 4, "k_norm": 1 + kn / 4}

    def normed_and_turned(y, weight, heads):
        y = np.asarray(y, np.float64).reshape(2, 9, heads, hd)
        y = y / np.sqrt(np.mean(y * y, axis=-1, keepdims=True) + 1e-5)
        y = y * np.asarray(weight, np.float64)
        angle = np.arange(9)[:, None] * 1e6 ** (-np.arange(hd // 2) / (hd // 2))
        cos, sin = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]
        y1, y2 = y[..., :hd // 2], y[..., hd // 2:]
        return np.concatenate([y1 * cos - y2 * sin, y1 * sin + y2 * cos], -1)

    q = normed_and_turned(x @ lp["wq"], lp["q_norm"], 4)
    k = normed_and_turned(x @ lp["wk"], lp["k_norm"], 2)
    v = np.asarray(x @ lp["wv"], np.float64).reshape(2, 9, 2, hd)
    want = attention_by_loops(q, k, v, lambda i: i // 2).reshape(2, 9, -1) @ (
        np.asarray(lp["wo"], np.float64)
    )
    assert close(ref.grouped_attention(lp, x, zoo.REFERENCE_SIZES), want, 1e-5)
    # and the program's layer is the reference's
    got = _attention_layer(cfg, lp, x)
    assert close(got, want, 1e-5)
    without = _attention_layer(cfg, {**lp, "q_norm": jnp.ones(hd) * 3.0}, x)
    assert not close(without, want, 1e-2)


def _attention_layer(cfg, lp, x):
    return lm._mha(cfg, lp, x, jnp.arange(x.shape[1]))


# ------------------------------------------------- the program, the reference


def shortconv():
    import shortconv_lm_tiny as zoo

    return zoo, load_module(os.path.join(CONFIG_DIR, "reference.py"))


def program_and_reference(length, seed=3):
    zoo, ref = shortconv()
    model = zoo.custom_model()
    variables = model.init(jax.random.PRNGKey(seed), None)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    # norms and the selection bias away from their initial ones and
    # zeros, so that a weight left out would show
    rng = np.random.default_rng(seed)
    for run in params["stack"]:
        for name in ("q_norm", "k_norm", "ln1", "ln2"):
            if name in run:
                run[name] = run[name] + jnp.asarray(
                    rng.normal(size=run[name].shape) * 0.2, jnp.float32
                )
        if "router_bias" in run:
            run["router_bias"] = jnp.asarray(
                rng.normal(size=run["router_bias"].shape) * 0.05, jnp.float32
            )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, length + 1), 0, 64)
    x, y = tokens[:, :-1], tokens[:, 1:]

    def program(p):
        out, state = model.apply(
            {"params": p, WINDOW_STATS: variables[WINDOW_STATS]}, x,
            mutable=[WINDOW_STATS],
        )
        return zoo.loss(out, y), (out[0], state[WINDOW_STATS])

    def reference(p):
        value, loads = ref.parts(p, x, y, zoo.REFERENCE_SIZES)
        return value, (ref.forward(p, x, zoo.REFERENCE_SIZES)[0], loads)

    return params, program, reference


@pytest.mark.parametrize("length", [32, 2, 41])
def test_the_program_s_logits_loss_and_loads_are_the_reference_s(length):
    params, program, reference = program_and_reference(length)
    got, (logits, stats) = jax.jit(program)(params)
    want, (ref_logits, loads) = jax.jit(reference)(params)
    assert close(logits, ref_logits)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    assert np.array_equal(
        np.asarray(stats["expert_tokens"]), np.asarray(loads)[:, 4:8]
    )
    assert float(stats["shortconv_gate_absmax"]) > 0
    assert 0 < float(stats["router_bias_absmax"]) < 0.5


@pytest.mark.parametrize("length", [32, 41])
def test_every_leaf_s_gradient_is_the_reference_s(length):
    params, program, reference = program_and_reference(length)
    got = jax.jit(jax.grad(lambda p: program(p)[0]))(params)
    want = jax.jit(jax.grad(lambda p: reference(p)[0]))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == 2 + 8 + 13 + 10
    for (path, a), b in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # no gradient reaches it, on either side
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b))
            continue
        assert np.any(np.asarray(b)), name
        assert close(a, b), name


def test_the_tied_embedding_s_gradient_is_the_sum_of_its_two_uses():
    """As a lookup and as the head: with the head's use cut off
    (`stop_gradient` on the transposed copy) plus with the lookup's cut
    off is the gradient the program gives."""
    import shortconv_lm_tiny as zoo

    model = zoo.custom_model()
    variables = model.init(jax.random.PRNGKey(4), None)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    assert "head" not in params
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 13), 0, 64)
    x, y = tokens[:, :-1], tokens[:, 1:]
    untied = zoo.custom_model(tie_embeddings=False)

    def loss(embed, head):
        out = untied.apply({"params": {**params, "embed": embed, "head": head}}, x)
        return zoo.loss(out, y)

    embed = params["embed"]
    as_lookup, as_head = jax.grad(loss, argnums=(0, 1))(embed, embed.T)
    tied = jax.grad(
        lambda p: zoo.loss(model.apply({"params": p}, x), y)
    )(params)["embed"]
    assert np.any(np.asarray(as_lookup)) and np.any(np.asarray(as_head))
    assert close(tied, as_lookup + as_head.T, 1e-5)
    assert not close(tied, as_lookup, 1e-2)


@pytest.mark.parametrize("control, setting", [
    ("no_qk_norm", {"qk_norm": False}),
    ("unnormalised", {"moe_renormalize": False}),
    ("untied", {"tie_embeddings": False}),
])
def test_a_block_that_leaves_a_part_out_is_not_the_reference(control, setting):
    import shortconv_lm_tiny as zoo

    params, _program, reference = program_and_reference(32)
    other = zoo.custom_model(**setting)
    if control == "untied":
        params = {**params, "head": params["embed"].T * 1.1}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 64)
    logits, _aux = other.apply({"params": params}, tokens[:, :-1])
    assert not close(logits, reference(params)[1][0], 1e-2)


# ------------------------------------------- a layer without a shared expert


def sigmoid_layer(seed=5, tokens=(2, 12), d=16, experts=64, f=8):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]), jnp.float32)

    x = jnp.asarray(rng.normal(size=tokens + (d,)), jnp.float32)
    return x, draw(d, experts), (
        draw(experts, d, f), draw(experts, d, f), draw(experts, f, d)
    )


def test_the_eight_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """Eight shares of 8 of 64 experts, with no shared expert to count
    once, are the uncut reference layer that holds all 64."""
    ref = load_module(os.path.join(CONFIG_DIR, "reference.py"))
    x, router, (wg, wu, wd) = sigmoid_layer()
    bias = jnp.asarray(np.random.default_rng(6).normal(size=64) * 0.1, jnp.float32)
    settings = dict(top_k=4, scaling=1.0, score="sigmoid", bias=bias,
                    renormalize=True, balance=False)
    uncut, loads = ref.expert_layer(
        {"router": router, "router_bias": bias, "eg": wg, "eu": wu, "ed": wd},
        x, {"top_k": 4, "routed_scaling": 1.0}, held=(0, 64),
    )
    assert float(jnp.sum(loads)) == 2 * 12 * 4
    parts, seen = 0.0, 0.0
    for first in range(0, 64, 8):
        held = slice(first, first + 8)
        part, term, share = moe.moe_topk_held(
            x, router, (wg[held], wu[held], wd[held]), None,
            held=(first, 8), **settings,
        )
        assert float(term) == 0.0
        assert np.array_equal(
            np.asarray(share["expert_tokens"]), np.asarray(loads)[held]
        )
        parts = parts + part
        seen += float(jnp.sum(share["expert_tokens"]))
    assert seen == 2 * 12 * 4
    assert close(parts, uncut, 1e-5)


def test_a_layer_without_a_shared_expert_has_no_shared_scope_and_no_leaf():
    import shortconv_lm_tiny as zoo

    x, router, experts = sigmoid_layer(experts=16)
    settings = dict(top_k=3, held=(0, 16), score="sigmoid", renormalize=True)
    shared = tuple(e[0] for e in experts)

    def scopes(shared):
        lowered = jax.jit(
            lambda x: moe.moe_topk_held(x, router, experts, shared, **settings)[0]
        ).lower(x)
        return lowered.as_text(debug_info=True)

    assert "shared/" in scopes(shared)  # the scope, in an `op_name` path
    assert "shared/" not in scopes(None)
    params = zoo.custom_model().init(jax.random.PRNGKey(0), None)["params"]
    for run in params["stack"]:
        assert not {"sg", "su", "sd"} & set(run)
    assert all(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    with_shared = zoo.custom_model(n_shared_experts=1).init(
        jax.random.PRNGKey(0), None
    )["params"]
    assert {"sg", "su", "sd"} <= set(with_shared["stack"][1])


# -------------------------------------------------- what stays as it was


def test_kimi_s_tiny_program_is_bit_for_bit_what_it_was(monkeypatch):
    """`_causal_conv` lost its SiLU and `_kda` applies it: the same
    operations in the same order. The hybrid's loss and every gradient
    against the convolution as it stood (taps and SiLU in one
    function) under a `_kda` whose own SiLU is the identity."""
    import hybrid_lm_tiny as zoo

    model = zoo.custom_model()
    variables = model.init(jax.random.PRNGKey(3), None)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 25), 0, 64)

    def loss(p):
        out, _state = model.apply(
            {**variables, "params": p}, tokens[:, :-1], mutable=[WINDOW_STATS]
        )
        return zoo.loss(out, tokens[:, 1:])

    both = jax.value_and_grad(loss)
    now, traced_now = jax.jit(loss)(params), str(jax.make_jaxpr(both)(params))

    silu, kda = jax.nn.silu, lm._kda

    def as_it_stood(x, taps):  # PR 38's `_causal_conv`, whole
        n, length = taps.shape[0], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
        return silu(sum(padded[:, i:i + length] * taps[i] for i in range(n)))

    def kda_without_its_silu(cfg, lp, x):
        with monkeypatch.context() as inner:
            inner.setattr(jax.nn, "silu", lambda y: y)
            return kda(cfg, lp, x)

    monkeypatch.setattr(lm, "_causal_conv", as_it_stood)
    monkeypatch.setattr(lm, "_kda", kda_without_its_silu)
    # the same operations in the same order, forward and backward
    assert str(jax.make_jaxpr(both)(params)) == traced_now
    assert np.array_equal(np.asarray(jax.jit(loss)(params)), np.asarray(now))


# ------------------------------------------------------------ the stack


def test_the_stack_is_cut_into_runs_of_one_mixer_and_one_mlp():
    import shortconv_lm_tiny as zoo

    cfg = zoo.custom_model().cfg
    assert cfg.mixed and cfg.runs == (
        ("conv", False, 1), ("mha", True, 1), ("conv", True, 3)
    )
    params = zoo.custom_model().init(jax.random.PRNGKey(0), None)["params"]
    assert sorted(params) == ["embed", "ln_f", "stack"]
    assert [run["ln1"].shape[0] for run in params["stack"]] == [1, 1, 3]
    assert params["stack"][1]["wk"].shape == (1, 48, 2 * 12)
    assert params["stack"][1]["q_norm"].shape == (1, 12)
    assert params["stack"][2]["conv"].shape == (3, 3, 48)
    assert params["stack"][2]["in_proj"].shape == (3, 48, 3 * 48)


@pytest.mark.parametrize("setting", [
    {"n_kv_heads": 2}, {"qk_norm": True}, {"tie_embeddings": True},
    {"layer_types": ("conv", "mha", "conv", "conv")},
])
def test_the_mesh_path_refuses_the_new_settings_by_name(setting):
    cfg = lm.TransformerConfig(**setting)
    with pytest.raises(NotImplementedError, match="plain_forward"):
        lm.param_partition_specs(cfg)
    with pytest.raises(NotImplementedError, match="plain_forward"):
        lm.reference_forward(cfg, {}, jnp.zeros((1, 4), jnp.int32))


@pytest.mark.parametrize("setting", [
    {"n_kv_heads": 2}, {"qk_norm": True}, {"tie_embeddings": True},
    {"n_kv_heads": 1, "qk_norm": True, "tie_embeddings": True},
])
def test_the_plain_block_learns_the_attention_s_settings_too(setting):
    """Outside the routed stack: a dense LM with grouped, normed, tied
    attention initialises and runs, and its loss falls on a step."""
    cfg = lm.TransformerConfig(
        vocab=32, d_model=32, n_heads=4, d_ff=48, n_layers=2, **setting
    )
    params = lm.init_params(np.random.default_rng(0), cfg)
    assert ("head" in params) != cfg.tie_embeddings
    assert params["layers"]["wk"].shape[-1] == cfg.kv_heads * 8
    assert ("q_norm" in params["layers"]) == cfg.qk_norm
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 12), 0, 32)
    loss = lm.build_loss_fn(cfg, lm.make_mesh_for(1))
    params = jax.tree_util.tree_map(jnp.asarray, params)
    value, grads = jax.value_and_grad(loss)(params, tokens)
    stepped = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
    assert float(loss(stepped, tokens)) < float(value)


class _Shapes:
    """A generator whose normals are shapes alone: 470 M draws take a
    minute and 1.9 GB; a zero-stride view of one zero takes neither."""

    class _Normal:
        def __init__(self, shape):
            self.shape = shape

        def __mul__(self, _scale):
            return self

        def astype(self, dtype):
            return np.broadcast_to(np.zeros((), dtype), self.shape)

    def standard_normal(self, shape):
        return self._Normal(shape)


def test_the_configuration_counts_its_parameters_as_its_file_derives_them():
    """469,285,248 as `config.json` derives them, no `head`, no shared
    expert's leaves, and no leaf of no width."""
    zoo = load_module(os.path.join(CONFIG_DIR, "zoo.py"))
    cfg = zoo.custom_model().cfg
    assert cfg.runs == (("conv", False, 1), ("mha", True, 1), ("conv", True, 3))
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.n_experts, cfg.held, cfg.moe_top_k, cfg.d_expert) == (
        64, (0, 8), 4, 1536
    )
    assert (cfg.d_ff, cfg.conv_taps, cfg.vocab) == (11776, 3, 8192)
    params = lm.init_params(_Shapes(), cfg)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert sum(leaf.size for _path, leaf in leaves) == zoo.SIZES["parameters"]
    assert zoo.SIZES["parameters"] == 469_285_248
    names = {jax.tree_util.keystr(path) for path, _leaf in leaves}
    assert not any(n.endswith(("['sg']", "['su']", "['sd']", "['head']")) for n in names)
    assert all(leaf.size for _path, leaf in leaves)
    per_run = [sum(x.size for x in jax.tree_util.tree_leaves(run))
               for run in params["stack"]]
    assert per_run == [89_139_200, 86_118_592, 3 * 92_416_064]


def test_the_configuration_s_flops_are_its_file_s_arithmetic():
    flops = load_module(os.path.join(CONFIG_DIR, "flops.py"))
    zoo = load_module(os.path.join(CONFIG_DIR, "zoo.py"))
    assert flops.flops_per_sample(zoo.SIZES) == 190_318_592 * 6 * 2048
    rows = 512 * 8
    assert flops.expert_matmul_flops(rows, zoo.SIZES) == 2 * rows * 2048 * 1536
