"""The window-and-full-attention stack (Laguna-XS.2's block at a tiny
size) and its parts, float32 on the CPU: the banded mask and both
kinds of attention layer against head-by-head loops in numpy, the
rotation of part of a head with a factor on cosine and sine, the
per-head gate, the program against the configuration's plain reference
(`benchmark/configs/laguna-xs2/reference.py`), the shares of an expert
layer WITH a shared expert against the uncut layer, the stack's runs,
and what the change leaves as it was: `head_dim` without a width, the
trees and traced programs of configurations that set none of it.

Tolerance: both sides are float32 with the same mathematics in another
order, so they agree to accumulated rounding: a relative 2e-4 of the
largest value, the other configurations' tolerance."""

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

from benchmark.harness.manifest import load_module  # noqa: E402
from elasticdl_tpu.common.constants import WINDOW_STATS  # noqa: E402
from elasticdl_tpu.models import transformer_lm as lm  # noqa: E402
from elasticdl_tpu.ops import flash_attention  # noqa: E402
from elasticdl_tpu.parallel import moe  # noqa: E402

TOLERANCE = 2e-4
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "laguna-xs2")
KINDS = ("full", "sliding", "sliding", "sliding", "full")


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


def window():
    import window_lm_tiny as zoo

    return zoo, load_module(os.path.join(CONFIG_DIR, "reference.py"))


def reference_sizes(zoo):
    return {**zoo.REFERENCE_SIZES, "kinds": KINDS}


# ------------------------------------------------------------- the band


def attention_by_loops(q, k, v, group_of, window=None):
    """Attention head by head and query by query, numpy float64: query
    head i reads key-value head `group_of(i)`; the query at t sees the
    keys u <= t and, under `window`, those with t - u < window."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    b, length, heads, d = q.shape
    out = np.zeros_like(q)
    for s in range(b):
        for i in range(heads):
            j = group_of(i)
            for t in range(length):
                first = 0 if window is None else max(0, t - window + 1)
                scores = k[s, first:t + 1, j] @ q[s, t, i] / np.sqrt(d)
                p = np.exp(scores - scores.max())
                out[s, t, i] = (p / p.sum()) @ v[s, first:t + 1, j]
    return out


@pytest.mark.parametrize("window", [1, 2, 5, 13, 14, 40])
def test_the_banded_mask_sees_window_keys_and_the_query_s_own_is_one(window):
    q, k, v = draws(1, (2, 13, 4, 6), (2, 13, 2, 6), (2, 13, 2, 6))
    got = flash_attention.attention(q, k, v, window=window)
    want = attention_by_loops(q, k, v, lambda i: i // 2, window)
    assert close(got, want, 1e-5)
    if window == 1:  # a query that sees itself alone returns its value
        assert close(got, jnp.repeat(v, 2, axis=2), 1e-6)
    if window < 13:
        assert not close(got, flash_attention.attention(q, k, v), 1e-2)


def test_a_window_that_holds_the_sequence_traces_as_the_causal_call():
    q, k, v = draws(2, (2, 16, 4, 8), (2, 16, 4, 8), (2, 16, 4, 8))
    causal = str(jax.make_jaxpr(flash_attention.attention)(q, k, v))
    for window in (None, 16, 17, 8192):
        traced = str(jax.make_jaxpr(
            lambda q, k, v: flash_attention.attention(q, k, v, window=window)
        )(q, k, v))
        assert traced == causal, window
    banded = str(jax.make_jaxpr(
        lambda q, k, v: flash_attention.attention(q, k, v, window=15)
    )(q, k, v))
    assert banded != causal


@pytest.mark.parametrize("length, window, share", [
    (8192, 512, 4_063_488 / 33_558_528), (48, 8, 356 / 1176),
    (8, 8, 1.0), (8, 100, 1.0), (5, 1, 5 / 15),
])
def test_the_band_s_share_of_the_triangle(length, window, share):
    pairs = load_module(os.path.join(CONFIG_DIR, "flops.py")).visible_pairs
    assert pairs(length, window) / pairs(length) == pytest.approx(
        share, rel=1e-12
    )
    mask = np.tril(np.ones((min(length, 64),) * 2))
    if length <= 64:
        band = mask - np.tril(mask, -window)
        assert band.sum() / mask.sum() == pytest.approx(share)


# -------------------------------------------------------- the rotation


def turned_by_hand(x, rot, freqs, factor):
    """numpy float64: the first `rot` columns of every head turned by
    position, pair i = (x[i], x[i + rot/2])."""
    x = np.asarray(x, np.float64)
    half = rot // 2
    angle = np.arange(x.shape[1])[:, None] * np.asarray(freqs, np.float64)
    cos = (np.cos(angle) * factor)[None, :, None]
    sin = (np.sin(angle) * factor)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return np.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rot:]], axis=-1
    )


def test_half_a_head_turns_and_the_factor_lies_on_cosine_and_sine():
    """64 of 128 columns turned by YaRN's blended frequencies at
    Laguna's settings, the attention factor on cosine and sine: the 64
    that pass are as projected, position 0 is turned by no angle (and
    so only scaled)."""
    (x,) = draws(3, (2, 9, 3, 128))
    yarn = lm.YarnScaling(64.0, 64.0, 1.0, 4096, 1.0, 0.0)
    factor = 0.1 * math.log(64.0) + 1.0
    assert factor == pytest.approx(1.4158883083359672)
    freqs = lm.yarn_frequencies(64, 500000.0, yarn)
    assert freqs.shape == (32,)
    got = lm._rope(x, jnp.arange(9), 500000.0, freqs, rot=64, factor=factor)
    assert close(got, turned_by_hand(x, 64, freqs, factor), 1e-6)
    assert np.array_equal(np.asarray(got[..., 64:]), np.asarray(x[..., 64:]))
    assert close(got[:, 0, :, :64], x[:, 0, :, :64] * factor, 1e-6)
    # the reference computes the same frequencies its own way
    _zoo, ref = window()
    kind = {"rope_dim": 64, "rope_base": 500000.0, "yarn": {
        "factor": 64.0, "beta_fast": 64.0, "beta_slow": 1.0,
        "original_length": 4096}}
    assert close(ref.rotary_frequencies(kind), freqs, 1e-6)
    # the blend: the fastest pairs keep their frequency, the slowest
    # take a 64th of it
    plain = lm.yarn_frequencies(64, 500000.0, None)
    assert freqs[0] == plain[0] and freqs[-1] == pytest.approx(plain[-1] / 64)


def test_a_whole_head_turned_without_a_factor_is_the_call_as_it_was():
    (x,) = draws(4, (2, 7, 2, 16))
    positions = jnp.arange(7)
    before = str(jax.make_jaxpr(lambda x: lm._rope(x, positions, 1e4))(x))
    now = str(jax.make_jaxpr(
        lambda x: lm._rope(x, positions, 1e4, None, rot=None, factor=1.0)
    )(x))
    assert now == before
    assert close(
        lm._rope(x, positions, 1e4, rot=16), lm._rope(x, positions, 1e4), 0
    )


# ------------------------------------------ the two kinds of layer, the gate


def attention_leaves(cfg, mixer, seed):
    d, hd = cfg.d_model, cfg.head_dim
    heads = cfg.attention_shape(mixer).heads
    wq, wk, wv, wo, wgate, x = draws(
        seed, (d, heads * hd), (d, cfg.kv_heads * hd), (d, cfg.kv_heads * hd),
        (heads * hd, d), (heads, d), (2, 21, d),
    )
    return {"wq": wq / 8, "wk": wk / 8, "wv": wv / 8, "wo": wo / 8,
            "wgate": wgate / 4}, x


@pytest.mark.parametrize("mixer, kind", [("mha", "full"), ("swa", "sliding")])
def test_a_layer_of_each_kind_is_the_loops_with_its_rotation_and_gate(mixer, kind):
    zoo, ref = window()
    cfg = zoo.custom_model().cfg
    shape, hd = cfg.attention_shape(mixer), cfg.head_dim
    assert (shape.heads, shape.window) == {"mha": (6, None), "swa": (8, 8)}[mixer]
    lp, x = attention_leaves(cfg, mixer, 5)
    freqs = lm.yarn_frequencies(
        shape.rope_dim or hd, shape.rope_base, shape.rope_yarn
    )

    def heads_of(w, n):
        y = np.asarray(x @ lp[w], np.float64).reshape(2, 21, n, hd)
        return turned_by_hand(y, shape.rope_dim or hd, freqs, shape.rope_factor)

    q, k = heads_of("wq", shape.heads), heads_of("wk", 2)
    v = np.asarray(x @ lp["wv"], np.float64).reshape(2, 21, 2, hd)
    group = shape.heads // 2
    o = attention_by_loops(q, k, v, lambda i: i // group, shape.window)
    gate = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(lp["wgate"], np.float64).T))
    want = (o * gate[..., None]).reshape(2, 21, -1) @ np.asarray(lp["wo"], np.float64)
    got, stats = lm._attend(cfg, lp, x, jnp.arange(21), mixer)
    assert close(got, want, 1e-5)
    assert float(stats["attn_gate_mean"]) == pytest.approx(gate.mean(), abs=1e-5)
    sizes = reference_sizes(zoo)
    assert close(ref.attention_mixer(lp, x, sizes[kind], sizes), want, 1e-5)
    # a gate left out, the other reading of a group, a key more: another result
    ungated, _ = lm._attend(
        cfg, {**lp, "wgate": jnp.zeros_like(lp["wgate"])}, x, jnp.arange(21), mixer
    )
    assert close(ungated, 0.5 * (o.reshape(2, 21, -1) @ np.asarray(lp["wo"], np.float64)), 1e-5)
    assert not close(ungated, want, 1e-2)
    interleaved = attention_by_loops(q, k, v, lambda i: i % 2, shape.window)
    assert not close(interleaved, o, 1e-2)


def test_the_reference_s_blocks_of_queries_are_the_whole_rows(monkeypatch):
    """The reference cuts its queries into blocks so that 8192 tokens
    fit: with blocks of 8 over 21 tokens it is what one block gives."""
    zoo, ref = window()
    cfg = zoo.custom_model().cfg
    sizes = reference_sizes(zoo)
    for mixer, kind in (("mha", "full"), ("swa", "sliding")):
        lp, x = attention_leaves(cfg, mixer, 6)
        whole = ref.attention_mixer(lp, x, sizes[kind], sizes)
        with monkeypatch.context() as patch:
            patch.setattr(ref, "QUERY_BLOCK", 8)
            blocked = ref.attention_mixer(lp, x, sizes[kind], sizes)
        assert close(blocked, whole, 1e-6)


# ------------------------------------------------- the program, the reference


def program_and_reference(length, seed=3):
    zoo, ref = window()
    model = zoo.custom_model()
    variables = model.init(jax.random.PRNGKey(seed), None)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    # norms away from their initial ones, so that a weight left out
    # would show
    rng = np.random.default_rng(seed)
    for run in params["stack"]:
        for name in ("ln1", "ln2"):
            run[name] = run[name] + jnp.asarray(
                rng.normal(size=run[name].shape) * 0.2, jnp.float32
            )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, length + 1), 0, 64)
    x, y = tokens[:, :-1], tokens[:, 1:]
    sizes = reference_sizes(zoo)

    def program(p):
        out, state = model.apply(
            {"params": p, WINDOW_STATS: variables[WINDOW_STATS]}, x,
            mutable=[WINDOW_STATS],
        )
        return zoo.loss(out, y), (out[0], state[WINDOW_STATS])

    def reference(p):
        value, loads = ref.parts(p, x, y, sizes)
        return value, (ref.logits_of(p, x, sizes), loads)

    return params, program, reference


@pytest.mark.parametrize("length", [48, 2, 41])
def test_the_program_s_logits_loss_and_loads_are_the_reference_s(length):
    params, program, reference = program_and_reference(length)
    got, (logits, stats) = jax.jit(program)(params)
    want, (ref_logits, loads) = jax.jit(reference)(params)
    assert close(logits, ref_logits)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    assert np.array_equal(
        np.asarray(stats["expert_tokens"]), np.asarray(loads)[:, 2:6]
    )
    assert sorted(stats) == [
        "attn_gate_mean", "expert_tokens", "held_share", "route_full",
        "route_rows", "router_entropy",
    ]
    assert 0.4 < float(stats["attn_gate_mean"]) < 0.6  # untrained: a half


@pytest.mark.parametrize("length", [48, 41])
def test_every_leaf_s_gradient_is_the_reference_s(length):
    params, program, reference = program_and_reference(length)
    got = jax.jit(jax.grad(lambda p: program(p)[0]))(params)
    want = jax.jit(jax.grad(lambda p: reference(p)[0]))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    # embed, head, ln_f; a dense layer's 10, an expert layer's 14 twice
    assert len(flat_got) == len(flat_want) == 3 + 10 + 14 + 14
    for (path, a), b in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        assert np.any(np.asarray(b)), name
        assert close(a, b), name


@pytest.mark.parametrize("control, setting", [
    ("no_window", {"swa_window": 4096}),
    ("a_key_more", {"swa_window": 9}),
    ("full_rotary", {"rope_dim": None}),
    ("no_attention_factor", {"rope_factor": 1.0}),
    ("no_yarn", {"rope_yarn": None}),
    ("unnormalised", {"moe_renormalize": False}),
    ("unscaled", {"routed_scaling": 1.0}),
])
def test_a_block_that_changes_a_part_is_not_the_reference(control, setting):
    zoo, _ref = window()
    params, _program, reference = program_and_reference(48)
    other = zoo.custom_model(**setting)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 49), 0, 64)
    logits, _aux = other.apply({"params": params}, tokens[:, :-1])
    assert not close(logits, reference(params)[1][0], 1e-2)


# ------------------------------------------------ the shares of a layer


def softmax_layer(seed=5, tokens=(2, 12), d=16, experts=32, f=8):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]), jnp.float32)

    x = jnp.asarray(rng.normal(size=tokens + (d,)), jnp.float32)
    return x, draw(d, experts), (
        draw(experts, d, f), draw(experts, d, f), draw(experts, f, d)
    ), (draw(d, f), draw(d, f), draw(f, d))


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer_the_shared_expert_once():
    """The guide's section 4: eight shares of 4 of 32 experts (as
    sixteen of 16 of 256), softmax top-3 renormalised x 2.5, each with
    the shared expert every chip computes alike: their routed parts and
    the shared expert counted ONCE are the uncut reference layer."""
    _zoo, ref = window()
    x, router, (wg, wu, wd), (sg, su, sd) = softmax_layer()
    settings = dict(top_k=3, scaling=2.5, score="softmax", renormalize=True,
                    balance=False)
    lp = {"router": router, "eg": wg, "eu": wu, "ed": wd,
          "sg": sg, "su": su, "sd": sd}
    ref_sizes = {"top_k": 3, "routed_scaling": 2.5}
    uncut, loads = ref.expert_layer(lp, x, ref_sizes, held=(0, 32))
    assert float(jnp.sum(loads)) == 2 * 12 * 3
    shared_alone = ref.gated_mlp(x, sg, su, sd)
    parts, seen = 0.0, 0.0
    for first in range(0, 32, 4):
        held = slice(first, first + 4)
        part, term, share = moe.moe_topk_held(
            x, router, (wg[held], wu[held], wd[held]), (sg, su, sd),
            held=(first, 4), **settings,
        )
        assert float(term) == 0.0
        assert np.array_equal(
            np.asarray(share["expert_tokens"]), np.asarray(loads)[held]
        )
        # what this share gives beside what every chip computes alike
        parts = parts + (part - shared_alone)
        seen += float(jnp.sum(share["expert_tokens"]))
        cut, _ = ref.expert_layer(
            {**lp, "eg": wg[held], "eu": wu[held], "ed": wd[held]}, x,
            ref_sizes, held=(first, 4),
        )
        assert close(part, cut, 1e-5)
    assert seen == 2 * 12 * 3
    assert close(parts + shared_alone, uncut, 1e-5)
    # the shared expert counted eight times is not the layer
    assert not close(parts + 8 * shared_alone, uncut, 1e-2)


def test_softmax_gates_renormalised_sum_to_one_over_the_chosen():
    """Under `renormalize` a softmax layer weighs its experts by
    `route_topk`'s gates over their sum over all k chosen; without it
    by the gates as they are, which sum to less than one."""
    _zoo, ref = window()
    x, router, (wg, wu, wd), _shared = softmax_layer(seed=7, experts=16)
    xf = x.reshape(-1, x.shape[-1])
    _probs, gate, chosen = moe.route_topk(xf, router, 8)
    assert float(jnp.max(jnp.sum(gate, axis=-1))) < 1.0
    outs = jnp.stack([
        ref.gated_mlp(xf, wg[e], wu[e], wd[e]) for e in range(16)
    ])  # [E, T, d]
    picked = outs[chosen, jnp.arange(xf.shape[0])[:, None]]  # [T, k, d]
    for renormalize, weights in (
        (True, gate / jnp.sum(gate, axis=-1, keepdims=True)), (False, gate),
    ):
        got, _term, _stats = moe.moe_topk_held(
            x, router, (wg, wu, wd), top_k=8, held=(0, 16),
            renormalize=renormalize, balance=False,
        )
        want = jnp.einsum("tk,tkd->td", weights, picked)
        assert close(got.reshape(want.shape), want, 1e-5), renormalize


# ------------------------------------------------------------ the stack


def test_the_stack_is_cut_into_runs_by_kind_of_attention_and_of_mlp():
    zoo, _ref = window()
    cfg = zoo.custom_model().cfg
    assert cfg.mixed and cfg.runs == (
        ("mha", False, 1), ("swa", True, 3), ("mha", True, 1)
    )
    params = zoo.custom_model().init(jax.random.PRNGKey(0), None)["params"]
    assert sorted(params) == ["embed", "head", "ln_f", "stack"]
    assert [run["ln1"].shape[0] for run in params["stack"]] == [1, 3, 1]
    full, sliding, last = params["stack"]
    assert full["wq"].shape == (1, 64, 6 * 16) and last["wq"].shape == (1, 64, 96)
    assert sliding["wq"].shape == (3, 64, 8 * 16)
    assert sliding["wo"].shape == (3, 8 * 16, 64)
    assert full["wk"].shape == (1, 64, 32) and sliding["wk"].shape == (3, 64, 32)
    # the gate's leaf is stored [heads, d]: no leaf ends in a narrow dim
    assert full["wgate"].shape == (1, 6, 64) and sliding["wgate"].shape == (3, 8, 64)
    assert "wg" in full and "router" in sliding and "sg" in last


def test_a_head_s_width_is_a_setting_and_d_model_over_heads_without_one():
    assert lm.TransformerConfig(d_model=96, n_heads=6).head_dim == 16
    assert lm.TransformerConfig(d_model=64, n_heads=6, head_width=16).head_dim == 16
    # a configuration that sets none of this PR's settings has the tree
    # it had: no gate's leaf, square projections
    import shortconv_lm_tiny as lfm2

    params = lfm2.custom_model().init(jax.random.PRNGKey(0), None)["params"]
    names = {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(params)}
    assert not any("wgate" in n for n in names)
    cfg = lm.TransformerConfig(vocab=32, d_model=32, n_heads=4, d_ff=48, n_layers=2)
    plain = lm.init_params(np.random.default_rng(0), cfg)
    assert sorted(plain["layers"]) == ["ln1", "ln2", "w1", "w2", "wk", "wo", "wq", "wv"]
    assert cfg.attention_shape("mha") == lm.AttentionShape(
        4, None, 10000.0, None, None, 1.0, None
    )


def test_a_stack_without_a_window_keeps_its_attention_scope_as_it_was():
    """`global`, `rope` and `gate` exist beside `swa` alone: LFM2's tiny
    program names `attention` and nothing under it."""
    import shortconv_lm_tiny as lfm2

    def scopes(zoo):
        model = zoo.custom_model()
        variables = model.init(jax.random.PRNGKey(0), None)
        tokens = jnp.zeros((1, 12), jnp.int32)
        return jax.jit(
            lambda p: model.apply({**variables, "params": p}, tokens)[0]
        ).lower(variables["params"]).as_text(debug_info=True)

    zoo, _ref = window()
    text = scopes(zoo)
    for want in ("attention/swa/", "attention/global/", "attention/swa/rope/",
                 "attention/global/rope/", "attention/swa/gate/",
                 "attention/global/gate/"):
        assert want in text, want
    text = scopes(lfm2)
    assert "attention/" in text
    for absent in ("global", "swa", "rope/", "gate/"):
        assert f"attention/{absent}" not in text, absent


@pytest.mark.parametrize("setting", [
    {"head_width": 16}, {"attn_gate": True}, {"rope_dim": 8},
    {"rope_factor": 1.4}, {"swa_heads": 8}, {"swa_window": 8},
    {"layer_types": ("mha", "swa", "swa", "swa")},
])
def test_the_mesh_path_refuses_the_new_settings_by_name(setting):
    cfg = lm.TransformerConfig(**setting)
    with pytest.raises(NotImplementedError, match="plain_forward"):
        lm.param_partition_specs(cfg)
    with pytest.raises(NotImplementedError, match="plain_forward"):
        lm.reference_forward(cfg, {}, jnp.zeros((1, 4), jnp.int32))
    assert next(iter(setting)).split("_")[0] in str(_refusal(cfg))


def _refusal(cfg):
    try:
        lm.param_partition_specs(cfg)
    except NotImplementedError as e:
        return e


# ------------------------------------------------------ the configuration


class _Shapes:
    """A generator whose normals are shapes alone: 490 M draws take a
    minute and 2 GB; a zero-stride view of one zero takes neither."""

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
    """490,297,344 as `config.json` derives them, the runs of the cut,
    and no leaf that ends in a narrow dim but the router's 256."""
    zoo = load_module(os.path.join(CONFIG_DIR, "zoo.py"))
    cfg = zoo.custom_model().cfg
    assert cfg.runs == (("mha", False, 1), ("swa", True, 3), ("mha", True, 1))
    assert (cfg.n_heads, cfg.swa_heads, cfg.kv_heads, cfg.head_dim) == (48, 64, 8, 128)
    assert (cfg.swa_window, cfg.rope_dim) == (512, 64)
    assert cfg.attention_shape("swa").rope_dim is None  # the whole head
    assert (cfg.rope_base, cfg.swa_rope_base) == (500000.0, 10000.0)
    assert cfg.rope_factor == 1.4158883083359672
    assert cfg.rope_yarn == lm.YarnScaling(64.0, 64.0, 1.0, 4096, 1.0, 0.0)
    assert (cfg.n_experts, cfg.held, cfg.moe_top_k, cfg.d_expert) == (
        256, (0, 16), 8, 512
    )
    assert (cfg.n_shared_experts, cfg.routed_scaling, cfg.moe_score,
            cfg.moe_renormalize, cfg.aux_weight) == (1, 2.5, "softmax", True, 0.0)
    assert (cfg.d_ff, cfg.vocab, cfg.norm_eps) == (8192, 12544, 1e-6)
    params = lm.init_params(_Shapes(), cfg)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert sum(leaf.size for _path, leaf in leaves) == zoo.SIZES["parameters"]
    assert zoo.SIZES["parameters"] == 490_297_344
    per_run = [sum(x.size for x in jax.tree_util.tree_leaves(run))
               for run in params["stack"]]
    assert per_run == [79_794_176, 3 * 91_885_568, 83_464_192]
    assert all(leaf.shape[-1] >= 256 for _path, leaf in leaves)


def test_the_zoo_refuses_a_file_that_states_another_block(monkeypatch):
    zoo = load_module(os.path.join(CONFIG_DIR, "zoo.py"))
    for key, other in (
        ("gating", False), ("attention_bias", True),
        ("tie_word_embeddings", True), ("model_type", "qwen2_moe"),
        ("moe_apply_router_weight_on_input", True),
        # a sliding layer, further down, with a head count of its own
        ("num_attention_heads_per_layer", [48, 64, 64, 64] * 9 + [48, 64, 64, 56]),
    ):
        monkeypatch.setitem(zoo.SIZES, key, other)
        with pytest.raises(ValueError, match="does not build"):
            zoo.custom_model()
        monkeypatch.undo()
    assert zoo.custom_model().cfg.swa_heads == 64


def test_the_file_keeps_every_number_of_the_catalog_s_row_but_the_reduced():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        sizes = json.load(f)
    assert sizes["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    published = {
        "hidden_size": 2048, "intermediate_size": 8192, "head_dim": 128,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "num_experts_per_tok": 8, "sliding_window": 512,
        "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 262144, "partial_rotary_factor": 0.5,
    }
    for key, value in published.items():
        assert sizes[key] == value, key
    assert (sizes["num_hidden_layers"], sizes["num_experts"], sizes["vocab_size"]) == (5, 16, 12544)
    assert sizes["published"]["num_experts"] == 256
    assert len(sizes["layer_types"]) == len(sizes["mlp_layer_types"]) == 40
    assert len(sizes["num_attention_heads_per_layer"]) == 40
    assert sizes["assumed"] and sizes["deployment"].startswith("16 chips")


def test_the_configuration_s_flops_are_its_file_s_arithmetic():
    flops = load_module(os.path.join(CONFIG_DIR, "flops.py"))
    zoo = load_module(os.path.join(CONFIG_DIR, "zoo.py"))
    sizes = zoo.SIZES
    band, triangle = 4_063_488, 33_558_528
    assert flops.visible_pairs(8192, 512) == band
    assert flops.visible_pairs(8192) == triangle
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    sliding = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    expert_layer = 2048 * 256 + 3 * 2048 * 512 + 0.5 * 3 * 2048 * 512
    a_token = (
        2048 * 12544 + 2 * full + 3 * sliding + 3 * 2048 * 8192
        + 4 * expert_layer
    )
    scores = 2 * 2 * 48 * 128 * triangle + 3 * 2 * 64 * 128 * band
    assert flops.flops_per_sample(sizes) == 6 * (8192 * a_token + scores)
    # what ISSUE 48 derived: matrices 269.5 M a token, 19.4 TFLOP a sequence
    assert a_token == pytest.approx(269.5e6, rel=2e-3)
    assert flops.flops_per_sample(sizes) == pytest.approx(19.4e12, rel=5e-3)
    assert flops.swa_call_flops(sizes, flops.FORWARD_PRODUCTS) == 4 * 64 * 128 * band
    assert flops.swa_call_flops(sizes, flops.BACKWARD_PRODUCTS) == 14 * 64 * 128 * band
    assert flops.swa_call_bytes(sizes, 4) == 4 * 2 * 8192 * 64 * 128
