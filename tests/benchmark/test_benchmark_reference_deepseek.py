"""DeepSeek-V2-Lite (`deepseek-v2-lite`): its zoo module against its
plain reference on seeded random weights at a small size, float32 on
the CPU, and what ties the configuration's cuts to the model.

Tolerance: both sides are float32 with the same mathematics in another
order (a sort by expert and grouped matmuls against a masked dense sum
over the held experts, scans with rematerialization against Python
loops), so they agree to accumulated rounding — a relative 2e-4 of the
largest value, the other configurations' tolerance, far tighter than
bfloat16's 4e-3: a lower precision or a left-out term (the latent's
norm, the shared rotary key, YaRN's scale, an expert, the shared
expert, the balance term) on either side fails."""

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
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "deepseek-v2-lite")
CELL = "deepseek-v2-lite.window16-serial-1w"

from benchmark.harness import flops, manifest as manifest_lib  # noqa: E402
from benchmark.harness.manifest import load_module  # noqa: E402


def load(name):
    return load_module(os.path.join(CONFIG_DIR, name + ".py"))


def close(a, b, tolerance=TOLERANCE):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tolerance * max(np.max(np.abs(b)), 1e-6)


SMALL = dict(
    vocab=97, d_model=48, n_heads=4, d_ff=80, kv_lora_rank=24, qk_nope_dim=8,
    qk_rope_dim=8, v_head_dim=12, d_expert=20,
)


def small(zoo, layers=3, experts=16, held=(4, 4), top_k=3, **overrides):
    import jax.numpy as jnp

    return zoo.custom_model(
        dtype=jnp.float32, n_layers=layers, n_experts=experts,
        held_experts=held, moe_top_k=top_k, **{**SMALL, **overrides},
    )


def reference_sizes(ref, zoo, cfg, **overrides):
    return ref.sizes_of(
        zoo.SIZES, heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope=cfg.qk_nope_dim, qk_rope=cfg.qk_rope_dim, v_head=cfg.v_head_dim,
        top_k=cfg.moe_top_k, held=cfg.held, **overrides,
    )


def seeded(model, seed=3, vocab=97, length=33):
    """Weights from a seed with every leaf filled (the norms' scales
    start at 1: a test that leaves them there cannot see a term they
    multiply away) and one batch of tokens."""
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


CASES = [
    pytest.param(dict(), id="3-layers-held-4-of-16-top-3"),
    pytest.param(dict(layers=2, held=(0, 8), experts=8, top_k=2), id="every-expert-held"),
    pytest.param(dict(layers=5, held=(12, 4), top_k=6), id="5-layers-top-6-last-four"),
    pytest.param(dict(held=(6, 2), top_k=4), id="fewer-held-than-chosen"),
]


@pytest.mark.parametrize("case", CASES)
def test_logits_loss_balance_and_loads_match_the_reference(case):
    from elasticdl_tpu.common.constants import WINDOW_STATS

    zoo, ref = load("zoo"), load("reference")
    model = small(zoo, **case)
    params, tokens, targets = seeded(model)
    sizes = reference_sizes(ref, zoo, model.cfg)
    want_logits, want_balance, want_loads = ref.forward(params, tokens, sizes)
    (logits, aux), state = model.apply(
        {"params": params, WINDOW_STATS: {}}, tokens, mutable=[WINDOW_STATS]
    )
    assert close(logits, want_logits)
    assert close(aux, model.cfg.aux_weight * want_balance)
    first, count = model.cfg.held
    stats = state[WINDOW_STATS]
    np.testing.assert_array_equal(
        np.asarray(stats["expert_tokens"]),
        np.asarray(want_loads)[:, first:first + count],
    )
    assert float(stats["held_share"]) == pytest.approx(
        float(np.sum(np.asarray(want_loads)[:, first:first + count]))
        / (want_loads.shape[0] * tokens.size * model.cfg.moe_top_k)
    )
    assert close(zoo.loss((logits, aux), targets), ref.loss(params, tokens, targets, sizes))


@pytest.mark.parametrize("case", CASES)
def test_every_gradient_leaf_matches_the_reference(case):
    import jax

    zoo, ref = load("zoo"), load("reference")
    model = small(zoo, **case)
    params, tokens, targets = seeded(model, seed=5)
    sizes = reference_sizes(ref, zoo, model.cfg)
    got = jax.grad(
        lambda p: zoo.loss(model.apply({"params": p}, tokens), targets)
    )(params)
    want = jax.grad(lambda p: ref.loss(p, tokens, targets, sizes))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == 27  # 10 dense, 14 expert, 3
    for (path, g), w in zip(flat_got, flat_want):
        assert float(np.max(np.abs(np.asarray(w)))) > 0, path
        assert close(g, w), (jax.tree_util.keystr(path), np.max(np.abs(g - w)))


@pytest.mark.parametrize("shares,held,top_k", [(8, 2, 6), (4, 4, 3), (2, 8, 6)])
def test_the_shares_add_up_to_the_uncut_layer(shares, held, top_k):
    """The guide's one test of the cut: the routed parts the `shares`
    chips of an expert-parallel layer compute, each told another range
    of `held` experts, plus what every chip computes alike (the shared
    expert) counted ONCE, equal the reference's layer with every
    expert held."""
    import jax.numpy as jnp

    from elasticdl_tpu.parallel import moe

    ref = load("reference")
    experts, d, f = shares * held, 32, 24
    rng = np.random.default_rng(shares)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)  # noqa: E731
    x = draw(2, 19, d)
    lp = {
        "router": draw(d, experts) * 3, "eg": draw(experts, d, f),
        "eu": draw(experts, d, f), "ed": draw(experts, f, d),
        "sg": draw(d, 2 * f), "su": draw(d, 2 * f), "sd": draw(2 * f, d),
    }
    sizes = {"top_k": top_k, "held": (0, experts), "routed_scaling": 1.0}
    whole, balance, loads = ref.expert_layer(lp, x, sizes)
    shared = (x.reshape(-1, d) @ lp["sg"], x.reshape(-1, d) @ lp["su"])
    shared = ((shared[0] * (1 / (1 + jnp.exp(-shared[0]))) * shared[1]) @ lp["sd"]).reshape(x.shape)
    routed, counted = 0.0, []
    for i in range(shares):
        rows = slice(i * held, (i + 1) * held)
        y, term, stats = moe.moe_topk_held(
            x, lp["router"], (lp["eg"][rows], lp["eu"][rows], lp["ed"][rows]),
            (lp["sg"], lp["su"], lp["sd"]), top_k=top_k, held=(i * held, held),
        )
        routed = routed + (y - shared)
        counted += list(np.asarray(stats["expert_tokens"]))
        assert close(term, balance)  # over all experts on every chip
    assert close(routed + shared, whole)
    np.testing.assert_array_equal(counted, np.asarray(loads))
    assert sum(counted) == x.shape[0] * x.shape[1] * top_k  # none dropped


@pytest.mark.parametrize("target,expect", [
    ("one-held", "all"), ("absent", "shared-only"), ("two-held", "all"),
])
def test_dropless_under_skew(target, expect):
    """A router that sends every token to one held expert loses none;
    one that sends all to absent experts returns S(x) alone."""
    import jax.numpy as jnp

    from elasticdl_tpu.parallel import moe
    from elasticdl_tpu.parallel.tp_layers import swiglu

    experts, d, f, top_k = 16, 32, 24, 2
    rng = np.random.default_rng(7)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)  # noqa: E731
    x = jnp.abs(draw(3, 17, d)) + 0.1  # positive: a column of the router decides
    bias = np.zeros((d, experts), np.float32)
    favoured = {"one-held": (5, 12), "absent": (0, 12), "two-held": (4, 7)}[target]
    bias[:, favoured[0]] = 4.0
    bias[:, favoured[1]] = 2.0
    router = jnp.asarray(bias)
    eg, eu, ed = draw(4, d, f), draw(4, d, f), draw(4, f, d)
    shared = (draw(d, f), draw(d, f), draw(f, d))
    y, _term, stats = moe.moe_topk_held(
        x, router, (eg, eu, ed), shared, top_k=top_k, held=(4, 4)
    )
    tokens = x.shape[0] * x.shape[1]
    want = swiglu(x, *shared)
    probs = np.asarray(jnp.exp(x @ router) / jnp.sum(jnp.exp(x @ router), -1, keepdims=True))
    loads = np.zeros(4)
    for e in favoured:
        if 4 <= e < 8:
            j = e - 4
            want = want + probs[..., e, None] * swiglu(x, eg[j], eu[j], ed[j])
            loads[j] = tokens
    assert close(y, want)
    np.testing.assert_array_equal(np.asarray(stats["expert_tokens"]), loads)
    if expect == "shared-only":
        assert float(stats["held_share"]) == 0.0
        assert close(y, swiglu(x, *shared))
    else:
        assert float(stats["held_share"]) == loads.sum() / (tokens * top_k)


@pytest.mark.parametrize("router_scale", [0.0, 1.0])
def test_ties_go_to_the_lower_expert_on_both_sides(router_scale):
    """Equal probabilities (a zero router: all 16; duplicated router
    columns: pairs) are resolved alike: the program's `lax.top_k` and
    the reference's repeated argmax both take the lower expert first."""
    import jax.numpy as jnp

    from elasticdl_tpu.parallel import moe

    ref = load("reference")
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(0, 1, (40, 32)), jnp.float32)
    half = jnp.asarray(rng.normal(0, 1, (32, 8)), jnp.float32) * router_scale
    router = jnp.repeat(half, 2, axis=1)  # experts 2i and 2i + 1 tie
    probs, _gate, chosen = moe.route_topk(x, router, 3)
    want = np.asarray(ref.greedy_top_k(probs, 3))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(chosen), 1.0, axis=-1)
    np.testing.assert_array_equal(got, want)
    if router_scale == 0.0:
        np.testing.assert_array_equal(np.asarray(chosen), [[0, 1, 2]] * 40)


def test_rotary_frequencies_and_scale_are_yarn_s():
    from elasticdl_tpu.models import transformer_lm

    zoo, ref = load("zoo"), load("reference")
    cfg = zoo.custom_model().cfg
    got = transformer_lm.yarn_frequencies(64, cfg.rope_base, cfg.rope_yarn)
    want = np.asarray(ref.yarn_frequencies(64, 10000.0, zoo.SIZES["rope_scaling"]))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    # pairs that turn more than 32 times in 4096 positions keep their
    # frequency, those that turn less than once are divided by 40
    assert got[0] == pytest.approx(plain[0]) and got[-1] == pytest.approx(plain[-1] / 40)
    assert np.all(np.diff(got) < 0)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert transformer_lm.mla_softmax_scale(cfg) == pytest.approx(192**-0.5 * mscale**2)
    assert ref.softmax_scale(192, zoo.SIZES["rope_scaling"]) == pytest.approx(
        transformer_lm.mla_softmax_scale(cfg)
    )


def test_compare_py_reads_the_gradient_out_of_the_worker_s_own_step(tmp_path, monkeypatch):
    compare = load("compare")
    monkeypatch.setattr(compare, "ROOT", str(tmp_path))
    assert compare.main(["--seed", "5", "--small"]) == 0
    with open(tmp_path / "chiprun_out" / "deepseek_compare.jsonl") as f:
        verdict = json.loads(f.readline())
    assert verdict["float32_beyond_tight"] == {}
    found = verdict["measures"]
    assert found["float32"]["grad_rel_l2"] < 1e-4 < found["timed"]["grad_rel_l2"]
    assert found["rounded"]["grad_rel_l2"] > 2 * found["timed"]["grad_rel_l2"]
    assert np.asarray(verdict["timed"]["loads"]).shape == (2, 4)


def test_flops_are_the_hand_count():
    # a token: latent attention 2048 x 3072 + 2048 x 576 + 512 x 4096 +
    # 2048 x 2048 = 13_762_560; the dense layer + 3 x 2048 x 10944 ->
    # 81_002_496; an expert layer 13_762_560 + router 131_072 + shared
    # 17_301_504 + 6 x 8 / 64 x 8_650_752 -> 37_683_200; head 2048 x
    # 12800 = 26_214_400; causal attention 5 x 16 x (192 + 128) x 2049 / 2
    # = 26_227_200 -> 284_176_896 MACs, x 6 x 2048
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        sizes = json.load(f)
    attention = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert attention == 13_762_560
    expert = 3 * 2048 * 1408
    layer = attention + 2048 * 64 + 2 * expert + 6 * 8 * expert // 64
    assert (expert, layer) == (8_650_752, 37_683_200)
    macs = attention + 3 * 2048 * 10944 + 4 * layer + 2048 * 12800
    macs += 5 * 16 * 320 * 2049 // 2
    assert macs == 284_176_896
    got = flops.flops_per_sample(sizes, CONFIG_DIR)
    assert got == pytest.approx(6 * macs * 2048, rel=1e-12)
    assert got == pytest.approx(3.49e12, rel=1e-3)
    module = load("flops")
    # one grouped matmul over 6144 routed rows (uniform routing at 4 x 2048)
    assert module.expert_matmul_flops(6144, sizes) == 2 * 6144 * 2048 * 1408
    assert module.expert_matmul_bytes(6144, sizes) == 2 * (
        6144 * 2048 + 6144 * 1408 + 8 * 2048 * 1408
    )


def test_the_configuration_states_its_source_cuts_and_sizes():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        sizes = json.load(f)
    for key in ("source", "assumed", "reduced", "published", "deployment",
                "parameters_how", "minibatch_rehearsal"):
        assert sizes[key], key
    assert sizes["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert sizes["published"]["num_hidden_layers"] == 27
    assert sizes["published"]["n_routed_experts"] == 64 == 8 * sizes["n_routed_experts"]
    assert sizes["published"]["vocab_size"] == 102400 == 8 * sizes["vocab_size"]
    assert sizes["held_experts"] == [0, 8] and "eight chips" in sizes["deployment"]
    # the published widths, unchanged
    assert (sizes["hidden_size"], sizes["num_attention_heads"],
            sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
            sizes["v_head_dim"], sizes["kv_lora_rank"], sizes["intermediate_size"],
            sizes["moe_intermediate_size"], sizes["num_experts_per_tok"],
            sizes["n_shared_experts"], sizes["first_k_dense_replace"]) == (
        2048, 16, 128, 64, 128, 512, 10944, 1408, 6, 2, 1)
    assert sizes["rope_scaling"]["factor"] == 40 and sizes["q_lora_rank"] is None
    assert sizes["num_hidden_layers"] == 1 + 4  # the dense layer and the floor of four
    attention = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    assert attention == 13_763_072
    dense = attention + 2 * 2048 + 3 * 2048 * 10944
    expert_layer = attention + 2 * 2048 + 2048 * 64 + (2 + 8) * 3 * 2048 * 1408
    assert (dense, expert_layer) == (81_007_104, 100_405_760)
    assert sizes["parameters"] == dense + 4 * expert_layer + 2 * 12800 * 2048 + 2048
    assert sizes["parameters"] == 535_060_992
    # one frame each way, under 2^31 bytes and under the 4 GiB frame
    assert sizes["parameters"] * 4 == 2_140_243_968 < 2**31
    rehearsal = sizes["minibatch_rehearsal"]
    assert rehearsal["bytes_at_1"] <= rehearsal["bytes_at_2"] <= rehearsal["bytes_at_4"]
    assert sizes["records_per_task"] == 16 * sizes["minibatch_per_chip"]
    with open(os.path.join(CONFIG_DIR, "zoo.py")) as f:
        assert "probe.start_if_worker()" in f.read()
    with open(os.path.join(CONFIG_DIR, "reference.py")) as f:
        assert "elasticdl_tpu" not in f.read().replace("`elasticdl_tpu`", "")


def test_the_zoo_module_builds_the_stated_model():
    from elasticdl_tpu.models import transformer_lm

    class Zeros:  # the shapes of the draw, none of its 22 seconds
        @staticmethod
        def standard_normal(shape):
            return np.zeros(shape, np.float32)

    zoo = load("zoo")
    cfg = zoo.custom_model().cfg
    params = transformer_lm.init_params(Zeros(), cfg)
    import jax

    count = sum(int(leaf.size) for leaf in jax.tree_util.tree_leaves(params))
    assert count == zoo.SIZES["parameters"]
    assert params["layers"]["router"].shape == (4, 2048, 64)
    assert params["layers"]["eg"].shape == (4, 8, 2048, 1408)
    assert params["dense"]["wg"].shape == (1, 2048, 10944)
    assert (cfg.attention, cfg.moe_top_k, cfg.held, cfg.n_dense_layers, cfg.remat) == (
        "mla", 6, (0, 8), 1, True)
    assert str(cfg.dtype) == "bfloat16" and cfg.aux_weight == 0.001
    with pytest.raises(NotImplementedError, match="unsharded path"):
        transformer_lm.param_partition_specs(cfg)


def test_the_committed_manifest_holds_this_configuration_and_its_cell():
    """Nothing here counts entries or reads a list from its end: a
    later entry, of this configuration or another, breaks nothing."""
    committed = manifest_lib.load(ROOT)
    assert manifest_lib.lint(committed, ROOT) == []
    resolved = manifest_lib.resolve(committed, CELL, ROOT)
    assert resolved["cell"]["chips"] == 1
    assert resolved["mix"]["workers"] == 1
    assert resolved["mix"]["master_flags"] == {
        "local_updates": 16, "grads_to_wait": 1, "overlap_sync": "off"
    }
    assert resolved["config"]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"
    ]
    assert resolved["config"]["source"] == resolved["sizes"]["source"]
    reported = manifest_lib.cell_metrics(committed, CELL, "per_layer")
    for name, source in (
        ("moe_experts_pct", "device_trace"), ("moe_route_pct", "device_trace"),
        ("mla_attention_pct", "device_trace"),
        ("experts_roofline_pct", "device_trace"),
        ("expert_load_max_over_mean", "program_span"),
    ):
        assert reported[name]["workloads"] == [CELL]
        assert reported[name]["source"] == source
        assert reported[name]["moves"] == "goodput"
        assert os.path.isfile(manifest_lib.reader_file(name, ROOT))
    assert reported["experts_roofline_pct"]["better"] == "higher"
    assert {
        m["name"] for m in committed["per_layer"] if "workloads" not in m
    } <= set(reported)
    assert {"goodput", "setup_s"} <= set(
        manifest_lib.cell_metrics(committed, CELL, "end_to_end")
    )
