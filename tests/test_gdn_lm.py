"""The scalar-decay delta-rule stack (Qwen3-Next's block at a tiny
size) and its parts, float32 on the CPU: the program against the
configuration's plain reference (`benchmark/configs/qwen3-next-80b-a3b/
reference.py`: the delta rule a token at a time, attention a key-value
head at a time, the experts as a masked dense sum), each new piece
alone (the channel gate, the partly turned head, the gated shared
expert), the shares of the expert layer against the uncut layer, the
stack's runs, and the configuration's file.

Tolerance: both sides are float32 with the same mathematics in another
order, so they agree to accumulated rounding. A layer alone agrees to a
relative 1e-5 of the largest value (the layers' tests below); through
the whole stack the gradients read up to 2.6e-4 on these seeds (the
other configurations' tiny stacks stay under their 2e-4), so the whole
program is held to 5e-4 and every control to 1e-2 and more."""

import json
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
from elasticdl_tpu.parallel import moe  # noqa: E402

TOLERANCE = 5e-4
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "qwen3-next-80b-a3b")


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def close(a, b, tolerance=TOLERANCE, floor=1e-6):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tolerance * max(np.max(np.abs(b)), floor)


def gdn():
    import gdn_lm_tiny as zoo

    return zoo, load_module(os.path.join(CONFIG_DIR, "reference.py"))


def seeded_params(model, seed):
    """The zoo's weights with every norm's weight, the decay's leaf and
    the narrow leaves moved off their initial ones and zeros, so that a
    weight left out or misplaced shows."""
    variables = model.init(jax.random.PRNGKey(seed), None)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 100))
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a) + (
            0.1 * jax.random.normal(next(keys), a.shape)
            if a.ndim <= 2 else 0.0
        ), variables["params"],
    )
    return variables, params


def program_and_reference(length, seed=3, **overrides):
    zoo, ref = gdn()
    model = zoo.custom_model(**overrides)
    variables, params = seeded_params(model, seed)
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
        return value, (ref.logits_of(p, x, zoo.REFERENCE_SIZES), loads)

    return params, program, reference


# 48 = three chunks of 16; 24 and 41 are not multiples of the chunk
@pytest.mark.parametrize("length", [48, 24, 41])
def test_the_program_s_logits_loss_and_loads_are_the_reference_s(length):
    params, program, reference = program_and_reference(length)
    got, (logits, stats) = jax.jit(program)(params)
    want, (ref_logits, loads) = jax.jit(reference)(params)
    assert close(logits, ref_logits)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    assert np.array_equal(
        np.asarray(stats["expert_tokens"]), np.asarray(loads)[:, 4:8]
    )
    assert sorted(stats) == [
        "attn_gate_mean", "expert_tokens", "gdn_beta_mean",
        "gdn_log_decay_min", "held_share", "route_full", "route_rows",
        "router_entropy", "shared_gate_mean",
    ]
    assert float(stats["gdn_log_decay_min"]) < 0
    for name in ("attn_gate_mean", "gdn_beta_mean", "shared_gate_mean"):
        assert 0.3 < float(stats[name]) < 0.7, name  # untrained: a half


@pytest.mark.parametrize("length", [48, 24, 41])
def test_every_leaf_s_gradient_is_the_reference_s(length):
    params, program, reference = program_and_reference(length)
    got = jax.jit(jax.grad(lambda p: program(p)[0]))(params)
    want = jax.jit(jax.grad(lambda p: reference(p)[0]))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == 4 + 15 + 16
    for (path, a), b in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        assert np.any(np.asarray(b)), name
        assert close(a, b), name


@pytest.mark.parametrize("control, setting", [
    ("full_rotary", {"rope_dim": None}),
    ("no_renormalise", {"moe_renormalize": False}),
    ("no_shared_gate", {"shared_expert_gate": False}),
])
def test_a_block_that_changes_a_part_is_not_the_reference(control, setting):
    """What `compare.py`'s controls change on the chip changes the
    logits here by far more than the tolerance."""
    zoo, ref = gdn()
    model = zoo.custom_model()
    _variables, params = seeded_params(model, 3)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    want = ref.logits_of(params, tokens, zoo.REFERENCE_SIZES)
    other = zoo.custom_model(**setting)
    if control == "no_shared_gate":  # the same weights but the gate's
        params = {**params, "stack": [
            {k: v for k, v in run.items() if k != "sgate"}
            for run in params["stack"]
        ]}
    logits, _aux = other.apply({"params": params}, tokens)
    assert not close(logits, want, 1e-2)


# ------------------------------------------------------- the layers alone


def layer_leaves(seed=5):
    zoo, ref = gdn()
    model = zoo.custom_model()
    _variables, params = seeded_params(model, seed)
    linear = {k: v[1] for k, v in params["stack"][0].items()}
    half = params["gdn_decay"].shape[0] // 2
    linear["a_log"] = params["gdn_decay"][4:8]
    linear["dt_bias"] = params["gdn_decay"][half + 4:half + 8]
    full = {k: v[0] for k, v in params["stack"][1].items()}
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 40, 64))
    return zoo, ref, model.cfg, linear, full, x


def test_a_gated_deltanet_layer_is_the_recurrence_a_token_at_a_time():
    zoo, ref, cfg, linear, _full, x = layer_leaves()
    got, stats = lm._gdn(cfg, linear, x)
    want = ref.delta_attention(linear, x, zoo.REFERENCE_SIZES)
    assert close(got, want, 1e-5)
    # the stats: the write strength's mean, the decay's lowest sum
    ba = x @ linear["wba"].T
    assert float(stats["gdn_beta_mean"]) == pytest.approx(
        float(jnp.mean(jax.nn.sigmoid(ba[..., :4]))), rel=1e-5
    )
    g = -jnp.exp(linear["a_log"]) * jax.nn.softplus(ba[..., 4:] + linear["dt_bias"])
    sums = jnp.pad(g, ((0, 0), (0, 8), (0, 0))).reshape(2, 3, 16, 4).sum(2)
    assert float(stats["gdn_log_decay_min"]) == pytest.approx(
        float(sums.min()), rel=1e-5
    )


@pytest.mark.parametrize("control", ["sigmoid_z", "no_l2", "key_head_mod", "no_decay"])
def test_a_gated_deltanet_layer_that_changes_a_part_is_not_the_reference(
    monkeypatch, control
):
    from elasticdl_tpu.ops import kda

    zoo, ref, cfg, linear, _full, x = layer_leaves()
    want = ref.delta_attention(linear, x, zoo.REFERENCE_SIZES)
    chunked = kda.kda_chunked
    swaps = {
        "sigmoid_z": (lm, "_gdn_out_gate",
                      lambda z: jax.nn.sigmoid(z.astype(jnp.float32))),
        "no_l2": (lm, "_unit_length", lambda y: y),
        "key_head_mod": (kda, "kda_chunked", lambda q, k, *a, **kw: chunked(
            jnp.tile(q, (1, 1, 2, 1)), jnp.tile(k, (1, 1, 2, 1)), *a, **kw)),
        "no_decay": (kda, "kda_chunked", lambda q, k, v, g, beta, **kw: chunked(
            q, k, v, jnp.zeros_like(g), beta, **kw)),
    }
    monkeypatch.setattr(*swaps[control])
    got, _stats = lm._gdn(cfg, linear, x)
    assert not close(got, want, 1e-2)


def test_the_channel_gate_is_the_second_half_of_the_query_projection():
    """o x sigmoid(gate) per output CHANNEL, the gate's columns behind
    the queries'; a gate averaged over a head is another layer."""
    zoo, ref, cfg, _linear, full, x = layer_leaves()
    positions = jnp.arange(x.shape[1])
    got, stats = lm._attend(cfg, full, x, positions, "mha")
    want = ref.gated_attention(full, x, zoo.REFERENCE_SIZES)
    assert close(got, want, 1e-5)
    gate = jax.nn.sigmoid((x @ full["wq"])[..., 4 * 32:])
    assert float(stats["attn_gate_mean"]) == pytest.approx(
        float(gate.mean()), abs=1e-6
    )
    # ungated: the same weights, the gate's columns cut off
    import dataclasses

    plain = dataclasses.replace(cfg, attn_channel_gate=False)
    ungated, _ = lm._attend(
        plain, {**full, "wq": full["wq"][:, :4 * 32]}, x, positions, "mha"
    )
    assert not close(ungated, want, 1e-2)
    heads = gate.reshape(2, 40, 4, 32)
    per_head = jnp.broadcast_to(heads.mean(-1, keepdims=True), heads.shape)
    kept = lm._channel_gate
    lm._channel_gate = lambda p: per_head.reshape(p.shape)
    try:
        averaged, _ = lm._attend(cfg, full, x, positions, "mha")
    finally:
        lm._channel_gate = kept
    assert not close(averaged, want, 1e-2)


def test_the_first_columns_of_a_head_turn_and_position_zero_does_not():
    """8 of 32 columns turn (the cell: 64 of 256), pair i = (x[i],
    x[i + 4]); the other 24 pass as projected; position 0 is
    unturned."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 32))
    positions = jnp.arange(6)
    turned = lm._rope(x, positions, 1e7, rot=8)
    assert np.array_equal(np.asarray(turned[..., 8:]), np.asarray(x[..., 8:]))
    assert close(turned[:, 0], x[:, 0], 1e-7)
    assert not close(turned[:, 1:, :, :8], x[:, 1:, :, :8], 1e-2)
    freqs = 1e7 ** (-jnp.arange(4) / 4.0)
    angle = positions[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :4], x[..., 4:8]
    assert close(turned[..., :4], x1 * cos - x2 * sin, 1e-6)
    assert close(turned[..., 4:8], x1 * sin + x2 * cos, 1e-6)
    _zoo, ref = gdn()
    mine = ref.rotate(x[:, :, 0], {"rope_dim": 8, "rope_base": 1e7})
    assert close(mine, turned[:, :, 0], 1e-6)


# ------------------------------------------------ the shares of a layer


def softmax_layer(seed=5, tokens=(2, 12), d=16, experts=32, f=8):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]), jnp.float32)

    x = jnp.asarray(rng.normal(size=tokens + (d,)), jnp.float32)
    return x, draw(d, experts), (
        draw(experts, d, f), draw(experts, d, f), draw(experts, f, d)
    ), (draw(d, f), draw(d, f), draw(f, d)), draw(1, d)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer_the_gated_shared_expert_once():
    """The guide's section 4: eight shares of 4 of 32 experts (as
    thirty-two of 16 of 512), softmax top-3 renormalised, each with the
    GATED shared expert every chip computes alike: their routed parts
    and the gated shared expert counted ONCE are the uncut reference
    layer."""
    _zoo, ref = gdn()
    x, router, (wg, wu, wd), (sg, su, sd), sgate = softmax_layer()
    settings = dict(top_k=3, score="softmax", renormalize=True, balance=False,
                    shared_gate=sgate)
    lp = {"router": router, "eg": wg, "eu": wu, "ed": wd,
          "sg": sg, "su": su, "sd": sd, "sgate": sgate}
    ref_sizes = {"top_k": 3}
    uncut, balance, loads = ref.expert_layer(lp, x, ref_sizes, held=(0, 32))
    # the balance term is over all experts: every share computes the same
    _y, term, _stats = moe.moe_topk_held(
        x, router, (wg[:4], wu[:4], wd[:4]), (sg, su, sd), held=(0, 4),
        **{**settings, "balance": True},
    )
    assert float(term) == pytest.approx(float(balance), rel=1e-5)
    assert float(jnp.sum(loads)) == 2 * 12 * 3
    shared_alone = jax.nn.sigmoid(x @ sgate.T) * ref.gated_mlp(x, sg, su, sd)
    without, _, _ = ref.expert_layer(lp, x, ref_sizes, held=(0, 32), shared=False)
    assert close(without + shared_alone, uncut, 1e-6)
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
        assert float(share["shared_gate_mean"]) == pytest.approx(
            float(jax.nn.sigmoid(x @ sgate.T).mean()), rel=1e-5
        )
        # what this share gives beside what every chip computes alike
        parts = parts + (part - shared_alone)
        seen += float(jnp.sum(share["expert_tokens"]))
        cut, _, _ = ref.expert_layer(
            {**lp, "eg": wg[held], "eu": wu[held], "ed": wd[held]}, x,
            ref_sizes, held=(first, 4),
        )
        assert close(part, cut, 1e-5)
    assert seen == 2 * 12 * 3
    assert close(parts + shared_alone, uncut, 1e-5)
    # the shared expert counted eight times, or ungated, is not the layer
    assert not close(parts + 8 * shared_alone, uncut, 1e-2)
    assert not close(parts + ref.gated_mlp(x, sg, su, sd), uncut, 1e-2)


def test_a_layer_without_the_gate_is_the_layer_as_it_was():
    """`shared_gate=None` adds no operation and no stat."""
    x, router, (wg, wu, wd), shared, _sgate = softmax_layer()
    held = (wg[:4], wu[:4], wd[:4])
    settings = dict(top_k=3, held=(0, 4), renormalize=True, balance=False)
    a, _t, stats = moe.moe_topk_held(x, router, held, shared, **settings)
    b, _t, _s = moe.moe_topk_held(
        x, router, held, shared, shared_gate=None, **settings
    )
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert "shared_gate_mean" not in stats


# ------------------------------------------------------------ the stack


def test_the_stack_is_cut_into_runs_by_mixer():
    zoo, _ref = gdn()
    cfg = zoo.custom_model().cfg
    assert cfg.mixed and cfg.runs == (("gdn", True, 3), ("mha", True, 1))
    params = zoo.custom_model().init(jax.random.PRNGKey(0), None)["params"]
    assert [run["ln1"].shape[0] for run in params["stack"]] == [3, 1]
    assert params["gdn_decay"].shape == (2 * 3 * 4,)
    assert "wqkvz" in params["stack"][0] and "wk" in params["stack"][1]
    assert "kda_a_log" not in params


def test_the_scopes_of_the_new_mixer_and_gates():
    """`gdn` ⊃ `conv`, `gates`, `scan` ⊃ `intra`, `state`, `out`;
    `attention` ⊃ `gate`; `moe` ⊃ `shared` ⊃ `gate`; no `kda` scope."""
    zoo, _ref = gdn()
    model = zoo.custom_model()
    variables = model.init(jax.random.PRNGKey(0), None)
    text = jax.jit(
        lambda p: model.apply({**variables, "params": p},
                              jnp.zeros((1, 40), jnp.int32))[0]
    ).lower(variables["params"]).as_text(debug_info=True)
    for want in ("gdn/conv", "gdn/gates", "gdn/scan/intra", "gdn/scan/state",
                 "gdn/out", "attention/gate", "moe/shared/gate", "moe/route"):
        assert want in text, want
    assert "kda/" not in text and "attention/global" not in text


def test_an_existing_stack_keeps_its_attention_scope_and_tree():
    """`attention/gate` exists under the channel gate alone, and no
    other configuration's tree gains a leaf: LFM2's and Laguna's tiny
    programs as they were."""
    import shortconv_lm_tiny as lfm2
    import window_lm_tiny as laguna

    def lowered(zoo):
        model = zoo.custom_model()
        variables = model.init(jax.random.PRNGKey(0), None)
        text = jax.jit(
            lambda p: model.apply({**variables, "params": p},
                                  jnp.zeros((1, 12), jnp.int32))[0]
        ).lower(variables["params"]).as_text(debug_info=True)
        return variables["params"], text

    params, text = lowered(lfm2)
    assert "attention/gate" not in text and "moe/shared" not in text
    params, text = lowered(laguna)
    assert "attention/global/gate" in text and "shared/gate" not in text
    assert "gdn_decay" not in params
    assert not any("sgate" in run for run in params["stack"])


@pytest.mark.parametrize("setting", [
    {"attention": "gdn"}, {"gdn_key_heads": 2}, {"gdn_value_heads": 4},
    {"gdn_head_dim": 16}, {"attn_channel_gate": True},
    {"shared_expert_gate": True},
    {"layer_types": ("gdn", "gdn", "gdn", "mha")},
])
def test_the_mesh_path_refuses_the_new_settings_by_name(setting):
    cfg = lm.TransformerConfig(**setting)
    with pytest.raises(NotImplementedError, match="plain_forward") as refusal:
        lm.param_partition_specs(cfg)
    with pytest.raises(NotImplementedError, match="plain_forward"):
        lm.reference_forward(cfg, {}, jnp.zeros((1, 4), jnp.int32))
    name = next(iter(setting))
    named = "gdn" if name in ("attention", "layer_types") else name
    assert named in str(refusal.value)


# ------------------------------------------------------ the configuration


class _Shapes:
    """A generator whose normals are shapes alone: 324 M draws take most
    of a minute and 1.3 GB; a zero-stride view of one zero takes
    neither."""

    class _Normal:
        def __init__(self, shape):
            self.shape = shape

        def __mul__(self, _scale):
            return self

        def astype(self, dtype):
            return np.broadcast_to(np.zeros((), dtype), self.shape)

    def standard_normal(self, shape):
        return self._Normal(shape)

    def uniform(self, low, high, shape):
        return np.full(shape, (low + high) / 2)


def test_the_configuration_counts_its_parameters_as_its_file_derives_them():
    """424,340,544 as `config.json` derives them, the runs of the cut,
    and no leaf that ends in a narrow dim: all are multiples of 64
    (the flat decay leaf's 192 among them) but the head's 18,992 rows
    of vocabulary."""
    zoo = load_module(os.path.join(CONFIG_DIR, "zoo.py"))
    cfg = zoo.custom_model().cfg
    assert cfg.runs == (("gdn", True, 3), ("mha", True, 1))
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_head_dim,
            cfg.gdn_conv, cfg.kda_chunk) == (16, 32, 128, 4, 64)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.rope_dim) == (16, 2, 256, 64)
    assert (cfg.qk_norm, cfg.attn_channel_gate, cfg.attn_gate) == (True, True, False)
    assert cfg.rope_base == 1e7 and cfg.rope_yarn is None
    assert (cfg.n_experts, cfg.held, cfg.moe_top_k, cfg.d_expert) == (
        512, (0, 16), 10, 512
    )
    assert (cfg.n_shared_experts, cfg.shared_expert_gate, cfg.moe_score,
            cfg.moe_renormalize, cfg.routed_scaling) == (
        1, True, "softmax", True, 1.0
    )
    # assumed (`balance_term`): the family's released coefficient on
    # the program's term, x the ten choices
    assert cfg.aux_weight == pytest.approx(0.001 * 10)
    assert (cfg.vocab, cfg.norm_eps, cfg.tie_embeddings) == (18992, 1e-6, False)
    params = lm.init_params(_Shapes(), cfg)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert sum(leaf.size for _path, leaf in leaves) == zoo.SIZES["parameters"]
    assert zoo.SIZES["parameters"] == 424_340_544
    per_run = [sum(x.size for x in jax.tree_util.tree_leaves(run))
               for run in params["stack"]]
    # the decay's 64 numbers a layer lie in `gdn_decay`, not in the run
    assert per_run == [3 * (88_250_560 - 64), 81_795_584]
    assert params["gdn_decay"].shape == (3 * 64,)
    assert all(
        leaf.shape[-1] % 64 == 0 or leaf.shape[-1] == 18992
        for _path, leaf in leaves
    )
    widths = {k: v.shape[1:] for k, v in params["stack"][0].items()}
    assert widths["wqkvz"] == (2048, 12288) and widths["conv"] == (4, 8192)
    assert widths["wba"] == (64, 2048) and widths["sgate"] == (1, 2048)
    assert widths["router"] == (2048, 512) and widths["eg"] == (16, 2048, 512)
    assert params["stack"][1]["wq"].shape == (1, 2048, 2 * 16 * 256)
    assert params["stack"][1]["wk"].shape == (1, 2048, 2 * 256)


def test_the_zoo_refuses_a_file_that_states_another_block(monkeypatch):
    zoo = load_module(os.path.join(CONFIG_DIR, "zoo.py"))
    for key, other in (
        ("model_type", "qwen3_moe"), ("tie_word_embeddings", True),
        ("norm_topk_prob", False), ("decoder_sparse_step", 2),
        ("mlp_only_layers", [0]), ("use_sliding_window", True),
        ("rope_scaling", {"type": "yarn"}), ("linear_value_head_dim", 64),
        ("full_attention_interval", 3),
    ):
        monkeypatch.setitem(zoo.SIZES, key, other)
        with pytest.raises(ValueError, match="does not build"):
            zoo.custom_model()
        monkeypatch.undo()
    assert zoo.custom_model().cfg.gdn_value_heads == 32


def test_the_file_keeps_every_number_of_the_catalog_s_row_but_the_reduced():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        sizes = json.load(f)
    assert sizes["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "mlp_only_layers": [], "model_type": "qwen3_next",
        "moe_intermediate_size": 512, "norm_topk_prob": True,
        "num_attention_heads": 16, "num_experts_per_tok": 10,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False,
    }
    for key, value in published.items():
        assert sizes[key] == value, key
    assert (sizes["num_hidden_layers"], sizes["num_experts"], sizes["vocab_size"]) == (4, 16, 18992)
    assert sizes["published"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936,
        "parameters": sizes["published"]["parameters"],
    }
    assert len(sizes["layer_types"]) == 48
    assert sizes["layer_types"].count("full_attention") == 12
    assert sizes["held_layers"] == [0, 4] and sizes["held_experts"] == [0, 16]
    assert sizes["assumed"] and sizes["departures"]
    assert sizes["deployment"].startswith("32 chips")
    # the balance term is assumed, no published key of the cut
    assert "router_aux_loss_coef" not in sizes
    term = sizes["balance_term"]
    assert term["assumed"] is True and term["weight_a_layer"] == pytest.approx(
        term["router_aux_loss_coef"] * term["times_the_choices"]
    )


def test_the_configuration_s_flops_are_its_file_s_arithmetic():
    flops = load_module(os.path.join(CONFIG_DIR, "flops.py"))
    zoo = load_module(os.path.join(CONFIG_DIR, "zoo.py"))
    sizes = zoo.SIZES
    triangle = 33_558_528
    assert flops.visible_pairs(8192) == triangle
    gdn_mixer = 2048 * 12288 + 4 * 8192 + 2048 * 64 + 4096 * 2048
    attention = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    # a token: the two triangles once a KEY head, the rest a VALUE head
    scan = (16 * 64 * 64 * 128 + 32 * (
        64 * 64 / 2 * 256 + 3 * 64 * 128 * 128 + 64 * 64 / 2 * 128
    )) / 64
    assert flops.gdn_scan_macs(sizes) == scan
    expert_layer = 2048 * 512 + 3 * 2048 * 512 + 2048 + (10 * 16 / 512) * 3 * 2048 * 512
    a_token = 2048 * 18992 + 3 * (gdn_mixer + scan) + attention + 4 * expert_layer
    scores = 2 * 16 * 256 * triangle
    assert flops.flops_per_sample(sizes) == 6 * (8192 * a_token + scores)
    # ISSUE 52 estimated "about 11.2 TFLOP" at 16 held
    assert flops.flops_per_sample(sizes) == pytest.approx(11.2e12, rel=5e-3)
    assert flops.gdn_scan_flops(8192, sizes) == 2 * 8192 * scan
    assert flops.gdn_scan_bytes(8192, sizes) == 8192 * (
        2 * (2 * 16 * 128 + 2 * 32 * 128) + 8 * 32
    )
    assert flops.attention_call_flops(sizes, flops.FORWARD_PRODUCTS) == (
        4 * 16 * 256 * triangle
    )
    assert flops.attention_call_flops(sizes, flops.BACKWARD_PRODUCTS) == (
        14 * 16 * 256 * triangle
    )
    assert flops.attention_call_bytes(sizes, 4) == 4 * 2 * 8192 * 16 * 256
