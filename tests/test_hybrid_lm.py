"""The hybrid stack (Kimi-Linear's block at a tiny size) and its parts,
float32 on the CPU: the chunked delta-rule recurrence against the
recurrence itself, the program against the configuration's plain
reference (`benchmark/configs/kimi-linear-48b-a3b/reference.py`: the
recurrence a token at a time, the experts as a masked dense sum), the
shares of the sigmoid expert layer against the uncut layer, and the
routed model as the pattern "all `mla`".

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
from elasticdl_tpu.ops import kda  # noqa: E402
from elasticdl_tpu.parallel import moe  # noqa: E402

TOLERANCE = 2e-4
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "kimi-linear-48b-a3b")


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def close(a, b, tolerance=TOLERANCE, floor=1e-6):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tolerance * max(np.max(np.abs(b)), floor)


def recurrence_inputs(decay, batch=2, length=150, heads=3, dk=16, dv=12, seed=0):
    """q, k, v, g, beta with a log-decay of about -`decay` a token."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (batch, length, heads, dk))
    k = jax.random.normal(keys[1], (batch, length, heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (batch, length, heads, dv))
    g = -decay * (0.5 + jax.nn.sigmoid(jax.random.normal(keys[3], q.shape)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, length, heads)))
    return q, k, v, g, beta


CHUNKED = jax.jit(kda.kda_chunked, static_argnames=("chunk", "sub"))
RECURRENT = jax.jit(kda.kda_recurrent)  # one compile a shape


# from 1 - 1e-4 a token down to e^-20 a token: 64 tokens of the last
# sum to -1280 and more, far below float32's range for exp(+x)
DECAYS = (1e-4, 1e-2, 0.3, 1.0, 5.0, 20.0)


@pytest.mark.parametrize("sub", [16, 64])
@pytest.mark.parametrize("decay", DECAYS)
def test_the_chunked_scan_is_the_recurrence_at_every_decay(decay, sub):
    args = recurrence_inputs(decay)
    got, lowest = CHUNKED(*args, chunk=64, sub=sub)
    want = RECURRENT(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert close(got, want, 2e-5)
    # three chunks of 150 tokens; the fullest has 64 tokens of g
    assert -64 * 1.5 * decay <= float(lowest) <= -22 * 0.5 * decay


@pytest.mark.parametrize("decay", [1e-4, 1.0, 20.0])
def test_the_chunked_scan_s_gradients_are_the_recurrence_s(decay):
    args = recurrence_inputs(decay, length=100)

    def through(f):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2, 3, 4)
        ))(*args)

    got = through(lambda *a: kda.kda_chunked(*a, chunk=32, sub=8)[0])
    want = through(kda.kda_recurrent)  # both jitted inside `through`
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        # at e^-20 a token the log-decay's gradient is 1e-9 and less
        # against q's, k's and v's of order one: held to their scale
        assert close(a, b, 1e-4, floor=1e-3), name


@pytest.mark.parametrize("length", [64, 65, 1, 63])
def test_a_length_need_not_be_a_multiple_of_the_chunk(length):
    args = recurrence_inputs(0.3, length=length)
    got, _ = CHUNKED(*args, chunk=64)
    assert got.shape == args[2].shape
    assert close(got, RECURRENT(*args), 2e-5)


@pytest.mark.parametrize("sub", [2, 4, 16])
def test_decay_pairs_are_the_masked_sums_of_differenced_exponentials(sub):
    rng = np.random.default_rng(3)
    q, k = rng.normal(size=(2, 2, 16, 5))
    # to -600, as float32 holds it (both sides difference the same sums)
    G = -np.cumsum(rng.uniform(0, 40, size=(2, 16, 5)), axis=1)
    G = G.astype(np.float32).astype(np.float64)
    want_a, want_b = np.zeros((2, 2, 16, 16))
    for r in range(16):
        for i in range(r + 1):
            pair = k[:, i] * np.exp(G[:, r] - G[:, i])
            want_b[:, r, i] = np.sum(q[:, r] * pair, axis=-1)
            if i < r:
                want_a[:, r, i] = np.sum(k[:, r] * pair, axis=-1)
    got_a, got_b = kda.decay_pairs(
        *(jnp.asarray(x, jnp.float32) for x in (q, k, G)), sub
    )
    assert close(got_a, want_a, 1e-5) and close(got_b, want_b, 1e-5)


def test_a_block_s_gradients_written_out_are_jax_s_through_its_forward_pass():
    rng = np.random.default_rng(5)
    q, k = jnp.asarray(rng.normal(size=(2, 3, 8, 5)), jnp.float32)
    G = jnp.asarray(-np.cumsum(rng.uniform(0, 3, size=(3, 8, 5)), axis=1), jnp.float32)
    dA, dB = jnp.asarray(rng.normal(size=(2, 3, 8, 8)), jnp.float32)

    def plain(q, k, G):
        rows = jnp.arange(8)
        E = jnp.exp(jnp.where(
            (rows[:, None] >= rows[None, :])[:, :, None],
            G[..., :, None, :] - G[..., None, :, :], -jnp.inf,
        ))
        A = jnp.sum(k[..., :, None, :] * k[..., None, :, :] * E, axis=-1)
        B = jnp.sum(q[..., :, None, :] * k[..., None, :, :] * E, axis=-1)
        return jnp.tril(A, k=-1), B

    def through(f):
        return jax.grad(
            lambda *a: sum(jnp.sum(x * d) for x, d in zip(f(*a), (dA, dB))),
            argnums=(0, 1, 2),
        )(q, k, G)

    for got, want in zip(through(kda.block_pairs), through(plain)):
        assert close(got, want, 1e-5)


@pytest.mark.parametrize("below", [0.2, 0.9, 1.0])
def test_the_triangular_system_is_solved_by_substitution(below):
    """Also where every entry below the diagonal is near one constant
    (keys that resemble each other, a slow decay, beta near 1): the
    powers of such an N reach 1e17 while its inverse stays below 1."""
    rng = np.random.default_rng(4)
    N = np.tril(below + rng.normal(size=(3, 64, 64)) * 0.02, k=-1)
    rhs = rng.normal(size=(3, 64, 5))
    got = kda.solve_unit_lower(
        jnp.asarray(N, jnp.float32), jnp.asarray(rhs, jnp.float32), 16
    )
    assert close(got, np.linalg.solve(np.eye(64) + N, rhs), 1e-4)


@pytest.mark.parametrize("sub", [8, 16])
def test_the_inverse_and_its_gradient_are_the_triangular_system_s(sub):
    rng = np.random.default_rng(6)
    N = jnp.asarray(np.tril(rng.normal(size=(3, 32, 32)) * 0.3, k=-1), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(3, 32, 32)), jnp.float32)
    want = np.linalg.inv(np.eye(32) + np.asarray(N, np.float64))
    assert close(kda.unit_lower_inverse(N, sub), want, 1e-4)
    got = jax.grad(lambda N: jnp.sum(kda.unit_lower_inverse(N, sub) * weight))(N)
    plain = jax.grad(lambda N: jnp.sum(
        kda.solve_unit_lower(N, jnp.broadcast_to(jnp.eye(32), N.shape), sub)
        * weight
    ))(N)
    assert close(got, jnp.tril(plain, k=-1), 1e-4)


def test_keys_that_resemble_each_other_under_a_slow_decay_stay_finite():
    q, k, v, g, beta = recurrence_inputs(1e-4, length=128)
    k = k * 0.05 + k[:, :1]  # one direction and a little of its own
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = 0.98 + 0.0 * beta
    got, _ = CHUNKED(q, k, v, g, beta)
    assert close(got, RECURRENT(q, k, v, g, beta), 1e-3)


def test_a_chunk_boundary_that_drops_the_state_is_not_the_recurrence():
    """The control `compare.py` runs on the chip: chunks that each start
    from a zero state."""
    args = recurrence_inputs(0.01, length=128)
    dropped = jnp.concatenate([
        kda.kda_recurrent(*(x[:, lo:lo + 64] for x in args))
        for lo in (0, 64)
    ], axis=1)
    want = kda.kda_recurrent(*args)
    assert close(dropped[:, :64], want[:, :64], 1e-6)
    assert not close(dropped, want, 1e-2)


def _dot_precisions(jaxpr):
    """The `precision` of every `dot_general` of a jaxpr and of the
    jaxprs inside it (scans, checkpoints, custom derivatives)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found.extend(_dot_precisions(inner))
    return found


@pytest.mark.parametrize("entry", ["jax", "kernels"])
@pytest.mark.parametrize("ambient", [None, "bfloat16"])
@pytest.mark.parametrize("pass_", ["forward", "backward"])
def test_every_product_of_the_scan_states_float32_whatever_the_caller_s_precision(
    ambient, pass_, entry
):
    """`config.json` states the chunked form's matrices and the carried
    state as float32; the TPU's default rounds a float32 operand to
    bfloat16, so each product names `HIGHEST` itself, forward and
    backward, under any ambient precision. The walk descends into a
    `pallas_call`'s jaxpr, so the kernel entry (a head of 128, the
    `intra` stage inside the kernels) is held to the same."""
    if entry == "jax":
        args, kw = recurrence_inputs(0.01, length=128), {}
        least = {"forward": 20, "backward": 50}[pass_]
    else:
        args = recurrence_inputs(0.01, length=128, heads=1, dk=128, dv=128)
        kw = {"interpret": True}
        # two chunks a grid step, eight products each forward (20 with
        # the pass over the chunks' four) and 63 with the transposes
        least = {"forward": 20, "backward": 60}[pass_]
    loss = lambda *a: jnp.sum(jnp.sin(kda.kda_chunked(*a, **kw)[0]))  # noqa: E731
    traced = loss if pass_ == "forward" else jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    with jax.default_matmul_precision(ambient):
        jaxpr = jax.make_jaxpr(traced)(*args)
        precisions = _dot_precisions(jaxpr.jaxpr)
    assert ("pallas_call" in str(jaxpr)) == (entry == "kernels")
    assert len(precisions) >= least
    highest = jax.lax.Precision.HIGHEST
    assert all(p in (highest, (highest, highest)) for p in precisions), precisions


def test_a_control_wraps_the_chunk_s_step_and_not_the_function(monkeypatch):
    """`compare.py`'s `bf16_state` rounds the state a chunk hands on by
    wrapping `kda.chunk_step`, which `kda_chunked` looks up at the call:
    the production function takes no dtype for it."""
    args = recurrence_inputs(0.01, length=256)
    exact, _ = kda.kda_chunked(*args)
    step = kda.chunk_step

    def rounded(S, xs):
        S, o = step(S, xs)
        return jax.lax.reduce_precision(S, 8, 7), o

    monkeypatch.setattr(kda, "chunk_step", rounded)
    got, _ = kda.kda_chunked(*args)
    assert close(got[:, :64], exact[:, :64], 1e-6)  # the first chunk's state is zero
    assert not close(got, exact, 1e-4)
    assert close(got, exact, 5e-2)


# ------------------------------------------------- the program, the reference


def hybrid():
    import hybrid_lm_tiny as zoo

    return zoo, load_module(os.path.join(CONFIG_DIR, "reference.py"))


def program_and_reference(length, seed=3):
    zoo, ref = hybrid()
    model = zoo.custom_model()
    variables = model.init(jax.random.PRNGKey(seed), None)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
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


# 32 = two chunks of 16; 24 and 41 are not multiples of the chunk
@pytest.mark.parametrize("length", [32, 24, 41])
def test_the_program_s_logits_loss_and_loads_are_the_reference_s(length):
    params, program, reference = program_and_reference(length)
    got, (logits, stats) = jax.jit(program)(params)
    want, (ref_logits, loads) = jax.jit(reference)(params)
    assert close(logits, ref_logits)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    assert np.array_equal(
        np.asarray(stats["expert_tokens"]), np.asarray(loads)[:, 4:8]
    )
    assert float(stats["kda_log_decay_min"]) < 0
    assert float(stats["router_bias_absmax"]) == 0.0


@pytest.mark.parametrize("length", [32, 24, 41])
def test_every_leaf_s_gradient_is_the_reference_s(length):
    params, program, reference = program_and_reference(length)
    got = jax.jit(jax.grad(lambda p: program(p)[0]))(params)
    want = jax.jit(jax.grad(lambda p: reference(p)[0]))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) > 60
    for (path, a), b in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # no gradient reaches it, on either side
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b))
            continue
        assert np.any(np.asarray(b)), name
        assert close(a, b), name


def test_the_rotation_left_on_is_not_the_reference():
    import hybrid_lm_tiny as zoo

    params, _program, reference = program_and_reference(32)
    turned = zoo.custom_model(mla_rope=True)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 64)
    logits, _aux = turned.apply({"params": params}, tokens[:, :-1])
    assert not close(logits, reference(params)[1][0], 1e-2)


# ------------------------------------------------------- the sigmoid layer


def sigmoid_layer(seed=5, tokens=(2, 12), d=16, experts=16, f=8):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]), jnp.float32)

    x = jnp.asarray(rng.normal(size=tokens + (d,)), jnp.float32)
    return x, draw(d, experts), (
        draw(experts, d, f), draw(experts, d, f), draw(experts, f, d)
    ), (draw(d, f), draw(d, f), draw(f, d))


@pytest.mark.parametrize("renormalize", [True, False])
def test_the_shares_of_the_sigmoid_layer_add_up_to_the_uncut_layer(renormalize):
    """Four shares of four experts, the shared expert counted once,
    are the layer that holds all sixteen."""
    x, router, (wg, wu, wd), shared = sigmoid_layer()
    bias = jnp.asarray(np.random.default_rng(6).normal(size=16) * 0.1, jnp.float32)
    settings = dict(top_k=3, scaling=2.446, score="sigmoid", bias=bias,
                    renormalize=renormalize, balance=False)
    whole, term, stats = moe.moe_topk_held(
        x, router, (wg, wu, wd), shared, held=(0, 16), **settings
    )
    assert float(term) == 0.0
    assert float(stats["held_share"]) == pytest.approx(1.0)
    once = lm.swiglu(x, *shared)
    parts, seen = once, 0.0
    for first in (0, 4, 8, 12):
        part, _term, share = moe.moe_topk_held(
            x, router, (wg[first:first + 4], wu[first:first + 4],
                        wd[first:first + 4]),
            shared, held=(first, 4), **settings,
        )
        parts = parts + (part - once)
        seen += float(jnp.sum(share["expert_tokens"]))
    assert seen == 2 * 12 * 3
    assert close(parts, whole, 1e-5)


def test_the_selection_bias_chooses_and_is_not_in_the_gate():
    x, router, _experts, _shared = sigmoid_layer()
    xf = x.reshape(-1, x.shape[-1])
    scores, gate, chosen = moe.route_sigmoid_topk(xf, router, None, 3, True)
    assert close(jnp.sum(gate, axis=-1), jnp.ones(len(xf)), 1e-6)
    assert np.array_equal(
        np.sort(np.asarray(chosen), axis=-1),
        np.sort(np.argsort(-np.asarray(scores), axis=-1)[:, :3], axis=-1),
    )
    # a bias that lifts expert 9 over every other makes every token
    # take it; its gate is still its own score's share
    bias = jnp.zeros(16).at[9].set(2.0)
    _s, lifted, took = moe.route_sigmoid_topk(xf, router, bias, 3, False)
    assert bool(jnp.all(took[:, 0] == 9))
    assert close(lifted[:, 0], scores[:, 9], 1e-6)
    # and no gradient reaches it
    grad = jax.grad(
        lambda b: jnp.sum(moe.route_sigmoid_topk(xf, router, b, 3, True)[1])
    )(bias)
    assert not np.any(np.asarray(grad))


def test_unnormalised_gates_are_the_scores_themselves():
    x, router, _experts, _shared = sigmoid_layer()
    xf = x.reshape(-1, x.shape[-1])
    scores, gate, chosen = moe.route_sigmoid_topk(xf, router, None, 3, False)
    assert close(gate, jnp.take_along_axis(scores, chosen, axis=-1), 1e-7)
    assert float(jnp.max(gate)) < 1.0


# ------------------------------------------------------------ the stack


def test_the_pattern_all_mla_is_the_routed_model_bit_for_bit():
    import routed_lm_tiny as zoo

    plain, patterned = zoo.custom_model(), zoo.custom_model(
        layer_types=("mla",) * 3
    )
    assert plain.cfg.runs == patterned.cfg.runs == (
        ("mla", False, 1), ("mla", True, 2)
    )
    a = plain.init(jax.random.PRNGKey(2), None)
    b = patterned.init(jax.random.PRNGKey(2), None)
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    assert sorted(a["params"]) == ["dense", "embed", "head", "layers", "ln_f"]
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert np.array_equal(x, y)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 20), 0, 64)

    def outputs(model, variables):
        def loss(p):
            out, state = model.apply(
                {**variables, "params": p}, tokens, mutable=[WINDOW_STATS]
            )
            return zoo.loss(out, tokens), (out, state)

        return jax.jit(jax.value_and_grad(loss, has_aux=True))(
            jax.tree_util.tree_map(jnp.asarray, variables["params"])
        )

    for x, y in zip(jax.tree_util.tree_leaves(outputs(plain, a)),
                    jax.tree_util.tree_leaves(outputs(patterned, b))):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_the_stack_is_cut_into_runs_of_one_mixer_and_one_mlp():
    import hybrid_lm_tiny as zoo

    cfg = zoo.custom_model().cfg
    assert cfg.mixed and cfg.runs == (
        ("kda", False, 1), ("kda", True, 2), ("mla", True, 1), ("kda", True, 1)
    )
    params = zoo.custom_model().init(jax.random.PRNGKey(0), None)["params"]
    assert [run["ln1"].shape[0] for run in params["stack"]] == [1, 2, 1, 1]
    assert params["kda_a_log"].shape == (4 * 4,)
    assert "router" not in params["stack"][0] and "wkva" in params["stack"][2]


def test_the_zoo_adapter_states_the_hybrid_s_window_stats():
    import hybrid_lm_tiny as zoo

    stats = zoo.custom_model().init(jax.random.PRNGKey(0), None)[WINDOW_STATS]
    assert sorted(stats) == [
        "expert_tokens", "held_share", "kda_log_decay_min", "route_full",
        "route_rows", "router_bias_absmax", "router_entropy",
    ]
    assert stats["expert_tokens"].shape == (4, 4)
    assert stats["route_rows"].shape == stats["route_full"].shape == ()


@pytest.mark.parametrize("setting", [
    {"attention": "kda"}, {"layer_types": ("mha", "mha", "mha", "mha")},
])
def test_the_mesh_path_refuses_the_hybrid_settings_by_name(setting):
    cfg = lm.TransformerConfig(**setting)
    with pytest.raises(NotImplementedError, match="plain_forward"):
        lm.param_partition_specs(cfg)


def test_the_stack_needs_a_mixer_named_for_every_layer():
    import hybrid_lm_tiny as zoo

    with pytest.raises(NotImplementedError, match="one a layer"):
        zoo.custom_model(layer_types=("kda", "mla")).init(
            jax.random.PRNGKey(0), None
        )


class _Shapes:
    """A generator whose normals are shapes alone: 600 M draws take a
    minute and 2.4 GB; a zero-stride view of one zero takes neither."""

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


def test_the_configuration_counts_its_parameters_and_keeps_off_rows_of_32():
    """602,434,432 as `config.json` derives them, and no leaf whose last
    dim is 32: the v5e compiler would view the whole flat vector as
    [n / 32, 32] to cut such a leaf out, padded fourfold."""
    zoo = load_module(os.path.join(CONFIG_DIR, "zoo.py"))
    cfg = zoo.custom_model().cfg
    params = lm.init_params(_Shapes(), cfg)
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(leaf.size for leaf in leaves) == zoo.SIZES["parameters"] == 602434432
    assert all(leaf.shape[-1] % 64 == 0 for leaf in leaves)
    assert cfg.runs == (
        ("kda", False, 1), ("kda", True, 2), ("mla", True, 1), ("kda", True, 1)
    )
    widths = {k: v.shape[1:] for k, v in params["stack"][1].items()}
    assert widths["wq"] == (2304, 4096) and widths["conv_q"] == (4, 4096)
    assert widths["router"] == (2304, 256) and widths["eg"] == (8, 2304, 1024)
    assert params["stack"][2]["wkvb"].shape == (1, 512, 32 * 256)
    assert params["stack"][0]["wg"].shape == (1, 2304, 9216)
