"""The expert layer's ladder (`parallel/moe.route_rungs`): whichever
rung `moe_topk_held` takes for the rows that came, its output, balance
term, stats and every gradient are the full buffer's, and a dense
per-expert reference's; the rung follows sum(sizes) to the row; a
router that sends every token to held experts takes the top rung and
loses nothing, forward or backward; `route_rows` and `route_full` say
what was taken. And the width the grouped matmuls run at
(`moe.run_width`): a layer whose width the rule moves is the layer at
its stated width, outputs and every gradient, and a width it leaves
alone traces the program it traced before the rule."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from elasticdl_tpu.parallel import moe  # noqa: E402
from elasticdl_tpu.parallel.tp_layers import swiglu  # noqa: E402

TOKENS, D, F, EXPERTS = 1024, 16, 12, 16

CASES = {
    # softmax top-2 of 16 with a shared expert and the balance term
    "softmax-top2-shared": dict(
        top_k=2, held=(4, 4), score="softmax", shared=True, bias=False,
        renormalize=False, scaling=1.0,
    ),
    # sigmoid scores, gates renormalised over all four chosen, no shared
    "sigmoid-top4-renormalised": dict(
        top_k=4, held=(0, 8), score="sigmoid", shared=False, bias=False,
        renormalize=True, scaling=2.5,
    ),
    # a selection bias that no gradient reaches
    "sigmoid-top3-bias": dict(
        top_k=3, held=(6, 4), score="sigmoid", shared=True, bias=True,
        renormalize=True, scaling=1.0,
    ),
}


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b))) <= tol * max(1.0, float(np.max(np.abs(b))))


def weights(case, seed=0):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)  # noqa: E731
    n = case["held"][1]
    return {
        "x": draw(2, TOKENS // 2, D).at[:, :, 0].set(1.0),
        "router": draw(D, EXPERTS),
        "experts": (draw(n, D, F), draw(n, D, F), draw(n, F, D)),
        "shared": (draw(D, F), draw(D, F), draw(F, D)) if case["shared"] else None,
        "bias": draw(EXPERTS) if case["bias"] else None,
    }


def settings(case):
    return dict(
        top_k=case["top_k"], held=case["held"], score=case["score"],
        renormalize=case["renormalize"], scaling=case["scaling"],
        balance=case["score"] == "softmax",
    )


def layer(w, case):
    return moe.moe_topk_held(
        w["x"], w["router"], w["experts"], w["shared"], bias=w["bias"],
        **settings(case),
    )


def loss_and_grads(w, case, fn=layer):
    """(y, term, stats), and the gradient of a loss that reads every
    entry of y and the term, by every differentiable leaf."""
    leaves = {k: v for k, v in w.items() if v is not None and k != "bias"}

    def loss(leaves):
        y, term, stats = fn({**w, **leaves}, case)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))) + term, (
            y, term, stats,
        )

    (_l, out), grads = jax.value_and_grad(loss, has_aux=True)(leaves)
    return out, grads


def dense_reference(w, case):
    """The same layer with no sort, no buffer and no ragged product:
    every held expert on every token, times its weight or zero."""
    x = w["x"].reshape(-1, D)
    if case["score"] == "softmax":
        probs, gate, chosen = moe.route_topk(x, w["router"], case["top_k"])
    else:
        probs, gate, chosen = moe.route_sigmoid_topk(
            x, w["router"], w["bias"], case["top_k"], case["renormalize"]
        )
    first, n = case["held"]
    y = 0.0 if w["shared"] is None else swiglu(x, *w["shared"])
    for j in range(n):
        weight = jnp.sum(
            jnp.where(chosen == first + j, gate * case["scaling"], 0.0), axis=-1
        )
        y = y + weight[:, None] * swiglu(x, *(e[j] for e in w["experts"]))
    term = moe.sequence_balance_loss(
        probs.reshape(2, TOKENS // 2, -1), chosen.reshape(2, TOKENS // 2, -1)
    ) if case["score"] == "softmax" else jnp.zeros(())
    return y.reshape(w["x"].shape), term, {}


def lean(w, case, low_rows, high_rows):
    """The router leant towards (or away from) the held experts until
    more than `low_rows` and at most `high_rows` assignments are held:
    every token's first feature is one, and bisection finds the held
    experts' weight on it."""
    first, n = case["held"]
    x = w["x"].reshape(-1, D)
    low, high = -12.0, 12.0
    for _ in range(40):
        router = w["router"].at[0, first:first + n].set((low + high) / 2)
        if case["score"] == "softmax":
            chosen = moe.route_topk(x, router, case["top_k"])[2]
        else:
            chosen = moe.route_sigmoid_topk(
                x, router, w["bias"], case["top_k"], case["renormalize"]
            )[2]
        came = int(jnp.sum((chosen >= first) & (chosen < first + n)))
        if low_rows < came <= high_rows:
            return {**w, "router": router}, came
        low, high = ((low + high) / 2, high) if came <= low_rows else (low, (low + high) / 2)
    raise AssertionError(f"no routing with {low_rows} < rows <= {high_rows}")


def full_buffer_only(monkeypatch):
    """The ladder cut down to its top rung: the path before the ladder."""
    whole = moe.route_rungs
    monkeypatch.setattr(moe, "route_rungs", lambda t, k, n: whole(t, k, n)[-1:])


def test_the_ladder_is_four_rungs_of_whole_row_tiles_from_the_shapes():
    assert moe.route_rungs(8192, 6, 8) == (6144, 12288, 18432, 49152)
    assert moe.route_rungs(8192, 4, 8) == (4096, 8192, 12288, 32768)
    assert moe.route_rungs(4096, 8, 8) == (4096, 8192, 12288, 32768)
    # k above n: a token's k different experts hold at most n here
    assert moe.route_rungs(1024, 6, 2)[-1] == 2048
    # rounded up to whole tiles, none repeated, none past the full buffer
    assert moe.route_rungs(1000, 3, 4) == (512, 768, 1280, 3000)
    assert moe.route_rungs(40, 3, 4) == (120,)
    for t, k, n in [(8192, 6, 8), (1000, 3, 4), (300, 2, 8)]:
        rungs = moe.route_rungs(t, k, n)
        assert list(rungs) == sorted(set(rungs)) and len(rungs) <= 4
        assert all(r % moe.ROW_TILE == 0 for r in rungs[:-1])


@pytest.mark.parametrize("rung", [0, 1, 2, 3])
@pytest.mark.parametrize("name", sorted(CASES))
def test_every_rung_gives_the_full_buffer_s_layer_and_gradients(
    name, rung, monkeypatch
):
    case = CASES[name]
    rungs = moe.route_rungs(TOKENS, case["top_k"], case["held"][1])
    assert len(rungs) == 4
    w, came = lean(weights(case), case, rungs[rung - 1] if rung else 0, rungs[rung])
    (y, term, stats), grads = loss_and_grads(w, case)
    assert float(stats["route_rows"]) == rungs[rung]
    assert float(stats["route_full"]) == (rung == 3)
    assert float(jnp.sum(stats["expert_tokens"])) == came
    # against a dense reference that knows no buffer
    (y_ref, term_ref, _), grads_ref = loss_and_grads(w, case, dense_reference)
    assert close(y, y_ref) and close(term, term_ref)
    # against the full buffer alone
    full_buffer_only(monkeypatch)
    (y_top, term_top, stats_top), grads_top = loss_and_grads(w, case)
    assert float(stats_top["route_rows"]) == rungs[-1]
    assert close(y, y_top, 2e-6) and close(term, term_top, 1e-7)
    for key in ("expert_tokens", "held_share", "router_entropy"):
        np.testing.assert_array_equal(stats[key], stats_top[key])
    flat, flat_top, flat_ref = (
        jax.tree_util.tree_leaves_with_path(g) for g in (grads, grads_top, grads_ref)
    )
    assert len(flat) == (8 if case["shared"] else 5)  # x, router, 3 experts, 3 shared
    for (path, g), (_p, g_top), (_q, g_ref) in zip(flat, flat_top, flat_ref):
        where = jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(g_top))) > 0, where
        assert close(g, g_top, 2e-6), where
        assert close(g, g_ref), where


def staged(pairs):
    """x and an identity router under which token t takes exactly the
    experts `pairs[t]`, the first with the larger probability."""
    x = np.zeros((len(pairs), EXPERTS), np.float32)
    for t, (a, b) in enumerate(pairs):
        x[t, a], x[t, b] = 4.0, 2.0
    return jnp.asarray(x).reshape(2, len(pairs) // 2, EXPERTS), jnp.eye(
        EXPERTS, dtype=jnp.float32
    )


@pytest.mark.parametrize("past", [0, 1])
def test_a_routing_on_a_rung_s_last_row_takes_it_and_one_row_more_the_next(
    past, monkeypatch
):
    """Experts 0 to 3 held, top-2: 256 tokens that take experts 0 and 1
    fill the second rung's 512 rows to the last; a 257th token with one
    held expert needs the third."""
    rungs = moe.route_rungs(TOKENS, 2, 4)
    assert rungs == (256, 512, 768, 2048)
    pairs = [(0, 1)] * 256 + [(8, 9)] * (TOKENS - 256)
    if past:
        pairs[700] = (10, 2)
    x, router = staged(pairs)
    rng = np.random.default_rng(3)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)  # noqa: E731
    w = {
        "x": x, "router": router, "bias": None,
        "experts": (draw(4, EXPERTS, F), draw(4, EXPERTS, F), draw(4, F, EXPERTS)),
        "shared": (draw(EXPERTS, F), draw(EXPERTS, F), draw(F, EXPERTS)),
    }
    case = dict(CASES["softmax-top2-shared"], held=(0, 4))

    def run():
        return loss_and_grads(w, case)

    (y, term, stats), grads = run()
    assert float(jnp.sum(stats["expert_tokens"])) == 512 + past
    assert float(stats["route_rows"]) == rungs[1 + past]
    assert float(stats["route_full"]) == 0.0
    full_buffer_only(monkeypatch)
    (y_top, term_top, _stats), grads_top = run()
    assert close(y, y_top, 2e-6) and close(term, term_top, 1e-7)
    # the last row of the rung is a row like any other: token 255's
    if not past:
        assert float(jnp.max(jnp.abs(y.reshape(TOKENS, -1)[255]))) > 0
    for g, g_top in zip(*(jax.tree_util.tree_leaves(g) for g in (grads, grads_top))):
        assert close(g, g_top, 2e-6)


def test_a_router_that_sends_every_token_to_held_experts_takes_the_top_rung():
    """The gradient twin of `test_dropless_under_skew`: every one of
    the T x 2 assignments is held, every row of the full buffer is
    used, and output and gradients are the dense reference's."""
    rng = np.random.default_rng(11)
    pairs = [tuple(4 + rng.permutation(4)[:2]) for _ in range(TOKENS)]
    x, router = staged(pairs)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)  # noqa: E731
    w = {
        "x": x, "router": router, "bias": None,
        "experts": (draw(4, EXPERTS, F), draw(4, EXPERTS, F), draw(4, F, EXPERTS)),
        "shared": (draw(EXPERTS, F), draw(EXPERTS, F), draw(F, EXPERTS)),
    }
    case = CASES["softmax-top2-shared"]
    (y, term, stats), grads = loss_and_grads(w, case)
    assert float(jnp.sum(stats["expert_tokens"])) == 2 * TOKENS  # none dropped
    assert float(stats["held_share"]) == 1.0
    assert float(stats["route_rows"]) == 2 * TOKENS
    assert float(stats["route_full"]) == 1.0
    (y_ref, term_ref, _), grads_ref = loss_and_grads(w, case, dense_reference)
    assert close(y, y_ref) and close(term, term_ref)
    for g, g_ref in zip(*(jax.tree_util.tree_leaves(g) for g in (grads, grads_ref))):
        assert float(jnp.max(jnp.abs(g_ref))) > 0
        assert close(g, g_ref)


def test_a_router_that_sends_nothing_here_takes_the_lowest_rung():
    pairs = [(8, 9)] * TOKENS
    x, router = staged(pairs)
    w = dict(weights(CASES["softmax-top2-shared"]), x=x, router=router)
    w["experts"] = tuple(
        jnp.ones(s, jnp.float32) for s in ((4, EXPERTS, F), (4, EXPERTS, F), (4, F, EXPERTS))
    )
    w["shared"] = tuple(
        jnp.ones(s, jnp.float32) * 0.1 for s in ((EXPERTS, F), (EXPERTS, F), (F, EXPERTS))
    )
    (y, _term, stats), grads = loss_and_grads(w, CASES["softmax-top2-shared"])
    assert float(stats["route_rows"]) == 256.0 and float(stats["held_share"]) == 0.0
    assert close(y, swiglu(x, *w["shared"]))
    assert all(float(jnp.max(jnp.abs(g))) == 0 for g in grads["experts"])


@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("remat", [False, True])
def test_the_layer_differentiates_inside_a_scanned_rematerialised_stack(
    remat, padded, monkeypatch
):
    """As `plain_forward` runs it: the layer in a scanned body under
    `jax.checkpoint`, two layers whose routers take different rungs;
    `padded`, with the experts' 12 columns run as 16."""
    if padded:
        monkeypatch.setattr(moe, "WIDTH_TILE", 8)
    case = CASES["sigmoid-top4-renormalised"]
    rungs = moe.route_rungs(TOKENS, 4, 8)
    low, _ = lean(weights(case, 1), case, 0, rungs[0])
    high, _ = lean(weights(case, 2), case, rungs[1], rungs[2])
    stack = jax.tree_util.tree_map(
        lambda a, b: jnp.stack([a, b]),
        {k: low[k] for k in ("router", "experts")},
        {k: high[k] for k in ("router", "experts")},
    )

    def forward(stack, x, fn):
        def body(h, lp):
            y, _term, stats = fn({**low, "x": h, **lp}, case)
            return h + 0.1 * y, stats.get("route_rows", 0.0)

        h, rows = jax.lax.scan(jax.checkpoint(body) if remat else body, x, stack)
        return jnp.sum(h * h), rows

    x = low["x"]
    with moe.widths_traced() as widths:
        (_l, rows), grads = jax.jit(
            jax.value_and_grad(lambda s, x: forward(s, x, layer), argnums=(0, 1), has_aux=True)
        )(stack, x)
    assert widths == ({(F, 16)} if padded else set())
    # the second layer reads the first's output, so its rows are its own
    assert float(rows[0]) == rungs[0] and float(rows[1]) in rungs
    (_l, _r), want = jax.value_and_grad(
        lambda s, x: forward(s, x, dense_reference), argnums=(0, 1), has_aux=True
    )(stack, x)
    for g, g_ref in zip(*(jax.tree_util.tree_leaves(g) for g in (grads, want))):
        assert close(g, g_ref, 5e-5)


# ---------------------------------------------------------------------------
# The width the grouped matmuls run at


def rule_held_off(monkeypatch):
    """Every width runs as it is stated: the layer before the rule."""
    monkeypatch.setattr(moe, "run_width", lambda f: f)


def of_kind(w, kind):
    """`w` with experts (and a shared expert) of `kind`'s leaves."""
    if kind == "swiglu":
        return w
    return {
        **w, "experts": w["experts"][1:],
        "shared": w["shared"] and w["shared"][1:],
    }


@pytest.mark.parametrize(
    "f, run",
    [(1408, 1536), (1536, 1536), (1024, 1024), (512, 512), (1856, 2048),
     (F, F), (256, 256), (257, 512), (1280, 1280), (1792, 1792)],
    ids=["deepseek-v2-lite", "lfm2-24b-a2b", "kimi-linear-48b-a3b",
         "laguna-xs2.qwen3-next-80b-a3b", "nemotron-3-nano-30b-a3b",
         "the-tests-own", "one-tile", "a-column-more", "5x256", "7x256"],
)
def test_the_rule_on_the_cells_widths(f, run):
    assert moe.run_width(f) == run


@pytest.mark.parametrize("rung", [0, 3])
@pytest.mark.parametrize("kind", ["swiglu", "relu2"])
def test_a_width_the_rule_moves_gives_the_stated_width_s_layer_and_gradients(
    kind, rung, monkeypatch
):
    """12 columns run as 16, on the lowest rung and on the full buffer:
    outputs, balance term and the gradient by x, the router and every
    expert leaf are those of the layer run at 12, to float32 rounding,
    and the leaves' gradients have the leaves' shapes."""
    monkeypatch.setattr(moe, "WIDTH_TILE", 8)
    case = CASES["softmax-top2-shared"]
    rungs = moe.route_rungs(TOKENS, case["top_k"], case["held"][1])
    w, _came = lean(
        of_kind(weights(case), kind), case, rungs[rung - 1] if rung else 0, rungs[rung]
    )
    with moe.widths_traced() as widths:
        (y, term, stats), grads = loss_and_grads(w, case)
    assert widths == {(F, 16)}
    assert float(stats["route_rows"]) == rungs[rung]
    rule_held_off(monkeypatch)
    with moe.widths_traced() as widths:
        (y_as, term_as, _stats), grads_as = loss_and_grads(w, case)
    assert widths == set()
    assert close(y, y_as, 2e-6) and close(term, term_as, 1e-7)
    assert len(grads["experts"]) == (3 if kind == "swiglu" else 2)
    for g, leaf in zip(grads["experts"], w["experts"]):
        assert g.shape == leaf.shape
    flat, flat_as = (jax.tree_util.tree_leaves_with_path(g) for g in (grads, grads_as))
    assert len(flat) == (8 if kind == "swiglu" else 6)
    for (path, g), (_p, g_as) in zip(flat, flat_as):
        where = jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(g_as))) > 0, where
        assert close(g, g_as, 2e-6), where


def traced(f, kind):
    """The jaxpr of a small layer of width `f`, value and gradients."""
    shape = jax.ShapeDtypeStruct
    w = of_kind({
        "x": shape((2, 32, D), jnp.float32), "router": shape((D, EXPERTS), jnp.float32),
        "experts": tuple(
            shape(s, jnp.float32) for s in ((8, D, f), (8, D, f), (8, f, D))
        ),
        "shared": None,
    }, kind)
    case = CASES["sigmoid-top4-renormalised"]

    def loss(x, router, experts):
        y, term, _stats = moe.moe_topk_held(x, router, experts, None, **settings(case))
        return jnp.sum(y) + term

    return jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        w["x"], w["router"], w["experts"]
    )


@pytest.mark.parametrize("kind", ["swiglu", "relu2"])
@pytest.mark.parametrize("f", [512, 1024, 1536, F, 1408, 1856])
def test_a_width_the_rule_leaves_alone_traces_the_program_it_traced_before(
    f, kind, monkeypatch
):
    """512, 1024, 1536 (and the tests' own 12): no `pad`, and the jaxpr
    of the layer with the rule held off, letter for letter. 1408 and
    1856: pads, and the leaves' gradients at the leaves' shapes."""
    run = moe.run_width(f)
    moved = run != f
    assert moved == (f in (1408, 1856))
    program = traced(f, kind)
    text = str(program)
    rule_held_off(monkeypatch)
    before = str(traced(f, kind))
    assert " pad[" not in before
    assert [v.shape for v in program.out_avals[3:]] == (
        [(8, D, f)] * (2 if kind == "swiglu" else 1) + [(8, f, D)]
    )
    if moved:
        assert " pad[" in text and f"{run}]" in text
        assert f"{run}]" not in before
    else:
        assert text == before


def test_the_transformer_reduces_the_two_counters_over_its_expert_layers():
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "fixtures"))
    import routed_lm_tiny as zoo
    from elasticdl_tpu.common.constants import WINDOW_STATS

    model = zoo.custom_model()
    variables = model.init(jax.random.PRNGKey(0), None)
    assert float(variables[WINDOW_STATS]["route_rows"]) == 0.0
    assert float(variables[WINDOW_STATS]["route_full"]) == 0.0
    tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0, 64)
    _out, state = model.apply(variables, tokens, mutable=[WINDOW_STATS])
    stats = state[WINDOW_STATS]
    rungs = moe.route_rungs(16 * 32, 3, 4)
    taken = [
        min(r for r in rungs if r >= rows)
        for rows in np.asarray(stats["expert_tokens"]).sum(axis=1)
    ]
    assert stats["route_rows"].shape == stats["route_full"].shape == ()
    assert float(stats["route_rows"]) == pytest.approx(np.mean(taken))
    assert float(stats["route_full"]) == sum(r == rungs[-1] for r in taken)


# ---------------------------------------------------------------------------
# The experts' kind from the caller, and a router that reads another tensor


def reglu_reference(w, case, route_from=None):
    """`dense_reference` for gated-ReLU experts: every held expert
    wd(relu(wg x) * wu x) on every token, times its weight or zero; the
    router on `route_from`'s rows where that is given, on x otherwise."""
    x = w["x"].reshape(-1, D)
    scored = x if route_from is None else route_from.reshape(-1, D)
    _probs, gate, chosen = moe.route_topk(scored, w["router"], case["top_k"])
    if case["renormalize"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    first, n = case["held"]
    y = 0.0
    for j in range(n):
        weight = jnp.sum(
            jnp.where(chosen == first + j, gate * case["scaling"], 0.0), axis=-1
        )
        wg, wu, wd = (e[j] for e in w["experts"])
        y = y + weight[:, None] * ((jax.nn.relu(x @ wg) * (x @ wu)) @ wd)
    return y.reshape(w["x"].shape)


REGLU = dict(
    top_k=3, held=(4, 4), score="softmax", shared=False, bias=False,
    renormalize=True, scaling=1.0,
)


@pytest.mark.parametrize("rung", [0, 3])
@pytest.mark.parametrize("padded", [False, True])
def test_reglu_experts_are_the_dense_gated_relu_layer_padded_or_not(
    padded, rung, monkeypatch
):
    """`kind="reglu"` through `_at_run_width`: 12 columns run as 16
    (relu(0) x 0 = 0 meets a zero row) or as they are, on the lowest
    rung and on the full buffer: the output and the gradient by x, the
    router and every expert leaf are the dense gated-ReLU layer's, and
    the padded layer is the unpadded one."""
    if padded:
        monkeypatch.setattr(moe, "WIDTH_TILE", 8)
    rungs = moe.route_rungs(TOKENS, REGLU["top_k"], REGLU["held"][1])
    w, _came = lean(
        weights(REGLU), REGLU, rungs[rung - 1] if rung else 0, rungs[rung]
    )

    def reglu(w, case):
        return moe.moe_topk_held(
            w["x"], w["router"], w["experts"], None, kind="reglu",
            **{**settings(case), "balance": False},
        )

    with moe.widths_traced() as widths:
        (y, term, stats), grads = loss_and_grads(w, REGLU, reglu)
    assert widths == ({(F, 16)} if padded else set())
    assert float(stats["route_rows"]) == rungs[rung] and float(term) == 0.0
    (want, _t, _s), want_grads = loss_and_grads(
        w, REGLU, lambda w, case: (reglu_reference(w, case), 0.0, {})
    )
    assert close(y, want)
    # SiLU in the gate is another layer
    (silu, _t, _s), _g = loss_and_grads(
        w, REGLU, lambda w, case: moe.moe_topk_held(
            w["x"], w["router"], w["experts"], None,
            **{**settings(case), "balance": False},
        )
    )
    assert not close(silu, want, 1e-2)
    flat, flat_want = (
        jax.tree_util.tree_leaves_with_path(g) for g in (grads, want_grads)
    )
    assert len(flat) == 5  # x, the router, wg, wu, wd
    for (path, g), (_p, g_want) in zip(flat, flat_want):
        where = jax.tree_util.keystr(path)
        assert g.shape == g_want.shape, where
        assert float(jnp.max(jnp.abs(g_want))) > 0, where
        assert close(g, g_want, 5e-5), where


@pytest.mark.parametrize("kind, leaves", [
    ("swiglu", 2), ("reglu", 2), ("relu2", 3), ("gelu", 3),
])
def test_a_kind_that_its_leaves_do_not_make_is_refused(kind, leaves):
    w = weights(REGLU)
    with pytest.raises(ValueError, match="leaves"):
        moe.moe_topk_held(
            w["x"], w["router"], w["experts"][:leaves], None, kind=kind,
            **settings(REGLU),
        )


def test_a_shared_expert_of_kind_reglu_is_the_gated_relu_mlp():
    case = {**REGLU, "shared": True}
    w = weights(case)
    y, _term, _stats = moe.moe_topk_held(
        w["x"], w["router"], w["experts"], w["shared"], kind="reglu",
        **{**settings(case), "balance": False},
    )
    wg, wu, wd = w["shared"]
    x = w["x"]
    want = reglu_reference(w, case) + (jax.nn.relu(x @ wg) * (x @ wu)) @ wd
    assert close(y, want)


def test_logits_formed_elsewhere_route_the_layer_and_carry_its_gradient():
    """`logits` from another tensor than x: the experts read x, the
    router what the logits were formed from; the layer is the dense
    one so told, the router's gradient comes through the logits, and
    `router_w` is not read (None will do). Logits formed from x itself
    are the layer as it was, bit for bit."""
    w = weights(REGLU)
    other = jnp.roll(w["x"], 7, axis=1) * 1.3
    kwargs = {**settings(REGLU), "balance": False, "kind": "reglu"}

    def early(x, router, experts):
        logits = moe.router_logits(other.reshape(-1, D), router)
        return moe.moe_topk_held(x, None, experts, None, logits=logits, **kwargs)

    def loss(fn):
        return lambda x, router, experts: jnp.sum(
            fn(x, router, experts)[0] * jnp.cos(jnp.arange(x.size).reshape(x.shape))
        )

    got = early(w["x"], w["router"], w["experts"])
    want = reglu_reference(w, REGLU, route_from=other)
    assert close(got[0], want)
    assert not close(got[0], reglu_reference(w, REGLU), 1e-2)
    grads = jax.grad(loss(early), argnums=(0, 1, 2))(
        w["x"], w["router"], w["experts"]
    )
    want_grads = jax.grad(loss(
        lambda x, router, experts: (reglu_reference(
            {"x": x, "router": router, "experts": experts}, REGLU, other
        ),)
    ), argnums=(0, 1, 2))(w["x"], w["router"], w["experts"])
    for g, g_want in zip(*(jax.tree_util.tree_leaves(t) for t in (grads, want_grads))):
        assert float(jnp.max(jnp.abs(g_want))) > 0
        assert close(g, g_want, 5e-5)
    # the same tensor: the layer as it was
    same = moe.moe_topk_held(
        w["x"], None, w["experts"], None,
        logits=moe.router_logits(w["x"].reshape(-1, D), w["router"]), **kwargs,
    )
    as_it_was = moe.moe_topk_held(w["x"], w["router"], w["experts"], None, **kwargs)
    assert np.array_equal(np.asarray(same[0]), np.asarray(as_it_was[0]))
    for name in as_it_was[2]:
        assert np.array_equal(
            np.asarray(same[2][name]), np.asarray(as_it_was[2][name])
        ), name


def test_sigmoid_scores_take_logits_formed_elsewhere_too():
    case = CASES["sigmoid-top3-bias"]
    w = weights(case)
    logits = moe.router_logits(w["x"].reshape(-1, D), w["router"])
    given = moe.moe_topk_held(
        w["x"], None, w["experts"], w["shared"], bias=w["bias"], logits=logits,
        **settings(case),
    )
    own = layer(w, case)
    assert np.array_equal(np.asarray(given[0]), np.asarray(own[0]))


# The tiny programs of the two configurations `tests/test_mamba2_lm.py`
# does not pin, traced on the parent of this change (commit 259850a):
# the same digests here, so `moe_topk_held`'s `kind` and `logits`,
# `AttentionShape.turns` and the `router` scope changed nothing they run
# (the five others: `test_mamba2_lm.py`'s `TRACED`, which still holds).
TRACED = {
    "mamba2_lm_tiny": "2e9631248a6c7813",
    "sambay_lm_tiny": "45f4e46ce1ee645c",
}


@pytest.mark.parametrize("fixture", sorted(TRACED))
def test_the_other_configurations_tiny_programs_trace_as_they_did(fixture):
    import hashlib
    import importlib
    import re
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "fixtures"))
    module = importlib.import_module(fixture)
    model = module.custom_model()
    variables = model.init(jax.random.PRNGKey(0), None)
    tokens = jnp.zeros((2, 32), jnp.int32)

    def loss(p):
        out, _ = model.apply({**variables, "params": p}, tokens, mutable=True)
        return module.loss(out, tokens)

    text = str(jax.make_jaxpr(jax.value_and_grad(loss))(variables["params"]))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == TRACED[fixture]
