"""What the window's loop carries (worker.CARRY_LEAVES_MIN_MEAN_ELEMENTS):
the flat vectors where a model's leaves are small, today's program to
the jaxpr; the template's trees where they are large, cut from and
joined to the worker's flat state by `jit_cut` and `jit_join` round
`jit_window`. CPU, tiny models: the constant is patched down where a
test needs the leaves carry."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.api.model_spec_helpers import spec_from_module
from elasticdl_tpu.common import codec
from elasticdl_tpu.master.ps_optimizer import PSOptimizer
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.models import transformer_lm, transformer_lm_zoo
from elasticdl_tpu.obs import trace
from elasticdl_tpu.testing import InProcessMaster, write_linear_records
from elasticdl_tpu.worker import worker as worker_module
from elasticdl_tpu.worker.worker import Worker, carries_leaves

from tests.fixtures import linear_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW = 4


def clipped_adam():
    return optax.chain(optax.clip_by_global_norm(1.0), optax.adam(1e-2))


def momentum_sgd():  # ResNet-50's optimizer in the benchmark
    return optax.sgd(0.1, momentum=0.9)


@pytest.fixture
def leaves_carry(monkeypatch):
    """Every template carries leaves (a mean leaf has an element)."""
    monkeypatch.setattr(worker_module, "CARRY_LEAVES_MIN_MEAN_ELEMENTS", 1)


def lm_worker(optimizer=clipped_adam, zoo=transformer_lm_zoo):
    spec = spec_from_module(zoo, optimizer=optimizer)
    servicer = MasterServicer(
        grads_to_wait=1, optimizer=PSOptimizer(spec.optimizer())
    )
    worker = Worker(
        0, InProcessMaster(servicer), spec, minibatch_size=2,
        local_updates=WINDOW,
    )
    worker._lazy_init_model(np.zeros((2, 8), np.int32))
    return worker


def batches(seed=0):
    tokens = np.random.default_rng(seed).integers(
        0, 128, (WINDOW, 2, 9)
    ).astype(np.int32)
    return tokens[..., :-1], tokens[..., 1:]


def moments(opt_state, n):
    return [
        np.asarray(a) for a in jax.tree_util.tree_leaves(opt_state)
        if np.shape(a) == (n,)
    ]


# -- (a) the same mathematics ------------------------------------------------


@pytest.mark.parametrize(
    "optimizer, rtol, atol",
    [(clipped_adam, 1e-4, 2e-6), (momentum_sgd, 1e-5, 5e-7)],
    ids=["clipped_adam", "momentum_sgd"],
)
def test_a_window_on_leaves_is_four_per_step_flat_updates(
    leaves_carry, optimizer, rtol, atol
):
    """Parameters, every moment and the last loss. Under clipped Adam
    to float32 rounding (the clip's norm sums the leaves' squares in
    another order than the vector's, and Adam's quotient of two small
    moments carries a last place into the parameter: 5e-7 on 4 of
    82,240 here). Under plain momentum SGD, whose transforms are
    elementwise, NOT bit for bit either on this backend: 6 % of the
    parameters differ by one unit in the last place (1.2e-7), the
    gradient of a leaf being summed in another order than the
    vector's slice; so a tolerance of a few last places."""
    worker = lm_worker(optimizer)
    features, labels = batches()
    flat0 = np.asarray(worker._flat)
    n = flat0.size
    tx = worker._spec.optimizer()

    step = worker._build_local_step()  # the per-step flat program
    flat, state, aux = jnp.asarray(flat0), tx.init(jnp.asarray(flat0)), worker._aux
    for f, l in zip(features, labels):
        flat, state, aux, loss = step(flat, state, aux, f, l)

    worker._local_window_fn = worker._build_local_window_fn()
    got_flat, got_state, _aux, got_loss = worker._run_window(
        jnp.asarray(flat0), tx.init(jnp.asarray(flat0)), worker._aux,
        features, labels,
    )
    assert not np.array_equal(np.asarray(got_flat), flat0)  # it trained
    # the moments stay trees from window to window; a per-step program
    # takes them back as vectors
    assert jax.tree_util.tree_structure(got_state) != (
        jax.tree_util.tree_structure(state)
    )
    got_state = worker._vector_state(got_state, got_flat)
    pairs = [(got_flat, flat), (got_loss, loss)] + list(
        zip(moments(got_state, n), moments(state, n))
    )
    assert len(pairs) == (4 if optimizer is clipped_adam else 3)
    for got, want in pairs:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=rtol, atol=atol
        )
    # the scalars the loop does not cut (Adam's count) counted the steps
    counts = [
        int(a) for a in jax.tree_util.tree_leaves(got_state)
        if np.shape(a) == ()
    ]
    assert counts == [WINDOW] * len(counts)


# -- (b) the rule ------------------------------------------------------------


class _Drawn:
    def __init__(self, shape):
        self.shape = tuple(shape)

    def __mul__(self, _scale):
        return self

    def astype(self, dtype):
        return jax.ShapeDtypeStruct(self.shape, dtype)


class _ShapesOnly(np.random.Generator):
    """`init_params` draws nothing: a configuration's shapes at no
    memory."""

    def __init__(self):
        super().__init__(np.random.PCG64(0))

    def standard_normal(self, shape):
        return _Drawn(shape)


def test_a_resnet_shaped_tree_keeps_the_flat_vector():
    """161 leaves of 159 K elements in the mean, 25.6 M in all (shapes
    only): 6.6 times under the constant."""
    tree = {
        f"leaf_{i}": jax.ShapeDtypeStruct((158_739,), np.float32)
        for i in range(161)
    }
    assert sum(s.shape[0] for s in tree.values()) == 25_556_979
    assert not carries_leaves(tree)
    assert not carries_leaves({})
    # a leaf of another float type keeps the vector too: the moments
    # are float32 and would be rounded to it
    big = {"w": jax.ShapeDtypeStruct((2**21,), np.float32)}
    assert carries_leaves(big)
    assert not carries_leaves(
        {**big, "h": jax.ShapeDtypeStruct((2**21,), jnp.bfloat16)}
    )


@pytest.mark.parametrize(
    "configuration, leaves",
    [
        ("lm-dense-160m", 11),
        ("ouro-2.6b", 16),
        ("deepseek-v2-lite", 27),
        ("kimi-linear-48b-a3b", 86),
        ("lfm2-24b-a2b", 33),
        ("laguna-xs2", 41),
        ("qwen3-next-80b-a3b", 35),
    ],
)
def test_every_lm_configuration_of_the_benchmark_carries_leaves(
    configuration, leaves
):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.harness.manifest import load_module

    zoo = load_module(
        os.path.join(ROOT, "benchmark", "configs", configuration, "zoo.py")
    )
    model = spec_from_module(zoo).model
    shapes = transformer_lm.init_params(_ShapesOnly(), model.cfg)
    sizes = [int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)]
    assert len(sizes) == leaves
    assert sum(sizes) / len(sizes) >= 7.0e6  # ISSUE 53: 7.0 to 19.8 M
    assert carries_leaves(shapes)


# -- (c) the programs --------------------------------------------------------


def _window_of_today(worker):
    """`_local_step_core` and the window as they stood before the
    leaves carry (PR 52), written out: the vector cut inside the
    differentiated function, the optimizer on the vector."""
    spec, unravel, tx = worker._spec, worker._unravel, worker._spec.optimizer()

    def step(flat, opt_state, aux, features, labels):
        def loss_fn(flat):
            params = unravel(flat)
            variables = {"params": params, **aux}
            outputs, new_aux = worker._apply_model(
                variables, features, None, train=True
            )
            return spec.loss(outputs, labels), new_aux

        (loss, new_aux), grad = jax.value_and_grad(loss_fn, has_aux=True)(
            flat
        )
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grad, opt_state, flat)
            flat = flat + updates
        return flat, opt_state, new_aux if new_aux else aux, loss

    def window(flat, opt_state, aux, features, labels):
        def body(carry, xs):
            flat, opt_state, aux = carry
            f, l = xs
            flat, opt_state, aux, loss = step(flat, opt_state, aux, f, l)
            return (flat, opt_state, aux), loss

        (flat, opt_state, aux), losses = jax.lax.scan(
            body, (flat, opt_state, aux), (features, labels), unroll=WINDOW
        )
        return flat, opt_state, aux, losses[-1]

    return window


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _carried(jaxpr):
    """Shapes of the float arrays the window's one scan carries."""
    (scan,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    first = scan.params["num_consts"]
    return [
        v.aval.shape
        for v in scan.invars[first:first + scan.params["num_carry"]]
        if v.aval.dtype == np.float32
    ]


def test_under_the_constant_the_window_is_today_s_program():
    worker = lm_worker()
    features, labels = batches()
    n = worker._flat.size
    state = worker._spec.optimizer().init(worker._flat)
    assert not carries_leaves(worker._template)
    args = (worker._flat, state, worker._aux, features, labels)
    # the CPU unrolls the scan; rolled, as the chip runs it, the carry
    # can be read
    got = jax.make_jaxpr(worker._build_local_window_fn())(*args)
    today = jax.jit(_window_of_today(worker), donate_argnums=(0, 1))
    assert str(got) == str(jax.make_jaxpr(today)(*args))
    rolled = jax.make_jaxpr(_rolled(worker))(*args)
    assert _carried(rolled.jaxpr.eqns[0].params["jaxpr"].jaxpr) == [(n,)] * 3


def _rolled(worker):
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"  # the builder's unroll only
    try:
        return worker._build_local_window_fn()
    finally:
        jax.default_backend = real


def test_over_the_constant_the_loop_carries_leaves_and_joins_nothing(
    leaves_carry,
):
    worker = lm_worker()
    features, labels = batches()
    n = worker._flat.size
    shapes = [a.shape for a in jax.tree_util.tree_leaves(worker._template)]
    tree = jax.tree_util.tree_map(jnp.asarray, worker._template)
    state = worker._spec.optimizer().init(tree)
    jaxpr = jax.make_jaxpr(_rolled(worker))(
        tree, state, worker._aux, features, labels
    )
    inner = jaxpr.jaxpr.eqns[0].params["jaxpr"].jaxpr
    assert _carried(inner) == shapes * 3  # the model, Adam's two moments
    whole = [
        e.primitive.name for e in _equations(inner)
        if e.primitive.name in ("concatenate", "dynamic_update_slice")
        and any(int(np.prod(v.aval.shape)) >= n for v in e.outvars)
    ]
    assert whole == []
    # no array of the whole model's size anywhere in the program
    sizes = {
        int(np.prod(v.aval.shape))
        for e in _equations(inner) for v in e.outvars
        if hasattr(v.aval, "shape")
    }
    assert n not in sizes


# -- (d) the counter ---------------------------------------------------------


def _programs():
    return {
        s["args"]["program"]: s["args"]
        for s in trace.RECORDER.snapshot() if s["name"] == "setup.program"
    }


@pytest.mark.parametrize("carry", ["flat", "leaves"])
def test_the_window_s_setup_span_says_what_the_loop_carries(
    monkeypatch, carry
):
    if carry == "leaves":
        monkeypatch.setattr(worker_module, "CARRY_LEAVES_MIN_MEAN_ELEMENTS", 1)
    trace.RECORDER.clear()
    worker = lm_worker()
    features, labels = batches()
    leaves = len(jax.tree_util.tree_leaves(worker._template))
    worker._local_window_fn = worker._build_local_window_fn()
    for _ in range(2):  # a program's span is its first call's
        state = worker._spec.optimizer().init(worker._flat)
        worker._flat, *_ = worker._run_window(
            worker._flat, state, worker._aux, features, labels
        )
    programs = _programs()
    window = programs["jit_window"]
    assert window["carry"] == carry
    # the model, Adam's two moments and its count
    assert window["carried"] == (3 * leaves + 1 if carry == "leaves" else 4)
    assert window["compiles"] >= 0
    assert ("jit_cut" in programs, "jit_join" in programs) == (
        (True, True) if carry == "leaves" else (False, False)
    )
    for name in ("jit_cut", "jit_join"):
        assert "carry" not in programs.get(name, {})


@pytest.mark.parametrize("zoo", ["dense", "routed", "routed-padded"])
def test_the_window_s_setup_span_says_which_expert_widths_it_pads(
    monkeypatch, zoo
):
    """`expert_widths`: the distinct (stated, run) widths of the expert
    layers the window's trace padded (`parallel/moe.run_width`); none
    for a model without expert layers, none where the rule leaves the
    width alone (the fixture's 20 columns lie inside one tile)."""
    from elasticdl_tpu.parallel import moe
    from tests.fixtures import routed_lm_tiny

    if zoo == "routed-padded":
        monkeypatch.setattr(moe, "WIDTH_TILE", 8)
    trace.RECORDER.clear()
    worker = lm_worker(
        zoo=transformer_lm_zoo if zoo == "dense" else routed_lm_tiny
    )
    features, labels = batches()
    worker._local_window_fn = worker._build_local_window_fn()
    state = worker._spec.optimizer().init(worker._flat)
    worker._run_window(worker._flat, state, worker._aux, features % 64, labels % 64)
    programs = _programs()
    assert programs["jit_window"]["expert_widths"] == (
        [(20, 24)] if zoo == "routed-padded" else []
    )
    assert all(
        "expert_widths" not in args
        for name, args in programs.items() if name != "jit_window"
    )


@pytest.mark.parametrize("zoo", ["dense", "grouped", "grouped-on-xla"])
def test_the_window_s_setup_span_says_which_groups_the_kernels_read_in_place(
    monkeypatch, zoo
):
    """`kv_groups`: the distinct (query heads, key-value heads) of the
    attention calls with fewer key-value heads that the window's trace
    handed to the Pallas kernels, k and v as they lay
    (`ops/flash_attention.groups_traced`). None for equal heads, and
    none where XLA's path took the call and widened it. The fixture has
    4 query heads on 2; the kernels run in the interpreter here, at the
    one tile that 128 tokens are."""
    import functools

    from elasticdl_tpu.ops import flash_attention as fa
    from tests.fixtures import shortconv_lm_tiny

    if zoo != "grouped-on-xla":  # the dispatcher on a TPU, told to engage
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv("EDL_TPU_FLASH", "1")
        monkeypatch.setattr(
            fa, "flash_attention",
            functools.partial(fa.flash_attention, interpret=True),
        )
    trace.RECORDER.clear()
    worker = lm_worker(
        zoo=transformer_lm_zoo if zoo == "dense" else shortconv_lm_tiny
    )
    tokens = np.random.default_rng(0).integers(
        0, 64, (WINDOW, 2, fa.BLOCK + 1)
    ).astype(np.int32)
    worker._local_window_fn = worker._build_local_window_fn()
    state = worker._spec.optimizer().init(worker._flat)
    *_, loss = worker._run_window(
        worker._flat, state, worker._aux, tokens[..., :-1], tokens[..., 1:]
    )
    assert np.isfinite(np.asarray(loss)).all()
    programs = _programs()
    assert programs["jit_window"]["kv_groups"] == (
        [(4, 2)] if zoo == "grouped" else []
    )
    assert all(
        "kv_groups" not in args
        for name, args in programs.items() if name != "jit_window"
    )


# -- (e) donation and the warm-up --------------------------------------------


def test_the_vectors_go_in_donated_and_come_back_in_their_form(leaves_carry):
    worker = lm_worker()
    features, labels = batches()
    flat = jnp.copy(worker._flat)
    state = worker._spec.optimizer().init(flat)
    given = [flat] + [
        a for a in jax.tree_util.tree_leaves(state) if a.shape == flat.shape
    ]
    worker._local_window_fn = worker._build_local_window_fn()
    out_flat, out_state, _aux, loss = worker._run_window(
        flat, state, worker._aux, features, labels
    )
    assert np.isfinite(float(loss))
    assert all(a.is_deleted() for a in given)  # as a donation leaves them
    assert (out_flat.shape, out_flat.dtype) == (flat.shape, flat.dtype)
    # the next window takes the moments as the last one left them, and
    # gives up the model's vector and the trees, in place
    trees = jax.tree_util.tree_leaves(out_state)
    again_flat, again_state, _aux, _loss = worker._run_window(
        out_flat, out_state, worker._aux, features, labels
    )
    assert out_flat.is_deleted() and all(a.is_deleted() for a in trees)
    assert jax.tree_util.tree_structure(again_state) == (
        jax.tree_util.tree_structure(out_state)
    )
    # and a per-step program gets the form `tx.init(flat)` gave
    vectors = worker._vector_state(again_state, again_flat)
    assert jax.tree_util.tree_structure(vectors) == (
        jax.tree_util.tree_structure(state)
    )
    for got, was in zip(
        [again_flat] + jax.tree_util.tree_leaves(vectors),
        [flat] + jax.tree_util.tree_leaves(state),
    ):
        assert (got.shape, got.dtype) == (was.shape, was.dtype)
        assert not got.is_deleted()
    assert worker._vector_state(vectors, again_flat) is vectors


def test_the_warm_up_runs_the_leaves_carry_on_copies(leaves_carry):
    worker = lm_worker()
    features, labels = batches()
    before = np.asarray(worker._flat)
    trace.RECORDER.clear()
    worker.warmup_local_window(features, labels)
    assert worker._window_cut_fn is not None  # the leaves carry ran
    assert not worker._flat.is_deleted()
    np.testing.assert_array_equal(np.asarray(worker._flat), before)
    assert "jit_window" not in _programs()  # a warm-up times nothing


# -- (f) through the syncs ---------------------------------------------------


def _job(tmp_path, monkeypatch, constant):
    """One in-process worker, two windows of four steps, a ragged tail
    of one step on the per-step program, and their syncs: the deltas as
    the master was sent them."""
    import random

    monkeypatch.setattr(
        worker_module, "CARRY_LEAVES_MIN_MEAN_ELEMENTS", constant
    )
    random.seed(7)  # one task order for both runs
    path = str(tmp_path / f"train-{constant}.rio")
    write_linear_records(path, 144, noise=0.05, seed=3)
    dispatcher = TaskDispatcher({path: 144}, {}, {}, 64, 1)
    spec = spec_from_module(linear_module, optimizer=lambda: optax.adam(0.05))
    servicer = MasterServicer(
        grads_to_wait=1,
        optimizer=PSOptimizer(spec.optimizer()),
        task_dispatcher=dispatcher,
    )
    deltas = []

    def keep(req):
        deltas.append(np.array(codec.delta_to_f32(req["delta_flat"])))
        return req

    trace.RECORDER.clear()
    worker = Worker(
        0, InProcessMaster(servicer, intercept={"ReportLocalUpdate": keep}),
        spec, minibatch_size=16, local_updates=WINDOW,
    )
    worker.run()
    assert dispatcher.finished()
    params, _aux, version = servicer.get_params_copy()
    return deltas, codec.ravel_np(params), version, _programs()["jit_window"]


def test_a_worker_s_syncs_on_leaves_push_the_flat_path_s_deltas(
    tmp_path, monkeypatch
):
    flat = _job(tmp_path, monkeypatch, 2**20)
    leaves = _job(tmp_path, monkeypatch, 1)
    assert (flat[3]["carry"], leaves[3]["carry"]) == ("flat", "leaves")
    assert flat[2] == leaves[2] == 9  # two windows of four steps and a tail
    assert len(flat[0]) == len(leaves[0]) == 3
    for got, want in zip(leaves[0], flat[0]):
        assert np.abs(want).max() > 0
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(leaves[1], flat[1], rtol=1e-5, atol=1e-7)
