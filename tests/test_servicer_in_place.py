"""`report_local_update` adds a window's delta into the model the
master holds (PR 28): the result is bit for bit what the rebuilt tree
`p + scale * d` gave, the master owns what it adds into, a leaf it may
not write is replaced once and added into from then on, no reader is
handed a leaf, and the `apply` span says how many leaves were added in
place."""

import copy
import sys
import threading
import time

import jax
import numpy as np
import optax
import pytest

from elasticdl_tpu.common import codec
from elasticdl_tpu.common.messages import MethodType
from elasticdl_tpu.common.timing import PhaseTimers
from elasticdl_tpu.master.ps_optimizer import PSOptimizer
from elasticdl_tpu.master.servicer import MasterServicer


def _tree(rng):
    """A 2-D table, a stacked 3-D layer leaf and a scalar-like one."""
    return {
        "emb": rng.standard_normal((7, 5)).astype(np.float32),
        "layers": {"w": rng.standard_normal((3, 4, 5)).astype(np.float32)},
        "temp": np.asarray(rng.standard_normal(), dtype=np.float32),
    }


def _size(tree):
    return sum(np.asarray(x).size for x in jax.tree_util.tree_leaves(tree))


def _delta(rng, n):
    return rng.standard_normal(n).astype(np.float32)


def _reference_add(params, vec, scale):
    """The expression the master evaluated until PR 28: a new tree."""
    return jax.tree_util.tree_map(
        lambda p, d: p + scale * np.asarray(d, dtype=np.float32),
        params,
        codec.unravel_np(vec, params),
    )


def _assert_trees_equal(got, want):
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype == np.float32
        assert g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def _read_only(tree):
    """The tree as a remote peer's frame delivers it: views of bytes."""
    return jax.tree_util.tree_map(
        lambda a: np.frombuffer(a.tobytes(), dtype=a.dtype).reshape(a.shape),
        tree,
    )


def _servicer(route, tree, **kwargs):
    """A master that adopted `tree` by `route`; `after_gradient` also
    takes one per-step report, which leaves `PSOptimizer.step`'s
    read-only leaves behind."""
    if route == "init_params":
        return MasterServicer(grads_to_wait=1, init_params=tree, **kwargs)
    s = MasterServicer(
        grads_to_wait=1, optimizer=PSOptimizer(optax.sgd(1.0)), **kwargs
    )
    if route == "report_variable":
        s.report_variable({"params": tree})
    elif route == "frombuffer":
        s.report_variable({"params": _read_only(tree)})
    elif route == "after_gradient":
        s.report_variable({"params": tree})
        grads = jax.tree_util.tree_map(np.ones_like, tree)
        assert s.report_gradient({"version": 0, "gradient": grads})["accepted"]
    else:
        raise ValueError(route)
    return s


def _update(s, vec, steps=1, base_version=None, **req):
    base = s.version if base_version is None else base_version
    return s.report_local_update(
        {"delta_flat": vec, "steps": steps, "base_version": base, **req}
    )


def _apply_spans(s):
    """Collect the `apply` spans' arguments from here on."""
    spans = []

    def sink(name, begin, dur, args, ctx=None):
        if name == "apply":
            spans.append(dict(args))

    s.timers = PhaseTimers(sink=sink)
    return spans


# -- (a) bit for bit the rebuilt tree ---------------------------------------


@pytest.mark.parametrize(
    "staleness_window, plan",
    [
        # (steps, base_version) per update; None = the current version
        (0, [(3, None), (2, None), (2, None)]),
        # window 2: the second delta is 3 versions stale (scale 2/3),
        # the third 5 (scale 0.4); the first is applied whole
        (2, [(3, None), (2, 0), (2, 0)]),
    ],
    ids=["scale_one", "downweighted"],
)
def test_three_updates_equal_the_rebuilt_tree(staleness_window, plan):
    rng = np.random.default_rng(28)
    tree = _tree(rng)
    s = _servicer(
        "init_params", tree, staleness_window=staleness_window
    )
    want = copy.deepcopy(tree)
    version, scales = 0, []
    for steps, base in plan:
        vec = _delta(rng, _size(tree))
        sent = vec.copy()
        sent.flags.writeable = False  # the request's buffer is only read
        scale = 1.0
        staleness = version - (version if base is None else base)
        if staleness_window and staleness > staleness_window:
            scale = staleness_window / float(staleness)
        scales.append(scale)
        want = _reference_add(want, vec, scale)
        _update(s, sent, steps=steps, base_version=base)
        version += steps
        np.testing.assert_array_equal(sent, vec)
    assert (min(scales) < 1.0) == bool(staleness_window)
    got, _, got_version = s.get_params_copy()
    assert got_version == version
    _assert_trees_equal(got, want)


# -- (b) the master took its own arrays -------------------------------------


@pytest.mark.parametrize("route", ["init_params", "report_variable"])
def test_the_callers_arrays_are_left_alone(route):
    rng = np.random.default_rng(1)
    tree = _tree(rng)
    before = copy.deepcopy(tree)
    s = _servicer(route, tree)
    want = before
    for _ in range(2):
        vec = _delta(rng, _size(tree))
        want = _reference_add(want, vec, 1.0)
        _update(s, vec)
    _assert_trees_equal(tree, before)
    _assert_trees_equal(s.get_params_copy()[0], want)


# -- (c) a tree it may not write, then a local update -----------------------


@pytest.mark.parametrize("route", ["frombuffer", "after_gradient"])
def test_a_read_only_tree_takes_a_following_local_update(route):
    rng = np.random.default_rng(2)
    s = _servicer(route, _tree(rng))
    want, _, _ = s.get_params_copy()
    for _ in range(2):
        vec = _delta(rng, _size(want))
        want = _reference_add(want, vec, 1.0)
        _update(s, vec)
    _assert_trees_equal(s.get_params_copy()[0], want)


# -- (d) what a reader was handed does not move -----------------------------


class _EveryVersionCheckpoints:
    def __init__(self):
        self.saved = []

    def crossed(self, prev, version):
        return True

    def save(self, params, version, aux=None, opt_state=None):
        self.saved.append(params)


def _read(s, reader):
    if reader == "get_params_copy":
        return s.get_params_copy()[0]
    if reader == "get_model_tree":
        return s.get_model({"method": MethodType.MINIMUM})["params"]
    if reader == "get_model_flat":
        return s.get_model({"method": MethodType.MINIMUM, "flat": True})[
            "params_flat"
        ]
    if reader == "get_model_fixed":
        return s.get_model(
            {"method": MethodType.FIXED, "version": s.version}
        )["params"]
    if reader == "update_response":
        vec = np.ones(_size(s.get_params_copy()[0]), np.float32)
        return _update(s, vec, want_model=True)["params_flat"]
    if reader == "duplicate_response":
        vec = np.ones(_size(s.get_params_copy()[0]), np.float32)
        _update(s, vec, report_key="k")
        resp = _update(s, vec, report_key="k")
        assert resp["duplicate"]
        return resp["params_flat"]
    if reader == "checkpoint_snapshot":
        vec = np.ones(_size(s.get_params_copy()[0]), np.float32)
        _update(s, vec)
        return s._checkpoint_service.saved[-1]
    raise ValueError(reader)


@pytest.mark.parametrize(
    "reader",
    [
        "get_params_copy",
        "get_model_tree",
        "get_model_flat",
        "get_model_fixed",
        "update_response",
        "duplicate_response",
        "checkpoint_snapshot",
    ],
)
def test_what_a_reader_was_handed_survives_the_next_update(reader):
    rng = np.random.default_rng(3)
    s = _servicer(
        "init_params",
        _tree(rng),
        checkpoint_service=_EveryVersionCheckpoints(),
    )
    _update(s, _delta(rng, _size(s.get_params_copy()[0])))
    handed = _read(s, reader)
    kept = copy.deepcopy(handed)
    model_then = s.get_params_copy()[0]
    _update(s, _delta(rng, _size(model_then)))
    for a, b in zip(
        jax.tree_util.tree_leaves(handed), jax.tree_util.tree_leaves(kept)
    ):
        np.testing.assert_array_equal(a, b)
    # and the update did land
    assert not np.array_equal(
        s.get_params_copy()[0]["emb"], model_then["emb"]
    )


# -- (e) the span says whether the add was in place -------------------------


@pytest.mark.parametrize(
    "route, first",
    [
        ("init_params", 3),
        ("report_variable", 3),
        ("frombuffer", 3),
        ("after_gradient", 0),
    ],
)
def test_the_apply_span_counts_the_leaves_added_in_place(route, first):
    rng = np.random.default_rng(4)
    s = _servicer(route, _tree(rng))
    spans = _apply_spans(s)
    n = _size(s.get_params_copy()[0])
    for _ in range(3):
        _update(s, _delta(rng, n))
    assert [a["kind"] for a in spans] == ["local_update"] * 3
    assert [a["leaves"] for a in spans] == [3, 3, 3]
    assert [a["in_place"] for a in spans] == [first, 3, 3]


def test_a_per_step_apply_span_carries_no_leaf_count():
    s = _servicer("report_variable", _tree(np.random.default_rng(5)))
    spans = _apply_spans(s)
    grads = jax.tree_util.tree_map(np.ones_like, s.get_params_copy()[0])
    s.report_gradient({"version": 0, "gradient": grads})
    assert [sorted(a) for a in spans] == [["kind", "version"]]


# -- (f) a duplicate adds nothing -------------------------------------------


@pytest.mark.parametrize("updates_between", [0, 2])
def test_a_duplicate_report_key_adds_nothing_and_bumps_nothing(
    updates_between,
):
    rng = np.random.default_rng(6)
    s = _servicer("init_params", _tree(rng))
    spans = _apply_spans(s)
    n = _size(s.get_params_copy()[0])
    vec = _delta(rng, n)
    _update(s, vec, steps=2, report_key="w0")
    for i in range(updates_between):
        _update(s, _delta(rng, n), report_key=f"other{i}")
    before, _, version = s.get_params_copy()
    stats = s.get_sched_stats({})["exactness"]
    resp = _update(s, vec, steps=2, base_version=0, report_key="w0")
    assert resp["duplicate"] and resp["version"] == version
    after, _, version_after = s.get_params_copy()
    assert version_after == version == 2 + updates_between
    _assert_trees_equal(after, before)
    np.testing.assert_array_equal(
        resp["params_flat"], codec.ravel_np(before)
    )
    now = s.get_sched_stats({})["exactness"]
    assert now["applied_update_steps"] == stats["applied_update_steps"]
    assert now["version"] == now["init_version"] + now["applied_update_steps"]
    assert now["duplicate_local_updates"] == 1
    assert len(spans) == 1 + updates_between  # the duplicate applied nothing


# -- readers against writers -------------------------------------------------


def test_no_reader_sees_a_delta_half_added():
    """Every delta adds the same number to every element, so a model read
    whole is constant over all its leaves; a reader that was handed a
    leaf, or read outside the lock, would see two values."""
    tree = {
        "a": np.zeros((512, 512), np.float32),
        "b": {"c": np.zeros((8, 128, 128), np.float32)},
        "d": np.zeros((), np.float32),
    }
    s = _servicer("init_params", tree)
    n = _size(tree)
    one = np.ones(n, np.float32)
    stop = time.monotonic() + 1.5
    torn, errors = [], []

    def write():
        try:
            while time.monotonic() < stop:
                s.report_local_update(
                    {"delta_flat": one, "steps": 1, "base_version": 0}
                )
        except Exception as exc:  # pragma: no cover - the assertion below
            errors.append(exc)

    def read(flat):
        try:
            while time.monotonic() < stop:
                if flat:
                    vec = _read(s, "get_model_flat")
                else:
                    vec = codec.ravel_np(s.get_params_copy()[0])
                if vec.min() != vec.max():
                    torn.append((float(vec.min()), float(vec.max())))
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=write) for _ in range(4)] + [
        threading.Thread(target=read, args=(i % 2 == 0,)) for i in range(8)
    ]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not torn
    model, _, version = s.get_params_copy()
    assert version > 0
    for leaf in jax.tree_util.tree_leaves(model):
        np.testing.assert_array_equal(leaf, np.float32(version))
