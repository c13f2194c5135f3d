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
                    vec = np.asarray(_read(s, "get_model_flat"))
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


# -- the model goes down as the leaves it lies in (PR 34) --------------------


_MODEL_LEAVES = 161  # ResNet-50's count


def _model(rng, elems=2000):
    """161 leaves off the 64-byte grid: matrices, vectors, a scalar."""
    tree = {
        f"l{i:03d}": rng.standard_normal(
            (elems + i % 7, 3) if i % 5 else (elems + i,)
        ).astype(np.float32)
        for i in range(_MODEL_LEAVES - 1)
    }
    tree["temp"] = np.asarray(rng.standard_normal(), dtype=np.float32)
    return tree


def _encode_spans(s):
    spans = []

    def sink(name, begin, dur, args, ctx=None):
        if name == "model_encode":
            spans.append(dict(args))

    s.timers = PhaseTimers(sink=sink)
    return spans


def _pull(s, **req):
    return s.get_model({"method": MethodType.MINIMUM, "flat": True, **req})


def _model_bytes(s):
    return 4 * _size(s.get_params_copy()[0])


@pytest.fixture
def lend_from_1mb(monkeypatch):
    """`transport.KEEP_FRAME_BYTES` is the chip's host's (32 MiB); the
    rule is the same from 1 MiB on, with a model a test can afford."""
    from elasticdl_tpu.rpc import transport

    monkeypatch.setattr(transport, "KEEP_FRAME_BYTES", 1 << 20)


def _peak_while(fn):
    """(what `fn` returned, the most numpy and Python held over what
    they held before, in bytes, while it ran)."""
    import tracemalloc

    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        out = fn()
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    return out, peak - before


@pytest.mark.parametrize(
    "reader", ["get_model", "gradient_response", "stale_rejection"]
)
def test_read_only_leaves_go_down_by_view(reader, lend_from_1mb):
    """What `PSOptimizer.step` leaves behind is read-only and replaced,
    never written: every leaf enters the frame where it lies, nothing
    of the model's size is allocated for the vector or for the frame's
    parts, and the span says so."""
    rng = np.random.default_rng(34)
    s = _servicer("after_gradient", _model(rng))
    spans = _encode_spans(s)
    grads = jax.tree_util.tree_map(np.ones_like, s.get_params_copy()[0])
    nbytes = _model_bytes(s)
    assert nbytes > 3 << 20

    def read():
        if reader == "get_model":
            return _pull(s)
        version = s.version if reader == "gradient_response" else -5
        return s.report_gradient(
            {"version": version, "gradient": grads, "return_model": True}
        )

    if reader == "get_model":
        from elasticdl_tpu.common import messages

        def read_and_pack():
            resp = read()
            return resp, messages.pack_parts(resp)

        (resp, payload), peak = _peak_while(read_and_pack)
        assert peak < nbytes // 8
        assert len(payload) > nbytes and not payload.joined
    else:
        resp = read()
    assert resp.get("accepted", True) == (reader != "stale_rejection")
    vec = resp["params_flat"]
    assert isinstance(vec, codec.LeafVector)
    held = jax.tree_util.tree_leaves(s._params)
    assert len(vec.pieces) == len(held) == _MODEL_LEAVES
    for piece, leaf in zip(vec.pieces, held):
        assert not leaf.flags.writeable
        assert piece.size == leaf.size and (
            leaf.size == 0 or np.shares_memory(piece, leaf)
        )
    np.testing.assert_array_equal(vec, codec.ravel_np(s._params))
    if reader != "stale_rejection":  # which records no span, as before
        (sent,) = spans
        assert (sent["by_view"], sent["copied"], sent["lent"]) == (
            _MODEL_LEAVES, 0, False,
        )
    # the next step replaces the leaves: what went down stays as it was
    kept = np.array(vec)
    s.report_gradient({"version": s.version, "gradient": grads})
    np.testing.assert_array_equal(vec, kept)
    assert not np.array_equal(kept, codec.ravel_np(s._params))


def test_writeable_leaves_are_copied_into_memory_the_servicer_lends(
    lend_from_1mb,
):
    """A model `_add_delta` writes in place is copied under the lock,
    into the memory the first response lay in once that is dropped; a
    response still held keeps its memory, and the next gets its own."""
    rng = np.random.default_rng(35)
    s = _servicer("init_params", _model(rng))
    spans = _encode_spans(s)
    nbytes = _model_bytes(s)

    def where(vec):
        return vec.pieces[0].__array_interface__["data"][0]

    first = _pull(s)["params_flat"]
    want = codec.ravel_np(s._params)
    np.testing.assert_array_equal(first, want)
    for piece in first.pieces:
        for leaf in jax.tree_util.tree_leaves(s._params):
            assert not np.shares_memory(piece, leaf)
    at = where(first)
    del first, piece
    # dropped: the second pull lies where the first did, and maps
    # nothing of the model's size
    resp, peak = _peak_while(lambda: _pull(s))
    assert peak < nbytes // 8
    second = resp.pop("params_flat")
    assert where(second) == at
    # held: the third gets memory of its own, and the update between
    # leaves the second as it was
    _update(s, np.ones(nbytes // 4, np.float32))
    third = _pull(s)["params_flat"]
    assert where(third) != at
    np.testing.assert_array_equal(second, want)
    np.testing.assert_array_equal(third, want + np.float32(1.0))
    assert [
        (a["by_view"], a["copied"], a["lent"]) for a in spans
    ] == [
        (0, _MODEL_LEAVES, False),
        (0, _MODEL_LEAVES, True),
        (0, _MODEL_LEAVES, False),
    ]


def test_each_leaf_goes_by_what_can_be_seen_of_it(lend_from_1mb):
    """One algorithm over a mixed tree: read-only float32 by view, a
    writeable leaf and one of another dtype copied, in order."""
    rng = np.random.default_rng(36)
    tree = _model(rng)
    s = _servicer("init_params", tree)
    spans = _encode_spans(s)
    leaves = jax.tree_util.tree_leaves(s._params)
    for leaf in leaves[::2]:
        leaf.flags.writeable = False
    s._params["l003"] = np.arange(7, dtype=np.int64)
    s._params["l003"].flags.writeable = False  # read-only, but not float32
    vec = _pull(s)["params_flat"]
    leaves = jax.tree_util.tree_leaves(s._params)
    by_view = [
        leaf.size and np.shares_memory(piece, leaf)
        for piece, leaf in zip(vec.pieces, leaves)
    ]
    want_by_view = [
        not leaf.flags.writeable and leaf.dtype == np.float32
        for leaf in leaves
    ]
    assert by_view == want_by_view and 70 < sum(by_view) < 90
    np.testing.assert_array_equal(vec, codec.ravel_np(s._params))
    (sent,) = spans
    assert sent["by_view"] == sum(by_view)
    assert sent["copied"] == _MODEL_LEAVES - sum(by_view)


def test_a_narrowed_model_is_a_copy_and_says_so():
    s = _servicer("after_gradient", _tree(np.random.default_rng(37)))
    spans = _encode_spans(s)
    grads = jax.tree_util.tree_map(np.ones_like, s.get_params_copy()[0])
    resp = s.report_gradient(
        {"version": s.version, "gradient": grads, "return_model": True,
         "model_dtype": "bfloat16"}
    )
    vec = resp["params_flat"]
    assert isinstance(vec, np.ndarray) and vec.dtype == codec._BFLOAT16
    np.testing.assert_array_equal(
        vec, codec.ravel_np(s._params).astype(codec._BFLOAT16)
    )
    assert spans == [{
        "kind": "gradient", "version": 2, "by_view": 0, "copied": 3,
        "lent": False,
    }]


@pytest.mark.parametrize("carrier", ["uds", "grpc", "inproc"])
def test_a_model_pulled_while_deltas_land_arrives_whole(
    carrier, monkeypatch, tmp_path, lend_from_1mb
):
    """The torn-read guard, over a real link. Every delta adds one to
    every element, in place, so a model received whole is constant and
    says its version. Two pullers keep two responses in flight: the
    copy is made under the lock, and the memory lent to one response is
    not handed to the next while the first is still on its way."""
    from elasticdl_tpu.common.constants import ENV_TRANSPORT, ENV_UDS_DIR
    from elasticdl_tpu.rpc.client import RpcClient
    from elasticdl_tpu.rpc.server import RpcServer

    monkeypatch.setenv(ENV_TRANSPORT, carrier)
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    tree = {
        "a": np.zeros((600, 512), np.float32),
        "b": {"c": np.zeros((8, 128, 128), np.float32)},
        "d": np.zeros((), np.float32),
    }
    s = _servicer("init_params", tree)
    spans = _encode_spans(s)
    n = _size(tree)
    assert 4 * n > 1 << 20
    one = np.ones(n, np.float32)
    server = RpcServer(s.handlers(), port=0)
    server.start()
    stop = time.monotonic() + 1.5
    torn, errors, pulls = [], [], []

    def write():
        try:
            while time.monotonic() < stop:
                s.report_local_update(
                    {"delta_flat": one, "steps": 1, "base_version": 0}
                )
        except Exception as exc:  # pragma: no cover - the assertion below
            errors.append(exc)

    def pull():
        client = RpcClient(f"localhost:{server.port}")
        try:
            assert (
                client._transport.name if client._transport else "grpc"
            ) == carrier
            while time.monotonic() < stop:
                resp = client.call(
                    "GetModel",
                    {"method": MethodType.MINIMUM, "flat": True},
                    timeout=60,
                )
                vec = resp["params_flat"]
                low, high = float(vec.min()), float(vec.max())
                if not (vec.shape == (n,) and low == high == resp["version"]):
                    torn.append((resp["version"], low, high))
                pulls.append(resp["version"])
        except Exception as exc:  # pragma: no cover
            errors.append(exc)
        finally:
            client.close()

    threads = [threading.Thread(target=write) for _ in range(2)] + [
        threading.Thread(target=pull) for _ in range(2)
    ]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
        server.stop()
    assert not any(t.is_alive() for t in threads)
    assert not errors and not torn
    assert len(pulls) > 4 and max(pulls) > 0
    assert all(a["copied"] == 3 and a["by_view"] == 0 for a in spans)
    # both ways: lent where the last response had left, fresh where
    # the other puller's was still in flight
    assert any(a["lent"] for a in spans)
