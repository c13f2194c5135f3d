"""Wire-contract round-trips: every request dataclass in
common/messages.py survives to_wire -> codec -> from_wire, and the
forward-compat rule (from_wire drops unknown keys) holds for all of
them. Complements the rpc-conformance lint, which proves the call
sites and handlers agree with these schemas statically."""

import dataclasses

import numpy as np
import pytest

from elasticdl_tpu.common import codec
from elasticdl_tpu.common import messages as M
from elasticdl_tpu.common.messages import WIRE_SCHEMAS

#: representative non-default values by field type/name, so the round
#: trip exercises real payloads, not just empty defaults
_SAMPLES = {
    int: 7,
    str: "sample",
    bool: True,
}


def _populate(cls):
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name in ("gradient", "params", "aux", "aux_state"):
            kwargs[f.name] = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
        elif f.name in ("grad", "delta", "delta_flat", "gradient_flat", "vec"):
            kwargs[f.name] = np.linspace(0, 1, 5, dtype=np.float32)
        elif f.name in ("ids",):
            kwargs[f.name] = np.asarray([1, 4, 9], dtype=np.int64)
        elif f.name in ("values",):
            kwargs[f.name] = np.ones((3, 4), dtype=np.float32)
        elif f.name == "metrics":
            kwargs[f.name] = {"accuracy": 0.5}
        elif f.name == "versions":
            kwargs[f.name] = [3, 4]
        elif f.name == "model_dtype":
            kwargs[f.name] = "bfloat16"
        elif f.type in ("int", int):
            kwargs[f.name] = _SAMPLES[int]
        elif f.type in ("str", str):
            kwargs[f.name] = _SAMPLES[str]
        elif f.type in ("bool", bool):
            kwargs[f.name] = _SAMPLES[bool]
    return cls(**kwargs)


def _assert_value_equal(a, b, where):
    if isinstance(a, np.ndarray):
        np.testing.assert_array_equal(a, b, err_msg=where)
    elif isinstance(a, dict):
        assert set(a) == set(b), where
        for k in a:
            _assert_value_equal(a[k], b[k], f"{where}[{k}]")
    else:
        assert a == b, where


@pytest.mark.parametrize(
    "method", sorted(WIRE_SCHEMAS), ids=sorted(WIRE_SCHEMAS)
)
def test_request_roundtrip_defaults(method):
    cls = WIRE_SCHEMAS[method]
    req = cls()
    back = cls.from_wire(codec.loads(codec.dumps(req.to_wire())))
    assert back == req


@pytest.mark.parametrize(
    "method", sorted(WIRE_SCHEMAS), ids=sorted(WIRE_SCHEMAS)
)
def test_request_roundtrip_populated(method):
    cls = WIRE_SCHEMAS[method]
    req = _populate(cls)
    back = cls.from_wire(codec.loads(codec.dumps(req.to_wire())))
    for f in dataclasses.fields(cls):
        _assert_value_equal(
            getattr(req, f.name), getattr(back, f.name), f"{method}.{f.name}"
        )


@pytest.mark.parametrize(
    "method", sorted(WIRE_SCHEMAS), ids=sorted(WIRE_SCHEMAS)
)
def test_request_ignores_unknown_keys(method):
    """A newer client may send fields an older server doesn't know;
    from_wire must drop them instead of raising TypeError."""
    cls = WIRE_SCHEMAS[method]
    wire = cls().to_wire()
    wire["__from_the_future__"] = 1
    assert cls.from_wire(wire) == cls()


def test_task_and_model_roundtrip():
    task = M.Task(task_id=3, shard_file_name="f.rio", start=10, end=20,
                  type=M.TaskType.TRAINING, model_version=5)
    assert M.Task.from_wire(codec.loads(codec.dumps(task.to_wire()))) == task

    model = M.Model(
        version=9,
        params={"w": np.ones((2, 2), dtype=np.float32)},
        aux=None,
    )
    back = M.Model.from_wire(codec.loads(codec.dumps(model.to_wire())))
    assert back.version == 9 and back.aux is None
    np.testing.assert_array_equal(back.params["w"], model.params["w"])


def test_schema_fields_are_unique_per_method():
    """No two methods may share a dataclass: the lint keys field checks
    by method, so aliasing would hide a drift."""
    classes = list(WIRE_SCHEMAS.values())
    assert len(classes) == len(set(classes))


# -- a packed frame whose pieces may still be on their way (PR 45) ------------


def _late_vector(vec, cut, events):
    """`vec` in two pending pieces, each landing with its event."""
    def wait(i, lo, hi, timeout):
        if not events[i].wait(timeout):
            raise TimeoutError(f"piece {i} has not landed")
        return vec[lo:hi]

    return codec.LeafVector([
        codec.PendingPiece(hi - lo, lambda t, a=(i, lo, hi): wait(*a, t))
        for i, (lo, hi) in enumerate([(0, cut), (cut, vec.size)])
    ])


def test_packed_parts_says_pending_and_joins_once_after_waiting():
    import threading

    vec = np.arange(5000, dtype=np.float32)
    events = [threading.Event(), threading.Event()]
    request = {"delta_flat": _late_vector(vec, 1234, events), "steps": 4}
    payload = M.pack_parts(request)
    want = M.pack({"delta_flat": vec, "steps": 4})
    assert payload.pending and payload.streamed and not payload.joined
    assert len(payload) == len(want) and payload.waited == 0.0
    events[0].set()
    # a carrier that needs one buffer waits, inside the call's budget
    with pytest.raises(TimeoutError):
        payload.contiguous(0.05)
    assert not payload.joined and payload.waited >= 0.04
    threading.Timer(0.05, events[1].set).start()
    data = payload.contiguous(5.0)
    assert data == want and payload.contiguous() is data
    assert payload.joined and not payload.streamed
    waited = payload.waited
    payload.contiguous(0.0)  # joined already: nothing to wait for
    assert payload.waited == waited
    assert M.unpack(data)["steps"] == 4


@pytest.mark.parametrize("obj", [
    {"worker_id": 3},
    {"params_flat": codec.LeafVector([np.ones(4, np.float32)])},
    M.Prepacked(codec.dumps({"x": 1})),
])
def test_a_frame_with_every_piece_there_is_not_pending(obj):
    payload = M.pack_parts(obj)
    assert not payload.pending and not payload.streamed
    assert payload.contiguous(0.0) == M.pack(obj) and payload.waited == 0.0
