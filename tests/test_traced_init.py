"""What `Worker._init_model` leaves behind: for a flax module the
variables eager `model.init` gives for the same key, built by tracing
`init` (the forward pass yields shapes and is compiled away); for a
duck-typed adapter the adapter's own draw, by the direct call."""

import jax
import numpy as np
import pytest

from elasticdl_tpu.api.model_spec_helpers import spec_from_module
from elasticdl_tpu.common import codec
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.models import record_codec as rc
from elasticdl_tpu.models import (
    cifar10_functional_api,
    cifar10_subclass,
    deepfm_edl_embedding,
    deepfm_functional_api,
    mnist_functional_api,
    mnist_subclass,
    resnet50_subclass,
    transformer_lm_zoo,
)
from elasticdl_tpu.models.transformer_lm import init_params
from elasticdl_tpu.obs import trace
from elasticdl_tpu.testing import InProcessMaster, build_job
from elasticdl_tpu.worker.worker import Worker

BATCH = 4
SMALL_RESNET = (32, 32, 3)  # every stage still strides: 54 convolutions


@pytest.fixture(autouse=True)
def _clean_recorder():
    trace.configure(0.0)
    trace.RECORDER.clear()
    yield
    trace.RECORDER.clear()
    trace.configure(None)


def _images(shape):
    rng = np.random.default_rng(0)
    return [
        rc.encode_image_record(rng.integers(0, 256, shape), int(rng.integers(10)))
        for _ in range(BATCH)
    ]


def _rows():
    rng = np.random.default_rng(0)
    fields = deepfm_functional_api.NUM_FIELDS
    return [
        rc.encode_tabular_record(rng.integers(1, 200, size=fields), 1.0)
        for _ in range(BATCH)
    ]


def _small_resnet_spec():
    """ResNet-50 as the benchmark's configuration runs it (bfloat16
    compute, uint8 images the model scales itself), at 32 px."""
    return spec_from_module(
        resnet50_subclass,
        model=resnet50_subclass.custom_model(bfloat16=True),
        dataset_fn=lambda records, mode: rc.decode_image_records(
            records, SMALL_RESNET, scale=False
        ),
    )


FLAX_ZOO = {
    "mnist_functional": (
        lambda: spec_from_module(mnist_functional_api), lambda: _images((28, 28, 1))
    ),
    "mnist_subclass": (
        lambda: spec_from_module(mnist_subclass), lambda: _images((28, 28, 1))
    ),
    "cifar10_functional": (
        lambda: spec_from_module(cifar10_functional_api),
        lambda: _images((32, 32, 3)),
    ),
    "cifar10_subclass": (
        lambda: spec_from_module(cifar10_subclass), lambda: _images((32, 32, 3))
    ),
    "deepfm": (lambda: spec_from_module(deepfm_functional_api), _rows),
    "deepfm_edl_embedding": (lambda: spec_from_module(deepfm_edl_embedding), _rows),
    "resnet50_bf16_32px": (_small_resnet_spec, lambda: _images(SMALL_RESNET)),
}


def _worker(spec):
    servicer, _eval, _ckpt = build_job(spec, TaskDispatcher({}, {}, {}, 8, 1))
    return Worker(0, InProcessMaster(servicer), spec, minibatch_size=BATCH)


def _init_args(worker, records):
    """The arguments `_lazy_init_model` hands `_init_model`."""
    features, _labels = worker._spec.dataset_fn(records, "training")
    embeddings = None
    if worker._emb_specs:
        embeddings = worker._dev_embedding_inputs(
            worker._prepare_embeddings(features)
        )
    return features, embeddings


def _eager_init(worker, features, embeddings):
    args = [features] if embeddings is None else [features, embeddings]
    kwargs = {"train": False} if worker._takes_train_kwarg() else {}
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        return worker._spec.model.init(worker._rng, *args, **kwargs)


def _assert_same(got, want, ulps=0):
    """Same structure, shapes and dtypes, and every float32 within
    `ulps` units in the last place (0: the same bits)."""
    got_leaves, got_tree = jax.tree_util.tree_flatten(got)
    want_leaves, want_tree = jax.tree_util.tree_flatten(want)
    assert got_tree == want_tree
    for g, w in zip(got_leaves, want_leaves):
        g, w = np.asarray(g), np.asarray(w)
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        if not ulps:
            assert g.tobytes() == w.tobytes()
            continue
        assert g.dtype == np.float32 and np.all(np.sign(g) == np.sign(w))
        apart = g.view(np.int32).astype(np.int64) - w.view(np.int32)
        assert np.abs(apart).max() <= ulps


# Both sides run the same initialisers on the same PRNG bits on the CPU
# backend, and an initialiser takes nothing from the forward pass: the
# same bits, with one exception measured here. `nn.Embed`'s default
# initialiser is `normal(key) * sqrt(1 / features)`, and `normal` ends
# in a multiply by sqrt(2): inside ONE program XLA folds the two
# constants and rounds once where op by op each multiply rounds. That
# is deepfm's `fm_second` (8 features; 61 % of its entries move, each
# by one unit in the last place, 1.2e-7 relative); `fm_first` (1
# feature: a factor of 1.0) and every Dense, Conv and BatchNorm leaf of
# the zoo (truncated normals end in a clip, which nothing folds across)
# keep their bits.
LAST_PLACE = {"deepfm": 1}


@pytest.mark.parametrize("name", list(FLAX_ZOO))
def test_a_flax_module_s_traced_init_is_eager_init(name):
    make_spec, make_records = FLAX_ZOO[name]
    ulps = LAST_PLACE.get(name, 0)
    worker = _worker(make_spec())
    features, embeddings = _init_args(worker, make_records())
    worker._init_model(features, embeddings)
    want = _eager_init(worker, features, embeddings)
    _assert_same(worker._template, want["params"], ulps)
    _assert_same(worker._aux, {k: v for k, v in want.items() if k != "params"})
    # the single buffer the step trains on: the tree, leaf after leaf
    assert worker._use_flat()  # every zoo model is float
    flat = np.asarray(worker._flat)
    assert flat.tobytes() == codec.ravel_np(worker._template).tobytes()
    _assert_same(worker._unravel(worker._flat), want["params"], ulps)
    (span,) = [
        s for s in trace.RECORDER.snapshot() if s["name"] == "setup.model_init"
    ]
    assert span["args"]["how"] == "init" and span["args"]["traced"] is True
    assert span["args"]["compiles"] >= 0
    assert ("cache_hit" in span["args"]) == bool(span["args"]["compiles"])
    # charged to `setup.model_init`, where it happens: no program span
    assert not [
        s for s in trace.RECORDER.snapshot() if s["name"] == "setup.program"
    ]


def test_the_compiled_init_of_resnet50_holds_no_forward_pass():
    """The forward is gone, not merely fast: the program `_init_model`
    builds for the small ResNet-50 (54 convolutions and a dense layer
    when run eagerly) compiles to initialisers alone."""
    worker = _worker(_small_resnet_spec())
    features, _ = _init_args(worker, _images(SMALL_RESNET))
    model = worker._spec.model

    def init(rng, x):
        return model.init(rng, x, train=False)

    forward = jax.jit(lambda v, x: model.apply(v, x, train=False)).lower(
        jax.eval_shape(init, worker._rng, features), features
    ).compile().as_text()
    # the control: this is how the CPU compiler spells the forward pass
    # (a library call would read `__onednn$matmul` for ` dot(`)
    assert "convolution" in forward
    assert " dot(" in forward or "matmul" in forward
    text = jax.jit(init).lower(worker._rng, features).compile().as_text()
    for op in ("convolution", " dot(", "matmul"):
        assert op not in text


LM_CONFIGS = {
    "dense": dict(vocab=64),
    "looped": dict(
        vocab=64, n_loops=3, mlp="swiglu", sandwich_norm=True, rope_base=1e6
    ),
}


@pytest.mark.parametrize("name", list(LM_CONFIGS))
def test_an_adapter_keeps_its_direct_init_and_its_parameters(name):
    """`TransformerLM` is no flax module: its `init` reads the key's
    data on the host and draws with numpy, which a trace could not do.
    Its parameters for a seed are the numpy generator's, as they were."""
    model = transformer_lm_zoo.custom_model(**LM_CONFIGS[name])
    spec = spec_from_module(transformer_lm_zoo, model=model)
    worker = _worker(spec)
    tokens = np.zeros((BATCH, 16), np.int32)
    worker._init_model(tokens, None)
    seed = int(np.asarray(jax.random.key_data(worker._rng)).ravel()[-1])
    want = init_params(np.random.default_rng(seed & 0x7FFFFFFF), model.cfg)
    _assert_same(worker._template, want)
    assert np.asarray(worker._flat).tobytes() == codec.ravel_np(want).tobytes()
    assert (transformer_lm_zoo.WINDOW_STATS in worker._aux) == (name == "looped")
    (span,) = [
        s for s in trace.RECORDER.snapshot() if s["name"] == "setup.model_init"
    ]
    assert span["args"] == {
        "how": "init", "traced": False, "thread": span["args"]["thread"],
    }
