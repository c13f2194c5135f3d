"""What the configurations' `compare.py` scripts share: the step a
`Worker` builds with the optimizer swapped for a keeper of the flat
gradient, the reference's step summed over a minibatch, a swap of a
module's attribute for the time of a trace, the count of assignments on
which a router and the reference's differ, and the small tools round
them. PR 52 parted them from `configs/qwen3-next-80b-a3b/compare.py`,
which imports them; they are `configs/laguna-xs2/compare.py`'s letter
for letter, and the four older scripts hold close copies of their own
(a `benchmark` PR's to point here: no file that stood is edited by a
PR of another kind).
"""

import contextlib
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.flatten_util import ravel_pytree

from elasticdl_tpu.api.model_spec import ModelSpec
from elasticdl_tpu.common.constants import WINDOW_STATS
from elasticdl_tpu.ops import flash_attention
from elasticdl_tpu.worker.worker import Worker


def say(msg):
    print(f"compare: {msg}", file=sys.stderr, flush=True)


def norm(vector, chunk=1 << 24):
    total = 0.0
    for i in range(0, vector.shape[0], chunk):
        piece = vector[i:i + chunk].astype(np.float64)
        total += float(piece @ piece)
    return total**0.5


def leaf_of(params, at):
    """Where entry `at` of the flat vector lies: {"leaf", "index"}."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if at < leaf.size:
            return {"leaf": jax.tree_util.keystr(path),
                    "index": [int(i) for i in np.unravel_index(at, leaf.shape)]}
        at -= leaf.size
    raise IndexError(at)


def gradient_keeper():
    return optax.trace(decay=0.0)


@contextlib.contextmanager
def swapped(module, name, other):
    kept = getattr(module, name)
    setattr(module, name, other)
    try:
        yield
    finally:
        setattr(module, name, kept)


def route_bf16(x, router_w, top_k):
    """`moe.route_topk` with everything in bfloat16."""
    logits = x.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, chosen = lax.top_k(probs, top_k)
    return (probs.astype(jnp.float32), gate.astype(jnp.float32),
            chosen.astype(jnp.int32))


_dot = flash_attention._dot


def dot_bf16(a, b, dims):
    return _dot(a, b, dims).astype(jnp.bfloat16).astype(jnp.float32)


def router_flips(ref, router_w, top_k, seed, route):
    """On how many of 8192 x `top_k` assignments `route` and the
    reference's float32 softmax and top-k differ, given the same rows:
    unit-variance normals rounded to bfloat16."""
    x = jax.random.normal(
        jax.random.PRNGKey(seed), (8192, router_w.shape[0]), jnp.bfloat16
    )
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(x.astype(jnp.float32) @ router_w, axis=-1)
        want = ref.top_k_by(probs, top_k)
    _p, _gate, chosen = jax.jit(route, static_argnums=(2,))(
        x, router_w, top_k
    )
    got = jnp.sum(jax.nn.one_hot(chosen, router_w.shape[1]), axis=1)
    return float(jnp.sum(jnp.abs(got - want)) / 2)


class WorkerStep:
    """The per-step program of a `Worker` for `model`, built by the
    worker's own builder: {loss, loads, grad} of one minibatch. `swap`:
    (module, name, other) in force while it traces."""

    def __init__(self, zoo, model, variables, swap=None):
        spec = ModelSpec(
            model=model, dataset_fn=zoo.dataset_fn, loss=zoo.loss,
            optimizer=gradient_keeper,
        )
        worker = Worker(
            0, None, spec, minibatch_size=zoo.SIZES["minibatch_per_chip"],
            local_updates=1,
        )
        worker._maybe_init_flat_from_tree(variables["params"])
        worker._flat = None  # the caller brings each seed's vector
        self._swap = swap
        self._step = worker._build_local_step()  # donates flat and state
        self._aux = {k: v for k, v in variables.items() if k != "params"}

    def __call__(self, flat, features, labels):
        with swapped(*self._swap) if self._swap else contextlib.nullcontext():
            state = gradient_keeper().init(flat)
            _flat, state, aux, loss = self._step(
                jnp.asarray(flat), state, self._aux, features, labels
            )
        out = jax.device_get({
            "loss": loss, "loads": aux[WINDOW_STATS]["expert_tokens"],
            "grad": state.trace, "stats": {
                k: v for k, v in aux[WINDOW_STATS].items()
                if k != "expert_tokens"
            },
        })
        return {
            "loss": float(out["loss"]),
            "loads": np.asarray(out["loads"], np.float64),
            "grad": np.asarray(out["grad"], np.float32),
            "stats": {k: float(v) for k, v in out["stats"].items()},
        }


class Hashable(dict):
    """The reference's settings as a static argument of a checkpoint."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


def reference_step(gradient, params, features, labels, held):
    total = None
    first, count = held
    for i in range(features.shape[0]):
        value, loads, grads = gradient(params, features[i:i + 1], labels[i:i + 1])
        assert jax.tree_util.tree_structure(grads) == (
            jax.tree_util.tree_structure(params)
        )
        out = jax.device_get({
            "loss": value, "loads": loads[:, first:first + count],
            "grad": ravel_pytree(grads)[0],
        })
        del grads
        part = {"loss": float(out["loss"]),
                "loads": np.asarray(out["loads"], np.float64),
                "grad": np.asarray(out["grad"], np.float32)}
        total = part if total is None else {k: total[k] + part[k] for k in total}
    n = features.shape[0]
    return {k: v if k == "loads" else v / n for k, v in total.items()}
