"""On the chip, at the configuration's sizes: what the worker's own
step computes against the plain reference.

    python benchmark/configs/laguna-xs2/compare.py --seed <n> [--seeds k]

One process that holds the chip itself (run it through `chiprun`, never
beside a job). For each seed: the zoo module's weights from the seed
and one minibatch (`minibatch_per_chip` x `seq_len` = 1 x 8192) of the
cell's own RecordIO data go through **the program a `Worker` builds**
(`Worker._build_local_step()`: `_local_step_core` jitted with its
donations, the step the window program scans 16 times), with the
model's non-trainable collection in `aux`, so `_apply_model`'s
`mutable` path runs as it does in the cell. The one thing swapped is
the zoo's optimizer, for `optax.trace(decay=0)`: its state after one
step IS the flat gradient the step differentiated, bit for bit. From
one call: the loss, the gradient and what the routers did
(`window_stats`). All held against `reference.py` (float32 under
`jax.default_matmul_precision("highest")`: attention a key-value head
at a time with its scores written out a block of queries at a time,
the experts a masked dense sum), differentiated LAYER BY LAYER by this
script (`reference_gradient`: the reference's own `layer` and
`head_loss`, one `jax.vjp` a layer from the head down, each block of
scores under `jax.checkpoint`: what is kept for the backward pass, not
what is computed), so that 8192 tokens fit and no program holds more
than a layer (the whole reference as one program met the machine's 40
GiB of host memory while it compiled).

Beside the whole step, the two kinds of attention layer ALONE, as the
step calls them (`transformer_lm._attend`, in the program's compute
dtype, no ambient precision: on the chip the Pallas kernels, the
sliding kind the banded call), on one sequence of inputs both sides
share, against the reference's mixer under `highest`: `swa_rel` and
`full_rel`, the largest error over the reference's largest output, and
`swa_l2`, `full_l2`, the error's norm over the output's. The whole
step's gradient carries bfloat16's rounding of every projection, under
which one key more in a band of 512 can hide; a mixer alone cannot.

1. `float32`: the model with `dtype` float32, same precision: the same
   mathematics in another order, so the two agree to accumulated
   rounding and to the assignments that rounding moves among the
   experts not held: `TIGHT` (the gradient by `TIGHT_FLIPPED` on a seed
   on which it moved one to or from an expert held here).
2. `timed`: the model as the cell times it, bfloat16 compute with
   float32 parameters, accumulation, router, scores and softmax, gate
   and logits-to-loss: inside `BAND`, whose limits lie between the
   timed program's largest reading over the seeds and the smallest of
   the controls, each of which has to come out NOT correct by at least
   one of `BAND`'s limits:
3. `no_window`: the sliding layers see the whole triangle;
4. `window_513`: one key more;
5. `full_rotary`: all 128 columns of a full layer's head turned;
6. `no_attention_factor`: cosine and sine as they are;
7. `no_gate`: the attention's output ungated;
8. `bf16_router`: the router's product, softmax, top-8 and gates in
   bfloat16; held where it can be seen: `router_flips`, the assignments
   on which the router and the reference's float32 one differ on
   inputs both share;
9. `bf16_scores`: every product inside the attention kernels rounded
   to bfloat16 as it leaves the MXU (scores, do v^T, and the
   accumulators' increments), the nearest precision below the float32
   the configuration states for them. Held where it can be seen: on
   the FLOAT32 program's layers alone, against `TIGHT`'s four mixer
   limits. Under bfloat16 inputs the rounding of a score is of the
   order the inputs' own rounding has already given it (a dot of 128
   products rounded to 2^-9 each is off by about 2^-9 of itself), so
   the timed layers read 0.0070 and 0.0088 in L2 with float32 scores
   and 0.0079 and 0.0110 with bfloat16 ones: no band lies between
   those with room. With float32 inputs the same rounding stands
   alone.

Not compared here: clipped Adam and the 16-step scan around the step,
which the cell itself runs to its loss check.

Prints one JSON line a seed and one verdict; exit 0 only if 1 and 2
hold and every control fails the band, for every seed. The whole step
is run for `timed`, `float32` and the controls a gradient can show
(`STEP_CONTROLS`); the others (a key more, a rotation's width or
factor, the kernels' products) are held by the layers alone, where
bfloat16's rounding of every projection does not cover them, and are
given the timed program's step. `--small` is the
CPU rehearsal of the script's plumbing (tiny sizes; its numbers are no
device numbers and its band is not judged).
"""

import argparse
import contextlib
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(_HERE)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax import lax  # noqa: E402
from jax.flatten_util import ravel_pytree  # noqa: E402

from benchmark.harness import data  # noqa: E402
from benchmark.harness.manifest import load_module  # noqa: E402
from elasticdl_tpu.api.model_spec import ModelSpec  # noqa: E402
from elasticdl_tpu.common.constants import WINDOW_STATS  # noqa: E402
from elasticdl_tpu.models import transformer_lm as lm  # noqa: E402
from elasticdl_tpu.ops import flash_attention  # noqa: E402
from elasticdl_tpu.parallel import moe  # noqa: E402
from elasticdl_tpu.worker.worker import Worker  # noqa: E402

# float32 against float32 (my chip runs, PR 48: calls 4 and 5 of the
# first round, 10 and 11 of the review's; PERF.md section 6). At 8192
# tokens x top-8 = 65,536 assignments a layer, rounding moves
# assignments on every seed: a token whose eighth and ninth
# probabilities lie within float32's rounding of the logits takes
# another expert. `load_abs` sees such a move only where one of the two
# experts is among the 16 of 256 held here (1 to 3 on four seeds of
# five): the token's whole expert gradient then lies elsewhere, and the
# gradient is read by TIGHT_FLIPPED (0.0027 to 0.0040 in L2, 0.049 to
# 0.058 of the largest entry). On the one seed with no held expert
# touched (call 10, 2147484102) the gradient read 4.2e-4 and 0.0062,
# over the 3e-4 and 6e-4 that the first round had set for a case it had
# never met. My reading, not proven: the moves among the 240 experts
# not held are still there (seven in eight of all moves, by the
# shares), and under renormalised gates each changes its token's input
# gradient through the gates' sum. `grad_max_leaf` names the entry
# furthest off: on every seed, touched or not, float32 or timed, one
# entry of an embedding row (one token's gradient), a tenth the size on
# the untouched seed, with the same ratio of L2 to largest entry: the
# same kind of event, smaller, and no diffuse rounding (the layers
# alone read 2e-5 of their largest entry). TIGHT's two gradient limits
# now lie between that reading and the smallest with a held expert
# touched (0.0027 and 0.049, calls 5 and 11); the timed
# bfloat16 program reads 0.065 to 0.067 and 0.106 to 0.129, outside
# both. The loss read 1.9e-7 to 6.6e-6 (timed 1.3e-5 to 1.3e-4: on one
# seed inside this limit, so the timed program is told by its gradient
# and its layers). The layers alone (no routing): `swa_rel` 1.6e-5 to
# 1.9e-5, `full_rel` 8e-6 to 2.5e-5, `swa_l2` 9.5e-5, `full_l2` 3.2e-5
# in float32 (the kernels' float32 products pass the MXU in fewer
# bfloat16 passes than `highest`'s six: that is their rounding), beside
# 0.0035 to 0.0057, 0.0038 to 0.0064, 0.0070 and 0.0088 timed. Each
# limit lies between the float32 program's largest reading and the
# timed program's smallest, the nearest precision below, as does
# `bf16_scores` on the float32 layers alone (0.0014 to 0.0018, 0.0020
# to 0.0028, 0.0038, 0.0067)
TIGHT = {
    "loss_rel": 1.5e-5,
    "load_abs": 12,
    "router_flips": 8,
    "grad_rel_l2": 1.2e-3,
    "grad_max_rel": 0.02,
    "swa_rel": 3e-4,
    "full_rel": 2e-4,
    "swa_l2": 8e-4,
    "full_l2": 5e-4,
}
TIGHT_FLIPPED = {**TIGHT, "grad_rel_l2": 0.015, "grad_max_rel": 0.085}
# bfloat16 compute against the float32 reference: each limit between
# the timed program's largest reading over three seeds and the smallest
# of the control that separates from it there (PERF.md section 6, PR
# 48). The gradient in L2 read 0.065 to 0.066 and holds `no_window`
# (0.225 to 0.226) and `no_gate` (1.04); its largest entry 0.106 to
# 0.129 beside 0.211 to 0.235 and 1.0 to 1.2; `load_abs` 53 to 68
# beside 250 to 280 and 2,140 to 2,390 (`bf16_router` 66 to 78: it is
# held by `router_flips`, 329 to 360 of 65,536, the program's own 0).
# `window_513` moves one key of 513 and is held by the sliding layer
# alone: `swa_l2` 0.038 to 0.039 beside the timed 0.0070 (`swa_rel`
# 0.013 to 0.032 beside 0.0045 to 0.0057); `full_rotary` and
# `no_attention_factor` by the full layer alone: `full_l2` 1.53 and
# 0.60 beside 0.0088 (`full_rel` 0.37 to 0.62 and 0.18 to 0.28 beside
# 0.0038 to 0.0058)
BAND = {
    "loss_rel": 3e-4,
    "load_abs": 150,
    "router_flips": 8,
    "grad_rel_l2": 0.12,
    "grad_max_rel": 0.17,
    "swa_rel": 0.0075,
    "full_rel": 0.03,
    "swa_l2": 0.016,
    "full_l2": 0.07,
}
CONTROLS = ("no_window", "window_513", "full_rotary", "no_attention_factor",
            "no_gate", "bf16_router", "bf16_scores")
# the controls whose whole step is run (a step's program takes a minute
# to compile and the machine's host memory is 40 GiB); the others are
# held by the layers alone
STEP_CONTROLS = ("no_window", "no_gate", "bf16_router")
SMALL = dict(
    vocab=97, d_model=64, head_width=16, n_heads=6, n_kv_heads=2, d_ff=96,
    rope_dim=8, swa_heads=8, swa_window=8, n_experts=16,
    held_experts=(4, 4), d_expert=24, moe_top_k=3,
)
MIXERS = {"swa": "sliding", "mha": "full"}  # the program's, the reference's


def say(msg):
    print(f"compare: {msg}", file=sys.stderr, flush=True)


def _norm(vector, chunk=1 << 24):
    total = 0.0
    for i in range(0, vector.shape[0], chunk):
        piece = vector[i:i + chunk].astype(np.float64)
        total += float(piece @ piece)
    return total**0.5


def measures(got, want):
    gap = got["grad"] - want["grad"]
    return {
        "loss_rel": abs(got["loss"] - want["loss"]) / abs(want["loss"]),
        "load_abs": float(np.max(np.sum(np.abs(got["loads"] - want["loads"]), axis=-1))),
        "router_flips": got["router_flips"],
        **{k: got[k] for k in ("swa_rel", "full_rel", "swa_l2", "full_l2")},
        "grad_rel_l2": _norm(gap) / _norm(want["grad"]),
        "grad_max_rel": float(np.max(np.abs(gap)) / np.max(np.abs(want["grad"]))),
        "grad_max_at": int(np.argmax(np.abs(gap))),
    }


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


# ------------------------------------------------------------- the controls


def route_bf16(x, router_w, top_k):
    """`moe.route_topk` with everything in bfloat16."""
    logits = x.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, chosen = lax.top_k(probs, top_k)
    return (probs.astype(jnp.float32), gate.astype(jnp.float32),
            chosen.astype(jnp.int32))


def ungated(lp, x):
    return jnp.ones(x.shape[:2] + (lp["wgate"].shape[0],), jnp.float32)


_dot = flash_attention._dot


def dot_bf16(a, b, dims):
    return _dot(a, b, dims).astype(jnp.bfloat16).astype(jnp.float32)


# a control is a model override or a swap (module, name, other)
OVERRIDES = {
    "no_window": lambda sizes: dict(swa_window=sizes["seq_len"]),
    "window_513": lambda sizes: dict(swa_window=sizes["sliding_window"] + 1),
    "full_rotary": lambda sizes: dict(rope_dim=None),
    "no_attention_factor": lambda sizes: dict(rope_factor=1.0),
}
SWAPS = {
    "no_gate": (lm, "_head_gate", ungated),
    "bf16_router": (moe, "route_topk", route_bf16),
    "bf16_scores": (flash_attention, "_dot", dot_bf16),
}


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


def mixer_inputs(cfg, mixer, seed, length):
    """One sequence of unit-variance rows and a layer's leaves
    (matrices at 1/sqrt(fan-in), the gate's among them), float32."""
    d, hd = cfg.d_model, cfg.head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    heads = cfg.attention_shape(mixer).heads

    def matrix(rows, cols, fan_in=None):
        return jax.random.normal(next(keys), (rows, cols)) / (
            fan_in or rows
        ) ** 0.5

    x = jax.random.normal(next(keys), (1, length, d))
    return {
        "wq": matrix(d, heads * hd), "wk": matrix(d, cfg.kv_heads * hd),
        "wv": matrix(d, cfg.kv_heads * hd), "wo": matrix(heads * hd, d),
        "wgate": matrix(heads, d, fan_in=d),
    }, x


class Mixers:
    """The program's two kinds of attention layer alone, in a model's
    compute dtype, against the reference's under `highest`, on inputs
    both share: {"swa_rel", "swa_l2", "full_rel", "full_l2"} of a
    (model, swap). Each side's program is traced once and kept: the
    reference's answer once a seed, whatever is held against it."""

    def __init__(self, ref, sizes):
        self._programs, self._want, self._seed = {}, {}, None
        self._reference = {
            mixer: jax.jit(
                lambda lp, x, kind=kind: ref.attention_mixer(
                    lp, x, sizes[kind], sizes
                )
            ) for mixer, kind in MIXERS.items()
        }

    def errors(self, name, cfg, seed, length, swap=None):
        found = {}
        if seed != self._seed:  # the last seed's answers go
            self._want, self._seed = {}, seed
        for mixer in MIXERS:
            leaves, x = mixer_inputs(cfg, mixer, seed, length)
            if mixer not in self._want:
                with jax.default_matmul_precision("highest"):
                    self._want[mixer] = self._reference[mixer](leaves, x)
            want = self._want[mixer]
            if (name, mixer) not in self._programs:
                self._programs[name, mixer] = jax.jit(
                    lambda lp, x, mixer=mixer: lm._attend(
                        cfg, lp, x, jnp.arange(x.shape[1]), mixer
                    )[0]
                )
            with swapped(*swap) if swap else contextlib.nullcontext():
                got = self._programs[name, mixer](
                    {k: v.astype(cfg.dtype) for k, v in leaves.items()},
                    x.astype(cfg.dtype),
                ).astype(jnp.float32)
            found[f"{kind_name(mixer)}_rel"] = float(
                jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))
            )
            found[f"{kind_name(mixer)}_l2"] = float(
                jnp.linalg.norm(got - want) / jnp.linalg.norm(want)
            )
        return found


def kind_name(mixer):
    return {"swa": "swa", "mha": "full"}[mixer]


@contextlib.contextmanager
def swapped(module, name, other):
    kept = getattr(module, name)
    setattr(module, name, other)
    try:
        yield
    finally:
        setattr(module, name, kept)


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


class _Hashable(dict):
    """The reference's settings as a static argument of a checkpoint."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


class ReferenceGradient:
    """The reference's loss, loads and gradient of one sequence, layer
    by layer: the forward pass keeps each layer's input, the head gives
    the loss and the last layer's cotangent, and one `jax.vjp` a layer
    walks back down, each block of scores under `jax.checkpoint`. One
    program a kind of layer (dense or expert, full or sliding), so a
    compile holds a layer and not the stack."""

    def __init__(self, ref, sizes):
        self._ref, self._sizes = ref, sizes
        block = jax.checkpoint(ref.block_attention, static_argnums=(3, 4, 5))

        def layer(lp, h, kind):
            with swapped(ref, "block_attention", block):
                return ref.layer(lp, h, sizes[kind], sizes)

        def back(lp, h, cotangent, kind):
            _out, pull, _load = jax.vjp(
                lambda lp, h: layer(lp, h, kind), lp, h, has_aux=True
            )
            return pull(cotangent)

        self._layer = jax.jit(layer, static_argnums=(2,))
        self._back = jax.jit(back, static_argnums=(3,))
        self._head = jax.jit(jax.value_and_grad(
            lambda ln_f, head, h, targets: ref.head_loss(
                ln_f, head, h, targets, sizes
            )[0], argnums=(0, 1, 2),
        ))
        self._embed = jax.jit(jax.grad(
            lambda embed, tokens, cotangent: jnp.sum(embed[tokens] * cotangent)
        ))

    def __call__(self, params, tokens, targets):
        """-> {loss, loads [expert layers, E], grad: the tree's}."""
        ref, kinds = self._ref, self._sizes["kinds"]
        layers = list(ref.layers_of(params))
        inputs, loads = [params["embed"][tokens]], []
        for lp, kind in zip(layers, kinds):
            h, load = self._layer(lp, inputs[-1], kind)
            inputs.append(h)
            if load is not None:
                loads.append(load)
        loss, (ln_f, head, cotangent) = self._head(
            params["ln_f"], params["head"], inputs.pop(), targets
        )
        grads = []
        for lp, kind in zip(reversed(layers), reversed(kinds)):
            lp_grad, cotangent = self._back(lp, inputs.pop(), cotangent, kind)
            grads.insert(0, lp_grad)
        stack, at = [], 0
        for run in params["stack"]:  # a layer's leaves back onto its run's
            n = run["ln1"].shape[0]
            stack.append({
                name: jnp.stack([g[name] for g in grads[at:at + n]])
                for name in run
            })
            at += n
        return loss, jnp.stack(loads), {
            "embed": self._embed(params["embed"], tokens, cotangent),
            "head": head, "ln_f": ln_f, "stack": stack,
        }


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


def compare_seed(zoo, programs, seed, small):
    import gc

    from elasticdl_tpu.data.recordio import RecordIOReader

    gc.collect()  # the last seed's vectors go before this one's come

    sizes = dict(zoo.SIZES)
    if small:
        sizes["data"] = {**sizes["data"], "seq_len": 64, "alphabet": 97, "records": 8}
    directory = data.ensure(ROOT, sizes, _HERE, seed)
    with RecordIOReader(os.path.join(directory, "train.rio")) as reader:
        records = list(reader.read_range(0, sizes["minibatch_per_chip"]))
    features, labels = zoo.dataset_fn(records, "training")
    features, labels = jnp.asarray(features), jnp.asarray(labels)
    models = programs["models"]
    timed = models["timed"]
    variables = timed.init(jax.random.PRNGKey(seed), None)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    # on the host between the programs: the float32 step takes 9.3 GB of
    # temporaries beside its own copy of the vector and the gradient
    flat = np.asarray(ravel_pytree(params)[0])
    shapes = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), params
    )
    if "steps" not in programs:
        programs["steps"] = {
            name: WorkerStep(
                zoo, models.get(name, timed), variables, SWAPS.get(name)
            ) for name in ("timed", "float32") + STEP_CONTROLS
        }
    steps = programs["steps"]
    say(f"seed {seed}: {flat.size} parameters, batch {features.shape}, "
        f"{jax.devices()[0].device_kind}")
    ref, ref_sizes = programs["ref"], programs["sizes"]
    with jax.default_matmul_precision("highest"):
        want = reference_step(
            programs["reference"], params, features, labels, timed.cfg.held
        )
    say(f"reference: loss {want['loss']:.6f}")
    router_w = jnp.asarray(
        [run for run in params["stack"] if "router" in run][0]["router"][0],
        jnp.float32,
    )
    flips = {
        name: router_flips(ref, router_w, timed.cfg.moe_top_k, seed, route)
        for name, route in (("own", moe.route_topk), ("bf16", route_bf16))
    }
    length = features.shape[1]
    alone = programs["mixers"]
    mixers = {"timed": alone.errors("timed", timed.cfg, seed, length)}
    with jax.default_matmul_precision("highest"):
        mixers["float32"] = alone.errors(
            "float32", models["float32"].cfg, seed, length
        )
    for name in OVERRIDES:
        mixers[name] = alone.errors(name, models[name].cfg, seed, length)
    mixers["no_gate"] = alone.errors(
        "no_gate", timed.cfg, seed, length, SWAPS["no_gate"]
    )
    with jax.default_matmul_precision("highest"):
        mixers["bf16_scores"] = alone.errors(
            "bf16_scores", models["float32"].cfg, seed, length,
            SWAPS["bf16_scores"],
        )
    del params
    found, stats, results = {}, {}, {}

    def judge(name, precision=None):
        # a control held by the layers alone is given the timed step
        step = name if name in steps else "timed"
        if step not in results:
            with jax.default_matmul_precision(precision) if precision else (
                contextlib.nullcontext()
            ):
                results.clear()  # one gradient of 2 GB on the host at a time
                results[step] = steps[step](flat, features, labels)
        result = dict(results[step])
        result["router_flips"] = flips["bf16" if name == "bf16_router" else "own"]
        result.update(mixers.get(name, mixers["timed"]))
        found[name] = measures(result, want)
        stats[name] = {"loss": result["loss"], **result["stats"]}

    judge("float32", "highest")
    ordered = [c for c in CONTROLS if c not in STEP_CONTROLS] + list(STEP_CONTROLS)
    for name in ["timed"] + ordered:  # the timed step's borrowers next to it
        judge(name)

    def beyond(name, limits):  # a NaN is beyond every limit
        return {
            k: found[name][k] for k, limit in limits.items()
            if not found[name][k] <= limit
        }

    flipped = found["float32"]["load_abs"] > 0
    tight = beyond("float32", TIGHT_FLIPPED if flipped else TIGHT)
    mixer_limits = {k: v for k, v in TIGHT.items() if k[-3:] in ("rel", "_l2")
                    and k.split("_")[0] in ("swa", "full")}
    out_of_band = {
        name: beyond(name, mixer_limits if name == "bf16_scores" else BAND)
        for name in ("timed",) + CONTROLS
    }
    verdict = {
        "seed": seed,
        "device": jax.devices()[0].device_kind,
        "reference": {"loss": want["loss"], "loads": want["loads"].tolist(),
                      "grad_norm": _norm(want["grad"])},
        "programs": stats,
        "measures": found,
        "float32_flipped": flipped,
        # the entry on which each program's gradient is furthest off
        # (an embedding row's, on every seed so far: one token's)
        "grad_max_leaf": {
            name: leaf_of(shapes, found[name]["grad_max_at"])
            for name in ("float32", "timed")
        },
        "float32_beyond_tight": tight,
        **{f"{name}_beyond_band": out_of_band[name] for name in out_of_band},
        "ok": not tight and (small or (
            not out_of_band["timed"]
            and all(out_of_band[name] for name in CONTROLS)
        )),
    }
    print(json.dumps(verdict), flush=True)
    return verdict


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seeds", type=int, default=1)
    parser.add_argument("--small", action="store_true")
    args = parser.parse_args(argv)
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.small:
        raise SystemExit(f"compare: on {platform!r}; the sizes need the TPU")
    zoo = load_module(os.path.join(_HERE, "zoo.py"))
    ref = load_module(os.path.join(_HERE, "reference.py"))
    overrides = SMALL if args.small else {}
    shape = {"seq_len": 64 if args.small else zoo.SIZES["seq_len"],
             "sliding_window": overrides.get(
                 "swa_window", zoo.SIZES["sliding_window"])}
    models = {
        "timed": zoo.custom_model(**overrides),
        "float32": zoo.custom_model(dtype="float32", **overrides),
        **{name: zoo.custom_model(**{**overrides, **override(shape)})
           for name, override in OVERRIDES.items()},
    }
    cfg = models["timed"].cfg
    sizes = ref.sizes_of(zoo.SIZES, top_k=cfg.moe_top_k, held=cfg.held,
                         kv_heads=cfg.kv_heads, head_dim=cfg.head_dim)
    for mixer, kind in MIXERS.items():  # `--small`'s shapes
        shape_ = cfg.attention_shape(mixer)
        sizes[kind] = _Hashable(
            sizes[kind], heads=shape_.heads, window=shape_.window,
            rope_dim=shape_.rope_dim or cfg.head_dim,
        )
    sizes = _Hashable(sizes)
    programs = {
        "models": models, "ref": ref, "sizes": sizes,
        "reference": ReferenceGradient(ref, sizes),
        "mixers": Mixers(ref, sizes),
    }
    verdicts = [
        compare_seed(zoo, programs, args.seed + i, args.small)
        for i in range(args.seeds)
    ]
    ok = all(v["ok"] for v in verdicts)
    out = os.path.join(ROOT, "chiprun_out", "laguna_compare.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        for v in verdicts:
            f.write(json.dumps(v) + "\n")
    say(f"{'PASS' if ok else 'FAIL'}: {len(verdicts)} seed(s); limits TIGHT "
        f"{TIGHT}, on a seed with a held expert's assignment moved "
        f"{TIGHT_FLIPPED}, BAND {BAND}; written to {out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
