"""On the chip, at the configuration's sizes: what the worker's own
step computes against the plain reference.

    python benchmark/configs/lfm2-24b-a2b/compare.py --seed <n> [--seeds k]

One process that holds the chip itself (run it through `chiprun`, never
beside a job). For each seed: the zoo module's weights from the seed
and one minibatch (`minibatch_per_chip` x `seq_len`) of the cell's own
RecordIO data go through **the program a `Worker` builds**
(`Worker._build_local_step()`: `_local_step_core` jitted with its
donations, the step the window program scans 16 times), with the
model's non-trainable collection in `aux`, so `_apply_model`'s
`mutable` path runs as it does in the cell. The one thing swapped is
the zoo's optimizer, for `optax.trace(decay=0)`: its state after one
step IS the flat gradient the step differentiated, bit for bit. From
one call: the loss, the gradient and what the routers did
(`window_stats`). All held against `reference.py` (float32 under
`jax.default_matmul_precision("highest")`: the convolution a token at a
time from a cache of three rows, attention head by head with its
scores written out, the experts a masked dense sum, the tied head),
computed one sequence at a time and, so that its activations fit, LAYER
BY LAYER: this script wraps each of the reference's layers in
`jax.checkpoint` (what is kept for the backward pass, not what is
computed).

Beside the whole step, the two mixers ALONE, as the step calls them
(`transformer_lm._conv` and `_mha`, in the program's compute dtype, no
ambient precision), on one sequence of inputs both sides share, with
norm weights and taps away from their initial values, against the
reference's mixers under `highest`: `conv_rel` and `attn_rel`, the
largest error over the reference's largest output. The whole step's
gradient carries bfloat16's rounding of every projection, under which
one changed layer of five can hide; a mixer alone cannot.

1. `float32`: the model with `dtype` float32, same precision: the same
   mathematics in another order, so the two agree to accumulated
   rounding: `TIGHT` (the gradient by `TIGHT_FLIPPED` on a seed on
   which that rounding moved an assignment).
2. `timed`: the model as the cell times it, bfloat16 compute with
   float32 parameters, accumulation, router, query and key norms and
   logits-to-loss: inside `BAND`, whose limits lie between the timed
   program's largest reading over the seeds and the smallest of the
   controls, each of which has to come out NOT correct by at least one
   of `BAND`'s limits:
3. `bf16_router`: the router's product, sigmoid, top-4 and gates in
   bfloat16; held where it can be seen: `router_flips`, the assignments
   on which the router and the reference's float32 one differ on
   inputs both share;
4. `activated_taps`: SiLU left on the convolution (Kimi's form of
   `_causal_conv`, which this block's has parted from);
5. `shifted_taps`: the convolution's window one token late;
6. `no_qk_norm`: queries and keys rotated as projected;
7. `interleaved_groups`: query head i reading key-value head i % 8
   (`jnp.tile` where the dispatcher repeats);
8. `unnormalised`: gates not renormalised over the chosen four.

Not compared here: clipped Adam and the 16-step scan around the step,
which the cell itself runs to its loss check.

Prints one JSON line a seed and one verdict; exit 0 only if 1 and 2
hold and every control fails the band, for every seed. `--small` is the
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

# float32 against float32: accumulated rounding (my chip runs, PR 44,
# PERF.md section 6: loss 7.6e-8 to 1.3e-7, gradient 2.7e-6 to 3.2e-6
# in L2 and 7.1e-6 to 9.7e-6 of the largest entry, `conv_rel` 4e-7,
# `attn_rel` 1.6e-7, no assignment moved), and the assignments that
# rounding flips (a token whose fourth and fifth scores lie within it
# takes another expert, and its whole gradient then lies there: Kimi's
# file saw one in eight seeds). A seed whose loads differ (`load_abs` >
# 0, within its limit) is held to TIGHT_FLIPPED's gradient limits, every
# other seed to TIGHT's. Each limit lies between the float32 program's
# largest reading and the timed bfloat16 program's smallest (loss
# 1.6e-5, gradient 0.045 and 0.089, `conv_rel` 6.5e-3, `attn_rel`
# 3.8e-3), the nearest precision below, which so falls outside every
# one of them
TIGHT = {
    "loss_rel": 2e-6,
    "load_abs": 8,
    "router_flips": 8,
    "grad_rel_l2": 3e-4,
    "grad_max_rel": 6e-4,
    "conv_rel": 5e-5,
    "attn_rel": 3e-5,
}
TIGHT_FLIPPED = {**TIGHT, "grad_rel_l2": 0.01, "grad_max_rel": 0.03}
# bfloat16 compute against the float32 reference: each limit between
# the timed program's largest reading over the seeds and the smallest
# of the control that separates from it there (PERF.md section 6, PR
# 44). The gradient in L2 read 0.045 to 0.052 and holds
# `interleaved_groups` (0.29 and more), `unnormalised` (0.72) and both
# tap controls (1.37 and more); its largest entry 0.089 to 0.094 beside
# 0.47 and more of the same four. `bf16_router` moves neither far
# enough (0.056 to 0.063 in L2) and is held by `router_flips` (415 to
# 432 of 32,768, the program's own 0); `no_qk_norm` neither (0.072 to
# 0.082: one layer of five, at norm weights of one) and is held by
# `attn_rel`, 0.157 to 0.206 beside the timed program's 0.0038 to
# 0.0046; `conv_rel` reads 0.0065 to 0.0070 timed and 0.69 to 0.92
# under the tap controls. `load_abs` 44 to 45 timed, 271 and more under
# the taps
BAND = {
    "loss_rel": 3e-4,
    "load_abs": 120,
    "router_flips": 8,
    "grad_rel_l2": 0.12,
    "grad_max_rel": 0.2,
    "conv_rel": 0.07,
    "attn_rel": 0.03,
}
CONTROLS = ("bf16_router", "activated_taps", "shifted_taps", "no_qk_norm",
            "interleaved_groups", "unnormalised")
SMALL = dict(
    vocab=97, d_model=64, n_heads=4, n_kv_heads=2, d_ff=96, n_experts=16,
    held_experts=(4, 4), d_expert=24, moe_top_k=3,
)


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
        "conv_rel": got["conv_rel"],
        "attn_rel": got["attn_rel"],
        "grad_rel_l2": _norm(gap) / _norm(want["grad"]),
        "grad_max_rel": float(np.max(np.abs(gap)) / np.max(np.abs(want["grad"]))),
    }


def gradient_keeper():
    return optax.trace(decay=0.0)


# ------------------------------------------------------------- the controls


def route_bf16(x, router_w, bias, top_k, renormalize):
    """`moe.route_sigmoid_topk` with everything in bfloat16."""
    scores = jax.nn.sigmoid(x.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16))
    biased = scores if bias is None else scores + bias.astype(jnp.bfloat16)
    _, chosen = lax.top_k(biased, top_k)
    gate = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return (scores.astype(jnp.float32), gate.astype(jnp.float32),
            chosen.astype(jnp.int32))


_taps = lm._causal_conv
_attention = flash_attention.attention


def activated_taps(x, taps):
    return jax.nn.silu(_taps(x, taps))


def shifted_taps(x, taps):
    return _taps(jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1], taps)


def unnormed(lp, q, k, eps):
    return q, k


def interleaved_groups(q, k, v, **kw):
    group = q.shape[2] // k.shape[2]
    return _attention(
        q, jnp.tile(k, (1, 1, group, 1)), jnp.tile(v, (1, 1, group, 1)), **kw
    )


SWAPS = {
    "bf16_router": (moe, "route_sigmoid_topk", route_bf16),
    "activated_taps": (lm, "_causal_conv", activated_taps),
    "shifted_taps": (lm, "_causal_conv", shifted_taps),
    "no_qk_norm": (lm, "_qk_norm", unnormed),
    "interleaved_groups": (flash_attention, "attention", interleaved_groups),
}


def router_flips(ref, router_w, top_k, seed, route):
    """On how many of 8192 x `top_k` assignments `route` and the
    reference's float32 sigmoid and top-k differ, given the same rows:
    unit-variance normals rounded to bfloat16."""
    x = jax.random.normal(
        jax.random.PRNGKey(seed), (8192, router_w.shape[0]), jnp.bfloat16
    )
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x.astype(jnp.float32) @ router_w)
        want = ref.top_k_by(scores, top_k)
    _s, _gate, chosen = jax.jit(route, static_argnums=(3, 4))(
        x, router_w, None, top_k, True
    )
    got = jnp.sum(jax.nn.one_hot(chosen, router_w.shape[1]), axis=1)
    return float(jnp.sum(jnp.abs(got - want)) / 2)


def mixer_errors(ref, cfg, sizes, seed, length, swap=None):
    """{"conv_rel", "attn_rel"}: the program's two mixers alone, in
    `cfg.dtype` with the leaves the program reads in float32 left so,
    against the reference's under `highest`, on one sequence of
    unit-variance rows and leaves both share: matrices at 1/sqrt(fan-in),
    taps and norm weights uniform in (0.5, 1.5), so that a norm left
    out, a tap moved or a head misread shows. `swap` is in force while
    the program's side traces."""
    d, hd = cfg.d_model, cfg.head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 12))

    def matrix(rows, cols):
        return jax.random.normal(next(keys), (rows, cols)) / rows**0.5

    def around_one(*shape):
        return jax.random.uniform(next(keys), shape, minval=0.5, maxval=1.5)

    x = jax.random.normal(next(keys), (1, length, d))
    conv = {"in_proj": matrix(d, 3 * d), "conv": around_one(cfg.conv_taps, d),
            "out_proj": matrix(d, d)}
    attn = {"wq": matrix(d, cfg.n_heads * hd), "wk": matrix(d, cfg.kv_heads * hd),
            "wv": matrix(d, cfg.kv_heads * hd), "wo": matrix(cfg.n_heads * hd, d),
            "q_norm": around_one(hd), "k_norm": around_one(hd)}
    with jax.default_matmul_precision("highest"):
        want = {
            "conv_rel": jax.jit(ref.conv_mixer)(conv, x),
            "attn_rel": jax.jit(lambda lp, x: ref.grouped_attention(lp, x, sizes))(attn, x),
        }

    def cast(lp):
        return {k: v if k in lm._FLOAT32_LEAVES else v.astype(cfg.dtype)
                for k, v in lp.items()}

    with swapped(*swap) if swap else contextlib.nullcontext():
        got = {
            "conv_rel": jax.jit(lambda lp, x: lm._conv(cfg, lp, x)[0])(
                cast(conv), x.astype(cfg.dtype)),
            "attn_rel": jax.jit(
                lambda lp, x: lm._mha(cfg, lp, x, jnp.arange(length))
            )(cast(attn), x.astype(cfg.dtype)),
        }
    return {
        name: float(
            jnp.max(jnp.abs(got[name].astype(jnp.float32) - want[name]))
            / jnp.max(jnp.abs(want[name]))
        )
        for name in want
    }


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
                jnp.copy(flat), state, self._aux, features, labels
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


def reference_program(ref, sizes):
    """The reference's loss and gradient, one sequence: each layer
    under `jax.checkpoint`, so that what the backward pass keeps fits."""

    def one(p, tokens, targets):
        with swapped(ref, "conv_mixer", jax.checkpoint(ref.conv_mixer)), swapped(
            ref, "grouped_attention",
            jax.checkpoint(ref.grouped_attention, static_argnums=(2,)),
        ), swapped(
            ref, "one_head", jax.checkpoint(ref.one_head)
        ), swapped(
            ref, "expert_layer", jax.checkpoint(ref.expert_layer, static_argnums=(2,))
        ), swapped(ref, "gated_mlp", jax.checkpoint(ref.gated_mlp)):
            return ref.parts(p, tokens, targets, sizes)

    return jax.jit(jax.value_and_grad(one, has_aux=True))


def reference_step(grad_fn, params, features, labels, held):
    total = None
    first, count = held
    for i in range(features.shape[0]):
        (value, loads), grads = grad_fn(params, features[i:i + 1], labels[i:i + 1])
        out = jax.device_get({
            "loss": value, "loads": loads[:, first:first + count],
            "grad": ravel_pytree(grads)[0],
        })
        part = {"loss": float(out["loss"]),
                "loads": np.asarray(out["loads"], np.float64),
                "grad": np.asarray(out["grad"], np.float32)}
        total = part if total is None else {k: total[k] + part[k] for k in total}
    n = features.shape[0]
    return {k: v if k == "loads" else v / n for k, v in total.items()}


class _Hashable(dict):
    """The reference's settings as a static argument of a checkpoint."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def compare_seed(zoo, programs, seed, small):
    from elasticdl_tpu.data.recordio import RecordIOReader

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
    flat = jnp.asarray(ravel_pytree(params)[0])
    if "steps" not in programs:
        programs["steps"] = {
            "timed": WorkerStep(zoo, timed, variables),
            "float32": WorkerStep(zoo, models["float32"], variables),
            "unnormalised": WorkerStep(zoo, models["unnormalised"], variables),
            **{name: WorkerStep(zoo, timed, variables, swap)
               for name, swap in SWAPS.items()},
        }
    steps = programs["steps"]
    say(f"seed {seed}: {flat.size} parameters, batch {features.shape}, "
        f"{jax.devices()[0].device_kind}")
    ref, ref_sizes = programs["ref"], programs["sizes"]
    with jax.default_matmul_precision("highest"):
        want = reference_step(
            programs["reference"], params, features, labels, timed.cfg.held
        )
    router_w = jnp.asarray(
        [run for run in params["stack"] if "router" in run][0]["router"][0],
        jnp.float32,
    )
    flips = {
        name: router_flips(ref, router_w, timed.cfg.moe_top_k, seed, route)
        for name, route in (("own", moe.route_sigmoid_topk), ("bf16", route_bf16))
    }
    length = features.shape[1]
    mixers = {"timed": mixer_errors(ref, timed.cfg, ref_sizes, seed, length)}
    with jax.default_matmul_precision("highest"):
        mixers["float32"] = mixer_errors(
            ref, models["float32"].cfg, ref_sizes, seed, length
        )
    for name in ("activated_taps", "shifted_taps", "no_qk_norm",
                 "interleaved_groups"):
        mixers[name] = mixer_errors(
            ref, timed.cfg, ref_sizes, seed, length, SWAPS[name]
        )
    del params
    found, stats = {}, {}

    def judge(name, precision=None):
        with jax.default_matmul_precision(precision) if precision else (
            contextlib.nullcontext()
        ):
            result = steps[name](flat, features, labels)
        result["router_flips"] = flips["bf16" if name == "bf16_router" else "own"]
        result.update(mixers.get(name, mixers["timed"]))
        found[name] = measures(result, want)
        stats[name] = {"loss": result["loss"], **result["stats"]}

    judge("float32", "highest")
    for name in ("timed",) + CONTROLS:
        judge(name)

    def beyond(name, limits):  # a NaN is beyond every limit
        return {
            k: found[name][k] for k, limit in limits.items()
            if not found[name][k] <= limit
        }

    flipped = found["float32"]["load_abs"] > 0
    tight = beyond("float32", TIGHT_FLIPPED if flipped else TIGHT)
    out_of_band = {name: beyond(name, BAND) for name in ("timed",) + CONTROLS}
    verdict = {
        "seed": seed,
        "device": jax.devices()[0].device_kind,
        "reference": {"loss": want["loss"], "loads": want["loads"].tolist(),
                      "grad_norm": _norm(want["grad"])},
        "programs": stats,
        "measures": found,
        "float32_flipped": flipped,
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
    models = {
        "timed": zoo.custom_model(**overrides),
        "float32": zoo.custom_model(dtype="float32", **overrides),
        "unnormalised": zoo.custom_model(moe_renormalize=False, **overrides),
    }
    cfg = models["timed"].cfg
    sizes = _Hashable(ref.sizes_of(
        zoo.SIZES, heads=cfg.n_heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, top_k=cfg.moe_top_k, held=cfg.held,
    ))
    programs = {
        "models": models, "ref": ref, "sizes": sizes,
        "reference": reference_program(ref, sizes),
    }
    verdicts = [
        compare_seed(zoo, programs, args.seed + i, args.small)
        for i in range(args.seeds)
    ]
    ok = all(v["ok"] for v in verdicts)
    out = os.path.join(ROOT, "chiprun_out", "lfm2_compare.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        for v in verdicts:
            f.write(json.dumps(v) + "\n")
    say(f"{'PASS' if ok else 'FAIL'}: {len(verdicts)} seed(s); limits TIGHT "
        f"{TIGHT}, on a seed with a flipped assignment {TIGHT_FLIPPED}, "
        f"BAND {BAND}; written to {out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
