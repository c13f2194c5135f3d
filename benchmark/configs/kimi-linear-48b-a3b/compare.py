"""On the chip, at the configuration's sizes: what the worker's own
step computes against the plain reference.

    python benchmark/configs/kimi-linear-48b-a3b/compare.py --seed <n> [--seeds k]

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
`jax.default_matmul_precision("highest")`: the recurrence a token at a
time, attention's scores written out, the experts a masked dense sum),
computed one sequence at a time and, so that its activations fit, LAYER
BY LAYER: this script wraps each of the reference's layers in
`jax.checkpoint` and its pass over the tokens in segments of 64 (what is
kept for the backward pass, not what is computed):

1. `float32`: the model with `dtype` float32, same precision: the same
   mathematics in another order, so the two agree to accumulated
   rounding: `TIGHT` (the gradient by `TIGHT_FLIPPED` on a seed on
   which that rounding moved an assignment).
2. `timed`: the model as the cell times it, bfloat16 compute with
   float32 parameters, accumulation, router, decay, state and
   logits-to-loss: inside `BAND`, whose limits lie between the timed
   program's largest reading over the seeds and the smallest of the
   controls, each of which has to come out NOT correct by at least one
   of `BAND`'s limits:
3. `bf16_router`: the router's product, sigmoid, top-8 and gates in
   bfloat16; held, as the routed configuration's, where it can be seen:
   `router_flips`, the assignments on which the router and the
   reference's float32 one differ on inputs both share;
4. `bf16_decay`: the log-decay rounded to bfloat16 before the
   recurrence;
5. `bf16_state`: the recurrence's carried state rounded to bfloat16
   from chunk to chunk;
   both held, as the router is, where they can be seen: the whole
   step's gradient carries 5 % of bfloat16's rounding in every
   projection, under which a rounded decay or state disappears
   (0.0513 and 0.0525 beside the timed program's 0.0508, my chip run,
   PR 38), so `scan_rel` is the recurrence alone: `kda.kda_chunked`,
   the function the step calls, AS THE STEP CALLS IT (float32 inputs,
   no ambient precision: its products state `Precision.HIGHEST`
   themselves, so what runs here is what runs inside the timed
   program), on inputs both sides share, against the reference's pass
   a token at a time under `highest`;
6. `dropped_state`: every chunk starts from a zero state (a chunk
   boundary that drops what was carried);
7. `rotated`: latent attention with the rotation left on;
8. `unnormalised`: gates not renormalised over the chosen eight.

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
from elasticdl_tpu.ops import kda  # noqa: E402
from elasticdl_tpu.parallel import moe  # noqa: E402
from elasticdl_tpu.worker.worker import Worker  # noqa: E402

# float32 against float32: accumulated rounding (gradient 4.5e-5 to
# 7.3e-5 in L2, 4.7e-5 to 3.6e-4 of the largest entry: my chip runs, PR
# 38, the seven seeds of eight on which no assignment differs), and the
# assignments that rounding flips: of 131,072 a step (4 expert layers x
# 4096 tokens x 8) one flipped on one seed of eight, and that token's
# whole gradient then lies at another expert: 0.00195 in L2 and 0.0136
# of the largest entry. A seed whose loads differ (`load_abs` > 0,
# within its limit) is held to TIGHT_FLIPPED's gradient limits, every
# other seed to TIGHT's; both lie under the timed bfloat16 program's
# smallest readings (0.0434 in L2, 0.0412 of the largest entry), which
# is the nearest precision below and so has to fall outside them.
# `load_abs` and `router_flips` are the routed configuration's
TIGHT = {
    "loss_rel": 2e-5,
    "load_abs": 8,
    "router_flips": 8,
    "grad_rel_l2": 0.001,
    "grad_max_rel": 0.002,
    "scan_rel": 4e-5,
}
TIGHT_FLIPPED = {**TIGHT, "grad_rel_l2": 0.01, "grad_max_rel": 0.03}
# bfloat16 compute against the float32 reference: each limit between
# the timed program's largest reading and the smallest of the control
# that separates from it there (PERF.md section 6, PR 38: eight seeds,
# the last two with the recurrence's products at HIGHEST and from the
# committed files; those two read inside the ranges of the six before).
# The gradient in L2 read 0.043 to 0.060 and holds `dropped_state` and
# `unnormalised` (0.73 and more); `rotated` reads 0.082 to 0.097 there,
# too near, and is held by the largest entry (0.19 to 0.28 beside the
# timed program's 0.041 to 0.090). `scan_rel`: the function as the step
# calls it reads 2.6e-6 to 4.9e-6, `bf16_decay` 4.2e-4 to 5.0e-4 and
# `bf16_state` 5.8e-4 to 9.4e-4, so 4e-5 leaves eight times and more
# on either side
BAND = {
    "loss_rel": 1e-3,
    "load_abs": 200,
    "router_flips": 8,
    "grad_rel_l2": 0.12,
    "grad_max_rel": 0.13,
    "scan_rel": 4e-5,
}
CONTROLS = ("bf16_router", "bf16_decay", "bf16_state", "dropped_state",
            "rotated", "unnormalised")
SMALL = dict(
    vocab=97, d_model=64, n_heads=4, d_ff=96, kv_lora_rank=24,
    qk_nope_dim=8, qk_rope_dim=8, v_head_dim=12, n_experts=16,
    held_experts=(4, 4), d_expert=24, moe_top_k=3, kda_heads=4,
    kda_head_dim=16, kda_chunk=16,
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
        "scan_rel": got["scan_rel"],
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


_chunked = kda.kda_chunked


def chunked_bf16_decay(q, k, v, g, beta, **kw):
    # `reduce_precision`, not a cast there and back: the TPU compiler
    # drops such a pair (`xla_allow_excess_precision`) and the control
    # then reads what the program reads (my chip run, PR 38)
    return _chunked(q, k, v, lax.reduce_precision(g, 8, 7), beta, **kw)


def chunked_bf16_state(q, k, v, g, beta, **kw):
    """The state a chunk hands to the next rounded to bfloat16."""
    exact = kda.chunk_step

    def rounded(S, xs):
        S, o = exact(S, xs)
        return lax.reduce_precision(S, 8, 7), o

    with swapped(kda, "chunk_step", rounded):
        return _chunked(q, k, v, g, beta, **kw)


def chunked_dropped_state(q, k, v, g, beta, chunk=64, **kw):
    """Every chunk a sequence of its own: nothing is carried."""
    b, length = q.shape[:2]

    def apart(x):
        return x.reshape((b * length // chunk, chunk) + x.shape[2:])

    o, lowest = _chunked(*(apart(x) for x in (q, k, v, g, beta)), chunk=chunk, **kw)
    return o.reshape((b, length) + o.shape[2:]), lowest


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


def scan_errors(ref, cfg, seed, length, variants):
    """{name: the largest error of `variants[name]`'s outputs over the
    largest output of the reference's pass a token at a time}, on one
    sequence of `length` tokens both share: q, k, v as a layer makes
    them (SiLU of normals, q and k scaled), a log-decay of -a x
    softplus(normal - 3) with a uniform in (1, 16) (the initialiser's
    rates at a step small enough that a state lives for tens of
    tokens). The reference under `highest`; a variant under no
    ambient precision, as the worker's step calls it."""
    heads, hd = cfg.kda_heads, cfg.kda_head_dim
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (1, length, heads, hd)
    q, k, v = (jax.nn.silu(jax.random.normal(key, shape)) for key in keys[:3])
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-12) * hd**-0.5
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-12)
    rate = jax.random.uniform(keys[3], (heads, 1), minval=1.0, maxval=16.0)
    g = -rate * jax.nn.softplus(jax.random.normal(keys[4], shape) - 3.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], shape[:3]))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.delta_rule)(q, k, v, g, beta)
    scale = float(jnp.max(jnp.abs(want)))
    return {
        name: float(jnp.max(jnp.abs(
            jax.jit(lambda *a, f=f: f(*a, chunk=cfg.kda_chunk)[0])(
                q, k, v, g, beta
            ) - want
        ))) / scale
        for name, f in variants.items()
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


def reference_program(ref, sizes, segment):
    """The reference's loss and gradient, one sequence: each layer
    under `jax.checkpoint` and the recurrence's pass over the tokens in
    segments of `segment`, so that what the backward pass keeps fits."""
    plain = ref.delta_rule

    def segmented(q, k, v, g, beta):
        batch, length, heads, dk = q.shape
        if length % segment:
            return plain(q, k, v, g, beta)

        def cut(x):
            x = x.reshape((batch, length // segment, segment) + x.shape[2:])
            return jnp.moveaxis(x, 1, 0)

        @jax.checkpoint
        def a_segment(state, xs):
            return lax.scan(
                ref.delta_step, state, tuple(jnp.moveaxis(x, 1, 0) for x in xs)
            )

        start = jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32)
        _, out = lax.scan(a_segment, start, tuple(cut(x) for x in (q, k, v, g, beta)))
        out = jnp.moveaxis(out, 1, 2)  # [segments, batch, segment, H, dv]
        return jnp.moveaxis(out, 0, 1).reshape(batch, length, heads, -1)

    def one(p, tokens, targets):
        with swapped(ref, "delta_rule", segmented), swapped(
            ref, "delta_attention", jax.checkpoint(ref.delta_attention, static_argnums=(3,))
        ), swapped(
            ref, "latent_attention", jax.checkpoint(ref.latent_attention, static_argnums=(2,))
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
    timed = programs["models"]["timed"]
    variables = timed.init(jax.random.PRNGKey(seed), None)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    flat = jnp.asarray(ravel_pytree(params)[0])
    if "steps" not in programs:
        models = programs["models"]
        programs["steps"] = {
            "timed": WorkerStep(zoo, timed, variables),
            "float32": WorkerStep(zoo, models["float32"], variables),
            "bf16_router": WorkerStep(
                zoo, timed, variables, (moe, "route_sigmoid_topk", route_bf16)),
            "bf16_decay": WorkerStep(
                zoo, timed, variables, (kda, "kda_chunked", chunked_bf16_decay)),
            "bf16_state": WorkerStep(
                zoo, timed, variables, (kda, "kda_chunked", chunked_bf16_state)),
            "dropped_state": WorkerStep(
                zoo, timed, variables, (kda, "kda_chunked", chunked_dropped_state)),
            "rotated": WorkerStep(zoo, models["rotated"], variables),
            "unnormalised": WorkerStep(zoo, models["unnormalised"], variables),
        }
    steps = programs["steps"]
    say(f"seed {seed}: {flat.size} parameters, batch {features.shape}, "
        f"{jax.devices()[0].device_kind}")
    with jax.default_matmul_precision("highest"):
        want = reference_step(
            programs["reference"], params, features, labels, timed.cfg.held
        )
    router_w = jnp.asarray(
        [run for run in params["stack"] if "router" in run][0]["router"][0],
        jnp.float32,
    )
    flips = {
        name: router_flips(programs["ref"], router_w, timed.cfg.moe_top_k, seed, route)
        for name, route in (("own", moe.route_sigmoid_topk), ("bf16", route_bf16))
    }
    scans = scan_errors(
        programs["ref"], timed.cfg, seed, features.shape[1], {
            "own": kda.kda_chunked, "bf16_decay": chunked_bf16_decay,
            "bf16_state": chunked_bf16_state,
            "dropped_state": chunked_dropped_state,
        },
    )
    del params
    found, stats = {}, {}

    def judge(name, precision=None):
        with jax.default_matmul_precision(precision) if precision else (
            contextlib.nullcontext()
        ):
            result = steps[name](flat, features, labels)
        result["router_flips"] = flips["bf16" if name == "bf16_router" else "own"]
        result["scan_rel"] = scans.get(name, scans["own"])
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
        "rotated": zoo.custom_model(mla_rope=True, **overrides),
        "unnormalised": zoo.custom_model(moe_renormalize=False, **overrides),
    }
    cfg = models["timed"].cfg
    sizes = _Hashable(ref.sizes_of(
        zoo.SIZES, heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope=cfg.qk_nope_dim, qk_rope=cfg.qk_rope_dim,
        v_head=cfg.v_head_dim, top_k=cfg.moe_top_k, held=cfg.held,
        kda_heads=cfg.kda_heads, kda_head_dim=cfg.kda_head_dim,
    ))
    programs = {
        "models": models, "ref": ref,
        "reference": reference_program(ref, sizes, 16 if args.small else 64),
    }
    verdicts = [
        compare_seed(zoo, programs, args.seed + i, args.small)
        for i in range(args.seeds)
    ]
    ok = all(v["ok"] for v in verdicts)
    out = os.path.join(ROOT, "chiprun_out", "kimi_compare.jsonl")
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
