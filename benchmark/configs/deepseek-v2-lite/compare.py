"""On the chip, at the configuration's sizes: what the worker's own
step computes against the plain reference.

    python benchmark/configs/deepseek-v2-lite/compare.py --seed <n> [--seeds k]

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
(`window_stats`, the span's source). The cross-entropy alone is read
from `model.apply`, forward only. All held against `reference.py`
(float32 under `jax.default_matmul_precision("highest")`, one sequence
at a time so that its unrecomputed activations fit, the sequences'
means averaged and their expert loads summed):

1. `float32`: the model with `dtype` float32, same precision. The same
   mathematics in another order (a sort and grouped matmuls against a
   masked dense sum, scans with rematerialization against Python
   loops), so the two agree to accumulated rounding: `TIGHT`, ten times
   the largest reading of ten seeds on the chip (PR 33, calls 4 and 5:
   loss 1.4e-7, balance term 4.3e-6, gradient 7.3e-6 in L2 and 1.7e-5
   of its largest entry, not one token routed otherwise) and a tenth of the
   looped configuration's limits: this block has 5 layers between the
   loss and a weight where that one has 16 applications. A token whose
   sixth and seventh probabilities lie within that rounding may take
   another expert on the two sides; `load_abs` allows a handful.
2. `timed`: the model as the cell times it, bfloat16 compute with
   float32 parameters, accumulation, router, logits-to-loss and rotary
   angles. Held inside `BAND`, whose limits lie between two readings on
   the chip (PERF.md section 6): the largest the timed program gave
   over the seeds, and the smallest of the controls below, each of
   which has to come out NOT correct by at least one of `BAND`'s limits,
   or the band would let a lower precision pass:
3. `rounded`: the timed program on weights rounded through
   float8_e4m3fn (3 bits of mantissa for bfloat16's 7), compute as
   timed: a step really computed below bfloat16;
4. `bf16_loss`: the timed program with logits-to-loss in bfloat16
   (`token_cross_entropy` replaced here, in this script, by such a
   one);
5. `bf16_router`: the timed program with the router's product, softmax
   and top-k in bfloat16 (`parallel.moe.route_topk` replaced
   likewise). Inside the whole step it cannot be told from the timed
   program: the float32 router is fed bfloat16 activations, which
   already move 36-65 of a layer's 9,000 held assignments to another
   expert, and a bfloat16 router moves 52-99 (calls 4 and 5, ten seeds,
   the ranges overlapping). So the router's precision is
   held where it can be seen, on inputs both sides share:
   `router_flips`, the assignments on which `route_topk` and the
   reference's float32 softmax and greedy top-6 differ for 8192
   unit-variance rows rounded to bfloat16 and the seed's first
   router. The program's router has to stay within `BAND`'s limit and
   the bfloat16 one has to pass it.

Not compared here: clipped Adam (elementwise on the flat vector, the
same optax code on either side) and the 16-step scan around the step,
which the cell itself runs to its loss check.

Prints one JSON line a seed and one verdict; exit 0 only if 1 and 2
hold and 3, 4 and 5 fail the band, for every seed. `--small` is the CPU
rehearsal of the script's plumbing (tiny sizes; its numbers are no
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
import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax import lax  # noqa: E402
from jax.flatten_util import ravel_pytree  # noqa: E402

from benchmark.harness import data  # noqa: E402
from benchmark.harness.manifest import load_module  # noqa: E402
from elasticdl_tpu.api.model_spec import ModelSpec  # noqa: E402
from elasticdl_tpu.common.constants import WINDOW_STATS  # noqa: E402
from elasticdl_tpu.models import transformer_lm_zoo  # noqa: E402
from elasticdl_tpu.parallel import moe  # noqa: E402
from elasticdl_tpu.worker.worker import Worker  # noqa: E402

# float32 against float32: accumulated rounding only (see the docstring)
TIGHT = {
    "loss_rel": 2e-5,
    "ce_rel": 2e-5,
    "balance_rel": 1e-4,
    "load_abs": 8,
    "router_flips": 8,
    "grad_rel_l2": 2e-4,
    "grad_max_rel": 3e-4,
}
# bfloat16 compute against the float32 reference. Each limit is the
# geometric mean of two readings on the chip (PR 33, calls 4 and 5, ten
# seeds; PERF.md section 6): the timed program's largest, and the
# smallest of the control whose readings separate from it there
BAND = {
    "loss_rel": 1.3e-4,  # 7.2e-5; `bf16_loss` 2.2e-4 (`rounded` 9.8e-5 to 5.5e-4: not against it)
    "balance_rel": 4e-4,  # 2.5e-4; `rounded` 5.5e-4 (narrow: `rounded` is held by the next two)
    "load_abs": 125,  # 65; `rounded` 241 (`bf16_router` 52 to 99: not against it)
    "router_flips": 8,  # the program's router 0 in five seeds; `bf16_router` 174 to 190
    "grad_rel_l2": 0.15,  # 0.052; `rounded` 0.424
}
CONTROLS = ("rounded", "bf16_loss", "bf16_router")  # each has to fall outside BAND
SMALL = dict(
    vocab=97, d_model=64, n_heads=4, d_ff=96, n_layers=3, kv_lora_rank=24,
    qk_nope_dim=8, qk_rope_dim=8, v_head_dim=12, n_experts=16,
    held_experts=(4, 4), d_expert=24, moe_top_k=3,
)


def say(msg):
    print(f"compare: {msg}", file=sys.stderr, flush=True)


def _norm(vector, chunk=1 << 24):
    """The L2 norm of a float32 vector, summed in float64 a piece at a
    time: 535 M entries as float64 would be 4.3 GB for each vector
    held, and the machine has 40."""
    total = 0.0
    for i in range(0, vector.shape[0], chunk):
        piece = vector[i:i + chunk].astype(np.float64)
        total += float(piece @ piece)
    return total**0.5


def measures(got, want):
    """How far `got` is from the reference's `want`: each a dict of
    loss, ce, balance, loads [layers, held], grad (flat, float32)."""
    gap = got["grad"] - want["grad"]
    return {
        "loss_rel": abs(got["loss"] - want["loss"]) / abs(want["loss"]),
        "ce_rel": abs(got["ce"] - want["ce"]) / abs(want["ce"]),
        "balance_rel": abs(got["balance"] - want["balance"]) / abs(want["balance"]),
        # tokens of a layer's held experts that the two sides count
        # differently, the worst layer's
        "load_abs": float(np.max(np.sum(np.abs(got["loads"] - want["loads"]), axis=-1))),
        "router_flips": got["router_flips"],
        "grad_rel_l2": _norm(gap) / _norm(want["grad"]),
        "grad_max_rel": float(np.max(np.abs(gap)) / np.max(np.abs(want["grad"]))),
    }


def gradient_keeper():
    """In the zoo's optimizer's place: the state after a step is the
    gradient the step was given (0 * zeros + g), bit for bit."""
    return optax.trace(decay=0.0)


def route_topk_bf16(x, router_w, top_k):
    """`moe.route_topk` with everything in bfloat16: a control."""
    logits = x.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, chosen = lax.top_k(probs, top_k)
    return (probs.astype(jnp.float32), gate.astype(jnp.float32),
            chosen.astype(jnp.int32))


def cross_entropy_bf16(logits, targets):
    """`token_cross_entropy` in bfloat16: a control."""
    logits = logits.astype(jnp.bfloat16)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold).astype(jnp.float32)


def router_flips(ref, router_w, top_k, seed, route):
    """On how many of 8192 x `top_k` assignments `route` (the program's
    `route_topk`, or a control) and the reference's float32 softmax and
    greedy top-k differ, given the same rows: unit-variance normals
    rounded to bfloat16, as a layer's normed activations reach the
    router in the timed program."""
    x = jax.random.normal(
        jax.random.PRNGKey(seed), (8192, router_w.shape[0]), jnp.bfloat16
    )
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(x.astype(jnp.float32) @ router_w, axis=-1)
        want = ref.greedy_top_k(probs, top_k)
    _probs, _gate, chosen = jax.jit(route, static_argnums=2)(x, router_w, top_k)
    got = jnp.sum(jax.nn.one_hot(chosen, router_w.shape[1]), axis=1)
    return float(jnp.sum(jnp.abs(got - want)) / 2)


@contextlib.contextmanager
def swapped(module, name, other):
    """`module.name` is `other` while a program is traced."""
    kept = getattr(module, name)
    setattr(module, name, other)
    try:
        yield
    finally:
        setattr(module, name, kept)


class WorkerStep:
    """The per-step program of a `Worker` for `model`, built by the
    worker's own builder: {loss, ce, balance, loads, grad} of one
    minibatch. `swap`: (module, name, other) in force while it
    traces."""

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
        weight = model.cfg.aux_weight

        def parts(flat, features, labels):
            logits, aux = model.apply({"params": worker._unravel(flat)}, features)
            return (
                transformer_lm_zoo.token_cross_entropy(logits, labels),
                aux / weight,
            )

        self._parts = jax.jit(parts)

    def __call__(self, flat, features, labels):
        with swapped(*self._swap) if self._swap else contextlib.nullcontext():
            ce, balance = self._parts(flat, features, labels)
            state = gradient_keeper().init(flat)
            _flat, state, aux, loss = self._step(
                jnp.copy(flat), state, self._aux, features, labels
            )
        return host({
            "loss": loss, "ce": ce, "balance": balance,
            "loads": aux[WINDOW_STATS]["expert_tokens"], "grad": state.trace,
        })


def host(result):
    out = jax.device_get(result)
    return {
        "loss": float(out["loss"]), "ce": float(out["ce"]),
        "balance": float(out["balance"]),
        "loads": np.asarray(out["loads"], np.float64),
        "grad": np.asarray(out["grad"], np.float32),
    }


def reference_program(ref, sizes):
    def one(p, tokens, targets):
        value, ce, balance, loads = ref.parts(p, tokens, targets, sizes)
        return value, (ce, balance, loads)

    return jax.jit(jax.value_and_grad(one, has_aux=True))


def reference_step(grad_fn, params, features, labels, held):
    """The reference, one sequence at a time; equal lengths, so the
    batch's mean is the mean of the sequences' means (the balance term
    is a mean over sequences already) and its loads their sum."""
    total = None
    first, count = held
    for i in range(features.shape[0]):
        (value, (ce, balance, loads)), grads = grad_fn(
            params, features[i:i + 1], labels[i:i + 1]
        )
        part = host({
            "loss": value, "ce": ce, "balance": balance,
            "loads": loads[:, first:first + count],
            "grad": ravel_pytree(grads)[0],
        })
        total = part if total is None else {
            k: total[k] + part[k] for k in total
        }
    n = features.shape[0]
    return {k: v if k == "loads" else v / n for k, v in total.items()}


def compare_seed(zoo, programs, seed, small):
    """`programs`: the models and the reference's jitted gradient,
    made once; the workers' steps are added at the first seed, whose
    tree every seed shares."""
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
        programs["steps"] = {
            "timed": WorkerStep(zoo, timed, variables),
            "float32": WorkerStep(zoo, programs["models"]["float32"], variables),
            "bf16_router": WorkerStep(
                zoo, timed, variables, (moe, "route_topk", route_topk_bf16)
            ),
            "bf16_loss": WorkerStep(
                zoo, timed, variables,
                (transformer_lm_zoo, "token_cross_entropy", cross_entropy_bf16),
            ),
        }
    steps = programs["steps"]
    say(f"seed {seed}: {flat.size} parameters, batch {features.shape}, "
        f"{jax.devices()[0].device_kind}")

    with jax.default_matmul_precision("highest"):
        want = reference_step(
            programs["reference"], params, features, labels, timed.cfg.held
        )
    router_w = jnp.asarray(params["layers"]["router"][0], jnp.float32)
    flips = {
        name: router_flips(programs["ref"], router_w, timed.cfg.moe_top_k, seed, route)
        for name, route in (("own", moe.route_topk), ("bf16", route_topk_bf16))
    }
    del params  # 2.1 GB the float32 step's activations need
    found, timed_parts = {}, None

    def judge(name, step, vector, precision=None):
        # one program's gradient on the host at a time, beside the
        # reference's: each is 2.1 GB
        with jax.default_matmul_precision(precision) if precision else (
            contextlib.nullcontext()
        ):
            result = step(vector, features, labels)
        result["router_flips"] = flips["bf16" if name == "bf16_router" else "own"]
        found[name] = measures(result, want)
        del result["grad"]
        return result

    judge("float32", steps["float32"], flat, "highest")
    timed_parts = judge("timed", steps["timed"], flat)
    judge("rounded", steps["timed"], jnp.asarray(
        np.asarray(flat).astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
    ))
    judge("bf16_loss", steps["bf16_loss"], flat)
    judge("bf16_router", steps["bf16_router"], flat)

    def beyond(name, limits):  # a NaN is beyond every limit
        return {
            k: found[name][k] for k, limit in limits.items()
            if not found[name][k] <= limit
        }

    tight = beyond("float32", TIGHT)
    out_of_band = {name: beyond(name, BAND) for name in ("timed",) + CONTROLS}
    verdict = {
        "seed": seed,
        "device": jax.devices()[0].device_kind,
        "reference": {"loss": want["loss"], "ce": want["ce"],
                      "balance": want["balance"],
                      "loads": want["loads"].tolist(),
                      "grad_norm": _norm(want["grad"])},
        "timed": {"loss": timed_parts["loss"], "ce": timed_parts["ce"],
                  "balance": timed_parts["balance"],
                  "loads": timed_parts["loads"].tolist()},
        "measures": found,
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
    }
    cfg = models["timed"].cfg
    sizes = ref.sizes_of(
        zoo.SIZES, heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope=cfg.qk_nope_dim, qk_rope=cfg.qk_rope_dim,
        v_head=cfg.v_head_dim, top_k=cfg.moe_top_k, held=cfg.held,
    )
    programs = {
        "models": models, "ref": ref,
        "reference": reference_program(ref, sizes),
    }
    verdicts = [
        compare_seed(zoo, programs, args.seed + i, args.small)
        for i in range(args.seeds)
    ]
    ok = all(v["ok"] for v in verdicts)
    out = os.path.join(ROOT, "chiprun_out", "deepseek_compare.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        for v in verdicts:
            f.write(json.dumps(v) + "\n")
    say(f"{'PASS' if ok else 'FAIL'}: {len(verdicts)} seed(s); limits TIGHT "
        f"{TIGHT}, BAND {BAND}; written to {out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
