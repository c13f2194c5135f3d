"""On the chip, at the configuration's sizes: what the worker's own
step computes against the plain reference.

    python benchmark/configs/ouro-2.6b/compare.py --seed <n> [--seeds k]

One process that holds the chip itself (run it through `chiprun`, never
beside a job). For each seed: the zoo module's weights from the seed
and one minibatch (`minibatch_per_chip` x `seq_len`) of the cell's own
RecordIO data go through **the program a `Worker` builds**
(`Worker._build_local_step()`: `_local_step_core` jitted with its
donations, the step the window program scans 16 times), with the
model's non-trainable collection in `aux`, so `_apply_model`'s
`mutable` path runs as it does in the cell. The one thing swapped is
the zoo's optimizer, for `optax.trace(decay=0)`: its state after one
step IS the flat gradient the step differentiated, bit for bit, where
clipped Adam's first update is `-lr * sign(g)` and says nothing of
`g`'s size. From one call: the loss, the mean exit distribution (what
the step left in `window_stats`, the span's source) and the gradient.
Every exit's cross-entropy is no output of the step: it is read from
`model.apply` alone, forward only. All held against `reference.py`
(float32 under `jax.default_matmul_precision("highest")`, one sequence
at a time so that its unrecomputed activations fit, the sequences'
means averaged):

1. `float32`: the model with `dtype` float32, same precision. The
   same mathematics in another order (scans and rematerialization
   against Python loops, log-sigmoid sums against products), so the
   two agree to accumulated rounding: `TIGHT`. What is computed
   forward (loss, every exit's cross-entropy, the exit distribution)
   is held to a relative 2e-4 of the largest value, the tolerance of
   the CPU tests and of C's (read on the chip: 7e-6 at most). The
   gradient is held to 2e-3 in L2 and 3e-3 of its largest entry: on
   the v5e a float32 matmul under `highest` is six bfloat16 passes and
   float32 transcendentals are approximations, and backward through 16
   layer applications of random weights the two orders came 1.9e-4 to
   3.8e-4 apart in L2, 3.8e-4 to 9.7e-4 of the largest entry (on the CPU,
   in true float32, the same two agree to 1e-6: the tests). bfloat16
   anywhere reads 1.7e-2 and 8.8e-3 at its best, so a lower precision
   or a left-out term on either side still fails.
2. `timed`: the model as the cell times it, bfloat16 compute with
   float32 parameters, accumulation, logits-to-loss and rotary angles.
   Held inside `BAND`, which lies between two readings on the chip (PR
   27, PERF.md section 6): the largest the timed program gave over the
   seeds, and the smallest of two controls, each of which has to come
   out NOT correct by at least one of `BAND`'s limits, or the band
   would let a lower precision pass:
3. `lower`: the same model with `dtype` float8_e5m2, a step really
   computed below bfloat16 (2 bits of mantissa for 7): every weight,
   activation and matmul input of the block is float8, forward and
   backward, the rest as stated (`LOWER` says why not float8_e4m3fn);
4. `rounded`: the timed program on weights rounded through
   float8_e4m3fn (3 bits), compute as timed: the milder control, whose
   readings lie closest to the timed program's and so set the limits
   it decides.

Not compared here: clipped Adam (elementwise on the flat vector, the
same optax code on either side) and the 16-step scan around the step,
which the cell itself runs to its loss check.

Prints one JSON line a seed and one verdict; exit 0 only if 1 and 2
hold and 3 and 4 fail the band, for every seed. `--small` is the CPU
rehearsal of the script's plumbing (tiny sizes; its numbers are no
device numbers and its band is not judged).
"""

import argparse
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
from jax.flatten_util import ravel_pytree  # noqa: E402

from benchmark.harness import data  # noqa: E402
from benchmark.harness.manifest import load_module  # noqa: E402
from elasticdl_tpu.api.model_spec import ModelSpec  # noqa: E402
from elasticdl_tpu.common.constants import WINDOW_STATS  # noqa: E402
from elasticdl_tpu.models.transformer_lm import (  # noqa: E402
    exit_cross_entropies,
)
from elasticdl_tpu.worker.worker import Worker  # noqa: E402

# float32 against float32: accumulated rounding only (see the docstring)
TIGHT = {
    "loss_rel": 2e-4,
    "ce_rel": 2e-4,
    "q_abs": 2e-4,
    "grad_rel_l2": 2e-3,
    "grad_max_rel": 3e-3,
}
# bfloat16 compute against the float32 reference. Each limit is the
# geometric mean of two readings on the chip (PR 27: twelve seeds of
# the timed program and of `rounded`, six of `lower`; PERF.md section
# 6): the timed program's largest, and the smallest of the control
# whose readings separate from it in that measure
BAND = {
    "loss_rel": 3e-4,  # 4.6e-5; `lower` 1.8e-3 (`rounded` 2.3e-5 to 3.9e-4: not against it)
    "ce_rel": 6e-4,  # 6.3e-5; `lower` 6.4e-3 (`rounded` 1.5e-4 to 6.3e-4: not against it)
    "q_abs": 2e-3,  # 1.34e-3; `rounded` 2.98e-3 (`lower` 1.5e-3 to 5.3e-2: not against it)
    "grad_rel_l2": 7e-2,  # 2.4e-2; `rounded` 2.0e-1 (`lower` 9.8e-1)
}
# the nearest formats below bfloat16 are the two float8s. The one with
# bfloat16's kind of range and an infinity is taken for the compute
# dtype: float8_e4m3fn has none, and the attention mask's -1e30 is a
# NaN in it before anything is computed
LOWER = "float8_e5m2"
CONTROLS = ("lower", "rounded")  # each has to fall outside BAND
SMALL = dict(vocab=97, d_model=64, n_heads=4, d_ff=96, n_layers=2)


def say(msg):
    print(f"compare: {msg}", file=sys.stderr, flush=True)


def measures(got, want):
    """How far `got` is from the reference's `want`: each a dict of
    loss, ce [T], q [T], grad (flat)."""
    gap = np.asarray(got["grad"], np.float64) - want["grad"]
    return {
        "loss_rel": abs(got["loss"] - want["loss"]) / abs(want["loss"]),
        "ce_rel": float(np.max(np.abs(got["ce"] - want["ce"])) / np.max(np.abs(want["ce"]))),
        "q_abs": float(np.max(np.abs(got["q"] - want["q"]))),
        "grad_rel_l2": float(np.linalg.norm(gap) / np.linalg.norm(want["grad"])),
        "grad_max_rel": float(np.max(np.abs(gap)) / np.max(np.abs(want["grad"]))),
    }


def gradient_keeper():
    """In the zoo's optimizer's place: the state after a step is the
    gradient the step was given (0 * zeros + g), bit for bit."""
    return optax.trace(decay=0.0)


class WorkerStep:
    """The per-step program of a `Worker` for `model`, built by the
    worker's own builder: {loss, ce, q, grad} of one minibatch."""

    def __init__(self, zoo, model, variables):
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
        self._step = worker._build_local_step()  # donates flat and state
        self._aux = {k: v for k, v in variables.items() if k != "params"}
        self._exits = jax.jit(
            lambda flat, features, labels: jnp.mean(
                exit_cross_entropies(
                    model.apply({"params": worker._unravel(flat)}, features).logits,
                    labels,
                ),
                axis=(1, 2),
            )
        )

    def __call__(self, flat, features, labels):
        ce = self._exits(flat, features, labels)
        state = gradient_keeper().init(flat)
        _flat, state, aux, loss = self._step(
            jnp.copy(flat), state, self._aux, features, labels
        )
        return host({
            "loss": loss, "ce": ce, "q": aux[WINDOW_STATS]["exit_q"],
            "grad": state.trace,
        })


def host(result):
    out = {k: np.asarray(v, np.float64) for k, v in jax.device_get(result).items()}
    out["loss"] = float(out["loss"])
    return out


def reference_program(ref, cfg):
    def one(p, tokens, targets):
        value, ce, q = ref.parts(
            p, tokens, targets, cfg.n_heads, cfg.n_loops,
            beta=cfg.exit_entropy_weight, rope_base=cfg.rope_base,
            eps=cfg.norm_eps,
        )
        return value, (ce, q)

    return jax.jit(jax.value_and_grad(one, has_aux=True))


def reference_step(grad_fn, params, features, labels):
    """The reference, one sequence at a time; equal lengths, so the
    batch's mean is the mean of the sequences' means."""
    total = None
    for i in range(features.shape[0]):
        (value, (ce, q)), grads = grad_fn(
            params, features[i:i + 1], labels[i:i + 1]
        )
        part = host({
            "loss": value, "ce": ce, "q": q, "grad": ravel_pytree(grads)[0],
        })
        total = part if total is None else {
            k: total[k] + part[k] for k in total
        }
    return {k: v / features.shape[0] for k, v in total.items()}


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
    variables = programs["models"]["timed"].init(jax.random.PRNGKey(seed), None)
    params = variables["params"]
    flat = jnp.asarray(ravel_pytree(params)[0])
    if "steps" not in programs:
        programs["steps"] = {
            name: WorkerStep(zoo, model, variables)
            for name, model in programs["models"].items()
        }
    steps = programs["steps"]
    say(f"seed {seed}: {flat.size} parameters, batch {features.shape}, "
        f"{jax.devices()[0].device_kind}")

    with jax.default_matmul_precision("highest"):
        want = reference_step(programs["reference"], params, features, labels)
        same = steps["float32"](flat, features, labels)
    got = {
        "float32": same,
        "timed": steps["timed"](flat, features, labels),
        "lower": steps["lower"](flat, features, labels),
        "rounded": steps["timed"](
            jnp.asarray(
                np.asarray(flat).astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
            ),
            features, labels,
        ),
    }
    found = {name: measures(result, want) for name, result in got.items()}

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
        "reference": {"loss": want["loss"], "ce": want["ce"].tolist(),
                      "q": want["q"].tolist(),
                      "grad_norm": float(np.linalg.norm(want["grad"]))},
        "timed": {"loss": got["timed"]["loss"], "ce": got["timed"]["ce"].tolist(),
                  "q": got["timed"]["q"].tolist()},
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
        "lower": zoo.custom_model(dtype=LOWER, **overrides),
    }
    programs = {
        "models": models,
        "reference": reference_program(ref, models["timed"].cfg),
    }
    verdicts = [
        compare_seed(zoo, programs, args.seed + i, args.small)
        for i in range(args.seeds)
    ]
    ok = all(v["ok"] for v in verdicts)
    out = os.path.join(ROOT, "chiprun_out", "ouro_compare.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        for v in verdicts:
            f.write(json.dumps(v) + "\n")
    say(f"{'PASS' if ok else 'FAIL'}: {len(verdicts)} seed(s); limits TIGHT "
        f"{TIGHT}, BAND {BAND}; written to {out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
