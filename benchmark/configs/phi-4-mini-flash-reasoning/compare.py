"""On the chip, at the configuration's sizes: what the worker's own
step computes against the plain reference.

    python benchmark/configs/phi-4-mini-flash-reasoning/compare.py --seed <n> [--seeds k]

One process that holds the chip itself (run it through `chiprun`, never
beside a job). For each seed: the zoo module's weights from the seed
(the zeros and ones of the initialiser moved off them, so that a bias or
a norm's weight cannot hide) and one minibatch (`minibatch_per_chip` x
`seq_len` = 1 x 4096) of the cell's own RecordIO data go through **the
program a `Worker` builds** (`Worker._build_local_step()`, the step the
window program scans 16 times), with the model's non-trainable
collection in `aux`, so `_apply_model`'s `mutable` path runs as it does
in the cell. The one thing swapped is the zoo's optimizer, for
`optax.trace(decay=0)`: its state after one step IS the flat gradient
the step differentiated. From that call the loss and the gradient, from
the model's own `apply` the logits. All held against `reference.py`
(float32 under `jax.default_matmul_precision("highest")`: the published
table walked a layer at a time, the recurrence a token at a time,
differential attention a pair of heads at a time), differentiated by
this script with every layer, every pair's block of scores and every
segment of 64 tokens of the recurrence under `jax.checkpoint` (what is
kept for the backward pass, not what is computed), so that 4096 tokens
fit beside 577 M float32 parameters and their gradient.

Beside the whole step, the SCAN alone (`ops/selective_scan`'s form for
this backend on float32 operands against the reference's recurrence a
token at a time) at the untrained steps and rates: `scan_rel`. The
whole step carries bfloat16's rounding of every projection, under which
the decay's arithmetic can hide; the scan alone cannot.

1. `timed`: the model as the cell times it, bfloat16 compute with the
   float32 parts `config.json` lists: inside `BAND`, whose limits lie
   between the timed program's largest reading over the seeds and the
   smallest of the controls, each of which has to come out NOT correct
   by at least one of `BAND`'s limits:
2. `bf16_decay`: every exp(dt A) rounded to bfloat16 (its argument
   too), the nearest precision below the float32 the configuration
   states for them; held by `scan_rel`;
3. `no_lambda`: lambda = 0, plain attention of the pairs' first maps;
4. `no_pair_norm`: no RMS norm over a pair's 128 output columns;
5. `memory_after_gate`: M = y x SiLU(z), not y;
6. `cross_own_keys`: the cross layer reads keys and values made of its
   own queries, not layer 17's;
7. `no_window`: the windowed layer sees the whole causal triangle;
8. `rms_norm`: RMS norms (no mean, no bias) for the LayerNorms;
9. `rotary`: queries and keys turned at theta 1e4.

Not compared here: clipped Adam and the 16-step scan around the step,
which the cell itself runs to its loss check.

Prints one JSON line a seed and one verdict; exit 0 only if 1 holds and
every control fails, for every seed. The controls are judged by their
logits and their loss (one forward program each) and are given the
timed step's gradient, but `memory_after_gate`, whose whole step is run
(`STEP_CONTROLS`: the gradient's second reading); `--small` is the CPU
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
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.flatten_util import ravel_pytree  # noqa: E402

from benchmark.harness import data  # noqa: E402
from benchmark.harness.compare_common import (  # noqa: E402
    Hashable,
    WorkerStep,
    gradient_keeper,
    leaf_of,
    norm,
    say,
    swapped,
)
from benchmark.harness.manifest import load_module  # noqa: E402
from elasticdl_tpu.common.constants import WINDOW_STATS  # noqa: E402
from elasticdl_tpu.models import transformer_lm as lm  # noqa: E402
from elasticdl_tpu.ops import selective_scan as ss  # noqa: E402

# The limits were set from this PR's first seed on the chip (my chip
# runs, PR 58, call 1, seed 2147484101) and then held to three seeds
# not used while they were set (call 2, seeds 2147484201 to ...203: PASS
# on all three; PERF.md section 6). bfloat16 compute against the float32
# reference: each limit lies between the timed program's largest reading
# over the four seeds and the smallest of the controls that separate
# from it there, with room on both sides. Logits 0.0266 to 0.0429 of the
# largest entry and 0.0244 to 0.0254 in L2 beside `cross_own_keys`' 0.213
# to 0.238 | 0.226 to 0.229 (the nearest control; `memory_after_gate`
# 0.385 to 0.417 | 0.351 to 0.362, `rms_norm` 0.397 to 0.499 | 0.337 to
# 0.504, the four others 1.1 to 1.4). The loss hardly feels the
# precision (timed 1.9e-5 to 7.7e-5; the controls 4e-6 to 3.9e-3, so no
# control is told by its loss alone; the limit keeps the timed program
# to the loss and stands 2.6 times over its largest reading). The
# gradient 0.0354 to 0.0375 in L2 and its worst leaf 0.045 to 0.103 (a
# layer's `diff`, 384 numbers whose gradient is 1e-4 of the whole's, or
# a norm's bias) beside the one control whose whole step is run,
# `memory_after_gate`: 0.460 to 0.470 | 0.98 to 1.35. The scan alone has
# float32 operands on both sides: the kernels step through the tokens in
# the recurrence's own order and read 0.0 (bit for bit) on all four
# seeds where `bf16_decay` reads 0.0043 to 0.0060. (`bf16_decay`'s
# logits: 0.026 to 0.103 | 0.025 to 0.038, inside the band on three
# seeds of four, which is why the scan is held alone.)
BAND = {
    "loss_rel": 2e-4,
    "logits_rel": 0.08,
    "logits_l2": 0.075,
    "grad_rel_l2": 0.1,
    "grad_leaf_worst": 0.25,
    "scan_rel": 1e-4,
}
CONTROLS = ("bf16_decay", "no_lambda", "no_pair_norm", "memory_after_gate",
            "cross_own_keys", "no_window", "rms_norm", "rotary")
# the control whose whole step is run, for the gradient's second
# reading (a step's program takes a minute to compile); the others are
# judged by their logits and are given the timed step's gradient
STEP_CONTROLS = ("memory_after_gate",)
SMALL = dict(
    vocab=97, d_model=32, d_ff=48, n_heads=4, n_kv_heads=2, swa_heads=4,
    swa_window=8, ssm1_inner=64, ssm1_state=4, ssm1_dt_rank=2,
)


# ------------------------------------------------------------- the controls


def bf16(a):
    """Rounded to bfloat16's 8 bits of mantissa, in float32
    (`reduce_precision`, not a cast there and back: the TPU compiler
    drops such a pair)."""
    return lax.reduce_precision(a, 8, 7)


def decay_bf16(dt, A):
    return bf16(jnp.exp(bf16(dt * A)))


def scan_bf16_decay(x, dt, A, Bm, Cm, h0=None):
    """The plain-jax form with every decay in bfloat16 (the kernels
    take their exponentials inside, so the control runs the chunked
    form on every backend)."""
    with swapped(ss, "_decay", decay_bf16):
        return ss.selective_scan_chunked(x, dt, A, Bm, Cm, h0)


def no_lambda(diff, lambda_init, hd):
    return jnp.zeros((), jnp.float32)


def no_pair_norm(o, weight, eps):
    return o


def memory_after_gate(y, gated):
    return gated


def cross_own_keys(shared_kv, q):
    """Keys of the layer's own: every second query head, and values
    of those, a pair's two as one."""
    k, v = shared_kv
    own = q[:, :, ::q.shape[2] // k.shape[2]]
    return own, own.reshape(v.shape)


def rms_for_layer_norm(x, weight, bias, eps):
    return lm.rms_norm(x, weight, eps)


# a control is a model override or a swap (module, name, other)
OVERRIDES = {
    "no_window": dict(swa_window=1 << 30),
    "rotary": dict(rope=True),
}
SWAPS = {
    "bf16_decay": (ss, "selective_scan", scan_bf16_decay),
    "no_lambda": (lm, "_diff_lambda", no_lambda),
    "no_pair_norm": (lm, "_diff_pair_norm", no_pair_norm),
    "memory_after_gate": (lm, "_mamba1_memory", memory_after_gate),
    "cross_own_keys": (lm, "_cross_kv", cross_own_keys),
    "rms_norm": (lm, "layer_norm", rms_for_layer_norm),
}


def control(name):
    """The swap of control `name` in force (nothing for `timed` and
    for a control that is a model override)."""
    return swapped(*SWAPS[name]) if name in SWAPS else contextlib.nullcontext()


# ------------------------------------------------------------ the program


class Step(WorkerStep):
    """`WorkerStep` for a model with no expert layer: {loss, grad,
    stats} of one minibatch."""

    def __call__(self, flat, features, labels):
        state = gradient_keeper().init(flat)
        with swapped(*self._swap) if self._swap else contextlib.nullcontext():
            _flat, state, aux, loss = self._step(
                jnp.asarray(flat), state, self._aux, features, labels
            )
        out = jax.device_get(
            {"loss": loss, "grad": state.trace, "stats": aux[WINDOW_STATS]}
        )
        return {
            "loss": float(out["loss"]),
            "grad": np.asarray(out["grad"], np.float32),
            "stats": {k: float(v) for k, v in out["stats"].items()},
        }


def forward_program(zoo, model, variables, name):
    """(params, tokens, targets) -> (loss, logits float32) of `model`
    under control `name`, traced once."""
    aux = {k: v for k, v in variables.items() if k != "params"}

    def forward(params, tokens, targets):
        with control(name):
            logits = model.apply({**aux, "params": params}, tokens)
        return zoo.loss(logits, targets), logits.astype(jnp.float32)

    return jax.jit(forward)


def scan_errors(ref, cfg, seed, length, variants):
    """{name: the largest error of `variants[name]`'s outputs over the
    largest output of the reference's recurrence a token at a time}, on
    one sequence both share: x, B and C as a layer makes them (SiLU of
    normals, normals), float32; the step log-uniform on (0.001, 0.1) and
    column n's rate n + 1, the initialiser's."""
    inner, n = cfg.ssm1_inner, cfg.ssm1_state
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.nn.silu(jax.random.normal(keys[0], (1, length, inner)))
    Bm, Cm = (jax.random.normal(key, (1, length, n)) for key in keys[1:3])
    dt = jnp.exp(jax.random.uniform(
        keys[3], (1, length, inner), minval=jnp.log(0.001), maxval=jnp.log(0.1)
    ))
    A = -jnp.broadcast_to(
        jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (n, inner)
    )
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.selective_scan)(x, dt, A.T, Bm, Cm)
        scale = float(jnp.max(jnp.abs(want)))
        return {
            name: float(jnp.max(jnp.abs(
                jax.jit(lambda *a, f=f: f(*a)[0])(x, dt, A, Bm, Cm) - want
            ))) / scale
            for name, f in variants.items()
        }


# ---------------------------------------------------------- the reference


def segmented_scan(ref, segment=64):
    """The reference's recurrence with its pass over the tokens in
    segments of `segment` under `jax.checkpoint`: what the backward
    pass keeps is a state a segment, not a state a token."""
    plain = ref.selective_scan

    def segmented(x, dt, A, b, c):
        batch, length, _ = x.shape
        if length % segment:
            return plain(x, dt, A, b, c)

        def cut(t):
            t = t.reshape((batch, length // segment, segment) + t.shape[2:])
            return jnp.moveaxis(t, 1, 0)

        @jax.checkpoint
        def a_segment(state, xs):
            return lax.scan(
                lambda s, row: ref.scan_step(s, row, A), state,
                tuple(jnp.moveaxis(t, 1, 0) for t in xs),
            )

        start = jnp.zeros((batch,) + A.shape, jnp.float32)
        _, out = lax.scan(a_segment, start, tuple(cut(t) for t in (x, dt, b, c)))
        out = jnp.moveaxis(out, 1, 2)  # [segments, batch, segment, D]
        return jnp.moveaxis(out, 0, 1).reshape(batch, length, -1)

    return segmented


def reference_gradient(ref, sizes):
    """(params, tokens, targets) -> (loss, logits, grad: the tree's),
    the reference's own functions with what the backward pass keeps cut
    down (module docstring)."""
    pair = jax.checkpoint(ref.pair_attention, static_argnums=(6,))
    layer = jax.checkpoint(ref.layer, static_argnums=(0, 1, 5))
    scan = segmented_scan(ref)

    def loss(params, tokens, targets):
        with swapped(ref, "pair_attention", pair), swapped(
            ref, "layer", layer
        ), swapped(ref, "selective_scan", scan):
            params = ref._float32(params)
            h = ref.forward(params, tokens, sizes)
            value, logits = ref.head_loss(params, h, targets, sizes)
        return value, logits

    return jax.jit(jax.value_and_grad(loss, has_aux=True))


# ------------------------------------------------------------- the verdict


def leaf_errors(shapes, got, want):
    """The worst leaf's error: each leaf's gradient's L2 error over the
    larger of its own norm and a thousandth of the whole gradient's (a
    leaf whose true gradient is rounding, as a key bias's is under a
    softmax, is held by the whole's scale) -> (worst, its leaf)."""
    floor = 1e-3 * norm(want)
    worst, at, where = 0.0, 0, None
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        piece = slice(at, at + leaf.size)
        error = norm(got[piece] - want[piece]) / max(norm(want[piece]), floor)
        if error > worst:
            worst, where = error, jax.tree_util.keystr(path)
        at += leaf.size
    return worst, where


def measures(got, want, shapes):
    gap = got["grad"] - want["grad"]
    logit_gap = got["logits"] - want["logits"]
    worst, where = leaf_errors(shapes, got["grad"], want["grad"])
    return {
        "loss_rel": abs(got["loss"] - want["loss"]) / abs(want["loss"]),
        "logits_rel": float(
            np.max(np.abs(logit_gap)) / np.max(np.abs(want["logits"]))
        ),
        "logits_l2": float(
            np.linalg.norm(logit_gap) / np.linalg.norm(want["logits"])
        ),
        "grad_rel_l2": norm(gap) / norm(want["grad"]),
        "grad_leaf_worst": worst,
        "grad_leaf_worst_at": where,
        "grad_max_at": int(np.argmax(np.abs(gap))),
        "scan_rel": got["scan_rel"],
    }


def off_their_start(params, seed):
    """The initialiser's zeros (every bias) and ones (every norm's
    weight, D) moved off them by normals at 0.05, so that a control
    that drops one is seen; the matrices stay as drawn."""
    rng = np.random.default_rng(seed)

    def moved(a):
        a = np.asarray(a)
        if a.ndim <= 2 and (np.all(a == 0) or np.all(a == 1)):
            return (a + 0.05 * rng.standard_normal(a.shape)).astype(a.dtype)
        return a

    return jax.tree_util.tree_map(moved, params)


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
    variables = {
        **variables, "params": off_their_start(variables["params"], seed)
    }
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    flat = np.asarray(ravel_pytree(params)[0])
    shapes = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), params
    )
    say(f"seed {seed}: {flat.size} parameters, batch {features.shape}, "
        f"{jax.devices()[0].device_kind}")
    ref = programs["ref"]
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = programs["reference"](params, features, labels)
        assert jax.tree_util.tree_structure(grads) == (
            jax.tree_util.tree_structure(params)
        )
        want = {
            "loss": float(loss), "logits": np.asarray(logits, np.float32),
            "grad": np.asarray(ravel_pytree(grads)[0], np.float32),
        }
        del grads, logits
    say(f"reference: loss {want['loss']:.6f}")
    scans = scan_errors(
        ref, timed.cfg, seed, features.shape[1],
        {"own": ss.selective_scan, "bf16_decay": scan_bf16_decay},
    )
    if "step" not in programs:
        programs["step"] = Step(zoo, timed, variables)
        programs["control_steps"] = {
            name: Step(zoo, models.get(name, timed), variables, SWAPS.get(name))
            for name in STEP_CONTROLS
        }
        programs["forward"] = {
            name: forward_program(zoo, models.get(name, timed), variables, name)
            for name in ("timed",) + CONTROLS
        }
    step = programs["step"](flat, features, labels)
    found, stats = {}, {"timed": {"loss": step["loss"], **step["stats"]}}
    for name in ("timed",) + CONTROLS:
        loss, logits = programs["forward"][name](params, features, labels)
        own = step
        if name in STEP_CONTROLS:  # one gradient of 2.3 GB more at a time
            own = programs["control_steps"][name](flat, features, labels)
            stats[name] = {"loss": own["loss"], **own["stats"]}
        got = {
            # the step's own loss where a step was run; any other
            # control is judged by its forward pass and borrows the
            # timed step's gradient
            "loss": float(loss) if own is step and name != "timed" else own["loss"],
            "logits": np.asarray(logits, np.float32), "grad": own["grad"],
            "scan_rel": scans.get(name, scans["own"]),
        }
        found[name] = measures(got, want, shapes)
        del got, logits, own

    def beyond(name):  # a NaN is beyond every limit
        return {
            k: found[name][k] for k, limit in BAND.items()
            if not found[name][k] <= limit
        }

    out_of_band = {name: beyond(name) for name in ("timed",) + CONTROLS}
    verdict = {
        "seed": seed,
        "device": jax.devices()[0].device_kind,
        "reference": {"loss": want["loss"], "grad_norm": norm(want["grad"])},
        "programs": stats,
        "measures": found,
        "grad_max_leaf": leaf_of(shapes, found["timed"]["grad_max_at"]),
        **{f"{name}_beyond_band": out_of_band[name] for name in out_of_band},
        "ok": small or (
            not out_of_band["timed"]
            and all(out_of_band[name] for name in CONTROLS)
        ),
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
        **{name: zoo.custom_model(**{**overrides, **override})
           for name, override in OVERRIDES.items()},
    }
    cfg = models["timed"].cfg
    sizes = Hashable(ref.sizes_of(
        zoo.SIZES, heads=cfg.n_heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, window=cfg.swa_window, inner=cfg.ssm1_inner,
        state=cfg.ssm1_state, dt_rank=cfg.ssm1_dt_rank,
    ))
    programs = {
        "models": models, "ref": ref,
        "reference": reference_gradient(ref, sizes),
    }
    verdicts = [
        compare_seed(zoo, programs, args.seed + i, args.small)
        for i in range(args.seeds)
    ]
    ok = all(v["ok"] for v in verdicts)
    out = os.path.join(ROOT, "chiprun_out", "phi4_flash_compare.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        for v in verdicts:
            f.write(json.dumps(v) + "\n")
    say(f"{'PASS' if ok else 'FAIL'}: {len(verdicts)} seed(s); BAND {BAND}; "
        f"written to {out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
