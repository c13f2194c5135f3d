"""On the chip, at the configuration's sizes: what the worker's own
step computes against the plain reference.

    python benchmark/configs/smallthinker-21b-a3b/compare.py --seed <n> [--seeds k]

One process that holds the chip itself (run it through `chiprun`, never
beside a job). For each seed: the zoo module's weights from the seed
and one minibatch (`minibatch_per_chip` x `seq_len` = 1 x 16,384) of
the cell's own RecordIO data go through **the program a `Worker`
builds** (`Worker._build_local_step()`: `_local_step_core` jitted with
its donations, the step the window program scans 16 times;
`harness/compare_common.WorkerStep`), with the model's non-trainable
collection in `aux`, so `_apply_model`'s `mutable` path runs as it does
in the cell. The one thing swapped is the zoo's optimizer, for
`optax.trace(decay=0)`: its state after one step IS the flat gradient
the step differentiated, bit for bit. From one call: the loss, the
gradient and what the routers did (`window_stats`). All held against
`reference.py` (float32 under `jax.default_matmul_precision("highest")`:
the router on the layer's input, attention a key-value head at a time
with its scores written out a block of queries at a time, the experts a
masked dense sum), differentiated LAYER BY LAYER by this script
(`ReferenceGradient`: the reference's own `layer` and `head_loss`, one
`jax.vjp` a layer from the head down, each block of scores under
`jax.checkpoint`: what is kept for the backward pass, not what is
computed), so that 16,384 tokens fit and no program holds more than a
layer.

Beside the whole step, each part ALONE, as the step calls it
(`transformer_lm._attend` for the two kinds of attention layer,
`moe.moe_topk_held` for the expert layer, in the program's compute
dtype, no ambient precision: on the chip the Pallas kernels, the
sliding kind the banded call), on one sequence of inputs both sides
share, against the reference's part under `highest`: `swa_rel`,
`full_rel`, `moe_rel`, the largest error over the reference's largest
output, and `swa_l2`, `full_l2`, `moe_l2`, the error's norm over the
output's. The whole step's gradient carries bfloat16's rounding of
every projection; a part alone does not.

1. `float32`: the model with `dtype` float32, same precision: the same
   mathematics in another order, so the two agree to accumulated
   rounding and to the assignments that rounding moves among the
   experts: `TIGHT` (the gradient by `TIGHT_FLIPPED` on a seed on which
   it moved one to or from an expert held here).
2. `timed`: the model as the cell times it, bfloat16 compute with
   float32 parameters, accumulation, router, scores and softmax and
   logits-to-loss: inside `BAND`, whose limits lie between the timed
   program's largest reading over the seeds and the smallest of the
   controls, each of which has to come out NOT correct by at least one
   of `BAND`'s limits:
3. `late_router`: the router fed ln2(h), behind the attention (the
   usual place; `early_router` off);
4. `full_turned`: the full layer's queries and keys turned as the
   sliding layers' are (`rope_mixers` None: one switch for both kinds);
5. `silu_gate`: SiLU in the experts' gate for the ReLU (`mlp`
   "swiglu");
6. `bf16_router`: the router's product in bfloat16 (`moe.router_logits`
   swapped), the nearest precision below the float32 the configuration
   states for it; held where it can be seen beside the whole step:
   `router_flips`, the assignments on which the router and the
   reference's float32 one differ on inputs both share;
7. `no_window`: the sliding layers see the whole triangle; held by the
   sliding layer alone (the one limit pair no other control reads), and
   given the timed program's step.

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
from jax.flatten_util import ravel_pytree  # noqa: E402

from benchmark.harness import data  # noqa: E402
from benchmark.harness.compare_common import (  # noqa: E402
    Hashable,
    WorkerStep,
    leaf_of,
    norm,
    reference_step,
    route_bf16,
    router_flips,
    say,
    swapped,
)
from benchmark.harness.manifest import load_module  # noqa: E402
from elasticdl_tpu.models import transformer_lm as lm  # noqa: E402
from elasticdl_tpu.parallel import moe  # noqa: E402

# float32 against float32 (my chip runs, PR 62, calls 1, 2 and 4, seeds
# 2147483777, 2147485111 and, from the committed files, 2147486233;
# PERF.md section 6). At 16,384 tokens x top-6 = 98,304 assignments a
# layer the router reads the residual stream itself, whose first layer
# is the embedding at a scale of 0.02: sixth and seventh logits lie
# within float32's rounding of each other for a token or two a seed,
# and such a token takes another expert. `load_abs` sees that move where
# one of the two experts is among the 8 of 64 held here (2, 1 and 4 on
# the three seeds), and the gradient is then read by TIGHT_FLIPPED. Each
# limit lies between the float32 program's largest reading and the
# timed bfloat16 program's smallest, the nearest precision below:
# `load_abs` 1 to 4 beside 158 to 228; the gradient 3.9e-4 to 6.9e-4 in
# L2 and 0.0013 to 0.0024 of the largest entry (one to four assignments
# moved) beside 0.0137 to 0.0225 and 0.031 to 0.043; the sliding layer
# alone 2.6e-5 and 3.2e-4 (its float32 angles at positions up to 16,383
# and the kernels' float32 products in fewer passes than `highest`'s
# six) beside 0.0036 to 0.0043 and 0.0062; the full layer alone, which
# turns nothing, under 1e-6 beside 0.0036 to 0.0040 and 0.0055; the
# expert layer alone 0 beside 0.033 to 0.035 in L2. The loss read 1e-6
# to 2e-6 beside the timed 6e-6 to 5.2e-5: on the second seed the timed
# program lies inside this limit, so it is told by its gradient and its
# parts, as Laguna's comparison found of its own
TIGHT = {
    "loss_rel": 1.5e-5,
    "load_abs": 12,
    "router_flips": 8,
    "grad_rel_l2": 1.5e-3,
    "grad_max_rel": 0.006,
    "swa_rel": 3e-4,
    "full_rel": 3e-4,
    "swa_l2": 1.5e-3,
    "full_l2": 8e-4,
    "moe_l2": 8e-4,
}
TIGHT_FLIPPED = {**TIGHT, "grad_rel_l2": 0.006, "grad_max_rel": 0.015}
# bfloat16 compute against the float32 reference: each limit between
# the timed program's largest reading over the seeds and the smallest of
# the controls that separate from it there (calls 1, 2 and 4; PERF.md
# section 6). The gradient in L2 read 0.0137 to 0.0225 and holds
# `silu_gate` (0.133 to 0.155), `full_turned` (0.206 to 0.223) and
# `late_router` (0.38 to 0.50); its largest entry 0.031 to 0.043 beside
# 0.121 to 0.142, 0.184 to 0.217 and 0.519 to 0.755; `load_abs` 158 to
# 228 (bfloat16's rounding of the stream the router reads moves that
# many of a layer's 12,288 held assignments) beside 1,964 to 2,810,
# 2,152 to 4,687 and 13,130 to 28,429. `bf16_router` moves what the
# timed program's own rounding moves (`load_abs` 305 to 316, gradient
# 0.022 to 0.025) and is held where it can be seen: `router_flips`, 159
# to 173 of 49,152 beside the program's own 0. The parts alone:
# `full_turned` 0.20 and 0.75 on the full layer beside 0.0036 to 0.0040
# and 0.0055; `silu_gate` 0.297 and `late_router` 1.36 on the expert
# layer in L2 beside 0.033 to 0.035 (`bf16_router` 0.053 to 0.057);
# `no_window`, which only the sliding layer alone is asked, 0.035 and
# 0.40 beside 0.0036 to 0.0043 and 0.0062. The loss tells little on an
# untrained model (10.37 = ln 18,992 + 0.5 whatever the block does: the
# controls read 1.4e-5 to 1.1e-3 beside the timed 6e-6 to 5.2e-5), so
# its limit only bounds it. The expert layer's LARGEST error
# (`moe_rel`) is one token whose sixth and seventh choice bfloat16
# swaps where one is held: 0.204 to 0.215 timed beside `silu_gate`'s
# 0.280 to 0.305; it tells nothing at these sizes (Nemotron's
# comparison found the same) and is reported, not judged
BAND = {
    "loss_rel": 3e-4,
    "load_abs": 800,
    "router_flips": 8,
    "grad_rel_l2": 0.06,
    "grad_max_rel": 0.08,
    "swa_rel": 0.02,
    "full_rel": 0.03,
    "swa_l2": 0.03,
    "full_l2": 0.06,
    "moe_l2": 0.1,
}
CONTROLS = ("late_router", "full_turned", "silu_gate", "bf16_router",
            "no_window")
# the controls whose whole step is run; `no_window` is held by the
# sliding layer alone and given the timed program's step
STEP_CONTROLS = CONTROLS[:4]
SMALL = dict(
    vocab=97, d_model=64, head_width=16, n_heads=7, n_kv_heads=1,
    swa_heads=7, swa_window=8, n_experts=16, held_experts=(4, 4),
    d_expert=24, moe_top_k=3,
)
MIXERS = {"swa": "sliding", "mha": "full"}  # the program's, the reference's
PARTS = ("swa", "full", "moe")


def logits_bf16(x, router_w):
    """`moe.router_logits` with the product in bfloat16."""
    return (
        x.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16)
    ).astype(jnp.float32)


# a control is a model override or a swap (module, name, other)
OVERRIDES = {
    "late_router": dict(early_router=False),
    "full_turned": dict(rope_mixers=None),
    "silu_gate": dict(mlp="swiglu"),
    "no_window": dict(swa_window=1 << 30),
}
SWAPS = {"bf16_router": (moe, "router_logits", logits_bf16)}


def measures(got, want):
    gap = got["grad"] - want["grad"]
    return {
        "loss_rel": abs(got["loss"] - want["loss"]) / abs(want["loss"]),
        "load_abs": float(np.max(np.sum(np.abs(got["loads"] - want["loads"]), axis=-1))),
        "router_flips": got["router_flips"],
        **{f"{part}_{m}": got[f"{part}_{m}"] for part in PARTS
           for m in ("rel", "l2")},
        "grad_rel_l2": norm(gap) / norm(want["grad"]),
        "grad_max_rel": float(np.max(np.abs(gap)) / np.max(np.abs(want["grad"]))),
        "grad_max_at": int(np.argmax(np.abs(gap))),
    }


def part_inputs(cfg, seed, length):
    """One sequence of unit-variance rows and one layer's leaves
    (matrices at 1/sqrt(fan-in)), float32; the router's logits of a
    second such sequence, as the layer's input gives them."""
    d, hd, f = cfg.d_model, cfg.head_dim, cfg.d_expert
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 12))
    first, held = cfg.held

    def matrix(*shape):
        return jax.random.normal(next(keys), shape) / shape[-2] ** 0.5

    heads = cfg.n_heads
    leaves = {
        "wq": matrix(d, heads * hd), "wk": matrix(d, cfg.kv_heads * hd),
        "wv": matrix(d, cfg.kv_heads * hd), "wo": matrix(heads * hd, d),
        "router": matrix(d, cfg.n_experts), "eg": matrix(held, d, f),
        "eu": matrix(held, d, f), "ed": matrix(held, f, d),
    }
    x = jax.random.normal(next(keys), (1, length, d))
    early = jax.random.normal(next(keys), (1, length, d))
    return leaves, x, early


class Parts:
    """The program's two kinds of attention layer and its expert layer
    alone, in a model's compute dtype, against the reference's under
    `highest`, on inputs both share: {"swa_rel", "swa_l2", ...} of a
    (model, swap). Each side's program is traced once and kept: the
    reference's answer once a seed, whatever is held against it."""

    def __init__(self, ref, sizes):
        self._programs, self._want, self._seed = {}, {}, None

        def experts(lp, x, early):
            gates, _chosen = ref.route(lp, early, sizes)
            return ref.expert_layer(lp, x, gates, sizes)

        self._reference = {
            **{
                mixer: jax.jit(
                    lambda lp, x, _early, kind=kind: ref.attention_mixer(
                        lp, x, sizes[kind], sizes
                    )
                ) for mixer, kind in MIXERS.items()
            },
            "moe": jax.jit(experts),
        }

    @staticmethod
    def _program(cfg, part):
        if part == "moe":
            def experts(lp, x, early):
                logits = moe.router_logits(
                    early.reshape(-1, early.shape[-1]), lp["router"]
                ) if cfg.early_router else None
                return moe.moe_topk_held(
                    x, lp["router"], (lp["eg"], lp["eu"], lp["ed"]), None,
                    top_k=cfg.moe_top_k, held=cfg.held, score=cfg.moe_score,
                    renormalize=cfg.moe_renormalize, balance=False,
                    kind=cfg.mlp, logits=logits,
                )[0]

            return experts
        return lambda lp, x, _early: lm._attend(
            cfg, lp, x, jnp.arange(x.shape[1]), part
        )[0]

    def errors(self, name, cfg, seed, length, swap=None):
        found = {}
        if seed != self._seed:  # the last seed's answers go
            self._want, self._seed = {}, seed
        leaves, x, early = part_inputs(cfg, seed, length)
        for part, short in (("swa", "swa"), ("mha", "full"), ("moe", "moe")):
            if part not in self._want:
                with jax.default_matmul_precision("highest"):
                    self._want[part] = self._reference[part](leaves, x, early)
            want = self._want[part]
            if (name, part) not in self._programs:
                self._programs[name, part] = jax.jit(self._program(cfg, part))
            cast = {
                k: v if k == "router" else v.astype(cfg.dtype)
                for k, v in leaves.items()
            }
            with swapped(*swap) if swap else contextlib.nullcontext():
                got = self._programs[name, part](
                    cast, x.astype(cfg.dtype), early.astype(cfg.dtype)
                ).astype(jnp.float32)
            found[f"{short}_rel"] = float(
                jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))
            )
            found[f"{short}_l2"] = float(
                jnp.linalg.norm(got - want) / jnp.linalg.norm(want)
            )
        return found


class ReferenceGradient:
    """The reference's loss, loads and gradient of one sequence, layer
    by layer: the forward pass keeps each layer's input, the head gives
    the loss and the last layer's cotangent, and one `jax.vjp` a layer
    walks back down, each block of scores under `jax.checkpoint`. One
    program a kind of layer (full or sliding), so a compile holds a
    layer and not the stack."""

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
        """-> {loss, loads [layers, E], grad: the tree's}."""
        ref, kinds = self._ref, self._sizes["kinds"]
        layers = list(ref.layers_of(params))
        inputs, loads = [params["embed"][tokens]], []
        for lp, kind in zip(layers, kinds):
            h, load = self._layer(lp, inputs[-1], kind)
            inputs.append(h)
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
    # on the host between the programs: a step's temporaries lie beside
    # its own copy of the vector and the gradient
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
    ref = programs["ref"]
    with jax.default_matmul_precision("highest"):
        want = reference_step(
            programs["reference"], params, features, labels, timed.cfg.held
        )
    say(f"reference: loss {want['loss']:.6f}")
    router_w = jnp.asarray(params["stack"][0]["router"][0], jnp.float32)
    flips = {
        name: router_flips(ref, router_w, timed.cfg.moe_top_k, seed, route)
        for name, route in (("own", moe.route_topk), ("bf16", route_bf16))
    }
    length = features.shape[1]
    alone = programs["parts"]
    parts = {"timed": alone.errors("timed", timed.cfg, seed, length)}
    with jax.default_matmul_precision("highest"):
        parts["float32"] = alone.errors(
            "float32", models["float32"].cfg, seed, length
        )
    for name in OVERRIDES:
        parts[name] = alone.errors(name, models[name].cfg, seed, length)
    parts["bf16_router"] = alone.errors(
        "bf16_router", timed.cfg, seed, length, SWAPS["bf16_router"]
    )
    del params
    found, stats = {}, {}

    results = {}

    def judge(name, precision=None):
        # a control held by a part alone is given the timed step
        step = name if name in steps else "timed"
        if step not in results:
            with jax.default_matmul_precision(precision) if precision else (
                contextlib.nullcontext()
            ):
                results.clear()  # one gradient of 1.5 GB on the host at a time
                results[step] = steps[step](flat, features, labels)
        result = dict(results[step])
        result["router_flips"] = flips["bf16" if name == "bf16_router" else "own"]
        result.update(parts[name])
        found[name] = measures(result, want)
        stats[name] = {"loss": result["loss"], **result["stats"]}
        say(f"{name}: " + json.dumps(
            {k: round(v, 6) for k, v in found[name].items()}
        ))

    try:  # first, before any other step's program is loaded beside it
        judge("float32", "highest")
    except Exception as e:  # the float32 step met the chip's memory
        say(f"float32: NOT RUN: {repr(e)[:400]}")
        found["float32"] = {k: float("nan") for k in TIGHT}
        found["float32"]["grad_max_at"] = 0
    for name in ("timed", "no_window") + STEP_CONTROLS:
        judge(name)

    def beyond(name, limits):  # a NaN is beyond every limit
        return {
            k: found[name][k] for k, limit in limits.items()
            if not found[name][k] <= limit
        }

    flipped = found["float32"]["load_abs"] > 0  # False for a NaN
    tight = beyond("float32", TIGHT_FLIPPED if flipped else TIGHT)
    out_of_band = {name: beyond(name, BAND) for name in ("timed",) + CONTROLS}
    verdict = {
        "seed": seed,
        "device": jax.devices()[0].device_kind,
        "reference": {"loss": want["loss"], "loads": want["loads"].tolist(),
                      "grad_norm": norm(want["grad"])},
        "programs": stats,
        "measures": found,
        "float32_flipped": flipped,
        # the entry on which each program's gradient is furthest off
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
    models = {
        "timed": zoo.custom_model(**overrides),
        "float32": zoo.custom_model(dtype="float32", **overrides),
        **{name: zoo.custom_model(**{**overrides, **override})
           for name, override in OVERRIDES.items()},
    }
    cfg = models["timed"].cfg
    sizes = ref.sizes_of(
        zoo.SIZES, top_k=cfg.moe_top_k, held=cfg.held, heads=cfg.n_heads,
        kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
    )
    for mixer, kind in MIXERS.items():  # `--small`'s window
        sizes[kind] = Hashable(
            sizes[kind], window=cfg.attention_shape(mixer).window
        )
    sizes = Hashable(sizes)
    programs = {
        "models": models, "ref": ref, "sizes": sizes,
        "reference": ReferenceGradient(ref, sizes),
        "parts": Parts(ref, sizes),
    }
    verdicts = [
        compare_seed(zoo, programs, args.seed + i, args.small)
        for i in range(args.seeds)
    ]
    ok = all(v["ok"] for v in verdicts)
    out = os.path.join(ROOT, "chiprun_out", "smallthinker_compare.jsonl")
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
