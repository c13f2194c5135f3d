"""On the chip, at the configuration's sizes: what the worker's own
step computes against the plain reference.

    python benchmark/configs/qwen3-next-80b-a3b/compare.py --seed <n> [--seeds k]

One process that holds the chip itself (run it through `chiprun`, never
beside a job), and at the configuration's sizes ONE SEED A PROCESS
(`--seeds 1`, the default: see the comment over `TIGHT`). For each seed: the zoo module's weights from the seed
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
`jax.default_matmul_precision("highest")`: the delta rule a token at a
time, attention a key-value head at a time with its scores written out
a block of queries at a time, the experts a masked dense sum),
differentiated LAYER BY LAYER by this script (`ReferenceGradient`: the
reference's own `layer` and `head_loss`, one `jax.vjp` a layer from the
head down, each block of scores and each segment of 64 tokens of the
recurrence under `jax.checkpoint`: what is kept for the backward pass,
not what is computed), so that 8192 tokens fit and no program holds
more than a layer.

Beside the whole step, each kind of layer ALONE, as the step calls it
(`transformer_lm._gdn`, `transformer_lm._attend`, `moe.moe_topk_held`,
in the program's compute dtype, no ambient precision: on the chip the
scalar-decay scan and the Pallas attention kernels at heads of 256), on
one sequence of inputs both sides share, against the reference's layer
under `highest`: `gdn_rel`, `full_rel`, `moe_rel`, the largest error
over the reference's largest output, and `gdn_l2`, `full_l2`, `moe_l2`,
the error's norm over the output's; and the SCAN alone
(`kda.kda_chunked` under one decay a head against the reference's
recurrence a token at a time) at a decay slow enough that a state lives
for tens of tokens: `scan_rel`. The whole step's gradient carries
bfloat16's rounding of every projection, under which a gate's shape or
a rounded state can hide; a layer alone cannot.

1. `float32`: the model with `dtype` float32, same precision: the same
   mathematics in another order, so the two agree to accumulated
   rounding and to the assignments that rounding moves among the
   experts: `TIGHT` (the gradient by `TIGHT_FLIPPED` on a seed on which
   it moved one to or from an expert held here).
2. `timed`: the model as the cell times it, bfloat16 compute with
   float32 parameters, accumulation, router, decay, scan, norms, gates,
   scores and softmax and logits-to-loss: inside `BAND`, whose limits
   lie between the timed program's largest reading over the seeds and
   the smallest of the controls, each of which has to come out NOT
   correct by at least one of `BAND`'s limits:
3. `no_decay`: g = 0, the delta rule without its gate;
4. `channel_mean_gate`: the attention's gate averaged over a head (one
   number a head, Laguna's kind of gate);
5. `sigmoid_z`: the scan's output gated by sigmoid(z) for SiLU(z);
6. `key_head_mod`: value head j reads key head j mod 16, not j // 2;
7. `no_l2`: q and k not scaled to unit length;
8. `full_rotary`: all 256 columns of a head turned;
9. `no_shared_gate`: the shared expert added ungated;
10. `no_renormalise`: the gates the chosen probabilities as they are;
11. `bf16_state`: the scan's carried state rounded to bfloat16 from
    chunk to chunk (through `kda.chunk_step`), held by `scan_rel`;
12. `bf16_router`: the router's product, softmax, top-10 and gates in
    bfloat16; held where it can be seen: `router_flips`, the
    assignments on which the router and the reference's float32 one
    differ on inputs both share;
13. `bf16_scores`: every product inside the attention kernels rounded
    to bfloat16 as it leaves the MXU, the nearest precision below the
    float32 the configuration states for them; held on the FLOAT32
    program's attention layer alone, against `TIGHT`'s two limits for
    it (Laguna's `compare.py` has why).

Not compared here: clipped Adam and the 16-step scan around the step,
which the cell itself runs to its loss check.

Prints one JSON line a seed and one verdict; exit 0 only if 1 and 2
hold and every control fails the band, for every seed. The whole step
is run for `timed`, `float32` and the controls of `STEP_CONTROLS`; the
others are held by the layers alone, where bfloat16's rounding of every
projection does not cover them, and are given the timed program's step.
`--small` is the CPU rehearsal of the script's plumbing (tiny sizes;
its numbers are no device numbers and its band is not judged).
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
    dot_bf16,
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
from elasticdl_tpu.ops import flash_attention, kda  # noqa: E402
from elasticdl_tpu.parallel import moe  # noqa: E402

# The limits below were set in this PR's first round, when the cut held
# experts 0-7 (my chip runs, PR 52, calls 4, 8, 10, 11 and 12, two fresh
# seeds each; PERF.md section 6), and every reading in these comments is
# that round's unless it says "at 16 held". The review's round holds
# experts 0-15: two limits moved for it BEFORE its run (`load_abs` and
# `moe_rel` of BAND, each with its reason there), the rest stayed. At
# 16 held ONE seed has run (call 15, seed 2147493700: PASS; the second
# was cut by the call's time limit): float32 loss to the digit, no
# assignment moved, gradient 9.2e-6 | 9.1e-6; timed gradient 0.063 |
# 0.154, `load_abs` 78, `gdn` 0.013 | 0.0068, `full` 0.0043 | 0.0063,
# `moe` 0.068 | 0.0075, `scan_rel` 9.6e-6 beside `bf16_state` 1.1e-3;
# every control beyond a limit (PERF.md section 6). Run a seed a
# process there: the float32 step's program is 15.16 GB of the chip's
# 16.9 by the v5e rehearsal, and a second seed in the same process
# finds the first one's four step programs loaded beside it.
#
# float32 against float32. On seven seeds no assignment
# moved (`load_abs` 0, `router_flips` 0) and the float32 program read:
# loss equal to the last digit, the gradient 5.6e-6 to 3.3e-5 in L2 and
# 8.0e-6 to 2.3e-4 of the largest entry. On the eighth rounding moved
# an assignment to or from an expert held here (`load_abs` 4; 8 of 512
# held: one move in 64 touches them, one in 32 at 16 held): loss 2.6e-6,
# the gradient 0.0018
# in L2 and 0.013 of the largest entry, so TIGHT_FLIPPED, which held
# Laguna's readings for that case (0.0027 to 0.0040 | 0.049 to 0.058)
# until a seed showed it here, has this cell's own reading under it.
# The layers alone, all eight seeds: `gdn_rel` 3.1e-6 to 7.5e-6,
# `gdn_l2` 6.7e-7 to 7.6e-7, `full_rel` 1.2e-5 to 1.6e-5, `full_l2`
# 1.1e-4 (the attention kernels' float32 products pass the MXU in fewer
# bfloat16 passes than `highest`'s six: that is their rounding, as in
# Laguna's file), `moe_rel` 1.7e-7 to 2.5e-7, `moe_l2` 1.8e-7; the scan
# alone at a slow decay `scan_rel` 8.8e-6 to 1.35e-4 (a largest error:
# it moves fifteenfold from seed to seed, and `bf16_state`'s with it).
# Each limit lies above that and, but for the loss, under the timed
# bfloat16 program's smallest reading, the nearest precision below
# (loads 33, gradient 0.056 | 0.067, `gdn` 0.011 | 0.0067, `full` 0.0042
# | 0.0062, `moe` 0.0066 | 0.0052); the loss hardly feels the precision
# (timed 4.4e-6 to 2.0e-4), so the timed program is told from the
# float32 one by its gradient and its layers, not by `loss_rel`;
# `scan_rel` lies between the scan's own 1.35e-4 and `bf16_state`'s
# 8.6e-4, and the attention layer's two also under `bf16_scores` on the
# float32 layer (0.0015 to 0.0019 | 0.0031 to 0.0033)
TIGHT = {
    "loss_rel": 1.5e-5,
    "load_abs": 12,
    "router_flips": 40,
    "grad_rel_l2": 1.2e-3,
    "grad_max_rel": 0.02,
    "gdn_rel": 3e-4,
    "gdn_l2": 1e-4,
    "full_rel": 2e-4,
    "full_l2": 5e-4,
    "moe_rel": 3e-4,
    "moe_l2": 1e-4,
    "scan_rel": 3.5e-4,
}
TIGHT_FLIPPED = {**TIGHT, "grad_rel_l2": 0.015, "grad_max_rel": 0.085}
# bfloat16 compute against the float32 reference: each limit between
# the timed program's largest reading over the eight seeds (calls 4, 8,
# 10 and 11) and the smallest of the controls that separate from it there.
# The gradient read 0.056 to 0.064 in L2 and 0.070 to 0.152 of its largest
# entry, beside `no_shared_gate`'s 1.16 | 1.0 and `no_decay`'s 1.44 |
# 1.34 (loss 4.4e-6 to 2.0e-4 beside 1.0e-3 to 3.4e-3 on the seeds
# where a control's loss separates at all; `load_abs` 33 to 67 beside
# 183 to 501). The GDN layer alone 0.011 to 0.016 | 0.0067 beside
# `sigmoid_z` 0.76 | 0.81, `key_head_mod` 1.09 | 0.73, `no_decay` 1.24 |
# 1.33 and `no_l2` not finite (unscaled keys overflow the chunk's
# system: beyond every limit). The attention layer 0.0042 to 0.0058 |
# 0.0064 beside `full_rotary` 0.22 | 0.65 and `channel_mean_gate` 0.35 |
# 0.38. The expert layer 0.0066 to 0.084 | 0.0052 to 0.0067 (a largest
# error over 0.04 is one token whose tenth and eleventh choice the
# bfloat16 rounding of its input swaps; the L2 is the rounding proper)
# beside
# `no_renormalise` 0.24 | 0.064 and `no_shared_gate` 0.78 | 0.99.
# `bf16_state` is held by `scan_rel` (8.6e-4 to 1.9e-3 beside the
# scan's own 8.8e-6 to 1.35e-4), `bf16_router` by `router_flips` (604 to
# 650 of 81,920, the program's own 0).
# Moved for 16 held, before its run: `load_abs` 120 -> 240 (it counts, in
# the fullest layer, the assignments rounding moved to or from an expert
# HELD here, so twice the experts held is twice the count on either
# side: the timed program's 33 to 67 becomes 66 to 134, the nearest
# control's 183 to 501 becomes 366 and more; no control is told by its
# loads alone); `moe_rel` 0.12 -> 0.16, the middle of the timed
# program's largest 0.084 and `no_renormalise`'s smallest 0.24 (its
# large readings are one token whose swapped tenth choice is held here,
# which twice the experts held meets twice as often; `no_renormalise`
# is told by `moe_l2` too)
BAND = {
    "loss_rel": 5e-4,
    "load_abs": 240,
    "router_flips": 40,
    "grad_rel_l2": 0.25,
    "grad_max_rel": 0.3,
    "gdn_rel": 0.1,
    "gdn_l2": 0.07,
    "full_rel": 0.035,
    "full_l2": 0.05,
    "moe_rel": 0.16,
    "moe_l2": 0.02,
    "scan_rel": 3.5e-4,
}
CONTROLS = ("no_decay", "channel_mean_gate", "sigmoid_z", "key_head_mod",
            "no_l2", "full_rotary", "no_shared_gate", "no_renormalise",
            "bf16_state", "bf16_router", "bf16_scores")
# the controls whose whole step is run (a step's program takes a minute
# to compile); the others are held by the layers alone
STEP_CONTROLS = ("no_decay", "no_shared_gate")
LAYERS = ("gdn", "full", "moe")
SMALL = dict(
    vocab=97, d_model=64, gdn_key_heads=2, gdn_value_heads=4,
    gdn_head_dim=16, kda_chunk=16, n_heads=4, n_kv_heads=1, head_width=32,
    rope_dim=8, n_experts=16, held_experts=(4, 4), d_expert=24, moe_top_k=3,
)


def measures(got, want):
    gap = got["grad"] - want["grad"]
    return {
        "loss_rel": abs(got["loss"] - want["loss"]) / abs(want["loss"]),
        "load_abs": float(
            np.max(np.sum(np.abs(got["loads"] - want["loads"]), axis=-1))
        ),
        "router_flips": got["router_flips"],
        "scan_rel": got["scan_rel"],
        **{f"{layer}_{kind}": got[f"{layer}_{kind}"]
           for layer in LAYERS for kind in ("rel", "l2")},
        "grad_rel_l2": norm(gap) / norm(want["grad"]),
        "grad_max_rel": float(np.max(np.abs(gap)) / np.max(np.abs(want["grad"]))),
        "grad_max_at": int(np.argmax(np.abs(gap))),
    }


# ------------------------------------------------------------- the controls


_chunked = kda.kda_chunked


def chunked_no_decay(q, k, v, g, beta, **kw):
    return _chunked(q, k, v, jnp.zeros_like(g), beta, **kw)


def chunked_key_head_mod(q, k, v, g, beta, **kw):
    """Value head j reads key head j mod the key heads."""
    group = v.shape[2] // q.shape[2]
    q, k = (jnp.tile(x, (1, 1, group, 1)) for x in (q, k))
    return _chunked(q, k, v, g, beta, **kw)


def chunked_bf16_state(q, k, v, g, beta, **kw):
    """The state a chunk hands to the next rounded to bfloat16
    (`reduce_precision`, not a cast there and back: the TPU compiler
    drops such a pair)."""
    exact = kda.chunk_step

    def rounded(S, xs):
        S, o = exact(S, xs)
        return lax.reduce_precision(S, 8, 7), o

    with swapped(kda, "chunk_step", rounded):
        return _chunked(q, k, v, g, beta, **kw)


def sigmoid_gate(z):
    return jax.nn.sigmoid(z.astype(jnp.float32))


def as_it_is(y):
    return y


def head_mean_gate(head_dim):
    def gate(projected):
        g = jax.nn.sigmoid(projected.astype(jnp.float32))
        heads = g.reshape(g.shape[:-1] + (-1, head_dim))
        return jnp.broadcast_to(
            jnp.mean(heads, axis=-1, keepdims=True), heads.shape
        ).reshape(g.shape)

    return gate


def shared_ungated(xf, w):
    return jnp.ones((xf.shape[0], 1), jnp.float32)


# a control is a model override or a swap (module, name, other)
OVERRIDES = {
    "full_rotary": dict(rope_dim=None),
    "no_renormalise": dict(moe_renormalize=False),
}


def swaps_for(cfg):
    return {
        "no_decay": (kda, "kda_chunked", chunked_no_decay),
        "key_head_mod": (kda, "kda_chunked", chunked_key_head_mod),
        "bf16_state": (kda, "kda_chunked", chunked_bf16_state),
        "sigmoid_z": (lm, "_gdn_out_gate", sigmoid_gate),
        "no_l2": (lm, "_unit_length", as_it_is),
        "channel_mean_gate": (lm, "_channel_gate", head_mean_gate(cfg.head_dim)),
        "no_shared_gate": (moe, "_shared_gate", shared_ungated),
        "bf16_router": (moe, "route_topk", route_bf16),
        "bf16_scores": (flash_attention, "_dot", dot_bf16),
    }


# which layer alone shows a control (the others borrow the timed
# program's readings there)
SHOWN_BY = {
    "no_decay": "gdn", "sigmoid_z": "gdn", "key_head_mod": "gdn",
    "no_l2": "gdn", "channel_mean_gate": "full", "full_rotary": "full",
    "bf16_scores": "full", "no_shared_gate": "moe", "no_renormalise": "moe",
}
SCAN_CONTROLS = {
    "no_decay": chunked_no_decay, "key_head_mod": chunked_key_head_mod,
    "bf16_state": chunked_bf16_state,
}


def scan_errors(ref, cfg, seed, length, variants):
    """{name: the largest error of `variants[name]`'s outputs over the
    largest output of the reference's recurrence a token at a time}, on
    one sequence of `length` tokens both share: q, k, v as a layer
    makes them (SiLU of normals, q and k scaled), a log-decay of -a x
    softplus(normal - 3) with a uniform in (0, 16) (the initialiser's
    rates at a step small enough that a state lives for tens of
    tokens). The reference under `highest`; a variant under no ambient
    precision, as the worker's step calls it."""
    kh, vh, hd = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_head_dim
    group = vh // kh
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (
        jax.nn.silu(jax.random.normal(key, (1, length, kh, hd)))
        for key in keys[:2]
    )
    v = jax.nn.silu(jax.random.normal(keys[2], (1, length, vh, hd)))
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * hd**-0.5
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    rate = jax.random.uniform(keys[3], (vh,), minval=1e-3, maxval=16.0)
    g = -rate * jax.nn.softplus(jax.random.normal(keys[4], (1, length, vh)) - 3.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (1, length, vh)))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.delta_rule)(
            jnp.repeat(q, group, axis=2), jnp.repeat(k, group, axis=2),
            v, g, beta,
        )
    scale = float(jnp.max(jnp.abs(want)))
    return {
        name: float(jnp.max(jnp.abs(
            jax.jit(lambda *a, f=f: f(*a, chunk=cfg.kda_chunk)[0])(
                q, k, v, g, beta
            ) - want
        ))) / scale
        for name, f in variants.items()
    }


def layer_inputs(cfg, seed, length):
    """One sequence of unit-variance rows and each kind of layer's
    leaves as the initialiser draws them (matrices at 1/sqrt(fan-in),
    the norms' weights off one, the untrained decay), float32."""
    d, hd = cfg.d_model, cfg.head_dim
    kh, vh, ghd = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 40))

    def matrix(*shape, fan_in=None):
        return jax.random.normal(next(keys), shape) / (
            fan_in or shape[-2]
        ) ** 0.5

    def weight(n):
        return 1.0 + 0.1 * jax.random.normal(next(keys), (n,))

    x = jax.random.normal(next(keys), (1, length, d))
    qkv = (2 * kh + vh) * ghd
    first, held = cfg.held
    f = cfg.d_expert
    return {
        "gdn": {
            "wqkvz": matrix(d, qkv + vh * ghd),
            "conv": matrix(cfg.gdn_conv, qkv, fan_in=cfg.gdn_conv),
            "wba": matrix(2 * vh, d, fan_in=d),
            "out_norm": weight(ghd), "wo": matrix(vh * ghd, d),
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (vh,), minval=1e-3, maxval=16.0)),
            "dt_bias": jnp.ones((vh,)),
        },
        "full": {
            "wq": matrix(d, 2 * cfg.n_heads * hd),
            "wk": matrix(d, cfg.kv_heads * hd),
            "wv": matrix(d, cfg.kv_heads * hd),
            "wo": matrix(cfg.n_heads * hd, d),
            "q_norm": weight(hd), "k_norm": weight(hd),
        },
        "moe": {
            "router": matrix(d, cfg.n_experts),
            "eg": matrix(held, d, f), "eu": matrix(held, d, f),
            "ed": matrix(held, f, d),
            "sg": matrix(d, f), "su": matrix(d, f), "sd": matrix(f, d),
            "sgate": matrix(1, d, fan_in=d),
        },
    }, x


_FLOAT32 = ("a_log", "dt_bias", "out_norm", "q_norm", "k_norm", "router")


def program_layer(cfg, layer, lp, x):
    """One kind of layer as the step calls it."""
    if layer == "gdn":
        return lm._gdn(cfg, lp, x)[0]
    if layer == "full":
        return lm._attend(cfg, lp, x, jnp.arange(x.shape[1]), "mha")[0]
    return moe.moe_topk_held(
        x, lp["router"], (lp["eg"], lp["eu"], lp["ed"]),
        (lp["sg"], lp["su"], lp["sd"]), top_k=cfg.moe_top_k, held=cfg.held,
        renormalize=cfg.moe_renormalize, balance=False,
        shared_gate=lp["sgate"],
    )[0]


class Layers:
    """Each kind of layer alone, in a model's compute dtype, against
    the reference's under `highest`, on inputs both share:
    {"<layer>_rel", "<layer>_l2"} of a (model, swap). Each side's
    program is traced once and kept: the reference's answer once a
    seed, whatever is held against it."""

    def __init__(self, ref, sizes):
        self._programs, self._want, self._seed = {}, {}, None
        reference = {
            "gdn": lambda lp, x: ref.delta_attention(lp, x, sizes),
            "full": lambda lp, x: ref.gated_attention(lp, x, sizes),
            "moe": lambda lp, x: ref.expert_layer(lp, x, sizes)[0],  # y
        }
        self._reference = {k: jax.jit(f) for k, f in reference.items()}

    def errors(self, name, cfg, seed, length, swap=None, only=LAYERS):
        found = {}
        if seed != self._seed:  # the last seed's answers go
            self._want, self._seed = {}, seed
        leaves, x = layer_inputs(cfg, seed, length)
        for layer in only:
            if layer not in self._want:
                with jax.default_matmul_precision("highest"):
                    self._want[layer] = self._reference[layer](leaves[layer], x)
            want = self._want[layer]
            if (name, layer) not in self._programs:
                self._programs[name, layer] = jax.jit(
                    lambda lp, x, layer=layer: program_layer(cfg, layer, lp, x)
                )
            with swapped(*swap) if swap else contextlib.nullcontext():
                got = self._programs[name, layer]({
                    k: v if k in _FLOAT32 else v.astype(cfg.dtype)
                    for k, v in leaves[layer].items()
                }, x.astype(cfg.dtype)).astype(jnp.float32)
            found[f"{layer}_rel"] = float(
                jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))
            )
            found[f"{layer}_l2"] = float(
                jnp.linalg.norm(got - want) / jnp.linalg.norm(want)
            )
        return found


def segmented_rule(ref, segment=64):
    """The reference's recurrence with its pass over the tokens in
    segments of `segment` under `jax.checkpoint`: what the backward
    pass keeps is a state a segment, not a state a token (17 GB at 8192
    tokens x 32 heads)."""
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

    return segmented


class ReferenceGradient:
    """The reference's loss, loads and gradient of one sequence, layer
    by layer: the forward pass keeps each layer's input, the head gives
    the loss and the last layer's cotangent, and one `jax.vjp` a layer
    walks back down, each block of scores and each segment of the
    recurrence under `jax.checkpoint`. One program a kind of layer, so
    a compile holds a layer and not the stack."""

    def __init__(self, ref, sizes):
        self._ref, self._sizes = ref, sizes
        block = jax.checkpoint(ref.block_attention, static_argnums=(3,))
        rule = segmented_rule(ref)

        def layer(lp, h, kind):
            with swapped(ref, "block_attention", block), swapped(
                ref, "delta_rule", rule
            ):
                return ref.layer(lp, h, kind, sizes)

        def apart(lp, h, kind):  # (outputs that carry gradient), the loads
            out, balance, load = layer(lp, h, kind)
            return (out, balance), load

        def back(lp, h, cotangent, kind):
            _out, pull, _load = jax.vjp(
                lambda lp, h: apart(lp, h, kind), lp, h, has_aux=True
            )
            # the loss holds every layer's balance term at its weight
            return pull((cotangent, jnp.float32(sizes["aux_weight"])))

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
        """-> (loss, loads [layers, E], grad: the tree's)."""
        ref, kinds = self._ref, self._sizes["kinds"]
        layers = list(ref.layers_of(params, self._sizes))
        inputs, loads, balance = [params["embed"][tokens]], [], 0.0
        for lp, kind in zip(layers, kinds):
            h, term, load = self._layer(lp, inputs[-1], kind)
            inputs.append(h)
            loads.append(load)
            balance = balance + term
        loss, (ln_f, head, cotangent) = self._head(
            params["ln_f"], params["head"], inputs.pop(), targets
        )
        loss = loss + self._sizes["aux_weight"] * balance
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
        # the decay's two numbers a value head, back into their one leaf
        linear = [g for g in grads if "a_log" in g]
        decay = jnp.concatenate(
            [g["a_log"] for g in linear] + [g["dt_bias"] for g in linear]
        )
        return loss, jnp.stack(loads), {
            "embed": self._embed(params["embed"], tokens, cotangent),
            "gdn_decay": decay, "head": head, "ln_f": ln_f, "stack": stack,
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
    swaps = swaps_for(timed.cfg)
    variables = timed.init(jax.random.PRNGKey(seed), None)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    # on the host between the programs: the float32 step's temporaries
    # lie beside its own copy of the vector and the gradient
    flat = np.asarray(ravel_pytree(params)[0])
    shapes = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), params
    )
    if "steps" not in programs:
        programs["steps"] = {
            name: WorkerStep(
                zoo, models.get(name, timed), variables, swaps.get(name)
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
    scans = scan_errors(
        ref, timed.cfg, seed, length, {"own": _chunked, **SCAN_CONTROLS}
    )
    alone = programs["layers"]
    layers = {"timed": alone.errors("timed", timed.cfg, seed, length)}
    with jax.default_matmul_precision("highest"):
        layers["float32"] = alone.errors(
            "float32", models["float32"].cfg, seed, length
        )
    for name in CONTROLS:
        shown = SHOWN_BY.get(name)
        if shown is None:
            continue
        if name == "bf16_scores":  # on the float32 program's layer
            with jax.default_matmul_precision("highest"):
                found = alone.errors(
                    name, models["float32"].cfg, seed, length, swaps[name],
                    only=(shown,),
                )
        else:
            found = alone.errors(
                name, models.get(name, timed).cfg, seed, length,
                swaps.get(name), only=(shown,),
            )
        layers[name] = {**layers["timed"], **found}
    del params
    found, stats, results = {}, {}, {}

    def judge(name, precision=None):
        # a control held by the layers alone is given the timed step
        step = name if name in steps else "timed"
        if step not in results:
            with jax.default_matmul_precision(precision) if precision else (
                contextlib.nullcontext()
            ):
                results.clear()  # one gradient of 1.3 GB on the host at a time
                results[step] = steps[step](flat, features, labels)
        result = dict(results[step])
        result["router_flips"] = flips["bf16" if name == "bf16_router" else "own"]
        result["scan_rel"] = scans.get(name, scans["own"])
        result.update(layers.get(name, layers["timed"]))
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
    attention_limits = {k: TIGHT[k] for k in ("full_rel", "full_l2")}
    out_of_band = {
        name: beyond(name, attention_limits if name == "bf16_scores" else BAND)
        for name in ("timed",) + CONTROLS
    }
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
    sizes = Hashable(ref.sizes_of(
        zoo.SIZES, top_k=cfg.moe_top_k, held=cfg.held,
        gdn_key_heads=cfg.gdn_key_heads, gdn_value_heads=cfg.gdn_value_heads,
        gdn_head_dim=cfg.gdn_head_dim, heads=cfg.n_heads,
        aux_weight=cfg.aux_weight,
        kv_heads=cfg.kv_heads, head_dim=cfg.head_dim, rope_dim=cfg.rope_dim,
    ))
    programs = {
        "models": models, "ref": ref, "sizes": sizes,
        "reference": ReferenceGradient(ref, sizes),
        "layers": Layers(ref, sizes),
    }
    verdicts = [
        compare_seed(zoo, programs, args.seed + i, args.small)
        for i in range(args.seeds)
    ]
    ok = all(v["ok"] for v in verdicts)
    out = os.path.join(ROOT, "chiprun_out", "qwen3_next_compare.jsonl")
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
