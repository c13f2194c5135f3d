"""On the chip, at the configuration's sizes: what the worker's own
step computes against the plain reference.

    python benchmark/configs/nemotron-3-nano-30b-a3b/compare.py --seed <n> [--seeds k]

One process that holds the chip itself (run it through `chiprun`, never
beside a job), ONE SEED A PROCESS at the configuration's sizes
(`--seeds 1`, the default: the float32 step's program fills the chip,
and a second seed would find the first one's step programs loaded
beside it). For each seed: the zoo module's weights from the seed and
one minibatch (`minibatch_per_chip` x `seq_len` = 1 x 4096) of the
cell's own RecordIO data go through **the program a `Worker` builds**
(`Worker._build_local_step()`: `_local_step_core` jitted with its
donations, the step the window program scans 16 times), with the
model's non-trainable collection in `aux`, so `_apply_model`'s
`mutable` path runs as it does in the cell. The one thing swapped is
the zoo's optimizer, for `optax.trace(decay=0)`: its state after one
step IS the flat gradient the step differentiated, bit for bit. From
one call: the loss, the gradient and what the routers did
(`window_stats`). All held against `reference.py` (float32 under
`jax.default_matmul_precision("highest")`: the pattern string walked a
block at a time, the recurrence a token at a time, attention a
key-value head at a time with its scores written out a block of
queries at a time, the experts a masked dense sum), differentiated
BLOCK BY BLOCK by this script (`ReferenceGradient`: the reference's own
`block` and `head_loss`, one `jax.vjp` a block from the head down, each
block of scores and each segment of 64 tokens of the recurrence under
`jax.checkpoint`: what is kept for the backward pass, not what is
computed), so that the sequence fits and no program holds more than a
block.

Beside the whole step, each kind of block ALONE, as the step calls it
(`transformer_lm._mamba2`, `transformer_lm._attend`,
`moe.moe_topk_held`, in the program's compute dtype, no ambient
precision: on the chip the Pallas attention kernels), on one sequence
of inputs both sides share, against the reference's block under
`highest`: `ssm_rel`, `attn_rel`, `moe_rel`, the largest error over the
reference's largest output, and `ssm_l2`, `attn_l2`, `moe_l2`, the
error's norm over the output's; and the SCAN alone (`ssd.ssd_chunked`
on float32 operands under `highest` against the reference's recurrence
a token at a time) at the untrained steps and rates, under which a head's state
lives for one to a thousand tokens: `scan_rel`. The whole step's
gradient carries bfloat16's rounding of every projection, under which
a grouping or a rounded sum can hide; a block alone cannot.

1. `float32`: the model with `dtype` float32, same precision: the same
   mathematics in another order, so the two agree to accumulated
   rounding and to the assignments that rounding moves among the
   experts: `TIGHT` (the gradient by `TIGHT_FLIPPED` on a seed on which
   it moved one to or from an expert held here).
2. `timed`: the model as the cell times it, bfloat16 compute with
   float32 parameters, accumulation, router, step, decay and its sums,
   states, norms, scores and softmax and logits-to-loss: inside `BAND`,
   whose limits lie between the timed program's largest reading over
   the seeds and the smallest of the controls, each of which has to
   come out NOT correct by at least one of `BAND`'s limits:
3. `no_decay`: A = 0, every a_t = 1;
4. `group_mod`: head j reads group j mod 8, not j // 8;
5. `whole_norm`: the gated norm over all 4096 channels;
6. `norm_before_gate`: the grouped norm first, then SiLU(z);
7. `no_skip`: D = 0;
8. `relu`: the experts' activation not squared;
9. `gates_x1`: no routed scaling of 2.5;
10. `rotary`: attention turned at theta 1e4;
11. `kv_head_mod`: query head i reads key-value head i mod 2;
12. `bf16_decay`: the cumulative sums of the log-decay in bfloat16, the
    nearest precision below the float32 the configuration states for
    them; held by `scan_rel` against `TIGHT`'s limit for it (its
    operands are float32 there) and by the float32 program's Mamba-2
    block alone against `TIGHT`'s two for it.

Not compared here: clipped Adam and the 16-step scan around the step,
which the cell itself runs to its loss check.

Prints one JSON line a seed and one verdict; exit 0 only if 1 and 2
hold and every control fails, for every seed. The whole step is run for
`timed`, `float32` and the controls of `STEP_CONTROLS`; the others are
held by the blocks alone, where bfloat16's rounding of every projection
does not cover them, and are given the timed program's step. `--small`
is the CPU rehearsal of the script's plumbing (tiny sizes; its numbers
are no device numbers and its band is not judged).
"""

import argparse
import contextlib
import dataclasses
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
    leaf_of,
    norm,
    reference_step,
    say,
    swapped,
)
from benchmark.harness.manifest import load_module  # noqa: E402
from elasticdl_tpu.models import transformer_lm as lm  # noqa: E402
from elasticdl_tpu.ops import flash_attention, ssd  # noqa: E402
from elasticdl_tpu.parallel import moe  # noqa: E402

# The limits were set from this PR's first three seeds on the chip (my
# chip runs, PR 56, call 2: 2147484611, 2147484712, 2147484813, a seed a
# process, about 15 minutes each; PERF.md section 6) and then held to
# seeds not used while they were set (call 4: one PASS, one that moved
# TIGHT_FLIPPED, below; call 5, under the limits as committed: PASS).
#
# float32 against float32: the same mathematics in another order. On
# the seed on which no assignment moved (`load_abs` 0) the float32
# program read: loss 9.3e-8, the gradient 1.5e-4 in L2 and 6.9e-4 of the
# largest entry. On the two on which rounding moved an assignment to or
# from an expert held here (`load_abs` 2 and 1; 8 of 128 held: one move
# in 16 touches them, and a gate is up to 2.5): loss 1.0e-5 and 1.7e-5,
# the gradient 0.0036 and 0.0029 in L2, 0.0178 and 0.0123 of the largest
# entry: TIGHT_FLIPPED. The blocks alone, all three seeds: `ssm_rel`
# 2.4e-5 to 5.9e-5, `ssm_l2` 1.5e-5 to 4.0e-5 (the scan's float32
# products under `highest` and the reference's in another order),
# `attn_rel` 3.5e-7 to 4.2e-7, `attn_l2` 5.5e-7, `moe_rel` 3.8e-7 to
# 4.3e-7, `moe_l2` 1.9e-7; the scan alone `scan_rel` 3.4e-5 to 4.0e-5.
# Each limit lies above that and under the nearest precision below: the
# timed bfloat16 program's smallest reading (loads 15, gradient 0.035 |
# 0.058, `ssm` 0.0057 | 0.0057, `attn` 0.0039 | 0.0055, `moe` 0.265 |
# 0.0114) or, for the decay's arithmetic, `bf16_decay` (`scan_rel` 0.069
# to 0.114; the float32 program's Mamba-2 block alone 0.068 to 0.17 |
# 0.020 to 0.031). The loss hardly feels the precision (timed 5.5e-5 to
# 1.0e-4), so the timed program is told from the float32 one by its
# gradient and its blocks, not by `loss_rel`.
TIGHT = {
    "loss_rel": 1.5e-5,
    "load_abs": 8,
    "grad_rel_l2": 1.2e-3,
    "grad_max_rel": 0.01,
    "ssm_rel": 3e-4,
    "ssm_l2": 3e-4,
    "attn_rel": 2e-4,
    "attn_l2": 5e-4,
    "moe_rel": 3e-4,
    "moe_l2": 1e-4,
    "scan_rel": 1e-3,
}
# A moved assignment (call 4's second fresh seed: three moved, loss
# 1.3e-5, the gradient 0.0123 in L2 and 0.0672 of its largest entry,
# which failed this dict's first values, 0.012 | 0.035, set from the two
# moved seeds of call 2) reads as large an entry as bfloat16's rounding
# does (timed 0.058 to 0.098), so on such a seed `grad_max_rel` tells
# nothing and is not judged; the L2 still parts the two tiers (float32
# at most 0.0123, timed at least 0.035).
TIGHT_FLIPPED = {
    k: v for k, v in {**TIGHT, "loss_rel": 5e-5, "grad_rel_l2": 0.02}.items()
    if k != "grad_max_rel"
}
# bfloat16 compute against the float32 reference: each limit between
# the timed program's largest reading over the three seeds and the
# smallest of the controls that separate from it there. Loss 5.5e-5 to
# 1.0e-4 beside `no_decay`'s 1.3e-3 to 5.3e-3; `load_abs` 15 to 26 beside
# `no_decay`'s 643 to 1180 (`gates_x1` 76 to 96: not told by its loads);
# the gradient 0.035 to 0.042 in L2 and 0.058 to 0.080 of its largest
# entry beside `gates_x1`'s 0.244 to 0.279 | 0.249 to 0.283 and
# `no_decay`'s 1.29 | 2.0. The Mamba-2 block alone 0.0057 to 0.0059 |
# 0.0057 to 0.0059 beside `norm_before_gate` 0.43 to 0.50 | 0.43,
# `group_mod` 0.46 to 0.61 | 0.28 to 0.34, `whole_norm` 0.57 to 0.65 |
# 0.25 to 0.27, `no_skip` 0.73 to 0.93 | 0.73 to 0.75, `no_decay` 1.06 to
# 1.11 | 1.06 to 1.10. The attention block 0.0039 to 0.0048 | 0.0055 to
# 0.0056 beside `rotary` 0.26 to 0.30 | 0.80 to 0.82 and `kv_head_mod`
# 0.90 to 1.05 | 1.0. The expert block's L2 0.0114 to 0.0163 beside
# `gates_x1` 0.147 to 0.148 and `relu` 0.52. **`moe_rel` is not in the
# band**: a largest error, it read 0.265 to 0.298 for the timed program
# on every seed, one token whose sixth and seventh choice the bfloat16
# rounding of its input swaps where one of the two is held here (a gate
# of up to 2.5 times an expert's output), as much as `gates_x1`'s 0.30 to
# 0.42: it tells nothing at these sizes, and `moe_l2` holds the block.
# `scan_rel` is TIGHT's: the scan alone has float32 operands on both
# tiers. Call 4's two fresh seeds widened the timed program's ranges
# inside these limits: loss 9.6e-6 to 1.0e-4, `load_abs` 12 to 29, the
# gradient 0.035 to 0.051 | 0.058 to 0.098, `ssm` 0.0057 to 0.0068 |
# 0.0057 to 0.0059, `attn` 0.0035 to 0.0048 | 0.0055 to 0.0056, `moe_l2`
# 0.0114 to 0.0174 (`moe_rel` 0.265 to 0.414); every control stayed
# outside on both.
BAND = {
    "loss_rel": 5e-4,
    "load_abs": 120,
    "grad_rel_l2": 0.12,
    "grad_max_rel": 0.15,
    "ssm_rel": 0.05,
    "ssm_l2": 0.04,
    "attn_rel": 0.035,
    "attn_l2": 0.05,
    "moe_l2": 0.05,
    "scan_rel": 1e-3,
}
CONTROLS = ("no_decay", "group_mod", "whole_norm", "norm_before_gate",
            "no_skip", "relu", "gates_x1", "rotary", "kv_head_mod",
            "bf16_decay")
# the controls whose whole step is run (a step's program takes a minute
# to compile); the others are held by the blocks alone
STEP_CONTROLS = ("no_decay", "gates_x1")
LAYERS = ("ssm", "attn", "moe")
SMALL = dict(
    vocab=97, d_model=64, ssm_heads=4, ssm_head_dim=16, ssm_state=16,
    ssm_groups=2, ssm_chunk=16, n_heads=4, n_kv_heads=2, head_width=16,
    n_experts=16, held_experts=(4, 4), d_expert=24, moe_top_k=3,
)


def measures(got, want):
    gap = got["grad"] - want["grad"]
    return {
        "loss_rel": abs(got["loss"] - want["loss"]) / abs(want["loss"]),
        "load_abs": float(
            np.max(np.sum(np.abs(got["loads"] - want["loads"]), axis=-1))
        ),
        "scan_rel": got["scan_rel"],
        **{f"{layer}_{kind}": got[f"{layer}_{kind}"]
           for layer in LAYERS for kind in ("rel", "l2")},
        "grad_rel_l2": norm(gap) / norm(want["grad"]),
        "grad_max_rel": float(np.max(np.abs(gap)) / np.max(np.abs(want["grad"]))),
        "grad_max_at": int(np.argmax(np.abs(gap))),
    }


# ------------------------------------------------------------- the controls


_chunked = ssd.ssd_chunked
_gate_norm = lm._ssm_gate_norm
_attention = flash_attention.attention


def chunked_no_decay(x, dt, A, Bm, Cm, **kw):
    return _chunked(x, dt, jnp.zeros_like(A), Bm, Cm, **kw)


def chunked_group_mod(x, dt, A, Bm, Cm, **kw):
    """Head j reads group j mod the groups."""
    reads = jnp.arange(x.shape[2]) % Bm.shape[2]
    return _chunked(x, dt, A, Bm[:, :, reads], Cm[:, :, reads], **kw)


def chunked_bf16_decay(x, dt, A, Bm, Cm, **kw):
    """The log-decay's cumulative sums in bfloat16 (`reduce_precision`
    on every partial sum's way out, not a cast there and back: the TPU
    compiler drops such a pair)."""
    def rounded(a, axis):
        return lax.reduce_precision(
            jnp.cumsum(lax.reduce_precision(a.astype(jnp.float32), 8, 7),
                       axis=axis), 8, 7,
        )

    with swapped(ssd, "_cumsum", rounded):
        return _chunked(x, dt, A, Bm, Cm, **kw)


def whole_norm(cfg, y, z, weight):
    return _gate_norm(dataclasses.replace(cfg, ssm_groups=1), y, z, weight)


def norm_before_gate(cfg, y, z, weight):
    b, l, inner = y.shape
    grouped = y.reshape(b, l, cfg.ssm_groups, inner // cfg.ssm_groups)
    grouped = grouped * lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + cfg.norm_eps
    )
    y = grouped.reshape(b, l, inner) * weight
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)


def no_skip(D, x):
    return jnp.zeros(x.shape, jnp.float32)


def attention_kv_head_mod(q, k, v, **kw):
    """Query head i reads key-value head i mod the key-value heads."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.tile(t, (1, 1, group, 1)) for t in (k, v))
    return _attention(q, k, v, **kw)


# a control is a model override or a swap (module, name, other)
OVERRIDES = {
    "gates_x1": dict(routed_scaling=1.0),
    "rotary": dict(rope=True),
}
SWAPS = {
    "no_decay": (ssd, "ssd_chunked", chunked_no_decay),
    "group_mod": (ssd, "ssd_chunked", chunked_group_mod),
    "bf16_decay": (ssd, "ssd_chunked", chunked_bf16_decay),
    "whole_norm": (lm, "_ssm_gate_norm", whole_norm),
    "norm_before_gate": (lm, "_ssm_gate_norm", norm_before_gate),
    "no_skip": (lm, "_ssm_skip", no_skip),
    "relu": (moe, "_relu2", jax.nn.relu),
    "kv_head_mod": (flash_attention, "attention", attention_kv_head_mod),
}
# which block alone shows a control (the others borrow the timed
# program's readings there)
SHOWN_BY = {
    "no_decay": "ssm", "group_mod": "ssm", "whole_norm": "ssm",
    "norm_before_gate": "ssm", "no_skip": "ssm", "bf16_decay": "ssm",
    "relu": "moe", "gates_x1": "moe", "rotary": "attn", "kv_head_mod": "attn",
}
SCAN_CONTROLS = {
    "no_decay": chunked_no_decay, "group_mod": chunked_group_mod,
    "bf16_decay": chunked_bf16_decay,
}


def scan_errors(ref, cfg, seed, length, variants):
    """{name: the largest error of `variants[name]`'s outputs over the
    largest output of the reference's recurrence a token at a time}, on
    one sequence of `length` tokens both share: x, B and C as a block
    makes them (SiLU of normals), float32; the step log-uniform on
    (0.001, 0.1) and the rate uniform on (1, 16), the initialiser's.
    Both sides under `highest`: the scan's four products take float32
    operands here, as the float32 program's do, so that what is read is
    the decay's arithmetic and not bfloat16's rounding of x, B and C
    (which `ssm_rel` of the timed block carries)."""
    heads, p = cfg.ssm_heads, cfg.ssm_head_dim
    groups, n = cfg.ssm_groups, cfg.ssm_state
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.nn.silu(jax.random.normal(keys[0], (1, length, heads, p)))
    Bm, Cm = (
        jax.nn.silu(jax.random.normal(key, (1, length, groups, n)))
        for key in keys[1:3]
    )
    dt = jnp.exp(jax.random.uniform(
        keys[3], (1, length, heads), minval=jnp.log(0.001), maxval=jnp.log(0.1)
    ))
    A = -jax.random.uniform(keys[4], (heads,), minval=1.0, maxval=16.0)
    reads = jnp.arange(heads) // (heads // groups)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.selective_scan)(
            x, dt, jnp.exp(dt * A), Bm[:, :, reads], Cm[:, :, reads]
        )
        scale = float(jnp.max(jnp.abs(want)))
        return {
            name: float(jnp.max(jnp.abs(
                jax.jit(lambda *a, f=f: f(*a, chunk=cfg.ssm_chunk)[0])(
                    x, dt, A, Bm, Cm
                ) - want
            ))) / scale
            for name, f in variants.items()
        }


def layer_inputs(cfg, seed, length):
    """One sequence of unit-variance rows and each kind of block's
    leaves as the initialiser draws them (matrices at 1/sqrt(fan-in),
    the norms' weights off one, a bias on the taps and on the
    selection, the untrained step and rate), float32."""
    d, hd = cfg.d_model, cfg.head_dim
    heads = cfg.ssm_heads
    inner, bc = heads * cfg.ssm_head_dim, 2 * cfg.ssm_groups * cfg.ssm_state
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 40))

    def matrix(*shape, fan_in=None):
        return jax.random.normal(next(keys), shape) / (
            fan_in or shape[-2]
        ) ** 0.5

    def weight(n):
        return 1.0 + 0.1 * jax.random.normal(next(keys), (n,))

    x = jax.random.normal(next(keys), (1, length, d))
    dt = jnp.exp(jax.random.uniform(
        next(keys), (heads,), minval=jnp.log(0.001), maxval=jnp.log(0.1)
    ))
    first, held = cfg.held
    f, fs = cfg.d_expert, cfg.n_shared_experts * cfg.d_expert
    return {
        "ssm": {
            "in_proj": matrix(d, 2 * inner + bc + heads),
            "conv": matrix(cfg.ssm_conv, inner + bc, fan_in=cfg.ssm_conv),
            "conv_bias": 0.1 * jax.random.normal(next(keys), (inner + bc,)),
            "ssm_norm": weight(inner), "out_proj": matrix(inner, d),
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (heads,), minval=1.0, maxval=16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": weight(heads),
        },
        "attn": {
            "wq": matrix(d, cfg.n_heads * hd),
            "wk": matrix(d, cfg.kv_heads * hd),
            "wv": matrix(d, cfg.kv_heads * hd),
            "wo": matrix(cfg.n_heads * hd, d),
        },
        "moe": {
            "router": matrix(d, cfg.n_experts),
            "router_bias": 0.01 * jax.random.normal(next(keys), (cfg.n_experts,)),
            "eu": matrix(held, d, f), "ed": matrix(held, f, d),
            "su": matrix(d, fs), "sd": matrix(fs, d),
        },
    }, x


_FLOAT32 = ("a_log", "dt_bias", "D", "ssm_norm", "router", "router_bias")


def program_layer(cfg, layer, lp, x):
    """One kind of block as the step calls it."""
    if layer == "ssm":
        return lm._mamba2(cfg, lp, x)[0]
    if layer == "attn":
        return lm._attend(cfg, lp, x, jnp.arange(x.shape[1]), "mha")[0]
    return moe.moe_topk_held(
        x, lp["router"], (lp["eu"], lp["ed"]), (lp["su"], lp["sd"]),
        top_k=cfg.moe_top_k, held=cfg.held, scaling=cfg.routed_scaling,
        score="sigmoid", bias=lp["router_bias"], renormalize=True,
        balance=False,
    )[0]


class Layers:
    """Each kind of block alone, in a model's compute dtype, against
    the reference's under `highest`, on inputs both share:
    {"<layer>_rel", "<layer>_l2"} of a (model, swap). Each side's
    program is traced once and kept: the reference's answer once a
    seed, whatever is held against it."""

    def __init__(self, ref, sizes):
        self._programs, self._want, self._seed = {}, {}, None
        reference = {
            "ssm": lambda lp, x: ref.mamba2(lp, x, sizes),
            "attn": lambda lp, x: ref.attention(lp, x, sizes),
            "moe": lambda lp, x: ref.experts(lp, x, sizes)[0],  # y
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


def segmented_scan(ref, segment=64):
    """The reference's recurrence with its pass over the tokens in
    segments of `segment` under `jax.checkpoint`: what the backward
    pass keeps is a state a segment, not a state a token (2 MB each at
    64 heads of 64 x 128)."""
    plain = ref.selective_scan

    def segmented(x, dt, decay, b, c):
        batch, length, heads, width = x.shape
        if length % segment:
            return plain(x, dt, decay, b, c)

        def cut(t):
            t = t.reshape((batch, length // segment, segment) + t.shape[2:])
            return jnp.moveaxis(t, 1, 0)

        @jax.checkpoint
        def a_segment(state, xs):
            return lax.scan(
                ref.ssm_step, state, tuple(jnp.moveaxis(t, 1, 0) for t in xs)
            )

        start = jnp.zeros((batch, heads, width, b.shape[-1]), jnp.float32)
        _, out = lax.scan(
            a_segment, start, tuple(cut(t) for t in (x, dt, decay, b, c))
        )
        out = jnp.moveaxis(out, 1, 2)  # [segments, batch, segment, H, P]
        return jnp.moveaxis(out, 0, 1).reshape(batch, length, heads, width)

    return segmented


class ReferenceGradient:
    """The reference's loss, loads and gradient of one sequence, block
    by block along the published pattern: the forward pass keeps each
    block's input, the head gives the loss and the last block's
    cotangent, and one `jax.vjp` a block walks back down, each block of
    scores and each segment of the recurrence under `jax.checkpoint`.
    One program a kind of block, so a compile holds a block and not the
    stack."""

    def __init__(self, ref, sizes):
        self._ref, self._sizes = ref, sizes
        scores = jax.checkpoint(ref.block_attention, static_argnums=(3,))
        scan = segmented_scan(ref)

        def block(lp, h, letter):
            with swapped(ref, "block_attention", scores), swapped(
                ref, "selective_scan", scan
            ):
                return ref.block(letter, lp, h, sizes)

        def back(lp, h, cotangent, letter):
            _out, pull, _load = jax.vjp(
                lambda lp, h: block(lp, h, letter), lp, h, has_aux=True
            )
            return pull(cotangent)

        self._block = jax.jit(block, static_argnums=(2,))
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
        """-> (loss, loads [E blocks, E], grad: the tree's)."""
        ref, letters = self._ref, self._sizes["blocks"]
        blocks = list(ref.blocks_of(params, self._sizes))
        assert "".join(letter for letter, _lp in blocks) == letters
        inputs, loads = [params["embed"][tokens]], []
        for letter, lp in blocks:
            h, load = self._block(lp, inputs[-1], letter)
            inputs.append(h)
            if load is not None:
                loads.append(load)
        loss, (ln_f, head, cotangent) = self._head(
            params["ln_f"], params["head"], inputs.pop(), targets
        )
        grads = []
        for letter, lp in reversed(blocks):
            lp_grad, cotangent = self._back(lp, inputs.pop(), cotangent, letter)
            grads.insert(0, lp_grad)
        # the blocks' gradients back onto the tree `blocks_of` read: a
        # layer's mixer block (`norm` is its `ln1`) and, where it has
        # `ln2`, the `E` block behind it; the runs stacked again
        stack, at, mixers = [], 0, []
        for run in params["stack"]:
            layers = []
            for _ in range(run["ln1"].shape[0]):
                mixer = grads[at]
                at += 1
                layer = {k: v for k, v in mixer.items() if k in run}
                layer["ln1"] = mixer["norm"]
                if "a_log" in mixer:
                    mixers.append(mixer)
                if "ln2" in run:
                    experts = grads[at]
                    at += 1
                    layer.update(
                        {k: v for k, v in experts.items() if k != "norm"}
                    )
                    layer["ln2"] = experts["norm"]
                layers.append(layer)
            stack.append({
                name: jnp.stack([layer[name] for layer in layers])
                for name in run
            })
        assert at == len(grads)
        decay = jnp.concatenate([
            g[name] for name in ("a_log", "dt_bias", "D") for g in mixers
        ])
        return loss, jnp.stack(loads), {
            "embed": self._embed(params["embed"], tokens, cotangent),
            "head": head, "ln_f": ln_f, "ssm_decay": decay, "stack": stack,
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
    # on the host between the programs: the float32 step's temporaries
    # lie beside its own copy of the vector and the gradient
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
        shown = SHOWN_BY[name]
        if name == "bf16_decay":  # on the float32 program's block
            with jax.default_matmul_precision("highest"):
                found = alone.errors(
                    name, models["float32"].cfg, seed, length, SWAPS[name],
                    only=(shown,),
                )
        else:
            found = alone.errors(
                name, models.get(name, timed).cfg, seed, length,
                SWAPS.get(name), only=(shown,),
            )
        layers[name] = {**layers["timed"], **found}
    del params
    found, stats, results = {}, {}, {}

    def judge(name, precision=None):
        # a control held by the blocks alone is given the timed step
        step = name if name in steps else "timed"
        if step not in results:
            with jax.default_matmul_precision(precision) if precision else (
                contextlib.nullcontext()
            ):
                results.clear()  # one gradient of 2.1 GB on the host at a time
                results[step] = steps[step](flat, features, labels)
        result = dict(results[step])
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
    decay_limits = {k: TIGHT[k] for k in ("scan_rel", "ssm_rel", "ssm_l2")}
    out_of_band = {
        name: beyond(name, decay_limits if name == "bf16_decay" else BAND)
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
        ssm_heads=cfg.ssm_heads, ssm_head_dim=cfg.ssm_head_dim,
        ssm_groups=cfg.ssm_groups, ssm_state=cfg.ssm_state,
        heads=cfg.n_heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
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
    out = os.path.join(ROOT, "chiprun_out", "nemotron_nano_compare.jsonl")
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
