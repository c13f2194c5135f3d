"""Mamba-2's chunked scan standalone on the chip at the
`nemotron-3-nano-30b-a3b` cell's shape: (1, 8192) tokens, 64 heads of
64 over a state of 128, B and C in 8 groups, chunks of 128.

The pass between chunks three ways, alone (a [1, 64, 64, 64, 128]
float32 stack of chunk states in, the state each chunk meets out) and
inside the whole call, ms a call, forward and forward + backward:

- `matrix`: `ops/ssd.state_pass`, ONE product with the [chunks, chunks]
  lower-triangular matrix of decay products a head: what the tree
  keeps;
- `associative`: `lax.associative_scan` over the chunks on (decay,
  state) pairs, then shifted by one chunk;
- `scan`: a `lax.scan` over the chunks that carries the state, of
  `kda.chunk_step`'s kind (a `while` of 64 trips).

The `intra` stage (and the other products) two ways: `bf16`, operands
in bfloat16 as a timed model hands them over, and `float32`, operands
in float32 under `jax.default_matmul_precision("highest")`. Each whole
call against `ssd_recurrent` (the recurrence a token at a time, four
heads at a time), outputs and the five input gradients.

    chiprun -- python scripts/ssd_scan_probe.py

Writes chiprun_out/ssd_scan_probe.json. `--small` is the CPU rehearsal
of the script's plumbing (its numbers are no device numbers).
"""

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from elasticdl_tpu.ops import ssd  # noqa: E402


def associative_pass(added, total):
    """(decay, state) pairs combine as (a2 a1, a2 s1 + s2); the state a
    chunk meets is the running state one chunk back."""
    decay = jnp.exp(total)[..., None, None]  # [B, n, H, 1, 1]

    def combine(left, right):
        return right[0] * left[0], right[0] * left[1] + right[1]

    _, through = lax.associative_scan(
        combine, (jnp.broadcast_to(decay, added.shape), added), axis=1
    )
    return jnp.concatenate(
        [jnp.zeros_like(through[:, :1]), through[:, :-1]], axis=1
    )


def scan_pass(added, total):
    def step(S, xs):
        add, keep = xs
        return keep[..., None, None] * S + add, S

    _, met = lax.scan(
        step, jnp.zeros_like(added[:, 0]),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(jnp.exp(total), 1, 0)),
    )
    return jnp.moveaxis(met, 0, 1)


PASSES = {
    "matrix": ssd.state_pass, "associative": associative_pass,
    "scan": scan_pass,
}


@contextlib.contextmanager
def state_pass_is(other):
    kept, ssd.state_pass = ssd.state_pass, other
    try:
        yield
    finally:
        ssd.state_pass = kept


def inputs(shape, groups, state, seed, dtype):
    """x [B, L, H, P], B and C [B, L, G, N] as a layer makes them (SiLU
    of normals), dt log-uniform on (0.001, 0.1), A = -uniform(1, 16):
    the configuration's untrained step and rate."""
    B, L, H, P = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.nn.silu(jax.random.normal(keys[0], shape)).astype(dtype)
    Bm, Cm = (
        jax.nn.silu(jax.random.normal(key, (B, L, groups, state))).astype(dtype)
        for key in keys[1:3]
    )
    dt = jnp.exp(jax.random.uniform(
        keys[3], (B, L, H), minval=jnp.log(0.001), maxval=jnp.log(0.1)
    ))
    A = -jax.random.uniform(keys[4], (H,), minval=1.0, maxval=16.0)
    return (x, dt, A, Bm, Cm), jax.random.normal(keys[5], shape)


def through(f, argnums):
    def loss(w, *a):
        o = f(*a)
        return jnp.sum(o * w), o

    return jax.jit(jax.value_and_grad(loss, argnums=argnums, has_aux=True))


def timed(fn, args, repeats=10):
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--small", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    shape, groups, state, chunk = (1, 8192, 64, 64), 8, 128, 128
    if args.small:
        shape, groups, state, chunk = (1, 96, 4, 8), 2, 16, 16
    B, L, H, P = shape
    results = {"device": jax.devices()[0].device_kind, "shape": shape,
               "groups": groups, "state": state, "chunk": chunk,
               "pass_alone": {}, "whole": {}}

    # ---- the pass between chunks alone
    keys = jax.random.split(jax.random.PRNGKey(args.seed + 7), 3)
    added = jax.random.normal(keys[0], (B, L // chunk, H, P, state))
    total = -jax.random.uniform(keys[1], (B, L // chunk, H), maxval=8.0)
    w = jax.random.normal(keys[2], added.shape)
    want = None
    for name, f in PASSES.items():
        both = through(f, (1, 2))
        (_, met), grads = both(w, added, total)
        got = (met, *grads)
        want = want or got  # `matrix` first: the others against it
        results["pass_alone"][name] = {
            "against_matrix": [
                float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                for a, b in zip(got, want)
            ],
            "fwd_ms": timed(jax.jit(f), (added, total)),
            "fwd_bwd_ms": timed(both, (w, added, total)),
        }
        print("pass alone", name, json.dumps(results["pass_alone"][name]),
              flush=True)

    # ---- the whole call, against the recurrence
    names = ("y", "dx", "ddt", "dA", "dB", "dC")
    arrays, w = inputs(shape, groups, state, args.seed, jnp.float32)
    x, dt, A, Bm, Cm = arrays
    recurrent, wants = through(ssd.ssd_recurrent, (1, 2, 3, 4, 5)), []
    per = min(4, H // groups)  # heads at a time, inside one group
    with jax.default_matmul_precision("highest"):
        for h in range(0, H, per):
            heads = slice(h, h + per)
            # the group these heads read, as a group of its own
            g = h // (H // groups)
            (_, yh), gh = recurrent(
                w[:, :, heads], x[:, :, heads], dt[:, :, heads], A[heads],
                Bm[:, :, g:g + 1], Cm[:, :, g:g + 1],
            )
            wants.append((yh, *gh))
    per_group = (H // groups) // per  # slices of heads that share a group

    def joined(i):
        parts = [p[i] for p in wants]
        if names[i] in ("dB", "dC"):  # a group's gradient: its heads' sum
            return jnp.concatenate([
                sum(parts[j:j + per_group])
                for j in range(0, len(parts), per_group)
            ], axis=2)
        return jnp.concatenate(parts, axis=0 if names[i] == "dA" else 2)

    want = [joined(i) for i in range(len(names))]

    def call(*a):
        return ssd.ssd_chunked(*a, chunk=chunk)[0]

    for operands in ("bf16", "float32"):
        cast = [
            t.astype(jnp.bfloat16) if operands == "bf16" and i in (0, 3, 4)
            else t for i, t in enumerate(arrays)
        ]
        precision = (
            jax.default_matmul_precision("highest") if operands == "float32"
            else contextlib.nullcontext()
        )
        for name, f in PASSES.items():
            with state_pass_is(f), precision:
                both, forward = through(call, (1, 2, 3, 4, 5)), jax.jit(call)
                (_, y), grads = both(w, *cast)
                entry = {
                    "errors": {
                        n: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                                 / jnp.max(jnp.abs(b)))
                        for n, a, b in zip(names, (y, *grads), want)
                    },
                    "fwd_ms": timed(forward, cast),
                    "fwd_bwd_ms": timed(both, (w, *cast)),
                }
            results["whole"][f"{operands}/{name}"] = entry
            print("whole", operands, name, json.dumps(entry), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "ssd_scan_probe.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
