"""The delta rule's scan under ONE decay a head, standalone on the chip
at the `qwen3-next-80b-a3b` cell's shape: (1, 8192) tokens, 16 key heads
under 32 value heads of 128, chunks of 64.

Four ways to the same outputs, ms a call, forward and forward +
backward, and each against `kda_recurrent` in float32 (the recurrence a
token at a time, four value heads at a time):

- `per_channel`: `kda_chunked` as the parent has it, given q and k
  widened to the value heads and the decay broadcast over the 128 key
  channels (on a TPU at this width: the Pallas kernels of
  `ops/kda_kernels.py`) — the baseline that needs no code;
- `scalar`: g [B, L, H], the key heads read in place (on a TPU at this
  width the same kernels under `scalar=True`: A and B two products on
  the multiplier times a [chunk, chunk] matrix of exponentials);
- `scalar_widened`: g [B, L, H], q and k widened in front;
- `scalar_off_the_kernels`: `scalar` as every other backend runs it,
  `kda.intra_stage` in plain jax told the decay on every channel with q
  and k widened (the scalar kernels' oracle). A scalar stage in plain
  jax (two products and one [chunk, chunk] exponential, no kernel) was
  measured here in PR 52's first round, 14.47 | 34.93 ms against
  `per_channel`'s 9.42 | 27.96, and went in its review: PERF.md.

    chiprun -- python scripts/gdn_scan_probe.py

Writes chiprun_out/gdn_scan_probe.json. `--small` is the CPU rehearsal
of the script's plumbing (its numbers are no device numbers).
"""

import argparse
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

from elasticdl_tpu.ops import kda  # noqa: E402


def inputs(shape, key_heads, seed):
    """q, k [B, L, Hk, d], v [B, L, H, d], g and beta [B, L, H] as a
    layer makes them: SiLU of normals, q and k over their lengths, a
    log-decay of -a softplus(normal + 1) with a uniform in (0, 16) (the
    configuration's untrained decay)."""
    B, L, H, d = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    q, k = (
        jax.nn.silu(jax.random.normal(key, (B, L, key_heads, d)))
        for key in keys[:2]
    )
    v = jax.nn.silu(jax.random.normal(keys[2], shape))
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * d**-0.5
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    rate = jax.random.uniform(keys[3], (H,), minval=1e-3, maxval=16.0)
    g = -rate * jax.nn.softplus(jax.random.normal(keys[4], shape[:3]) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], shape[:3]))
    return (q, k, v, g, beta), jax.random.normal(keys[6], shape)


def ways(group, chunk):
    def widen(x):
        return jnp.repeat(x, group, axis=2)

    def per_channel(q, k, v, g, beta):
        wide = jnp.broadcast_to(g[..., None], g.shape + (q.shape[-1],))
        return kda.kda_chunked(widen(q), widen(k), v, wide, beta, chunk=chunk)[0]

    def scalar(q, k, v, g, beta):
        return kda.kda_chunked(q, k, v, g, beta, chunk=chunk)[0]

    def scalar_widened(q, k, v, g, beta):
        return kda.kda_chunked(widen(q), widen(k), v, g, beta, chunk=chunk)[0]

    def scalar_off_the_kernels(q, k, v, g, beta):
        kept, kda.takes_kernels = kda.takes_kernels, lambda *a, **kw: False
        try:  # read while the call is traced
            return scalar(q, k, v, g, beta)
        finally:
            kda.takes_kernels = kept

    return {"per_channel": per_channel, "scalar": scalar,
            "scalar_widened": scalar_widened,
            "scalar_off_the_kernels": scalar_off_the_kernels}


def through(f):
    def loss(w, *a):
        o = f(*a)
        return jnp.sum(o * w), o

    return jax.jit(
        jax.value_and_grad(loss, argnums=(1, 2, 3, 4, 5), has_aux=True)
    )


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
    shape, key_heads, chunk = (1, 8192, 32, 128), 16, 64
    if args.small:
        shape, key_heads, chunk = (1, 96, 4, 16), 2, 16
    group = shape[2] // key_heads
    arrays, w = inputs(shape, key_heads, args.seed)
    q, k, v, g, beta = arrays
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    # the recurrence four value heads at a time: its backward pass keeps
    # a state a token
    recurrent, wants = through(kda.kda_recurrent), []
    per = min(4, shape[2])
    for h in range(0, shape[2], per):
        heads = slice(h, h + per)
        keys_ = slice(h // group, (h + per) // group)
        (_, oh), gh = recurrent(
            w[:, :, heads], q[:, :, keys_], k[:, :, keys_], v[:, :, heads],
            g[:, :, heads], beta[:, :, heads],
        )
        wants.append((oh, *gh))
    want = [jnp.concatenate(parts, axis=2) for parts in zip(*wants)]
    results = {"device": jax.devices()[0].device_kind, "shape": shape,
               "key_heads": key_heads, "chunk": chunk, "ways": {}}
    for name, f in ways(group, chunk).items():
        both, forward = through(f), jax.jit(f)
        (_, o), grads = both(w, *arrays)
        errors = {
            n: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for n, a, b in zip(names, (o, *grads), want)
        }
        results["ways"][name] = {
            "errors": errors,
            "fwd_ms": timed(forward, arrays),
            "fwd_bwd_ms": timed(both, (w, *arrays)),
        }
        print(name, json.dumps(results["ways"][name]), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "gdn_scan_probe.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
