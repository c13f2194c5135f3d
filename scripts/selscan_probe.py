"""The selective scan standalone on the chip, at the one shape a cell
runs, (1, 4096, 5120) x 16 (`phi-4-mini-flash-reasoning`): every form
of `ops/selective_scan.py` against the recurrence a token at a time
(outputs and all five gradients, bfloat16 x, B and C as the timed
program hands them), then ms a call, forward and forward + backward:

- `tokens`: `lax.scan` over the tokens (forward only: its backward
  pass keeps a state a token, 1.3 GB);
- `associative`: ONE `lax.associative_scan` over [T, N, D] pairs, the
  naive form (forward only, for the same reason);
- `chunked`: plain jax, chunks of 64 | 128 | 256 in a `lax.scan`;
- `kernels`: the Pallas kernels at channel tiles of 128 | 256 | 512 |
  1024 (`--only kernels` times these alone).

    chiprun -- python scripts/selscan_probe.py

Writes chiprun_out/selscan_probe.json. `--small` is the CPU rehearsal
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

from elasticdl_tpu.ops import selective_scan as ss  # noqa: E402


def associative(x, dt, A, Bm, Cm, h0=None):
    f32 = jnp.float32
    x, Bm, Cm = (a.astype(f32) for a in (x, Bm, Cm))
    decay = jnp.exp(dt[:, :, None, :] * A)
    write = (dt * x)[:, :, None, :] * Bm[..., None]
    _, states = lax.associative_scan(ss._combine, (decay, write), axis=1)
    return jnp.sum(states * Cm[..., None], axis=2), states[:, -1]


def inputs(b, t, d, n, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.nn.silu(jax.random.normal(keys[0], (b, t, d))).astype(jnp.bfloat16)
    dt = jnp.exp(jax.random.uniform(
        keys[1], (b, t, d), minval=jnp.log(1e-3), maxval=jnp.log(0.1)
    ))
    A = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (n, d))
    Bm, Cm = (
        jax.random.normal(k, (b, t, n)).astype(jnp.bfloat16) for k in keys[2:4]
    )
    w = jax.random.normal(keys[4], (b, t, d))
    return x, dt, A, Bm, Cm, w


def timed(fn, args, repeats=5):
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--small", action="store_true")
    parser.add_argument("--only", default="", help="forms whose name starts so")
    args = parser.parse_args()
    shape = (1, 256, 256, 16) if args.small else (1, 4096, 5120, 16)
    x, dt, A, Bm, Cm, w = inputs(*shape)
    kernels = (
        {"kernels_256": lambda *a: ss.selective_scan_kernels(*a, interpret=True)}
        if args.small else
        {f"kernels_{width}": ss.selective_scan_kernels
         for width in (128, 256, 512, 1024)}
    )
    forms = {
        **{f"chunked_{c}": (lambda *a, c=c: ss.selective_scan_chunked(*a, chunk=c))
           for c in (64, 128, 256)},
        **kernels,
    }
    forward_only = {"tokens": ss.selective_scan_tokens}
    last = {"associative": associative}  # 1.3 GB a level: may not fit

    def loss(scan):
        return lambda x, dt, A, Bm, Cm: jnp.sum(scan(x, dt, A, Bm, Cm)[0] * w)

    results = {"device": jax.devices()[0].device_kind, "shape": shape, "forms": {}}
    want = jax.jit(ss.selective_scan_tokens)(x, dt, A, Bm, Cm)[0]
    scale = float(jnp.max(jnp.abs(want)))
    # the gradients' yardstick: the chunked form at 64 (held to the
    # recurrence by the tests on the CPU; the recurrence's own backward
    # pass does not fit at this shape)
    grads = {}
    for name, scan in {**forward_only, **forms, **last}.items():
        if not (name.startswith(args.only) or name == "chunked_64"):
            continue  # chunked_64 is the gradients' yardstick
        row = {}
        if name.startswith("kernels_"):  # read where the calls are traced
            ss.LANE_TILE = int(name.split("_")[1])
        try:
            forward = jax.jit(lambda *a, scan=scan: scan(*a)[0])
            got = forward(x, dt, A, Bm, Cm)
            row["y_rel"] = float(jnp.max(jnp.abs(got - want))) / scale
            row["fwd_ms"] = timed(forward, (x, dt, A, Bm, Cm))
            if name in forms:
                both = jax.jit(jax.grad(loss(scan), argnums=(0, 1, 2, 3, 4)))
                grads[name] = both(x, dt, A, Bm, Cm)
                row["fwd_bwd_ms"] = timed(both, (x, dt, A, Bm, Cm))
                if name != "chunked_64":
                    row["grad_rel"] = [
                        float(jnp.max(jnp.abs(
                            g.astype(jnp.float32) - r.astype(jnp.float32)
                        )) / jnp.max(jnp.abs(r.astype(jnp.float32))))
                        for g, r in zip(grads[name], grads["chunked_64"])
                    ]
                if name != "chunked_64":
                    del grads[name]
        except Exception as e:  # a form the chip's memory or Mosaic refuses
            row["failed"] = repr(e)[:300]
        print(json.dumps({name: row}), flush=True)
        results["forms"][name] = row
    out = os.path.join(ROOT, "chiprun_out", "selscan_probe.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
