"""The banded attention kernels standalone on the chip: forward and
backward together on bf16 inputs inside one program, ms a call, over
the tile ladder, beside the causal call of the same shapes, and the
kernels against the float32 reference at a length that fits. (PR 48
also ran it with the other way of walking a band, the causal call's
whole row held at the band's nearest tile on both sides, which lost
and went: `ops/flash_attention.py:_Band` has the numbers.)

    chiprun -- python scripts/swa_kernel_sweep.py

Writes chiprun_out/swa_kernel_sweep.json. `--compile_only` lowers and
compiles every case for a described v5e (no chip) and times nothing.
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
import numpy as np  # noqa: E402

from elasticdl_tpu.ops import flash_attention as fa  # noqa: E402

BANDED = (1, 8192, 64, 128)
CAUSAL = (1, 8192, 48, 128)
WINDOW = 512
# (shape, window, tiles)
CASES = [
    (BANDED, WINDOW, (bq, bk))
    for bq, bk in (
        (128, 128), (256, 256), (512, 512), (1024, 1024), (256, 128),
        (512, 128), (512, 256), (1024, 256), (1024, 512), (256, 512),
        (128, 512), (2048, 512),
    )
] + [
    (CAUSAL, None, (e, e)) for e in (512, 1024)
] + [(BANDED, None, (1024, 1024))]


def program(window, tiles):
    def loss(q, k, v, w):
        o = fa.flash_attention(q, k, v, tiles=tiles, window=window)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def forward_program(window, tiles):
    return jax.jit(
        lambda q, k, v: fa.flash_attention(q, k, v, tiles=tiles, window=window)
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
    parser.add_argument("--compile_only", action="store_true")
    args = parser.parse_args()
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        fa.jax.default_backend = lambda: "tpu"  # the public entry's gate
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
        chip = SingleDeviceSharding(topo.devices[0])
        for shape, window, tiles in CASES:
            x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)
            t0 = time.perf_counter()
            program(window, tiles).lower(x, x, x, x).compile()
            print(shape, window, tiles, "compiles",
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
        return
    rng = np.random.default_rng(0)
    results = {"device": jax.devices()[0].device_kind, "cases": []}
    # the kernels against the float32 reference, a head at a time
    for window, tiles_len in ((WINDOW, 2048), (300, 2048), (None, 2048)):
        errors = fa.check_against_reference(
            (1, tiles_len, 4, 128), window=window
        )
        print("check", window, errors, flush=True)
        results.setdefault("checks", []).append(
            {"window": window, "L": tiles_len, "errors": errors}
        )
    arrays = {}
    for shape, window, tiles in CASES:
        if shape not in arrays:
            arrays[shape] = [
                jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                for _ in range(4)
            ]
        q, k, v, w = arrays[shape]
        try:
            both = timed(program(window, tiles), (q, k, v, w))
            fwd = timed(forward_program(window, tiles), (q, k, v))
        except Exception as e:  # a tile pair Mosaic refuses
            print(shape, window, tiles, "FAILED", repr(e)[:300], flush=True)
            continue
        case = {"shape": shape, "window": window, "tiles": tiles,
                "fwd_bwd_ms": both, "fwd_ms": fwd}
        print(json.dumps(case), flush=True)
        results["cases"].append(case)
    out = os.path.join(ROOT, "chiprun_out", "swa_kernel_sweep.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
