"""The attention kernels standalone on the chip: forward and backward
together on bf16 inputs inside one program, ms a call, and the kernels
against the float32 reference at a length that fits.

`--cases band` (PR 48): the banded call over the tile ladder, beside
the causal call of the same shapes. (PR 48 also ran it with the other
way of walking a band, the causal call's whole row held at the band's
nearest tile on both sides, which lost and went:
`ops/flash_attention.py:_Band` has the numbers.)

`--cases latent` (PR 49): 192-wide queries and keys over 128-wide
values under the routed cell's softmax scale, at the ladder's tiles:
XLA's materialised path | the kernels with q and k folded to
[B*H, L, 192] through memory (what `_Layout` does with a width the
lanes do not divide) | q and k padded with zeros to 256 and read in
place (`padded`, stated here: the other honest layout; zeros add
nothing to a score, dq and dk are cut back by the pad's own gradient).

`--cases wide` (PR 52): the causal call at heads of 256, (1, 8192, 16,
256), over the tile ladder, beside the same pairs at 32 heads of 128.

`--cases diff` (PR 58): differential attention's call, 40 heads of
64-wide queries and keys over 128-wide values at 4096 tokens, under the
window of 512 and in full: XLA's materialised path | the kernels at the
ladder's tiles.

`--cases band4k` (PR 62): 28 heads of 128 at 16,384 tokens under the
window of 4096 and in full (SmallThinker's sliding and full layers, the
key-value heads widened already), over the tile ladder.

`--cases gqa` (PR 63): fewer key-value heads than query heads, at the
six grouped cells' calls: k and v widened to the query heads in front of
the kernels with the group's sum behind them (`widened`, what the
dispatcher did until PR 63) | read where they lie through the index
maps, dk and dv summed over the group in the kernel (`in_place`).

    chiprun -- python scripts/swa_kernel_sweep.py --cases latent

Writes chiprun_out/swa_kernel_sweep.<cases>.json. `--compile_only`
lowers and compiles every case for a described v5e (no chip) and
times nothing.
"""

import argparse
import json
import math
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
# the routed cell's calls, and the hybrid cell's
LATENT = ((4, 2048, 16, 192), (2, 2048, 32, 192))
V_WIDTH = 128
# deepseek-v2-lite's `mla_softmax_scale`: 192^-0.5 x mscale(40, 0.707)^2
LATENT_SCALE = 192**-0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
WIDE = (1, 8192, 16, 256)
DIFF = (1, 4096, 40, 64)  # both members of 20 pairs of heads
BAND4K = (1, 16384, 28, 128)
WINDOW4K = 4096
# (q's shape, window, v_width, key-value heads): Laguna's sliding and
# full layers, SmallThinker's, Qwen3-Next's gated attention, LFM2's,
# Nemotron's, and phi-4's differential call (keys of 64 folded, the
# pairs' values of 128 in place) under its window and in full
GQA = (
    ((1, 8192, 64, 128), WINDOW, None, 8),
    ((1, 8192, 48, 128), None, None, 8),
    (BAND4K, WINDOW4K, None, 4),
    (BAND4K, None, None, 4),
    (WIDE, None, None, 2),
    ((4, 2048, 32, 64), None, None, 8),
    ((1, 4096, 32, 128), None, None, 2),
    (DIFF, WINDOW, V_WIDTH, 20),
    (DIFF, None, V_WIDTH, 20),
)
# (shape, window, tiles, v_width, scale, path[, key-value heads])
CASES = {
    "gqa": [
        (shape, window, None, v_width, None, path, kv_heads)
        for shape, window, v_width, kv_heads in GQA
        for path in ("widened", "in_place")
    ],
    "wide": [
        (WIDE, None, tiles, None, None, "kernels")
        for tiles in (
            (1024, 1024), (512, 512), (256, 256), (512, 1024), (1024, 512),
            (2048, 1024), (1024, 2048),
        )
    ] + [((1, 8192, 32, 128), None, (1024, 1024), None, None, "kernels")],
    "band": [
        (BANDED, WINDOW, (bq, bk), None, None, "kernels")
        for bq, bk in (
            (128, 128), (256, 256), (512, 512), (1024, 1024), (256, 128),
            (512, 128), (512, 256), (1024, 256), (1024, 512), (256, 512),
            (128, 512), (2048, 512),
        )
    ] + [
        (CAUSAL, None, (e, e), None, None, "kernels") for e in (512, 1024)
    ] + [(BANDED, None, (1024, 1024), None, None, "kernels")],
    "band4k": [
        (BAND4K, WINDOW4K, (bq, bk), None, None, "kernels")
        for bq, bk in (
            (1024, 1024), (512, 512), (2048, 1024), (1024, 512),
            (512, 1024), (2048, 2048), (256, 256),
        )
    ] + [
        (BAND4K, None, (e, e), None, None, "kernels") for e in (1024, 512)
    ],
    "diff": [
        (DIFF, window, tiles, V_WIDTH, None, path)
        for window in (WINDOW, None)
        for tiles, path in (
            (None, "xla"), ((1024, 1024), "kernels"), ((512, 512), "kernels"),
        )
    ],
    "latent": [
        (shape, None, tiles, V_WIDTH, LATENT_SCALE, path)
        for shape in LATENT
        for tiles, path in (
            (None, "xla"), ((1024, 1024), "folded"), ((1024, 1024), "padded"),
            ((512, 512), "folded"), ((512, 512), "padded"),
        )
    ] + [  # the equal-width call beside them: FLASH_MIN_LENGTH's row
        ((2, 2048, 16, 128), None, t, None, None, p)
        for t, p in ((None, "xla"), ((1024, 1024), "kernels"))
    ],
}


def widened(q, k, v):
    """k and v repeated to q's heads, as the dispatcher did in front of
    the kernels until PR 63: the transpose sums dk and dv over a group."""
    group = q.shape[2] // k.shape[2]
    return (jnp.repeat(x, group, axis=2) for x in (k, v))


def attend(window, tiles, scale, path):
    """q, k, v -> o by one of the paths a case names."""
    if path == "xla":
        return lambda q, k, v: fa.reference_attention(
            q, k, v, True, scale, window
        )

    def kernels(q, k, v):
        if path == "padded":
            pad = ((0, 0),) * 3 + ((0, -q.shape[-1] % 128),)
            q, k = jnp.pad(q, pad), jnp.pad(k, pad)
        if path == "widened":
            k, v = widened(q, k, v)
        return fa.flash_attention(
            q, k, v, tiles=tiles, window=window, scale=scale
        )

    return kernels


def program(*case):
    def loss(q, k, v, w):
        o = attend(*case)(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def forward_program(*case):
    return jax.jit(attend(*case))


def operands(shape, v_width=None, kv_heads=None):
    """The shapes of q, k, v and the cotangent of a case."""
    b, L, h, d = shape
    kv = (b, L, kv_heads or h)
    return shape, (*kv, d), (*kv, v_width or d), (b, L, h, v_width or d)


def widened_errors(shape, **how):
    """`check_against_reference` with k and v widened in front of the
    kernels: what the in-place call's dk and dv are held beside."""
    inner = fa.flash_attention

    fa.flash_attention = lambda q, k, v, **kw: inner(
        q, *widened(q, k, v), **kw
    )
    try:
        return fa.check_against_reference(shape, **how)
    finally:
        fa.flash_attention = inner


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
    parser.add_argument("--cases", choices=sorted(CASES), default="band")
    args = parser.parse_args()
    cases = CASES[args.cases]
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        fa.jax.default_backend = lambda: "tpu"  # the public entry's gate
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
        chip = SingleDeviceSharding(topo.devices[0])
        for shape, window, tiles, v_width, scale, path, *kv_heads in cases:
            structs = [
                jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=chip)
                for s in operands(shape, v_width, *kv_heads)
            ]
            t0 = time.perf_counter()
            compiled = (
                program(window, tiles, scale, path).lower(*structs).compile()
            )
            print(shape, v_width, window, tiles, path, *kv_heads, "compiles",
                  f"{time.perf_counter() - t0:.1f}s", "temp bytes",
                  compiled.memory_analysis().temp_size_in_bytes, flush=True)
        return
    rng = np.random.default_rng(0)
    results = {"device": jax.devices()[0].device_kind, "cases": []}
    # the kernels against the float32 reference, a head at a time
    checks = {
        "band": [
            ((1, 2048, 4, 128), {"window": w}) for w in (WINDOW, 300, None)
        ],
        "latent": [
            (shape, {"v_width": V_WIDTH, "scale": LATENT_SCALE})
            for shape in LATENT
        ],
        "wide": [((1, 2048, 16, 256), {}), (WIDE, {})],
        "band4k": [
            ((1, 8192, 7, 128), {"window": w}) for w in (WINDOW4K, None)
        ],
        "diff": [
            ((1, 2048, 8, 64), {"v_width": V_WIDTH, "window": w})
            for w in (WINDOW, None)
        ],
        # groups of 8 under the band, 7 and 6 in full, 8 at heads of
        # 256, 4 folded, and phi-4's 2 at 64 | 128
        "gqa": [
            ((1, 2048, 16, 128), {"kv_heads": 2, "window": WINDOW}),
            ((1, 2048, 14, 128), {"kv_heads": 2}),
            ((1, 4096, 12, 128), {"kv_heads": 2}),
            ((1, 2048, 8, 256), {"kv_heads": 1}),
            ((2, 2048, 8, 64), {"kv_heads": 2}),
            ((1, 2048, 8, 64),
             {"kv_heads": 4, "v_width": V_WIDTH, "window": WINDOW}),
        ],
    }[args.cases]
    for shape, how in checks:
        errors = fa.check_against_reference(shape, **how)
        print("check", shape, how, errors, flush=True)
        check = {"shape": shape, **how, "errors": errors}
        if "kv_heads" in how:
            check["widened_errors"] = widened_errors(shape, **how)
            print("  widened in front", check["widened_errors"], flush=True)
        results.setdefault("checks", []).append(check)
    arrays = {}
    for shape, window, tiles, v_width, scale, path, *kv_heads in cases:
        shapes = operands(shape, v_width, *kv_heads)
        if shapes not in arrays:
            arrays[shapes] = [
                jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
                for s in shapes
            ]
        q, k, v, w = arrays[shapes]
        case = (window, tiles, scale, path)
        try:
            both = timed(program(*case), (q, k, v, w))
            fwd = timed(forward_program(*case), (q, k, v))
        except Exception as e:  # a tile pair Mosaic refuses
            print(shape, *case, "FAILED", repr(e)[:300], flush=True)
            continue
        case = {"shape": shape, "v_width": v_width, "window": window,
                "tiles": tiles, "path": path, "fwd_bwd_ms": both,
                "fwd_ms": fwd}
        if kv_heads:
            case["kv_heads"] = kv_heads[0]
        print(json.dumps(case), flush=True)
        results["cases"].append(case)
    out = os.path.join(
        ROOT, "chiprun_out", f"swa_kernel_sweep.{args.cases}.json"
    )
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
