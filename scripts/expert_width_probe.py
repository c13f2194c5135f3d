"""Standalone chip probe behind `parallel/moe.run_width` (PERF.md,
PR 61): what `lax.ragged_dot` costs by the held experts' inner width,
alone, jitted, bfloat16, 8 groups, at the rows and rungs the expert
cells really see.

    chiprun -- python scripts/expert_width_probe.py [--case N] [--reps R]

For every case (hidden width, rows that came, rung) and every width
pair (stated f, run f'):

- the single products at f': `up` rows x [d, f'], `down` hidden x
  [f', d], and `up_bwd`, `down_bwd`, the two backward products of each
  as `jax.vjp` makes them (no forward product in them);
- `set_fwd` and `set`: `moe._expert_groups` on `moe._at_run_width` of
  the leaves, forward, and forward and backward (`jax.vjp` and its
  pull: 3 + 6 grouped matmuls for SwiGLUs, 2 + 4 for squared-ReLU
  experts, the kind the case names). Where f' > f twice: `padded` = the
  leaves go in at f, `_at_run_width` pads them and the gradients come
  back at f (the pads and the slices in the timing: what a layer pays),
  and `apart` = the leaves go in at f' already;
- `pad` alone: the leaves' zero-padding and nothing else.

One JSON object on stdout (and in `chiprun_out/expert_width_probe.json`):
milliseconds a call, the median of `--reps` bursts of `--burst` calls
asked for back to back and waited for once, and for the sets the useful
TFLOP/s at the STATED width.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

GROUPS = 8
# (name, d, rows that came, rung, kind): the routed cell on its two
# usual rungs (LFM2 is the first's twin at 7,258 rows on 8,192), and
# Nemotron's 61 rows an expert
CASES = [
    ("d2048.rows11000.rung12288", 2048, 11000, 12288, "swiglu"),
    ("d2048.rows6144.rung6144", 2048, 6144, 6144, "swiglu"),
    ("d2688.rows490.rung3072", 2688, 490, 3072, "relu2"),
]
# (stated, run)
WIDTHS = [
    (1408, 1408), (1408, 1536), (1536, 1536),
    (1856, 1856), (1856, 1920), (1856, 2048),
    (512, 512), (1024, 1024),
    # what tells a tile of 256 from one of 512: 5 x 256 and 7 x 256
    (1280, 1280), (1280, 1536), (1792, 1792), (1792, 2048),
]


def timed(fn, args, reps, burst):
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(burst):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e3 / burst)
    return round(statistics.median(times), 4)


def probe(case, widths, reps, burst):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from elasticdl_tpu.parallel import moe

    _name, d, came, rung, kind = case
    rng = np.random.default_rng(0)
    # a skew like the cells' (`expert_load_max_over_mean` 1.2 to 1.5)
    sizes_np = rng.multinomial(came, rng.dirichlet(np.full(GROUPS, 20.0)))
    sizes = jnp.asarray(sizes_np, jnp.int32)
    used = (np.arange(rung) < came)[:, None]

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.bfloat16)

    rows = jnp.where(used, normal(rung, d), 0)
    g_out = jnp.where(used, normal(rung, d), 0)
    ups = 2 if kind == "swiglu" else 1
    out = {"sizes": sizes_np.tolist()}

    def leaves(f):
        return (
            *(normal(GROUPS, d, f, scale=0.02) for _ in range(ups)),
            normal(GROUPS, f, d, scale=0.02),
        )

    def the_set():
        """(forward, forward and backward) under the `moe.run_width` of
        the moment; functions of their own each time: jax keeps a
        function's traces by its arguments' shapes."""
        def forward(rows, experts):
            return moe._expert_groups(rows, moe._at_run_width(experts), sizes)

        def both(rows, experts, g):
            y, pull = jax.vjp(forward, rows, experts)
            return y, pull(g)

        return forward, both

    def pull_of(a, w, g):
        return jax.vjp(lambda a, w: lax.ragged_dot(a, w, sizes), a, w)[1](g)

    def run(label, fn, *args):
        # jitted as a function of its own: `moe._at_run_width` comes back
        # with the same shapes under another `moe.run_width`
        entry[label] = timed(jax.jit(lambda *a: fn(*a)), args, reps, burst)

    kept = moe.run_width
    try:
        for f, f_run in widths:
            entry = out.setdefault(f"{f}" if f == f_run else f"{f}->{f_run}", {})
            stated, wide = leaves(f), leaves(f_run)
            hidden = jnp.where(used, normal(rung, f_run), 0)
            moe.run_width = lambda f: f  # the leaves' own width, whatever it is
            run("up", lambda a, w: lax.ragged_dot(a, w, sizes), rows, wide[0])
            run("down", lambda a, w: lax.ragged_dot(a, w, sizes), hidden, wide[-1])
            run("up_bwd", pull_of, rows, wide[0], hidden)
            run("down_bwd", pull_of, hidden, wide[-1], g_out)
            tag = "" if f == f_run else ".apart"
            forward, both = the_set()
            run("set_fwd" + tag, forward, rows, wide)
            run("set" + tag, both, rows, wide, g_out)
            if f != f_run:
                moe.run_width = lambda _f, f_run=f_run: f_run
                forward, both = the_set()
                run("pad", moe._at_run_width, stated)
                run("set_fwd.padded", forward, rows, stated)
                run("set.padded", both, rows, stated, g_out)
                program = jax.make_jaxpr(both)(rows, stated, g_out)
                assert f",{f_run}]" in str(program), "the set did not run padded"
                assert [g.shape for g in program.out_avals[-len(stated):]] == [
                    w.shape for w in stated
                ]
            # forward and backward are three products a leaf, 2 FLOPs each
            useful = 3 * (ups + 1) * 2 * came * d * f
            for label in ("set", "set.apart", "set.padded"):
                if label in entry:
                    entry[label + ".tflops"] = round(useful / entry[label] / 1e9, 2)
    finally:
        moe.run_width = kept
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--case", type=int, help="index into CASES")
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--burst", type=int, default=10)
    parser.add_argument("--small", action="store_true",
                        help="a CPU rehearsal: tiny shapes, no timing worth reading")
    args = parser.parse_args()

    import jax
    import jaxlib

    cases = CASES if args.case is None else [CASES[args.case]]
    widths = WIDTHS
    if args.small:
        cases = [(n, d // 32, came // 32, rung // 32, kind)
                 for n, d, came, rung, kind in cases]
        widths = [(f // 32, f_run // 32) for f, f_run in WIDTHS]
    device = jax.devices()[0]
    result = {
        "device": {"platform": device.platform, "kind": device.device_kind},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__},
    }
    try:
        from importlib.metadata import version

        result["versions"]["libtpu"] = version("libtpu")
    except Exception:
        pass
    for case in cases:
        result[case[0]] = probe(case, widths, args.reps, args.burst)
        print(json.dumps({case[0]: result[case[0]]}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "expert_width_probe.json"), "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
