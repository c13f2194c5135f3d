"""Standalone chip probe behind `parallel/moe.route_rungs` (PERF.md,
PR 46): what the expert layer's moves between token order and expert
order cost at the three expert cells' shapes, on the full buffer of
T x min(k, n) rows (the path before PR 46, kept here as `today_*`) and
on each rung of the ladder, for every candidate form of the sum of
sorted rows back into their tokens.

    chiprun -- python scripts/route_probe.py [--only moves|layer] [--shape N]

Two parts. `moves`: each move alone, jitted, on a routing drawn at the
cell's share of held rows. `layer`: `moe_topk_held` whole, forward and
backward under `jax.checkpoint` as a layer of the stack runs it, with
the ladder forced to one rung and `moe._sum_by_token` swapped for each
candidate, beside the full buffer alone (`today`) and, with `--parent
<a copy of another commit's parallel/moe.py>`, that commit's layer. One
JSON object on stdout (and in `chiprun_out/route_probe.json`):
milliseconds a call, the median of `--reps` after a warm-up.
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

# (name, tokens, d, expert width, router outputs, held, top_k, score,
#  shared width or 0, rows that come to the held experts in the cell)
SHAPES = [
    ("deepseek-v2-lite", 8192, 2048, 1408, 64, 8, 6, "softmax", 2816, 11000),
    ("lfm2-24b-a2b", 8192, 2048, 1536, 64, 8, 4, "sigmoid", 0, 7258),
    ("kimi-linear-48b-a3b", 4096, 2304, 1024, 256, 8, 8, "sigmoid", 1024, 400),
]


def timed(fn, args, reps):
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return round(statistics.median(times), 4)


def draw_routing(rng, t, k, n, n_all, rows):
    """A routing with about `rows` assignments to held experts: each
    token takes k different experts of n_all, held ones with the odds
    that give the share. -> group [t k] (held index, else n)."""
    share = rows / (t * k)
    odds = np.full(n_all, (1 - share) / (n_all - n))
    odds[:n] = share / n
    keys = rng.random((t, n_all)) ** (1.0 / odds)  # weighted, without replacement
    chosen = np.argsort(-keys, axis=1)[:, :k]
    return np.where(chosen < n, chosen, n).reshape(t * k).astype(np.int32)


def moves(shape, reps):
    """Each move alone, ms."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.parallel import moe

    name, t, d, _f, n_all, n, k, _score, _shared, rows_come = shape
    rng = np.random.default_rng(0)
    group = draw_routing(rng, t, k, n, n_all, rows_come)
    came = int(np.sum(group < n))
    order_np = np.argsort(group, kind="stable").astype(np.int32)
    pos_np = np.argsort(order_np).astype(np.int32)
    full = t * min(k, n)
    rungs = [r for r in moe.route_rungs(t, k, n) if r >= came]
    out = {"rows_came": came, "full": full, "rungs": rungs}

    xf = jnp.asarray(rng.standard_normal((t, d)), jnp.bfloat16)
    g_tok = jnp.asarray(rng.standard_normal((t, d)), jnp.bfloat16)
    weight = jnp.asarray(rng.random((t, k)), jnp.float32)
    group_j = jnp.asarray(group)
    order_full = jnp.asarray(order_np)
    pos = jnp.asarray(pos_np)

    def run(label, fn, *args):
        out[label] = timed(jax.jit(fn), args, reps)

    # what a call costs that does nothing: every number below holds it
    run("call", lambda g: g + 1, group_j[:8])
    # what does not shrink: the sorts and the sizes
    run("sort.order", lambda g: jnp.argsort(g, stable=True).astype(jnp.int32), group_j)
    run("sort.pos", lambda o: jnp.argsort(o).astype(jnp.int32), order_full)
    run("sizes", lambda g: jnp.sum(
        jax.nn.one_hot(g, n + 1, dtype=jnp.int32), axis=0)[:n], group_j)

    # today's four moves, on the full buffer
    order_a = order_full[:full]
    sorted_a = jnp.asarray(rng.standard_normal((full, d)), jnp.bfloat16)
    g_tk = jnp.asarray(rng.standard_normal((t * k, d)), jnp.bfloat16)
    used_a = (jnp.arange(full) < came)[:, None]
    run("today.dispatch", lambda x, o, u: jnp.where(u, x[o // k], 0), xf, order_a, used_a)
    run("today.collect", lambda s, p, w: jnp.sum(
        w[:, :, None] * moe._take_sorted(s, p).reshape(t, k, d).astype(jnp.float32),
        axis=1).astype(jnp.bfloat16), sorted_a, pos, weight)
    run("today.collect_bwd", lambda g, o: g[o], g_tk, order_a)
    run("today.dispatch_bwd", lambda g, p: moe._take_sorted(g, p).reshape(
        t, k, d).sum(axis=1), sorted_a, pos)

    for rung in rungs[:-1]:
        taken = order_full[:rung]
        tok = taken // k
        used = jnp.arange(rung) < came
        gate = jnp.where(used, weight.reshape(-1)[taken], 0.0)
        sorted_r = jnp.where(used[:, None], sorted_a[:rung], 0)
        weighted = gate[:, None] * sorted_r.astype(jnp.float32)
        tag = f"rung{rung}."
        run(tag + "to_experts", lambda x, tk, u: jnp.where(u[:, None], x[tk], 0),
            xf, tok, used)
        run(tag + "to_tokens_bwd", lambda g, tk, gt, s: (
            (gt[:, None] * g[tk].astype(jnp.float32)).astype(jnp.bfloat16),
            jnp.sum(s.astype(jnp.float32) * g[tk].astype(jnp.float32), axis=-1),
        ), g_tok, tok, gate, sorted_r)
        run(tag + "gate.gather", lambda w, tk: w.reshape(-1)[tk], weight, taken)
        run(tag + "gate.scatter", lambda v, tk: jnp.zeros(
            (t * k,), jnp.float32).at[tk].add(v), gate, taken)
        # the sum of R' rows into their tokens: the candidates
        for label, fn in SUMS.items():
            run(tag + "sum." + label,
                lambda w, tk, fn=fn: fn(w, tk, t).astype(jnp.bfloat16), weighted, tok)
        # through `pos` kept at T k, as today but on the short buffer
        run(tag + "sum.pos_gather", lambda s, p, w: jnp.sum(
            w[:, :, None] * moe._take_sorted(s, p).reshape(t, k, d).astype(jnp.float32),
            axis=1).astype(jnp.bfloat16), sorted_r, pos, weight)
        # a slot a token and held choice: a scatter of rows no two of
        # which meet, then a sum over the slots
        m = min(k, n)
        here = np.asarray(group).reshape(t, k) < n
        slot_np = (np.cumsum(here, axis=1) - 1).reshape(-1)[np.asarray(taken)]
        target = jnp.asarray(
            np.asarray(tok) * m + np.clip(slot_np, 0, m - 1), jnp.int32)
        target = jnp.where(used, target, t * m)  # dropped
        run(tag + "sum.slots", lambda w, tg: jnp.zeros((t * m, d), jnp.float32).at[tg].set(
            w, mode="drop", unique_indices=True).reshape(t, m, d).sum(axis=1).astype(
            jnp.bfloat16), weighted, target)
    return out


def _sum_segment(rows, tok, t):
    import jax

    return jax.ops.segment_sum(rows, tok, num_segments=t)


def _sum_sorted(rows, tok, t):
    """Token-major first: a sort of R' keys and a gather of R' rows,
    then a segment sum told its indices are sorted."""
    import jax
    import jax.numpy as jnp

    by_token = jnp.argsort(tok)
    return jax.ops.segment_sum(
        rows[by_token], tok[by_token], num_segments=t, indices_are_sorted=True
    )


def _sum_onehot(rows, tok, t):
    """A product with the [t, R'] matrix of ones: float32 rows as three
    bfloat16 parts, so the products are exact and the sums float32."""
    import jax.numpy as jnp

    ones = (tok[None, :] == jnp.arange(t)[:, None]).astype(jnp.bfloat16)
    total = jnp.zeros((t, rows.shape[1]), jnp.float32)
    left = rows
    for _ in range(3):
        part = left.astype(jnp.bfloat16)
        total = total + jnp.dot(ones, part, preferred_element_type=jnp.float32)
        left = left - part.astype(jnp.float32)
    return total


SUMS = {"segment": _sum_segment, "sorted": _sum_sorted, "onehot": _sum_onehot}


def layer(shape, reps, parent=None):
    """`moe_topk_held` forward and backward under `jax.checkpoint`, ms:
    today's full buffer, and each rung that holds the rows with each
    candidate sum."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.parallel import moe

    name, t, d, f, n_all, n, k, score, shared_f, rows_come = shape
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, t // 4, d)), jnp.bfloat16)
    experts = tuple(
        jnp.asarray(rng.standard_normal(s) * 0.02, jnp.bfloat16)
        for s in ((n, d, f), (n, d, f), (n, f, d))
    )
    shared = tuple(
        jnp.asarray(rng.standard_normal(s) * 0.02, jnp.bfloat16)
        for s in ((d, shared_f), (d, shared_f), (shared_f, d))
    ) if shared_f else None
    router = rng.standard_normal((d, n_all)).astype(np.float32) * 0.02
    bias = jnp.zeros((n_all,), jnp.float32) if score == "sigmoid" else None
    tilt = jnp.asarray(rng.standard_normal((4, t // 4, d)), jnp.float32)

    def build(module=moe):
        def body(x, router, experts, shared):
            y, term, stats = module.moe_topk_held(
                x, router, experts, shared, top_k=k, held=(0, n), score=score,
                bias=bias, renormalize=score == "sigmoid",
            )
            # a loss that is linear in y: a layer of the stack hands y on
            # and nothing of its backward pass reads it again
            return jnp.sum(y.astype(jnp.float32) * tilt) + term, stats

        def step(x, router, experts, shared):
            (_loss, stats), grads = jax.value_and_grad(
                jax.checkpoint(body), argnums=(0, 1, 2, 3) if shared else (0, 1, 2),
                has_aux=True,
            )(x, router, experts, shared)
            return grads, stats["held_share"], stats.get("route_rows")

        return jax.jit(step)

    # lean the router towards the held experts until the cell's rows
    # come: every token's first feature is one, and the held experts'
    # weight on it is found by bisection
    x = x.at[:, :, 0].set(1.0)
    counted = build()
    low, high, came = -16.0, 16.0, 0.0
    for _ in range(30):
        leaned = router.copy()
        leaned[0, :n] = (low + high) / 2
        args = (x, jnp.asarray(leaned), experts, shared)
        came = float(counted(*args)[1]) * t * k
        if abs(came - rows_come) < 0.03 * rows_come:
            break
        low, high = ((low + high) / 2, high) if came < rows_come else (low, (low + high) / 2)
    out = {"rows_came": came}
    if parent is not None:  # the layer of another commit's moe.py
        out["parent"] = timed(build(parent), args, reps)
    ladder = moe.route_rungs(t, k, n)
    kept = (moe.route_rungs, moe._sum_by_token)
    try:
        moe.route_rungs = lambda *_a: ladder[-1:]
        out["today"] = timed(build(), args, reps)
        for rung in ladder[:-1]:
            if rung < came:
                continue
            moe.route_rungs = lambda *_a, rung=rung: (rung, ladder[-1])
            for label, fn in SUMS.items():
                if label == "onehot" and t * rung > 1 << 25:
                    continue  # a [t, R'] matrix of ones too large to be a candidate
                moe._sum_by_token = fn
                step = build()
                out[f"rung{rung}.{label}"] = timed(step, args, reps)
                assert float(step(*args)[2]) == rung
        moe.route_rungs, moe._sum_by_token = kept
        out["ladder"] = timed(build(), args, reps)
    finally:
        moe.route_rungs, moe._sum_by_token = kept
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--only", choices=("moves", "layer"))
    parser.add_argument("--shape", type=int, help="index into SHAPES")
    parser.add_argument("--reps", type=int, default=10)
    parser.add_argument("--parent", help="another commit's parallel/moe.py, "
                        "whose layer is timed beside this one's")
    parser.add_argument("--small", action="store_true",
                        help="a CPU rehearsal: tiny shapes, no timing worth reading")
    args = parser.parse_args()

    import jax

    shapes = SHAPES if args.shape is None else [SHAPES[args.shape]]
    if args.small:
        shapes = [
            (name, 512, 64, 48, n_all, n, k, score, 32 if shared else 0, rows // 16)
            for name, _t, _d, _f, n_all, n, k, score, shared, rows in shapes
        ]
    parent = None
    if args.parent:
        import importlib.util

        spec = importlib.util.spec_from_file_location("moe_parent", args.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    device = jax.devices()[0]
    result = {"device": {"platform": device.platform, "kind": device.device_kind}}
    for shape in shapes:
        entry = result.setdefault(shape[0], {})
        if args.only != "layer":
            entry["moves_ms"] = moves(shape, args.reps)
        if args.only != "moves":
            entry["layer_ms"] = layer(shape, args.reps, parent)
        print(json.dumps({shape[0]: entry}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "route_probe.json"), "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
