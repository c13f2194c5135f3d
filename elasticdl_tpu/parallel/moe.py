"""Mixture-of-Experts with expert parallelism (manual SPMD).

No reference equivalent (SURVEY §2.10: EP absent upstream). GShard/
Switch-style top-1 routing with capacity-bounded dense dispatch — the
formulation that maps onto the MXU (dispatch/combine are einsums, not
scatters) and onto ICI (`lax.all_to_all` over the `ep` mesh axis):

  tokens --(dispatch einsum)--> [E, C, d] --all_to_all--> local experts
  --ffn--> --all_to_all back--> (combine einsum) --> tokens

Called inside `shard_map`; expert weights are sharded over `ep` (their
leading E dim), the router weight is replicated. Tokens beyond an
expert's capacity are dropped (standard Switch behavior) — size
capacity_factor so drops are rare. Returns the Switch load-balancing
auxiliary loss alongside the output.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax



def _route(
    x: jnp.ndarray, router_w: jnp.ndarray, num_experts: int, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-1 capacity-bounded routing — ONE definition shared by the
    expert-parallel and single-host paths.
    -> (dispatch [T,E,C], combine [T,E,C], scalar Switch aux loss)."""
    # routing numerics are f32/int32 REGARDLESS of the activation
    # dtype: a bf16 cumsum over thousands of tokens loses integer
    # exactness above 256, silently corrupting slot assignment (and
    # the f32 softmax keeps the gate/aux statistics well-conditioned)
    logits = (x @ router_w).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jnp.max(probs, axis=-1)  # [T] f32
    expert = jnp.argmax(probs, axis=-1)  # [T]
    onehot_i = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)  # [T, E]

    # Switch aux loss: E * Σ_e (token fraction)·(mean router prob)
    frac = jnp.mean(onehot_i.astype(jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = (num_experts * jnp.sum(frac * mean_prob)).astype(x.dtype)

    # position of each token within its expert's send buffer
    pos = jnp.cumsum(onehot_i, axis=0) * onehot_i - 1  # [T, E], -1 if not routed
    keep = (pos >= 0) & (pos < capacity)  # [T, E]
    slot = jnp.sum(jnp.where(keep, pos, 0), axis=-1).astype(jnp.int32)  # [T]
    slot_onehot = jax.nn.one_hot(slot, capacity, dtype=x.dtype)  # [T, C]
    # keep (routed AND under capacity) gates the whole row: dropped
    # tokens dispatch nowhere and combine to zero
    dispatch = keep.astype(x.dtype)[:, :, None] * slot_onehot[:, None, :]  # [T,E,C]
    combine = dispatch * gate.astype(x.dtype)[:, None, None]  # [T, E, C]
    return dispatch, combine, aux


def moe_ffn_local(
    x: jnp.ndarray,
    router_w: jnp.ndarray,
    w1: jnp.ndarray,
    w2: jnp.ndarray,
    capacity_factor: float = 2.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-host fast path: the same capacity-bounded einsum dispatch
    with every expert local — no collectives, no mesh, jit-plain
    (VERDICT r3 #6: the zoo/PS runtime path must not fall back to the
    per-token reference loop). x: [T, d]; w1: [E, d, f]; w2: [E, f, d].
    -> ([T, d] output, scalar Switch aux loss)."""
    e, d, _f = w1.shape
    capacity = max(1, math.ceil(x.shape[0] * capacity_factor / e))
    dispatch, combine, aux = _route(x, router_w, e, capacity)
    xe = jnp.einsum("tec,td->ecd", dispatch, x)  # [E, C, d]
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, w1))
    ye = jnp.einsum("ecf,efd->ecd", h, w2)
    out = jnp.einsum("tec,ecd->td", combine, ye)
    return out, aux


def moe_ffn(
    x: jnp.ndarray,
    router_w: jnp.ndarray,
    w1_local: jnp.ndarray,
    w2_local: jnp.ndarray,
    axis_name: str,
    capacity_factor: float = 2.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [T, d] local tokens; router_w: [d, E] replicated;
    w1_local: [E/ep, d, f]; w2_local: [E/ep, f, d].
    -> ([T, d] output, scalar load-balance aux loss for the local shard).
    """
    ep = lax.axis_size(axis_name)
    e_local, d, _f = w1_local.shape
    num_experts = e_local * ep
    t = x.shape[0]
    # per-(source-rank, expert) slots; every rank sends ≤ C tokens to
    # each expert, keeping the all_to_all block static-shaped
    capacity = max(1, math.ceil(t * capacity_factor / num_experts))
    dispatch, combine, aux = _route(x, router_w, num_experts, capacity)

    xe = jnp.einsum("tec,td->ecd", dispatch, x)  # [E, C, d]
    xe = xe.reshape(ep, e_local, capacity, d)
    # regroup by expert owner; received dim 0 indexes the source rank
    xe = lax.all_to_all(xe, axis_name, split_axis=0, concat_axis=0)
    xe = xe.transpose(1, 0, 2, 3).reshape(e_local, ep * capacity, d)

    h = jax.nn.gelu(jnp.einsum("egd,edf->egf", xe, w1_local))
    ye = jnp.einsum("egf,efd->egd", h, w2_local)

    ye = ye.reshape(e_local, ep, capacity, d).transpose(1, 0, 2, 3)
    ye = lax.all_to_all(ye, axis_name, split_axis=0, concat_axis=0)
    ye = ye.reshape(num_experts, capacity, d)
    out = jnp.einsum("tec,ecd->td", combine, ye)
    return out, aux


# ---------------------------------------------------------------------------
# Top-k, dropless, gated experts, on the share of the experts held here


def router_logits(x: jnp.ndarray, router_w: jnp.ndarray) -> jnp.ndarray:
    """x W in float32 by a `HIGHEST` product, whatever x is: x [T, d],
    router_w [d, E] -> [T, E] float32."""
    return jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )


def route_topk(x: jnp.ndarray, router_w: jnp.ndarray, top_k: int,
               logits=None):
    """softmax over all of the router's outputs in float32, then the
    `top_k` largest, greedy: x [T, d], router_w [d, E] ->
    (probs [T, E] f32, gate [T, k] f32, chosen [T, k] int32).

    Float32 whatever x is, with a float32 product (`HIGHEST`: the TPU's
    default passes a float32 matmul through bfloat16 once): which
    experts a token takes is decided by the order of its
    probabilities, and two of 64 lie closer than bfloat16 tells apart
    for a token in every few. The gates are the chosen probabilities as
    they are (not renormalised over the k). Equal probabilities go to
    the lower expert first (`lax.top_k` is stable). `logits` [T, E]
    float32, where the caller formed them already (`router_logits`, from
    another tensor than the experts read), take the product's place."""
    if logits is None:
        logits = router_logits(x, router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, chosen = lax.top_k(probs, top_k)
    return probs, gate, chosen.astype(jnp.int32)


def route_sigmoid_topk(
    x: jnp.ndarray, router_w: jnp.ndarray, bias, top_k: int,
    renormalize: bool, logits=None,
):
    """Each expert's own score, s = sigmoid(x W) in float32 (a
    `HIGHEST` product, as `route_topk`'s), then the `top_k` largest of
    s + `bias` ([E], a selection bias no gradient reaches; None = 0).
    The gates are the chosen experts' s, the bias not in them, and with
    `renormalize` divided by their sum over all `top_k` chosen, held
    here or not. -> (s [T, E], gate [T, k], chosen [T, k] int32).
    `logits` as `route_topk`'s."""
    if logits is None:
        logits = router_logits(x, router_w)
    scores = jax.nn.sigmoid(logits)
    biased = scores if bias is None else scores + lax.stop_gradient(
        bias.astype(jnp.float32)
    )
    _, chosen = lax.top_k(biased, top_k)
    gate = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return scores, gate, chosen.astype(jnp.int32)


def sequence_balance_loss(probs: jnp.ndarray, chosen: jnp.ndarray):
    """The sequence-wise balance term, before its weight: per sequence
    sum_e f_e P_e with f_e = E / (k s) x #{tokens of the sequence that
    chose e} and P_e the sequence's mean probability of e, then the
    mean over sequences. probs [B, S, E] f32, chosen [B, S, k]. The
    counts carry no gradient; over all E experts, held here or not."""
    _b, s, e = probs.shape
    k = chosen.shape[-1]
    counts = jnp.sum(
        jax.nn.one_hot(chosen, e, dtype=jnp.float32), axis=(1, 2)
    )  # [B, E]
    f = lax.stop_gradient(counts) * (e / (k * s))
    return jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))


def _take_sorted(sorted_rows: jnp.ndarray, pos: jnp.ndarray):
    """sorted_rows[pos], zeros where pos lies behind the buffer."""
    rows = sorted_rows.shape[0]
    taken = sorted_rows[jnp.minimum(pos, rows - 1)]
    return jnp.where((pos < rows)[:, None], taken, 0)


# The two moves between token order and expert order on the full
# buffer. `order[r]` is the assignment (token x k + choice) that sorted
# row r holds and `pos` is its inverse, so each move is a gather and its
# transpose is a gather the other way: differentiated as they stand,
# both would transpose into scatter-adds of T k rows of d, which the TPU
# walks row by row.


@jax.custom_vjp
def _dispatch(xf, order, pos):
    """Token rows to expert order: xf [T, d] -> [R, d], R = len(order)."""
    return xf[order // (pos.shape[0] // xf.shape[0])]


def _dispatch_fwd(xf, order, pos):
    return _dispatch(xf, order, pos), (order, pos, xf.shape[0])


def _dispatch_bwd(saved, g):
    _order, pos, t = saved
    return _take_sorted(g, pos).reshape(t, -1, g.shape[-1]).sum(axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _collect(sorted_rows, weight, order, pos):
    """Expert order back to tokens, weighted: sum over a token's k
    assignments of weight x its sorted row (zeros for an assignment
    whose row lies behind the buffer): [R, d], [T, k] f32 -> [T, d]."""
    t, k = weight.shape
    picked = _take_sorted(sorted_rows, pos).reshape(t, k, -1)
    return jnp.sum(
        weight[:, :, None] * picked.astype(jnp.float32), axis=1
    ).astype(sorted_rows.dtype)


def _collect_fwd(sorted_rows, weight, order, pos):
    return _collect(sorted_rows, weight, order, pos), (
        sorted_rows, weight, order, pos,
    )


def _collect_bwd(saved, g):
    # neither the T k picked rows nor their T k cotangents are kept or
    # formed (0.2 GB each at 49,152 rows of 2048): the rows' cotangent
    # is a gather of R rows of g, the weights' picks the rows once more
    sorted_rows, weight, order, pos = saved
    t, k = weight.shape
    theirs = g[order // k].astype(jnp.float32)  # [R, d]
    picked = _take_sorted(sorted_rows, pos).reshape(t, k, -1)
    return (
        (weight.reshape(-1)[order][:, None] * theirs).astype(sorted_rows.dtype),
        jnp.sum(
            picked.astype(jnp.float32) * g.astype(jnp.float32)[:, None, :], axis=-1
        ),
        None,
        None,
    )


_collect.defvjp(_collect_fwd, _collect_bwd)


# The same two moves on a rung of R' rows, far fewer than T k: there
# both walk the R' rows that came and not the T k that could. `tok[r]`
# is the token sorted row r belongs to. Rows to expert order is a
# gather of R' rows and its transpose a sum of R' rows into their
# tokens; the weighted sum back into tokens is that sum and its
# transpose that gather. A token's at most min(k, n) terms are added in
# float32 and cast once.


def _sum_by_token(rows, tok, t: int):
    """rows [R', d] float32 summed into their tokens: -> [t, d] f32."""
    return jax.ops.segment_sum(rows, tok, num_segments=t)


@jax.custom_vjp
def _to_experts(xf, tok):
    """Token rows to expert order: xf [T, d] -> [R', d]."""
    return xf[tok]


def _to_experts_fwd(xf, tok):
    return xf[tok], (tok, xf.shape[0])


def _to_experts_bwd(saved, g):
    tok, t = saved
    return _sum_by_token(g.astype(jnp.float32), tok, t).astype(g.dtype), None


_to_experts.defvjp(_to_experts_fwd, _to_experts_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_tokens(sorted_rows, gate, tok, t: int):
    """Expert order back to tokens, weighted: sum over a token's rows
    of gate[r] x sorted_rows[r]: [R', d], [R'] f32 -> [t, d]."""
    weighted = gate[:, None] * sorted_rows.astype(jnp.float32)
    return _sum_by_token(weighted, tok, t).astype(sorted_rows.dtype)


def _to_tokens_fwd(sorted_rows, gate, tok, t):
    return _to_tokens(sorted_rows, gate, tok, t), (sorted_rows, gate, tok)


def _to_tokens_bwd(_t, saved, g):
    sorted_rows, gate, tok = saved
    theirs = g[tok].astype(jnp.float32)  # [R', d]
    return (
        (gate[:, None] * theirs).astype(sorted_rows.dtype),
        jnp.sum(sorted_rows.astype(jnp.float32) * theirs, axis=-1),
        None,
    )


_to_tokens.defvjp(_to_tokens_fwd, _to_tokens_bwd)


# ---------------------------------------------------------------------------
# The ladder: the buffer's length follows the rows that came

# Measured (scripts/route_probe.py; PERF.md, PR 46): off the full buffer
# a layer's cost hardly follows the rung, so three lower rungs are
# enough, and 3/8 catches what 2/8 just misses for less than 4/8 would.
ROW_TILE = 256  # every rung is whole tiles of rows, as the grouped matmul walks them
_RUNG_EIGHTHS = (1, 2, 3)  # the lower rungs, in eighths of the full buffer


def route_rungs(t: int, top_k: int, n: int) -> Tuple[int, ...]:
    """The lengths the sorted buffer can take for `t` tokens, ascending,
    from the shapes alone. The last is the full buffer, t x min(k, n)
    rows, which holds whatever the router does."""
    full = t * min(top_k, n)
    tiles = 8 * ROW_TILE
    lower = {-(-full * e // tiles) * ROW_TILE for e in _RUNG_EIGHTHS}
    return tuple(sorted(r for r in lower if r < full)) + (full,)


def _rung_taken(rungs: Tuple[int, ...], sizes):
    """Index of the smallest rung that holds the sum(sizes) rows."""
    lower = jnp.asarray(rungs[:-1], jnp.int32)
    return jnp.sum(jnp.sum(sizes) > lower).astype(jnp.int32)


def _relu2(h):
    """relu(h) squared. Looked up at the call: a control of the
    benchmark's comparison leaves the square out."""
    return jnp.square(jax.nn.relu(h))


# ---------------------------------------------------------------------------
# The width the held experts' grouped matmuls run at

# Measured (scripts/expert_width_probe.py; PERF.md, PR 61; one v5e chip,
# jax 0.9.0, libtpu 0.0.34): what the compiler's grouped matmul costs
# follows how its tiles divide the experts' inner width f, not f. The
# set of a SwiGLU layer's nine products (forward and backward, 8 groups,
# bfloat16, d = 2048, 11,000 rows on the 12,288 rung), ms and the useful
# TFLOP/s at the stated width:
#
#    f      as it is       zero-padded to (pads and slices counted)
#   1408  10.84   52.7     1536:  7.68  74.4   (1536 as it is: 6.65  93.7)
#   1856  14.41   52.2     1920: 15.45  48.7    2048: 10.48  71.8
#   1280   6.84   75.9     1536:  7.70  67.4
#   1792   9.74   74.6     2048: 10.37  70.1
#    512   2.13   97.4     1024:  4.47  92.9 as they are
#
# A multiple of 512 runs at 93-97, a multiple of 256 at 75, anything
# else (11 x 128, 14.5 x 128, 15 x 128) at 49-53, at 6,144 rows and at
# Nemotron's 490 rows of d = 2688 alike (there 1856 -> 2048 halves the
# set, 6.50 -> 3.18 ms before the pads, 4.36 with them). A pad to the
# next multiple of 256 pays for itself 1.3 to 1.5 times over; one from a
# multiple of 256 to the next of 512 does not pay for its pads.
WIDTH_TILE = 256


def run_width(f: int) -> int:
    """The inner width the grouped matmuls run an expert of width `f`
    at: the smallest multiple of `WIDTH_TILE` that holds it; a width
    inside one tile as it is. From `f` alone."""
    return f if f <= WIDTH_TILE else -(-f // WIDTH_TILE) * WIDTH_TILE


_WIDTHS_TRACED = contextvars.ContextVar("widths_traced", default=None)


@contextlib.contextmanager
def widths_traced():
    """-> a set that gains (stated, run) for every expert layer this
    thread traces inside the block whose width `run_width` moves."""
    seen = set()
    token = _WIDTHS_TRACED.set(seen)
    try:
        yield seen
    finally:
        _WIDTHS_TRACED.reset(token)


def _at_run_width(experts):
    """The leaves zero-padded from their inner width f to
    `run_width(f)`: wg, wu [n, d, f] in their last axis, wd [n, f, d]
    in its middle one. A padded column of wg and wu gives a hidden
    entry silu(0) x 0 = 0 (relu(0) x 0 = 0, relu(0)^2 = 0), which meets
    a zero row of wd: every sum gains exact zeros, and the pads' gradients are
    slices, so the leaves' come back at their own shapes. A width the
    rule leaves alone comes back as it is, with no operation."""
    *ups, wd = experts
    f = wd.shape[1]
    more = run_width(f) - f
    if not more:
        return experts
    seen = _WIDTHS_TRACED.get()
    if seen is not None:
        seen.add((f, f + more))
    return (
        *(jnp.pad(w, ((0, 0), (0, 0), (0, more))) for w in ups),
        jnp.pad(wd, ((0, 0), (0, more), (0, 0))),
    )


# An expert's kind, as a model's `mlp` names it. "swiglu" and "reglu"
# are gated, three leaves (wg, wu, wd): wd(act(wg x) * wu x), the gate's
# activation SiLU or ReLU; "relu2" has two (wu, wd) and no gate: wd
# relu(wu x)^2. A caller that names no kind gets it from the count of
# leaves (three: "swiglu"), as before there were two gated kinds.
_GATE_ACTIVATIONS = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu}
EXPERT_KINDS = (*_GATE_ACTIVATIONS, "relu2")


def _kind_of(leaves, kind: Optional[str]) -> str:
    kind = kind or ("relu2" if len(leaves) == 2 else "swiglu")
    if kind not in EXPERT_KINDS or len(leaves) != 2 + (kind != "relu2"):
        raise ValueError(
            f"an expert of kind {kind!r} with {len(leaves)} leaves: "
            f"{EXPERT_KINDS[:-1]} take (wg, wu, wd), 'relu2' (wu, wd)"
        )
    return kind


def _expert_groups(rows, experts, sizes, kind: Optional[str] = None):
    """The held experts of `kind` on their groups of sorted rows."""
    kind = _kind_of(experts, kind)
    if kind == "relu2":
        wu, wd = experts
        return lax.ragged_dot(_relu2(lax.ragged_dot(rows, wu, sizes)), wd, sizes)
    wg, wu, wd = experts
    hidden = _GATE_ACTIVATIONS[kind](
        lax.ragged_dot(rows, wg, sizes)
    ) * lax.ragged_dot(rows, wu, sizes)
    return lax.ragged_dot(hidden, wd, sizes)


def _shared_expert(xf, shared, kind: Optional[str] = None):
    """The shared expert of `kind` on every token."""
    kind = _kind_of(shared, kind)
    if kind == "relu2":
        wu, wd = shared
        return _relu2(xf @ wu) @ wd
    wg, wu, wd = shared
    return (_GATE_ACTIVATIONS[kind](xf @ wg) * (xf @ wu)) @ wd


def _on_rung(rung: int, kind, xf, weight, experts, order, sizes):
    """The held experts' part of the layer on a buffer of `rung` rows,
    which must hold sum(sizes): xf [T, d], weight [T, k] f32 (0 for an
    assignment not held), order [T k] -> [T, d]."""
    t, k = weight.shape
    with jax.named_scope("route"):
        taken = order[:rung]
        tok = taken // k
        used = jnp.arange(rung) < jnp.sum(sizes)
        # rows behind the groups are zeros going in and coming out:
        # what a grouped matmul leaves there is not specified
        rows = jnp.where(used[:, None], _to_experts(xf, tok), 0)
        gate = jnp.where(used, weight.reshape(-1)[taken], 0.0)
    with jax.named_scope("experts"):
        out = _expert_groups(rows, experts, sizes, kind)
    with jax.named_scope("route"):
        return _to_tokens(jnp.where(used[:, None], out, 0), gate, tok, t)


def _on_full_buffer(kind, xf, weight, experts, order, sizes):
    """The top rung: T x min(k, n) rows, every move a gather of that
    many rows or of T k, whatever came."""
    t, k = weight.shape
    with jax.named_scope("route"):
        pos = jnp.argsort(order).astype(jnp.int32)  # its inverse
        order = order[: t * min(k, sizes.shape[0])]
        used = (jnp.arange(order.shape[0]) < jnp.sum(sizes))[:, None]
        rows = jnp.where(used, _dispatch(xf, order, pos), 0)
    with jax.named_scope("experts"):
        out = _expert_groups(rows, experts, sizes, kind)
    with jax.named_scope("route"):
        return _collect(jnp.where(used, out, 0), weight, order, pos)


def _branches(weight, sizes, kind):
    """(index of the rung to take, one function a rung)."""
    t, k = weight.shape
    rungs = route_rungs(t, k, sizes.shape[0])
    return _rung_taken(rungs, sizes), [
        functools.partial(_on_rung, rung, kind) for rung in rungs[:-1]
    ] + [functools.partial(_on_full_buffer, kind)]


# One custom_vjp round the switch, each rule choosing the rung anew: a
# `lax.switch` differentiated as it stands hands the backward pass the
# union of every branch's residuals, the full buffer's among them, and
# writes zeros for the branches not taken. Here nothing sized by a rung
# leaves a branch: the backward rule's branch recomputes its own
# forward pass (under the layer's `_remat` that is the recomputation
# the layer would have made anyway, and the forward rule's switch, whose
# result nothing reads there, is dropped).


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _held_experts(xf, weight, experts, order, sizes, kind=None):
    """sum_{e held} weight_e E_e(x) on the smallest rung that holds the
    rows that came; the chip runs that branch alone. `kind` is the
    experts' (`_expert_groups`)."""
    taken, branches = _branches(weight, sizes, kind)
    return lax.switch(taken, branches, xf, weight, experts, order, sizes)


def _held_experts_fwd(xf, weight, experts, order, sizes, kind):
    saved = (xf, weight, experts, order, sizes)
    return _held_experts(*saved, kind), saved


def _held_experts_bwd(kind, saved, g):
    xf, weight, experts, order, sizes = saved
    taken, branches = _branches(weight, sizes, kind)

    def backward(branch):
        def run(xf, weight, experts, g):
            _out, pull = jax.vjp(
                jax.checkpoint(lambda *held: branch(*held, order, sizes)),
                xf, weight, experts,
            )
            return pull(g)

        return run

    grads = lax.switch(
        taken, [backward(branch) for branch in branches],
        xf, weight, experts, g,
    )
    return (*grads, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _shared_gate(xf: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """sigmoid(x . w), [T, 1] float32; w [1, d]."""
    return jax.nn.sigmoid(jnp.einsum(
        "td,gd->tg", xf, w, preferred_element_type=jnp.float32
    ))


def moe_topk_held(
    x: jnp.ndarray,
    router_w: jnp.ndarray,
    experts: Tuple[jnp.ndarray, ...],
    shared: Optional[Tuple[jnp.ndarray, ...]] = None,
    *,
    top_k: int,
    held: Tuple[int, int],
    scaling: float = 1.0,
    score: str = "softmax",
    bias=None,
    renormalize: bool = False,
    balance: bool = True,
    shared_gate=None,
    kind: Optional[str] = None,
    logits=None,
):
    """A top-k dropless expert layer that is told which experts it
    holds: y = sum_{e in top_k ∩ held} p_e E_e(x) + S(x) (no S(x)
    and no `shared` scope when `shared` is None). The experts, and the
    shared one, are of `kind` (`EXPERT_KINDS`; None: "relu2" for two
    leaves, "swiglu" for three). With `logits` [T, E] float32 the
    router's product was formed ahead of the layer, from another tensor
    than the x the experts read (`router_logits`; a router that reads
    its layer's input in front of the mixer): `router_w` is then not
    read, and everything behind the product is the layer's own. With `shared_gate`
    [1, d] the shared expert stands behind a gate of its own, one
    number a token: + sigmoid(x . shared_gate) S(x), the product
    accumulated and the sigmoid taken in float32 (scope `shared/gate`,
    stat `shared_gate_mean`).

    `score` "softmax" routes by `route_topk`; "sigmoid" by
    `route_sigmoid_topk` with the selection `bias`; under
    `renormalize` either's gates sum to one over a token's k experts
    (then times `scaling`). `balance` False leaves the balance term out
    (0): a layer balanced by its selection bias has none in its loss.

    x [B, S, d]; router_w [d, E] over ALL E experts; `experts` the
    stacked weights of the n experts `held = (first, n)` names, experts
    first .. first + n - 1 of E: three leaves (wg [n, d, f], wu
    [n, d, f], wd [n, f, d]) are gated experts, two (wu, wd)
    squared-ReLU experts (`_expert_groups`); `shared` one expert of the same kind
    (the shared experts side by side), None for a layer without one.
    -> (y [B, S, d], the sequence-wise balance term (unweighted, f32),
    stats of the
    routing: `expert_tokens` [n] (counts, float32), `held_share`,
    `router_entropy`, `route_rows`, `route_full`, and under
    `shared_gate` `shared_gate_mean`).

    This is one chip's part of an expert-parallel layer, computed
    without the exchange: what the experts held elsewhere would add is
    left out (the guide's section 4), and nothing here stands in for
    them. No capacity and no dropped token. The T k assignments are
    sorted by expert, those to experts not held behind the last held
    group, and the held groups go through `lax.ragged_dot` (on the TPU
    a grouped matmul that walks whole tiles of rows and skips what lies
    behind the groups). A token takes k DIFFERENT experts, so at most
    T x min(k, n) assignments can be held here, and a router that sends
    every token to held experts sends that many; at uniform routing
    n / E of them come. The sorted buffer is as long as the smallest
    rung of `route_rungs` that holds the rows that came (sum(sizes),
    chosen inside the program), and the moves between token order and
    expert order walk that many rows; the top rung is the full
    T x min(k, n), so nothing drops under any skew. `route_rows` of the
    stats is the rung taken, `route_full` 1.0 where it was the top."""
    b, s, d = x.shape
    t = b * s
    first, n = held
    xf = x.reshape(t, d)
    with jax.named_scope("route"):
        # handed on only where given: the benchmark's comparisons swap
        # both routes for their own, which take no logits
        formed = () if logits is None else (logits,)
        if score == "softmax":
            probs, gate, chosen = route_topk(xf, router_w, top_k, *formed)
            if renormalize:  # over all k chosen, held here or not
                gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        else:
            probs, gate, chosen = route_sigmoid_topk(
                xf, router_w, bias, top_k, renormalize, *formed
            )
        local = chosen - first
        here = (local >= 0) & (local < n)
        # a stable sort on (held group, else n) keeps token order
        # inside a group and puts every absent assignment last
        group = jnp.where(here, local, n).reshape(t * top_k)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        sizes = jnp.sum(
            jax.nn.one_hot(group, n + 1, dtype=jnp.int32), axis=0
        )[:n]  # [n] rows of each held expert
        weight = jnp.where(here, gate * scaling, 0.0)  # [T, k] f32
    with jax.named_scope("experts"):
        # in front of the switch, not in its branches: a branch's pad
        # is an operation of its own, a pad out here fuses with the
        # slice that cuts the layer's leaves from their stack, and the
        # backward rule's branch finds the leaves padded already
        experts = _at_run_width(tuple(experts))
    routed = _held_experts(xf, weight, experts, order, sizes, kind)
    with jax.named_scope("route"):
        balance_term = sequence_balance_loss(
            probs.reshape(b, s, -1), chosen.reshape(b, s, top_k)
        ) if balance else jnp.zeros((), jnp.float32)
        if score != "softmax":  # the entropy of the scores' shares
            probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        entropy = -jnp.sum(probs * jnp.log(probs + 1e-30), axis=-1)
        rungs = route_rungs(t, top_k, n)
        taken = _rung_taken(rungs, sizes)
        stats = {
            "expert_tokens": sizes.astype(jnp.float32),
            "held_share": jnp.sum(sizes) / jnp.float32(t * top_k),
            "router_entropy": jnp.mean(entropy),
            "route_rows": jnp.asarray(rungs, jnp.float32)[taken],
            "route_full": (taken == len(rungs) - 1).astype(jnp.float32),
        }
        if bias is not None:
            stats["router_bias_absmax"] = jnp.max(jnp.abs(bias))
    if shared is None:
        y = routed
    else:
        with jax.named_scope("shared"):
            out = _shared_expert(xf, shared, kind)
            if shared_gate is not None:
                with jax.named_scope("gate"):
                    gate = _shared_gate(xf, shared_gate)  # [T, 1] f32
                    out = out * gate.astype(out.dtype)
                    stats["shared_gate_mean"] = jnp.mean(gate)
            y = routed + out
    return y.reshape(b, s, d), balance_term, jax.tree_util.tree_map(
        lax.stop_gradient, stats
    )
