"""Flagship decoder-only transformer LM — the full-parallelism model.

No reference equivalent (the 2019 reference has no attention model at
all, SURVEY §5.7); this is the TPU-native capability demanded of the
rebuild: one model exercising every mesh axis simultaneously over a
``("pp", "dp", "sp", "tp")`` device mesh:

- **pp**: transformer blocks pipelined with `parallel.pipeline.gpipe`
  (stacked layer params sharded on the leading dim);
- **dp**: batch sharding; also the **ep** axis — MoE expert weights are
  sharded over dp and tokens all_to_all within it
  (`parallel.moe.moe_ffn`), DeepSeek-style EP≡DP groups;
- **sp**: sequence sharding with exact causal ring attention
  (`parallel.ring_attention`) and RoPE applied at global positions;
- **tp**: Megatron-style column/row-parallel QKV/O and MLP matmuls
  (`parallel.tp_layers`), one psum per sublayer.

Everything lives in ONE `shard_map` over the whole mesh; the global
loss is formed inside (pmean over dp×sp), so JAX's vma-typed
transposition inserts the correct gradient psums for replicated params
automatically — no hand-written per-leaf gradient sync rules.

Params are a plain pytree (no flax): stacked [n_layers, ...] leaves so
pipeline stages shard the leading dim and each stage `lax.scan`s its
local layers.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticdl_tpu.parallel.moe import EXPERT_KINDS, moe_ffn
from elasticdl_tpu.parallel.pipeline import gpipe
from elasticdl_tpu.parallel.ring_attention import ring_attention
from elasticdl_tpu.parallel.tp_layers import rms_norm, swiglu

MESH_AXES = ("pp", "dp", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 1024
    d_model: int = 128
    n_heads: int = 8
    d_ff: int = 512
    n_layers: int = 4
    n_experts: int = 0  # 0 = dense FFN; >0 = every FFN is MoE (ep over dp)
    d_expert: int = 256  # per-expert hidden dim when MoE
    capacity_factor: float = 2.0
    aux_weight: float = 0.01  # Switch load-balance loss weight
    n_micro: int = 2  # pipeline microbatches
    dtype: Any = jnp.float32  # compute dtype (bfloat16 on real TPUs)
    # rematerialize each layer in the backward pass (jax.checkpoint):
    # activation memory drops from O(L_layers * B * L * d_ff) to the
    # per-layer carry, buying ~3x larger batch/depth per chip for ~1/3
    # extra forward FLOPs — the standard HBM<->FLOPs trade
    remat: bool = False
    # -- the unsharded block's further settings (plain_forward only; the
    # mesh path refuses them, `_require_mesh_support`) ----------------
    rope_base: float = 10000.0
    norm_eps: float = 1e-6
    # "gelu": w2(gelu(w1 x)); "swiglu": wd(silu(wg x) * wu x), no bias;
    # "relu2": wd relu(wu x)^2, two matrices and no gate; "reglu":
    # wd(relu(wg x) * wu x), the gated MLP under a ReLU (both the routed
    # stack's experts and shared expert only)
    mlp: str = "gelu"
    # four norms a layer: h + ln1b(attn(ln1(h))), h + ln2b(mlp(ln2(h)))
    sandwich_norm: bool = False
    # > 1: a looped LM (Ouro): the whole stack runs n_loops times over
    # ONE set of layer weights, the final norm closes every pass, and
    # every pass is an exit with its own logits and a learned gate; the
    # outputs are `LoopedOutputs` and the loss `looped_exit_loss`
    n_loops: int = 1
    exit_entropy_weight: float = 0.1  # beta of the exit objective
    # "mla": latent attention (DeepSeek-V2). Keys and values come from
    # one normed latent of `kv_lora_rank`; a head's query and key are
    # `qk_nope_dim` of their own plus `qk_rope_dim` that turn (the
    # turning key is ONE vector all heads share), its value `v_head_dim`
    attention: str = "mha"
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_yarn: Optional["YarnScaling"] = None  # the turning part's angles
    # this many leading layers keep the dense MLP (`mlp`, `d_ff`) in a
    # stack whose other layers are expert layers (`params["dense"]`)
    n_dense_layers: int = 0
    # > 0: the expert layers route every token to its `moe_top_k`
    # likeliest of `n_experts` and drop none; experts are SwiGLUs of
    # `d_expert`, `n_shared_experts` more of that width see every
    # token, and of the routed ones this program holds `held_experts`
    # = (first, count), all of them when None
    # (`parallel.moe.moe_topk_held`); `aux_weight` is then the weight of
    # the sequence-wise balance term. 0: the Switch top-1 layer
    moe_top_k: int = 0
    n_shared_experts: int = 0
    held_experts: Optional[Tuple[int, int]] = None
    routed_scaling: float = 1.0
    # how the top-k layer scores and gates: "softmax" over all experts
    # with the chosen probabilities as gates, or "sigmoid" (each
    # expert's own score; the k largest of score + `router_bias`, a
    # leaf no gradient reaches, are chosen and the bias is not in the
    # gate). `moe_renormalize` divides the chosen scores by their sum.
    # `aux_weight` 0 leaves the balance term out of the loss
    moe_score: str = "softmax"
    moe_renormalize: bool = False
    # False: latent attention turns nothing (`mla_use_nope`): the
    # shared key columns and the queries' last `qk_rope_dim` stay as
    # projected
    mla_rope: bool = True
    # the mixer of every layer of the stack, dense layers first:
    # "mha", "swa", "mla", "kda", "gdn", "conv", "mamba2", "mamba1", "gmu"
    # or "cross"; None = `attention` in every layer.
    # Layers that follow each other with one mixer and one kind of
    # feed-forward part are one scanned run (`memory_layer` and
    # `kv_layer` are each a run of their own: what they hand on is one
    # layer's). A layer named in
    # `bare_layers` (its index in the stack) is its mixer ALONE,
    # h + mixer(ln1(h)): no feed-forward part, no `ln2`, no `mlp` or
    # `moe` scope. (A model whose published blocks are each a mixer OR a
    # feed-forward part is written so: a mixer block and the
    # feed-forward block behind it are one layer here, a mixer block
    # that no feed-forward block follows a bare one)
    layer_types: Optional[Tuple[str, ...]] = None
    bare_layers: Tuple[int, ...] = ()
    # "kda": gated delta-rule linear attention (Kimi Delta Attention):
    # `kda_heads` heads of `kda_head_dim` (keys and values alike), a
    # causal depthwise convolution of `kda_conv` taps on q, k and v, a
    # per-channel decay and an output gate through low-rank pairs of
    # `kda_head_dim`, the recurrence in chunks of `kda_chunk` tokens
    # (`ops/kda.py`)
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_chunk: int = 64
    # "conv": the double-gated short convolution (LFM2): (b, c, u) =
    # split3(x W_in), a causal depthwise convolution of `conv_taps`
    # taps over b * u with no bias and no activation, then (c * that)
    # W_out
    conv_taps: int = 3
    # "mha" with fewer key-value heads than query heads (grouped-query
    # attention: query head i reads key-value head i // group); None =
    # `n_heads`
    n_kv_heads: Optional[int] = None
    # "mha": an RMS norm over every query's and key's head_dim, one
    # weight of head_dim each a layer, before the rotation
    qk_norm: bool = False
    # the logits read the embedding transposed; no "head" leaf
    tie_embeddings: bool = False
    # a head's width where it is not d_model // n_heads (the
    # projections are then not square: 48 heads of 128 over 2048)
    head_width: Optional[int] = None
    # "mha" and "swa": a per-head gate on the attention's output,
    # o_h * sigmoid(x . wgate_h), before the output projection
    attn_gate: bool = False
    # "mha": only a head's first `rope_dim` columns turn (None: all of
    # them), by `rope_yarn`'s blended frequencies where that is set,
    # the angles' cosine and sine times `rope_factor` (YaRN's
    # attention factor, laid on the rotation and not on the scores)
    rope_dim: Optional[int] = None
    rope_factor: float = 1.0
    # "swa": "mha" under a window (the query at t sees the keys u with
    # 0 <= t - u < `swa_window`), with a head count and a rotary base
    # of its own, the whole head turned; the key-value heads and the
    # head's width are the stack's
    swa_heads: int = 0
    swa_window: int = 0
    swa_rope_base: float = 10000.0
    # "gdn": the gated delta rule under ONE decay a head (Gated
    # DeltaNet): `gdn_key_heads` heads of q and k under `gdn_value_heads`
    # of v (a multiple: value head j reads key head j // group), all of
    # `gdn_head_dim`; one causal depthwise convolution of `gdn_conv`
    # taps over q | k | v; the decay and the write strength from one
    # [d, 2 x value heads] projection; the output normed per head and
    # gated by SiLU of a full-width z; chunks of `kda_chunk`
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_head_dim: int = 0
    gdn_conv: int = 4
    # "mha": a gate per output CHANNEL, o * sigmoid(gate), the gate the
    # second half of a query projection twice as wide (`attn_gate` is
    # one number a head from a leaf of its own)
    attn_channel_gate: bool = False
    # the shared expert behind a gate of its own, one number a token:
    # y = routed + sigmoid(x . sgate) S(x)
    shared_expert_gate: bool = False
    # "mha" and "swa": False turns nothing, queries and keys enter the
    # scores as projected (`mla_rope` is latent attention's)
    rope: bool = True
    # "mamba2": the selective state-space mixer (Mamba-2): `ssm_heads`
    # heads of `ssm_head_dim` (the inner width their product) over a
    # state of `ssm_state`, B and C in `ssm_groups` groups (head j reads
    # group j // (heads / groups)); ONE projection z | x | B | C | dt, a
    # causal depthwise convolution of `ssm_conv` taps WITH a bias over
    # x | B | C, then SiLU; dt = softplus(dt + dt_bias), the log-decay
    # dt x -exp(A_log), one number a head and token; the recurrence in
    # chunks of `ssm_chunk` (`ops/ssd.py`) plus D x; the output times
    # SiLU(z), then RMS-normed over each group's channels (one weight of
    # the inner width), then projected. `ssm_residual_blocks` > 0
    # divides the output projection's initial values by its root
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_residual_blocks: int = 0
    # "mamba1": the selective state-space mixer (Mamba-1): (x | z) = u
    # W_in, `ssm1_inner` wide each; x through a causal depthwise
    # convolution of `ssm1_conv` taps WITH a bias, then SiLU; (delta | B
    # | C) = x W_x, `ssm1_dt_rank` | `ssm1_state` | `ssm1_state`; dt =
    # softplus(delta W_dt + dt_bias); the decay exp(dt x -exp(a_log)),
    # one number a channel AND state column (`a_log` [state, inner]);
    # the recurrence is `ops/selective_scan.py`'s, plus D x; the output
    # times SiLU(z), projected. The layer `memory_layer` (its index in
    # the stack) also hands its y, after D x and before the gate, to
    # the layers behind it as their `memory`.
    # "gmu": the gated memory unit, W_2 (memory x SiLU(W_1 u)), both
    # matrices between d_model and `ssm1_inner`, no state of its own
    ssm1_inner: int = 0
    ssm1_state: int = 16
    ssm1_conv: int = 4
    ssm1_dt_rank: int = 0
    memory_layer: Optional[int] = None
    # "mha", "swa" and "cross" as DIFFERENTIAL attention (Ye et al.
    # 2024): heads come in pairs (2p, 2p + 1), key-value heads too, a
    # pair's values read as ONE head twice as wide; pair p's output is
    # softmax(q1 k1) v - lambda softmax(q2 k2) v, lambda = exp(lq1 . lk1)
    # - exp(lq2 . lk2) + lambda_init with four learned vectors of
    # head_dim a layer, lambda_init = 0.8 - 0.6 exp(-0.3 depth), depth
    # the layer's entry of `diff_depths` (its index in the PUBLISHED
    # stack, one a layer here); then an RMS norm over the pair's 2 x
    # head_dim columns (one weight of that width a layer) times (1 -
    # lambda_init). The four vectors and the norm's weight are one leaf
    # `diff` [6 x head_dim]; `wq`'s and `wk`'s columns hold the pairs'
    # first members, then their second members.
    # "cross": such a layer with queries of its own and the keys and
    # values of the layer `kv_layer` (its index in the stack), full
    # causal; it has no `wk` and no `wv`
    diff_attention: bool = False
    diff_depths: Optional[Tuple[int, ...]] = None
    kv_layer: Optional[int] = None
    # "mha", "swa", "cross": a bias on the q, k, v and output projections
    attn_bias: bool = False
    # "layer": every norm of the residual stream is a LayerNorm with a
    # weight and a bias (`ln1_bias`, `ln2_bias`, `ln_f_bias`), its
    # statistics in float32; "rms": the RMS norm with a weight
    norm: str = "rms"
    # the router of every expert layer reads the layer's INPUT, the
    # residual stream as it enters the layer, un-normed and ahead of the
    # mixer; the experts read ln2 of the stream behind the mixer as ever
    # (scope `router`, in front of the mixer's; `moe_top_k` only)
    early_router: bool = False
    # the kinds of layer, of "mha" and "swa", whose queries and keys
    # turn; None: both. `rope` False turns nothing whatever this says
    rope_mixers: Optional[Tuple[str, ...]] = None

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def mixers(self) -> Tuple[str, ...]:
        return tuple(self.layer_types or (self.attention,) * self.n_layers)

    @property
    def runs(self) -> Tuple[Tuple[str, Optional[bool], int], ...]:
        """The stack as scanned runs: (mixer, expert layer?, layers);
        the second is None for layers with no feed-forward part."""
        runs = []
        gives = (self.memory_layer, self.kv_layer)
        for i, mixer in enumerate(self.mixers):
            kind = (mixer, None if i in self.bare_layers else (
                bool(self.n_experts) and i >= self.n_dense_layers
            ))
            alone = i in gives or i - 1 in gives
            if runs and runs[-1][:2] == kind and not alone:
                runs[-1] = kind + (runs[-1][2] + 1,)
            else:
                runs.append(kind + (1,))
        return tuple(runs)

    @property
    def mixed(self) -> bool:
        """More than one mixer: the runs lie under `params["stack"]`,
        not under "dense" and "layers"."""
        return len(set(self.mixers)) > 1

    @property
    def looped(self) -> bool:
        return self.n_loops > 1

    def attention_shape(self, mixer: str) -> "AttentionShape":
        """What parts an "mha" layer from a "swa" one. In a stack that
        holds both, each kind has a scope of its own under
        `attention`."""
        turns = self.rope and (
            self.rope_mixers is None or mixer in self.rope_mixers
        )
        if mixer == "swa":
            return AttentionShape(
                self.swa_heads, self.swa_window, self.swa_rope_base,
                None, None, 1.0, "swa", turns,
            )
        if mixer == "cross":
            return AttentionShape(
                self.n_heads, None, self.rope_base, self.rope_dim,
                self.rope_yarn, self.rope_factor, "cross", turns,
            )
        return AttentionShape(
            self.n_heads, None, self.rope_base, self.rope_dim,
            self.rope_yarn, self.rope_factor,
            "global" if "swa" in self.mixers else None, turns,
        )

    @property
    def held(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.n_experts)


class YarnScaling(NamedTuple):
    """YaRN's settings as a model's `rope_scaling` states them."""

    factor: float
    beta_fast: float
    beta_slow: float
    original_length: int
    mscale: float
    mscale_all_dim: float


class AttentionShape(NamedTuple):
    """One kind of attention layer: `TransformerConfig.attention_shape`."""

    heads: int
    window: Optional[int]
    rope_base: float
    rope_dim: Optional[int]  # None: the whole head turns
    rope_yarn: Optional[YarnScaling]
    rope_factor: float
    scope: Optional[str]  # its scope under `attention`
    turns: bool = True  # whether its queries and keys turn at all


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, base: float, yarn: Optional[YarnScaling]):
    """The `dim // 2` rotary frequencies, float32: base^(-2i/dim), and
    under YaRN that blended with the same divided by `factor` — pairs
    that turn more than `beta_fast` times over the original length
    keep their frequency, those that turn less than `beta_slow` times
    take the divided one, a linear ramp between (Peng et al. 2023, as
    the released DeepSeek-V2 modelling file computes it)."""
    exponent = np.arange(0, dim, 2, dtype=np.float32) / dim
    plain = 1.0 / base**exponent
    if yarn is None:
        return plain.astype(np.float32)

    def turns_to_pair(turns):
        return dim * math.log(
            yarn.original_length / (turns * 2 * math.pi)
        ) / (2 * math.log(base))

    low = max(math.floor(turns_to_pair(yarn.beta_fast)), 0)
    high = min(math.ceil(turns_to_pair(yarn.beta_slow)), dim - 1)
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float32) - low)
        / max(high - low, 0.001),
        0, 1,
    )
    return (plain / yarn.factor * ramp + plain * (1 - ramp)).astype(np.float32)


def mla_softmax_scale(cfg: "TransformerConfig") -> float:
    """(qk_nope + qk_rope)^-0.5 x mscale^2, mscale from YaRN's
    `mscale_all_dim` (1 without YaRN)."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.rope_yarn is not None:
        scale *= _yarn_mscale(
            cfg.rope_yarn.factor, cfg.rope_yarn.mscale_all_dim
        ) ** 2
    return scale


def _expert_leaves(mlp: str):
    """(a routed expert's leaves, the shared expert's), in the order
    the layer takes them: a gated expert's gate, up and down ("swiglu",
    "reglu"), a squared-ReLU expert's up and down."""
    if mlp == "relu2":
        return ("eu", "ed"), ("su", "sd")
    return ("eg", "eu", "ed"), ("sg", "su", "sd")


# every mixer the routed stack builds (`_init_routed_params`)
ROUTED_MIXERS = (
    "mla", "kda", "gdn", "conv", "mamba2", "mamba1", "gmu", "mha", "swa",
    "cross",
)
# a run's leaves that the forward pass reads as stored (float32) and not
# as cast to the compute dtype
_FLOAT32_LEAVES = (
    "router", "router_bias", "dt_bias", "q_norm", "k_norm", "out_norm",
    "ssm_norm", "a_log", "D", "diff",
)
# how a routed stack's stats, one a layer, become one number a step; a
# stat without a rule here is a KeyError when the program is traced
_OVER_LAYERS = {
    "held_share": jnp.mean,
    "router_entropy": jnp.mean,
    "route_rows": jnp.mean,
    "route_full": jnp.sum,
    "router_bias_absmax": jnp.max,
    "kda_log_decay_min": jnp.min,
    "shortconv_gate_absmax": jnp.max,
    "attn_gate_mean": jnp.mean,
    "gdn_log_decay_min": jnp.min,
    "gdn_beta_mean": jnp.mean,
    "shared_gate_mean": jnp.mean,
    "ssm_log_decay_min": jnp.min,
    "ssm_dt_mean": jnp.mean,
    "ssm1_log_decay_min": jnp.min,
    "ssm1_dt_mean": jnp.mean,
    "diff_lambda_mean": jnp.mean,
    "gmu_gate_absmax": jnp.max,
}


def _remat(body):
    """Per-layer rematerialization: the backward pass recomputes the
    layer from its input, all but the attention kernels' output and
    log-sum-exp, which it keeps (`flash_attention.RESIDUAL_NAMES`: at
    most four times the layer input, and the forward kernel is not run
    a second time). Off the kernels no such name exists and nothing is
    kept."""
    from elasticdl_tpu.ops.flash_attention import RESIDUAL_NAMES

    return jax.checkpoint(
        body,
        policy=jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES),
    )


# ------------------------------------------------------------------- params


def init_params(rng: np.random.Generator, cfg: TransformerConfig) -> Dict:
    """Host-side init (numpy, float32 master copies)."""

    def norm(*shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2] if len(shape) > 1 else shape[-1])
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    L, d = cfg.n_layers, cfg.d_model
    if (
        cfg.moe_top_k or cfg.n_dense_layers or cfg.layer_types
        or cfg.attention in ("mla", "kda", "gdn", "mamba2")
    ):
        return _init_routed_params(norm, rng, cfg)
    layers = _init_mha(norm, cfg, L)
    if cfg.n_experts:
        layers["router"] = norm(L, d, cfg.n_experts)
        layers["ew1"] = norm(L, cfg.n_experts, d, cfg.d_expert)
        layers["ew2"] = norm(L, cfg.n_experts, cfg.d_expert, d)
    elif cfg.mlp == "swiglu":
        layers["wg"] = norm(L, d, cfg.d_ff)
        layers["wu"] = norm(L, d, cfg.d_ff)
        layers["wd"] = norm(L, cfg.d_ff, d)
    else:
        layers["w1"] = norm(L, d, cfg.d_ff)
        layers["w2"] = norm(L, cfg.d_ff, d)
    if cfg.sandwich_norm:
        layers["ln1b"] = np.ones((L, d), np.float32)
        layers["ln2b"] = np.ones((L, d), np.float32)
    params = {
        "embed": norm(cfg.vocab, d, scale=0.02),
        "layers": layers,
        "ln_f": np.ones((d,), np.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = norm(d, cfg.vocab)
    if cfg.looped:
        # Linear(d, 1): the gates start near one half at every exit
        params["exit_gate"] = {
            "w": norm(d, 1, scale=0.02),
            "b": np.zeros((1,), np.float32),
        }
    return params


def _init_mha(norm, cfg: TransformerConfig, L: int, mixer="mha") -> Dict:
    """A multi-head attention layer's leaves but its MLP's, for L
    stacked layers of `mixer` ("mha" or "swa": its own head count):
    `n_kv_heads` key-value heads, under `qk_norm` the two norms'
    weights, under `attn_gate` the gate's; under `attn_channel_gate`
    `wq` is twice as wide, the queries' columns then the gate's."""
    d, hd = cfg.d_model, cfg.head_dim
    heads = cfg.attention_shape(mixer).heads
    tree = {
        "ln1": np.ones((L, d), np.float32),
        "wq": norm(L, d, (1 + cfg.attn_channel_gate) * heads * hd),
        "wk": norm(L, d, cfg.kv_heads * hd),
        "wv": norm(L, d, cfg.kv_heads * hd),
        "wo": norm(L, heads * hd, d),
        "ln2": np.ones((L, d), np.float32),
    }
    if cfg.attn_bias:
        for name in ("bq", "bk", "bv", "bo"):
            tree[name] = np.zeros((L, tree["w" + name[1]].shape[-1]), np.float32)
    if mixer == "cross":  # its keys and values are another layer's
        for name in ("wk", "wv", "bk", "bv"):
            tree.pop(name, None)
    if cfg.diff_attention:
        # lq1 | lk1 | lq2 | lk2 (normal at 0.1) | the pair norm's weight
        # (ones), ONE leaf of 6 x head_dim: five leaves of 64 or 128
        # would each end in a narrow dim (`kda_a_log`, below)
        tree["diff"] = np.concatenate([
            norm(L, 4 * hd, scale=0.1), np.ones((L, 2 * hd), np.float32),
        ], axis=1)
    if cfg.qk_norm:
        tree["q_norm"] = np.ones((L, hd), np.float32)
        tree["k_norm"] = np.ones((L, hd), np.float32)
    if cfg.attn_gate:
        # [heads, d], not [d, heads]: a leaf that ends in a narrow dim
        # makes the v5e compiler pad the flat vector (`wbeta`, below)
        tree["wgate"] = norm(L, heads, d, scale=1.0 / math.sqrt(d))
    return tree


def _init_routed_params(norm, rng, cfg: TransformerConfig) -> Dict:
    """The stack that is not of one shape: one stacked tree a run of
    `cfg.runs`, each on its own leading dim. With one mixer throughout
    that is `n_dense_layers` layers with the dense gated MLP under
    "dense", then the expert layers under "layers"; with mixers that
    differ the runs lie in order under "stack"."""
    d = cfg.d_model
    dense = any(experts is False for _mixer, experts, _layers in cfg.runs)
    if not (
        # top-k experts, or none at all: every layer's MLP the dense one
        (cfg.moe_top_k or not cfg.n_experts)
        and cfg.mlp in EXPERT_KINDS
        and not (dense and cfg.mlp != "swiglu")
        and set(cfg.mixers) <= set(ROUTED_MIXERS)
        and len(cfg.mixers) == cfg.n_layers
    ):
        raise NotImplementedError(
            "the routed stack is built with latent attention, delta-rule "
            "attention, short convolutions, state-space mixers or gated "
            "memory units beside grouped-query, differential and cross "
            "attention, top-k experts and gated (SiLU or ReLU) or "
            "squared-ReLU MLPs "
            "together (attention or layer_types of "
            + ", ".join(repr(m) for m in ROUTED_MIXERS)
            + ", one a layer; moe_top_k > 0, or n_experts = 0; "
            "mlp='swiglu', or mlp='relu2' or 'reglu' in a stack with no "
            "dense layer)"
        )
    _require_feeders(cfg)

    def mla(L):
        heads, rank = cfg.n_heads, cfg.kv_lora_rank
        return {
            "ln1": np.ones((L, d), np.float32),
            "wq": norm(L, d, heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
            "wkva": norm(L, d, rank + cfg.qk_rope_dim),
            "kv_norm": np.ones((L, rank), np.float32),
            "wkvb": norm(L, rank, heads * (cfg.qk_nope_dim + cfg.v_head_dim)),
            "wo": norm(L, heads * cfg.v_head_dim, d),
            "ln2": np.ones((L, d), np.float32),
        }

    def kda(L):
        heads, hd, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
        wide = heads * hd

        def conv():  # a tap's weights; the taps sum like a fan-in
            return norm(L, taps, wide, scale=1.0 / math.sqrt(taps))

        block = {
            "ln1": np.ones((L, d), np.float32),
            "wq": norm(L, d, wide), "wk": norm(L, d, wide),
            "wv": norm(L, d, wide),
            "conv_q": conv(), "conv_k": conv(), "conv_v": conv(),
            "f_down": norm(L, d, hd), "f_up": norm(L, hd, wide),
            "wbeta": norm(L, heads, d, scale=1.0 / math.sqrt(d)),  # [heads, d]
            "g_down": norm(L, d, hd), "g_up": norm(L, hd, wide),
            "wo": norm(L, wide, d),
        }
        # the family's convention: the decay's step softplus(dt_bias)
        # uniform in (0.001, 0.1); its rate is `kda_a_log`, below
        dt = rng.uniform(0.001, 0.1, (L, wide))
        block.update(
            dt_bias=(dt + np.log(-np.expm1(-dt))).astype(np.float32),
            o_norm=np.ones((L, hd), np.float32),
            ln2=np.ones((L, d), np.float32),
        )
        return block

    def gdn(L):
        kh, vh, hd = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_head_dim
        qkv = (2 * kh + vh) * hd
        return {
            "ln1": np.ones((L, d), np.float32),
            # the columns: q | k | v | z
            "wqkvz": norm(L, d, qkv + vh * hd),
            # a tap's weights; the taps sum like a fan-in
            "conv": norm(L, cfg.gdn_conv, qkv, scale=1.0 / math.sqrt(cfg.gdn_conv)),
            # [2 x value heads, d], the write strength's rows then the
            # decay's: not [d, 64] (`wbeta`)
            "wba": norm(L, 2 * vh, d, scale=1.0 / math.sqrt(d)),
            "out_norm": np.ones((L, hd), np.float32),
            "wo": norm(L, vh * hd, d),
            "ln2": np.ones((L, d), np.float32),
        }

    def conv(L):
        taps = cfg.conv_taps
        return {
            "ln1": np.ones((L, d), np.float32),
            "in_proj": norm(L, d, 3 * d),
            # a tap's weights; the taps sum like a fan-in
            "conv": norm(L, taps, d, scale=1.0 / math.sqrt(taps)),
            "out_proj": norm(L, d, d),
            "ln2": np.ones((L, d), np.float32),
        }

    def mamba2(L):
        inner = cfg.ssm_heads * cfg.ssm_head_dim
        bc = 2 * cfg.ssm_groups * cfg.ssm_state
        out_proj = norm(L, inner, d)
        if cfg.ssm_residual_blocks:
            out_proj /= np.float32(math.sqrt(cfg.ssm_residual_blocks))
        return {
            "ln1": np.ones((L, d), np.float32),
            # the columns: z | x | B | C | dt
            "in_proj": norm(L, d, 2 * inner + bc + cfg.ssm_heads),
            # a tap's weights; the taps sum like a fan-in
            "conv": norm(L, cfg.ssm_conv, inner + bc,
                         scale=1.0 / math.sqrt(cfg.ssm_conv)),
            "conv_bias": np.zeros((L, inner + bc), np.float32),
            "ssm_norm": np.ones((L, inner), np.float32),
            "out_proj": out_proj,
            "ln2": np.ones((L, d), np.float32),
        }

    def mamba1(L):
        inner, n, rank = cfg.ssm1_inner, cfg.ssm1_state, cfg.ssm1_dt_rank
        # the family's initialiser: the step's bias the inverse softplus
        # of a step drawn log-uniform on (0.001, 0.1) and floored at
        # 1e-4, its projection uniform at rank^-1/2; column n's rate
        # exp(a_log) = n + 1 on every channel; the skip D = 1
        dt = np.maximum(np.exp(rng.uniform(
            math.log(0.001), math.log(0.1), (L, inner)
        )), 1e-4)
        return {
            "ln1": np.ones((L, d), np.float32),
            "in_proj": norm(L, d, 2 * inner),  # the columns: x | z
            # a tap's weights; the taps sum like a fan-in
            "conv": norm(L, cfg.ssm1_conv, inner,
                         scale=1.0 / math.sqrt(cfg.ssm1_conv)),
            "conv_bias": np.zeros((L, inner), np.float32),
            "x_proj": norm(L, inner, rank + 2 * n),  # delta | B | C
            "dt_proj": rng.uniform(
                -rank**-0.5, rank**-0.5, (L, rank, inner)
            ).astype(np.float32),
            "dt_bias": (dt + np.log(-np.expm1(-dt))).astype(np.float32),
            # [state, inner], not [inner, 16] (`kda_a_log`, below)
            "a_log": np.broadcast_to(
                np.log(np.arange(1, n + 1, dtype=np.float32))[None, :, None],
                (L, n, inner),
            ).copy(),
            "D": np.ones((L, inner), np.float32),
            "out_proj": norm(L, inner, d),
            "ln2": np.ones((L, d), np.float32),
        }

    def gmu(L):
        return {
            "ln1": np.ones((L, d), np.float32),
            "w1": norm(L, d, cfg.ssm1_inner),
            "w2": norm(L, cfg.ssm1_inner, d),
            "ln2": np.ones((L, d), np.float32),
        }

    mixer_trees = {
        "mla": mla, "kda": kda, "gdn": gdn, "conv": conv, "mamba2": mamba2,
        "mamba1": mamba1, "gmu": gmu,
        "mha": lambda L: _init_mha(norm, cfg, L),
        "swa": lambda L: _init_mha(norm, cfg, L, "swa"),
        "cross": lambda L: _init_mha(norm, cfg, L, "cross"),
    }
    (_first, held) = cfg.held
    f, fs = cfg.d_expert, cfg.n_shared_experts * cfg.d_expert

    routed_leaves, shared_leaves = _expert_leaves(cfg.mlp)
    shapes = {
        "eg": (held, d, f), "eu": (held, d, f), "ed": (held, f, d),
        "sg": (d, fs), "su": (d, fs), "sd": (fs, d),
    }

    def run(mixer, experts, L):
        tree = mixer_trees[mixer](L)
        if experts is None:  # the mixer alone
            del tree["ln2"]
        elif experts:
            tree["router"] = norm(L, d, cfg.n_experts)
            # a layer with no shared expert has no such leaves
            for name in routed_leaves + (shared_leaves if fs else ()):
                tree[name] = norm(L, *shapes[name])
            if fs and cfg.shared_expert_gate:  # [1, d], not [d, 1] (`wbeta`)
                tree["sgate"] = norm(L, 1, d, scale=1.0 / math.sqrt(d))
            if cfg.moe_score == "sigmoid":
                tree["router_bias"] = np.zeros((L, cfg.n_experts), np.float32)
        else:
            tree.update(
                wg=norm(L, d, cfg.d_ff), wu=norm(L, d, cfg.d_ff),
                wd=norm(L, cfg.d_ff, d),
            )
        if cfg.norm == "layer":
            for name in ("ln1", "ln2"):
                if name in tree:
                    tree[name + "_bias"] = np.zeros((L, d), np.float32)
        return tree

    if cfg.mixed:
        stack = {"stack": [run(*r) for r in cfg.runs]}
    else:
        n_dense = cfg.n_dense_layers
        stack = {
            "dense": run(cfg.mixers[0], False, n_dense),
            "layers": run(cfg.mixers[0], True, cfg.n_layers - n_dense),
        }
    params = {
        "embed": norm(cfg.vocab, d, scale=0.02),
        **stack,
        "ln_f": np.ones((d,), np.float32),
    }
    if cfg.norm == "layer":
        params["ln_f_bias"] = np.zeros((d,), np.float32)
    if not cfg.tie_embeddings:
        params["head"] = norm(d, cfg.vocab)
    n_kda = cfg.mixers.count("kda")
    if n_kda:
        # the decay's rate exp(a_log), uniform in (1, 16): ONE flat leaf
        # for all KDA layers, in stack order, [KDA layers x heads]. No
        # leaf of this tree ends in a dim of 32 (`wbeta` is stored
        # [heads, d] for the same reason): the v5e compiler reads such
        # a leaf out of the flat parameter vector by viewing the WHOLE
        # vector as [n / 32, 32], which its (8, 128) tiles pad fourfold
        # (8.98 GB for the 2.41 GB of Kimi-Linear's cut, and half of
        # that again for the bfloat16 copy)
        params["kda_a_log"] = np.log(
            rng.uniform(1.0, 16.0, (n_kda * cfg.kda_heads,))
        ).astype(np.float32)
    n_gdn = cfg.mixers.count("gdn")
    if n_gdn:
        # one decay a VALUE HEAD: its rate exp(a_log), uniform in
        # (0, 16), and its step's bias, 1. ONE flat leaf for all GDN
        # layers, [a_log | dt_bias] each in stack order [GDN layers x
        # value heads], as `kda_a_log` and for its reason
        n = n_gdn * cfg.gdn_value_heads
        params["gdn_decay"] = np.concatenate([
            np.log(rng.uniform(1e-3, 16.0, (n,))), np.ones((n,)),
        ]).astype(np.float32)
    n_ssm = cfg.mixers.count("mamba2")
    if n_ssm:
        # a head's rate exp(a_log), uniform in (1, 16); its step's bias,
        # the inverse softplus of a step drawn log-uniform on (0.001,
        # 0.1) and floored at 1e-4; its skip D = 1 (the family's
        # initialiser). ONE flat leaf for all Mamba-2 layers, [a_log |
        # dt_bias | D] each in stack order [layers x heads], as
        # `kda_a_log` and for its reason
        n = n_ssm * cfg.ssm_heads
        dt = np.maximum(
            np.exp(rng.uniform(math.log(0.001), math.log(0.1), (n,))), 1e-4
        )
        params["ssm_decay"] = np.concatenate([
            np.log(rng.uniform(1.0, 16.0, (n,))),
            dt + np.log(-np.expm1(-dt)), np.ones((n,)),
        ]).astype(np.float32)
    return params


def param_partition_specs(cfg: TransformerConfig) -> Dict:
    """PartitionSpec per leaf over the ("pp","dp","sp","tp") mesh.

    Stacked layer dims shard over pp; TP shards the matmul dims; expert
    weights shard their E dim over dp (the EP group). Embedding/head
    replicated (vocab-parallel is a later optimization).
    """
    _require_mesh_support(cfg)
    layers = {
        "ln1": P("pp", None),
        "wq": P("pp", None, "tp"),
        "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"),
        "wo": P("pp", "tp", None),
        "ln2": P("pp", None),
    }
    if cfg.n_experts:
        layers["router"] = P("pp", None, None)
        layers["ew1"] = P("pp", "dp", None, None)
        layers["ew2"] = P("pp", "dp", None, None)
    else:
        layers["w1"] = P("pp", None, "tp")
        layers["w2"] = P("pp", "tp", None)
    return {
        "embed": P(None, None),
        "layers": layers,
        "ln_f": P(None),
        "head": P(None, None),
    }


# -------------------------------------------------------------------- model


def _require_feeders(cfg: TransformerConfig):
    """A "gmu" layer reads the memory of `memory_layer` and a "cross"
    layer the keys and values of `kv_layer`: each an EARLIER layer of
    the right kind, or the stack is refused."""
    mixers = cfg.mixers
    for reader, feeder, at, what in (
        ("gmu", "mamba1", cfg.memory_layer, "memory_layer"),
        ("cross", "mha", cfg.kv_layer, "kv_layer"),
    ):
        if at is not None and not (
            0 <= at < len(mixers) and mixers[at] == feeder
        ):
            raise ValueError(f"{what} {at} is no {feeder!r} layer of {mixers}")
        if reader in mixers and (at is None or mixers.index(reader) < at):
            raise ValueError(
                f"a {reader!r} layer at {mixers.index(reader)} of {mixers} "
                f"has no earlier {feeder!r} layer to read ({what} = {at})"
            )
    if cfg.diff_attention and (
        cfg.diff_depths is None or len(cfg.diff_depths) != len(mixers)
        or cfg.n_heads % 2 or cfg.kv_heads % 2
    ):
        raise ValueError(
            "diff_attention pairs the heads (an even n_heads and "
            "n_kv_heads) and takes diff_depths, one published index a layer"
        )
    if cfg.early_router and not cfg.moe_top_k:
        raise ValueError(
            "early_router places the top-k expert layers' router "
            "(moe_top_k > 0); this stack has none"
        )
    if cfg.rope_mixers is not None and (
        cfg.diff_attention or not set(cfg.rope_mixers) <= {"mha", "swa"}
    ):
        raise ValueError(
            f"rope_mixers {cfg.rope_mixers} names the kinds of layer that "
            "turn, of 'mha' and 'swa', outside differential attention "
            "(whose 'cross' layers read another layer's turned keys)"
        )


def _require_mesh_support(cfg: TransformerConfig):
    """The 4-axis mesh path knows the original block only."""
    if (
        cfg.mlp != "gelu" or cfg.sandwich_norm or cfg.looped
        or cfg.attention != "mha" or cfg.n_dense_layers or cfg.moe_top_k
        or cfg.layer_types or cfg.n_kv_heads or cfg.qk_norm
        or cfg.tie_embeddings or cfg.head_width or cfg.attn_gate
        or cfg.rope_dim or cfg.rope_factor != 1.0 or cfg.swa_heads
        or cfg.swa_window or cfg.gdn_key_heads or cfg.gdn_value_heads
        or cfg.gdn_head_dim or cfg.attn_channel_gate
        or cfg.shared_expert_gate or cfg.bare_layers or not cfg.rope
        or cfg.ssm_heads or cfg.ssm_head_dim or cfg.ssm_state
        or cfg.ssm1_inner or cfg.ssm1_dt_rank or cfg.memory_layer is not None
        or cfg.diff_attention or cfg.diff_depths or cfg.kv_layer is not None
        or cfg.attn_bias or cfg.norm != "rms" or cfg.early_router
        or cfg.rope_mixers is not None
    ):
        raise NotImplementedError(
            "the (pp, dp, sp, tp) mesh path runs the two-matrix GELU "
            "block once: mlp='swiglu', 'relu2' and 'reglu', sandwich_norm, "
            "n_loops > 1, attention='mla', 'kda', 'gdn' and 'mamba2', "
            "layer_types (with 'swa' and 'conv'), bare_layers, "
            "n_dense_layers, moe_top_k, n_kv_heads, qk_norm, "
            "tie_embeddings, head_width, attn_gate, attn_channel_gate, "
            "shared_expert_gate, rope=False, rope_dim, rope_factor, the "
            "windowed mixer (swa_heads, swa_window), the scalar-decay "
            "delta rule (gdn_key_heads, gdn_value_heads, gdn_head_dim), "
            "the state-space mixers (ssm_heads, ssm_head_dim, ssm_state; "
            "'mamba1' with ssm1_inner, ssm1_dt_rank, memory_layer and the "
            "'gmu' that reads it), differential attention (diff_attention, "
            "diff_depths, 'cross' and kv_layer), attn_bias, norm='layer', "
            "early_router and rope_mixers exist on the unsharded path "
            "(plain_forward) only"
        )


def _rope(
    x: jnp.ndarray, positions: jnp.ndarray, base: float = 10000.0,
    freqs=None, rot: Optional[int] = None, factor: float = 1.0,
) -> jnp.ndarray:
    """Rotary embedding; x: [B, L, H, D], positions: [L] global. The
    angles are float32 whatever x is (bfloat16 holds no whole number
    above 256 exactly: position 2047 would turn as 2048); their cosine
    and sine are cast to x's dtype. Pair i is (x[i], x[i + D/2]).
    `freqs` [D/2] float32 replaces base^(-2i/D) (`yarn_frequencies`).
    With `rot` only the first `rot` columns turn (pair i is (x[i],
    x[i + rot/2]), D read as `rot` above) and the rest pass as they
    are; `factor` multiplies cosine and sine, so it scales what turns
    and nothing else."""
    d = x.shape[-1] if rot is None else rot
    half = d // 2
    if freqs is None:
        freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # [L, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    cos = cos.astype(x.dtype)[None, :, None, :]
    sin = sin.astype(x.dtype)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:d]
    turned = [x1 * cos - x2 * sin, x1 * sin + x2 * cos]
    if rot is not None and rot < x.shape[-1]:
        turned.append(x[..., rot:])
    return jnp.concatenate(turned, axis=-1)


def _block(cfg: TransformerConfig, lp: Dict, h: jnp.ndarray, positions) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One transformer block on local shards; h: [mb, Lc, d]."""
    mb, lc, d = h.shape
    tp = lax.axis_size("tp")
    h_local = cfg.n_heads // tp

    x = rms_norm(h, lp["ln1"], cfg.norm_eps)
    q = (x @ lp["wq"]).reshape(mb, lc, h_local, cfg.head_dim)
    k = (x @ lp["wk"]).reshape(mb, lc, h_local, cfg.head_dim)
    v = (x @ lp["wv"]).reshape(mb, lc, h_local, cfg.head_dim)
    q = _rope(q, positions, cfg.rope_base)
    k = _rope(k, positions, cfg.rope_base)
    attn = ring_attention(q, k, v, "sp", causal=True)
    attn = attn.reshape(mb, lc, h_local * cfg.head_dim)
    h = h + lax.psum(attn @ lp["wo"], "tp")

    x = rms_norm(h, lp["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        flat = x.reshape(mb * lc, d)
        out, aux = moe_ffn(
            flat,
            lp["router"],
            lp["ew1"],
            lp["ew2"],
            "dp",
            capacity_factor=cfg.capacity_factor,
        )
        # expert compute is replicated across tp (experts shard over dp
        # only); no tp collective needed here
        h = h + out.reshape(mb, lc, d)
    else:
        up = jax.nn.gelu(x @ lp["w1"])
        h = h + lax.psum(up @ lp["w2"], "tp")
        aux = jnp.zeros((), dtype=h.dtype)
    return h, aux


def _local_forward(cfg: TransformerConfig, params: Dict, tokens: jnp.ndarray):
    """Per-device forward; tokens: [B_local, L_local] -> (logits, aux)."""
    sp_idx = lax.axis_index("sp")
    b, lc = tokens.shape
    positions = sp_idx * lc + jnp.arange(lc)

    h = params["embed"].astype(cfg.dtype)[tokens]  # [B, Lc, d]

    n_micro = cfg.n_micro
    mb = b // n_micro
    micro = h.reshape(n_micro, mb, lc, cfg.d_model)

    stage_fn = lambda sp_params, x: _stage(cfg, sp_params, x, positions)
    outputs, aux = gpipe(stage_fn, params["layers"], micro, "pp", has_aux=True)
    h = outputs.reshape(b, lc, cfg.d_model)

    h = rms_norm(h, params["ln_f"].astype(cfg.dtype), cfg.norm_eps)
    logits = h @ params["head"].astype(cfg.dtype)  # [B, Lc, V]
    return logits, aux


def _stage(cfg, stage_params, x, positions):
    """One pipeline stage: scan this rank's stacked local layers."""
    from elasticdl_tpu.parallel.vma_util import match_vma

    def body(carry, lp):
        h, aux = carry
        h, a = _block(cfg, lp, h, positions)
        return (h, aux + a), None

    if cfg.remat:
        body = _remat(body)

    # promote the carry to the block output's varying axes (params vary
    # over pp, so the first block output does too); probe is DCE'd
    lp0 = jax.tree_util.tree_map(lambda a: a[0], stage_params)
    probe_h, probe_a = _block(cfg, lp0, x, positions)
    x = match_vma(x, probe_h)
    aux0 = match_vma(jnp.zeros((), dtype=x.dtype), probe_a, probe_h)
    (h, aux), _ = lax.scan(body, (x, aux0), stage_params)
    return h, aux


def token_cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray):
    """Mean next-token CE in f32 — THE loss definition, shared by the
    sharded path, the plain fast path, the dense reference, and the
    model-zoo spec (one place to fix numerics/masking for all four)."""
    with jax.named_scope("head"):
        logits = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)


def _local_loss(cfg: TransformerConfig, params, inputs, targets):
    """Global mean next-token CE + aux loss, formed inside shard_map."""
    params = jax.tree_util.tree_map(lambda a: a.astype(cfg.dtype), params)
    logits, aux = _local_forward(cfg, params, inputs)
    ce = token_cross_entropy(logits, targets)
    loss = lax.pmean(ce, ("dp", "sp"))
    if cfg.n_experts:
        loss = loss + cfg.aux_weight * lax.pmean(
            aux.astype(jnp.float32), ("dp", "sp")
        )
    # identical on every rank now; collapse any residual vma typing
    return lax.pmean(loss, ("pp", "tp"))


# ---------------------------------------------------------------- build API


def make_mesh_for(n_devices: int, devices=None) -> Mesh:
    """Factorize n devices onto (pp, dp, sp, tp), favoring the order
    pp≤2, tp≤2, then dp/sp — small axes everywhere so every parallelism
    mode is exercised even on an 8-device test mesh."""
    devices = devices if devices is not None else jax.devices()[:n_devices]
    shape = _factorize(n_devices)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, MESH_AXES)


def _factorize(n: int) -> Tuple[int, int, int, int]:
    pp = 2 if n % 2 == 0 and n >= 4 else 1
    rem = n // pp
    sp = 2 if rem % 2 == 0 else 1
    rem //= sp
    dp = 2 if rem % 2 == 0 else 1
    tp = rem // dp
    assert pp * dp * sp * tp == n
    return (pp, dp, sp, tp)


def data_spec() -> P:
    return P("dp", "sp")


class LoopedOutputs(NamedTuple):
    """What a looped LM's forward gives and its loss takes: every
    exit's logits, the pre-sigmoid value of every exit's gate, and the
    configuration's weight of the objective's entropy term."""

    logits: jnp.ndarray  # [T, B, L, V], compute dtype
    gates: jnp.ndarray  # [T, B, L], float32
    entropy_weight: float


def exit_distribution(gates: jnp.ndarray):
    """(q, log q), [T, ...] each, of the exit gates' pre-sigmoid values
    `gates` [T, ...]: with lambda_t = sigmoid(gates[t]),
    q_1 = lambda_1, q_t = lambda_t prod_{j<t} (1 - lambda_j), and the
    last exit takes what is left, q_T = prod_{j<T} (1 - lambda_j) (its
    own gate is not read). Formed from log-sigmoids, so a saturated
    gate gives a finite log q."""
    gates = gates.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gates[:-1]), axis=0)  # [T-1, ...]
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], axis=0)
    log_q = jnp.concatenate(
        [jax.nn.log_sigmoid(gates[:-1]) + before, stay[-1:]], axis=0
    )
    return jnp.exp(log_q), log_q


def exit_cross_entropies(logits: jnp.ndarray, targets: jnp.ndarray):
    """Next-token cross-entropy of every exit and token, float32:
    logits [T, B, L, V], targets [B, L] -> [T, B, L]."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    index = jnp.broadcast_to(targets[None, ..., None], logits.shape[:-1] + (1,))
    return logz - jnp.take_along_axis(logits, index, axis=-1)[..., 0]


def looped_exit_loss(outputs: LoopedOutputs, targets):
    """The looped LM's stage-I objective (Ouro): per token the exit
    distribution's expected cross-entropy less `entropy_weight` times
    its entropy, then the mean over tokens."""
    with jax.named_scope("exit_heads"), jax.named_scope("head"):
        ce = exit_cross_entropies(outputs.logits, targets)
        q, log_q = exit_distribution(outputs.gates)
        entropy = -jnp.sum(q * log_q, axis=0)
        return jnp.mean(
            jnp.sum(q * ce, axis=0) - outputs.entropy_weight * entropy
        )


def exit_stats(gates: jnp.ndarray) -> Dict:
    """The mean exit distribution [T] over the batch's tokens and the
    expected exit pass sum_t t q_t (1-based) — what the zoo adapter
    leaves in its `window_stats` collection."""
    q, _ = exit_distribution(lax.stop_gradient(gates))
    mean_q = jnp.mean(q.reshape(q.shape[0], -1), axis=1)
    passes = jnp.arange(1, q.shape[0] + 1, dtype=jnp.float32)
    return {"exit_q": mean_q, "expected_exit": jnp.sum(passes * mean_q)}


def _mla(cfg: TransformerConfig, lp: Dict, x: jnp.ndarray, positions):
    """Latent attention on the normed x [B, L, d] -> [B, L, d]. Queries
    are projected whole (no query latent); keys and values come from
    one normed latent c and ONE rotary key that every head shares:
    q = [q_nope | rope(q_pe)], k = [k_nope(c) | rope(k_pe)], v = v(c).
    `wkvb`'s columns are a head's k_nope then its v, head by head. The
    turning parts are laid out as `_rope` turns them, pair i =
    (x[i], x[i + D/2]); the released modelling file stores them
    interleaved and permutes to this layout before it turns them, so
    with weights of one's own the two differ by a fixed permutation of
    the turning columns of `wq` and `wkva`. With `mla_rope` off
    nothing turns: those columns enter the scores as projected."""
    from elasticdl_tpu.ops.flash_attention import attention

    b, l, _ = x.shape
    heads, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, rot = cfg.qk_nope_dim, cfg.qk_rope_dim
    freqs = yarn_frequencies(rot, cfg.rope_base, cfg.rope_yarn)
    q = (x @ lp["wq"]).reshape(b, l, heads, nope + rot)
    kva = x @ lp["wkva"]  # [B, L, rank + rot]
    latent = rms_norm(kva[..., :rank], lp["kv_norm"], cfg.norm_eps)
    kv = (latent @ lp["wkvb"]).reshape(b, l, heads, nope + cfg.v_head_dim)
    q_pe, k_pe = q[..., nope:], kva[..., None, rank:]  # k_pe: one head
    if cfg.mla_rope:
        q_pe = _rope(q_pe, positions, freqs=freqs)
        k_pe = _rope(k_pe, positions, freqs=freqs)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, l, heads, rot))], axis=-1
    )
    out = attention(
        q, k, kv[..., nope:], causal=True, scale=mla_softmax_scale(cfg)
    )
    return out.reshape(b, l, heads * cfg.v_head_dim) @ lp["wo"]


def _causal_conv(x: jnp.ndarray, taps: jnp.ndarray, bias=None) -> jnp.ndarray:
    """Depthwise over time: y_t = sum_i taps[i] * x_{t - (n - 1) + i}
    (+ `bias` [C] where one is given), zeros before the sequence's
    start. x [B, L, C], taps [n, C]."""
    n, length = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    y = sum(padded[:, i:i + length] * taps[i] for i in range(n))
    return y if bias is None else y + bias


def _scope(name: Optional[str]):
    return jax.named_scope(name) if name else contextlib.nullcontext()


def _mha(cfg: TransformerConfig, lp: Dict, x: jnp.ndarray, positions):
    """An "mha" layer's attention on the normed x [B, L, d] ->
    [B, L, d] (`_attend` without its stats)."""
    return _attend(cfg, lp, x, positions, "mha")[0]


def _head_gate(lp: Dict, x: jnp.ndarray) -> jnp.ndarray:
    """sigmoid(x . wgate_h), [B, L, heads] float32: the product
    accumulates in float32 and the sigmoid is taken there."""
    return jax.nn.sigmoid(jnp.einsum(
        "bld,hd->blh", x, lp["wgate"], preferred_element_type=jnp.float32
    ))


def _channel_gate(projected: jnp.ndarray) -> jnp.ndarray:
    """sigmoid of the gate's columns [B, L, heads x head_dim],
    float32."""
    return jax.nn.sigmoid(projected.astype(jnp.float32))


def layer_norm(x: jnp.ndarray, weight, bias, eps: float) -> jnp.ndarray:
    """LayerNorm over the feature dim, its mean and variance in
    float32, back in x's dtype."""
    y = x.astype(jnp.float32)
    y = y - jnp.mean(y, axis=-1, keepdims=True)
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return (y * weight + bias).astype(x.dtype)


def diff_lambda_init(depth) -> float:
    """Differential attention's lambda_init at a layer's published
    index: 0.8 - 0.6 exp(-0.3 depth)."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def _diff_lambda(diff: jnp.ndarray, lambda_init, hd: int):
    """exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init from the leaf
    `diff` = lq1 | lk1 | lq2 | lk2 | ..., float32."""
    lq1, lk1, lq2, lk2 = (diff[i * hd:(i + 1) * hd] for i in range(4))
    return jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lambda_init


def _diff_pair_norm(o: jnp.ndarray, weight, eps: float) -> jnp.ndarray:
    """The RMS norm over a pair's 2 x head_dim output columns,
    float32."""
    return rms_norm(o, weight, eps)


def _diff_combine(cfg: TransformerConfig, out: jnp.ndarray, diff, lambda_init):
    """The two maps' outputs [B, L, heads, 2 hd] (the pairs' first
    members, then their second) -> ([B, L, pairs, 2 hd] in out's
    dtype, lambda): o1 - lambda o2, the pair norm, times (1 -
    lambda_init); in float32."""
    hd, pairs = cfg.head_dim, out.shape[2] // 2
    diff = diff.astype(jnp.float32)
    lam = _diff_lambda(diff, lambda_init, hd)
    o = out[:, :, :pairs].astype(jnp.float32) - lam * out[:, :, pairs:].astype(
        jnp.float32
    )
    o = _diff_pair_norm(o, diff[4 * hd:], cfg.norm_eps) * (1.0 - lambda_init)
    return o.astype(out.dtype), lam


def _cross_kv(shared_kv, q: jnp.ndarray):
    """The keys and values a "cross" layer reads: `kv_layer`'s. (The
    queries are handed in for the benchmark's comparison, whose control
    gives the layer keys of its own.)"""
    return shared_kv


def _diff_attend(cfg: TransformerConfig, lp: Dict, x: jnp.ndarray, positions,
                 mixer: str, shared_kv=None):
    """Differential attention on the normed x [B, L, d] -> ([B, L, d],
    its stats, (k, v) as this layer read them). Heads come in pairs;
    `wq`'s and `wk`'s columns hold every pair's first member, then
    every pair's second, so that ONE call of the dispatcher computes
    both maps: query head c x pairs + p reads key head c x kv_pairs +
    p // group, which is its own i // group, and the value heads are
    the pairs' 2 x head_dim-wide values, once for each map. A "cross"
    layer projects queries only and reads `shared_kv`. Under `rope`
    queries and a layer's own keys turn whole (the shared keys were
    turned where they were made)."""
    from elasticdl_tpu.ops.flash_attention import attention

    b, l, _ = x.shape
    shape = cfg.attention_shape(mixer)
    hd = cfg.head_dim

    def project(w, bias, heads, width):
        y = x @ lp[w]
        if cfg.attn_bias:
            y = y + lp[bias]
        return y.reshape(b, l, heads, width)

    with _scope(shape.scope):
        q = project("wq", "bq", shape.heads, hd)
        if cfg.rope:
            q = _rope(q, positions, shape.rope_base)
        if mixer == "cross":
            k, v = _cross_kv(shared_kv, q)
        else:
            k = project("wk", "bk", cfg.kv_heads, hd)
            v = project("wv", "bv", cfg.kv_heads // 2, 2 * hd)
            if cfg.rope:
                k = _rope(k, positions, shape.rope_base)
        out = attention(
            q, k, jnp.concatenate([v, v], axis=2), causal=True,
            window=shape.window,
        )
        with jax.named_scope("diff"):
            out, lam = _diff_combine(cfg, out, lp["diff"], lp["lambda_init"])
        out = out.reshape(b, l, -1) @ lp["wo"]
        if cfg.attn_bias:
            out = out + lp["bo"]
        return out, {"diff_lambda_mean": lax.stop_gradient(lam)}, (k, v)


def _mamba1_step(cfg: TransformerConfig, lp: Dict, xs: jnp.ndarray):
    """(delta | B | C) = x W_x; dt = softplus(delta W_dt + dt_bias),
    float32 -> (dt [B, L, inner] float32, B and C [B, L, state])."""
    n, rank = cfg.ssm1_state, cfg.ssm1_dt_rank
    delta, Bm, Cm = jnp.split(xs @ lp["x_proj"], [rank, rank + n], axis=-1)
    dt = jax.nn.softplus(
        (delta @ lp["dt_proj"]).astype(jnp.float32)
        + lp["dt_bias"].astype(jnp.float32)
    )
    return dt, Bm, Cm


def _mamba1_memory(y: jnp.ndarray, gated: jnp.ndarray) -> jnp.ndarray:
    """What `memory_layer` hands on: y, the scan's output plus D x,
    BEFORE the gate (`gated` is y x SiLU(z), which it is not)."""
    return y.astype(gated.dtype)


def _mamba1_gate(y: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """y x SiLU(z) in float32 -> z's dtype."""
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)


def _mamba1(cfg: TransformerConfig, lp: Dict, x: jnp.ndarray):
    """Mamba-1 on the normed x [B, L, d] -> ([B, L, d], its stats, y):
    y, the scan's output plus D x and BEFORE the gate, in x's dtype, is
    what `memory_layer` hands on. `a_log` [state, inner], `dt_bias` and
    `D` are the float32 leaves; the step and its softplus, the rate,
    every exponential, the state and its sums, D x and the gate are
    float32 whatever `cfg.dtype` is."""
    from elasticdl_tpu.ops import selective_scan

    f32 = jnp.float32
    with jax.named_scope("in_proj"):
        xs, z = jnp.split(x @ lp["in_proj"], 2, axis=-1)
    with jax.named_scope("conv"):
        xs = jax.nn.silu(_causal_conv(xs, lp["conv"], lp["conv_bias"]))
    with jax.named_scope("step"):
        dt, Bm, Cm = _mamba1_step(cfg, lp, xs)
    with jax.named_scope("scan"):
        A = -jnp.exp(lp["a_log"].astype(f32))
        # looked up here, at the call: the controls of the benchmark's
        # comparison wrap it
        y, _last = selective_scan.selective_scan(xs, dt, A, Bm, Cm)
        y = y + lp["D"].astype(f32) * xs.astype(f32)
    with jax.named_scope("gate"):
        out = _mamba1_gate(y, z)
    with jax.named_scope("out_proj"):
        # dt A is most negative where the step is largest and the rate
        # fastest: a channel's largest step times its fastest column
        return out @ lp["out_proj"], {
            "ssm1_log_decay_min": lax.stop_gradient(jnp.min(
                jnp.max(dt, axis=(0, 1)) * jnp.min(A, axis=0)
            )),
            "ssm1_dt_mean": lax.stop_gradient(jnp.mean(dt)),
        }, _mamba1_memory(y, out)


def _gmu(cfg: TransformerConfig, lp: Dict, x: jnp.ndarray, memory):
    """The gated memory unit on the normed x [B, L, d] -> ([B, L, d],
    its stats): W_2 (memory x SiLU(W_1 x)), the gate and the product in
    float32; `memory` [B, L, inner] is `memory_layer`'s."""
    with jax.named_scope("in_proj"):
        gate = x @ lp["w1"]
    with jax.named_scope("gate"):
        gated = memory.astype(jnp.float32) * jax.nn.silu(
            gate.astype(jnp.float32)
        )
        absmax = lax.stop_gradient(jnp.max(jnp.abs(gated)))
        gated = gated.astype(x.dtype)
    with jax.named_scope("out_proj"):
        return gated @ lp["w2"], {"gmu_gate_absmax": absmax}


def _attend(cfg: TransformerConfig, lp: Dict, x: jnp.ndarray, positions,
            mixer: str):
    """Multi-head attention on the normed x [B, L, d] -> ([B, L, d],
    its stats): the queries of `mixer`'s kind of layer
    (`cfg.attention_shape`: head count, window, rotation) over
    `kv_heads` keys and values (the kernels read a key-value head where
    it lies; XLA's path has them widened in front of it), under
    `qk_norm` queries and keys normed per head, then rotated; under
    `attn_gate` each head's output times sigmoid(x . wgate_h), the
    sigmoid in float32 (stat `attn_gate_mean`, its mean); under
    `attn_channel_gate` every output channel times the sigmoid of its
    own gate, the second half of `wq`'s columns (scope `gate`; the same
    stat, the mean over channels)."""
    from elasticdl_tpu.ops.flash_attention import attention

    b, l, _ = x.shape
    shape = cfg.attention_shape(mixer)
    inner = shape.scope is not None  # `rope` and `gate` beside `swa`
    stats = {}
    with _scope(shape.scope):
        q = x @ lp["wq"]
        if cfg.attn_channel_gate:
            q, channel_gate = jnp.split(q, 2, axis=-1)
        q = q.reshape(b, l, shape.heads, cfg.head_dim)
        k = (x @ lp["wk"]).reshape(b, l, cfg.kv_heads, cfg.head_dim)
        v = (x @ lp["wv"]).reshape(b, l, cfg.kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q, k = _qk_norm(lp, q, k, cfg.norm_eps)
        with _scope(shape.turns and inner and "rope"):
            turn = dict(
                freqs=shape.rope_yarn and yarn_frequencies(
                    shape.rope_dim or cfg.head_dim, shape.rope_base,
                    shape.rope_yarn,
                ),
                rot=shape.rope_dim, factor=shape.rope_factor,
            )
            if shape.turns:
                q = _rope(q, positions, shape.rope_base, **turn)
                k = _rope(k, positions, shape.rope_base, **turn)
        out = attention(q, k, v, causal=True, window=shape.window)
        if cfg.attn_gate:
            with _scope(inner and "gate"):
                gate = _head_gate(lp, x)
                out = out * gate[..., None].astype(out.dtype)
                stats["attn_gate_mean"] = jnp.mean(gate)
        if cfg.attn_channel_gate:
            with jax.named_scope("gate"):
                gate = _channel_gate(channel_gate)
                out = out * gate.reshape(out.shape).astype(out.dtype)
                stats["attn_gate_mean"] = jnp.mean(gate)
        return out.reshape(b, l, -1) @ lp["wo"], stats


def _qk_norm(lp: Dict, q: jnp.ndarray, k: jnp.ndarray, eps: float):
    """Every query and key [B, L, H, D] RMS-normed over its D, one
    weight of D for the queries and one for the keys; in float32, as
    the weights are stored, then back to the compute dtype."""
    f32 = jnp.float32
    return (
        rms_norm(q.astype(f32), lp["q_norm"], eps).astype(q.dtype),
        rms_norm(k.astype(f32), lp["k_norm"], eps).astype(k.dtype),
    )


def _conv(cfg: TransformerConfig, lp: Dict, x: jnp.ndarray):
    """The double-gated short convolution (LFM2) on the normed x
    [B, L, d] -> ([B, L, d], the largest |c * y|): (b, c, u) =
    split3(x W_in); y = the causal depthwise taps over b * u, no bias
    and no activation; out = (c * y) W_out."""
    with jax.named_scope("shortconv"):
        with jax.named_scope("in_proj"):
            b, c, u = jnp.split(x @ lp["in_proj"], 3, axis=-1)
        with jax.named_scope("gate_conv"):
            gated = c * _causal_conv(b * u, lp["conv"])
            absmax = jnp.max(jnp.abs(gated)).astype(jnp.float32)
        with jax.named_scope("out_proj"):
            return gated @ lp["out_proj"], absmax


def _kda(cfg: TransformerConfig, lp: Dict, x: jnp.ndarray):
    """Kimi Delta Attention on the normed x [B, L, d] -> ([B, L, d],
    the most negative cumulative log-decay inside a chunk). q, k and v
    are projected, convolved over time and SiLU'd; per head q and k are
    scaled to unit length (q by d^-1/2 more); the log-decay per key
    channel is -exp(a_log) softplus(f_up f_down x + dt_bias) and the
    write strength sigmoid(wbeta x); the recurrence is `ops/kda.py`'s;
    its output is RMS-normed per head, gated by sigmoid(g_up g_down x)
    and projected. `a_log` and `dt_bias` are the float32 leaves; decay,
    lengths and the recurrence are float32 whatever `cfg.dtype` is."""
    from elasticdl_tpu.ops.kda import kda_chunked

    b, l, _ = x.shape
    heads, hd = cfg.kda_heads, cfg.kda_head_dim
    f32 = jnp.float32

    def per_head(y):
        return y.reshape(b, l, heads, hd)

    with jax.named_scope("conv"):
        q, k, v = (
            per_head(jax.nn.silu(_causal_conv(x @ lp[w], lp[c]))).astype(f32)
            for w, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v"))
        )
        q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-12) * hd**-0.5
        k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-12)
    with jax.named_scope("gates"):
        step = jax.nn.softplus(
            ((x @ lp["f_down"]) @ lp["f_up"]).astype(f32) + lp["dt_bias"].astype(f32)
        )
        g = -jnp.exp(lp["a_log"].astype(f32))[:, None] * per_head(step)
        beta = jax.nn.sigmoid((x @ lp["wbeta"].T).astype(f32))
        gate = jax.nn.sigmoid(per_head((x @ lp["g_down"]) @ lp["g_up"]))
    with jax.named_scope("scan"):
        o, log_decay_min = kda_chunked(q, k, v, g, beta, chunk=cfg.kda_chunk)
    with jax.named_scope("out"):
        o = rms_norm(o, lp["o_norm"].astype(f32), cfg.norm_eps).astype(x.dtype)
        return (o * gate).reshape(b, l, heads * hd) @ lp["wo"], log_decay_min


def _unit_length(y: jnp.ndarray) -> jnp.ndarray:
    """Every head's vector [..., D] over its length, float32."""
    return y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)


def _gdn_out_gate(z: jnp.ndarray) -> jnp.ndarray:
    """SiLU of the output gate's columns, float32."""
    return jax.nn.silu(z.astype(jnp.float32))


def _gdn_qkv(cfg: TransformerConfig, projected: jnp.ndarray, taps: jnp.ndarray):
    """The projected q | k | v [B, L, (2 kh + vh) hd] -> (q, k
    [B, L, kh, hd], v [B, L, vh, hd]) float32: the convolution, SiLU,
    and q and k scaled to unit length per head (q by d^-1/2 more)."""
    b, l, _ = projected.shape
    kh, vh, hd = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_head_dim
    qkv = jax.nn.silu(_causal_conv(projected, taps)).astype(jnp.float32)
    q, k, v = jnp.split(qkv, [kh * hd, 2 * kh * hd], axis=-1)
    q, k = (y.reshape(b, l, kh, hd) for y in (q, k))
    return (
        _unit_length(q) * hd**-0.5, _unit_length(k), v.reshape(b, l, vh, hd)
    )


def _gdn_out(cfg: TransformerConfig, o: jnp.ndarray, z: jnp.ndarray, weight):
    """The scan's output [B, L, vh, hd] float32 normed per head and
    gated by SiLU(z), in float32 -> [B, L, vh x hd] in z's dtype."""
    o = rms_norm(o, weight.astype(jnp.float32), cfg.norm_eps)
    o = o * _gdn_out_gate(z).reshape(o.shape)
    return o.astype(z.dtype).reshape(z.shape)


def _gdn(cfg: TransformerConfig, lp: Dict, x: jnp.ndarray):
    """Gated DeltaNet on the normed x [B, L, d] -> ([B, L, d], its
    stats). (q, k, v, z) = x W_qkvz; q | k | v pass ONE causal depthwise
    convolution and SiLU; per head q and k are scaled to unit length (q
    by d^-1/2 more); the write strength is sigmoid(b) and the log-decay
    -exp(a_log) softplus(a + dt_bias), ONE number a value head and
    token, (b, a) = x W_ba; value head j reads q and k of key head
    j // group; the recurrence is `ops/kda.py`'s under the scalar
    decay; its output is RMS-normed per head, gated by SiLU(z) and
    projected. `a_log`, `dt_bias` and `out_norm` are the float32
    leaves; decay, lengths, the recurrence, the norm and its gate are
    float32 whatever `cfg.dtype` is. The two elementwise stages round
    the scan are recomputed in the backward pass from the projection
    and from the scan's output (`jax.checkpoint`): at 8192 tokens their
    float32 intermediates, 8192 and 4096 wide, were 0.9 GB of a layer's
    backward pass (the v5e compiler's rehearsal, PR 52)."""
    from elasticdl_tpu.ops.kda import kda_chunked

    kh, vh, hd = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_head_dim
    f32 = jnp.float32
    with jax.named_scope("conv"):
        qkv, z = jnp.split(x @ lp["wqkvz"], [(2 * kh + vh) * hd], axis=-1)
        q, k, v = jax.checkpoint(partial(_gdn_qkv, cfg))(qkv, lp["conv"])
    with jax.named_scope("gates"):
        ba = (x @ lp["wba"].T).astype(f32)  # [B, L, 2 vh]
        beta = jax.nn.sigmoid(ba[..., :vh])
        g = -jnp.exp(lp["a_log"].astype(f32)) * jax.nn.softplus(
            ba[..., vh:] + lp["dt_bias"].astype(f32)
        )
    with jax.named_scope("scan"):
        o, log_decay_min = kda_chunked(q, k, v, g, beta, chunk=cfg.kda_chunk)
    with jax.named_scope("out"):
        o = jax.checkpoint(partial(_gdn_out, cfg))(o, z, lp["out_norm"])
        return o @ lp["wo"], {
            "gdn_log_decay_min": log_decay_min,
            "gdn_beta_mean": jnp.mean(beta),
        }


def _ssm_gate_norm(cfg: TransformerConfig, y: jnp.ndarray, z: jnp.ndarray,
                   weight: jnp.ndarray) -> jnp.ndarray:
    """The scan's output y [B, L, inner] float32 times SiLU(z), THEN
    RMS-normed over each group's inner / `ssm_groups` channels, one
    weight of the inner width; float32 -> z's dtype."""
    groups = (cfg.ssm_groups, y.shape[-1] // cfg.ssm_groups)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(
        y.reshape(y.shape[:-1] + groups), weight.reshape(groups), cfg.norm_eps
    )
    return y.reshape(z.shape).astype(z.dtype)


def _ssm_skip(D: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """D x: a head's input [B, L, H, P] times its skip D [H], float32."""
    return D[:, None] * x.astype(jnp.float32)


def _ssm_conv(cfg: TransformerConfig, xbc: jnp.ndarray, taps, bias):
    """x | B | C [B, L, inner + 2 G N] through the convolution with its
    bias and SiLU -> (x [B, L, H, P], B and C [B, L, G, N])."""
    b, l, _ = xbc.shape
    inner, gn = cfg.ssm_heads * cfg.ssm_head_dim, cfg.ssm_groups * cfg.ssm_state
    xbc = jax.nn.silu(_causal_conv(xbc, taps, bias))
    x, Bm, Cm = jnp.split(xbc, [inner, inner + gn], axis=-1)
    groups = (b, l, cfg.ssm_groups, cfg.ssm_state)
    return (
        x.reshape(b, l, cfg.ssm_heads, cfg.ssm_head_dim),
        Bm.reshape(groups), Cm.reshape(groups),
    )


def _mamba2(cfg: TransformerConfig, lp: Dict, x: jnp.ndarray):
    """Mamba-2 on the normed x [B, L, d] -> ([B, L, d], its stats).
    (z, xBC, dt) = x W_in; xBC through the causal depthwise convolution
    with its bias, then SiLU, and split x [H, P] | B [G, N] | C [G, N]
    (head j reads group j // (H / G)); dt = softplus(dt + dt_bias),
    unclamped; the log-decay dt x -exp(a_log), one number a head and
    token; y = the recurrence of `ops/ssd.py` + D x; y x SiLU(z), then
    the RMS norm over each group's channels; out = y W_out. `a_log`,
    `dt_bias`, `D` and `ssm_norm` are the float32 leaves; the step, the
    decay, the recurrence's sums and states, the gate and the norm are
    float32 whatever `cfg.dtype` is. The elementwise stages round the
    scan are recomputed in the backward pass (`jax.checkpoint`), as
    `_gdn`'s are."""
    from elasticdl_tpu.ops import ssd

    b, l, _ = x.shape
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    bc = 2 * cfg.ssm_groups * cfg.ssm_state
    f32 = jnp.float32
    with jax.named_scope("in_proj"):
        z, xbc, dt = jnp.split(
            x @ lp["in_proj"], [inner, 2 * inner + bc], axis=-1
        )
    with jax.named_scope("conv"):
        xs, Bm, Cm = jax.checkpoint(partial(_ssm_conv, cfg))(
            xbc, lp["conv"], lp["conv_bias"]
        )
    with jax.named_scope("scan"):
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
        A = -jnp.exp(lp["a_log"].astype(f32))
        # `ssd_chunked` is looked up here, at the call: the controls of
        # the benchmark's comparison wrap it
        y, log_decay_min = ssd.ssd_chunked(
            xs, dt, A, Bm, Cm, chunk=cfg.ssm_chunk
        )
        with jax.named_scope("out"):
            y = y + _ssm_skip(lp["D"].astype(f32), xs)
    with jax.named_scope("gate_norm"):
        y = jax.checkpoint(partial(_ssm_gate_norm, cfg))(
            y.reshape(b, l, inner), z, lp["ssm_norm"].astype(f32)
        )
    with jax.named_scope("out_proj"):
        return y @ lp["out_proj"], {
            "ssm_log_decay_min": log_decay_min,
            "ssm_dt_mean": lax.stop_gradient(jnp.mean(dt)),
        }


def plain_forward(cfg: TransformerConfig, params: Dict, tokens: jnp.ndarray):
    """Vectorized unsharded forward — the same math as the sharded path
    restricted to a 1-device mesh, without the machinery: `lax.scan`
    over the stacked layers, the fused-attention dispatcher
    (ops/flash_attention.attention) instead of the ring, no vma shims,
    no pipeline stage loop. Steady-state speed is IDENTICAL to the
    shard_map path on a trivial mesh (measured; XLA DCEs the no-op
    collectives) — the value is (a) a mesh-free entry point for simple
    callers (the model-zoo adapter), (b) compile time flat in depth
    where reference_forward's Python unroll grows linearly (measured
    1.5s vs 3.9s at 24 layers), (c) the flash-kernel hook. MoE layers
    use the capacity-bounded einsum dispatch with every expert local
    (parallel/moe.moe_ffn_local — same routing math as the
    expert-parallel path, no collectives). Casts params to cfg.dtype
    itself. Returns (logits, aux): aux is the summed Switch
    load-balance loss (0 for dense).

    ONE definition of the block serves every unsharded LM; the
    configuration selects the attention (`attention`), the MLP (`mlp`),
    the rotary base, the norm epsilon and the four-norm sandwich. With
    `n_loops` > 1 the stack is run that many times by an outer
    `lax.scan` that closes over the one set of stacked layer weights
    (the gradients of the passes sum into one leaf), the final norm
    closes every pass, each layer application is rematerialized, and
    the first return value is `LoopedOutputs`: all exits' logits and
    gates. With `moe_top_k` the stack is not of one shape: the
    `n_dense_layers` dense layers are scanned first, then the expert
    layers (`parallel/moe.moe_topk_held` on the experts held here),
    and aux is the summed sequence-wise balance term. Every
    configuration's operations carry `jax.named_scope`s, which a
    reader of a device trace joins to (obs/hlo_scopes.py): `embed`;
    in each layer `attention` (`mla`, `kda`, `gdn`, `conv`,
    `mamba2` > `run<i>`) and `mlp` (`moe` > `route`, `experts`,
    `shared`; a layer of `bare_layers` has no such scope; under
    `early_router` the router's product lies under `router`, in front
    of the layer's mixer); `head` round
    the final norm, the logits and
    the loss; and round those the looped LM's `looped_stack` and
    `exit_heads`."""
    return plain_forward_stats(cfg, params, tokens)[:2]


def plain_forward_stats(
    cfg: TransformerConfig, params: Dict, tokens: jnp.ndarray
):
    """`plain_forward` and, third, what the expert layers' routers did
    with this batch: `expert_tokens` [expert layers, held], the
    layers' mean `held_share`, `router_entropy` and `route_rows` (the
    length of the sorted buffer a layer took) and `route_full`, how
    many layers took the full one ({} without `moe_top_k`). The zoo
    adapter leaves it in `window_stats`."""
    from elasticdl_tpu.parallel.moe import (
        moe_ffn_local, moe_topk_held, router_logits,
    )

    stored = params
    params = jax.tree_util.tree_map(lambda a: a.astype(cfg.dtype), params)
    b, l = tokens.shape
    with jax.named_scope("embed"):
        h = params["embed"][tokens]  # [B, L, d]
    positions = jnp.arange(l)
    eps = cfg.norm_eps
    routed = bool(cfg.moe_top_k)

    def norm(h, lp, name):
        """The residual stream's norm `name` of a layer's leaves."""
        if cfg.norm == "layer":
            return layer_norm(h, lp[name], lp[name + "_bias"], eps)
        return rms_norm(h, lp[name], eps)

    def attend(mixer, lp, x, shared):
        """-> (the mixer's output, its stats, what it could hand to
        the layers behind it); `shared` is what earlier layers handed
        on."""
        if mixer == "mla":
            return _mla(cfg, lp, x, positions), {}, {}
        if mixer == "kda":
            out, log_decay_min = _kda(cfg, lp, x)
            return out, {"kda_log_decay_min": log_decay_min}, {}
        if mixer == "gdn":
            return _gdn(cfg, lp, x) + ({},)
        if mixer == "conv":
            out, gate_absmax = _conv(cfg, lp, x)
            return out, {"shortconv_gate_absmax": gate_absmax}, {}
        if mixer == "mamba2":
            return _mamba2(cfg, lp, x) + ({},)
        if mixer == "mamba1":
            out, stats, y = _mamba1(cfg, lp, x)
            return out, stats, {"memory": y}
        if mixer == "gmu":
            return _gmu(cfg, lp, x, shared["memory"]) + ({},)
        if cfg.diff_attention:
            out, stats, kv = _diff_attend(
                cfg, lp, x, positions, mixer, shared.get("shared_kv")
            )
            return out, stats, {"shared_kv": kv}
        return _attend(cfg, lp, x, positions, mixer) + ({},)

    routed_leaves, shared_leaves = _expert_leaves(cfg.mlp)

    def layer(mixer: str, experts: Optional[bool], index: int,
              shared: Dict, gives: Tuple[str, ...] = ()):
        """The scanned body of a layer of run `index` with `mixer` and
        the dense MLP, or the configuration's expert layer in its
        place, or (`experts` None) no feed-forward part at all. The
        body closes over `shared`, what earlier runs handed on, and
        its scan's `ys` carry, beside the layer's stats, what this run
        hands on itself (`gives`: "memory", "shared_kv")."""

        def body(carry, lp):
            h, aux = carry
            logits = None
            if cfg.early_router and experts and routed:
                # the scores of the layer's input: nothing the mixer
                # does reaches them
                with jax.named_scope("router"):
                    logits = router_logits(
                        h.reshape(b * l, cfg.d_model), lp["router"]
                    )
            # a state-space mixer's operations name their run as well:
            # a reader of the trace counts the passes of the scan run
            # by run (`benchmark/layer_metrics/_ssm.py`)
            with jax.named_scope(
                "attention" if mixer in ("mha", "swa", "cross") else mixer
            ), _scope(mixer in ("mamba2", "mamba1") and f"run{index}"):
                out, stats, handed = attend(
                    mixer, lp, norm(h, lp, "ln1"), shared
                )
                handed = {name: handed[name] for name in gives}
                if cfg.sandwich_norm:
                    out = rms_norm(out, lp["ln1b"], eps)
                h = h + out
            if experts is None:
                return (h, aux), (stats, handed)
            with jax.named_scope("moe" if experts and routed else "mlp"):
                x = norm(h, lp, "ln2")
                if experts and routed:
                    out, a, routing = moe_topk_held(
                        x, lp["router"],
                        tuple(lp[n] for n in routed_leaves),
                        tuple(lp[n] for n in shared_leaves)
                        if cfg.n_shared_experts else None,
                        top_k=cfg.moe_top_k, held=cfg.held,
                        scaling=cfg.routed_scaling,
                        score=cfg.moe_score, bias=lp.get("router_bias"),
                        renormalize=cfg.moe_renormalize,
                        balance=bool(cfg.aux_weight),
                        shared_gate=lp.get("sgate"),
                        kind=cfg.mlp, logits=logits,
                    )
                    stats = {**stats, **routing}
                elif experts:
                    out, a = moe_ffn_local(
                        x.reshape(b * l, cfg.d_model),
                        lp["router"],
                        lp["ew1"],
                        lp["ew2"],
                        capacity_factor=cfg.capacity_factor,
                    )
                    out = out.reshape(b, l, cfg.d_model)
                elif cfg.mlp == "swiglu":
                    out = swiglu(x, lp["wg"], lp["wu"], lp["wd"])
                else:
                    out = jax.nn.gelu(x @ lp["w1"]) @ lp["w2"]
                if cfg.sandwich_norm:
                    out = rms_norm(out, lp["ln2b"], eps)
                h = h + out
                if experts:
                    aux = aux + a
            return (h, aux), (stats, handed)

        if cfg.remat or cfg.looped:
            return _remat(body)
        return body

    def run_trees(tree):
        if cfg.mixed:
            return tree["stack"]
        return ([tree["dense"]] if cfg.n_dense_layers else []) + [tree["layers"]]

    # what decides in float32 reads the float32 leaves, not their casts:
    # the router (and its selection bias), the decay's rate and step
    trees = [
        {**cast, **{k: kept[k] for k in _FLOAT32_LEAVES if k in kept}}
        for cast, kept in zip(run_trees(params), run_trees(stored))
    ]
    runs = [r for r in cfg.runs if r[2]]
    if "kda" in cfg.mixers:  # their rates lie in one leaf, in stack order
        # behind a barrier: the compiler otherwise moves this reshape
        # to rows of 32 in front of the slice that cuts the leaf out of
        # the flat parameter vector, and views the whole vector so
        # (`_init_routed_params`)
        a_log = lax.optimization_barrier(stored["kda_a_log"]).reshape(
            -1, cfg.kda_heads
        )
        seen = 0
        for (mixer, _experts, layers), tree in zip(runs, trees):
            if mixer == "kda":
                tree["a_log"] = a_log[seen:seen + layers]
                seen += layers

    if "gdn" in cfg.mixers:  # as `kda_a_log`: [a_log | dt_bias]
        decay = lax.optimization_barrier(stored["gdn_decay"]).reshape(
            2, -1, cfg.gdn_value_heads
        )
        seen = 0
        for (mixer, _experts, layers), tree in zip(runs, trees):
            if mixer == "gdn":
                tree["a_log"] = decay[0, seen:seen + layers]
                tree["dt_bias"] = decay[1, seen:seen + layers]
                seen += layers

    if "mamba2" in cfg.mixers:  # as `kda_a_log`: [a_log | dt_bias | D]
        decay = lax.optimization_barrier(stored["ssm_decay"]).reshape(
            3, -1, cfg.ssm_heads
        )
        seen = 0
        for (mixer, _experts, layers), tree in zip(runs, trees):
            if mixer == "mamba2":
                for at, name in enumerate(("a_log", "dt_bias", "D")):
                    tree[name] = decay[at, seen:seen + layers]
                seen += layers

    if cfg.diff_attention:  # a layer's lambda_init, by its published index
        seen = 0
        for (mixer, _experts, layers), tree in zip(runs, trees):
            if mixer in ("mha", "swa", "cross"):
                tree["lambda_init"] = jnp.asarray([
                    diff_lambda_init(depth)
                    for depth in cfg.diff_depths[seen:seen + layers]
                ], jnp.float32)
            seen += layers

    def stack(carry):
        gathered, shared, first = {}, {}, 0
        for index, ((mixer, experts, layers), tree) in enumerate(
            zip(runs, trees)
        ):
            gives = tuple(
                name for name, at in (
                    ("memory", cfg.memory_layer), ("shared_kv", cfg.kv_layer)
                ) if at == first
            )
            carry, (stats, handed) = lax.scan(
                layer(mixer, experts, index, shared, gives), carry, tree
            )
            # a giving layer is a run of its own: its `ys` hold one layer
            shared = {
                **shared, **jax.tree_util.tree_map(lambda a: a[0], handed)
            }
            first += layers
            for name, value in stats.items():
                gathered.setdefault(name, []).append(value)
        h, aux = carry
        with jax.named_scope("head"):
            return norm(h, params, "ln_f"), aux, {
                name: jnp.concatenate(values)
                for name, values in gathered.items()
            }

    def head(h):
        """The logits: by the head, or by the embedding transposed."""
        if cfg.tie_embeddings:
            return h @ params["embed"].T
        return h @ params["head"]

    carry = (h, jnp.zeros((), dtype=jnp.float32 if routed else h.dtype))
    if not cfg.looped:
        h, aux, stats = stack(carry)
        if routed:
            stats = {
                "expert_tokens": stats.pop("expert_tokens"),
                **{
                    name: _OVER_LAYERS[name](value)
                    for name, value in stats.items()
                },
            }
        elif cfg.mixed:
            stats = {
                name: _OVER_LAYERS[name](value)
                for name, value in stats.items()
            }
        with jax.named_scope("head"):
            return head(h), aux, stats

    def one_pass(carry, _):
        h, aux, _stats = stack(carry)
        return (h, aux), h  # the next pass reads this pass's normed output

    with jax.named_scope("looped_stack"):
        (_, aux), exits = lax.scan(
            one_pass, carry, None, length=cfg.n_loops
        )  # exits: [T, B, L, d]
    with jax.named_scope("exit_heads"):
        gate = params["exit_gate"]
        gates = exits.astype(jnp.float32) @ gate["w"].astype(
            jnp.float32
        ) + gate["b"].astype(jnp.float32)
        with jax.named_scope("head"):
            logits = head(exits)
        return LoopedOutputs(
            logits, gates[..., 0], cfg.exit_entropy_weight
        ), aux, {}


def build_loss_fn(cfg: TransformerConfig, mesh: Mesh):
    """Returns loss(params, tokens) — tokens [B, L+1]; jit-able with
    params/data sharded over `mesh`. A single-device mesh takes the
    plain_forward fast path (identical math, no shard_map scaffolding);
    MoE included — the local einsum dispatch stands in for the
    all_to_all one."""
    from jax import shard_map

    if mesh.size == 1:

        def plain_loss(params, tokens):
            logits, aux = plain_forward(cfg, params, tokens[:, :-1])
            if cfg.looped:
                return looped_exit_loss(logits, tokens[:, 1:])
            loss = token_cross_entropy(logits, tokens[:, 1:])
            if cfg.n_experts:
                loss = loss + cfg.aux_weight * aux.astype(jnp.float32)
            return loss

        return plain_loss

    specs = param_partition_specs(cfg)

    local = partial(_local_loss, cfg)
    smapped = shard_map(
        local,
        mesh=mesh,
        in_specs=(specs, data_spec(), data_spec()),
        out_specs=P(),
    )

    def loss_fn(params, tokens):
        inputs = tokens[:, :-1]
        targets = tokens[:, 1:]
        return smapped(params, inputs, targets)

    return loss_fn


def build_forward(cfg: TransformerConfig, mesh: Mesh):
    """Returns forward(params, inputs) -> logits [B, L, V]; inputs
    [B, L] int32. Jittable; used by the single-chip compile check."""
    from jax import shard_map

    specs = param_partition_specs(cfg)

    def local(params, inputs):
        params = jax.tree_util.tree_map(lambda a: a.astype(cfg.dtype), params)
        logits, _aux = _local_forward(cfg, params, inputs)
        # replicated across pp (gpipe broadcast) and tp already; pmean
        # collapses the vma typing so out_specs P("dp","sp") is valid
        return lax.pmean(logits.astype(jnp.float32), ("pp", "tp"))

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(specs, data_spec()),
        out_specs=P("dp", "sp"),
    )


def build_train_step(cfg: TransformerConfig, mesh: Mesh, optimizer):
    """Full sharded training step: value_and_grad through the shard_map
    (vma transposition inserts the gradient psums), then the optax
    update runs under GSPMD with param-matching shardings."""
    loss_fn = build_loss_fn(cfg, mesh)

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    specs = param_partition_specs(cfg)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    # raw tokens are [B, L+1]: the odd L+1 can't shard over sp, so shard
    # the batch dim only; the shard_map's in_specs reshard the sliced
    # inputs/targets onto ("dp", "sp")
    data_sharding = NamedSharding(mesh, P("dp"))
    return jax.jit(
        step,
        in_shardings=(shardings, None, data_sharding),
        out_shardings=(shardings, None, None),
    )


def place_params(params: Dict, cfg: TransformerConfig, mesh: Mesh) -> Dict:
    specs = param_partition_specs(cfg)
    return jax.tree_util.tree_map(
        lambda a, s: jax.device_put(jnp.asarray(a), NamedSharding(mesh, s)),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, P) or isinstance(x, np.ndarray),
    )


def reference_forward(cfg: TransformerConfig, params: Dict, tokens: jnp.ndarray):
    """Unsharded single-device reference (for equivalence tests):
    the same math with loops instead of collectives. The original
    block only, as the mesh path it is held against."""
    _require_mesh_support(cfg)
    inputs = tokens
    b, l = inputs.shape
    h = jnp.asarray(params["embed"])[inputs]
    positions = jnp.arange(l)
    aux_total = 0.0
    for i in range(cfg.n_layers):
        lp = {k: jnp.asarray(v[i]) for k, v in params["layers"].items()}
        x = rms_norm(h, lp["ln1"])
        q = (x @ lp["wq"]).reshape(b, l, cfg.n_heads, cfg.head_dim)
        k = (x @ lp["wk"]).reshape(b, l, cfg.n_heads, cfg.head_dim)
        v = (x @ lp["wv"]).reshape(b, l, cfg.n_heads, cfg.head_dim)
        q, k = _rope(q, positions), _rope(k, positions)
        s = jnp.einsum("blhd,bmhd->bhlm", q, k) / math.sqrt(cfg.head_dim)
        mask = jnp.tril(jnp.ones((l, l), dtype=bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("bhlm,bmhd->blhd", p, v).reshape(b, l, -1)
        h = h + attn @ lp["wo"]
        x = rms_norm(h, lp["ln2"])
        if cfg.n_experts:
            flat = x.reshape(b * l, cfg.d_model)
            probs = jax.nn.softmax(flat @ lp["router"], axis=-1)
            eidx = jnp.argmax(probs, axis=-1)
            gate = jnp.max(probs, axis=-1)
            outs = []
            for t in range(flat.shape[0]):
                e = eidx[t]
                hh = jax.nn.gelu(flat[t] @ lp["ew1"][e])
                outs.append(gate[t] * (hh @ lp["ew2"][e]))
            h = h + jnp.stack(outs).reshape(b, l, cfg.d_model)
        else:
            h = h + jax.nn.gelu(x @ lp["w1"]) @ lp["w2"]
    h = rms_norm(h, jnp.asarray(params["ln_f"]))
    return h @ jnp.asarray(params["head"])


def reference_loss(cfg: TransformerConfig, params, tokens):
    logits = reference_forward(cfg, params, tokens[:, :-1])
    return token_cross_entropy(logits, tokens[:, 1:])
