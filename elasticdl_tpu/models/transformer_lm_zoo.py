"""Model-zoo entry for the flagship transformer LM.

This makes `parallel/` + `models/transformer_lm.py` a full framework
citizen (VERDICT r2 weak #6): the same parameter pytree that
`transformer_lm.build_train_step` shards over a ("pp","dp","sp","tp")
mesh here trains through the elastic PS loop — master/main.py,
dispatcher tasks over token RecordIO shards, gradient/delta transport,
checkpoints, eval service. No reference equivalent (the 2019 reference
has no attention model); the spec contract mirrors its model zoo
(e.g. model_zoo/cifar10_functional_api, reference model_helper.py:79-125).

Deployment shape (SURVEY §7.1): each gRPC worker is a TPU-VM host —
data parallelism *between* hosts rides the PS protocol, and *within* a
host the 4-axis mesh path (`transformer_lm.build_train_step`) drives
the local chips. In single-chip tests/CI this adapter's unsharded
forward is the whole step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.common.constants import WINDOW_STATS
from elasticdl_tpu.models.record_codec import decode_token_records
from elasticdl_tpu.models.transformer_lm import (
    LoopedOutputs,
    TransformerConfig,
    exit_stats,
    init_params,
    looped_exit_loss,
    plain_forward_stats,
    token_cross_entropy,
)


class TransformerLM:
    """Duck-typed flax-module adapter (init/apply) over the functional
    transformer, so the worker's generic step builder can drive it."""

    def __init__(self, **cfg_kwargs):
        self.cfg = TransformerConfig(**cfg_kwargs)

    def init(self, rng, tokens):
        seed = int(np.asarray(jax.random.key_data(rng)).ravel()[-1]) & 0x7FFFFFFF
        params = init_params(np.random.default_rng(seed), self.cfg)
        if self.cfg.looped:
            return {
                "params": params,
                WINDOW_STATS: {
                    "exit_q": np.zeros((self.cfg.n_loops,), np.float32),
                    "expected_exit": np.zeros((), np.float32),
                },
            }
        if self.cfg.moe_top_k:
            layers = sum(
                layers for _mixer, experts, layers in self.cfg.runs if experts
            )
            stats = {
                "expert_tokens": np.zeros(
                    (layers, self.cfg.held[1]), np.float32
                ),
                "held_share": np.zeros((), np.float32),
                "router_entropy": np.zeros((), np.float32),
                "route_rows": np.zeros((), np.float32),
                "route_full": np.zeros((), np.float32),
            }
            # what a layer kind adds to the routing's three
            if self.cfg.moe_score == "sigmoid":
                stats["router_bias_absmax"] = np.zeros((), np.float32)
            if "kda" in self.cfg.mixers:
                stats["kda_log_decay_min"] = np.zeros((), np.float32)
            if "conv" in self.cfg.mixers:
                stats["shortconv_gate_absmax"] = np.zeros((), np.float32)
            if "gdn" in self.cfg.mixers:
                stats["gdn_log_decay_min"] = np.zeros((), np.float32)
                stats["gdn_beta_mean"] = np.zeros((), np.float32)
            if "mamba2" in self.cfg.mixers:
                stats["ssm_log_decay_min"] = np.zeros((), np.float32)
                stats["ssm_dt_mean"] = np.zeros((), np.float32)
            if self.cfg.attn_gate or self.cfg.attn_channel_gate:
                stats["attn_gate_mean"] = np.zeros((), np.float32)
            if self.cfg.shared_expert_gate:
                stats["shared_gate_mean"] = np.zeros((), np.float32)
            return {"params": params, WINDOW_STATS: stats}
        stats = self._dense_stack_stats()
        if stats:
            return {"params": params, WINDOW_STATS: stats}
        return {"params": params}

    def _dense_stack_stats(self):
        """The health readings of a mixed stack with no expert layer:
        what its mixers leave in `window_stats` ({} for any other
        model)."""
        if self.cfg.n_experts or not self.cfg.mixed:
            return {}
        names = []
        if "mamba1" in self.cfg.mixers:
            names += ["ssm1_log_decay_min", "ssm1_dt_mean"]
        if "gmu" in self.cfg.mixers:
            names.append("gmu_gate_absmax")
        if self.cfg.diff_attention:
            names.append("diff_lambda_mean")
        return {name: np.zeros((), np.float32) for name in names}

    def apply(self, variables, tokens, mutable=None):
        # the vectorized scan-over-layers fast path for dense AND MoE
        # (capacity-bounded einsum dispatch, parallel/moe.moe_ffn_local).
        # MoE configs return (logits, aux): the Switch load-balance
        # term must reach loss() or top-1 routed experts train with no
        # balance regularizer on the PS runtime and collapse on longer
        # runs (ADVICE r4) — `loss`/`eval_metrics_fn` below unpack the
        # pair, mirroring the mesh path's build_loss_fn
        # (transformer_lm.py:243-253).
        logits, aux, stats = plain_forward_stats(
            self.cfg, variables["params"], tokens
        )
        if self.cfg.looped:
            # all T exits and gates go to loss(); the step's mean exit
            # distribution is the new state of the non-trainable
            # collection, which a training step asks for (`mutable`)
            if mutable:
                return logits, {WINDOW_STATS: exit_stats(logits.gates)}
            return logits
        if self.cfg.moe_top_k and mutable:
            # what the routers did with this batch, as the looped LM
            # leaves its exit distribution
            return (logits, self.cfg.aux_weight * aux), {WINDOW_STATS: stats}
        if self.cfg.n_experts:
            return logits, self.cfg.aux_weight * aux
        names = self._dense_stack_stats() if mutable else ()
        if names:
            return logits, {WINDOW_STATS: {n: stats[n] for n in names}}
        return logits


def sambay_layers(n_published: int, held=None):
    """A decoder-hybrid-decoder stack's (SambaY's) layers as
    `TransformerConfig` settings, from the PUBLISHED depth and the
    layers held here, `held` = (first, count) of the published ones
    (None: all). Published layer i's mixer: up to the middle, N / 2,
    "mamba1" where i is even and windowed attention "swa" where it is
    odd, the middle one also giving the memory; N / 2 + 1 full
    attention "mha" that also gives the shared keys and values; behind
    it "gmu" (even) and "cross" (odd) that read them. -> layer_types,
    diff_depths (each held layer's published index), memory_layer and
    kv_layer (their index among the held layers, None where not held:
    a cut that holds a reader without its feeder is refused when the
    parameters are built)."""
    first, count = held or (0, n_published)
    half = n_published // 2
    if half % 2 or not 0 <= first <= first + count <= n_published:
        raise ValueError(
            f"{n_published} published layers, held {held}: the middle "
            "layer is a state-space one (N / 2 even) and the held layers "
            "lie inside the stack"
        )

    def mixer(i):
        if i <= half:
            return "swa" if i % 2 else "mamba1"
        if i == half + 1:
            return "mha"
        return "cross" if i % 2 else "gmu"

    depths = tuple(range(first, first + count))

    def among_held(i):
        return i - first if i in depths else None

    return dict(
        layer_types=tuple(mixer(i) for i in depths),
        diff_depths=depths,
        memory_layer=among_held(half),
        kv_layer=among_held(half + 1),
    )


def custom_model(**model_params):
    # sized so CI trains it in seconds; override via --model_params
    # (e.g. "d_model=512,n_layers=8,vocab=32000")
    defaults = dict(vocab=128, d_model=64, n_heads=4, d_ff=128, n_layers=2)
    defaults.update(model_params)
    return TransformerLM(**defaults)


def dataset_fn(records, mode):
    tokens = decode_token_records(records)  # [B, T+1] int32
    return tokens[:, :-1], tokens[:, 1:].astype(np.int32)


def _split_outputs(outputs):
    """(logits, weighted_aux) for MoE configs, (logits, 0) for dense;
    a looped configuration is judged by its last exit."""
    if isinstance(outputs, LoopedOutputs):
        outputs = outputs.logits[-1]
    if isinstance(outputs, tuple):
        return outputs
    return outputs, jnp.zeros((), dtype=jnp.float32)


def loss(outputs, labels):
    if isinstance(outputs, LoopedOutputs):
        return looped_exit_loss(outputs, labels)
    logits, aux = _split_outputs(outputs)
    return token_cross_entropy(logits, labels) + aux.astype(jnp.float32)


def optimizer():
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adam(1e-3),
    )


def eval_metrics_fn(predictions, labels):
    logits, _aux = _split_outputs(predictions)
    ce = token_cross_entropy(logits, labels)
    acc = jnp.mean(jnp.argmax(logits, axis=-1) == labels)
    return {"cross_entropy": ce, "accuracy": acc, "perplexity": jnp.exp(ce)}
