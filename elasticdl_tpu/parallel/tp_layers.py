"""Tensor-parallel building blocks (manual SPMD, Megatron-style).

No reference equivalent (SURVEY §2.10: TP absent upstream; provided
natively by the TPU stack). These helpers are called inside
`shard_map` with a `tp` mesh axis:

- column parallel: weight sharded on the output dim; no communication
  on the forward (each rank produces its slice of the features);
- row parallel: weight sharded on the input dim; forward ends with a
  `psum` over tp that reassembles the full output — the single
  all-reduce per (attention|MLP) block that rides the innermost ICI
  axis (scaling-book layout: tp innermost).

The pair composes: column(W1) -> pointwise -> row(W2) needs exactly one
all-reduce, and autodiff through the psum yields the mirrored
all-reduce on the backward pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def column_parallel(x: jnp.ndarray, w_local: jnp.ndarray) -> jnp.ndarray:
    """x: [..., d_in] replicated; w_local: [d_in, d_out/tp] local shard
    -> [..., d_out/tp] local output slice. No collective."""
    return x @ w_local


def row_parallel(
    x_local: jnp.ndarray, w_local: jnp.ndarray, axis_name: str
) -> jnp.ndarray:
    """x_local: [..., d_in/tp] local slice; w_local: [d_in/tp, d_out]
    -> [..., d_out] full output via one tp all-reduce."""
    return lax.psum(x_local @ w_local, axis_name)


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm over the feature dim (replicated weight)."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * weight


def swiglu(x: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray, wd: jnp.ndarray) -> jnp.ndarray:
    """The gated MLP wd(silu(wg x) * wu x), no bias: a dense layer's, a
    looped layer's and a shared expert's alike."""
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd
