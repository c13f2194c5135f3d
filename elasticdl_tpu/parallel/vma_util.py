"""Varying-manual-axes (vma) helpers for JAX 0.9 shard_map typing.

Inside `shard_map`, freshly-created arrays are typed as replicated
("unvarying"); a `lax.scan` whose carry becomes device-varying then
fails type checking. These helpers promote initial carries to match the
vma of the values they will be combined with — crucially *deriving* the
axis set from example values, so the same library code works on a 1-D
sp mesh and a 4-D (pp, dp, sp, tp) mesh alike.
"""

from __future__ import annotations

import jax
from jax import lax


def vma_of(x) -> frozenset:
    return frozenset(jax.typeof(x).vma)


def match_vma(x, *examples):
    """Promote x to vary over the union of the examples' varying axes."""
    want = frozenset().union(*[vma_of(e) for e in examples])
    missing = tuple(sorted(want - vma_of(x)))
    if missing:
        x = lax.pcast(x, missing, to="varying")
    return x
