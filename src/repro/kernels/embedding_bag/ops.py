"""jit wrapper for the EmbeddingBag kernel (padding + default weights)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.embedding_bag.kernel import embedding_bag_kernel


def embedding_bag(table, ids, weights=None, mode: str = "sum",
                  block_b: int = 8, interpret: bool | None = None):
    """table [V, D]; ids [B, nnz] (-1 pad) -> [B, D]."""
    if interpret is None:
        interpret = interpret_mode()
    b, nnz = ids.shape
    pad = (-b) % block_b
    if weights is None:
        weights = jnp.ones_like(ids, jnp.float32)
    if pad:
        ids = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
        weights = jnp.pad(weights, ((0, pad), (0, 0)))
    out = embedding_bag_kernel(table, ids, weights, mode=mode,
                               block_b=block_b, interpret=interpret)
    return out[:b]
