"""Pallas TPU kernel: per-node min-plus subset convolution with top-K.

Layout choice (hardware adaptation): the engine's ``S[V, 2^m, K]`` puts K
(2..4) in the minor dim — hostile to the 8x128 VPU registers.  The kernel
operates on the transposed ``S_t[2^m, K, V]`` so nodes ride the 128-wide
lane axis and every min/add/select is a full-width vector op.  The (t,a,b)
split-pair loop is unrolled in popcount order *inside* the kernel, so one
grid step reaches full closure for its node block while the table stays in
VMEM — the jnp fallback needs ceil(log2 m) passes, each re-streaming S
through HBM.

VMEM per block: 2^m * K * BV * 4B  (m=6, K=4, BV=1024 -> 1 MiB) plus the
[K, K, BV] outer-sum scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import INF
from repro.core.spa import split_pairs


def _topk_unique_rows(cand: jnp.ndarray, k: int) -> jnp.ndarray:
    """cand: [n, BV] -> [k, BV]: per-column k smallest distinct values.

    K rounds of (column-min, mask-equal) — every op is lane-vectorized.
    """
    outs = []
    for _ in range(k):
        cur = jnp.min(cand, axis=0)                    # [BV]
        outs.append(cur)
        cand = jnp.where(cand <= cur[None, :], INF, cand)
    return jnp.stack(outs, axis=0)                     # [k, BV]


def _combine_kernel(s_ref, o_ref, *, m: int, k: int):
    """s_ref/o_ref: [2^m, K, BV] block in VMEM."""
    # Rows kept as a list of [K, BV] arrays and stacked once: Mosaic has
    # no scatter, so ``s.at[t].set`` cannot lower.
    rows = list(s_ref[...])
    for t, a, b in split_pairs(m):
        pair = rows[a][:, None, :] + rows[b][None, :, :]  # [K, K, BV]
        pair = jnp.minimum(pair, INF)
        cand = jnp.concatenate(
            [rows[t], pair.reshape(k * k, -1)], axis=0)   # [K+K^2, BV]
        rows[t] = _topk_unique_rows(cand, k)
    o_ref[...] = jnp.stack(rows)


@functools.partial(jax.jit, static_argnames=("m", "block_v", "interpret"))
def subset_combine_t(
    s_t: jax.Array, m: int, block_v: int = 512, interpret: bool = False,
) -> jax.Array:
    """s_t: [2^m, K, V] (V multiple of block_v) -> closed table."""
    n_sets, k, v = s_t.shape
    assert n_sets == 1 << m and v % block_v == 0
    grid = (v // block_v,)
    return pl.pallas_call(
        functools.partial(_combine_kernel, m=m, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((n_sets, k, block_v),
                               lambda i: (0, 0, i))],
        out_specs=pl.BlockSpec((n_sets, k, block_v), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct(s_t.shape, s_t.dtype),
        interpret=interpret,
    )(s_t)
