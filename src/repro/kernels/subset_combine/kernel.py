"""Pallas TPU kernel: per-node min-plus subset convolution with top-K.

Layout choice (hardware adaptation): the engine's ``S[V, 2^m, K]`` puts K
(2..4) in the minor dim — hostile to the 8x128 VPU registers.  The kernel
operates on the transposed ``S_t[2^m, K, V]`` so nodes ride the 128-wide
lane axis and every min/add/select is a full-width vector op.  The (t,a,b)
split-pair loop runs in popcount order *inside* the kernel, so one grid
step reaches full closure for its node block while the table stays in
VMEM — the jnp fallback needs ceil(log2 m) passes, each re-streaming S
through HBM.  The loop is a ``fori_loop`` over a pair table in SMEM with
the set rows indexed dynamically (:func:`combine_sweep`, shared with the
fused lane superstep): unrolled, the 301 pairs of m = 6 at K = 10 took
Mosaic minutes to compile.

VMEM per block: 2^m * K * BV * 4B  (m=6, K=4, BV=1024 -> 1 MiB) plus the
[K, K, BV] outer-sum scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import INF
from repro.core.spa import split_pairs


def topk_distinct(cand: jnp.ndarray, k: int, axis: int) -> jnp.ndarray:
    """K rounds of (min, mask-equal) along ``axis``: the k smallest
    *distinct* values, sorted ascending, INF-padded — exactly
    ``semiring.segment_topk_min``'s per-cell semantics, vectorized."""
    outs = []
    for _ in range(k):
        cur = jnp.minimum(jnp.min(cand, axis=axis), INF)
        outs.append(cur)
        cand = jnp.where(cand <= jnp.expand_dims(cur, axis), INF, cand)
    return jnp.stack(outs, axis=axis)


def pair_table(m: int) -> jax.Array:
    """:func:`split_pairs` flattened to ``i32[3 P]`` (``t, a, b`` per
    pair), for the sweep's SMEM operand; one never-read entry at m = 1,
    where there is no pair."""
    return jnp.asarray(np.asarray(split_pairs(m) or ((0, 0, 0),),
                                  np.int32).reshape(-1))


def combine_sweep(tab_ref, pairs_ref, n_pairs: int, k: int) -> None:
    """The min-plus subset convolution of a ``[2^m, K, BV]`` table in
    VMEM, in place: ``tab[t] <- topk(tab[t] ∪ tab[a] ⊕ tab[b])`` for the
    ``n_pairs`` split pairs of ``pairs_ref`` (:func:`pair_table`, in
    SMEM) in popcount order, which reaches the closure in one sweep.  A
    loop over the table with the set rows indexed dynamically: the
    program does not grow with the pair count (301 pairs at m = 6)."""

    def pair(p, carry):
        t = pairs_ref[3 * p]
        a, b = tab_ref[pairs_ref[3 * p + 1]], tab_ref[pairs_ref[3 * p + 2]]
        prod = jnp.minimum(a[:, None, :] + b[None, :, :], INF)  # [K, K, BV]
        tab_ref[t] = topk_distinct(
            jnp.concatenate([tab_ref[t], prod.reshape(k * k, -1)], axis=0),
            k, axis=0)
        return carry

    jax.lax.fori_loop(0, n_pairs, pair, 0)


def _combine_kernel(pairs_ref, s_ref, o_ref, *, k: int, n_pairs: int):
    """s_ref/o_ref: [2^m, K, BV] block in VMEM; the sweep runs in o_ref."""
    o_ref[...] = s_ref[...]
    combine_sweep(o_ref, pairs_ref, n_pairs, k)


@functools.partial(jax.jit, static_argnames=("m", "block_v", "interpret"))
def subset_combine_t(
    s_t: jax.Array, m: int, block_v: int = 512, interpret: bool = False,
) -> jax.Array:
    """s_t: [2^m, K, V] (V multiple of block_v) -> closed table."""
    n_sets, k, v = s_t.shape
    assert n_sets == 1 << m and v % block_v == 0
    grid = (v // block_v,)
    return pl.pallas_call(
        functools.partial(_combine_kernel, k=k,
                          n_pairs=len(split_pairs(m))),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((n_sets, k, block_v),
                               lambda i: (0, 0, i))],
        out_specs=pl.BlockSpec((n_sets, k, block_v), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct(s_t.shape, s_t.dtype),
        interpret=interpret,
    )(pair_table(m), s_t)
