"""Top-K min-plus lattice operations.

The paper keeps, at every node and for every keyword-set ``k ⊆ Q``, the top-K
best partial-answer path-lengths (the ``S_K`` structure, Sec. 4/5.1).  On TPU
we realize ``S_K`` as a dense tensor ``S[V, 2^m, K]`` whose last axis is a
*sorted, duplicate-free, INF-padded* K-vector.  All DKS dataflow is then
algebra over this lattice:

- ``topk_merge``      — join of two K-vectors (Pregel "receive messages")
- ``outer_combine``   — min-plus product of two K-vectors (local-tree combine)
- ``segment_topk_min``— top-K min-reduce by segment id (message scatter)

Duplicate-free matters: Pregel vertices resend their whole table whenever
active, so the merge must be *idempotent* (merging the same table twice is a
no-op).  We therefore keep top-K **distinct weights** — this also implements
the paper's duplicate-answer removal at the aggregator (Sec. 4, Step 5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import INF


def sorted_unique_k(x: jax.Array, k: int) -> jax.Array:
    """Sort ascending along the last axis, drop duplicate values, pad with INF,
    and keep the first ``k`` entries.

    ``x``: (..., n) with n >= k.  Returns (..., k).
    """
    x = jnp.sort(x, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros_like(x[..., :1], dtype=bool), x[..., 1:] == x[..., :-1]],
        axis=-1,
    )
    x = jnp.where(dup, INF, x)
    x = jnp.sort(x, axis=-1)
    return x[..., :k]


def topk_merge(a: jax.Array, b: jax.Array) -> jax.Array:
    """Merge two sorted-unique K-vectors into one (idempotent lattice join)."""
    k = a.shape[-1]
    return sorted_unique_k(jnp.concatenate([a, b], axis=-1), k)


def outer_combine(a: jax.Array, b: jax.Array) -> jax.Array:
    """Min-plus product: all pairwise sums of two K-vectors, reduced to the
    top-K distinct sums.  This is the paper's combination of two disjoint
    keyword-set partial answers at a node ((1+2K)^m analysis, Sec. 5.1).

    ``a``, ``b``: (..., K) -> (..., K).
    """
    k = a.shape[-1]
    s = a[..., :, None] + b[..., None, :]
    s = jnp.minimum(s, INF)  # saturate so INF+x does not overflow usefully
    return sorted_unique_k(s.reshape(*s.shape[:-2], k * k), k)


def segment_topk_min(
    values: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    k: int,
    pooled: bool = False,
) -> jax.Array:
    """Exact per-segment top-K smallest *distinct* values.

    ``values``: (N, ...F) candidate values; ``segment_ids``: (N,) int32.
    Returns (num_segments, ...F, k), sorted-unique-INF-padded.
    ``pooled``: the last axis of ``values`` holds further candidates of
    the same (row, feature) cell (``values``: (N, ...F, P)), reduced
    along with the rows — the same result as folding it into the row
    axis, without the reshape, which on TPU costs minutes of compile at
    graph widths.

    Implementation: K rounds of (segment-min -> winner masking).  Each round
    extracts one distinct minimum per (segment, feature) cell; every candidate
    equal to the extracted minimum is masked (distinct-weight semantics), so
    K rounds suffice and the result is duplicate-free by construction.
    """
    vals = values
    outs = []
    for _ in range(k):
        cur = jax.ops.segment_min(
            vals, segment_ids, num_segments=num_segments,
            indices_are_sorted=False, unique_indices=False,
        )
        if pooled:
            cur = jnp.min(cur, axis=-1)
        cur = jnp.minimum(cur, INF)
        outs.append(cur)
        # Mask every candidate equal to its segment's extracted minimum.
        won = cur[segment_ids]
        if pooled:
            won = won[..., None]
        vals = jnp.where(vals <= won, INF, vals)
    out = jnp.stack(outs, axis=-1)
    return out


def smallest_k_2d(x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """The ``k`` smallest entries of ``x[..., N, M]`` over its last two
    axes, ordered by (value, row-major flat index): exactly what
    ``lax.top_k`` of the negated ``x.reshape(..., N * M)`` selects, ties
    at the lower index first.  Returns ``(values[..., k], flat
    indices[..., k])``.

    ``k`` rounds of min-reductions instead of that reshape: on TPU,
    flattening a narrow minor axis (``M`` = K slots) ahead of ``top_k``
    costs minutes of compile at graph widths.
    """
    n, m = x.shape[-2:]
    flat = jnp.arange(n * m, dtype=jnp.int32).reshape(n, m)
    taken = jnp.zeros(x.shape, bool)
    vals, idxs = [], []
    for _ in range(k):
        free = jnp.where(taken, jnp.inf, x)
        cur = jnp.min(free, axis=(-2, -1))
        hit = ~taken & (x == cur[..., None, None])
        i = jnp.min(jnp.where(hit, flat, n * m), axis=(-2, -1))
        taken = taken | (flat == i[..., None, None])
        vals.append(cur)
        idxs.append(i)
    return jnp.stack(vals, axis=-1), jnp.stack(idxs, axis=-1)


def bump_to_inf(x: jax.Array, thresh: float = INF * 0.5) -> jax.Array:
    """Saturate any value that drifted past thresh back to exactly INF."""
    return jnp.where(x >= thresh, INF, x)
