"""Smallest-Possible-Answer (SPA) estimation and sound exit bounds.

Paper Sec. 5.4: when traversal is stopped early (message budget), a dynamic
program over keyword-set *covers* estimates the smallest answer weight that
could still be discovered; the ratio best-found / SPA is the reported
SPA-ratio.  Paper Sec. 6 (Theorem 1) stops BFS via Fagin's argument once the
estimated next-superstep path-lengths exceed those in the current top-K.

This module provides:

- ``spa_cover_dp``   — the paper's cover DP over estimated path-lengths.
- ``nu_lower_bound`` — a *provably sound* per-keyword-set lower bound on any
  value that can newly appear at any node in a future superstep, for the
  dense re-fire semantics of this engine (see DESIGN.md Sec. 5).  A new
  answer is a newly-appearing full-set value, so BFS may stop once
  ``nu[full] >= W_K``.

All DPs are over the 2^m keyword-set lattice (m <= ~6) and cost nothing
next to the graph-sized work.  ``nu_lower_bound`` runs inside every
compiled superstep, so it is one vectorized step per popcount level
(:func:`pair_levels`), not one per split pair (301 at m = 6);
``spa_cover_dp`` runs on the host, on forced exits only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import INF


@functools.lru_cache(maxsize=None)
def split_pairs(m: int) -> tuple[tuple[int, int, int], ...]:
    """All (t, a, b) with a ⊎ b = t, a < b, nonempty — in popcount(t) order."""
    pairs = []
    masks = sorted(range(1, 1 << m), key=lambda t: (bin(t).count("1"), t))
    for t in masks:
        a = (t - 1) & t
        while a:
            b = t ^ a
            if a < b:
                pairs.append((t, a, b))
            a = (a - 1) & t
    return tuple(pairs)


@functools.lru_cache(maxsize=None)
def submasks(u: int) -> tuple[int, ...]:
    """All nonempty submasks of u."""
    out, s = [], u
    while s:
        out.append(s)
        s = (s - 1) & u
    return tuple(out)


def nu_lower_bound(
    g: jax.Array, e_min: jax.Array, m: int
) -> jax.Array:
    """Lower bound ``nu[t]`` on any value for keyword-set ``t`` that first
    appears at some node in a superstep after the current one.

    ``g[t]``: global minimum value for ``t`` seen anywhere so far (INF if
    never seen).  New values arise by (i) arrival over an edge — at least
    ``g[t] + e_min`` — or (ii) a combine with at least one locally-new input
    — at least ``min(nu[a]+g[b], g[a]+nu[b], nu[a]+nu[b])`` over splits.
    """
    nu = jnp.minimum(g + e_min, INF)
    nu = nu.at[0].set(INF)
    # A pair reads sets of lower popcount only, so each popcount level is
    # one vectorized min over its pairs: the program grows with m, not
    # with the 3^m pairs.
    for t_ids, a_ids, b_ids in pair_levels(m):
        cand = jnp.minimum(
            jnp.minimum(nu[a_ids] + g[b_ids], g[a_ids] + nu[b_ids]),
            nu[a_ids] + nu[b_ids])
        nu = nu.at[t_ids].min(jnp.minimum(cand, INF))
    return nu


@functools.lru_cache(maxsize=None)
def pair_levels(m: int) -> tuple[tuple[np.ndarray, np.ndarray, np.ndarray],
                                 ...]:
    """:func:`split_pairs` grouped by the popcount of ``t``: one
    ``(t, a, b)`` triple of index arrays per level, levels ascending."""
    levels: dict[int, list] = {}
    for t, a, b in split_pairs(m):
        levels.setdefault(bin(t).count("1"), []).append((t, a, b))
    return tuple(tuple(np.asarray(col, np.int32) for col in zip(*levels[p]))
                 for p in sorted(levels))


def spa_cover_dp(shat: jax.Array, m: int) -> jax.Array:
    """Paper Sec. 5.4 DP: cheapest cover of the full keyword set by
    keyword-sets priced at ``shat`` (estimated next-superstep path-lengths).

    ``cost[U] = min(shat[U], min_{T ⊂ U} shat[T] + cost[U \\ T])``; returns
    ``cost[full]`` — the smallest possible answer weight by further traversal.
    """
    n = 1 << m
    cost = jnp.minimum(shat, INF)
    cost = cost.at[0].set(0.0)
    # Popcount-ordered relaxation: covers may overlap in the paper's wording
    # ("collectively contain all keywords"), so U \ T with T any submask.
    order = sorted(range(1, n), key=lambda t: (bin(t).count("1"), t))
    for u in order:
        best = cost[u]
        for t in submasks(u):
            if t == u:
                continue
            best = jnp.minimum(best, jnp.minimum(shat[t], INF) + cost[u ^ t])
        cost = cost.at[u].set(jnp.minimum(best, INF))
    return cost[(1 << m) - 1]


def spa_ratio(best_found: jax.Array, spa: jax.Array) -> jax.Array:
    """Paper Fig. 12: degree of approximation on forced early exit.

    Returns best_found / spa (>= 1 when optimality is unproven).  Per the
    paper's convention, returns 0 when the answer is proven optimal —
    including the case spa >= best_found, where further traversal cannot
    beat the best answer already found.
    """
    return jnp.where(
        (best_found >= INF) | (spa <= 0.0) | (spa >= INF),
        jnp.float32(jnp.inf),
        jnp.where(spa >= best_found, 0.0, best_found / spa),
    )
