"""Pallas kernel: ONE launch for the whole per-superstep inner loop.

The lane driver's jnp superstep lowers to a long XLA op chain per
superstep: an edge gather, ``K`` rounds of segment-min scatter
(``semiring.segment_topk_min``), a sorted-unique merge, and a
``ceil(log2 m)``-pass subset-combine scan — each op re-streaming the
``S[L, V, 2^m, K]`` table through HBM.  This kernel fuses the chain into
a single ``pallas_call`` whose grid is ``(lanes, row blocks)``:

  1. **relax reduce** — per padded-CSR virtual row, the top-K distinct
     min-plus candidates (``kernels/segment_minplus``'s reduce, inlined);
  2. **hub merge** — a segmented Hillis–Steele merge along the row axis
     folds a hub's split rows (rows of one node are contiguous and the
     layout builder never lets them straddle a block);
  3. **receive** — merge with the node's previous table (``topk_merge``);
  4. **combine** — the unrolled popcount-ordered split-pair sweep from
     ``kernels/subset_combine``, reaching full closure in one pass while
     the table stays in VMEM;
  5. **freeze** — a finished lane writes its old table back (per-lane
     freeze masking; ragged frontiers cost nothing — an empty-frontier
     lane just produces all-INF candidates).

Layout (hardware adaptation, same choice as ``subset_combine``): virtual
rows ride the minor 128-wide lane axis — ``cand[L, 2^m, dmax*K, Vv]``,
``S0/out [L, 2^m, K, Vv]`` — so every min/add/select is a full-width
vector op.  VMEM per block: ``2^m * dmax * K * BV * 4B`` for the
candidate tile (m=4, dmax=16, K=2, BV=128 -> 256 KiB), held twice
(double-buffered) next to the reduce's working set.  On a v5e's 16 MiB
of scoped VMEM, Mosaic takes a 4 MiB tile and refuses an 8 MiB one
(m=3 K=2 at BV=8192 asks for 18.1 MB), so :func:`fused_lane_step`
refuses tiles past :data:`MAX_CAND_TILE_BYTES` up front.

Bit-identity to the jnp path holds because every stage reduces the same
candidate multiset with the same distinct-top-K semantics: the combine
dependency graph is acyclic in popcount, so the one-sweep closure equals
the jnp scan's ``ceil(log2 m)``-pass fixpoint, float rounding included
(each candidate is a single f32 add of fixpoint values on both paths).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import INF
from repro.core.spa import split_pairs

# Largest candidate tile (one block's f32[2^m, dmax*K, BV]) the kernel
# takes: twice this, plus the reduce's temporaries, fits v5e's 16 MiB of
# scoped VMEM.
MAX_CAND_TILE_BYTES = 4 << 20


def _topk_distinct(cand: jnp.ndarray, k: int, axis: int) -> jnp.ndarray:
    """K rounds of (min, mask-equal) along ``axis``: the k smallest
    *distinct* values, sorted ascending, INF-padded — exactly
    ``semiring.segment_topk_min``'s per-cell semantics, vectorized."""
    outs = []
    for _ in range(k):
        cur = jnp.minimum(jnp.min(cand, axis=axis), INF)
        outs.append(cur)
        cand = jnp.where(cand <= jnp.expand_dims(cur, axis), INF, cand)
    return jnp.stack(outs, axis=axis)


def _merge2(a: jnp.ndarray, b: jnp.ndarray, k: int) -> jnp.ndarray:
    """topk_merge of two [..., K, BV] tables along the K axis."""
    return _topk_distinct(jnp.concatenate([a, b], axis=-2), k, axis=-2)


def _lane_step_kernel(seg_ref, done_ref, cand_ref, s0_ref, out_ref,
                      *, m: int, k: int, span: int):
    """One (lane, row-block) grid step.

    seg_ref:  i32[1, BV]   node id per virtual row (-1 on pad rows)
    done_ref: i32[L]       every lane's freeze flag, whole in SMEM (a
                           (1, 1) VMEM block of i32[L, 1] breaks the
                           TPU's (8, 128) tiling rule)
    cand_ref: f32[1, 2^m, dmax*K, BV]  min-plus candidates
    s0_ref:   f32[1, 2^m, K, BV]       pre-relax table, gathered per row
    out_ref:  f32[1, 2^m, K, BV]       post-combine table (valid at each
                                       node's tail row)
    """
    cand = cand_ref[0]                              # [F, C, BV]
    s0 = s0_ref[0]                                  # [F, K, BV]
    seg = seg_ref[...]                              # [1, BV] (2-D: Mosaic
    # cannot shift a 1-D vector by a whole 128-lane tile)

    # 1) per-row relax reduce: top-K distinct over the candidate axis.
    r = _topk_distinct(cand, k, axis=1)             # [F, K, BV]

    # 2) segmented hub merge along rows.  The merge is associative and
    #    idempotent, so an inclusive Hillis–Steele scan leaves the full
    #    per-node merge at each segment's LAST row (the tail row the
    #    host gathers).  Pad rows (seg == -1) never join a segment.
    #    Shifts below ``span`` (the most rows any node holds) reach every
    #    segment's first row.
    shift = 1
    while shift < span:
        prev = jnp.concatenate(
            [jnp.full(r.shape[:-1] + (shift,), INF, r.dtype),
             r[..., :-shift]], axis=-1)
        pseg = jnp.concatenate(
            [jnp.full((1, shift), -2, seg.dtype), seg[:, :-shift]], axis=1)
        same = (seg == pseg) & (seg >= 0)           # [1, BV]
        r = jnp.where(same[None], _merge2(r, prev, k), r)
        shift *= 2

    # 3) receive: merge what arrived with the node's previous table.
    s = _merge2(r, s0, k)                           # [F, K, BV]

    # 4) subset-combine sweep (popcount order -> closure in one pass).
    #    The table is held as a list of [K, BV] rows and stacked once:
    #    Mosaic has no scatter, so ``s.at[t].set`` cannot lower.
    rows = list(s)
    for t, a, b in split_pairs(m):
        pair = rows[a][:, None, :] + rows[b][None, :, :]  # [K, K, BV]
        pair = jnp.minimum(pair, INF)
        cand_t = jnp.concatenate(
            [rows[t], pair.reshape(k * k, -1)], axis=0)  # [K+K^2, BV]
        rows[t] = _topk_distinct(cand_t, k, axis=0)
    s = jnp.stack(rows)

    # 5) per-lane freeze: a finished lane keeps its pre-step table.
    frozen = done_ref[pl.program_id(0)] != 0
    out_ref[0] = jnp.where(frozen, s0, s)


@functools.partial(jax.jit,
                   static_argnames=("m", "block_v", "span", "interpret"))
def fused_lane_step(
    cand_t: jax.Array,   # f32[L, 2^m, dmax*K, Vv]
    s0_t: jax.Array,     # f32[L, 2^m, K, Vv]
    seg: jax.Array,      # i32[1, Vv]
    done: jax.Array,     # i32[L]
    m: int,
    block_v: int = 128,
    span: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """The fused superstep body as ONE pallas launch over
    ``grid = (lanes, Vv / block_v)``.  ``span``: the most virtual rows
    one node holds (default ``block_v``), which bounds the hub-merge
    scan.  Returns f32[L, 2^m, K, Vv]."""
    lanes, n_sets, c, vv = cand_t.shape
    k = s0_t.shape[2]
    assert n_sets == 1 << m and vv % block_v == 0
    span = block_v if span is None else span
    assert 1 <= span <= block_v
    tile = n_sets * c * block_v * cand_t.dtype.itemsize
    if tile > MAX_CAND_TILE_BYTES:
        raise ValueError(
            f"fused lane step: a {block_v}-row block at m={m}, "
            f"{c} candidates per row needs a {tile:,} B candidate tile in "
            f"VMEM, over the {MAX_CAND_TILE_BYTES:,} B the kernel takes; "
            f"the graph's largest hub sets the block (use backend='jnp')")
    grid = (lanes, vv // block_v)
    return pl.pallas_call(
        functools.partial(_lane_step_kernel, m=m, k=k, span=span),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_v), lambda l, i: (0, i)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n_sets, c, block_v), lambda l, i: (l, 0, 0, i)),
            pl.BlockSpec((1, n_sets, k, block_v), lambda l, i: (l, 0, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, n_sets, k, block_v),
                               lambda l, i: (l, 0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((lanes, n_sets, k, vv), cand_t.dtype),
        interpret=interpret,
    )(seg, done, cand_t, s0_t)
