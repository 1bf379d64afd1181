"""Pallas kernel: ONE launch for the whole per-superstep inner loop.

The lane driver's jnp superstep lowers to a long XLA op chain per
superstep: an edge gather, ``K`` rounds of segment-min scatter
(``semiring.segment_topk_min``), a sorted-unique merge, and a
``ceil(log2 m)``-pass subset-combine scan — each op re-streaming the
``S[L, V, 2^m, K]`` table through HBM.  This kernel fuses the chain into
a single ``pallas_call`` whose grid is ``(lanes, row blocks)``, with an
innermost axis of keyword-set chunks where the candidate tile is too
large for VMEM whole (:func:`set_chunks`):

  1. **relax reduce** — per padded-CSR virtual row, the top-K distinct
     min-plus candidates (``kernels/segment_minplus``'s reduce, inlined),
     per set chunk;
  2. **hub merge** — a segmented Hillis–Steele merge along the row axis
     folds a hub's split rows (rows of one node are contiguous and the
     layout builder never lets them straddle a block), per set chunk,
     into a VMEM scratch table;
  3. **receive** — merge with the node's previous table (``topk_merge``);
  4. **combine** — the popcount-ordered split-pair sweep of
     ``kernels/subset_combine`` (a loop over a pair table in SMEM),
     reaching full closure in one pass while the table stays in VMEM;
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
(m=3 K=2 at BV=8192 asks for 18.1 MB), so :func:`set_chunks` splits the
sets until a tile fits :data:`MAX_CAND_TILE_BYTES` (two chunks at m=6,
K=10, BV=128), and :func:`fused_lane_step` refuses up front only a block
where one set's tile alone does not.

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
from repro.kernels.subset_combine.kernel import (combine_sweep, pair_table,
                                                 topk_distinct)

# Largest candidate tile (one block's f32[2^m, dmax*K, BV]) the kernel
# takes: twice this, plus the reduce's temporaries, fits v5e's 16 MiB of
# scoped VMEM.
MAX_CAND_TILE_BYTES = 4 << 20


def _merge2(a: jnp.ndarray, b: jnp.ndarray, k: int) -> jnp.ndarray:
    """topk_merge of two [..., K, BV] tables along the K axis."""
    return topk_distinct(jnp.concatenate([a, b], axis=-2), k, axis=-2)


def set_chunks(n_sets: int, cands: int, block_v: int,
               itemsize: int = 4) -> int:
    """Keyword-set chunks per row block: the fewest divisor of ``n_sets``
    (a power of two, ``2^m``) whose ``[n_sets / chunks, cands, block_v]``
    candidate tile stays within :data:`MAX_CAND_TILE_BYTES`.  ``cands``
    is a row's ``dmax * K``.  ValueError where one set's tile alone is
    over the budget."""
    one_set = cands * block_v * itemsize
    if one_set > MAX_CAND_TILE_BYTES:
        raise ValueError(
            f"fused lane step: a {block_v}-row block with {cands} "
            f"candidates per row needs a {one_set:,} B candidate tile in "
            f"VMEM for one keyword set, over the {MAX_CAND_TILE_BYTES:,} B "
            f"the kernel takes; the graph's largest hub sets the block "
            f"(use backend='jnp')")
    chunks = 1
    while n_sets // chunks * one_set > MAX_CAND_TILE_BYTES:
        chunks *= 2
    return chunks


def _relax_reduce(cand: jnp.ndarray, seg: jnp.ndarray, k: int,
                  span: int) -> jnp.ndarray:
    """Steps 1 and 2 for the sets of one tile: ``cand[F', C, BV]`` ->
    each node's merged top-K at its tail row, ``[F', K, BV]``."""
    # 1) per-row relax reduce: top-K distinct over the candidate axis.
    r = topk_distinct(cand, k, axis=1)             # [F', K, BV]

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
    return r


def _lane_step_kernel(seg_ref, done_ref, pairs_ref, cand_ref, s0_ref,
                      out_ref, tab_ref, *, k: int, span: int, chunks: int,
                      n_pairs: int):
    """One (lane, row-block, set-chunk) grid step.

    seg_ref:   i32[1, BV]   node id per virtual row (-1 on pad rows)
    done_ref:  i32[L]       every lane's freeze flag, whole in SMEM (a
                            (1, 1) VMEM block of i32[L, 1] breaks the
                            TPU's (8, 128) tiling rule)
    pairs_ref: i32[3 P]     the combine's ``n_pairs`` split pairs
                            ``(t, a, b)`` in popcount order, whole in
                            SMEM
    cand_ref:  f32[1, 2^m / chunks, dmax*K, BV]  min-plus candidates of
                            this step's set chunk
    s0_ref:    f32[1, 2^m, K, BV]  pre-relax table, gathered per row
    out_ref:   f32[1, 2^m, K, BV]  post-combine table (valid at each
                                   node's tail row)
    tab_ref:   f32[2^m, K, BV] VMEM scratch: the chunks' reduced rows,
                            then the table the combine updates in place

    Steps 1 and 2 are independent per set, so they run per set chunk
    (the innermost grid axis); receive, combine and freeze run once,
    after the last chunk, while the output block stays in VMEM.
    """
    # Grid positions are read here, outside any branch: the interpreter
    # binds them only at the kernel's top level.
    frozen = done_ref[pl.program_id(0)] != 0
    j = pl.program_id(2)
    r = _relax_reduce(cand_ref[0], seg_ref[...], k, span)
    fc = r.shape[0]
    tab_ref[pl.ds(pl.multiple_of(j * fc, fc), fc)] = r

    @pl.when(j == chunks - 1)
    def _():
        _finish(tab_ref[...], frozen, pairs_ref, s0_ref, out_ref, tab_ref,
                k, n_pairs)


def _finish(r, frozen, pairs_ref, s0_ref, out_ref, tab_ref, k: int,
            n_pairs: int):
    """Steps 3 to 5 on the whole table of one row block; ``frozen``:
    whether this block's lane has finished."""
    s0 = s0_ref[0]                                  # [F, K, BV]
    # 3) receive: merge what arrived with the node's previous table.
    tab_ref[...] = _merge2(r, s0, k)

    # 4) subset-combine sweep (popcount order -> closure in one pass),
    #    in place in the scratch table.
    combine_sweep(tab_ref, pairs_ref, n_pairs, k)

    # 5) per-lane freeze: a finished lane keeps its pre-step table.
    out_ref[0] = jnp.where(frozen, s0, tab_ref[...])


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
    ``grid = (lanes, Vv / block_v, set chunks)``: more than one set chunk
    (:func:`set_chunks`) only where the whole candidate tile would not
    fit VMEM.  ``span``: the most virtual rows one node holds
    (default ``block_v``), which bounds the hub-merge scan.  Returns
    f32[L, 2^m, K, Vv]."""
    lanes, n_sets, c, vv = cand_t.shape
    k = s0_t.shape[2]
    assert n_sets == 1 << m and vv % block_v == 0
    span = block_v if span is None else span
    assert 1 <= span <= block_v
    chunks = set_chunks(n_sets, c, block_v, cand_t.dtype.itemsize)
    fc = n_sets // chunks
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    lane_sets = lambda l, i, j: (l, 0, 0, i)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_lane_step_kernel, k=k, span=span,
                          chunks=chunks, n_pairs=len(split_pairs(m))),
        grid=(lanes, vv // block_v, chunks),
        in_specs=[
            pl.BlockSpec((1, block_v), lambda l, i, j: (0, i)),
            smem,
            smem,
            pl.BlockSpec((1, fc, c, block_v), lambda l, i, j: (l, j, 0, i)),
            pl.BlockSpec((1, n_sets, k, block_v), lane_sets),
        ],
        out_specs=pl.BlockSpec((1, n_sets, k, block_v), lane_sets),
        out_shape=jax.ShapeDtypeStruct((lanes, n_sets, k, vv), cand_t.dtype),
        scratch_shapes=[pltpu.VMEM((n_sets, k, block_v), cand_t.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(seg, done, pair_table(m), cand_t, s0_t)
