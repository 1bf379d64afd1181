"""Host-facing wrapper for the fused lane-superstep kernel.

Two pieces:

- :class:`LaneCSR` / :func:`lane_csr_from_device_graph` — the padded-CSR
  layout the kernel consumes, built ONCE per graph on the host (numpy)
  and cached by ``QueryEngine.build``.  It is ``segment_minplus``'s
  ``PaddedCSR`` idea (per-destination padded rows, hubs split into
  ``ceil(d / dmax)`` virtual rows) with one extra invariant: a node's
  rows are **block-aligned** — they never straddle a ``block_v``
  boundary — so the kernel's in-block segmented scan always produces the
  complete hub merge at the node's tail row, and no second-level jnp
  merge is needed.

- :func:`fused_lane_superstep` — the drop-in replacement for the lane
  driver's vmapped :func:`~repro.core.dks.superstep` on dense graphs:
  XLA gathers build the candidate tensor (weights straight from the
  ``DeviceGraph``, so :class:`~repro.graph.weights.WeightPolicy`
  effective weights flow in untouched), ONE ``pallas_call`` runs
  relax + hub merge + receive + combine + per-lane freeze
  (:mod:`.kernel`), and the shared jnp tail
  (:func:`~repro.core.dks.finish_superstep`) recomputes the frontier,
  aggregators, and exit check — bit-identical to the jnp superstep.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import INF
from repro.core import semiring
from repro.core.dks import DKSConfig, DKSState, finish_superstep
from repro.kernels import interpret_mode
from repro.kernels.lane_superstep.kernel import fused_lane_step

# Widest row block a layout takes: at the default dmax=16, its m=3, K=2
# candidate tile (2^3 * 16*2 * 4096 * 4 B) is the kernel's whole
# MAX_CAND_TILE_BYTES.  The kernel refuses wider query shapes on a
# layout this wide at trace time.
MAX_BLOCK_V = 4096

# Largest padded temporary one chunk of the candidate row gather may take
# in HBM (the gather's per-chunk counterpart of the kernel's
# MAX_CAND_TILE_BYTES).  An f32[rows, 2^m*K] gather result pads its minor
# axis to 128 lanes (up to 32x its size), and at the paper's widths the
# 8-lane candidate tensor already takes a quarter of the chip, so the
# gather runs in chunks sized against this.  At sec-rdfabout's 496,128
# rows one m=3, K=2 slot takes 254 MB: one slot per chunk.
MAX_GATHER_CHUNK_BYTES = 256 << 20


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LaneCSR:
    """Block-aligned padded CSR over the *symmetrized padded* node space.

    Attributes:
      src_pad: i32[Vv, dmax] source node per candidate slot (0 on pads).
      w_pad:   f32[Vv, dmax] effective edge weight (INF on pads).
      gather_of: i32[Vv] owning real node per virtual row (0 on pad
        rows — their candidates are all INF, so the gathered table is
        never consumed).
      seg:     i32[Vv] owning real node per row, -1 on pad rows (the
        kernel's segment ids; distinct from ``gather_of`` so pad rows
        never join a real segment).
      tail_row: i32[v_pad] LAST virtual row of each node — where the
        kernel's segmented scan leaves the complete merge.
      dmax / block_v / n_rows: static layout parameters.
      span: the most virtual rows any one node holds (bounds the
        kernel's hub-merge scan).
    """

    src_pad: jax.Array
    w_pad: jax.Array
    gather_of: jax.Array
    seg: jax.Array
    tail_row: jax.Array
    dmax: int = dataclasses.field(metadata=dict(static=True))
    block_v: int = dataclasses.field(metadata=dict(static=True))
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    span: int = dataclasses.field(metadata=dict(static=True))


def lane_csr_from_device_graph(graph, dmax: int = 16,
                               block_v: int = 128) -> LaneCSR:
    """Build the kernel layout from a dense :class:`DeviceGraph`.

    Host-side numpy, paid once per ``QueryEngine.build``.  ``block_v``
    doubles until every node fits in at most ``block_v`` virtual rows
    (the block-alignment invariant is unconditional).  Growing the block
    rather than ``dmax`` keeps one hub from widening every row of the
    candidate tensor.  A node whose in-edges exceed ``dmax *
    MAX_BLOCK_V`` cannot fit one block in VMEM: ValueError.
    """
    valid = np.asarray(graph.valid)
    src = np.asarray(graph.src)[valid].astype(np.int64)
    dst = np.asarray(graph.dst)[valid].astype(np.int64)
    w = np.asarray(graph.w)[valid].astype(np.float32)
    n = int(graph.v_pad)

    deg = np.bincount(dst, minlength=n).astype(np.int64)
    max_deg = int(deg.max()) if deg.size else 0
    if max_deg > dmax * MAX_BLOCK_V:
        raise ValueError(
            f"node {int(deg.argmax())} has {max_deg:,} in-edges; the fused "
            f"kernel's layout holds at most {dmax * MAX_BLOCK_V:,} per node "
            f"(one {MAX_BLOCK_V}-row block of {dmax} slots in VMEM); use "
            f"backend='jnp'")
    while max_deg > dmax * block_v:
        block_v *= 2
    rows = np.maximum(1, -(-deg // dmax))           # ceil, >= 1 row/node

    # Block-aligned row starts: advance to the next block boundary when a
    # node's rows would straddle it.
    row0 = np.zeros(n, np.int64)
    cur = 0
    for v in range(n):
        if (cur % block_v) + rows[v] > block_v:
            cur = (cur // block_v + 1) * block_v
        row0[v] = cur
        cur += rows[v]
    n_rows = max(block_v, int(np.ceil(cur / block_v)) * block_v)

    seg = np.full(n_rows, -1, np.int32)
    starts = np.cumsum(rows) - rows
    row_idx = np.repeat(row0, rows) + (np.arange(rows.sum()) -
                                       np.repeat(starts, rows))
    seg[row_idx] = np.repeat(np.arange(n, dtype=np.int32), rows)
    tail_row = (row0 + rows - 1).astype(np.int32)

    src_pad = np.zeros((n_rows, dmax), np.int32)
    w_pad = np.full((n_rows, dmax), INF, np.float32)
    order = np.argsort(dst, kind="stable")
    ds, ss, ws = dst[order], src[order], w[order]
    estart = np.cumsum(deg) - deg
    within = np.arange(ds.size) - estart[ds]
    r, c = row0[ds] + within // dmax, within % dmax
    src_pad[r, c] = ss.astype(np.int32)
    w_pad[r, c] = ws

    return LaneCSR(
        src_pad=jnp.asarray(src_pad), w_pad=jnp.asarray(w_pad),
        gather_of=jnp.asarray(np.maximum(seg, 0).astype(np.int32)),
        seg=jnp.asarray(seg), tail_row=jnp.asarray(tail_row),
        dmax=int(dmax), block_v=int(block_v), n_rows=int(n_rows),
        span=int(rows.max()) if rows.size else 1,
    )


def gather_chunks(dmax: int, n_rows: int, row_len: int) -> int:
    """Slot groups per lane of the candidate row gather: the fewest (a
    divisor of ``dmax``) whose ``[dmax / chunks * n_rows, row_len]`` f32
    result, its minor axis padded to 128 lanes, stays within
    :data:`MAX_GATHER_CHUNK_BYTES`.  ``row_len`` is a table row's
    ``2^m * K``.  Never more than ``dmax`` chunks: one slot per chunk is
    the finest split."""
    slot_bytes = n_rows * -(-row_len // 128) * 128 * 4
    for chunks in range(1, dmax + 1):
        if dmax % chunks == 0 and \
                dmax // chunks * slot_bytes <= MAX_GATHER_CHUNK_BYTES:
            return chunks
    return dmax


def fused_lane_superstep(graph, csr: LaneCSR, state: DKSState,
                         cfg: DKSConfig,
                         interpret: bool | None = None) -> DKSState:
    """One superstep for every lane, inner loop as ONE kernel launch.

    ``state``: lane-batched (``S[L, V, 2^m, K]``, ``done[L]``, ...).
    Returns the stepped state *without* the driver's cross-lane freeze
    select — :func:`~repro.core.driver.lane_superstep` applies
    ``freeze_lanes`` exactly as on the jnp path (the kernel's own
    per-lane freeze keeps a finished lane's table; the driver select
    keeps its counters).
    """
    if interpret is None:
        interpret = interpret_mode()
    S0 = state.S                                    # [L, V, F, K]
    lanes = S0.shape[0]
    f, k = cfg.n_sets, cfg.k

    deg = graph.out_degree.astype(jnp.float32)
    n_bfs = jnp.sum(jnp.where(state.first_fire, deg, 0.0), axis=1)
    n_deep = jnp.sum(
        jnp.where(state.changed & ~state.first_fire, deg, 0.0), axis=1)

    # Candidate gather (XLA), one index per table row: a node's 2^m*K
    # floats are contiguous in S's [L, V, 2^m, K] layout, so each
    # (slot, row) takes its sender's whole row at once.  The sender's
    # ``changed`` flag is folded into the rows first: INF + w bumps to INF
    # for every non-negative w (pads carry w=INF), the value the jnp relax
    # gives a silent sender, so the candidate multiset is identical.  The
    # result is transposed into the kernel's layout, virtual rows minor
    # (cand[l, s, (kk, slot), row]); the kernel min-reduces each row's
    # dmax*K candidates, so their order is free.  The gather runs in
    # chunks of slots (``gather_chunks``) for each lane in turn, written
    # in place into the candidate tensor, the only full-size buffer;
    # ``s0_t`` and the tail's ``S1`` (row gathers too) go one lane at a
    # time, each lane's padded [Vv, F*K] as large as one slot.  The
    # named scopes (``dks.gather``, ``dks.kernel``, ``dks.finish``) label
    # the ops in the compiled program; they change no op.
    fk, vv = f * k, csr.n_rows
    chunks = gather_chunks(csr.dmax, vv, fk)
    g = csr.dmax // chunks
    src_c = csr.src_pad.T.reshape(chunks, g * vv)   # slot-major indices
    w_c = csr.w_pad.T.reshape(chunks, g, vv)

    def rows_of(table):                             # [V, F, K] -> [V, F*K]
        return table.reshape(table.shape[0], fk)

    def cand_chunk(i, cand):
        lane, c = i // chunks, i % chunks
        rows = jnp.where(state.changed[lane][:, None], rows_of(S0[lane]),
                         INF)
        got = jnp.take(rows, src_c[c], axis=0, mode="clip")
        got = semiring.bump_to_inf(got.reshape(g, vv, fk) + w_c[c][..., None])
        piece = got.reshape(g, vv, f, k).transpose(2, 3, 0, 1)
        return jax.lax.dynamic_update_slice(
            cand, piece.reshape(1, f, k * g, vv), (lane, 0, c * k * g, 0))

    def lane_s0(table):                             # [V, F, K] -> [F, K, Vv]
        got = jnp.take(rows_of(table), csr.gather_of, axis=0, mode="clip")
        return got.reshape(vv, f, k).transpose(1, 2, 0)

    with jax.named_scope("dks.gather"):
        cand_t = jax.lax.fori_loop(
            0, lanes * chunks, cand_chunk,
            jnp.empty((lanes, f, k * csr.dmax, vv), S0.dtype))
        s0_t = jax.lax.map(lane_s0, S0)             # [L, F, K, Vv]
    done_i = state.done.astype(jnp.int32)

    with jax.named_scope("dks.kernel"):
        out_t = fused_lane_step(cand_t, s0_t, csr.seg[None, :], done_i,
                                m=cfg.m, block_v=csr.block_v,
                                span=csr.span,
                                interpret=interpret)  # [L, F, K, Vv]
    with jax.named_scope("dks.finish"):
        S1 = jax.lax.map(
            lambda out: jnp.take(out.reshape(fk, vv).T, csr.tail_row,
                                 axis=0, mode="clip").reshape(-1, f, k),
            out_t)                                  # [L, V, F, K]
        nxt = dataclasses.replace(
            state,
            S=S1,
            msgs_bfs=state.msgs_bfs + n_bfs,
            msgs_deep=state.msgs_deep + n_deep,
            step=state.step + 1,
        )
        return jax.vmap(
            lambda s0, st: finish_superstep(graph, s0, st, cfg))(S0, nxt)
