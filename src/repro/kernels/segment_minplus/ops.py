"""Wrapper: DKS relax via padded CSR + hub splitting + the Pallas reduce.

``padded_csr_from_graph`` (host, numpy) builds the degree-decomposed layout
once per graph; ``segment_minplus`` runs each superstep: XLA gather of
source tables (+w), Pallas padded top-K reduce, jnp second-level merge of
split hubs.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import INF
from repro.core import semiring
from repro.kernels import interpret_mode
from repro.kernels.segment_minplus.kernel import padded_topk


@dataclasses.dataclass(frozen=True)
class PaddedCSR:
    """Degree-decomposed incoming-edge layout.

    src_pad:  i32[Vv, DMAX]  source node per candidate slot (0 on padding)
    w_pad:    f32[Vv, DMAX]  edge length (INF on padding)
    real_of:  i32[Vv]        owning real node of each virtual row
    dmax:     int
    n_virtual:int
    """

    src_pad: jax.Array
    w_pad: jax.Array
    real_of: jax.Array
    dmax: int
    n_virtual: int


def padded_csr_from_graph(src: np.ndarray, dst: np.ndarray, w: np.ndarray,
                          n_nodes: int, dmax: int = 64,
                          pad_rows_to: int = 8) -> PaddedCSR:
    """Build per-destination padded rows, splitting hubs over >1 row."""
    order = np.argsort(dst, kind="stable")
    src, dst, w = src[order], dst[order], w[order]
    deg = np.bincount(dst, minlength=n_nodes)
    rows_per = np.maximum(1, -(-deg // dmax))
    n_virt = int(rows_per.sum())
    n_virt_pad = int(-(-n_virt // pad_rows_to) * pad_rows_to)
    src_pad = np.zeros((n_virt_pad, dmax), np.int32)
    w_pad = np.full((n_virt_pad, dmax), INF, np.float32)
    real_of = np.zeros(n_virt_pad, np.int32)
    row_start = np.concatenate([[0], np.cumsum(rows_per)])
    edge_start = np.concatenate([[0], np.cumsum(deg)])
    for v in range(n_nodes):
        e0, e1 = edge_start[v], edge_start[v + 1]
        r0 = row_start[v]
        for j, e in enumerate(range(e0, e1)):
            r, c = divmod(j, dmax)
            src_pad[r0 + r, c] = src[e]
            w_pad[r0 + r, c] = w[e]
        for r in range(row_start[v], row_start[v + 1]):
            real_of[r] = v
    real_of[n_virt:] = 0
    w_pad[n_virt:] = INF
    return PaddedCSR(
        src_pad=jnp.asarray(src_pad), w_pad=jnp.asarray(w_pad),
        real_of=jnp.asarray(real_of), dmax=dmax, n_virtual=n_virt_pad)


def segment_minplus_padded(
    S: jax.Array, csr: PaddedCSR, changed: jax.Array, k: int,
    n_nodes: int, block_v: int = 8, interpret: bool | None = None,
) -> jax.Array:
    """One relax step: S[V, F, K] tables -> R[V, F, K] received tables."""
    if interpret is None:
        interpret = interpret_mode()
    v, f, _ = S.shape
    vv, dmax = csr.src_pad.shape
    # Gather source tables (+ edge length) — XLA gather, streams well.
    src_flat = csr.src_pad.reshape(-1)
    fire = changed[src_flat]
    cand = S[src_flat] + csr.w_pad.reshape(-1)[:, None, None]
    cand = jnp.where(fire[:, None, None], cand, INF)
    cand = semiring.bump_to_inf(cand)
    cand = cand.reshape(vv, dmax, f, k)
    cand = cand.transpose(0, 1, 3, 2).reshape(vv, dmax * k, f)
    red = padded_topk(cand, k, block_v=block_v, interpret=interpret)  # [Vv,F,K]
    # Second-level merge of split hubs (few rows per real node).
    out = jnp.full((n_nodes, f, k), INF, S.dtype)
    flat = red.transpose(0, 2, 1).reshape(vv * k, f)   # rows (virt, slot)
    seg = jnp.repeat(csr.real_of, k)
    return semiring.segment_topk_min(flat, seg, n_nodes, k)


def segment_minplus(S, src, dst, w, changed, v_pad, k):
    """Engine-compatible signature (graph edge-list); builds candidates via
    gather and reduces with the K-round jnp path.  The padded-CSR Pallas
    path is selected by the engine when a PaddedCSR is attached."""
    send = changed[src]
    cand = S[src] + w[:, None, None]
    cand = jnp.where(send[:, None, None], cand, INF)
    cand = semiring.bump_to_inf(cand)
    e_pad, n, kk = cand.shape
    vals = cand.transpose(0, 2, 1).reshape(e_pad * kk, n)
    seg = jnp.repeat(dst, kk)
    return semiring.segment_topk_min(vals, seg, v_pad, kk)
