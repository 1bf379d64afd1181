"""Device-batched answer-tree backtrace (the paper's ``V_K`` role, on
device, for a whole lane bucket at once).

The host :func:`repro.core.reconstruct.backtrace` recovers one tree by a
recursive first-match search over split decompositions (``val == S[v,a,i]
+ S[v,b,j]``, ``a ⊎ b = ks``) and edge decompositions (``val == S[u,ks,j]
+ w(u,v)``).  Per candidate that is a Python recursion of numpy point
lookups — fine for one query, a serial bottleneck for a bucket.

This module runs the *same* search as one device program over the final
lane-batched table ``S[L, V, 2^m, K]`` (the lane conventions of
:mod:`repro.core.driver`): top-``C`` candidate cells per lane are selected
with ``lax.top_k`` (ties at lower cell index first — exactly the host's
stable value-ascending order), and every candidate walks a bounded
obligation queue top-down (children always land behind the cursor, so
one first-choice resolve per step covers the whole tree):

- **leaf**: ``val <= tol`` at a node covering every singleton keyword;
- **split**: first matching ``(a-pair, i, j)`` in the host's scan order
  (submask pairs descending from ``(ks-1) & ks``, slot prefixes honoring
  the host's early ``break``\\ s);
- **edge**: first matching ``(neighbor, j)`` in CSR neighbor order.

Because every obligation takes the host's *first* choice, a fully
resolved candidate is bit-identical to the host recursion (which only
deviates from first choices by backtracking out of a failed subtree — and
a failed subtree here marks the whole candidate).  Anything the bounded
pass cannot prove — a dead-end obligation, buffer/iteration overflow, a
node with more neighbors than the degree window — is a **ragged
straggler**: the candidate falls back to the host ``backtrace``, so the
final answer set is always bit-for-bit the host's.  The decomposition
records are replayed on the host into the host's exact edge order, then
pruned / cycle-repaired / deduped / ranked by the shared
:func:`repro.core.reconstruct.collect_answers` collector.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import INF
from repro.core import semiring
from repro.core.reconstruct import _TOL, AnswerTree, backtrace, collect_answers
from repro.graph.structure import Graph

# Obligation kinds in the device buffer.
_PENDING, _LEAF, _SPLIT, _EDGE, _FAIL = 0, 1, 2, 3, 4
_UNUSED = -1


@functools.lru_cache(maxsize=16)
def split_pair_table(m: int) -> tuple[np.ndarray, np.ndarray]:
    """Per keyword-subset ``ks``: the ordered ``(a, b)`` submask pairs the
    host split scan visits (``a`` descending from ``(ks-1) & ks``, only
    ``a <= b`` kept).  Padded with ``a = 0`` (never a valid submask).
    Shapes ``[2^m, P]`` with ``P >= 1``."""
    n_sets = 1 << m
    pairs: list[list[tuple[int, int]]] = []
    for ks in range(n_sets):
        row = []
        a = (ks - 1) & ks
        while a:
            b = ks ^ a
            if a <= b:
                row.append((a, b))
            a = (a - 1) & ks
        pairs.append(row)
    p_max = max(1, max(len(row) for row in pairs))
    pa = np.zeros((n_sets, p_max), np.int32)
    pb = np.zeros((n_sets, p_max), np.int32)
    for ks, row in enumerate(pairs):
        for i, (a, b) in enumerate(row):
            pa[ks, i], pb[ks, i] = a, b
    return pa, pb


@dataclasses.dataclass
class BatchedBacktrace:
    """Host copy of one device backtrace pass (all lanes, all candidates).

    ``cand_idx[L, C]`` are flat ``(root * K + slot)`` cell indices in the
    device's value-ascending scan order; ``fail[L, C]`` marks ragged
    stragglers (host fallback).  The per-obligation record arrays
    (``node/kind/child0/child1/edge_u``, each ``[L, C, B]``) replay into
    the host backtrace's exact edge order via :meth:`replay_edges`."""

    cand_idx: np.ndarray
    cand_val: np.ndarray
    fail: np.ndarray
    node: np.ndarray
    kind: np.ndarray
    child0: np.ndarray
    child1: np.ndarray
    edge_u: np.ndarray

    @property
    def n_candidates(self) -> int:
        return self.cand_idx.shape[1]

    def replay_edges(self, lane: int, cand: int) -> list[tuple[int, int]] | None:
        """Reconstruct the host-ordered edge list for one resolved
        candidate; None when the device pass flagged it ragged."""
        if self.fail[lane, cand]:
            return None
        kind = self.kind[lane, cand]
        node = self.node[lane, cand]
        child0 = self.child0[lane, cand]
        child1 = self.child1[lane, cand]
        edge_u = self.edge_u[lane, cand]
        out: list[tuple[int, int]] = []
        # Explicit stack replaying the host recursion's emit order: a split
        # emits left edges then right, an edge decomposition emits its
        # subtree first, then itself (post-order).
        stack: list[tuple[int, int]] = [(0, 0)]
        while stack:
            slot, phase = stack.pop()
            kd = int(kind[slot])
            if kd == _LEAF:
                continue
            if kd == _SPLIT:
                stack.append((int(child1[slot]), 0))
                stack.append((int(child0[slot]), 0))
            elif kd == _EDGE:
                if phase == 0:
                    stack.append((slot, 1))
                    stack.append((int(child0[slot]), 0))
                else:
                    v, u = int(node[slot]), int(edge_u[slot])
                    out.append((min(v, u), max(v, u)))
            else:
                # Pending/fail slot on a "resolved" path: treat as ragged.
                return None
        return out


class BatchedBacktracer:
    """Per-graph device backtracer: candidate selection + obligation
    expansion fused into one jitted program per ``(L, C, m, K)`` shape.

    ``degree_cap`` bounds the per-obligation neighbor window (a node with
    more neighbors whose match lies beyond the window falls back to the
    host — correctness never depends on the cap).  ``buffer`` bounds the
    per-candidate obligation count (= tree edges + splits + leaves).
    """

    def __init__(self, graph: Graph, degree_cap: int = 2048,
                 buffer: int = 64) -> None:
        self.graph = graph
        deg_max = int(np.diff(graph.indptr).max()) if graph.n_nodes else 1
        self.degree_cap = max(1, min(degree_cap, max(deg_max, 1)))
        self.buffer = buffer
        # Host CSR, device-resident: indices/ew in the exact neighbor order
        # the host backtrace scans (ascending neighbor id per node).  An
        # edgeless graph keeps one sentinel entry (never selected: every
        # node's degree window is empty) so gathers stay in-bounds.
        indices = np.asarray(graph.indices, np.int32)
        ews = np.asarray(graph.ew, np.float32)
        if indices.size == 0:
            indices, ews = np.zeros(1, np.int32), np.full(1, INF, np.float32)
        self._indptr = jnp.asarray(np.asarray(graph.indptr, np.int32))
        self._esrc = jnp.asarray(indices)
        self._ew = jnp.asarray(ews)
        self._kernels: dict[tuple, Any] = {}
        # Introspection: how much the device pass actually resolved.
        self.device_resolved = 0
        self.host_fallbacks = 0

    def stats(self) -> dict[str, int]:
        """``{device_resolved, host_fallbacks}`` — obligation backtraces
        the device program settled vs ragged stragglers that re-ran the
        host search (both monotone over the tracer's lifetime; the
        metrics registry exports them as counters)."""
        return {"device_resolved": self.device_resolved,
                "host_fallbacks": self.host_fallbacks}

    # -- device kernel --------------------------------------------------

    def _kernel(self, L: int, C: int, m: int, K: int):
        key = (L, C, m, K)
        fn = self._kernels.get(key)
        if fn is not None:
            return fn
        full = (1 << m) - 1
        B = self.buffer
        D = self.degree_cap
        pa_np, pb_np = split_pair_table(m)
        pa = jnp.asarray(pa_np)
        pb = jnp.asarray(pb_np)
        indptr, esrc, ew = self._indptr, self._esrc, self._ew
        tol = jnp.float32(_TOL)
        inf = jnp.float32(INF)

        def resolve(S, kw, v, s, x):
            """First-choice decomposition of one obligation ``(v, s, x)``
            — the host scan orders, vectorized."""
            # Leaf: zero value at a node covering every singleton of s.
            bits = (s >> jnp.arange(m)) & 1
            covered = jnp.all((bits == 0) | kw[jnp.arange(m), v])
            leaf = (x <= tol) & covered
            # Split scan over (a-pair, i, j) in host lexicographic order.
            a = pa[s]
            b = pb[s]
            Sa = S[v, a, :]                               # [P, K]
            Sb = S[v, b, :]
            # cumprod == the host's prefix `break` semantics per slot.
            ia_ok = jnp.cumprod(
                ((Sa <= x + tol) & (Sa < inf)).astype(jnp.int32), axis=1) > 0
            jb_ok = jnp.cumprod((Sb < inf).astype(jnp.int32), axis=1) > 0
            close = jnp.abs(Sa[:, :, None] + Sb[:, None, :] - x) <= tol
            smatch = ((a > 0)[:, None, None] & ia_ok[:, :, None]
                      & jb_ok[:, None, :] & close)
            sflat = smatch.reshape(-1)
            s_found = jnp.any(sflat)
            sidx = jnp.argmax(sflat)
            p_i, i_i, j_i = sidx // (K * K), (sidx // K) % K, sidx % K
            sa, sb = a[p_i], b[p_i]
            sva, svb = Sa[p_i, i_i], Sb[p_i, j_i]
            # Edge scan over (CSR neighbor, j) in host order.
            start = indptr[v]
            deg = indptr[v + 1] - start
            off = jnp.arange(D)
            ei = jnp.clip(start + off, 0, esrc.shape[0] - 1)
            u = esrc[ei]                                  # [D]
            w = ew[ei]
            emask = (off < deg) & (w < inf) & (w <= x + tol)
            Su = S[u, s, :]                               # [D, K]
            ju_ok = jnp.cumprod((Su < inf).astype(jnp.int32), axis=1) > 0
            eclose = jnp.abs(Su - (x - w)[:, None]) <= tol
            ematch = emask[:, None] & ju_ok & eclose
            eflat = ematch.reshape(-1)
            e_found = jnp.any(eflat)
            eidx = jnp.argmax(eflat)
            d_i, ej = eidx // K, eidx % K
            eu, ev = u[d_i], Su[d_i, ej]
            kind = jnp.where(
                leaf, _LEAF,
                jnp.where(s_found, _SPLIT,
                          jnp.where(e_found, _EDGE, _FAIL)))
            # Child obligations: split -> (v,sa,sva),(v,sb,svb);
            # edge -> (eu,s,ev).
            c0 = jnp.where(kind == _SPLIT,
                           jnp.stack([v, sa, 0]),
                           jnp.stack([eu, s, 0])).astype(jnp.int32)
            c0v = jnp.where(kind == _SPLIT, sva, ev)
            c1 = jnp.stack([v, sb, 0]).astype(jnp.int32)
            c1v = svb
            return kind.astype(jnp.int32), c0[0], c0[1], c0v, c1[0], c1[1], c1v, eu

        def one(S, kw, root, val, valid):
            # Obligation queue with a cursor: children are always appended
            # *behind* the cursor (at slots n, n+1 > it), so one resolve
            # per iteration walks the whole tree in BFS order — the loop
            # runs tree-size iterations and each touches O(P·K² + D·K)
            # table cells, instead of re-resolving every buffer slot every
            # round.  Arrays carry a sacrificial B-th slot that absorbs
            # masked / overflowing writes.
            node = jnp.zeros(B + 1, jnp.int32).at[0].set(root)
            ks = jnp.zeros(B + 1, jnp.int32).at[0].set(full)
            vals = jnp.zeros(B + 1, jnp.float32).at[0].set(val)
            kind = jnp.full(B + 1, _UNUSED, jnp.int32).at[0].set(_PENDING)
            child0 = jnp.full(B + 1, _UNUSED, jnp.int32)
            child1 = jnp.full(B + 1, _UNUSED, jnp.int32)
            edge_u = jnp.full(B + 1, _UNUSED, jnp.int32)
            n = jnp.int32(1)
            fail = ~valid
            it = jnp.int32(0)

            def cond(carry):
                node, ks, vals, kind, child0, child1, edge_u, n, fail, it = carry
                return (it < n) & ~fail

            def body(carry):
                node, ks, vals, kind, child0, child1, edge_u, n, fail, it = carry
                kd, c0n, c0s, c0v, c1n, c1s, c1v, eu = resolve(
                    S, kw, node[it], ks[it], vals[it])
                fail = fail | (kd == _FAIL)
                cnt = jnp.where(kd == _SPLIT, 2,
                                jnp.where(kd == _EDGE, 1, 0))
                new_n = n + cnt
                fail = fail | (new_n > B)
                has0 = (kd == _SPLIT) | (kd == _EDGE)
                has1 = kd == _SPLIT
                idx0 = jnp.where(has0, jnp.minimum(n, B), B)
                idx1 = jnp.where(has1, jnp.minimum(n + 1, B), B)
                node = node.at[idx0].set(c0n).at[idx1].set(c1n)
                ks = ks.at[idx0].set(c0s).at[idx1].set(c1s)
                vals = vals.at[idx0].set(c0v).at[idx1].set(c1v)
                kind = (kind.at[idx0].set(_PENDING).at[idx1].set(_PENDING)
                        .at[it].set(kd))
                child0 = child0.at[it].set(jnp.where(has0, idx0, _UNUSED))
                child1 = child1.at[it].set(jnp.where(has1, idx1, _UNUSED))
                edge_u = edge_u.at[it].set(
                    jnp.where(kd == _EDGE, eu, _UNUSED))
                return (node, ks, vals, kind, child0, child1, edge_u,
                        jnp.minimum(new_n, B), fail, it + 1)

            carry = (node, ks, vals, kind, child0, child1, edge_u, n, fail, it)
            carry = jax.lax.while_loop(cond, body, carry)
            node, ks, vals, kind, child0, child1, edge_u, n, fail, it = carry
            return dict(node=node[:B], kind=kind[:B], child0=child0[:B],
                        child1=child1[:B], edge_u=edge_u[:B], fail=fail)

        @jax.named_scope("dks.backtrace")
        def kernel(S_lanes, kw_lanes):
            # Candidate selection: value-ascending with ties at lower cell
            # index first, matching the host's stable argsort exactly.
            vals, idx = semiring.smallest_k_2d(S_lanes[:, :, full, :], C)
            roots = idx // K
            valid = vals < inf
            per_cand = jax.vmap(one, in_axes=(None, None, 0, 0, 0))
            per_lane = jax.vmap(per_cand, in_axes=(0, 0, 0, 0, 0))
            recs = per_lane(S_lanes, kw_lanes, roots, vals, valid)
            return idx, vals, recs

        fn = jax.jit(kernel)
        self._kernels[key] = fn
        return fn

    # -- host orchestration ---------------------------------------------

    def backtrace_lanes(self, S_lanes, kw_lanes, k: int,
                        candidate_factor: int = 4) -> BatchedBacktrace:
        """One device program: top-``k * candidate_factor`` candidates per
        lane, backtraced.  ``S_lanes``: ``[L, Vp, 2^m, K]`` (device);
        ``kw_lanes``: ``[L, m, Vp]`` bool."""
        L, _vp, n_sets, K = S_lanes.shape
        m = int(n_sets).bit_length() - 1
        C = max(1, min(int(np.prod(S_lanes.shape[1::2])),
                       max(k, 1) * candidate_factor))
        fn = self._kernel(L, C, m, K)
        idx, vals, recs = jax.block_until_ready(
            fn(jnp.asarray(S_lanes), jnp.asarray(kw_lanes)))
        return BatchedBacktrace(
            cand_idx=np.asarray(idx), cand_val=np.asarray(vals),
            fail=np.asarray(recs["fail"]), node=np.asarray(recs["node"]),
            kind=np.asarray(recs["kind"]), child0=np.asarray(recs["child0"]),
            child1=np.asarray(recs["child1"]),
            edge_u=np.asarray(recs["edge_u"]))

    def extract_lanes(
        self,
        S_lanes,
        kw_lanes: np.ndarray,
        k: int,
        candidate_factor: int = 4,
        lanes: list[int] | None = None,
        n_nodes: int | None = None,
        batch: BatchedBacktrace | None = None,
    ) -> list[tuple[list[AnswerTree], bool]]:
        """Device-batched :func:`collect_answers` for a whole bucket.

        Returns ``(ranked_answers, exhausted)`` per requested lane —
        bit-identical to the host path: device-resolved candidates replay
        the host's first-choice search, ragged stragglers re-run the host
        ``backtrace``, and collection/pruning/ranking is the shared host
        collector either way.  ``lanes``: which lanes to collect (default
        all — serving passes the real lanes of a padded bucket).
        ``n_nodes``: real node count (kw mask columns beyond it are
        padding).  ``batch``: this bucket's :meth:`backtrace_lanes`
        output when the caller already ran it (and timed it apart from
        the host collection)."""
        if batch is None:
            batch = self.backtrace_lanes(S_lanes, kw_lanes, k,
                                         candidate_factor)
        S_host = np.asarray(S_lanes)
        kw_host = np.asarray(kw_lanes)
        V = n_nodes if n_nodes is not None else self.graph.n_nodes
        m = kw_host.shape[1]
        full = (1 << m) - 1
        out: list[tuple[list[AnswerTree], bool]] = []
        for lane in (range(S_host.shape[0]) if lanes is None else lanes):
            S = S_host[lane]
            kw = kw_host[lane][:, :V]

            def from_device(pos: int, root: int, val: float,
                            _lane=lane, _S=S, _kw=kw):
                # Use the device record only when the device's pos-th
                # candidate is the host's pos-th candidate (same cell, same
                # value) — a tie-order sanity check; mismatch or a ragged
                # straggler re-runs the host search.
                if pos < batch.n_candidates:
                    K = _S.shape[2]
                    ci = int(batch.cand_idx[_lane, pos])
                    cv = float(batch.cand_val[_lane, pos])
                    if ci // K == root and abs(cv - val) <= 1e-6:
                        edges = batch.replay_edges(_lane, pos)
                        if edges is not None:
                            self.device_resolved += 1
                            return edges
                self.host_fallbacks += 1
                return backtrace(_S, self.graph, _kw, root, full, val)

            answers, exhausted = collect_answers(
                S, self.graph, kw, k, candidate_factor,
                backtrace_fn=from_device)
            out.append((answers, exhausted))
        return out
