"""Plain reference for DKS relationship queries, in NumPy.

Independent of the code under test: it imports nothing from the
program and takes none of its tables.  It computes the least fixpoint of
the top-K distinct min-plus lattice that DKS (arXiv:1605.00060, Sec. 4
and 5.1) defines over the symmetric graph:

    S[v, {i}]  holds 0 where node v carries keyword i;
    S[v, X]   <- top-K distinct of  S[v, X] u {S[u, X] + w(u, v)}
                                           u {S[v, A] + S[v, X \\ A]}

(a sorted, duplicate-free, INF-padded K-vector per node and keyword
set), by label-correcting rounds from the changed nodes until nothing
changes.  The answers of a query are the K smallest entries of
``S[:, full, :]`` over every (node, slot) cell, duplicates across nodes
kept: the paper's aggregator A_A.  Values at or above INF / 2 saturate to
INF (edges into hubs above the degree cut-off carry INF and connect
nothing).  Every weight is a small integer, so every value is a whole
number, exact in float64, and the merge sorts them as integers.

``precision`` runs the same fixpoint with every value rounded to a lower
precision after each operation: the control that a lower-precision
program would compute.
"""

from __future__ import annotations

import dataclasses

import numpy as np

INF = 1e9


@dataclasses.dataclass(frozen=True)
class SymGraph:
    """Symmetric, de-duplicated edge list: for every unordered pair the
    smallest weight of the directed edges between them, both directions
    kept; no self-loops.  ``src``/``dst``/``w`` are sorted by ``src``."""

    n_nodes: int
    src: np.ndarray
    dst: np.ndarray
    w: np.ndarray

    @classmethod
    def from_directed(cls, n_nodes: int, src, dst, w) -> "SymGraph":
        u = np.concatenate([src, dst]).astype(np.int64)
        v = np.concatenate([dst, src]).astype(np.int64)
        ww = np.concatenate([w, w]).astype(np.float64)
        keep = u != v
        u, v, ww = u[keep], v[keep], ww[keep]
        key = u * n_nodes + v
        order = np.lexsort((ww, key))
        key, u, v, ww = key[order], u[order], v[order], ww[order]
        first = np.ones(len(key), bool)
        first[1:] = key[1:] != key[:-1]
        return cls(n_nodes, u[first], v[first], ww[first])

    @property
    def n_edges(self) -> int:
        return len(self.src)

    def edge_weights(self) -> dict:
        """(u, v) -> weight, for checking answer trees."""
        return dict(zip(zip(self.src.tolist(), self.dst.tolist()),
                        self.w.tolist()))


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return x
    if precision == "bfloat16":
        # Round to nearest even on the top 16 bits of the float32 word.
        f = np.ascontiguousarray(x, np.float32)
        bits = f.view(np.uint32).astype(np.uint64)
        bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
        return bits.astype(np.uint32).view(np.float32).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


# Values are whole numbers (sums of integer weights, also after rounding
# to bfloat16) below 2^VALUE_BITS; INF takes the all-ones code.
_VALUE_BITS = 24
_NO_VALUE = (1 << _VALUE_BITS) - 1


def _keys(group: np.ndarray, values: np.ndarray) -> np.ndarray:
    """int64 sort keys ``group << VALUE_BITS | value`` (broadcast)."""
    finite = values < INF
    if np.any(finite & ((values != np.round(values))
                        | (values >= _NO_VALUE))):
        raise ValueError("a value is not a whole number below 2^24")
    code = np.where(finite, values, _NO_VALUE).astype(np.int64)
    return ((group << _VALUE_BITS) | code).reshape(-1)


def _saturate(x: np.ndarray) -> np.ndarray:
    return np.where(x >= INF * 0.5, INF, x)


def _unique_k(vals: np.ndarray, k: int) -> np.ndarray:
    """Sorted, duplicate-free, INF-padded first ``k`` of each row."""
    x = np.sort(vals, axis=-1)
    dup = np.zeros(x.shape, bool)
    dup[..., 1:] = x[..., 1:] == x[..., :-1]
    x = np.sort(np.where(dup, INF, x), axis=-1)
    return x[..., :k]


def _split_pairs(m: int) -> list[tuple[int, list[tuple[int, int]]]]:
    """Keyword sets in popcount order with their splits ``(a, b)``,
    ``a | b = t``, ``a < b``, both non-empty."""
    out = []
    for t in sorted(range(1, 1 << m), key=lambda t: (bin(t).count("1"), t)):
        pairs = [(a, t ^ a) for a in range(1, t) if a & t == a and a < t ^ a]
        if pairs:
            out.append((t, pairs))
    return out


def _combine(S: np.ndarray, rows: np.ndarray, m: int, k: int,
             precision: str) -> None:
    """Close ``S[rows]`` under the split combination, in place.  One pass
    in popcount order reaches the closure: every split's parts are
    smaller sets, already closed."""
    if m <= 1 or not len(rows):
        return
    T = S[rows]
    for t, pairs in _split_pairs(m):
        cands = [T[:, t, :]]
        for a, b in pairs:
            s = T[:, a, :, None] + T[:, b, None, :]
            cands.append(s.reshape(len(rows), k * k))
        c = _saturate(_round(np.concatenate(cands, axis=1), precision))
        T[:, t, :] = _unique_k(c, k)
    S[rows] = T


def fixpoint(graph: SymGraph, kw_nodes: list[np.ndarray], k: int,
             precision: str = "float64") -> tuple[np.ndarray, int]:
    """The DKS table at its least fixpoint: ``(S[V, 2^m, k], rounds)``.

    ``kw_nodes[i]``: the nodes carrying keyword ``i``."""
    m = len(kw_nodes)
    n_sets = 1 << m
    V = graph.n_nodes
    S = np.full((V, n_sets, k), INF)
    for i, nodes in enumerate(kw_nodes):
        S[np.asarray(nodes, np.int64), 1 << i, 0] = 0.0
    active = np.zeros(V, bool)
    for nodes in kw_nodes:
        active[np.asarray(nodes, np.int64)] = True
    _combine(S, np.flatnonzero(active), m, k, precision)
    # Edges sorted by source: the out-edges of a node set are the ranges.
    starts = np.searchsorted(graph.src, np.arange(V + 1))
    rounds = 0
    while active.any():
        rounds += 1
        act = np.flatnonzero(active)
        lo, hi = starts[act], starts[act + 1]
        n_out = hi - lo
        eidx = np.repeat(lo - np.cumsum(n_out) + n_out, n_out) + np.arange(
            n_out.sum())
        src, dst, w = graph.src[eidx], graph.dst[eidx], graph.w[eidx]
        cand = _saturate(_round(S[src] + w[:, None, None], precision))
        # Merge the candidates of each (destination, set) with its current
        # values: one sorted, de-duplicated int64 key per value,
        # (destination, set) in the high bits and the value below, then
        # the first k of each (destination, set).
        touched = np.unique(dst)
        group = np.arange(n_sets, dtype=np.int64)[None, :, None]
        keys = np.unique(np.concatenate([
            _keys(dst[:, None, None] * n_sets + group, cand),
            _keys(touched[:, None, None] * n_sets + group, S[touched]),
        ], axis=None))
        gid, code = keys >> _VALUE_BITS, keys & _NO_VALUE
        first = np.ones(len(keys), bool)
        first[1:] = gid[1:] != gid[:-1]
        rank = np.arange(len(keys)) - np.maximum.accumulate(
            np.where(first, np.arange(len(keys)), 0))
        keep = (rank < k) & (code != _NO_VALUE)
        R = np.full((V, n_sets, k), INF)
        R[gid[keep] // n_sets, gid[keep] % n_sets, rank[keep]] = code[keep]
        old = S[touched].copy()
        S[touched] = R[touched]
        _combine(S, touched, m, k, precision)
        changed = np.any(S[touched] < old, axis=(1, 2))
        active[:] = False
        active[touched[changed]] = True
    return S, rounds


def top_answers(S: np.ndarray, k: int) -> np.ndarray:
    """The ``k`` smallest cells of ``S[:, full, :]``, duplicates kept."""
    full = S.shape[1] - 1
    flat = np.sort(S[:, full, :].reshape(-1))
    out = np.full(k, INF)
    out[: min(k, len(flat))] = flat[:k]
    return out


def answer_weights(graph: SymGraph, kw_nodes: list[np.ndarray], k: int,
                   precision: str = "float64") -> np.ndarray:
    """The top-``k`` answer weights of one query (INF-padded)."""
    S, _ = fixpoint(graph, kw_nodes, k, precision)
    return top_answers(S, k)


def check_tree(edges, nodes, weight: float, kw_nodes: list[np.ndarray],
               edge_w: dict) -> str | None:
    """Why an answer tree is not a keyword-covering tree of the graph
    with the stated weight, or None when it is."""
    nodes = {int(v) for v in nodes}
    for u, v in edges:
        nodes.update((int(u), int(v)))
    total = 0.0
    for u, v in edges:
        wt = edge_w.get((int(u), int(v)))
        if wt is None or wt >= INF:
            return f"edge ({u}, {v}) is not in the graph"
        total += wt
    if len(edges) != len(nodes) - 1:
        return f"{len(edges)} edges over {len(nodes)} nodes is not a tree"
    parent = {v: v for v in nodes}

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for u, v in edges:
        ru, rv = find(int(u)), find(int(v))
        if ru == rv:
            return "the edges hold a cycle"
        parent[ru] = rv
    for i, kw in enumerate(kw_nodes):
        if not nodes.intersection(np.asarray(kw).tolist()):
            return f"keyword {i} is not covered"
    if total != weight:
        return f"edge weights sum to {total}, tree says {weight}"
    return None
