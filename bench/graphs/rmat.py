"""Graph kind ``rmat``: an R-MAT graph with Zipf keyword labels and the
DKS degree weights, made from a configuration's sizes and a seed.

``rmat_edges`` is the R-MAT generator (Chakrabarti et al., SDM'04) with
self-loops resampled.  Labels are drawn from a Zipf (1/rank) vocabulary,
``labels_per_node`` per node.  Edge weights follow the DKS degree model
(arXiv:1605.00060, Sec. 7.1): ``max(1, int(log10 d_in(dst)))`` below the
hub cut-off ``tau`` and INF at or above it.

Configuration keys: ``n_nodes``, ``n_edges`` (directed, before
symmetrizing), ``vocab``, ``labels_per_node``, ``tau``, ``rmat``
([a, b, c]) and ``structure_seed``.  The edges come from the
configuration's ``structure_seed``, so every seed serves a graph of the
same sizes and degree sequence, and so the same compiled programs; the
run's seed draws which nodes carry which keywords.
"""

from __future__ import annotations

import numpy as np

import graphgen


def rmat_edges(n_nodes: int, n_edges: int, rng: np.random.Generator,
               a: float = 0.57, b: float = 0.19, c: float = 0.19,
               max_resample_rounds: int = 16) -> tuple[np.ndarray, np.ndarray]:
    """``n_edges`` directed R-MAT edges over ``n_nodes`` ids, no
    self-loops (their slots are drawn again)."""
    scale = int(np.ceil(np.log2(max(n_nodes, 2))))

    def draw(n: int) -> tuple[np.ndarray, np.ndarray]:
        src = np.zeros(n, np.int64)
        dst = np.zeros(n, np.int64)
        for _ in range(scale):
            r = rng.random(n)
            go_right = (r >= a) & (r < a + b) | (r >= a + b + c)
            go_down = r >= a + b
            src = src * 2 + go_down
            dst = dst * 2 + go_right
        src %= n_nodes
        dst %= n_nodes
        keep = src != dst
        return src[keep], dst[keep]

    src, dst = draw(n_edges)
    for _ in range(max_resample_rounds):
        deficit = n_edges - len(src)
        if deficit == 0:
            break
        s2, d2 = draw(max(2 * deficit, 64))
        src = np.concatenate([src, s2[:deficit]])
        dst = np.concatenate([dst, d2[:deficit]])
    return src.astype(np.int32), dst.astype(np.int32)


def degree_weights(dst: np.ndarray, n_nodes: int, tau: int) -> np.ndarray:
    d_in = np.bincount(dst, minlength=n_nodes)
    wd = np.maximum(1, np.log10(np.maximum(d_in, 1)).astype(np.int64))
    wd = np.where(d_in >= tau, graphgen.INF, wd.astype(np.float64))
    return wd[dst].astype(np.float32)


def zipf_tokens(n_nodes: int, vocab: int, per_node: int,
                rng: np.random.Generator) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = (1.0 / ranks) / np.sum(1.0 / ranks)
    return rng.choice(vocab, size=(n_nodes, per_node),
                      p=probs).astype(np.int32)


def make(config: dict, seed: int) -> graphgen.Dataset:
    n, e = int(config["n_nodes"]), int(config["n_edges"])
    a, b, c = config["rmat"]
    src, dst = rmat_edges(n, e, graphgen.rng(config["structure_seed"], 0),
                          a, b, c)
    w = degree_weights(dst, n, int(config["tau"]))
    tokens = zipf_tokens(n, int(config["vocab"]),
                         int(config["labels_per_node"]), graphgen.rng(seed, 1))
    return graphgen.Dataset(n, src, dst, w, tokens)
