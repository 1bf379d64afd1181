"""The repro.answers subsystem: device-batched backtrace parity,
diversified ranking, rendering/pagination, and streaming extraction
overlap."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.answers import (
    BatchedBacktracer,
    ExtractionOverlap,
    cluster_trees,
    diversified_order,
    paginate,
    render_tree,
    split_pair_table,
    top_k_diverse,
    tree_distance,
)
from repro.core.reconstruct import AnswerTree, collect_answers
from repro.engine import ExecutionPolicy, QueryEngine
from repro.graph.generators import random_weighted_graph


def tree(root, edges, weight):
    nodes = tuple(sorted({n for e in edges for n in e} | {root}))
    return AnswerTree(root=root, edges=tuple(sorted(edges)), weight=weight,
                      raw_value=weight, nodes=nodes)


def lane_tables(g, masks_host, k, L=4, max_supersteps=24):
    """Final lane-batched tables straight off the fused driver."""
    engine = QueryEngine.build(
        g, tokens=np.zeros((g.n_nodes, 1), np.int64),
        policy=ExecutionPolicy(max_supersteps=max_supersteps))
    m = masks_host.shape[0]
    kw = np.zeros((L, m, engine.device_graph.v_pad), bool)
    kw[:, :, : g.n_nodes] = masks_host
    fn = engine._executable(engine._config(m, k), "fused")
    states = engine._execute(fn, jnp.asarray(kw))
    return np.asarray(states.S), kw


# -- device-batched backtrace ------------------------------------------


def test_split_pair_table_matches_host_scan():
    pa, pb = split_pair_table(3)
    # ks=0b111: host scans a = 6,5,4,3,2,1 keeping a <= b, so the kept
    # pairs arrive as (3,4),(2,5),(1,6).
    row = [(int(a), int(b)) for a, b in zip(pa[7], pb[7]) if a > 0]
    assert row == [(3, 4), (2, 5), (1, 6)]
    # Singletons split nowhere.
    assert int(pa[1].max()) == 0 and int(pa[2].max()) == 0


@pytest.mark.parametrize("seed", range(6))
def test_batched_backtrace_bit_identical_to_host(seed):
    rng = np.random.default_rng(300 + seed)
    n = int(rng.integers(10, 24))
    g = random_weighted_graph(n, n + int(rng.integers(6, 30)), seed=seed)
    m = int(rng.integers(2, 4))
    k = int(rng.integers(1, 4))
    masks_host = np.zeros((m, n), bool)
    for t in range(m):
        masks_host[t, rng.choice(n, size=max(1, n // 4), replace=False)] = True
    S_all, kw = lane_tables(g, masks_host, k)
    bt = BatchedBacktracer(g)
    got = bt.extract_lanes(S_all, kw, k=k, n_nodes=n)
    assert bt.device_resolved > 0, "device pass resolved nothing"
    for lane in range(S_all.shape[0]):
        ref, ex_ref = collect_answers(S_all[lane], g, masks_host, k=k)
        ans, ex = got[lane]
        key = lambda a: (a.root, a.weight, tuple(sorted(a.edges)))
        assert [key(a) for a in ans] == [key(a) for a in ref]
        assert ex == ex_ref


def test_ragged_stragglers_fall_back_to_host():
    """A degree window smaller than the hub degree must produce the same
    answers anyway — via the host fallback."""
    seed = 5
    rng = np.random.default_rng(400)
    n = 16
    g = random_weighted_graph(n, 48, seed=seed)
    masks_host = np.zeros((2, n), bool)
    masks_host[0, rng.choice(n, 4, replace=False)] = True
    masks_host[1, rng.choice(n, 4, replace=False)] = True
    S_all, kw = lane_tables(g, masks_host, k=2)
    tight = BatchedBacktracer(g, degree_cap=1, buffer=3)
    got = tight.extract_lanes(S_all, kw, k=2, n_nodes=n)
    assert tight.host_fallbacks > 0, "tight caps should produce stragglers"
    for lane in range(S_all.shape[0]):
        ref, _ = collect_answers(S_all[lane], g, masks_host, k=2)
        ans, _ = got[lane]
        key = lambda a: (a.root, a.weight, tuple(sorted(a.edges)))
        assert [key(a) for a in ans] == [key(a) for a in ref]


# -- diversified ranking ------------------------------------------------


def test_tree_distance_extremes():
    a = tree(0, [(0, 1), (1, 2)], 2.0)
    b = tree(0, [(0, 1), (1, 2)], 2.0)
    c = tree(7, [(7, 8)], 1.0)
    assert tree_distance(a, b) == 0.0
    assert tree_distance(a, c) == 1.0
    assert 0.0 < tree_distance(a, tree(0, [(0, 1), (1, 3)], 2.0)) < 1.0


def test_diversified_order_is_permutation_and_leads_with_best():
    trees = [tree(0, [(0, 1), (1, 2)], 2.0),
             tree(0, [(0, 1), (1, 3)], 2.1),   # near-copy of #0
             tree(7, [(7, 8), (8, 9)], 2.2),   # disjoint
             tree(0, [(0, 1), (1, 4)], 2.3)]   # near-copy of #0
    order = diversified_order(trees, lambda_=0.5)
    assert sorted(order) == [0, 1, 2, 3]
    assert order[0] == 0
    # The disjoint tree outranks the near-copies under diversification.
    assert order[1] == 2
    # lambda_=1 reproduces weight order exactly.
    assert diversified_order(trees, lambda_=1.0) == [0, 1, 2, 3]
    with pytest.raises(ValueError):
        diversified_order(trees, lambda_=1.5)


def test_top_k_diverse_no_duplicates():
    trees = [tree(0, [(0, 1), (1, 2)], 2.0),
             tree(0, [(0, 1), (1, 2)], 2.0),   # exact duplicate
             tree(7, [(7, 8)], 3.0)]
    top = top_k_diverse(trees, 2, lambda_=0.5)
    assert len(top) == 2
    assert tree_distance(top[0], top[1]) > 0.0


def test_cluster_trees_groups_near_copies():
    trees = [tree(0, [(0, 1), (1, 2)], 2.0),
             tree(0, [(0, 1), (1, 3)], 2.1),
             tree(7, [(7, 8), (8, 9)], 2.2)]
    clusters = cluster_trees(trees, threshold=0.6)
    assert [0, 1] in clusters and [2] in clusters


# -- rendering / pagination ---------------------------------------------


def test_render_and_paginate():
    g = random_weighted_graph(6, 10, seed=1)
    trees = [tree(0, [(0, 1)], 1.0), tree(2, [(2, 3)], 1.5),
             tree(4, [(4, 5)], 2.0)]
    labels = {i: f"entity-{i}" for i in range(6)}
    page = paginate(trees, [0, 1, 2], cursor=0, page_size=2,
                    ranking="weight", exhausted=False,
                    label_fn=labels.get, graph=g)
    assert [t.root_label for t in page.items] == ["entity-0", "entity-2"]
    assert page.next_cursor == 2 and page.total == 3
    # Edge weights come from the graph, labels from label_fn.
    e = page.items[0].edges[0]
    assert e.u_label == "entity-0" and e.weight > 0.0
    assert "entity-0" in page.items[0].describe()
    # Last page: clamped cursor, next_cursor None.
    last = paginate(trees, [0, 1, 2], cursor=2, page_size=2,
                    ranking="weight", exhausted=True)
    assert len(last.items) == 1 and last.next_cursor is None
    assert last.exhausted
    # Default labels without a label_fn.
    assert last.items[0].root_label == "node:4"
    beyond = paginate(trees, [0, 1, 2], cursor=99, page_size=2,
                      ranking="weight", exhausted=False)
    assert beyond.items == () and beyond.next_cursor is None


def test_render_single_node_tree():
    t = AnswerTree(root=3, edges=(), weight=0.0, raw_value=0.0, nodes=(3,))
    rt = render_tree(t)
    assert "single node" in rt.describe()


# -- streaming extraction -----------------------------------------------


def test_extraction_overlap_matches_inline():
    rng = np.random.default_rng(7)
    n = 12
    g = random_weighted_graph(n, 30, seed=3)
    masks_host = np.zeros((2, n), bool)
    masks_host[0, rng.choice(n, 3, replace=False)] = True
    masks_host[1, rng.choice(n, 3, replace=False)] = True
    S_all, _ = lane_tables(g, masks_host, k=2, L=3)
    with ExtractionOverlap(g, k=2) as ov:
        ov.submit(0, S_all[0], masks_host)
        ov.submit(0, S_all[0], masks_host)  # idempotent per lane
        ov.submit(1, S_all[1], masks_host)
        assert ov.pending(0) and ov.pending(1) and not ov.pending(2)
        got0 = ov.result(0)
        got2 = ov.result(2, S_all[2], masks_host)  # inline path
        assert ov.overlapped == 2 and ov.inline == 1
        with pytest.raises(ValueError):
            ov.result(9)
    for lane, got in ((0, got0), (2, got2)):
        ref = collect_answers(S_all[lane], g, masks_host, k=2)
        key = lambda a: (a.root, a.weight, tuple(sorted(a.edges)))
        assert [key(a) for a in got[0]] == [key(a) for a in ref[0]]
        assert got[1] == ref[1]
