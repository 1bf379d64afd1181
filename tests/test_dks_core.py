"""Core DKS engine vs. exact oracles (paper Theorem 1 / Def. 2.2)."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro import INF
from repro.core import (
    DKSConfig, run_dks, extract_answers, dreyfus_wagner, brute_force_topk,
)
from repro.core import dks as dks_mod
from repro.graph.generators import grid_graph, random_weighted_graph
from repro.graph.structure import build_graph


def make_masks(groups, n_nodes):
    m = np.zeros((len(groups), n_nodes), bool)
    for i, grp in enumerate(groups):
        m[i, list(grp)] = True
    return m


def run_engine(g, groups, k=1, **kw):
    masks = make_masks(groups, g.n_nodes)
    dg = g.to_device()
    cfg = DKSConfig(m=len(groups), k=k, **kw)
    state = run_dks(dg, jnp.asarray(masks), cfg)
    return state, cfg, masks


def test_single_edge():
    #  0 --1-- 1 ; query {0}, {1}
    g = build_graph([0], [1], 2, w=np.asarray([1.0], np.float32))
    state, cfg, _ = run_engine(g, [[0], [1]])
    assert float(state.topk_w[0]) == 1.0


def test_path_graph_root_in_middle():
    # 0-1-2-3-4 unit weights, keywords at ends -> optimum 4.
    g = build_graph([0, 1, 2, 3], [1, 2, 3, 4], 5,
                    w=np.ones(4, np.float32))
    state, _, _ = run_engine(g, [[0], [4]])
    assert float(state.topk_w[0]) == 4.0


def test_star_answer_tree():
    # Paper Fig. 1 style: center 0, leaves 1,2,3 with weights 1,2,3.
    g = build_graph([0, 0, 0], [1, 2, 3], 4,
                    w=np.asarray([1, 2, 3], np.float32))
    state, cfg, masks = run_engine(g, [[1], [2], [3]])
    assert float(state.topk_w[0]) == 6.0
    answers = extract_answers(np.asarray(state.S), g, masks, k=1)
    assert answers[0].weight == 6.0
    assert answers[0].root == 0 or len(answers[0].edges) == 3


def test_unbalanced_tree_needs_deep_messages():
    # Paper Fig. 4(a): BFS alone only finds root-balanced trees.  Chain
    # q1 -1- a -1- b -1- q2 with q2 also 10 away from q1 directly.
    # Optimal tree is the chain (weight 3), whose best root is unbalanced.
    g = build_graph([0, 1, 2, 0], [1, 2, 3, 3], 4,
                    w=np.asarray([1, 1, 1, 10], np.float32))
    state, _, _ = run_engine(g, [[0], [3]])
    assert float(state.topk_w[0]) == 3.0


def test_multi_keyword_node():
    # One node contains both keywords -> weight 0.
    g = build_graph([0], [1], 2, w=np.asarray([1.0], np.float32))
    groups = [[0], [0]]
    state, _, _ = run_engine(g, groups)
    assert float(state.topk_w[0]) == 0.0


def test_infeasible_query_terminates():
    # Keyword 1 exists nowhere.
    g = build_graph([0], [1], 2, w=np.asarray([1.0], np.float32))
    state, _, _ = run_engine(g, [[0], []])
    assert float(state.topk_w[0]) >= INF
    assert bool(state.done)


@pytest.mark.parametrize("seed", range(6))
def test_random_graphs_match_dreyfus_wagner(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(6, 14))
    g = random_weighted_graph(n, n + int(rng.integers(0, 8)), seed=seed)
    m = int(rng.integers(2, 4))
    groups = [rng.choice(n, size=int(rng.integers(1, 3)), replace=False)
              for _ in range(m)]
    opt = dreyfus_wagner(g, groups)
    state, _, _ = run_engine(g, groups, max_supersteps=64)
    got = float(state.topk_w[0])
    assert got == pytest.approx(opt, abs=1e-3), f"engine {got} vs DW {opt}"


@pytest.mark.parametrize("seed", range(4))
def test_topk_answers_match_brute_force(seed):
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(5, 8))
    g = random_weighted_graph(n, n + 2, seed=seed, max_w=4)
    groups = [[int(rng.integers(0, n))] for _ in range(2)]
    k = 3
    # Full list of achievable minimal-tree weights (large K).
    all_weights = [w for w in brute_force_topk(g, groups, 50) if w < INF]
    state, cfg, masks = run_engine(g, groups, k=k, max_supersteps=64)
    answers = extract_answers(np.asarray(state.S), g, masks, k=k)
    got = sorted({a.weight for a in answers})
    # Engine answers must (a) include the optimum, (b) be true tree weights.
    assert got[0] == pytest.approx(all_weights[0], abs=1e-3)
    for w in got:
        assert any(abs(w - e) < 1e-3 for e in all_weights), (
            f"weight {w} is not an achievable minimal-tree weight {all_weights}")
    # Every returned answer's true weight never exceeds its DP value.
    for a in answers:
        assert a.weight <= a.raw_value + 1e-3


def test_early_exit_never_misses_optimum():
    # exit_mode="sound" must match a run with no early exit.
    for seed in range(5):
        g = random_weighted_graph(12, 20, seed=seed)
        rng = np.random.default_rng(seed)
        groups = [[int(rng.integers(0, 12))] for _ in range(3)]
        s_exit, _, _ = run_engine(g, groups, k=2, exit_mode="sound")
        s_full, _, _ = run_engine(g, groups, k=2, exit_mode="none",
                                  max_supersteps=128)
        np.testing.assert_allclose(
            np.asarray(s_exit.topk_w), np.asarray(s_full.topk_w), atol=1e-3)
        # And the early exit actually exits earlier or at the same step.
        assert int(s_exit.step) <= int(s_full.step)


def test_grid_graph_exact():
    g = grid_graph(4, 4)
    groups = [[0], [15], [3]]
    opt = dreyfus_wagner(g, groups)
    state, _, _ = run_engine(g, groups)
    assert float(state.topk_w[0]) == pytest.approx(opt)


def test_message_budget_forces_stop():
    g = grid_graph(6, 6)
    groups = [[0], [35]]
    state, _, _ = run_engine(g, groups, message_budget=10.0)
    assert bool(state.done)
    assert bool(state.budget_hit)


def test_explored_fraction_less_than_full():
    # Early exit should leave part of the graph unexplored (paper Fig. 13).
    g = grid_graph(12, 12)
    groups = [[0], [1]]
    state, _, _ = run_engine(g, groups, exit_mode="sound", max_supersteps=64)
    explored = float(jnp.mean(state.visited[: g.n_nodes]))
    assert explored < 0.9


@pytest.mark.parametrize("m", [2, 3, 4, 5, 6])
def test_nu_lower_bound_levels_equal_pair_sweep(m):
    """The exit bound, one vectorized min per popcount level, equals the
    pair-by-pair sweep in popcount order bit for bit, unseen sets
    included."""
    from repro.core.spa import nu_lower_bound, split_pairs

    rng = np.random.default_rng(m)
    for _ in range(5):
        g = rng.integers(0, 40, 1 << m).astype(np.float32) / 4
        g[rng.random(1 << m) < 0.3] = INF
        e_min = np.float32(rng.integers(1, 4) / 2)
        want = jnp.minimum(jnp.asarray(g) + e_min, INF).at[0].set(INF)
        for t, a, b in split_pairs(m):
            cand = jnp.minimum(jnp.minimum(want[a] + g[b], g[a] + want[b]),
                               want[a] + want[b])
            want = want.at[t].min(jnp.minimum(cand, INF))
        got = nu_lower_bound(jnp.asarray(g), jnp.float32(e_min), m)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
