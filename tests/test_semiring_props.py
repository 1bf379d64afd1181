"""Property-based tests (hypothesis) for the system's invariants: the
top-K min-plus lattice, the SPA bounds, and the HLO analyzer."""

import numpy as np
import pytest

import jax.numpy as jnp

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro import INF
from repro.core import semiring
from repro.core.spa import nu_lower_bound, spa_cover_dp, split_pairs

ks = st.integers(1, 4)
vals = st.lists(st.integers(1, 30), min_size=1, max_size=12)


def to_vec(xs, k):
    v = jnp.asarray(sorted(set(xs))[:k] + [INF] * k, jnp.float32)[:k]
    return v


@settings(max_examples=40, deadline=None)
@given(a=vals, b=vals, k=ks)
def test_topk_merge_commutative_associative_idempotent(a, b, k):
    va, vb = to_vec(a, k), to_vec(b, k)
    ab = semiring.topk_merge(va, vb)
    ba = semiring.topk_merge(vb, va)
    np.testing.assert_array_equal(np.asarray(ab), np.asarray(ba))
    # Idempotent: merging a vector with itself is a no-op.
    np.testing.assert_array_equal(
        np.asarray(semiring.topk_merge(va, va)), np.asarray(va))
    # Merge result equals brute force top-k distinct.
    brute = sorted(set([float(x) for x in list(va) + list(vb) if x < INF]))
    brute = (brute + [INF] * k)[:k]
    np.testing.assert_allclose(np.asarray(ab), brute)


@settings(max_examples=40, deadline=None)
@given(a=vals, b=vals, k=ks)
def test_outer_combine_matches_bruteforce(a, b, k):
    va, vb = to_vec(a, k), to_vec(b, k)
    got = semiring.outer_combine(va, vb)
    sums = sorted({float(x) + float(y) for x in va for y in vb
                   if x < INF and y < INF})
    want = (sums + [INF] * k)[:k]
    np.testing.assert_allclose(np.asarray(got), np.minimum(want, INF),
                               rtol=1e-6)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(5, 40), v=st.integers(2, 10), k=ks,
       seed=st.integers(0, 99))
def test_segment_topk_matches_numpy(n, v, k, seed):
    rng = np.random.default_rng(seed)
    vals_ = rng.integers(1, 50, n).astype(np.float32)
    seg = rng.integers(0, v, n).astype(np.int32)
    got = np.asarray(semiring.segment_topk_min(
        jnp.asarray(vals_), jnp.asarray(seg), v, k))
    for s in range(v):
        mine = sorted(set(vals_[seg == s]))[:k]
        mine = mine + [INF] * (k - len(mine))
        np.testing.assert_allclose(got[s], mine)


@settings(max_examples=30, deadline=None)
@given(m=st.integers(2, 5), seed=st.integers(0, 99))
def test_nu_lower_bound_sound_vs_cover(m, seed):
    """nu[full] is a valid lower bound: it never exceeds any achievable
    combination of g-values + one arrival step."""
    rng = np.random.default_rng(seed)
    g = rng.integers(1, 20, 1 << m).astype(np.float32)
    g[0] = INF
    # Randomly mark some sets unseen.
    g[rng.random(1 << m) < 0.3] = INF
    e_min = 1.0
    nu = np.asarray(nu_lower_bound(jnp.asarray(g), jnp.float32(e_min), m))
    full = (1 << m) - 1
    # Direct arrival bound.
    assert nu[full] <= g[full] + e_min + 1e-5
    # Any split with one arrival must dominate nu.
    for t, a, b in split_pairs(m):
        if t == full and g[a] < INF and g[b] < INF:
            assert nu[full] <= min(g[a] + e_min + g[b],
                                   g[a] + g[b] + e_min) + 1e-4


@settings(max_examples=30, deadline=None)
@given(m=st.integers(2, 4), seed=st.integers(0, 99))
def test_spa_cover_dp_is_min_cover(m, seed):
    """On monotone path-length estimates (real DKS tables are monotone in
    set inclusion), the cover DP equals the brute-force minimum cover."""
    import itertools

    rng = np.random.default_rng(seed)
    shat = rng.integers(1, 30, 1 << m).astype(np.float64)
    shat[0] = 0.0
    # Monotonize: superset >= any subset (path-length property).
    full = (1 << m) - 1
    for t in sorted(range(1, full + 1), key=lambda x: bin(x).count("1")):
        a = (t - 1) & t
        while a:
            shat[t] = max(shat[t], shat[a])
            a = (a - 1) & t
    shat[0] = INF
    got = float(spa_cover_dp(jnp.asarray(shat, jnp.float32), m))
    best = INF
    sets = list(range(1, full + 1))
    for r in range(1, m + 1):
        for combo in itertools.combinations(sets, r):
            u = 0
            for c in combo:
                u |= c
            if u == full:
                best = min(best, float(sum(shat[c] for c in combo)))
    assert got == pytest.approx(best, abs=1e-3)


def test_hlo_analyzer_counts_loop_multipliers():
    import jax
    from repro.analysis import analyze_hlo

    def f(x, w):
        def body(x, _):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(body, x, None, length=12)
        return y.sum()

    c = jax.jit(f).lower(jnp.ones((64, 64)), jnp.ones((64, 64))).compile()
    s = analyze_hlo(c.as_text())
    # 12 iterations x 2*64^3 flops
    assert abs(s.dot_flops - 12 * 2 * 64**3) / (12 * 2 * 64**3) < 0.01
    assert s.static_loops == 1 and s.dynamic_loops == 0


def test_hlo_analyzer_dynamic_loop_flagged():
    import jax
    from repro.analysis import analyze_hlo

    def f(x):
        def cond(c):
            return c[0].sum() > 0
        def body(c):
            return (c[0] - 0.1, c[1] @ c[1])
        return jax.lax.while_loop(cond, body, (x, x))[1]

    c = jax.jit(f).lower(jnp.ones((8, 8))).compile()
    s = analyze_hlo(c.as_text())
    assert s.dynamic_loops >= 1


tied = st.sampled_from([1.0, 2.0, 3.0, INF])


@settings(max_examples=40, deadline=None)
@given(b=st.integers(1, 3), n=st.integers(1, 6), m=st.integers(1, 3),
       data=st.data())
def test_smallest_k_2d_matches_flat_top_k(b, n, m, data):
    """Same values and flat indices as ``lax.top_k`` of the negated,
    flattened input: many ties (lower index first), all-INF rows, and k
    up to N*M."""
    import jax
    k = data.draw(st.integers(1, n * m))
    x = np.asarray(data.draw(st.lists(tied, min_size=b * n * m,
                                      max_size=b * n * m)),
                   np.float32).reshape(b, n, m)
    x[0] = INF                                    # one all-INF table
    got_v, got_i = semiring.smallest_k_2d(jnp.asarray(x), k)
    neg, want_i = jax.lax.top_k(-jnp.asarray(x).reshape(b, n * m), k)
    np.testing.assert_array_equal(np.asarray(got_v), -np.asarray(neg))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 30), v=st.integers(1, 8), f=st.integers(1, 3),
       p=st.integers(1, 3), k=ks, seed=st.integers(0, 99))
def test_segment_topk_pooled_matches_folded_rows(n, v, f, p, k, seed):
    """``pooled=True`` reduces the last axis with the rows: the same
    result as folding it into the row axis (tied and INF candidates
    included)."""
    rng = np.random.default_rng(seed)
    vals_ = rng.choice(np.float32([1, 2, 3, 5, INF]), (n, f, p))
    seg = rng.integers(0, v, n).astype(np.int32)
    got = semiring.segment_topk_min(jnp.asarray(vals_), jnp.asarray(seg),
                                    v, k, pooled=True)
    folded = vals_.transpose(0, 2, 1).reshape(n * p, f)
    want = semiring.segment_topk_min(jnp.asarray(folded),
                                     jnp.asarray(np.repeat(seg, p)), v, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
