"""QueryEngine facade tests: parity with the raw core entry points,
m-bucketed batching, streaming bound monotonicity, and compiled-executable
cache reuse (no re-tracing for repeated query shapes)."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro import INF
from repro.core import DKSConfig, extract_answers, run_dks
from repro.engine import ExecutionPolicy, QueryEngine, WeightPolicy
from repro.graph.generators import lod_like_graph
from repro.graph.index import InvertedIndex


@pytest.fixture(scope="module")
def setup():
    g, tokens = lod_like_graph(600, 1800, seed=11, vocab=120)
    index = InvertedIndex.from_token_matrix(tokens)
    engine = QueryEngine.build(
        g, index=index, policy=ExecutionPolicy(max_supersteps=32))
    return g, index, engine


def mid_df_tokens(index, n, lo=2, hi=60):
    """n tokens with moderate document frequency (answerable queries)."""
    toks = [t for t in sorted(index.vocabulary(), key=index.df)
            if lo <= index.df(t) <= hi]
    assert len(toks) >= n
    return toks[:n]


def test_query_matches_raw_run_dks(setup):
    g, index, engine = setup
    query = mid_df_tokens(index, 3)
    k = 2
    res = engine.query(query, k=k)

    masks = index.keyword_masks(query, g.n_nodes,
                                v_pad=engine.device_graph.v_pad)
    cfg = DKSConfig(m=len(query), k=k, max_supersteps=32)
    state = run_dks(engine.device_graph, jnp.asarray(masks), cfg)
    np.testing.assert_allclose(res.weights, np.asarray(state.topk_w))
    assert res.supersteps == int(state.step)
    assert res.msgs_bfs == float(state.msgs_bfs)
    assert res.msgs_deep == float(state.msgs_deep)

    raw_answers = extract_answers(np.asarray(state.S), g,
                                  masks[:, : g.n_nodes], k=k)
    assert [(a.weight, a.edges) for a in res.answers] == \
           [(a.weight, a.edges) for a in raw_answers]
    assert res.found and res.best.weight == res.answers[0].weight


def test_query_batch_matches_per_query_runs(setup):
    g, index, engine = setup
    toks = mid_df_tokens(index, 10)
    # Mixed keyword counts force m-bucketing (2- and 3-keyword buckets).
    queries = [toks[0:2], toks[2:5], toks[5:7], toks[7:10]]
    batched = engine.query_batch(queries, k=2)
    assert len(batched) == len(queries)
    for q, br in zip(queries, batched):
        sr = engine.query(q, k=2)
        assert br.query == tuple(q) and br.m == len(q)
        np.testing.assert_allclose(br.weights, sr.weights)
        assert br.supersteps == sr.supersteps
        # Finished lanes are frozen, so batched counters match exactly even
        # though the vmapped while-loop runs until the slowest query exits.
        assert br.msgs_bfs == sr.msgs_bfs
        assert br.msgs_deep == sr.msgs_deep
        assert [a.weight for a in br.answers] == [a.weight for a in sr.answers]


def test_query_stream_bound_never_worsens(setup):
    g, index, engine = setup
    query = mid_df_tokens(index, 3)
    updates = list(engine.query_stream(query, k=1))
    assert updates, "stream yielded nothing"
    ratios = [u.spa_ratio for u in updates]
    # inf while no answer is known, then monotone non-increasing.
    for prev, cur in zip(ratios, ratios[1:]):
        assert cur <= prev, f"SPA ratio worsened: {ratios}"
    # Steps advance one superstep at a time.
    assert [u.step for u in updates] == list(range(len(updates)))
    last = updates[-1]
    assert last.done
    # Sound exit without a budget: the final answer is proven optimal.
    assert last.spa_ratio == 0.0 and last.proven_optimal
    # And the streamed final weights match the one-shot query.
    res = engine.query(query, k=1)
    np.testing.assert_allclose(last.weights, res.weights)


def test_compiled_executable_cache_reuse(setup):
    g, index, engine = setup
    toks = mid_df_tokens(index, 8)
    before = engine.cache_stats["traces"]
    engine.query(toks[0:3], k=3, extract=False)
    engine.query(toks[3:6], k=3, extract=False)
    engine.query(toks[5:8], k=3, extract=False)
    # Three same-(m, k) queries -> exactly one trace.
    assert engine.trace_count(3, 3) == 1
    assert engine.cache_stats["traces"] == before + 1
    # A different shape compiles its own executable once.
    engine.query(toks[0:2], k=3, extract=False)
    engine.query(toks[2:4], k=3, extract=False)
    assert engine.trace_count(2, 3) == 1


def test_policy_overrides_key_the_cache(setup):
    g, index, engine = setup
    toks = mid_df_tokens(index, 2)
    r1 = engine.query(toks, k=1, extract=False)
    r2 = engine.query(toks, k=1, extract=False, message_budget=10.0)
    assert r2.budget_hit and not r1.budget_hit
    assert engine.trace_count(2, 1) == 1
    assert engine.trace_count(2, 1, message_budget=10.0) == 1


def test_keyword_masks_v_pad():
    idx = InvertedIndex.from_token_matrix(
        np.asarray([[0, 1], [1, 2], [2, 0]], np.int32))
    masks = idx.keyword_masks([1, 2], 3, v_pad=8)
    assert masks.shape == (2, 8)
    assert masks[:, 3:].sum() == 0
    np.testing.assert_array_equal(
        masks[:, :3], idx.keyword_masks([1, 2], 3))
    with pytest.raises(ValueError):
        idx.keyword_masks([1], 3, v_pad=2)


def test_build_from_labels():
    from repro.graph.structure import build_graph
    g = build_graph([0, 1], [1, 2], 3, w=np.ones(2, np.float32),
                    labels=["red piano", "blue piano", "red door"])
    engine = QueryEngine.build(g)
    res = engine.query(["blue", "door"], k=1)
    assert res.found
    assert res.best_weight == 1.0  # blue@1 -- door@2 over the unit edge


def test_capped_run_is_not_certified_optimal():
    """A run truncated by max_supersteps must report capped (with an SPA
    ratio), never a proven-optimal answer — the heavy direct edge is found
    early, the cheap long path only after more supersteps."""
    from repro.graph.structure import build_graph
    # Direct edge 0-1 of weight 100 vs a cheap 10-hop unit path 0-2-...-10-1.
    src = [0, 0] + list(range(2, 10)) + [10]
    dst = [1, 2] + list(range(3, 11)) + [1]
    w = np.asarray([100.0] + [1.0] * 10, np.float32)
    g = build_graph(src, dst, 11, w=w)
    tokens = np.arange(11, dtype=np.int32).reshape(11, 1)  # node i holds tok i
    engine = QueryEngine.build(g, tokens=tokens)

    trunc = engine.query([0, 1], k=1, max_supersteps=2)
    assert trunc.best_weight == 100.0
    assert trunc.capped and trunc.done and not trunc.budget_hit
    assert trunc.spa is not None and trunc.spa_ratio > 0.0

    updates = list(engine.query_stream([0, 1], k=1, max_supersteps=2))
    assert not updates[-1].proven_optimal

    full = engine.query([0, 1], k=1)
    assert full.best_weight == 10.0  # the cheap path, proven
    assert not full.capped and full.spa_ratio == 0.0 and full.spa is None


def test_infeasible_query(setup):
    g, index, engine = setup
    missing = max(index.vocabulary()) + 1000
    # strict (default): unmatched keywords are a hard error naming the token.
    with pytest.raises(KeyError, match=str(missing)):
        engine.query([missing, missing + 1], k=1)
    # best-effort: INF answer, and the result says *why*.
    res = engine.query([missing, missing + 1], k=1, strict=False)
    assert not res.found and res.answers == []
    assert res.done and not res.budget_hit
    assert res.weights[0] >= INF
    assert res.unmatched == (missing, missing + 1)
    # The streaming surface carries the same diagnosis on every update,
    # and strict validation fires at the call site (not first iteration).
    with pytest.raises(KeyError):
        engine.query_stream([missing], k=1)
    ups = list(engine.query_stream([missing, missing + 1], k=1,
                                   strict=False))
    assert ups and ups[0].unmatched == (missing, missing + 1)
    seen = []
    engine.query_streamed([missing, missing + 1], k=1, strict=False,
                          extract=False, on_update=seen.append)
    assert seen and seen[0].unmatched == (missing, missing + 1)


def test_partially_matched_query_reports_unmatched(setup):
    g, index, engine = setup
    tok = index.vocabulary()[0]
    missing = max(index.vocabulary()) + 1000
    with pytest.raises(KeyError):
        engine.query([tok, missing], k=1)
    res = engine.query([tok, missing], k=1, strict=False)
    assert res.unmatched == (missing,)
    matched = engine.query([tok, index.vocabulary()[1]], k=1)
    assert matched.unmatched == ()


def test_own_time_reporting(setup):
    """own_time_s: per-query serve time where measurable — equal to the
    wall time on single-query surfaces, None inside a vmapped bucket."""
    g, index, engine = setup
    toks = mid_df_tokens(index, 4)
    res = engine.query(toks[:2], k=1, extract=False)
    assert res.own_time_s == res.wall_time_s and res.own_time_s > 0
    batched = engine.query_batch([toks[0:2], toks[2:4]], k=1, extract=False)
    assert all(b.own_time_s is None for b in batched)


def test_query_deadline_hook(setup):
    """The serving hook: wall-clock-bounded stepping, bounds computed once
    at the end (valid, though not the stream's running max)."""
    g, index, engine = setup
    q = mid_df_tokens(index, 3)
    full = engine.query(q, k=1, extract=False)
    res, info = engine.query_deadline(q, k=1, extract=False,
                                      deadline_s=120.0)
    assert not info["interrupted"] and res.done
    np.testing.assert_allclose(res.weights, full.weights)
    # A proven exit certifies the best answer soundly; both bounds say so.
    assert info["sound_opt_lower_bound"] == res.best_weight
    assert info["opt_lower_bound"] == res.best_weight
    trunc, info2 = engine.query_deadline(q, k=1, extract=False,
                                         deadline_s=0.0)
    assert info2["interrupted"] and not trunc.done
    assert trunc.spa is not None  # forced-stop SPA on the result
    # Valid bracket around the optimum.
    assert info2["sound_opt_lower_bound"] <= info2["opt_lower_bound"] + 1e-6
    assert info2["sound_opt_lower_bound"] <= full.best_weight + 1e-5
    assert trunc.weights[0] >= full.weights[0] - 1e-5


def test_query_deadline_batch_per_lane_bounds(setup):
    """A deadline bucket of heterogeneous same-m queries rides ONE lane
    driver; every lane gets its own best-so-far answer with a valid
    per-lane bound bracket, and with a generous budget the bucket costs
    max(lane supersteps), not the sum."""
    g, index, engine = setup
    toks = mid_df_tokens(index, 6)
    queries = [toks[0:3], toks[3:6]]
    fulls = [engine.query(q, k=1, extract=False) for q in queries]
    out = engine.query_deadline_batch(queries, k=1, extract=False,
                                      deadline_s=0.0)
    assert len(out) == 2
    for (res, info), full in zip(out, fulls):
        assert info["interrupted"] and not res.done
        assert res.spa is not None  # per-lane forced-stop SPA
        # Valid per-lane bracket: sound <= reported <= optimum <= best.
        assert info["sound_opt_lower_bound"] <= \
            info["opt_lower_bound"] + 1e-6
        assert info["sound_opt_lower_bound"] <= full.best_weight + 1e-5
        assert res.weights[0] >= full.weights[0] - 1e-5
        assert res.own_time_s is not None and res.own_time_s > 0

    out2 = engine.query_deadline_batch(queries, k=1, extract=False,
                                       deadline_s=120.0)
    for (res, info), full in zip(out2, fulls):
        assert not info["interrupted"] and res.done
        np.testing.assert_allclose(res.weights, full.weights)
        # Lanes freeze individually: per-lane counters match solo runs...
        assert res.supersteps == full.supersteps
        # ...while the shared driver stepped only as far as the slowest.
        assert info["driver_supersteps"] == \
            max(f.supersteps for f in fulls)
        assert info["opt_lower_bound"] == res.best_weight

    # Padding lanes (serving hook) skip result construction.
    padded = engine.query_deadline_batch(queries + [queries[-1]], k=1,
                                         extract=False, deadline_s=120.0,
                                         n_real=2)
    assert padded[2] is None and padded[0] is not None

    # A bucket cannot mix keyword counts (one driver = one table shape).
    with pytest.raises(ValueError, match="same keyword count"):
        engine.query_deadline_batch([toks[0:2], toks[0:3]], k=1,
                                    deadline_s=1.0)


def test_query_batch_n_real_skips_padding(setup):
    """The serving hook: padding lanes (index >= n_real) ride the vmapped
    program but skip host-side result construction, returning None."""
    g, index, engine = setup
    toks = mid_df_tokens(index, 4)
    queries = [toks[0:2], toks[2:4], toks[2:4]]
    out = engine.query_batch(queries, k=1, extract=False, n_real=2)
    assert out[2] is None
    refs = engine.query_batch(queries[:2], k=1, extract=False)
    for served, ref in zip(out[:2], refs):
        np.testing.assert_allclose(served.weights, ref.weights)


def test_engine_reexports_from_core():
    import repro.core as core
    assert core.QueryEngine is QueryEngine
    assert core.ExecutionPolicy is ExecutionPolicy
    with pytest.raises(AttributeError):
        core.not_a_symbol


# ---------------------------------------------------------------------------
# Sharded partition in-process (1 local device -> 1-shard mesh).  The full
# multi-device story lives in tests/test_distributed.py; these tier-1 tests
# keep the shard_map code path and its engine plumbing exercised on every
# pytest run.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sharded_setup(setup):
    g, index, _ = setup
    engine = QueryEngine.build(
        g, index=index,
        policy=ExecutionPolicy(partition="sharded", max_supersteps=32,
                               frontier_frac=1.0))
    return g, index, engine


def test_sharded_engine_matches_single_inprocess(setup, sharded_setup):
    _, index, single = setup
    _, _, sharded = sharded_setup
    assert sharded.mesh is not None
    query = mid_df_tokens(index, 3)
    rs = single.query(query, k=2, extract=False)
    rh = sharded.query(query, k=2, extract=False)
    np.testing.assert_array_equal(rs.weights, rh.weights)
    assert rs.supersteps == rh.supersteps
    assert not rh.budget_hit


def test_sharded_engine_stream_inprocess(sharded_setup):
    _, index, sharded = sharded_setup
    query = mid_df_tokens(index, 2)
    updates = list(sharded.query_stream(query, k=1))
    assert updates and updates[-1].done
    ratios = [u.spa_ratio for u in updates]
    assert all(cur <= prev for prev, cur in zip(ratios, ratios[1:]))
    res = sharded.query(query, k=1, extract=False)
    np.testing.assert_array_equal(updates[-1].weights, res.weights)


def test_sharded_query_batch_one_execution_per_bucket(setup, sharded_setup):
    """The restored sharded batch win: a bucket of same-m queries rides
    the lane driver as ONE device execution (the lane axis lives inside
    the shard_map body — no sequential fallback, no vmap-over-shard_map),
    and the answers are bit-identical to the dense batch."""
    _, index, single = setup
    _, _, sharded = sharded_setup
    toks = mid_df_tokens(index, 7)
    queries = [toks[0:2], toks[2:4], toks[4:7]]  # two m=2, one m=3
    before = sharded.execute_count
    results = sharded.query_batch(queries, k=1, extract=False)
    # Two m-buckets -> exactly two device executions, regardless of
    # bucket size (the acceptance criterion: count dispatches, not time).
    assert sharded.execute_count == before + 2
    t2a, t2b, t3 = (results[0].wall_time_s, results[1].wall_time_s,
                    results[2].wall_time_s)
    # Same-m queries share one bucket and must report one shared time;
    # lanes advance in lockstep, so there is no honest per-query time.
    assert t2a == t2b
    assert t2a > 0 and t3 > 0
    assert all(br.own_time_s is None for br in results)
    dense = single.query_batch(queries, k=1, extract=False)
    for q, br, dr in zip(queries, results, dense):
        np.testing.assert_array_equal(br.weights, dr.weights)
        assert br.supersteps == dr.supersteps
        assert br.msgs_bfs == dr.msgs_bfs and br.msgs_deep == dr.msgs_deep
        sr = sharded.query(q, k=1, extract=False)
        np.testing.assert_array_equal(br.weights, sr.weights)


def test_sharded_query_instrumented(setup, sharded_setup):
    """The partition='single' restriction is lifted: the sharded engine
    serves query_instrumented with the same timings/history contract and
    parity with the dense path."""
    _, index, single = setup
    _, _, sharded = sharded_setup
    query = mid_df_tokens(index, 2)
    res, info = sharded.query_instrumented(query, k=1, extract=False,
                                           max_supersteps=24)
    ref = single.query(query, k=1, extract=False, max_supersteps=24)
    np.testing.assert_allclose(res.weights, ref.weights)
    assert set(info["timings"]) == \
        {"send_bfs", "receive", "evaluate", "send_agg"}
    assert all(v >= 0 for v in info["timings"].values())
    assert res.supersteps == len(info["history"])
    assert info["history"][-1]["best"] == ref.best_weight


# ----------------------------------------------------------------------
# Fused pallas lane-superstep kernel (interpret mode on CPU)
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def pallas_setup():
    """A jnp engine and its pallas twin over one graph, small enough for
    the interpret-mode kernel to stay CI-speed."""
    g, tokens = lod_like_graph(300, 1200, seed=7, vocab=80)
    index = InvertedIndex.from_token_matrix(tokens)
    ej = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
        backend="jnp", max_supersteps=16))
    ep = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
        backend="pallas", max_supersteps=16))
    assert ep.lane_csr is not None  # built once per graph at build()
    return index, ej, ep


def test_pallas_query_bit_identical(pallas_setup):
    index, ej, ep = pallas_setup
    query = mid_df_tokens(index, 3)
    rj = ej.query(query, k=2, extract=False)
    rp = ep.query(query, k=2, extract=False)
    np.testing.assert_array_equal(rp.weights, rj.weights)
    assert rp.supersteps == rj.supersteps
    assert rp.msgs_bfs == rj.msgs_bfs and rp.msgs_deep == rj.msgs_deep


def test_pallas_query_batch_bit_identical(pallas_setup):
    index, ej, ep = pallas_setup
    toks = mid_df_tokens(index, 8)
    queries = [toks[0:2], toks[2:5], toks[5:8], toks[1:3]]
    bj = ej.query_batch(queries, k=2, extract=False)
    bp = ep.query_batch(queries, k=2, extract=False)
    for rj, rp in zip(bj, bp):
        np.testing.assert_array_equal(rp.weights, rj.weights)
        assert rp.supersteps == rj.supersteps


def test_pallas_stream_bit_identical(pallas_setup):
    index, ej, ep = pallas_setup
    query = mid_df_tokens(index, 3)
    upd_j, upd_p = [], []
    rj = ej.query_streamed(query, k=2, on_update=upd_j.append,
                           extract=False)
    rp = ep.query_streamed(query, k=2, on_update=upd_p.append,
                           extract=False)
    np.testing.assert_array_equal(rp.weights, rj.weights)
    # The whole per-superstep trajectory matches, not just the answer.
    assert len(upd_p) == len(upd_j)
    for uj, up in zip(upd_j, upd_p):
        assert up.step == uj.step and up.frontier == uj.frontier
        assert up.best_weight == uj.best_weight


def test_pallas_deadline_bit_identical(pallas_setup):
    index, ej, ep = pallas_setup
    query = mid_df_tokens(index, 3)
    rj, _ = ej.query_deadline(query, k=2, deadline_s=60.0, extract=False)
    rp, _ = ep.query_deadline(query, k=2, deadline_s=60.0, extract=False)
    np.testing.assert_array_equal(rp.weights, rj.weights)
    assert rp.supersteps == rj.supersteps
    # A deadline bucket shares one driver, so both lanes need the same m.
    toks = mid_df_tokens(index, 6)
    bucket = [toks[:3], toks[3:6]]
    out_j = ej.query_deadline_batch(
        bucket, k=2, deadline_s=60.0, extract=False)
    out_p = ep.query_deadline_batch(
        bucket, k=2, deadline_s=60.0, extract=False)
    for (qj, _), (qp, _) in zip(out_j, out_p):
        np.testing.assert_array_equal(qp.weights, qj.weights)


def test_pallas_telemetry_buffer_bit_identical(pallas_setup):
    """telemetry=True rides the same fused loop: the per-superstep
    counter rows AND the answers must match the jnp telemetry path
    exactly."""
    index, ej, ep = pallas_setup
    g = ej.graph
    tj = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
        backend="jnp", max_supersteps=16, telemetry=True))
    tp = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
        backend="pallas", max_supersteps=16, telemetry=True))
    query = mid_df_tokens(index, 3)
    rj = tj.query(query, k=2, extract=False)
    rp = tp.query(query, k=2, extract=False)
    np.testing.assert_array_equal(rp.weights, rj.weights)
    assert rj.telemetry is not None and rp.telemetry is not None
    assert rp.telemetry.rows() == rj.telemetry.rows()
    # And telemetry-on matches telemetry-off on the pallas path.
    base = pallas_setup[2].query(query, k=2, extract=False)
    np.testing.assert_array_equal(rp.weights, base.weights)


def test_pallas_typed_weight_policy_bit_identical():
    """Effective WeightPolicy weights (typed channel) flow through the
    LaneCSR layout: confidence-blended and predicate-filtered engines
    answer bit-identically on both backends."""
    from tests.test_weights import typed_diamond

    g, index = typed_diamond()
    for wp in (WeightPolicy(kind="confidence", blend=1.0),
               WeightPolicy(predicates=("knows", "funds"))):
        ej = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
            backend="jnp", max_supersteps=8, weights=wp))
        ep = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
            backend="pallas", max_supersteps=8, weights=wp))
        rj = ej.query(["alpha", "beta"], k=2, extract=False)
        rp = ep.query(["alpha", "beta"], k=2, extract=False)
        np.testing.assert_array_equal(rp.weights, rj.weights)
        assert rp.supersteps == rj.supersteps


def test_pallas_ragged_frontier_lane_frozen_mid_bucket(pallas_setup):
    """A bucket whose lanes finish at different supersteps: once a lane's
    exit fires its frontier is empty and the kernel's per-lane freeze
    mask must hold its table at s0 while other lanes keep relaxing."""
    index, ej, ep = pallas_setup
    toks = mid_df_tokens(index, 6)
    # Same-m bucket, different finishing times (different keyword sets).
    queries = [toks[0:3], toks[3:6]]
    bj = ej.query_batch(queries, k=1, extract=False)
    bp = ep.query_batch(queries, k=1, extract=False)
    steps = {r.supersteps for r in bj}
    assert len(steps) >= 1  # trajectory lengths may or may not differ...
    for rj, rp in zip(bj, bp):
        np.testing.assert_array_equal(rp.weights, rj.weights)
        assert rp.supersteps == rj.supersteps
        # Frozen lanes stop accumulating: message counters must match the
        # per-query runs exactly (the freeze-mask acceptance check).
        assert rp.msgs_bfs == rj.msgs_bfs
        assert rp.msgs_deep == rj.msgs_deep


def test_pallas_executable_cache_no_retrace(pallas_setup):
    index, _, ep = pallas_setup
    query = mid_df_tokens(index, 3)
    ep.query(query, k=2, extract=False)
    traces = ep.trace_count(3, 2)
    ep.query(list(reversed(query)), k=2, extract=False)
    assert ep.trace_count(3, 2) == traces  # same shape -> no re-trace


def test_pallas_single_launch_per_superstep(pallas_setup):
    """The perf claim's structural proxy on CPU: the fused path lowers to
    exactly ONE pallas_call per superstep and strictly fewer jaxpr
    equations than the jnp op chain."""
    import jax

    from repro.core.driver import lane_init, lane_superstep

    index, ej, ep = pallas_setup
    query = mid_df_tokens(index, 3)
    cfg_j = ej.policy.dks_config(3, 2)
    cfg_p = ep.policy.dks_config(3, 2)
    masks = jnp.asarray(ej._masks(query)[0])[None]
    st = lane_init(ej.device_graph, masks, cfg_j)
    jx_j = jax.make_jaxpr(
        lambda s: lane_superstep(ej.device_graph, s, cfg_j))(st)
    jx_p = jax.make_jaxpr(
        lambda s: lane_superstep(ep.device_graph, s, cfg_p,
                                 csr=ep.lane_csr))(st)

    def all_eqns(jaxpr):
        out = list(jaxpr.eqns)
        for eq in jaxpr.eqns:
            for p in eq.params.values():
                inner = getattr(p, "jaxpr", None)
                if inner is not None:
                    out += all_eqns(getattr(inner, "jaxpr", inner))
        return out

    eq_j, eq_p = all_eqns(jx_j.jaxpr), all_eqns(jx_p.jaxpr)
    assert sum(1 for e in eq_p if e.primitive.name == "pallas_call") == 1
    assert sum(1 for e in eq_j if e.primitive.name == "pallas_call") == 0
    assert len(eq_p) < len(eq_j)


def test_pallas_sharded_raises_not_implemented():
    with pytest.raises(NotImplementedError, match="shard_map body"):
        ExecutionPolicy(backend="pallas", partition="sharded")
