"""Serving subsystem tests: micro-batcher coalescing + shape separation,
result-cache hits that skip device execution (asserted via the engine's
trace/executor counters), deadline-bounded approximate answers with valid
SPA bounds, and multi-threaded client parity with direct engine.query."""

import threading
import time

import numpy as np
import pytest

from repro.engine import ExecutionPolicy, QueryEngine
from repro.graph.generators import lod_like_graph
from repro.graph.index import InvertedIndex
from repro.serve import DKSService, ResultCache, ServeConfig
from repro.serve.loadgen import TraceRequest, make_trace, replay


@pytest.fixture(scope="module")
def engine():
    g, tokens = lod_like_graph(600, 1800, seed=11, vocab=120)
    index = InvertedIndex.from_token_matrix(tokens)
    return QueryEngine.build(
        g, index=index, policy=ExecutionPolicy(max_supersteps=32))


def mid_df_tokens(index, n, lo=2, hi=60):
    toks = [t for t in sorted(index.vocabulary(), key=index.df)
            if lo <= index.df(t) <= hi]
    assert len(toks) >= n
    return toks[:n]


def test_concurrent_clients_match_direct_engine(engine):
    """8 closed-loop clients; every served answer equals engine.query."""
    toks = mid_df_tokens(engine.index, 9)
    pool = [tuple(toks[0:2]), tuple(toks[2:4]), tuple(toks[4:6]),
            tuple(toks[6:9]), tuple(toks[3:6])]
    trace = [TraceRequest(pool[i % len(pool)]) for i in range(15)]
    with DKSService(engine, ServeConfig(max_batch=4, max_wait_ms=40.0,
                                        cache_size=64)) as svc:
        served = replay(svc, trace, n_clients=8)
        stats = svc.stats()
    assert stats.requests == len(trace)
    assert stats.batch_dispatches > 0
    # The trace repeats each query 3x; a repeat is reused either from the
    # warm cache (the earlier run resolved) or by single-flight attach
    # (it was still in flight) — never re-executed.
    assert stats.cache_hits + stats.single_flight_hits > 0
    refs = {q: engine.query(list(q), k=1) for q in pool}
    for req, srv in zip(trace, served):
        assert not srv.approximate
        ref = refs[req.keywords]
        np.testing.assert_allclose(srv.result.weights, ref.weights)
        assert [a.weight for a in srv.result.answers] == \
               [a.weight for a in ref.answers]


def test_batcher_coalesces_same_shape_and_separates(engine):
    """Same-shape requests share one vmapped dispatch; a different m (or
    k) cannot ride along — the DKS table shape [V, 2^m, K] differs."""
    toks = mid_df_tokens(engine.index, 9)
    m2 = [toks[0:2], toks[2:4], toks[4:6], toks[6:8]]
    m3 = [toks[0:3], toks[6:9]]
    with DKSService(engine, ServeConfig(max_batch=4, max_wait_ms=250.0,
                                        cache_size=0)) as svc:
        futures = [svc.submit(q, k=1) for q in m2 + m3]
        served = [f.result(timeout=300) for f in futures]
        stats = svc.stats()
    # The four m=2 queries filled one batch exactly...
    assert [s.batch_size for s in served[:4]] == [4, 4, 4, 4]
    # ...and the m=3 queries dispatched separately, together.
    assert [s.batch_size for s in served[4:]] == [2, 2]
    assert stats.batch_dispatches == 2
    assert stats.mean_batch_fill == 3.0
    assert stats.cache_hits == 0 and stats.cache_misses == 0  # cache off
    for q, srv in zip(m2 + m3, served):
        np.testing.assert_allclose(
            srv.result.weights, engine.query(q, k=1).weights)


def wait_until(cond, timeout=120.0):
    t_end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < t_end, "timed out"
        time.sleep(0.005)


def test_coalesce_counts_requests_waiting_behind_a_held_dispatch(
        engine, monkeypatch):
    """Each bucket's ``coalesce`` span records the queue depth it left:
    ``waiting`` real requests (other pending buckets and the admission
    queue) and ``waiting_buckets`` (other non-empty buckets), read while
    the dispatcher is held on a group."""
    toks = mid_df_tokens(engine.index, 10)
    gates = [threading.Event(), threading.Event()]
    calls = []
    query_batch = engine.query_batch

    def held(*args, **kwargs):
        calls.append(len(args[0]))
        if len(calls) <= len(gates):
            assert gates[len(calls) - 1].wait(120)
        return query_batch(*args, **kwargs)

    monkeypatch.setattr(engine, "query_batch", held)
    with DKSService(engine, ServeConfig(max_batch=4, max_wait_ms=1.0,
                                        cache_size=0)) as svc:
        queue = svc._batcher._queue
        first = svc.submit(toks[0:2], k=2)        # held on gate 0
        wait_until(lambda: len(calls) == 1)
        m2 = [svc.submit(q, k=1) for q in (toks[2:4], toks[4:6], toks[6:8])]
        m3 = [svc.submit(q, k=1) for q in (toks[0:3], toks[3:6])]
        wait_until(lambda: queue.qsize() == 5)
        time.sleep(0.05)                          # both windows expire
        gates[0].set()                            # m=2 bucket: gate 1
        wait_until(lambda: len(calls) == 2)
        late = svc.submit(toks[8:10], k=3)
        wait_until(lambda: queue.qsize() == 1)
        gates[1].set()
        served = [f.result(timeout=300) for f in [first, *m2, *m3, late]]

        def depth(result):
            coalesce = next(sp for sp in svc.trace(result.trace_id).spans
                            if sp.name == "coalesce")
            return (coalesce.attrs["waiting"],
                    coalesce.attrs["waiting_buckets"])

        leaders = [served[0], served[1], served[4], served[6]]
        assert [s.batch_size for s in leaders] == [1, 3, 2, 1]
        assert [depth(s) for s in leaders] == [(0, 0), (2, 1), (1, 0),
                                               (0, 0)]


def test_cache_hit_skips_execution_and_normalizes(engine):
    q = mid_df_tokens(engine.index, 2)
    with DKSService(engine, ServeConfig(max_batch=2, max_wait_ms=1.0,
                                        cache_size=8)) as svc:
        first = svc.query(q, k=1)
        assert not first.cache_hit and first.batch_size == 1
        executes = engine.execute_count
        traces = engine.cache_stats["traces"]
        second = svc.query(q, k=1)
        permuted = svc.query(list(reversed(q)), k=1)
        # Hits skip the device entirely: no dispatch, no re-trace.
        assert second.cache_hit and permuted.cache_hit
        assert second.batch_size == 0
        assert engine.execute_count == executes
        assert engine.cache_stats["traces"] == traces
        np.testing.assert_allclose(second.result.weights,
                                   first.result.weights)
        np.testing.assert_allclose(permuted.result.weights,
                                   first.result.weights)
        stats = svc.stats()
        assert stats.cache_hits == 2 and stats.cache_misses == 1
        # A different k or policy override is a different answer: miss.
        assert not svc.query(q, k=2).cache_hit
        assert not svc.query(q, k=1, max_supersteps=8).cache_hit
        # Explicit invalidation (graph rebuild): the entry is gone.
        assert svc.invalidate_cache() > 0
        assert not svc.query(q, k=1).cache_hit


def test_single_flight_coalesces_identical_misses(engine):
    """Two (here: five) concurrent identical cache misses execute once —
    the first leads, the rest attach to its in-flight future and resolve
    from the leader's result with ``coalesced=True``."""
    q = mid_df_tokens(engine.index, 2)
    ref = engine.query(q, k=1)
    executes = engine.execute_count
    with DKSService(engine, ServeConfig(max_batch=8, max_wait_ms=300.0,
                                        cache_size=8)) as svc:
        futures = [svc.submit(q, k=1) for _ in range(5)]
        served = [f.result(timeout=300) for f in futures]
        stats = svc.stats()
    # One device dispatch total for the five identical requests.
    assert engine.execute_count == executes + 1
    leaders = [s for s in served if not s.coalesced and not s.cache_hit]
    followers = [s for s in served if s.coalesced]
    assert len(leaders) == 1 and len(followers) == 4
    assert stats.requests == 5
    assert stats.single_flight_hits == 4
    assert stats.cache_misses == 1   # one durable miss, not five
    for srv in served:
        np.testing.assert_array_equal(srv.result.weights, ref.weights)
    # A later identical request is a plain cache hit, not single-flight.
    with DKSService(engine, ServeConfig(cache_size=8)) as svc:
        first = svc.query(q, k=1)
        again = svc.query(q, k=1)
    assert not first.cache_hit and again.cache_hit and not again.coalesced


def test_cache_lru_eviction_and_disable():
    cache = ResultCache(capacity=2)
    cache.put("a", 1)
    cache.put("b", 2)
    assert cache.get("a") == 1       # refreshes a
    cache.put("c", 3)                # evicts b (LRU)
    assert cache.get("b") is None
    assert cache.get("a") == 1 and cache.get("c") == 3
    st = cache.stats()
    assert st["evictions"] == 1 and st["size"] == 2
    disabled = ResultCache(capacity=0)
    disabled.put("a", 1)
    assert disabled.get("a") is None
    assert disabled.stats()["hits"] == 0 and disabled.stats()["misses"] == 0


def test_deadline_expiry_returns_approximate_with_bound():
    """The paper's early-termination guarantee as a serving feature: a
    heavy direct edge is found early, the cheap 10-hop path later; an
    expired deadline returns best-so-far + a valid lower bound."""
    from repro.graph.structure import build_graph
    src = [0, 0] + list(range(2, 10)) + [10]
    dst = [1, 2] + list(range(3, 11)) + [1]
    w = np.asarray([100.0] + [1.0] * 10, np.float32)
    g = build_graph(src, dst, 11, w=w)
    tokens = np.arange(11, dtype=np.int32).reshape(11, 1)
    engine = QueryEngine.build(g, tokens=tokens)
    with DKSService(engine, ServeConfig(cache_size=8)) as svc:
        exact = svc.query([0, 1], k=1)
        assert not exact.approximate and exact.best_weight == 10.0
        svc.invalidate_cache()
        served = svc.query([0, 1], k=1, deadline_ms=0.0)
        assert served.approximate
        assert not served.result.done
        # Valid bracket: lower bound <= optimum <= best-so-far.  The
        # sound bound is the guaranteed one; the reported bound (paper
        # convention, SPA estimator) also holds on this graph.
        assert served.opt_lower_bound is not None
        assert served.sound_opt_lower_bound is not None
        assert served.sound_opt_lower_bound <= served.opt_lower_bound
        assert served.sound_opt_lower_bound <= 10.0 + 1e-6
        assert served.opt_lower_bound <= 10.0 + 1e-6
        assert served.result.weights[0] >= 10.0 - 1e-6
        # The interrupted run reports its forced-stop SPA bound, and is
        # never presented as certified (ratio 0 only means certified).
        assert served.result.spa is not None
        # Approximate results are budget-specific: never cached.
        assert svc.stats().cache_hits == 0
        again = svc.query([0, 1], k=1)
        assert not again.cache_hit and not again.approximate
        assert again.best_weight == 10.0
        # A budget generous enough to finish yields the exact answer.
        done = svc.query([0, 1], k=1, deadline_ms=60_000.0)
        assert done.cache_hit and not done.approximate


def test_deadline_bucket_coalesces_and_shares_supersteps(engine):
    """Same-budget same-shape deadline requests ride ONE lane driver:
    one deadline dispatch for the bucket, and the shared driver's
    superstep count is max(lane steps), far below the N x solo sum a
    per-request streaming executor would pay."""
    toks = mid_df_tokens(engine.index, 8)
    queries = [toks[0:2], toks[2:4], toks[4:6], toks[6:8]]
    solo = [engine.query(q, k=1, extract=False) for q in queries]
    with DKSService(engine, ServeConfig(max_batch=4, max_wait_ms=400.0,
                                        cache_size=0)) as svc:
        futures = [svc.submit(q, k=1, deadline_ms=60_000.0)
                   for q in queries]
        served = [f.result(timeout=300) for f in futures]
        stats = svc.stats()
    assert stats.deadline_dispatches == 1
    assert stats.deadline_batched_requests == 4
    assert stats.mean_deadline_fill == 4.0
    # All lanes finished inside the generous budget: exact answers...
    for q, srv, ref in zip(queries, served, solo):
        assert not srv.approximate and srv.batch_size == 4
        np.testing.assert_allclose(srv.result.weights, ref.weights)
    # ...each lane billed its own supersteps (frozen individually)...
    assert stats.deadline_lane_supersteps == \
        sum(r.supersteps for r in solo)
    # ...while the shared driver stepped only as far as the slowest lane.
    assert stats.deadline_driver_supersteps == \
        max(r.supersteps for r in solo)
    assert stats.deadline_driver_supersteps < stats.deadline_lane_supersteps


def test_deadline_bucket_expiry_per_lane_bounds():
    """An expired coalesced bucket resolves every lane with its own
    best-so-far answer and a valid per-lane bound bracket."""
    from repro.graph.structure import build_graph
    src = [0, 0] + list(range(2, 10)) + [10]
    dst = [1, 2] + list(range(3, 11)) + [1]
    w = np.asarray([100.0] + [1.0] * 10, np.float32)
    g = build_graph(src, dst, 11, w=w)
    tokens = np.arange(11, dtype=np.int32).reshape(11, 1)
    engine = QueryEngine.build(g, tokens=tokens)
    with DKSService(engine, ServeConfig(max_batch=4, max_wait_ms=10.0,
                                        cache_size=0)) as svc:
        # Occupy the dispatcher with a deadline-less query (cold compile
        # takes far longer than the admission window), so the two
        # zero-budget submits below are guaranteed to sit in the queue
        # together and drain into ONE deadline bucket — the coalescing
        # must not depend on racing the tiny budget-capped window.
        warm = svc.submit([3, 4], k=1)
        import time as _time
        _time.sleep(0.05)
        futures = [svc.submit([0, 1], k=1, deadline_ms=0.0),
                   svc.submit([2, 10], k=1, deadline_ms=0.0)]
        served = [f.result(timeout=300) for f in futures]
        warm.result(timeout=300)
        stats = svc.stats()
    assert stats.deadline_dispatches == 1 and stats.mean_deadline_fill == 2.0
    ref = {(0, 1): engine.query([0, 1], k=1).best_weight,
           (2, 10): engine.query([2, 10], k=1).best_weight}
    for srv, q in zip(served, [(0, 1), (2, 10)]):
        assert srv.approximate and not srv.result.done
        assert srv.result.spa is not None
        assert srv.sound_opt_lower_bound <= srv.opt_lower_bound + 1e-6
        assert srv.sound_opt_lower_bound <= ref[q] + 1e-6
        assert srv.result.weights[0] >= ref[q] - 1e-6


def test_streamed_until_bound_monotone_and_forced(engine):
    """The engine primitive under the deadline path: until= interrupts the
    stream, bounds never worsen, and the result reports a forced stop."""
    q = mid_df_tokens(engine.index, 3)
    updates = []
    res = engine.query_streamed(
        q, k=1, extract=False, on_update=updates.append,
        until=lambda u: u.step >= 1)
    assert len(updates) == 2 and not res.done
    assert res.spa is not None
    ratios = [u.spa_ratio for u in updates]
    assert all(cur <= prev for prev, cur in zip(ratios, ratios[1:]))
    bounds = [u.opt_lower_bound for u in updates]
    assert all(cur >= prev for prev, cur in zip(bounds, bounds[1:]))
    # Without until= the same call runs to its proven exit.
    full = engine.query_streamed(q, k=1, extract=False)
    assert full.done and full.spa is None


def test_strict_admission_rejects_unmatched_alone(engine):
    """An unmatched keyword fails its own future at admission — it must
    not poison a co-batched dispatch."""
    good = mid_df_tokens(engine.index, 2)
    missing = max(engine.index.vocabulary()) + 1000
    with DKSService(engine, ServeConfig(max_batch=4, max_wait_ms=60.0,
                                        cache_size=0)) as svc:
        bad_future = svc.submit([missing, missing + 1], k=1)
        good_future = svc.submit(good, k=1)
        with pytest.raises(KeyError, match=str(missing)):
            bad_future.result(timeout=300)
        served = good_future.result(timeout=300)
    np.testing.assert_allclose(served.result.weights,
                               engine.query(good, k=1).weights)


def test_set_engine_inflight_served_by_admitting_build(engine):
    """A set_engine swap must not change the build mid-flight: queued
    requests are served by the engine that admitted them, and their
    results are unreachable to post-swap clients (version-keyed cache)."""
    g2, tokens2 = lod_like_graph(300, 900, seed=5, vocab=80)
    engine2 = QueryEngine.build(g2, tokens=tokens2)
    both = set(engine2.index.vocabulary())
    q = [t for t in sorted(engine.index.vocabulary(), key=engine.index.df)
         if engine.index.df(t) >= 2 and t in both][:2]
    assert len(q) == 2
    with DKSService(engine, ServeConfig(max_batch=8, max_wait_ms=400.0,
                                        cache_size=8)) as svc:
        queued = svc.submit(q, k=1)          # sits in the admission window
        svc.set_engine(engine2)              # graph rebuild mid-flight
        served = queued.result(timeout=300)
        np.testing.assert_allclose(served.result.weights,
                                   engine.query(q, k=1).weights)
        # The old build's answer was cached under its version: a
        # post-swap client cannot hit it.
        post = svc.query(q, k=1)
        assert not post.cache_hit
        np.testing.assert_allclose(post.result.weights,
                                   engine2.query(q, k=1).weights)


def test_default_equal_override_coalesces(engine):
    """An override equal to the engine policy's value is normalized away
    at admission, so the request coalesces with no-override requests."""
    toks = mid_df_tokens(engine.index, 4)
    with DKSService(engine, ServeConfig(max_batch=2, max_wait_ms=250.0,
                                        cache_size=0)) as svc:
        f1 = svc.submit(toks[0:2], k=1)
        f2 = svc.submit(toks[2:4], k=1, max_supersteps=32)  # policy value
        r1 = f1.result(timeout=300)
        r2 = f2.result(timeout=300)
    assert r1.batch_size == 2 and r2.batch_size == 2


def test_unhashable_override_fails_alone(engine):
    """An unhashable override value fails its own future at admission —
    it must not reach (and kill) the dispatcher thread."""
    good = mid_df_tokens(engine.index, 2)
    with DKSService(engine, ServeConfig(max_wait_ms=1.0,
                                        cache_size=0)) as svc:
        bad = svc.submit(good, k=1, max_supersteps=[8])
        with pytest.raises(TypeError, match="unhashable"):
            bad.result(timeout=60)
        # The service is still alive and serving.
        ok = svc.query(good, k=1)
    np.testing.assert_allclose(ok.result.weights,
                               engine.query(good, k=1).weights)


def test_loadgen_trace_shapes(engine):
    trace = make_trace(engine.index, 12, unique=4, deadline_frac=0.25,
                       deadline_ms=50.0, seed=1)
    assert len(trace) == 12
    assert {len(t.keywords) for t in trace} <= {2, 3}
    assert sum(t.deadline_ms is not None for t in trace) == 3
    assert len({t.keywords for t in trace}) <= 4
    # deterministic
    assert trace == make_trace(engine.index, 12, unique=4,
                               deadline_frac=0.25, deadline_ms=50.0, seed=1)


def test_stopped_service_rejects_submits(engine):
    svc = DKSService(engine, ServeConfig())
    with pytest.raises(RuntimeError):
        svc.submit(mid_df_tokens(engine.index, 2), k=1)
    svc.start()
    svc.stop()
    with pytest.raises(RuntimeError):
        svc.submit(mid_df_tokens(engine.index, 2), k=1)


def tree_key(t):
    return (t.root, tuple(sorted((e.u, e.v) for e in t.edges)))


def test_return_trees_end_to_end_from_artifact(tmp_path):
    """The full answer pipeline off an ingested artifact: served trees are
    label-rendered from the artifact's label blob (the graph itself
    carries no labels in memory), diversity-ranked, paginated, and a
    warm identical request is served whole from the tree-pool cache."""
    from repro.graph.structure import build_graph
    from repro.store import open_artifact, write_artifact

    #   paris hotel (0) --- cafe (2) --- piano bar (1)
    #        \------------ bistro (3) ------/
    # plus pendants so the graph has non-answer material.
    labels = ["paris hotel", "piano bar", "cafe central", "bistro nord",
              "museum", "shop"]
    src = [0, 2, 0, 3, 4, 5]
    dst = [2, 1, 3, 1, 0, 1]
    g = build_graph(src, dst, 6, w=np.ones(6, np.float32), labels=labels)
    index = InvertedIndex.from_labels(labels)
    art = write_artifact(tmp_path / "art", g, index)
    engine = QueryEngine.build(artifact=open_artifact(art.path))
    assert engine.graph.labels is None  # labels live only in the blob
    with DKSService(engine, ServeConfig(cache_size=8,
                                        tree_page_size=2)) as svc:
        srv = svc.query(["paris", "piano"], k=2, return_trees=True)
        page = srv.trees
        assert page is not None and page.ranking == "diverse"
        assert page.total >= 2 and len(page.items) == 2
        assert len({tree_key(t) for t in page.items}) == 2
        for t in page.items:
            # Labels are the artifact's entity strings, not node:<id>.
            assert t.root_label == labels[t.root]
            assert all(lbl == labels[n]
                       for n, lbl in zip(t.nodes, t.node_labels))
            joined = " ".join(t.node_labels)
            assert "paris" in joined and "piano" in joined
        # Both two-hop connections appear among the served explanations.
        mids = {n for t in page.items for n in t.nodes} - {0, 1}
        assert {2, 3} <= mids
        before = svc.stats()
        assert before.tree_requests == 1 and before.tree_cache_hits == 0
        executes = engine.execute_count
        warm = svc.query(["paris", "piano"], k=2, return_trees=True)
        assert warm.cache_hit and engine.execute_count == executes
        assert [tree_key(t) for t in warm.trees.items] == \
               [tree_key(t) for t in page.items]
        assert svc.stats().tree_cache_hits == 1
        # Tree caches drain on invalidation too.
        assert svc.invalidate_cache() >= 2
        assert not svc.query(["paris", "piano"], k=2,
                             return_trees=True).cache_hit


def test_tree_ranking_and_pagination(engine):
    toks = mid_df_tokens(engine.index, 2)
    with DKSService(engine, ServeConfig(cache_size=8, tree_page_size=2,
                                        tree_pool_factor=4)) as svc:
        srv = svc.query(toks, k=3, return_trees=True, tree_ranking="weight")
        page = srv.trees
        assert page.ranking == "weight"
        ws = [t.weight for t in page.items]
        assert ws == sorted(ws), "weight ranking must be ascending"
        # Walk the cursor to the end: pages partition the pool, each
        # follow-up is served from the caches (no device work).
        seen = list(page.items)
        cursor = page.next_cursor
        while cursor is not None:
            nxt = svc.query(toks, k=3, return_trees=True,
                            tree_ranking="weight", tree_cursor=cursor)
            assert nxt.cache_hit
            assert nxt.trees.cursor == cursor
            seen.extend(nxt.trees.items)
            cursor = nxt.trees.next_cursor
        assert len(seen) == page.total
        assert len({tree_key(t) for t in seen}) == page.total, (
            "pool contains duplicate trees")
        # Diverse ranking is a permutation of the same pool.
        div = svc.query(toks, k=3, return_trees=True,
                        tree_ranking="diverse", tree_page_size=page.total)
        assert {tree_key(t) for t in div.trees.items} == \
               {tree_key(t) for t in seen}
        # Bad ranking fails that request alone; the service lives on.
        with pytest.raises(ValueError, match="tree_ranking"):
            svc.submit(toks, k=1, return_trees=True,
                       tree_ranking="bogus").result(timeout=60)
        assert svc.query(toks, k=3, return_trees=True).trees is not None


# ----------------------------------------------------------------------
# Adaptive lane occupancy (AdaptiveLanePolicy + pad_batches="adaptive")
# ----------------------------------------------------------------------


def test_adaptive_lane_policy_degrades_to_pow2_until_measured():
    from repro.engine import AdaptiveLanePolicy

    pol = AdaptiveLanePolicy(max_lanes=16)
    d = pol.lanes_for(5)
    assert d.lanes == 8 and d.reason == "pow2" and d.est_ms is None
    assert pol.lanes_for(16).lanes == 16
    assert pol.lanes_for(100).lanes == 16  # clamped at max_lanes


def test_adaptive_lane_policy_prefers_cheap_warm_counts():
    from repro.engine import AdaptiveLanePolicy

    pol = AdaptiveLanePolicy(max_lanes=16, retrace_cost_ms=200.0)
    # Warm measurements: 6 lanes is cheap, 8 lanes is pathological.
    for _ in range(3):
        pol.observe(6, 10.0)
        pol.observe(8, 500.0)
    d = pol.lanes_for(5)
    assert d.lanes == 6 and d.reason == "warm"
    # Exact fit wins when padding to a warm count costs more than a
    # cold dispatch at n itself would.
    d2 = pol.lanes_for(7)   # candidates: 7 (cold), 8 (warm but 500ms), 16
    assert d2.lanes == 7 and d2.reason == "exact"
    assert pol.target_fill() in (6, 8)
    snap = pol.snapshot()
    assert snap["last_lanes"] == d2.lanes
    assert snap["decisions"]["warm"] >= 1


def test_adaptive_lane_policy_uses_hot_shape_candidates():
    from repro.engine import AdaptiveLanePolicy

    pol = AdaptiveLanePolicy(max_lanes=32, retrace_cost_ms=0.0)
    pol.observe(4, 100.0)   # per-lane estimate: 25 ms
    # A swapped-in engine's histogram says the workload runs 6-lane
    # buckets: 6 joins the candidate set though never measured here.
    d = pol.lanes_for(5, hot_shapes=(((3, 2, 6), 40),))
    # With zero retrace cost the cheapest candidate >= 5 is 5 itself;
    # raise the retrace cost and the hot 6 would compete.  Just assert
    # the decision is sane and 6 was considered (<= max, >= n).
    assert d.lanes in (5, 6)


def test_adaptive_padding_serves_parity_and_exports_metrics(engine):
    """pad_batches='adaptive' end to end: answers match the direct
    engine, the policy observes real dispatches, and the decision
    metrics ride /metrics."""
    from repro.obs import parse_prometheus

    toks = mid_df_tokens(engine.index, 6)
    queries = [toks[i:i + 3] for i in range(3)]
    with DKSService(engine, ServeConfig(
            max_batch=8, max_wait_ms=4.0,
            pad_batches="adaptive", cache_size=0)) as svc:
        futs = [svc.submit(q, k=1) for q in queries]
        results = [f.result(120) for f in futs]
        # Second wave: the policy now has measurements to score with.
        futs2 = [svc.submit(q, k=1) for q in reversed(queries)]
        results2 = [f.result(120) for f in futs2]
        snap = svc.lane_policy.snapshot()
        metrics = parse_prometheus(svc.registry.render())
    for q, served in zip(queries, results):
        direct = engine.query(q, k=1)
        np.testing.assert_array_equal(served.result.weights,
                                      direct.weights)
    for q, served in zip(list(reversed(queries)), results2):
        direct = engine.query(q, k=1)
        np.testing.assert_array_equal(served.result.weights,
                                      direct.weights)
    assert snap["observed_counts"]          # dispatches were observed
    assert sum(snap["decisions"].values()) >= 1
    assert "dks_lane_policy_last_lanes" in metrics
    assert "dks_lane_policy_decision_pow2_total" in metrics


def test_serve_config_rejects_unknown_pad_mode():
    with pytest.raises(ValueError, match="pad_batches"):
        ServeConfig(pad_batches="nope")
