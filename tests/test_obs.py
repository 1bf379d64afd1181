"""Observability tests: tracer ring + deterministic sampling, registry
render/parse round-trip, fused-loop telemetry bit-identity (dense and
sharded), instrumented-surface parity with the shared collector, the
serve-layer /metrics surface (counters equal ServeStats, monotone across
scrapes), and trace completeness under coalescing + single-flight."""

import json
import os
import subprocess
import sys
import threading
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import repro
from repro.engine import ExecutionPolicy, QueryEngine
from repro.graph.generators import lod_like_graph
from repro.graph.index import InvertedIndex
from repro.obs import (
    MetricsRegistry,
    MetricsServer,
    Tracer,
    parse_prometheus,
    render_span_tree,
    timed_span,
)
from repro.serve import DKSService, ServeConfig
from repro.serve.loadgen import latency_split
from repro.serve.stats import StatsCollector


@pytest.fixture(scope="module")
def graph_data():
    g, tokens = lod_like_graph(600, 1800, seed=11, vocab=120)
    return g, InvertedIndex.from_token_matrix(tokens)


@pytest.fixture(scope="module")
def engine(graph_data):
    g, index = graph_data
    return QueryEngine.build(
        g, index=index, policy=ExecutionPolicy(max_supersteps=32))


@pytest.fixture(scope="module")
def tel_engine(graph_data):
    g, index = graph_data
    return QueryEngine.build(
        g, index=index,
        policy=ExecutionPolicy(max_supersteps=32, telemetry=True))


def mid_df_tokens(index, n, lo=2, hi=60):
    toks = [t for t in sorted(index.vocabulary(), key=index.df)
            if lo <= index.df(t) <= hi]
    assert len(toks) >= n
    return toks[:n]


# ---------------------------------------------------------------------------
# repro.obs.trace
# ---------------------------------------------------------------------------


def test_tracer_ring_bounded_and_counters():
    tracer = Tracer(capacity=4)
    ids = []
    for i in range(10):
        tr = tracer.begin("req", i=i)
        with tr.span("outer") as outer:
            outer.set(note="x")
            with tr.span("inner"):
                pass
        tr.add_span("retro", tr.t_start, tr.t_start + 0.001, kind="queue")
        tr.finish()
        tr.finish()  # idempotent: must not double-count
        ids.append(tr.trace_id)
    st = tracer.stats()
    assert st == {"begun": 10, "finished": 10, "sampled": 10, "buffered": 4}
    # The ring keeps the newest `capacity` traces, newest last.
    assert [t.trace_id for t in tracer.recent()] == ids[-4:]
    assert tracer.get(ids[0]) is None and tracer.get(ids[-1]) is not None
    # Span tree: inner nested under outer (same thread), retro a sibling.
    tr = tracer.get(ids[-1])
    by_name = {sp.name: sp for sp in tr.spans}
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["retro"].parent_id is None
    rendered = render_span_tree(tr)
    for name in ("outer", "inner", "retro", "note=x"):
        assert name in rendered
    # to_dict round-trips through JSON, spans ordered by start time (the
    # retro span was backdated to trace start, so it sorts first).
    d = json.loads(json.dumps(tr.to_dict()))
    assert [s["name"] for s in d["spans"]] == ["retro", "outer", "inner"]


def test_sampling_deterministic_per_seed():
    def sampled_ids(seed):
        tracer = Tracer(capacity=256, sample=0.3, seed=seed)
        out = set()
        for _ in range(200):
            tr = tracer.begin("req")
            if tr.sampled:
                out.add(tr.trace_id)
            with tr.span("s"):
                pass
            tr.finish()
        return out, tracer.stats()

    a, st_a = sampled_ids(7)
    b, _ = sampled_ids(7)
    c, _ = sampled_ids(8)
    assert a == b, "same seed must sample the same trace ids"
    assert a != c, "a different seed must pick a different subset"
    assert 0 < len(a) < 200
    # Unsampled traces still finish (completeness counts every request)
    # but record no spans and stay out of the ring.
    assert st_a["begun"] == st_a["finished"] == 200
    assert st_a["sampled"] == st_a["buffered"] == len(a)
    tracer = Tracer(sample=0.0)
    tr = tracer.begin("req")
    with tr.span("ignored") as h:
        h.set(x=1)
    tr.finish()
    assert tr.spans == [] and tracer.stats()["sampled"] == 0


class _Annotations:
    """A recording stand-in for ``jax.profiler.TraceAnnotation``."""

    def __init__(self):
        self.events = []

    def __call__(self, name):
        events = self.events

        class _Note:
            def __enter__(self):
                events.append(("enter", name, threading.get_ident()))

            def __exit__(self, *exc):
                events.append(("exit", name, threading.get_ident()))

        return _Note()


def test_annotate_hook_wraps_context_spans_only():
    notes = _Annotations()
    tracer = Tracer(annotate=notes)
    tr = tracer.begin("req")
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    tr.add_span("retro", tr.t_start, tr.t_start + 0.001)
    tr.finish()
    me = threading.get_ident()
    assert notes.events == [("enter", "dks.outer", me),
                            ("enter", "dks.inner", me),
                            ("exit", "dks.inner", me),
                            ("exit", "dks.outer", me)]
    # Unsampled traces open no annotation; a traceless handle only times.
    notes.events.clear()
    tr = Tracer(sample=0.0, annotate=notes).begin("req")
    with tr.span("ignored"):
        pass
    with timed_span(None, "untraced") as h:
        pass
    assert notes.events == [] and tr.spans == []
    assert h.t_end >= h.t_start


def test_obs_imports_no_jax():
    """The leaf package takes its profiler hook as an argument: importing
    it loads no jax."""
    code = ("import sys; import repro.obs; "
            "assert 'jax' not in sys.modules, 'repro.obs imported jax'")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(Path(repro.__file__).resolve().parents[1]),
         os.environ.get("PYTHONPATH", "")]))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)


def test_engine_spans_nest_and_tile_extract(engine):
    toks = mid_df_tokens(engine.index, 6)
    queries = [toks[0:2], toks[2:4], toks[4:6], toks[1:3]]
    notes = _Annotations()
    tracer = Tracer(annotate=notes)
    tr = tracer.begin("req")
    before = engine.trace_count(2, 2)
    results = engine.query_batch(queries, k=2, trace=tr, n_real=3)
    tr.finish()
    spans = {sp.name: sp for sp in tr.spans}
    assert len(tr.spans) == len(spans) == 6
    dispatch, extract = spans["device_dispatch"], spans["extract"]
    assert dispatch.attrs == {"lanes": 4, "compiled":
                              engine.trace_count(2, 2) > before}
    # The span and wall_time_s are the same two clock reads.
    assert dispatch.t_end - dispatch.t_start == results[0].wall_time_s
    assert spans["masks"].t_end <= dispatch.t_start
    assert dispatch.t_end <= extract.t_start
    children = [spans[n] for n in ("backtrace", "trees", "results")]
    for sp in children:
        assert sp.parent_id == extract.span_id
    for a, b in zip(children, children[1:]):
        assert a.t_end <= b.t_start
    assert extract.t_start <= children[0].t_start
    assert children[-1].t_end <= extract.t_end
    covered = sum(sp.t_end - sp.t_start for sp in children)
    assert covered >= 0.9 * (extract.t_end - extract.t_start)
    trees = spans["trees"].attrs
    assert trees["device_resolved"] + trees["host_fallbacks"] > 0
    # Every context-manager span is also a profiler annotation.
    entered = [name for kind, name, _ in notes.events if kind == "enter"]
    assert entered == ["dks.masks", "dks.device_dispatch", "dks.extract",
                       "dks.backtrace", "dks.trees", "dks.results"]


def test_engine_untraced_batch_matches_traced_and_single(engine):
    toks = mid_df_tokens(engine.index, 6)
    queries = [toks[0:2], toks[2:4], toks[4:6]]
    plain = engine.query_batch(queries, k=2)
    traced = engine.query_batch(queries, k=2,
                                trace=Tracer().begin("req"))
    for q, a, b in zip(queries, plain, traced):
        ref = engine.query(q, k=2)
        for res in (a, b):
            np.testing.assert_array_equal(res.weights, ref.weights)
            assert [t.edges for t in res.answers] == \
                [t.edges for t in ref.answers]
            assert res.supersteps == ref.supersteps


def test_engine_deadline_spans(engine):
    toks = mid_df_tokens(engine.index, 4)
    tracer = Tracer()
    tr = tracer.begin("req")
    out = engine.query_deadline_batch([toks[0:2], toks[2:4]], k=1,
                                      deadline_s=60.0, trace=tr)
    spans = {sp.name: sp for sp in tr.spans}
    assert set(spans) == {"masks", "device_dispatch", "extract", "trees",
                          "results"}
    dispatch = spans["device_dispatch"]
    assert dispatch.attrs["driver_supersteps"] == \
        out[0][1]["driver_supersteps"]
    assert dispatch.t_end - dispatch.t_start == out[0][0].wall_time_s
    for name in ("trees", "results"):
        assert spans[name].parent_id == spans["extract"].span_id
    assert spans["trees"].attrs == out[0][1]["extraction"]


def test_fused_program_carries_named_scopes():
    g, tokens = lod_like_graph(200, 600, seed=5, vocab=40)
    index = InvertedIndex.from_token_matrix(tokens)
    eng = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
        backend="pallas", max_supersteps=4))
    cfg = eng._config(2, 1)
    masks = np.stack([eng._masks(mid_df_tokens(index, 2, hi=40))[0]])
    hlo = eng._executable(cfg, "fused").lower(
        eng.device_graph, eng.lane_csr, masks).compile().as_text()
    for scope in ("dks.init", "dks.gather", "dks.kernel", "dks.finish"):
        assert scope in hlo, scope


def test_pallas_dispatch_names_its_gather_chunks():
    """On the pallas path both dispatch spans carry the candidate
    gather's chunk count, which the program's static shapes fix."""
    from repro.kernels.lane_superstep import gather_chunks

    g, tokens = lod_like_graph(200, 600, seed=5, vocab=40)
    index = InvertedIndex.from_token_matrix(tokens)
    eng = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
        backend="pallas", max_supersteps=4))
    toks = mid_df_tokens(index, 2, hi=40)
    csr = eng.lane_csr
    want = gather_chunks(csr.dmax, csr.n_rows, 4)
    tr = Tracer().begin("req")
    eng.query_batch([toks], k=1, trace=tr)
    eng.query_deadline_batch([toks], k=1, deadline_s=60.0, trace=tr)
    got = [sp.attrs["gather_chunks"] for sp in tr.spans
           if sp.name == "device_dispatch"]
    assert got == [want, want] == [1, 1]


def test_pallas_dispatch_names_its_set_chunks():
    """The dispatch span carries the kernel's keyword-set chunks: one at
    m=2 K=1, two at m=6 K=10 on the 16-slot, 128-row layout (a
    5,242,880 B tile whole)."""
    g, tokens = lod_like_graph(200, 600, seed=5, vocab=40)
    index = InvertedIndex.from_token_matrix(tokens)
    eng = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
        backend="pallas", max_supersteps=4))
    assert (eng.lane_csr.dmax, eng.lane_csr.block_v) == (16, 128)
    tr = Tracer().begin("req")
    eng.query_batch([mid_df_tokens(index, 2, hi=40)], k=1, trace=tr)
    eng.query_batch([mid_df_tokens(index, 6, hi=40)], k=10, trace=tr)
    got = [(sp.attrs["gather_chunks"], sp.attrs["set_chunks"])
           for sp in tr.spans if sp.name == "device_dispatch"]
    assert got == [(1, 1), (1, 2)]


def test_trace_log_jsonl(tmp_path):
    log = tmp_path / "traces.jsonl"
    tracer = Tracer(capacity=8, log_path=str(log))
    for i in range(3):
        tr = tracer.begin("req", i=i)
        with tr.span("work"):
            pass
        tr.finish()
    lines = [json.loads(ln) for ln in log.read_text().splitlines()]
    assert [d["attrs"]["i"] for d in lines] == [0, 1, 2]
    assert all(d["spans"][0]["name"] == "work" for d in lines)


# ---------------------------------------------------------------------------
# repro.obs.metrics
# ---------------------------------------------------------------------------


def test_registry_render_parse_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("rt_requests_total", "requests")
    g = reg.gauge("rt_depth", "queue depth")
    h = reg.histogram("rt_latency_ms", "latency", buckets=(1.0, 10.0, 100.0))
    c.inc(); c.inc(2.5)
    g.set(7); g.dec(2)
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    reg.register_collector(
        lambda: {"rt_external_total": 42},
        kinds={"rt_external_total": "counter"})

    parsed = parse_prometheus(reg.render())
    assert parsed == reg.sample()
    assert parsed["rt_requests_total"] == 3.5
    assert parsed["rt_depth"] == 5.0
    assert parsed["rt_external_total"] == 42.0
    # Histogram exposition: cumulative buckets ending at +Inf == count.
    assert parsed['rt_latency_ms_bucket{le="1"}'] == 1.0
    assert parsed['rt_latency_ms_bucket{le="10"}'] == 2.0
    assert parsed['rt_latency_ms_bucket{le="100"}'] == 3.0
    assert parsed['rt_latency_ms_bucket{le="+Inf"}'] == 4.0
    assert parsed["rt_latency_ms_count"] == 4.0
    assert parsed["rt_latency_ms_sum"] == pytest.approx(555.5)
    # Same-name same-kind returns the SAME instrument; kind change raises.
    assert reg.counter("rt_requests_total") is c
    with pytest.raises(ValueError):
        reg.gauge("rt_requests_total")
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        reg.counter("0bad name")


def test_stats_empty_window_no_nan():
    empty = StatsCollector().report({})
    for f, v in vars(empty).items():
        if isinstance(v, (int, float)):
            assert np.isfinite(v), \
                f"ServeStats.{f} not finite on empty window"
    assert empty.hot_shapes == ()
    assert empty.p50_ms == 0.0 and empty.throughput_rps == 0.0
    assert empty.queue_p95_ms == 0.0 and empty.device_mean_ms == 0.0
    assert "nan" not in empty.summary().lower()
    split = latency_split([])
    assert split["n"] == 0 and split["latency_p95_ms"] == 0.0


# ---------------------------------------------------------------------------
# Superstep telemetry (the fused-loop carry)
# ---------------------------------------------------------------------------


def test_telemetry_bit_identical_dense(engine, tel_engine):
    toks = mid_df_tokens(engine.index, 4)
    for q in (toks[0:2], toks[1:4]):
        r_base = engine.query(q, k=2, extract=False)
        r_tel = tel_engine.query(q, k=2, extract=False)
        np.testing.assert_array_equal(r_base.weights, r_tel.weights)
        np.testing.assert_array_equal(r_base.roots, r_tel.roots)
        assert r_base.supersteps == r_tel.supersteps
        assert r_base.telemetry is None
        tel = r_tel.telemetry
        assert tel is not None and tel.n_steps == r_tel.supersteps
        assert not tel.truncated
        # Column semantics: message columns are cumulative (nondecreasing,
        # per-step deltas nonnegative); the run converged, so the final
        # frozen count covers the lane and the totals match the result.
        assert np.all(np.diff(tel.msgs_bfs) >= 0)
        assert np.all(np.diff(tel.msgs_deep) >= 0)
        assert np.all(tel.msgs_bfs_delta >= 0)
        assert int(tel.frozen[-1]) == 1
        assert tel.msgs_bfs[-1] == pytest.approx(r_tel.msgs_bfs)
        assert tel.msgs_deep[-1] == pytest.approx(r_tel.msgs_deep)
        rows = tel.rows()
        assert [r["step"] for r in rows] == list(range(1, tel.n_steps + 1))
        assert tel.summary()["msgs_total"] == pytest.approx(
            r_tel.msgs_bfs + r_tel.msgs_deep)


def test_telemetry_batch_and_lane_sums(engine, tel_engine):
    toks = mid_df_tokens(engine.index, 4)
    queries = [toks[0:2], toks[2:4]]
    base = engine.query_batch(queries, k=1, extract=False)
    tel = tel_engine.query_batch(queries, k=1, extract=False)
    for rb, rt in zip(base, tel):
        np.testing.assert_array_equal(rb.weights, rt.weights)
        assert rt.telemetry is not None
    # One bucket = one fused dispatch = ONE lane-summed telemetry record
    # shared by the bucket's results; its final frozen count is the lanes.
    assert tel[0].telemetry is tel[1].telemetry
    assert int(tel[0].telemetry.frozen[-1]) == len(queries)


def test_telemetry_bit_identical_sharded(graph_data):
    g, index = graph_data
    base = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
        max_supersteps=32, partition="sharded", n_shards=1))
    tel = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
        max_supersteps=32, partition="sharded", n_shards=1, telemetry=True))
    q = mid_df_tokens(index, 2)
    r_base = base.query(q, k=1, extract=False)
    r_tel = tel.query(q, k=1, extract=False)
    np.testing.assert_array_equal(r_base.weights, r_tel.weights)
    np.testing.assert_array_equal(r_base.roots, r_tel.roots)
    assert r_tel.telemetry is not None
    assert r_tel.telemetry.n_steps == r_tel.supersteps


def test_instrumented_parity_with_collector(engine, tel_engine):
    """query_instrumented is a compat wrapper over the shared collector:
    its legacy history rows ARE telemetry.rows(), and the counters agree
    with the device-carried buffer for the same query."""
    q = mid_df_tokens(engine.index, 2)
    res, info = engine.query_instrumented(q, k=1)
    tel = info["telemetry"]
    assert info["history"] == tel.rows()
    assert tel.n_steps == res.supersteps
    assert tel.best is not None  # host collector tracks best weight
    r_dev = tel_engine.query(q, k=1, extract=False)
    dev = r_dev.telemetry
    assert dev.n_steps == tel.n_steps
    np.testing.assert_array_equal(dev.frontier, tel.frontier)
    np.testing.assert_allclose(dev.msgs_bfs, tel.msgs_bfs)
    np.testing.assert_allclose(dev.msgs_deep, tel.msgs_deep)


# ---------------------------------------------------------------------------
# Serve-layer observability (traces + /metrics)
# ---------------------------------------------------------------------------


def test_trace_completeness_coalescing_and_single_flight(engine):
    toks = mid_df_tokens(engine.index, 6)
    distinct = [toks[0:2], toks[2:4], toks[4:6]]
    with DKSService(engine, ServeConfig(max_batch=4, max_wait_ms=250.0,
                                        cache_size=8)) as svc:
        # Three DISTINCT same-shape queries coalesce into one bucket.
        served = [f.result(timeout=300)
                  for f in [svc.submit(q, k=1) for q in distinct]]
        assert [s.batch_size for s in served] == [3, 3, 3]
        traces = [svc.trace(s.trace_id) for s in served]
        leader, riders = traces[0], traces[1:]
        names = {sp.name for sp in leader.spans}
        assert {"admit", "cache_lookup", "queue_wait", "coalesce",
                "device_dispatch", "extract"} <= names
        coalesce = next(sp for sp in leader.spans if sp.name == "coalesce")
        assert coalesce.attrs["fill"] == 3 and coalesce.attrs["shape"] == "m2k1"
        dispatch = next(
            sp for sp in leader.spans if sp.name == "device_dispatch")
        assert dispatch.attrs["compiled"] in (True, False)
        for tr in riders:
            assert tr.links["coalesced_into"] == leader.trace_id
            assert tr.attrs["outcome"] == "served"
        # A repeat is a cache hit: its trace resolves without queue spans.
        hit = svc.query(distinct[0], k=1)
        assert hit.cache_hit
        hit_tr = svc.trace(hit.trace_id)
        assert hit_tr.attrs["outcome"] == "cache_hit"
        assert {sp.name for sp in hit_tr.spans} == {"admit", "cache_lookup"}
        # Five identical concurrent misses: leader + 4 single-flight
        # attachees, each with its own finished trace linking the leader.
        q = toks[1:3]
        sf = [f.result(timeout=300)
              for f in [svc.submit(q, k=1) for _ in range(5)]]
        sf_traces = [svc.trace(s.trace_id) for s in sf]
        followers = [t for t in sf_traces if "coalesced_into" in t.links]
        assert len(followers) == 4
        lead_id = {t.links["coalesced_into"] for t in followers}
        assert lead_id == {t.trace_id for t in sf_traces
                           if "coalesced_into" not in t.links}
        # Completeness: every admitted request resolved to one finished
        # trace (no leaks from any resolve path).
        st = svc.tracer.stats()
        assert st["begun"] == st["finished"] == 9
        assert len(svc.recent_traces(100)) == 9


def test_metrics_surface_matches_stats_and_is_monotone(engine):
    toks = mid_df_tokens(engine.index, 4)
    with DKSService(engine, ServeConfig(max_batch=2, max_wait_ms=5.0,
                                        cache_size=8)) as svc:
        svc.query(toks[0:2], k=1)
        svc.query(toks[0:2], k=1)  # cache hit
        first = parse_prometheus(svc.registry.render())
        stats = svc.stats()
        assert first["dks_requests_total"] == stats.requests == 2
        assert first["dks_cache_hits_total"] == stats.cache_hits == 1
        assert first["dks_batch_dispatches_total"] == stats.batch_dispatches
        assert first["dks_request_latency_ms_count"] == stats.requests
        assert first["dks_engine_execute_count_total"] == \
            engine.execute_count
        assert first["dks_traces_begun_total"] == \
            first["dks_traces_finished_total"] == 2
        # Dispatch-reason counters partition total dispatches.
        reasons = (first["dks_dispatch_reason_full_total"]
                   + first["dks_dispatch_reason_window_total"]
                   + first["dks_dispatch_reason_flush_total"])
        assert reasons == stats.batch_dispatches + stats.deadline_dispatches
        svc.query(toks[2:4], k=1)
        second = parse_prometheus(svc.registry.render())
        for name in ("dks_requests_total", "dks_cache_misses_total",
                     "dks_batch_dispatches_total",
                     "dks_request_latency_ms_count"):
            assert second[name] > first[name], f"{name} must be monotone"
        assert second["dks_cache_hits_total"] == first["dks_cache_hits_total"]


def test_metrics_server_endpoints(engine):
    toks = mid_df_tokens(engine.index, 2)
    with DKSService(engine, ServeConfig(max_batch=2, max_wait_ms=5.0,
                                        cache_size=8)) as svc:
        svc.query(toks, k=1)
        server = MetricsServer(svc.registry, tracer=svc.tracer).start()
        try:
            def get(path):
                with urllib.request.urlopen(server.url + path,
                                            timeout=30) as resp:
                    return resp.read().decode()

            assert get("/healthz").strip() == "ok"
            scraped = parse_prometheus(get("/metrics"))
            assert scraped["dks_requests_total"] == svc.stats().requests
            lines = [json.loads(ln)
                     for ln in get("/traces?n=8").splitlines() if ln]
            assert len(lines) == 1
            span_names = {sp["name"] for sp in lines[0]["spans"]}
            assert {"admit", "device_dispatch"} <= span_names
            one = json.loads(get(f"/traces?id={lines[0]['trace_id']}"))
            assert one["trace_id"] == lines[0]["trace_id"]
        finally:
            server.stop()
