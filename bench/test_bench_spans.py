"""The readers of the engine's extraction spans, the batcher's queue
depth and the dispatcher's device idle share, on a synthetic run."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import tracereduce  # noqa: E402

MS = 1e6  # ns


def leader(index: int, t0: float, waiting: int) -> harness.Record:
    """A bucket leader whose dispatch starts at ``t0`` seconds: 10 ms on
    the device, then 2 ms of backtrace, 3 ms of trees, 1 ms of results."""
    spans = [
        ("coalesce", t0 - 0.005, t0, {"fill": 1, "lanes": 1,
                                      "waiting": waiting,
                                      "waiting_buckets": 1}),
        ("device_dispatch", t0, t0 + 0.010, {"lanes": 1}),
        ("extract", t0 + 0.010, t0 + 0.016, {}),
        ("backtrace", t0 + 0.010, t0 + 0.012, {}),
        ("trees", t0 + 0.012, t0 + 0.015, {}),
        ("results", t0 + 0.015, t0 + 0.016, {}),
    ]
    return harness.Record(index, t0 - 0.005, t0 + 0.017,
                          trace={"id": index, "links": {}, "spans": spans})


def context(records, trace=None) -> harness.Context:
    return harness.Context(
        window=records, dispatches=[], n_nodes=10, n_edges_sym=20,
        device={"kind": "TPU v5 lite"}, trace=trace,
        trace_window=(0.0, 100 * MS) if trace else None,
        to_trace_ns=lambda t: t * 1e9)


def synthetic_trace():
    # The device is busy [0, 10) and [50, 60) ms; the dispatcher's
    # annotations: a dispatch [0, 10) with its extraction [10, 16), a
    # dispatch [50, 60) with its extraction [60, 64) and a cache store
    # [64, 65); the batcher waits [20, 50).  The window is [0, 100) ms.
    host = [("bench.clock_sync", 0.0, 1.0),
            ("dks.device_dispatch", 0.0, 10 * MS),
            ("dks.extract", 10 * MS, 6 * MS),
            ("dks.backtrace", 10 * MS, 2 * MS),
            ("dks.batcher_wait", 20 * MS, 30 * MS),
            ("dks.device_dispatch", 50 * MS, 10 * MS),
            ("dks.extract", 60 * MS, 4 * MS),
            ("dks.cache_store", 64 * MS, 1 * MS),
            ("dks.render", 120 * MS, 5 * MS)]
    trace = {"host": host, "devices": {"/device:TPU:0": [
        ("fusion", 0.0, 10 * MS), ("fusion", 50 * MS, 10 * MS)]}}
    return trace | {"summary": tracereduce.device_summary(
        trace, 0.0, 100 * MS)}


@pytest.mark.parametrize("metric,want", [
    ("answers.backtrace_p50_ms", 2.0),
    ("answers.trees_p50_ms", 3.0),
    ("engine.results_p50_ms", 1.0),
    ("serve.waiting_at_dispatch", 5.0),
])
def test_span_readers(metric, want):
    records = [leader(0, 1.0, 4), leader(1, 2.0, 6),
               harness.Record(2, 1.0, 1.02)]       # a rider: no spans
    read = harness.load_reader(metric)
    assert read(context(records)) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "answers.backtrace_p50_ms", "answers.trees_p50_ms",
    "engine.results_p50_ms", "serve.waiting_at_dispatch",
    "serve.dispatch_idle_pct"])
def test_readers_read_nothing_without_their_spans(metric):
    read = harness.load_reader(metric)
    # No trace of any request, and a program whose spans lack the new
    # names and attributes: nothing to read, no error.
    assert read(context([harness.Record(0, 1.0, 1.02)])) is None
    old = leader(0, 1.0, 4)
    old.trace["spans"] = [
        (n, a, b, {k: v for k, v in at.items() if not k.startswith("wait")})
        for n, a, b, at in old.trace["spans"]
        if n not in ("backtrace", "trees", "results")]
    bare = synthetic_trace()
    bare["host"] = [e for e in bare["host"] if not e[0].startswith("dks.")]
    assert read(context([old], bare)) is None


def test_extraction_split_tiles_extract():
    records = [leader(0, 1.0, 4), leader(1, 2.0, 6)]
    ctx = context(records)
    parts = sum(harness.load_reader(m)(ctx) for m in (
        "answers.backtrace_p50_ms", "answers.trees_p50_ms",
        "engine.results_p50_ms"))
    assert parts == pytest.approx(
        harness.load_reader("answers.extract_p50_ms")(ctx))


def test_dispatch_idle_pct_reads_idle_inside_annotations():
    read = harness.load_reader("serve.dispatch_idle_pct")
    # Idle inside the annotations: [10, 16) and [60, 65) = 11 ms of the
    # 100 ms window; the batcher's wait and the render past the window
    # are not counted, nor is the nested backtrace counted twice.
    assert read(context([], synthetic_trace())) == pytest.approx(11.0)
    assert read(context([])) is None

