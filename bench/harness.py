"""One run of one benchmark cell: set-up, a timed closed-loop window
through ``DKSService``, the check against the plain reference, the
metrics.  ``run.py`` is the command; this module is what it runs.

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration file (``bench/configs/``) and the graph kind it names
(``bench/graphs/<kind>.py``), its traffic file (``bench/traffic/``) and
the traffic kind it names (``bench/traffic/<kind>.py``), and one reader
per per-layer metric (``bench/metrics/<metric name>.py``, a
``read(ctx)`` that returns a number, or None when the run holds nothing
to read).
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

import graphgen
import reference
import stats
import tracereduce
import traffic as trafficgen

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache, at a fixed path inside the checkout
# (the path is part of an entry's key, so it may not move between runs).
CACHE_DIR = ROOT / ".jax_cache"
# Seconds past the close of the window that an answer may take to come.
LATE_S = 60.0
# The service's spans of its dispatcher thread, which idle gaps are
# charged to.
DISPATCHER_SPANS = ("device_dispatch", "extract", "render", "cache_store")


# --------------------------------------------------------------------------
# The cell, found by name
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(workload: str) -> Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = trafficgen.load_params(w["traffic"])

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(workload, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)])


def load_reader(metric_name: str):
    return graphgen.load_module(BENCH / "metrics", metric_name,
                                "metric").read


# --------------------------------------------------------------------------
# Device
# --------------------------------------------------------------------------


def device_info(chips: int, require_tpu: bool) -> dict | None:
    """The device as JAX reports it, or None (with the reason on stderr)
    when there is no TPU or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and (info["platform"] != "tpu" or info["count"] < chips):
        print(f"bench: needs {chips} TPU chip(s); JAX's default devices are "
              f"{info['count']} x {info['platform']} ({info['kind']})",
              file=sys.stderr)
        return None
    return info


def prepare() -> None:
    """Make the program importable, and keep the TPU runtime's logs out
    of a fixed directory under /tmp."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))


def enable_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs loaded for the device (``loads``) and those of
    them found in the persistent cache (``hits``); the rest compiled."""

    LOAD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax

        self.loads = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_load)
        jax.monitoring.register_event_listener(self._on_hit)

    def _on_load(self, event: str, _secs: float, **_kw) -> None:
        self.loads += event == self.LOAD

    def _on_hit(self, event: str, **_kw) -> None:
        self.hits += event == self.HIT

    def __str__(self) -> str:
        return (f"{self.loads} programs loaded, {self.hits} from the "
                f"compile cache, {self.loads - self.hits} compiled")


# --------------------------------------------------------------------------
# Set-up: graph, engine, service, warm programs
# --------------------------------------------------------------------------


@dataclasses.dataclass
class System:
    dataset: graphgen.Dataset
    sym: reference.SymGraph
    postings: dict
    engine: object
    serve_config: object


def build_system(config: dict, seed: int) -> System:
    from repro.engine import ExecutionPolicy, QueryEngine
    from repro.graph.index import InvertedIndex
    from repro.graph.structure import build_graph
    from repro.serve import ServeConfig

    ds = graphgen.make_dataset(config, seed)
    g = build_graph(ds.src, ds.dst, ds.n_nodes, w=ds.w)
    index = InvertedIndex.from_token_matrix(ds.tokens)
    engine = QueryEngine.build(g, index=index,
                               policy=ExecutionPolicy(**config["policy"]))
    sym = reference.SymGraph.from_directed(ds.n_nodes, ds.src, ds.dst, ds.w)
    return System(ds, sym, graphgen.postings(ds.tokens), engine,
                  ServeConfig(**config["serve"]))


def lane_counts(serve_config) -> list[int]:
    """Every lane count the service's padding can dispatch."""
    top = serve_config.max_batch
    if serve_config.pad_batches == "max":
        return [top]
    if serve_config.pad_batches != "pow2":
        return list(range(1, top + 1))
    out, p = [], 1
    while p < top:
        out.append(p)
        p *= 2
    return out + [top]


def warm(system: System, requests: list, log) -> int:
    """Run every program the requests' shapes can dispatch once, at every
    lane count, with calls that do no real search: each keyword set
    repeats one token, so every node carrying it holds the whole set at
    weight 0 and the search proves its exit at the first superstep.
    Returns the calls made.  A traffic kind whose requests run other
    programs defines a ``warm`` of its own, which the harness calls in
    place of this one."""
    eng, cfg = system.engine, system.serve_config
    token = max(system.postings, key=lambda t: len(system.postings[t]))
    shapes = sorted({(len(r.keywords), r.k) for r in requests})
    calls, t0 = 0, time.perf_counter()
    for lanes in lane_counts(cfg):
        for m, k in shapes:
            eng.query_batch([[token] * m] * lanes, k=k, extract=cfg.extract,
                            strict=cfg.strict, n_real=lanes)
            calls += 1
        log(f"warmed {lanes}-lane programs at "
            f"{time.perf_counter() - t0:.3f}s of warm-up")
    return calls


# --------------------------------------------------------------------------
# What a traffic kind's window records
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Record:
    index: int
    t_submit: float
    t_done: float | None = None
    served: object = None
    error: str | None = None
    trace: dict | None = None


def trace_of(svc, served) -> dict | None:
    """The service's trace of a served request, as plain data."""
    tr = svc.trace(served.trace_id) if served.trace_id is not None else None
    if tr is None:
        return None
    return {"id": tr.trace_id, "links": dict(tr.links),
            "spans": [(s.name, s.t_start, s.t_end, dict(s.attrs))
                      for s in tr.spans]}


# --------------------------------------------------------------------------
# The check against the plain reference
# --------------------------------------------------------------------------


def pick_checked(records: list[Record], requests: list, limit: int,
                 seed: int) -> list[Record]:
    """Every answered request when they are at most ``limit``; otherwise
    the request with the most supersteps of each shape, the first of
    each way of being served (from the device, the cache or another
    request's run), and a sample drawn from the seed for the rest."""
    done = [r for r in records if r.served is not None]
    if len(done) <= limit:
        return done
    longest, first = {}, {}
    for r in done:
        q, s = requests[r.index], r.served
        key = (len(q.keywords), q.k)
        if (key not in longest or s.result.supersteps
                > longest[key].served.result.supersteps):
            longest[key] = r
        first.setdefault((s.cache_hit, s.coalesced), r)
    chosen = {r.index: r for r in [*longest.values(), *first.values()]}
    rest = [r for r in done if r.index not in chosen]
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 11])
    for j in rng.permutation(len(rest))[: max(0, limit - len(chosen))]:
        chosen[rest[int(j)].index] = rest[int(j)]
    return sorted(chosen.values(), key=lambda r: r.index)


def same_weights(got, want) -> bool:
    """The exact comparison of top-K answer weights (INF-padded)."""
    return np.array_equal(np.asarray(got, np.float64),
                          np.asarray(want, np.float64))


def check(system: System, requests: list, checked: list[Record]) -> dict:
    """Counts of answers that disagree with the plain reference:
    ``wrong_weights`` (answers whose top-K weights differ, or that came
    back approximate) and ``bad_trees`` (answer trees that are not
    keyword-covering trees of the graph with their stated weight, or an
    answer whose best tree misses the optimum)."""
    edge_w = system.sym.edge_weights()
    want_cache: dict = {}
    out = {"wrong_weights": 0, "bad_trees": 0, "checked": len(checked)}
    for rec in checked:
        q = requests[rec.index]
        key = (tuple(sorted(q.keywords)), q.k)
        kw = [system.postings[t] for t in q.keywords]
        if key not in want_cache:
            want_cache[key] = reference.answer_weights(system.sym, kw, q.k)
        want = want_cache[key]
        s = rec.served
        if s.approximate or not same_weights(s.result.weights, want):
            out["wrong_weights"] += 1
        trees = [(t.edges, t.nodes, t.weight) for t in s.result.answers]
        bad = any(reference.check_tree(e, n, w, kw, edge_w) is not None
                  for e, n, w in trees)
        bad |= any(w < want[0] for _e, _n, w in trees)
        if want[0] < reference.INF:
            bad |= not s.result.answers or s.result.answers[0].weight != want[0]
        out["bad_trees"] += int(bad)
    return out


LIMITS = {"wrong_weights": ("max", 0), "bad_trees": ("max", 0),
          "failed": ("max", 0), "checked": ("min", 1)}


def within(checks: dict) -> bool:
    for name, (kind, limit) in LIMITS.items():
        v = checks[name]
        if (kind == "max" and v > limit) or (kind == "min" and v < limit):
            return False
    return True


# --------------------------------------------------------------------------
# Metrics
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    window: list[Record]          # answered inside the window
    dispatches: list[dict]        # device dispatches of the window
    n_nodes: int
    n_edges_sym: int
    device: dict                  # the device as JAX reports it
    trace: dict | None            # tracereduce.load output
    trace_window: tuple | None    # (lo_ns, hi_ns) of the window
    to_trace_ns: object           # perf_counter seconds -> trace ns


def dispatches_of(records: list[Record], requests: list) -> list[dict]:
    """Device dispatches from the service's own spans: each bucket
    leader's trace holds ``coalesce`` (fill, lanes), ``device_dispatch``
    and ``extract``; riders link to it with ``coalesced_into``."""
    by_id = {r.trace["id"]: r for r in records if r.trace is not None}
    groups: dict[int, list[Record]] = {}
    for r in by_id.values():
        s = r.served
        if s.cache_hit or s.coalesced or s.device_ms is None:
            continue
        lead = r.trace["links"].get("coalesced_into", r.trace["id"])
        groups.setdefault(lead, []).append(r)
    out = []
    for lead, members in groups.items():
        spans = {n: (a, b, at) for n, a, b, at in
                 by_id[lead].trace["spans"]} if lead in by_id else {}
        if "coalesce" not in spans or "device_dispatch" not in spans:
            continue
        q0 = requests[members[0].index]
        out.append({
            "m": len(q0.keywords), "k": q0.k,
            "lanes": int(spans["coalesce"][2]["lanes"]),
            "fill": int(spans["coalesce"][2]["fill"]),
            "t_start": spans["device_dispatch"][0],
            "t_end": spans.get("extract", spans["device_dispatch"])[1],
            "wall_s": members[0].served.result.wall_time_s,
            "supersteps": [r.served.result.supersteps for r in members],
        })
    return out


def end_to_end(window: list[Record], seconds: float) -> dict:
    lat = [(r.t_done - r.t_submit) * 1e3 for r in window]
    return {"queries_per_s": stats.rate(len(window), seconds),
            "latency_p50_ms": stats.percentile(lat, 50),
            "latency_p90_ms": stats.percentile(lat, 90)}


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, require_tpu: bool = True) -> int:
    """One run of ``cell``; prints progress and the checks to stderr and
    the result line last to stdout.  Returns the exit code."""
    prepare()
    device = device_info(cell.chips, require_tpu)
    if device is None:
        return 2
    tag = f"[{device['platform']} {device['kind']} x{device['count']}]"

    def log(msg: str) -> None:
        print(f"{tag} {msg}", file=sys.stderr, flush=True)

    import jax

    enable_compile_cache()
    compiles = CompileCounter()
    log(f"JAX up at {time.perf_counter() - t_process:.3f}s")
    system = build_system(cell.config, seed)
    dfs = {t: len(v) for t, v in system.postings.items()}
    kind = trafficgen.kind(cell.traffic)
    requests = kind.make_requests(cell.traffic, dfs, seed)
    log(f"graph V={system.dataset.n_nodes} E_sym={system.sym.n_edges}, "
        f"engine built, {len(requests)} requests drawn, seed {seed}, at "
        f"{time.perf_counter() - t_process:.3f}s")
    calls = getattr(kind, "warm", warm)(system, requests, log)
    from repro.serve import DKSService

    svc = DKSService(system.engine, system.serve_config).start()
    setup_s = time.perf_counter() - t_process
    log(f"set-up {setup_s:.3f}s: {calls} warm-up calls; {compiles}")

    profile_dir = tempfile.mkdtemp(prefix="bench-profile-") if trace else None
    sync = {}

    def on_start():
        if trace:
            jax.profiler.start_trace(profile_dir)
            with jax.profiler.TraceAnnotation("bench.clock_sync"):
                sync["t"] = time.perf_counter()

    loads_before = compiles.loads
    try:
        records, t0, t1 = kind.run_window(svc, requests, cell.traffic,
                                          seconds, on_start=on_start)
        if trace:
            jax.profiler.stop_trace()
    finally:
        svc.stop()
    log(f"programs loaded or compiled inside the window: "
        f"{compiles.loads - loads_before}")
    peak = None
    mem = jax.devices()[0].memory_stats()
    if mem:
        peak = int(mem.get("peak_bytes_in_use", 0)) or None

    window = [r for r in records if r.served is not None
              and r.t_done is not None and r.t_done <= t1]
    attempted = len(records)
    errors = [r.error for r in records if r.served is None]
    failed = len(errors)
    if errors:
        log(f"{failed} requests failed; the first: {errors[0]}")

    verify = cell.config["verify"]
    checked = pick_checked(records, requests, int(verify["max_requests"]),
                           seed)
    t_check = time.perf_counter()
    checks = check(system, requests, checked)
    checks["failed"] = failed
    log(f"checked {len(checked)} answers against the reference in "
        f"{time.perf_counter() - t_check:.3f}s")

    result_device = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if trace:
        tr = tracereduce.load(profile_dir)
        shutil.rmtree(profile_dir, ignore_errors=True)
        anchor = tracereduce.find_event(tr["host"], "bench.clock_sync")
        shift = anchor[1] - sync["t"] * 1e9

        def to_ns(t: float) -> float:
            return t * 1e9 + shift

        lo, hi = to_ns(t0), to_ns(t1)
        summary = tracereduce.device_summary(tr, lo, hi)
        result_device.update(busy_s=summary["busy_s"],
                             window_s=summary["window_s"])
        # What the dispatcher thread was doing; a gap outside all of
        # these is the batcher waiting for requests.
        host = {}
        for r in records:
            for name, a, b, _at in (r.trace or {}).get("spans", []):
                if name in DISPATCHER_SPANS and b is not None:
                    host.setdefault(name, []).append((to_ns(a), to_ns(b)))
        breakdown = {
            "device_ops": tracereduce.top(tracereduce.short_names(
                summary["per_op"])),
            "idle_gaps": tracereduce.top(tracereduce.idle_gaps(
                summary["intervals"], lo, hi, host)),
        }
        ctx = Context(window, dispatches_of(
            [r for r in records if r.served is not None], requests),
            system.dataset.n_nodes, system.sym.n_edges, device,
            tr | {"summary": summary}, (lo, hi), to_ns)
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = end_to_end(window, seconds)
        values["setup_s"] = setup_s
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}

    correct = bool(window) and within(checks)
    shown = {name: {"value": checks[name], kind: limit}
             for name, (kind, limit) in LIMITS.items()}
    for name, v in shown.items():
        kind = "at most" if "max" in v else "at least"
        lim = v.get("max", v.get("min"))
        print(f"{tag} check {name}: {v['value']} ({kind} {lim})",
              file=sys.stderr)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": result_device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = shown
    sys.stderr.flush()
    print(json.dumps(line))
    return 0
