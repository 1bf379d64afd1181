#!/usr/bin/env python3
"""On-chip smoke test: DKS serving on one TPU at the paper's own scale.

    python chip_smoke.py                # one chip: pallas serving vs jnp
    python chip_smoke.py --four-chips   # sharded jnp on 4 chips vs dense

One chip (the default): generates the paper's ``sec-rdfabout`` graph
(460,451 nodes, 500,384 edges, Sec. 7.1) from its seed, builds a
``backend="pallas"`` engine and a ``backend="jnp"`` reference engine, and
serves a short replay through :class:`repro.serve.DKSService` on the
pallas engine: keyword counts m in {2, 3}, k in {1, 2}, exact requests, a
burst of deadline requests and a ``return_trees=True`` request.  The lane
count of a dispatch is sized from the fused program's memory analysis.
It fails unless no request failed, each m has a finite exact answer, the
exact answers' top-K weights equal the jnp engine's bit for bit, and a
keyword-covering answer tree is served.

``--four-chips`` runs only the distributed path (the paper's Pregel
search sharded over a mesh, ``partition="sharded"``) and its reference:
a sharded jnp engine over four chips with an exact frontier cap, against
a dense jnp engine on one chip, on the replay's distinct top-2 queries.
It fails unless the packed graph's shards sit on four distinct devices
and the top-K weights are bit-identical.  Both modes stop every query
at ``MAX_SUPERSTEPS``.

The script refuses to run (non-zero exit, no result line) when JAX's
default device is not a TPU; all work runs in this one process.  The
compile cache is ``JAX_COMPILATION_CACHE_DIR`` when set, otherwise
``.jax_cache/`` in the checkout.  Times are printed for the record; none
is a benchmark metric.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
# The TPU library writes its logs to a fixed directory under /tmp unless
# told otherwise; the smoke needs none.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import INF  # noqa: E402
from repro.configs import DKS_CONFIGS  # noqa: E402
from repro.engine import ExecutionPolicy, QueryEngine  # noqa: E402
from repro.kernels import interpret_mode  # noqa: E402
from repro.launch import enable_compile_cache  # noqa: E402
from repro.launch.dks_query import generate_dataset  # noqa: E402
from repro.launch.serve_dks import verify_served, verify_trees  # noqa: E402
from repro.serve import DKSService, ServeConfig  # noqa: E402
from repro.serve.loadgen import latency_split, make_trace, replay  # noqa: E402

DATASET = "sec-rdfabout"
# A bound on each query's run time, not on its answer: every query of the
# trace proves its exit before it (on a v5e the replay took as long at 32
# supersteps as at 16).
MAX_SUPERSTEPS = 16
MAX_LANES = 8
# Share of the HBM left free after the resident graphs that one dispatch's
# temporaries and outputs may take; the rest covers the other programs'
# buffers and the allocator's fragmentation.
HBM_SHARE = 0.75
N_CLIENTS = 8


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


def require_tpu():
    """JAX's default device, which must be a TPU: a run that cannot see
    the chip stops here instead of emulating it."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's default device is "
                 f"{dev.platform!r}, {dev.device_kind!r}); run it on a "
                 f"TPU host")
    return dev


def build_graph(ds):
    (g, index), dt = timed(generate_dataset, ds)
    print(f"graph {ds.name}: V={g.n_nodes:,} E_sym={g.n_edges_sym:,}, "
          f"generated in {dt:.3f}s")
    return g, index


def build_trace(index, seed: int) -> list:
    """20 requests over m in {2, 3}: ten at k=1 carrying a burst of
    deadline requests, then ten exact ones at k=2."""
    return (make_trace(index, 10, unique=5, k=1, deadline_frac=0.3,
                       seed=seed)
            + make_trace(index, 10, unique=5, k=2, seed=seed + 1))


def size_lanes(engine, m: int, k: int, free_bytes: int):
    """Lanes per dispatch from the fused program's memory analysis.

    One lane's temporaries and outputs (the candidate tensor dominates)
    set the count that fits in ``HBM_SHARE`` of ``free_bytes``, at most
    ``MAX_LANES``; the program is then compiled at that count and checked
    to fit.  Returns ``(lanes, compiled program)``."""
    fn = engine._executable(engine._config(m, k), "fused")

    def compile_at(lanes: int):
        masks = jax.ShapeDtypeStruct((lanes, m, engine.v_pad), jnp.bool_)
        compiled, dt = timed(lambda: fn.lower(
            engine.device_graph, engine.lane_csr, masks).compile())
        mem = compiled.memory_analysis()
        need = mem.temp_size_in_bytes + mem.output_size_in_bytes
        print(f"fused m={m} k={k} lanes={lanes}: compiled in {dt:.3f}s; "
              f"temp={mem.temp_size_in_bytes:,} B "
              f"out={mem.output_size_in_bytes:,} B "
              f"args={mem.argument_size_in_bytes:,} B")
        return compiled, need

    _, per_lane = compile_at(1)
    lanes = max(1, min(MAX_LANES, int(HBM_SHARE * free_bytes // per_lane)))
    compiled, need = compile_at(lanes)
    check(need <= HBM_SHARE * free_bytes,
          f"{lanes} lanes need {need:,} B; {free_bytes:,} B free")
    print(f"lanes per dispatch: {lanes} ({need:,} B of {free_bytes:,} B "
          f"free)")
    return lanes, compiled


def warm(engine, shapes, lanes: int, deadline_shapes=()):
    """Compile every program the replay dispatches, with one timed call
    each: the fused driver per (m, k) with the batched answer backtrace,
    and the stepwise driver where deadlines ride."""
    for m, k, q in shapes:
        # Compiled ahead with the call's own argument types, so the call
        # reuses the program and its time is the answer backtrace's
        # compile plus the run.
        fn = engine._executable(engine._config(m, k), "fused")
        masks = jnp.zeros((lanes, m, engine.v_pad), jnp.bool_)
        _, dt_c = timed(lambda: fn.lower(engine.device_graph,
                                         engine.lane_csr, masks).compile())
        _, dt = timed(engine.query_batch, [list(q)] * lanes, k, extract=True)
        print(f"fused m={m} k={k} lanes={lanes}: compiled in {dt_c:.3f}s, "
              f"first call {dt:.3f}s")
    for m, k, q in deadline_shapes:
        _, dt = timed(engine.query_deadline_batch, [list(q)] * lanes, k,
                      deadline_s=600.0, extract=True)
        print(f"stepwise m={m} k={k} lanes={lanes}: first call {dt:.3f}s "
              f"(compile + run)")


def finite_ms(results) -> dict:
    """m -> number of results whose best weight is finite."""
    out: dict = {}
    for r in results:
        if float(r.weights[0]) < INF:
            out[r.m] = out.get(r.m, 0) + 1
    return out


def serve_phase(ds, seed: int, lanes: int | None = None) -> dict:
    """The one-chip phase: engines, sizing, warm-up, replay, checks.

    Without ``lanes``, the lane count is sized from the fused program
    against the device memory left free once both engines are resident.
    Returns the compiled fused program of the sizing (None without
    sizing) and the served results."""
    g, index = build_graph(ds)
    policy = ExecutionPolicy(backend="jnp", max_supersteps=MAX_SUPERSTEPS)
    ref, dt_ref = timed(QueryEngine.build, g, index=index, policy=policy)
    print(f"jnp engine built in {dt_ref:.3f}s (graph to device)")
    eng, dt_eng = timed(QueryEngine.build, g, index=index,
                        policy=ExecutionPolicy(
                            backend="pallas", max_supersteps=MAX_SUPERSTEPS))
    csr = eng.lane_csr
    print(f"pallas engine built in {dt_eng:.3f}s (graph to device + "
          f"LaneCSR); LaneCSR alone: {eng.lane_csr_build_s:.3f}s, "
          f"{csr.n_rows:,} rows x {csr.dmax} slots in {csr.block_v}-row "
          f"blocks, at most {csr.span} rows per node")

    trace = build_trace(index, seed)
    shapes = sorted({(len(r.keywords), r.k): r.keywords
                     for r in trace}.items())
    shapes = [(m, k, q) for (m, k), q in shapes]
    dl_shapes = sorted({(len(r.keywords), r.k): r.keywords for r in trace
                        if r.deadline_ms is not None}.items())
    dl_shapes = [(m, k, q) for (m, k), q in dl_shapes]
    print(f"trace: {len(trace)} requests, shapes (m, k) "
          f"{[(m, k) for m, k, _ in shapes]}, "
          f"{sum(r.deadline_ms is not None for r in trace)} with deadlines")

    compiled = None
    if lanes is None:
        stats = jax.devices()[0].memory_stats()
        big = max(shapes, key=lambda s: (s[0], s[1]))
        lanes, compiled = size_lanes(
            eng, big[0], big[1], stats["bytes_limit"] - stats["bytes_in_use"])
    warm(eng, shapes, lanes, dl_shapes)

    cfg = ServeConfig(max_batch=lanes, max_wait_ms=25.0, pad_batches="max",
                      trace_seed=seed)
    with DKSService(eng, cfg) as svc:
        served, dt = timed(replay, svc, trace, n_clients=N_CLIENTS)
        tree_kw, n_trees = verify_trees(svc, eng, trace, k=2)
        stats = svc.stats()
    print(f"replayed {len(trace)} requests through {N_CLIENTS} clients in "
          f"{dt:.3f}s: {stats.failures} failed, {stats.cache_hits} cache "
          f"hits, {stats.deadline_dispatches} deadline dispatches, "
          f"{stats.approximate} approximate")
    check(stats.failures == 0, f"{stats.failures} requests failed")
    split = latency_split(served)
    print(f"served latency (warm): p50 {split['latency_p50_ms']:.3f} ms, "
          f"p95 {split['latency_p95_ms']:.3f} ms; device p50 "
          f"{split['device_p50_ms']:.3f} ms")

    refs: dict = {}
    n_exact, n_approx = verify_served(ref, trace, served, refs=refs)
    exact = []
    for req, srv in zip(trace, served):
        if srv.approximate:
            continue
        key = (req.keywords, req.k)
        check(np.array_equal(np.asarray(srv.result.weights),
                             np.asarray(refs[key].weights)),
              f"pallas weights {srv.result.weights} != jnp "
              f"{refs[key].weights} for {req.keywords} k={req.k}")
        exact.append(srv.result)
    found = finite_ms(exact)
    for m in sorted({len(r.keywords) for r in trace}):
        check(found.get(m, 0) > 0, f"no exact finite answer for m={m}")
    print(f"pallas == jnp bit-identical on {len(exact)} exact answers "
          f"({n_approx} approximate within their bounds); finite exact "
          f"answers per m: {found}")
    print(f"answer trees: {n_trees} distinct keyword-covering trees for "
          f"{tree_kw}")
    return {"compiled": compiled, "served": served}


def four_chip_phase(ds, seed: int, n_shards: int = 4) -> None:
    """Sharded jnp over ``n_shards`` devices vs dense jnp on one."""
    g, index = build_graph(ds)
    dense, dt_d = timed(QueryEngine.build, g, index=index,
                        policy=ExecutionPolicy(
                            backend="jnp", max_supersteps=MAX_SUPERSTEPS))
    sharded, dt_s = timed(QueryEngine.build, g, index=index,
                          policy=ExecutionPolicy(
                              backend="jnp", partition="sharded",
                              n_shards=n_shards, frontier_frac=1.0,
                              max_supersteps=MAX_SUPERSTEPS))
    print(f"dense engine built in {dt_d:.3f}s, sharded ({n_shards} shards) "
          f"in {dt_s:.3f}s")
    fg = sharded.device_graph
    on = {s.device for s in fg.edge_src.addressable_shards}
    check(len(on) == n_shards,
          f"edge shards on {len(on)} devices, want {n_shards}")
    devs = {d for leaf in jax.tree_util.tree_leaves(fg) for d in leaf.devices()}
    check(devs == on, f"packed graph spans {devs}, edges {on}")
    print(f"packed graph shards on {len(on)} distinct devices: "
          f"{sorted(str(d) for d in on)}")

    # The trace's distinct top-2 queries (both m): the stricter
    # comparison, at one compiled program per m on each engine.
    k = 2
    qs = sorted({r.keywords for r in build_trace(index, seed) if r.k == k})
    qs = [list(q) for q in qs]
    # One lane at a time on the dense engine: its jnp temporaries are
    # several GB per lane at this scale.
    want, dt_w = timed(lambda: [dense.query(q, k, extract=False)
                                for q in qs])
    got, dt_g = timed(sharded.query_batch, qs, k, extract=False)
    for q, a, b in zip(qs, want, got):
        check(np.array_equal(np.asarray(a.weights), np.asarray(b.weights)),
              f"sharded {b.weights} != dense {a.weights} for {q}")
    print(f"k={k}: {len(qs)} queries, dense {dt_w:.3f}s, sharded "
          f"{dt_g:.3f}s (first calls, compile included)")
    found = finite_ms(want)
    for m in sorted({len(q) for q in qs}):
        check(found.get(m, 0) > 0, f"no finite answer for m={m}")
    print(f"sharded == dense bit-identical on {len(want)} queries; "
          f"finite answers per m: {found}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path over four chips and "
                         "its dense one-chip reference")
    ap.add_argument("--seed", type=int, default=6,
                    help="request-trace seed (the graph has its own)")
    args = ap.parse_args(argv)

    dev = require_tpu()
    print(f"device: {dev.platform} {dev.device_kind}, "
          f"{len(jax.devices())} visible")
    print(f"compile cache: {enable_compile_cache()}")
    check(not interpret_mode(), "pallas would run interpreted")
    print("pallas interpret mode: off")
    ds = DKS_CONFIGS[DATASET]

    if args.four_chips:
        check(len(jax.devices()) >= 4,
              f"--four-chips needs 4 devices, found {len(jax.devices())}")
        four_chip_phase(ds, args.seed)
    else:
        out = serve_phase(ds, args.seed)
        check("tpu_custom_call" in out["compiled"].as_text(),
              "the fused program holds no tpu_custom_call")
        print("fused program holds a tpu_custom_call (Mosaic kernel)")
        peak = dev.memory_stats()["peak_bytes_in_use"]
        print(f"peak_bytes_in_use: {peak:,}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
