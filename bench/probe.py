#!/usr/bin/env python3
"""Scale probe: run a configuration's window under a traffic mix at
several cuts of its full scale, to size the cut of its file.

    python3 bench/probe.py --config lod-sec-rdfabout \\
        --traffic closed8_distinct --steps 64,32,16 --seed 5

A step divides the sizes of the file's ``full_scale`` block together.
Steps run in the order given, smallest graph first, in this one process;
the probe stops after the first step whose window completes fewer than
``--need`` requests, so the last step that reached it is the cut.  Each
step prints one JSON line: the sizes, the requests completed, the rate,
the median and 90th percentile latency, and the device seconds per
query.  No answer is checked: this sizes a cut, it measures nothing the
benchmark reports.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import traffic as trafficgen  # noqa: E402


def sized(config: dict, divisor: int) -> dict:
    return dict(config, **{key: value // divisor for key, value
                           in config["full_scale"].items()})


def probe_step(config: dict, params: dict, seed: int,
               seconds: float) -> dict:
    from repro.serve import DKSService

    system = harness.build_system(config, seed)
    dfs = {t: len(v) for t, v in system.postings.items()}
    kind = trafficgen.kind(params)
    requests = kind.make_requests(params, dfs, seed)
    t0 = time.perf_counter()
    getattr(kind, "warm", harness.warm)(system, requests, lambda msg: None)
    warm_s = time.perf_counter() - t0
    svc = DKSService(system.engine, system.serve_config).start()
    try:
        records, _t0, t1 = kind.run_window(svc, requests, params, seconds)
    finally:
        svc.stop()
    window = [r for r in records if r.served is not None and r.t_done <= t1]
    e2e = harness.end_to_end(window, seconds)
    disp = harness.dispatches_of(
        [r for r in records if r.served is not None], requests)
    real = sum(d["fill"] for d in disp)
    return {"n_nodes": system.dataset.n_nodes,
            "n_edges_sym": system.sym.n_edges,
            "vocab": config["vocab"], "warm_s": warm_s,
            "completed": len(window), **e2e,
            "device_s_per_query": (sum(d["wall_s"] for d in disp) / real
                                   if real else None)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--steps", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--need", type=int, default=100)
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or spec["run_seconds"]
    config = json.loads((harness.BENCH / "configs" / f"{args.config}.json")
                        .read_text())
    params = trafficgen.load_params(args.traffic)
    harness.prepare()
    info = harness.device_info(args.chips, require_tpu=True)
    if info is None:
        return 2
    harness.enable_compile_cache()
    for step in (int(s) for s in args.steps.split(",")):
        out = probe_step(sized(config, step), params, args.seed, seconds)
        print(json.dumps({"config": args.config, "traffic": args.traffic,
                          "divisor": step, "seconds": seconds, **out,
                          "device": info}), flush=True)
        if out["completed"] < args.need:
            break
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
