#!/usr/bin/env python3
"""The precision control of the check: the plain reference put in the
program's place, computed in bfloat16 (the precision below the float32
the program states for its weights), judged by the comparison that
decides ``correct`` (``harness.same_weights``).

    python3 bench/control.py --workload lod-exact --seeds 3,4,5

For each seed it builds the cell's graph and requests, takes as many
requests as a run checks (the first ones of the request list), and
counts the answers whose top-K weights differ from the reference's.
Every weight of these configurations is a small whole number, and every
sum stays exact in bfloat16, so this control reads 0: it cannot fail the
comparison, and the control that sets the upper reading is the
truncated search planted by ``faults.py --fault first_answer``.

Prints one JSON line per seed.  Needs no device.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import graphgen  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import traffic as trafficgen  # noqa: E402


def readings(cell: harness.Cell, seed: int) -> dict:
    ds = graphgen.make_dataset(cell.config, seed)
    sym = reference.SymGraph.from_directed(ds.n_nodes, ds.src, ds.dst, ds.w)
    post = graphgen.postings(ds.tokens)
    reqs = trafficgen.kind(cell.traffic).make_requests(
        cell.traffic, {t: len(v) for t, v in post.items()}, seed)
    reqs = reqs[: int(cell.config["verify"]["max_requests"])]
    wrong, t0 = 0, time.perf_counter()
    for r in reqs:
        kw = [post[t] for t in r.keywords]
        want = reference.answer_weights(sym, kw, r.k)
        got = reference.answer_weights(sym, kw, r.k, "bfloat16")
        wrong += not harness.same_weights(got, want)
    return {"seed": seed, "checked": len(reqs),
            "wrong_weights_bfloat16": wrong,
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--divide", type=int, default=1,
                    help="divide the graph's sizes (for a test's size)")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for key in ("n_nodes", "n_edges", "vocab"):
        cell.config[key] = max(1, cell.config[key] // args.divide)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": args.workload, **readings(cell, seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
