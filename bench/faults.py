#!/usr/bin/env python3
"""Drive a whole benchmark run with a fault planted in the timed path,
to show that the check catches it.  Used by ``test_bench_checks.py``, and
on the chip for the control.

    python3 bench/faults.py --workload lod-exact --fault answer_altered
    python3 bench/faults.py --workload lod-exact --fault first_answer \\
        --at cell --seed 7 --seconds 25

``--at test`` (the default) runs without looking for a chip, on
whatever device JAX has, at a test's size: the cell's graph divided by
``--divide``, the traffic's shapes of the fewest keywords, two lanes at
most.  ``--at cell`` runs the cell as it is, on the chip it asks for.
Prints the run's result line.  Faults:

- ``none``: nothing planted; the run must read correct.
- ``superstep_noop``: the superstep returns its state unchanged but for
  the counter and an empty frontier, so every search stops at once.
- ``half_batch``: a dispatch runs only the first half of its requests
  and serves their answers to the other half as well.
- ``answer_altered``: the best answer weight of every answer is raised
  by one where the engine produces it.
- ``tree_altered``: the first answer tree of every answer loses an edge.
- ``first_answer``: every search stops at the first superstep that
  holds a finite answer over the whole keyword set, in place of its
  proven exit: the truncated search that breaks the exactness the
  configuration states (the control of the comparison).

A fault between chips does not exist on these one-chip cells.
"""

import argparse
import dataclasses
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

FAULTS = ("none", "superstep_noop", "half_batch", "answer_altered",
          "tree_altered", "first_answer")


def plant(fault: str) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.engine import engine as engine_mod

    if fault == "superstep_noop":
        def noop(graph, state, cfg, csr=None):
            return dataclasses.replace(
                state, step=state.step + 1,
                changed=jnp.zeros_like(state.changed),
                done=jnp.ones_like(state.done))

        engine_mod.lane_superstep = noop
    elif fault == "first_answer":
        real_step = engine_mod.lane_superstep

        def truncated(graph, state, cfg, csr=None):
            nxt = real_step(graph, state, cfg, csr=csr)
            found = nxt.topk_w[..., 0] < harness.reference.INF / 2
            return dataclasses.replace(nxt, done=nxt.done | found)

        engine_mod.lane_superstep = truncated
    elif fault == "half_batch":
        real = engine_mod.QueryEngine.query_batch

        def half(self, queries, k=1, *, n_real=None, **kw):
            n = len(queries) if n_real is None else n_real
            keep = max(1, n // 2)
            out = real(self, list(queries[:keep]), k, n_real=keep, **kw)
            return [out[i % keep] for i in range(n)] + [None] * (
                len(queries) - n)

        engine_mod.QueryEngine.query_batch = half
    elif fault in ("answer_altered", "tree_altered"):
        real = engine_mod.QueryEngine._make_result

        def altered(self, *args, **kw):
            res = real(self, *args, **kw)
            if fault == "answer_altered":
                w = np.array(res.weights)
                w[0] = w[0] + 1.0 if w[0] < 1e8 else w[0]
                return dataclasses.replace(res, weights=w)
            if res.answers and res.answers[0].edges:
                t = res.answers[0]
                t = dataclasses.replace(t, edges=t.edges[1:])
                return dataclasses.replace(
                    res, answers=[t] + list(res.answers[1:]))
            return res

        engine_mod.QueryEngine._make_result = altered
    elif fault != "none":
        raise ValueError(f"unknown fault {fault!r}")


def test_size(cell: harness.Cell, divide: int) -> None:
    """Cut ``cell`` in place to a size a test run holds."""
    c = cell.config
    for key in ("n_nodes", "n_edges", "vocab"):
        c[key] = max(1, c[key] // divide)
    c["serve"] = dict(c["serve"], max_batch=2)
    c["verify"] = dict(c["verify"], max_requests=16)
    least_m = min(m for m, _ in cell.traffic["shapes"])
    cell.traffic = dict(cell.traffic, requests=256, shapes=[
        s for s in cell.traffic["shapes"] if s[0] == least_m])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=FAULTS, required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 17)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--at", choices=("test", "cell"), default="test")
    ap.add_argument("--divide", type=int, default=16)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if args.at == "test":
        test_size(cell, args.divide)
    harness.prepare()
    plant(args.fault)
    return harness.run(cell, args.seed, args.seconds, False, T_START,
                       require_tpu=args.at == "cell")


if __name__ == "__main__":
    raise SystemExit(main())
