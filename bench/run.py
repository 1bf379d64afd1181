#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload lod-exact --seed 7 --seconds 51 --trace 0

Builds the cell's graph and requests from ``--seed``, serves them through
``DKSService`` in a closed loop for ``--seconds``, checks the answers
against the plain reference (``bench/reference.py``) and prints one JSON
line last on stdout: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
with ``--trace 1``), ``device`` and, traced, ``breakdown``; the numbers
compared and their limits come last, on stderr and under ``checks``.
Exits non-zero, printing no result, without a TPU.
"""

import os
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since this file
    began to run."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        boot = time.clock_gettime(time.CLOCK_BOOTTIME)
        return boot - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return time.perf_counter() - T_START


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # The set-up clock starts at process start, not at this line.
    t_process = time.perf_counter() - process_age_s()

    import harness

    cell = harness.load_cell(args.workload)
    return harness.run(cell, args.seed, args.seconds, bool(args.trace),
                       t_process)


if __name__ == "__main__":
    raise SystemExit(main())
