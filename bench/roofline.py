"""Peaks and least-work counts: the yardstick of the roofline shares.

``peaks.json`` holds the published peaks of each device kind, with their
source; a device kind that is not there is an error, never a default.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" in {PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def superstep_least_bytes(n_nodes: int, n_edges_sym: int, m: int,
                          k: int) -> int:
    """Least HBM bytes of one lane-superstep of DKS, whatever implements
    it: the float32 table ``S[V, 2^m, K]`` read once and written once,
    each symmetric edge's (src, dst, weight) read once (12 bytes), and
    one source row of ``S`` (``2^m * K`` floats) read per edge.  From
    the graph's sizes and the query's shape only, never from a padded
    layout."""
    row = (1 << m) * k * 4
    return 2 * n_nodes * row + n_edges_sym * (12 + row)
