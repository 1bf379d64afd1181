"""The fused lane-superstep kernel (``fused_lane_step``, one Pallas
custom call per superstep) in a traced run: its device time inside each
fused dispatch, and the least HBM bytes one lane-step of it needs.  Read
by ``kernels.lane_step_ms_per_lane_step`` and
``fused_lane_step_roofline``; a trace that holds no op of the kernel
reads as None.
"""

from __future__ import annotations

import roofline
import tracereduce

KERNEL = "fused_lane_step"


def is_kernel(op: str) -> bool:
    """Whether a device op event is the kernel: its HLO name is
    ``fused_lane_step`` or ``fused_lane_step.<n>``."""
    return tracereduce.short_name(op).split(" ")[0].split(".")[0] == KERNEL


def kernel_dispatches(ctx) -> list[tuple[dict, float]] | None:
    """``(dispatch, kernel seconds)`` for each fused dispatch wholly
    inside the traced window: the kernel's device time from the
    dispatch's start to its extraction's end (a custom call encloses no
    other op, so its duration is its self time).  None without a trace
    or without a kernel op in it."""
    if ctx.trace is None:
        return None
    ops = [(s, s + d) for events in ctx.trace["devices"].values()
           for name, s, d in events if is_kernel(name)]
    if not ops:
        return None
    busy = tracereduce.union(ops)
    lo, hi = ctx.trace_window
    out = []
    for d in ctx.dispatches:
        a, b = ctx.to_trace_ns(d["t_start"]), ctx.to_trace_ns(d["t_end"])
        if lo <= a and b <= hi:
            out.append((d, tracereduce.covered(busy, a, b) / 1e9))
    return out


def least_bytes(n_nodes: int, n_edges_sym: int, m: int, k: int) -> int:
    """Least HBM bytes of one lane-step of the kernel, whatever its
    layout: the whole superstep's count
    (``roofline.superstep_least_bytes``) less the edge list's 12 bytes
    (src, dst, weight) per symmetric edge, which the gather in front of
    the kernel reads and the kernel never does.  What is left is one
    float32 candidate row per symmetric edge and the table ``S`` read
    once and written once, from the graph's sizes and the query's shape
    only, so a layout change leaves the count standing."""
    return (roofline.superstep_least_bytes(n_nodes, n_edges_sym, m, k)
            - 12 * n_edges_sym)
