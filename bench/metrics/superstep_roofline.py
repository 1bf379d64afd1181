"""Share of the HBM roofline reached by the fused superstep dispatches.

The least bytes of every real request's supersteps
(``roofline.superstep_least_bytes``: from the graph's sizes and the
query's shape only) over the device's peak bandwidth, divided by the
device's busy time inside those dispatches (profiler trace; from the
start of the dispatch to the end of its extraction, so the answer
backtrace's device time is counted against the share, never left out).
Only dispatches wholly inside the traced window count."""

import roofline
import tracereduce


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace_window
    busy_iv = ctx.trace["summary"]["intervals"]
    least, busy = 0, 0.0
    for d in ctx.dispatches:
        a, b = ctx.to_trace_ns(d["t_start"]), ctx.to_trace_ns(d["t_end"])
        if a < lo or b > hi:
            continue
        per_step = roofline.superstep_least_bytes(
            ctx.n_nodes, ctx.n_edges_sym, d["m"], d["k"])
        least += per_step * sum(d["supersteps"])
        busy += tracereduce.covered(busy_iv, a, b) / 1e9
    if not least or busy <= 0:
        return None
    peak = roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / peak / busy
