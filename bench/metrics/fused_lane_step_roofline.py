"""Share of the HBM roofline reached by the fused lane-superstep kernel:
the least bytes of every real request's lane-steps
(``lanestep.least_bytes``: from the graph's sizes and the query's shape
only) over the device's peak bandwidth (``peaks.json``), divided by the
``fused_lane_step`` custom calls' device time in the same dispatches
(profiler trace; padding lanes' kernel time counts against the share)."""

import lanestep
import roofline


def read(ctx):
    got = lanestep.kernel_dispatches(ctx)
    if not got:
        return None
    least = sum(lanestep.least_bytes(ctx.n_nodes, ctx.n_edges_sym,
                                     d["m"], d["k"]) * sum(d["supersteps"])
                for d, _s in got)
    seconds = sum(s for _d, s in got)
    if not least or seconds <= 0:
        return None
    peak = roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / peak / seconds
