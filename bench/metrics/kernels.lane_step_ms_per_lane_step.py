"""Device time of the fused lane-superstep kernel per lane-step, in ms:
the ``fused_lane_step`` custom calls' device time inside the fused
dispatches wholly in the traced window (profiler trace, op names), over
those dispatches' lanes (padding included) times the bucket's
supersteps, as ``engine.device_ms_per_lane_step`` counts lane-steps."""

import lanestep


def read(ctx):
    got = lanestep.kernel_dispatches(ctx)
    if not got:
        return None
    steps = sum(d["lanes"] * max(d["supersteps"]) for d, _s in got)
    seconds = sum(s for _d, s in got)
    if not steps or seconds <= 0:
        return None
    return seconds * 1e3 / steps
