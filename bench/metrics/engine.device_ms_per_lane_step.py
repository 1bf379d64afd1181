"""Device time per lane-superstep of the fused dispatches: the sum of
their ``wall_time_s`` (host clock around the fused program) over the sum
of lanes (padding included) times the bucket's supersteps, in ms."""


def read(ctx):
    steps = sum(d["lanes"] * max(d["supersteps"]) for d in ctx.dispatches)
    if not steps:
        return None
    return sum(d["wall_s"] for d in ctx.dispatches) * 1e3 / steps
