"""Mean real requests per device dispatch in the window, from the
bucket leaders' ``coalesce`` spans (padding lanes not counted)."""

import stats


def read(ctx):
    return stats.mean(d["fill"] for d in ctx.dispatches)
