"""Mean requests left waiting when a bucket dispatched, over the
window's dispatches: the ``waiting`` attribute of the bucket leaders'
``coalesce`` spans (real requests in the other pending buckets, the rest
of the bucket's own, and the admission queue)."""

import stats


def read(ctx):
    return stats.mean(
        at["waiting"] for r in ctx.window if r.trace
        for name, _a, _b, at in r.trace["spans"]
        if name == "coalesce" and "waiting" in at)
