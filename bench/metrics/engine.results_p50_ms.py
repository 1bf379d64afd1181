"""Median time of per-lane result construction per dispatch in the
window (the bucket leaders' ``results`` spans, opened by the engine
inside ``extract``: each real lane's ``QueryResult`` built from the
final state), in ms."""

import stats


def read(ctx):
    spans = [(b - a) * 1e3 for r in ctx.window if r.trace
             for name, a, b, _ in r.trace["spans"] if name == "results"]
    return stats.percentile(spans, 50)
