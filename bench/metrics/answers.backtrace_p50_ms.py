"""Median time of the batched device backtrace per dispatch in the
window (the bucket leaders' ``backtrace`` spans, opened by the engine
inside ``extract``: the backtracer's device program and its readback),
in ms."""

import stats


def read(ctx):
    spans = [(b - a) * 1e3 for r in ctx.window if r.trace
             for name, a, b, _ in r.trace["spans"] if name == "backtrace"]
    return stats.percentile(spans, 50)
