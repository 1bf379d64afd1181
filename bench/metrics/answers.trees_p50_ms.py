"""Median host time of answer-tree collection per dispatch in the
window (the bucket leaders' ``trees`` spans, opened by the engine inside
``extract``: the replay of the device backtrace records, and the host
search for ragged stragglers), in ms."""

import stats


def read(ctx):
    spans = [(b - a) * 1e3 for r in ctx.window if r.trace
             for name, a, b, _ in r.trace["spans"] if name == "trees"]
    return stats.percentile(spans, 50)
