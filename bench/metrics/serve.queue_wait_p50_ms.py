"""Median admission-queue wait of the window's requests, from the
service's ``queue_wait`` spans (submit to bucket dispatch), in ms."""

import stats


def read(ctx):
    waits = [(b - a) * 1e3 for r in ctx.window if r.trace
             for name, a, b, _ in r.trace["spans"] if name == "queue_wait"]
    return stats.percentile(waits, 50)
