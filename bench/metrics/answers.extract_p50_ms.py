"""Median host time of answer extraction per dispatch in the window
(the bucket leaders' ``extract`` spans: device backtrace plus host tree
collection), in ms."""

import stats


def read(ctx):
    ex = [(b - a) * 1e3 for r in ctx.window if r.trace
          for name, a, b, _ in r.trace["spans"] if name == "extract"]
    return stats.percentile(ex, 50)
