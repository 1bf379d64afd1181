"""Device idle time while the dispatcher thread serves a bucket, as %
of the traced window: the idle time inside the service's
``dks.device_dispatch``, ``dks.extract``, ``dks.cache_store`` and
``dks.render`` profiler annotations (host events of the trace, on the
profiler's own clock), their overlaps counted once."""

import tracereduce

ANNOTATIONS = ("dks.device_dispatch", "dks.extract", "dks.cache_store",
               "dks.render")


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace_window
    events = [e for e in ctx.trace["host"] if e[0] in ANNOTATIONS]
    spans = tracereduce.union(
        (s, s + d) for _n, s, d in tracereduce.clip(events, lo, hi))
    if not spans or hi <= lo:
        return None
    busy = ctx.trace["summary"]["intervals"]
    idle = sum((b - a) - tracereduce.covered(busy, a, b) for a, b in spans)
    return 100.0 * idle / (hi - lo)
