"""Mean supersteps of the requests the device served in the window
(``QueryResult.supersteps``; cache hits and attached requests left
out)."""

import stats


def read(ctx):
    return stats.mean(
        r.served.result.supersteps for r in ctx.window
        if not r.served.cache_hit and not r.served.coalesced)
