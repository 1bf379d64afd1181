"""Traffic kind ``closed_loop``: clients that each send their next
request when the last one returns, every request a keyword set of its
own (the paper's random-query runs).

Parameters:

- ``clients``: closed-loop clients.
- ``shapes``: ``[m, K]`` pairs (keyword count, answers), in equal shares.
- ``requests``: how many requests to draw; more than a window completes.

Keywords are spread over the df-sorted vocabulary (tokens on two nodes or
more) as the paper's query sampling does (arXiv:1605.00060, Sec. 7.1):
each keyword set takes ``m`` tokens from a window of ``max(2m, 10)``
neighbouring ranks (wider where its sets are all taken), and the
windows' positions follow the golden-ratio sequence from a seeded
offset, so every prefix of the request list (a closed loop completes
only a prefix) spans the whole df range and holds each shape in its
share.  The seed changes which tokens are picked and the offset, not
the mix.
"""

from __future__ import annotations

import threading
import time

import numpy as np

import graphgen
import harness
from traffic import Req

GOLDEN = (5 ** 0.5 - 1) / 2


def make_requests(params: dict, dfs: dict, seed: int) -> list[Req]:
    usable = [t for t, d in sorted(dfs.items(), key=lambda p: (p[1], p[0]))
              if d >= 2]
    shapes = [tuple(s) for s in params["shapes"]]
    if len(usable) < 2 * max(m for m, _ in shapes):
        raise ValueError("vocabulary too small for the traffic")
    rng = graphgen.rng(seed, 7)
    offset = rng.random()
    out: list[Req] = []
    seen: set = set()
    for j in range(int(params["requests"])):
        m, k = shapes[j % len(shapes)]
        pos = (offset + j * GOLDEN) % 1.0
        for attempt in range(64):
            # A window whose sets are all taken widens, staying at its
            # place in the df order.
            width = max(2 * m, 10) << (attempt // 8)
            lo = int((len(usable) - m) * pos)
            hi = min(len(usable) - 1, lo + width)
            lo = max(0, min(lo, hi - width))
            picks = rng.choice(np.arange(lo, hi + 1), size=m, replace=False)
            q = tuple(sorted(int(usable[int(p)]) for p in picks))
            if q not in seen:
                break
        else:
            raise ValueError("vocabulary too small for distinct requests")
        seen.add(q)
        out.append(Req(q, int(k)))
    return out


def run_window(svc, requests: list[Req], params: dict, seconds: float,
               on_start=None) -> tuple[list, float, float]:
    """``clients`` threads each send the next request of the list when
    their last one returns, from the window's start until its close.
    Requests in flight at the close are waited for."""
    clients = int(params["clients"])
    records: list[harness.Record] = []
    lock = threading.Lock()
    cursor = [0]
    start = threading.Barrier(clients + 1)
    t_window = [0.0, 0.0]

    def client() -> None:
        start.wait()
        while True:
            with lock:
                i = cursor[0]
                cursor[0] += 1
                t = time.perf_counter()
                if t >= t_window[1] or i >= len(requests):
                    return
                rec = harness.Record(i, t)
                records.append(rec)
            r = requests[i]
            try:
                served = svc.query(list(r.keywords), k=r.k,
                                   timeout=seconds + harness.LATE_S)
                rec.t_done = time.perf_counter()
                rec.served = served
                rec.trace = harness.trace_of(svc, served)
            except Exception as exc:  # noqa: BLE001 - counted as failed
                rec.t_done = time.perf_counter()
                rec.error = repr(exc)

    threads = [threading.Thread(target=client, name=f"bench-client-{c}",
                                daemon=True) for c in range(clients)]
    for t in threads:
        t.start()
    if on_start is not None:
        on_start()
    t_window[0] = time.perf_counter()
    t_window[1] = t_window[0] + seconds
    start.wait()
    for t in threads:
        t.join(timeout=seconds + 2 * harness.LATE_S)
    hung = [t.name for t in threads if t.is_alive()]
    if hung:
        raise RuntimeError(f"clients never returned: {hung}")
    return records, t_window[0], t_window[1]
