"""The closed loop, the choice of answers to check and the comparison,
on a stand-in service and hand-made answers (no device)."""

import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
from traffic import Req  # noqa: E402

SEED = 2**31 + 4242


class StandInService:
    """Answers after ``delay_s``; raises for keyword sets in ``fail``."""

    def __init__(self, delay_s: float, fail=()):
        self.delay_s, self.fail = delay_s, set(fail)
        self.in_flight = self.most_in_flight = 0
        self.lock = threading.Lock()

    def query(self, keywords, k, timeout):
        with self.lock:
            self.in_flight += 1
            self.most_in_flight = max(self.most_in_flight, self.in_flight)
        time.sleep(self.delay_s)
        with self.lock:
            self.in_flight -= 1
        if tuple(keywords) in self.fail:
            raise RuntimeError("refused")
        return SimpleNamespace(trace_id=None, keywords=tuple(keywords), k=k)

    def trace(self, trace_id):
        return None


def test_closed_loop_window():
    loop = traffic.kind({"kind": "closed_loop"})
    reqs = [Req((i, i + 1), 1) for i in range(1000)]
    svc = StandInService(0.01, fail=[(3, 4)])
    records, t0, t1 = loop.run_window(svc, reqs, {"clients": 4}, 0.5)
    assert t1 - t0 == pytest.approx(0.5)
    assert svc.most_in_flight == 4
    # Sent in list order, each once, none after the close, all returned.
    assert sorted(r.index for r in records) == list(range(len(records)))
    assert all(t0 <= r.t_submit < t1 and r.t_done >= r.t_submit
               for r in records)
    assert 40 < len(records) < 4 * 0.5 / 0.01 + 5
    failed = [r for r in records if r.error is not None]
    assert [r.index for r in failed] == [3] and failed[0].served is None
    ok = [r for r in records if r.error is None]
    assert all(r.served.keywords == reqs[r.index].keywords for r in ok)


def _served(steps, cache_hit=False, coalesced=False):
    return SimpleNamespace(result=SimpleNamespace(supersteps=steps),
                           cache_hit=cache_hit, coalesced=coalesced)


def test_pick_checked_keeps_the_longest_of_each_shape():
    reqs = [Req((i,), 1 + i % 2) for i in range(40)]
    recs = [harness.Record(i, 0.0, 1.0, _served(i % 7, cache_hit=i == 30))
            for i in range(40)]
    recs.append(harness.Record(40, 0.0))          # never answered
    picked = harness.pick_checked(recs, reqs + [Req((40,), 1)], 8, SEED)
    assert len(picked) == 8
    assert picked == harness.pick_checked(recs, reqs, 8, SEED)
    idx = {r.index for r in picked}
    assert {6, 13} <= idx                          # 6 supersteps, K=1 / K=2
    assert 30 in idx                                # the one cache hit
    assert 40 not in idx
    small = harness.pick_checked(recs[:5], reqs, 8, SEED)
    assert [r.index for r in small] == [0, 1, 2, 3, 4]


def _system():
    # A path 0-1-2-3 with weights 1, 2, 1 and a shortcut 0-3 of weight 5.
    sym = reference.SymGraph.from_directed(
        4, np.array([0, 1, 2, 0]), np.array([1, 2, 3, 3]),
        np.array([1.0, 2.0, 1.0, 5.0], np.float32))
    return SimpleNamespace(sym=sym, postings={7: np.array([0]),
                                              8: np.array([3])})


def _answer(weights, trees, approximate=False):
    answers = [SimpleNamespace(edges=e, nodes=n, weight=w) for e, n, w in trees]
    return SimpleNamespace(approximate=approximate, result=SimpleNamespace(
        weights=np.array(weights, np.float32), answers=answers))


PATH = (((0, 1), (1, 2), (2, 3)), (0, 1, 2, 3), 4.0)
SHORTCUT = (((0, 3),), (0, 3), 5.0)


@pytest.mark.parametrize("case,want", [
    ("exact", (0, 0)),
    ("weight_raised", (1, 0)),
    ("approximate", (1, 0)),
    ("no_trees", (0, 1)),
    ("tree_not_in_graph", (0, 1)),
    ("best_tree_misses_optimum", (0, 1)),
])
def test_check_counts(case, want):
    # The top 2 cells are the path's weight at two of its nodes: the
    # answers keep the same tree rooted at different nodes.
    served = {
        "exact": _answer([4.0, 4.0], [PATH, SHORTCUT]),
        "weight_raised": _answer([4.0, 5.0], [PATH, SHORTCUT]),
        "approximate": _answer([4.0, 4.0], [PATH, SHORTCUT], True),
        "no_trees": _answer([4.0, 4.0], []),
        "tree_not_in_graph": _answer([4.0, 4.0], [
            PATH, (((1, 3),), (1, 3), 5.0)]),
        "best_tree_misses_optimum": _answer([4.0, 4.0], [SHORTCUT]),
    }[case]
    rec = harness.Record(0, 0.0, 1.0, served)
    out = harness.check(_system(), [Req((7, 8), 2)], [rec])
    assert (out["wrong_weights"], out["bad_trees"]) == want
    assert harness.within(dict(out, failed=0)) == (want == (0, 0))
