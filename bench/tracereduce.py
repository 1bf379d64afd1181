"""Reduction of a profiler trace to device busy time, top device
operations and idle gaps attributed to host activity.

Works on plain tuples so that it can be checked on a synthetic trace:
an event is ``(name, start_ns, duration_ns)``.  :func:`load` reads the
``.xplane.pb`` file the JAX profiler writes, with nothing but JAX.

Device operations are the events of the ``XLA Ops`` line of each
``/device:`` plane.  An event that encloses others on that line (a
control-flow op such as a while loop) counts for its own time only,
so that nothing is counted twice.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

Event = tuple  # (name, start_ns, duration_ns)

OPS_LINE = "XLA Ops"


def load(profile_dir: str) -> dict:
    """``{"host": [events of the host's python threads], "devices":
    {plane name: [device op events]}}`` from the newest xplane file
    under ``profile_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane file under {profile_dir}")
    data = ProfileData.from_file(paths[-1])
    host: list[Event] = []
    devices: dict[str, list[Event]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                        for line in plane.lines for e in line.events)
    return {"host": host, "devices": devices}


def find_event(events: list[Event], name: str) -> Event | None:
    hits = [e for e in events if e[0] == name]
    return hits[0] if hits else None


def clip(events: list[Event], lo: float, hi: float) -> list[Event]:
    """Events cut to ``[lo, hi)``; those outside dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals, sorted."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def covered(merged: list[tuple[float, float]], lo: float,
            hi: float) -> float:
    """Length of ``merged`` (sorted, disjoint: :func:`union`'s output)
    inside ``[lo, hi)``."""
    i = max(0, bisect.bisect_right(merged, (lo, float("inf"))) - 1)
    total = 0.0
    while i < len(merged) and merged[i][0] < hi:
        a, b = merged[i]
        total += max(0.0, min(b, hi) - max(a, lo))
        i += 1
    return total


def self_times(ops: list[Event]) -> dict[str, float]:
    """Seconds per op name, an enclosing op counting its own time only."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []  # [name, end, duration, child time]

    def pop():
        name, _end, dur, child = stack.pop()
        out[name] += max(0.0, dur - child) / 1e9

    for name, s, d in ops:
        while stack and s >= stack[-1][1]:
            pop()
        if stack:
            stack[-1][3] += d
        stack.append([name, s + d, d, 0.0])
    while stack:
        pop()
    return dict(out)


def device_summary(trace: dict, lo: float, hi: float) -> dict:
    """Busy seconds (averaged over device planes), window seconds, the
    merged busy intervals of every device, and seconds per op name."""
    busy, per_op, intervals = [], defaultdict(float), []
    for ops in trace["devices"].values():
        ops = clip(ops, lo, hi)
        iv = union((s, s + d) for _n, s, d in ops)
        intervals.extend(iv)
        busy.append(sum(b - a for a, b in iv) / 1e9)
        for name, sec in self_times(ops).items():
            per_op[name] += sec
    n = max(1, len(busy))
    return {"busy_s": sum(busy) / n, "window_s": (hi - lo) / 1e9,
            "intervals": union(intervals),
            "per_op": {k: v / n for k, v in per_op.items()}}


def idle_gaps(busy: list[tuple[float, float]], lo: float, hi: float,
              host: dict[str, list[tuple[float, float]]]) -> dict[str, float]:
    """Idle seconds of the device inside ``[lo, hi)``, each gap charged
    to the host activity (``host``: label -> intervals on the trace's
    clock) that overlaps it most, or to ``"no dispatch"``."""
    out: dict[str, float] = defaultdict(float)
    edges = [lo] + [x for a, b in union(busy) for x in (a, b)] + [hi]
    merged = {label: union(iv) for label, iv in host.items()}
    for a, b in zip(edges[0::2], edges[1::2]):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        best, best_len = "no dispatch", 0.0
        for label, iv in merged.items():
            length = covered(iv, a, b)
            if length > best_len:
                best, best_len = label, length
        out[best] += (b - a) / 1e9
    return dict(out)


def short_name(op: str) -> str:
    """``%fusion.68 = f32[487424]{...} fusion(...), kind=kCustom, ...``
    -> ``fusion.68 f32[487424] kCustom``: the op's name, its result's
    type and shape without layout, and its fusion kind or custom-call
    target when it has one."""
    name, _, rest = op.partition(" = ")
    out = [name.lstrip("%")]
    if rest:
        out.append(rest.split("{")[0].split(" ")[0].lstrip("("))
        for key in ("kind=", "custom_call_target="):
            if key in rest:
                out.append(rest.split(key)[1].split(",")[0].strip('"'))
    return " ".join(out)


def short_names(per_op: dict[str, float]) -> dict[str, float]:
    out: dict[str, float] = defaultdict(float)
    for op, sec in per_op.items():
        out[short_name(op)] += sec
    return dict(out)


def top(pairs: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(pairs.items(),
                                      key=lambda p: -p[1])[:n]]
