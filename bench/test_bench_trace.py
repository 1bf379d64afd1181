"""The trace reduction, the peak table and the superstep roofline, on a
synthetic trace."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import roofline  # noqa: E402
import tracereduce  # noqa: E402

MS = 1e6  # ns


def synthetic_trace():
    # One device: a while loop [0, 40) ms holding two fusions, a kernel
    # [60, 90) ms; the window is [0, 100) ms.
    ops = [("while", 0, 40 * MS), ("gather", 5 * MS, 10 * MS),
           ("gather", 20 * MS, 15 * MS), ("lane_superstep", 60 * MS, 30 * MS),
           ("late", 120 * MS, 5 * MS)]
    return {"host": [("bench.clock_sync", 0.0, 1.0)],
            "devices": {"/device:TPU:0": ops}}


def test_busy_time_and_self_times():
    s = tracereduce.device_summary(synthetic_trace(), 0.0, 100 * MS)
    assert s["busy_s"] == pytest.approx(0.070)
    assert s["window_s"] == pytest.approx(0.100)
    assert s["per_op"] == pytest.approx(
        {"while": 0.015, "gather": 0.025, "lane_superstep": 0.030})
    top = tracereduce.top(s["per_op"], 2)
    assert [name for name, _ in top] == ["lane_superstep", "gather"]


def test_idle_gaps_charged_to_host_spans():
    s = tracereduce.device_summary(synthetic_trace(), 0.0, 100 * MS)
    gaps = tracereduce.idle_gaps(
        s["intervals"], 0.0, 100 * MS,
        {"extract": [(40 * MS, 58 * MS)], "device_dispatch": [(59 * MS,
                                                               61 * MS)]})
    # [40, 60) goes to extract (18 ms of 20), [90, 100) to no dispatch.
    assert gaps == pytest.approx({"extract": 0.020, "no dispatch": 0.010})


def test_covered_uses_merged_intervals():
    merged = tracereduce.union([(0, 2), (1, 3), (5, 6)])
    assert merged == [(0, 3), (5, 6)]
    assert tracereduce.covered(merged, 1, 5.5) == pytest.approx(2.5)
    assert tracereduce.covered(merged, 3, 5) == 0.0


def test_peaks_table():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_least_bytes_from_sizes_only():
    # m=3, K=2: a row is 8 * 2 floats = 64 bytes.
    assert roofline.superstep_least_bytes(460_451, 990_648, 3, 2) == (
        2 * 460_451 * 64 + 990_648 * (12 + 64))


def test_superstep_roofline_reader():
    tr = synthetic_trace()
    summary = tracereduce.device_summary(tr, 0.0, 100 * MS)
    n, e = 1000, 4000
    least = roofline.superstep_least_bytes(n, e, 2, 1) * (3 + 5)
    ctx = harness.Context(
        window=[], n_nodes=n, n_edges_sym=e,
        dispatches=[{"m": 2, "k": 1, "lanes": 2, "fill": 2,
                     "t_start": 0.050, "t_end": 0.090,
                     "wall_s": 0.03, "supersteps": [3, 5]}],
        device={"kind": "TPU v5 lite"}, trace=tr | {"summary": summary},
        trace_window=(0.0, 100 * MS), to_trace_ns=lambda t: t * 1e9)
    read = harness.load_reader("superstep_roofline")
    # Busy inside [50, 90) ms: the kernel's 30 ms.
    assert read(ctx) == pytest.approx(100 * least / 819e9 / 0.030)
    ctx.trace = None
    assert read(ctx) is None
