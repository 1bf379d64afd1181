"""The lod-m56-exact cell's files: its traffic, a whole run at a test's
size, and the readers of the fused kernel's time and roofline share on a
synthetic trace."""

import itertools
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

import harness  # noqa: E402
import lanestep  # noqa: E402
import traffic  # noqa: E402

SEED = 2**31 + 4242
MS = 1e6  # ns
KERNEL_OP = ('%fused_lane_step.9 = f32[1,64,10,3840]{3,2,1,0:T(8,128)} '
             'custom-call(%a, %b), custom_call_target="tpu_custom_call"')


def test_m56_traffic_is_four_shapes_in_equal_shares():
    params = traffic.load_params("closed8_m56_distinct")
    assert params["kind"] == "closed_loop" and params["clients"] == 8
    shapes = [tuple(s) for s in params["shapes"]]
    assert sorted(shapes) == sorted(itertools.product((5, 6), (2, 10)))
    # Requests cycle through the shapes, so every share is equal.
    dfs = {t: 2 + t % 5 for t in range(60)}
    reqs = traffic.kind(params).make_requests(params, dfs, SEED)
    counts = {s: 0 for s in shapes}
    for r in reqs:
        counts[(len(r.keywords), r.k)] += 1
    assert set(counts.values()) == {params["requests"] // 4}
    assert len({r.keywords for r in reqs}) == len(reqs)


def test_m56_cell_resolves():
    cell = harness.load_cell("lod-m56-exact")
    base = json.loads((BENCH_DIR / "configs" /
                       "lod-sec-rdfabout.json").read_text())
    # The same graph, policy and service as lod-exact's configuration;
    # only the number of checked answers is cut.
    for key in ("kind", "n_nodes", "n_edges", "vocab", "labels_per_node",
                "tau", "rmat", "structure_seed", "policy", "serve"):
        assert cell.config[key] == base[key], key
    assert cell.config["verify"] == {"max_requests": 24}
    # Every per-layer metric of lod-exact reads this cell too, and the
    # two of the fused kernel read this cell alone.
    exact = [m["name"] for m in harness.load_cell("lod-exact").per_layer]
    assert [m["name"] for m in cell.per_layer] == exact + [
        "kernels.lane_step_ms_per_lane_step", "fused_lane_step_roofline"]


def test_m56_whole_run_at_test_size_reads_correct():
    """The cell at a test's size (graph / 16, two lanes at most, all four
    shapes) through the harness on the CPU: every answer checked equals
    the plain reference."""
    code = (
        "import sys, time; sys.path.insert(0, 'bench'); import harness\n"
        "cell = harness.load_cell('lod-m56-exact')\n"
        "c = cell.config\n"
        "for key in ('n_nodes', 'n_edges', 'vocab'):\n"
        "    c[key] //= 16\n"
        "c['serve'] = dict(c['serve'], max_batch=2)\n"
        "c['verify'] = dict(c['verify'], max_requests=8)\n"
        "cell.traffic = dict(cell.traffic, requests=64)\n"
        f"sys.exit(harness.run(cell, {SEED}, 2.0, False, time.perf_counter(),"
        " require_tpu=False))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT,
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["checks"]["checked"]["value"] >= 1
    assert line["failed"] == 0


def context(ops, dispatches):
    return harness.Context(
        window=[], dispatches=dispatches, n_nodes=1000, n_edges_sym=4000,
        device={"kind": "TPU v5 lite"},
        trace={"host": [], "devices": {"/device:TPU:0": ops}},
        trace_window=(0.0, 100 * MS), to_trace_ns=lambda t: t * 1e9)


def dispatch(t0, t1, m=6, k=10, lanes=2, supersteps=(3, 5)):
    return {"m": m, "k": k, "lanes": lanes, "fill": len(supersteps),
            "t_start": t0, "t_end": t1, "wall_s": t1 - t0,
            "supersteps": list(supersteps)}


def test_kernel_readers_on_a_synthetic_trace():
    # Two kernel calls (4 + 6 ms) inside a dispatch [10, 40) ms, a
    # gather beside them, one kernel call inside a dispatch [90, 110)
    # that leaves the window, and one outside every dispatch.
    ops = [("%fusion.3 = f32[61440,640]{1,0} fusion(%x), kind=kCustom",
            10 * MS, 5 * MS),
           (KERNEL_OP, 15 * MS, 4 * MS),
           (KERNEL_OP.replace(".9", ".11"), 25 * MS, 6 * MS),
           (KERNEL_OP, 95 * MS, 3 * MS), (KERNEL_OP, 50 * MS, 1 * MS)]
    ctx = context(ops, [dispatch(0.010, 0.040), dispatch(0.090, 0.110)])
    # 10 ms of kernel over 2 lanes x 5 supersteps.
    ms = harness.load_reader("kernels.lane_step_ms_per_lane_step")(ctx)
    assert ms == pytest.approx(10.0 / 10)
    least = lanestep.least_bytes(1000, 4000, 6, 10) * (3 + 5)
    assert least == (4000 + 2 * 1000) * 64 * 10 * 4 * 8
    share = harness.load_reader("fused_lane_step_roofline")(ctx)
    assert share == pytest.approx(100 * least / 819e9 / 0.010)
    assert 0 < share < 100


@pytest.mark.parametrize("reader", ["kernels.lane_step_ms_per_lane_step",
                                    "fused_lane_step_roofline"])
def test_kernel_readers_none_without_the_kernel(reader):
    read = harness.load_reader(reader)
    gather_only = [("%fusion.3 = f32[61440,640]{1,0} fusion(%x)",
                    10 * MS, 5 * MS),
                   ("%fused_lane_step_other.2 = f32[8] add(%a)",
                    20 * MS, 5 * MS)]
    assert read(context(gather_only, [dispatch(0.0, 0.04)])) is None
    ctx = context([(KERNEL_OP, 15 * MS, 4 * MS)], [dispatch(0.0, 0.04)])
    assert read(ctx) is not None
    ctx.trace = None
    assert read(ctx) is None
