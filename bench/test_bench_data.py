"""The benchmark's data: graphs and requests from the seed, the
configuration files against BENCHMARK.json, and the command's refusal
without a TPU."""

import itertools
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

import graphgen  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
SEED = 2**31 + 12345


def small(config: dict, divide: int = 16) -> dict:
    c = dict(config)
    for key in ("n_nodes", "n_edges", "vocab"):
        c[key] = max(1, c[key] // divide)
    return c


CONFIG_FILES = sorted(p.stem for p in (BENCH_DIR / "configs").glob("*.json"))


def config_file(name: str) -> dict:
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_same_seed_same_graph(name):
    c = small(config_file(name))
    a, b = graphgen.make_dataset(c, SEED), graphgen.make_dataset(c, SEED)
    other = graphgen.make_dataset(c, SEED + 1)
    for f in ("src", "dst", "w", "tokens"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.tokens, other.tokens)
    assert np.array_equal(a.src, other.src)
    # Every seed: the same sizes and degree sequence, so the same
    # compiled programs.
    sa = reference.SymGraph.from_directed(a.n_nodes, a.src, a.dst, a.w)
    so = reference.SymGraph.from_directed(a.n_nodes, other.src, other.dst,
                                          other.w)
    assert sa.n_edges == so.n_edges
    assert np.array_equal(np.sort(np.bincount(sa.src, minlength=a.n_nodes)),
                          np.sort(np.bincount(so.src, minlength=a.n_nodes)))
    assert np.array_equal(np.sort(a.w), np.sort(other.w))
    assert len(a.src) == c["n_edges"] and a.n_nodes == c["n_nodes"]
    assert a.tokens.shape == (c["n_nodes"], c["labels_per_node"])
    assert a.tokens.max() < c["vocab"]
    assert not np.any(a.src == a.dst)


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_sizes_match_reduced(name):
    c = config_file(name)
    assert c["name"] == name
    for conf in SPEC["configs"]:
        if conf["name"] == name:
            assert sorted(c["reduced"]) == sorted(conf["reduced"])
            assert harness.ROOT / conf["file"] == (
                BENCH_DIR / "configs" / f"{name}.json")
    for key, published in c["published"].items():
        if c[key] != published:
            assert key in c["reduced"], key
    full = c["full_scale"]
    div = full["n_nodes"] // c["n_nodes"]
    for key, value in full.items():
        assert c[key] == value // div
        assert key in c["reduced"] or div == 1, key


TRAFFIC_FILES = sorted(p.stem for p in (BENCH_DIR / "traffic").glob("*.json"))


@pytest.mark.parametrize("config,mix", itertools.product(CONFIG_FILES,
                                                          TRAFFIC_FILES))
def test_same_seed_same_requests(config, mix):
    params = traffic.load_params(mix)
    make = traffic.kind(params).make_requests
    ds = graphgen.make_dataset(config_file(config), SEED)
    dfs = {t: len(v) for t, v in graphgen.postings(ds.tokens).items()}
    a = make(params, dfs, SEED)
    assert a == make(params, dfs, SEED)
    assert a != make(params, dfs, SEED + 1)
    assert len(a) == params["requests"]
    for r in a:
        assert all(dfs[t] >= 2 for t in r.keywords)
        assert len(set(r.keywords)) == len(r.keywords)
    assert len({r.keywords for r in a}) == len(a)
    # Every prefix holds the shapes in equal shares.
    shapes = [tuple(s) for s in params["shapes"]]
    head = [(len(r.keywords), r.k) for r in a[: 4 * len(shapes)]]
    assert sorted(head) == sorted(shapes * 4)


def test_exact_traffic_is_the_papers_query_mix():
    """The random-query runs of the source: m from 2 to 4 keywords and K
    in {1, 2, 5, 10}, every pair in equal shares."""
    shapes = traffic.load_params("closed8_distinct")["shapes"]
    assert sorted(map(tuple, shapes)) == sorted(
        itertools.product((2, 3, 4), (1, 2, 5, 10)))


@pytest.mark.parametrize("where", ["graph", "traffic"])
def test_unknown_kind_is_an_error(where):
    with pytest.raises(KeyError, match="no .* 'no-such-kind'"):
        if where == "graph":
            graphgen.make_dataset({"kind": "no-such-kind"}, SEED)
        else:
            traffic.kind({"kind": "no-such-kind"})


def test_kind_module_found_by_name(tmp_path):
    (tmp_path / "flat.py").write_text("def make(config, seed):\n"
                                      "    return (config['n'], seed)\n")
    mod = graphgen.load_module(tmp_path, "flat", "graph")
    assert mod.make({"n": 3}, 5) == (3, 5)


@pytest.mark.parametrize("pad,want", [("pow2", [1, 2, 4, 8]), ("max", [8]),
                                      ("none", list(range(1, 9))),
                                      ("adaptive", list(range(1, 9)))])
def test_warm_lane_counts(pad, want):
    from types import SimpleNamespace

    assert harness.lane_counts(
        SimpleNamespace(max_batch=8, pad_batches=pad)) == want


def test_every_cell_resolves_by_name():
    end_to_end = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in end_to_end
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.per_layer and len(cell.end_to_end) >= 2
        for m in cell.per_layer:
            assert callable(harness.load_reader(m["name"]))
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
            assert m["layer"]
    for m in SPEC["per_layer"]:
        for name in m.get("workloads", []):
            assert name in {w["name"] for w in SPEC["workloads"]}


def test_reference_matches_brute_force_steiner_trees():
    """K=1: the reference's answer is the least weight of a connected
    node set covering every keyword (its spanning tree's weight), found
    here by enumerating node sets of small graphs."""
    rng = np.random.default_rng(3)
    for _ in range(20):
        n = 7
        src = rng.integers(0, n, 12)
        dst = rng.integers(0, n, 12)
        w = rng.integers(1, 4, 12).astype(np.float32)
        g = reference.SymGraph.from_directed(n, src, dst, w)
        ew = g.edge_weights()
        kws = [rng.choice(n, size=int(rng.integers(1, 3)), replace=False)
               for _ in range(int(rng.integers(2, 4)))]
        best = reference.INF
        for size in range(1, n + 1):
            for nodes in itertools.combinations(range(n), size):
                if not all(set(k) & set(nodes) for k in kws):
                    continue
                # Prim's tree over the induced subgraph.
                inside, total = {nodes[0]}, 0.0
                while len(inside) < size:
                    cand = [(ew[(u, v)], v) for u in inside for v in nodes
                            if v not in inside and (u, v) in ew]
                    if not cand:
                        break
                    wt, v = min(cand)
                    inside.add(v)
                    total += wt
                if len(inside) == size:
                    best = min(best, total)
        got = reference.answer_weights(g, kws, 1)
        assert got[0] == best


def test_reference_tree_check():
    g = reference.SymGraph.from_directed(
        4, np.array([0, 1, 2]), np.array([1, 2, 3]),
        np.array([1.0, 2.0, 1.0], np.float32))
    ew, kw = g.edge_weights(), [np.array([0]), np.array([3])]
    assert reference.check_tree(((0, 1), (1, 2), (2, 3)), (0, 1, 2, 3), 4.0,
                                kw, ew) is None
    assert "not covered" in reference.check_tree(((0, 1),), (0, 1), 1.0,
                                                 kw, ew)
    assert "sum" in reference.check_tree(((0, 1), (1, 2), (2, 3)),
                                         (0, 1, 2, 3), 5.0, kw, ew)
    assert "not in the graph" in reference.check_tree(((0, 3),), (0, 3), 1.0,
                                                      kw, ew)


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_refuses_without_tpu():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "TPU" in p.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
