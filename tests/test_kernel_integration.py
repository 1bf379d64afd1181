"""Kernel <-> engine integration: the DKS engine with Pallas combine
(interpret mode) produces identical results to the jnp path end-to-end."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import INF

from repro.core import DKSConfig, run_dks
from repro.graph.generators import random_weighted_graph


def masks_of(groups, n):
    m = np.zeros((len(groups), n), bool)
    for i, grp in enumerate(groups):
        m[i, list(grp)] = True
    return m


@pytest.mark.parametrize("seed", [0, 1])
def test_engine_with_pallas_combine(seed):
    g = random_weighted_graph(24, 60, seed=seed)
    groups = [[2], [9], [17]]
    masks = jnp.asarray(masks_of(groups, g.n_nodes))
    dg = g.to_device()

    jnp_state = run_dks(dg, masks, DKSConfig(m=3, k=2, max_supersteps=48))
    pl_state = run_dks(dg, masks, DKSConfig(m=3, k=2, max_supersteps=48,
                                            combine_impl="pallas"))
    np.testing.assert_allclose(np.asarray(jnp_state.topk_w),
                               np.asarray(pl_state.topk_w), atol=1e-4)
    np.testing.assert_allclose(np.asarray(jnp_state.S),
                               np.asarray(pl_state.S), atol=1e-4)
    assert int(jnp_state.step) == int(pl_state.step)


def test_attention_impls_agree_in_model():
    """Full transformer forward with flash_jax == naive attention."""
    import jax
    from repro.configs import get_arch
    from repro.models import transformer as tfm

    cfg = get_arch("chatglm3-6b").config.smoke()
    b = tfm.build(cfg, tp=1)
    params = tfm.init_params(jax.random.PRNGKey(0), b)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    h_naive, _, _ = tfm.forward(params, toks, b, attn_impl="naive")
    h_flash, _, _ = tfm.forward(params, toks, b, attn_impl="flash_jax")
    np.testing.assert_allclose(
        np.asarray(h_naive, np.float32), np.asarray(h_flash, np.float32),
        atol=5e-2, rtol=5e-2)



# ----------------------------------------------------------------------
# LaneCSR + fused lane-superstep kernel (repro.kernels.lane_superstep)
# ----------------------------------------------------------------------

from repro.core.dks import DKSConfig as _DKSConfig  # noqa: E402
from repro.core.driver import lane_init as _lane_init  # noqa: E402
from repro.core.dks import superstep as _superstep  # noqa: E402
from repro.graph.generators import lod_like_graph as _lod  # noqa: E402
from repro.kernels.lane_superstep import (  # noqa: E402
    fused_lane_superstep,
    lane_csr_from_device_graph,
)


def _device_graph(v=200, e=900, seed=3):
    g, _ = _lod(v, e, seed=seed, vocab=40)
    return g.to_device()


def test_lane_csr_builder_invariants():
    dg = _device_graph()
    csr = lane_csr_from_device_graph(dg)
    src = np.asarray(csr.src_pad)
    w = np.asarray(csr.w_pad)
    seg = np.asarray(csr.seg)
    tail = np.asarray(csr.tail_row)
    n_rows, dmax = src.shape
    assert n_rows == csr.n_rows and n_rows % csr.block_v == 0
    # Pad rows carry seg=-1 and INF weights (they never join a segment);
    # real rows point at their destination node.
    pad_rows = seg < 0
    assert np.all(w[pad_rows] >= INF)
    # Block alignment: a node's virtual rows never straddle a block_v
    # boundary — the in-kernel segmented merge can then complete within
    # one grid block, with no second-level jnp hub merge.
    for node in np.unique(seg[seg >= 0]):
        rows = np.nonzero(seg == node)[0]
        assert rows.min() // csr.block_v == rows.max() // csr.block_v
        assert np.array_equal(rows, np.arange(rows.min(), rows.max() + 1))
        assert tail[node] == rows.max()  # the merge lands on the tail row
    # Every real (src -> dst) edge with finite weight appears exactly
    # once across the dst's rows.
    e_valid = np.asarray(dg.valid)
    dsts = np.asarray(dg.dst)[e_valid]
    per_node_edges = {int(n): int(c) for n, c in
                      zip(*np.unique(dsts, return_counts=True))}
    for node, want in per_node_edges.items():
        rows = np.nonzero(seg == node)[0]
        got = int(np.sum(w[rows] < INF))
        assert got == want


def test_lane_csr_hub_splitting_bumps_rows_not_dmax_past_block():
    """A hub with degree > dmax splits over multiple virtual rows; dmax
    never changes (a hub too big for one block widens the block)."""
    dg = _device_graph(v=120, e=2000, seed=5)   # dense -> hubs
    csr = lane_csr_from_device_graph(dg, dmax=4)
    seg = np.asarray(csr.seg)
    counts = np.bincount(seg[seg >= 0])
    assert counts.max() > 1      # at least one split node
    assert counts.max() <= csr.block_v
    assert csr.dmax == 4 and csr.span == counts.max()


def _star(leaves, seed=0):
    """Hub 0 joined to every leaf, plus a leaf chain; integer weights."""
    from repro.graph.structure import build_graph
    n = leaves + 1
    src = np.concatenate([np.zeros(leaves, np.int32),
                          np.arange(1, leaves, dtype=np.int32)])
    dst = np.concatenate([np.arange(1, n, dtype=np.int32),
                          np.arange(2, n, dtype=np.int32)])
    w = np.random.default_rng(seed).integers(1, 9, src.size)
    return build_graph(src, dst, n, w=w.astype(np.float32)).to_device()


def test_fused_lane_superstep_on_widened_block():
    """A 300-degree hub at dmax=1 widens the block to 512 rows and spans
    300 of them: the hub-merge scan bounded by that span still merges
    all of them, bit for bit with the vmapped jnp superstep."""
    dg = _star(300)
    csr = lane_csr_from_device_graph(dg, dmax=1)
    assert (csr.dmax, csr.block_v, csr.span) == (1, 512, 300)
    cfg_j = _DKSConfig(m=2, k=2, max_supersteps=8)
    cfg_p = _DKSConfig(m=2, k=2, max_supersteps=8,
                       relax_impl="pallas", combine_impl="pallas")
    rng = np.random.default_rng(2)
    masks = np.zeros((2, 2, dg.v_pad), bool)
    for lane in range(2):
        for kw in range(2):
            masks[lane, kw, 1 + rng.choice(300, 40, replace=False)] = True
    st = _lane_init(dg, jnp.asarray(masks), cfg_j)
    ref = jax.vmap(lambda s: _superstep(dg, s, cfg_j))(st)
    out = fused_lane_superstep(dg, csr, st, cfg_p)
    np.testing.assert_array_equal(np.asarray(out.S), np.asarray(ref.S))
    np.testing.assert_array_equal(np.asarray(out.topk_w),
                                  np.asarray(ref.topk_w))


def test_lane_csr_refuses_hub_past_widest_block():
    """One block must hold a node's rows in VMEM, so a node with more
    in-edges than ``dmax * MAX_BLOCK_V`` is refused, naming its degree."""
    from repro.kernels.lane_superstep.ops import MAX_BLOCK_V
    dg = _star(MAX_BLOCK_V + 1)
    with pytest.raises(ValueError, match=f"{MAX_BLOCK_V + 1:,} in-edges"):
        lane_csr_from_device_graph(dg, dmax=1)
    csr = lane_csr_from_device_graph(dg, dmax=2)      # the widest block
    assert (csr.block_v, csr.span) == (MAX_BLOCK_V, MAX_BLOCK_V // 2 + 1)


def test_fused_lane_step_refuses_tile_past_vmem_budget():
    """The kernel refuses, before lowering, a block where one keyword
    set's candidate tile alone exceeds its VMEM budget (a wider tile of
    several sets splits into set chunks instead)."""
    from repro.kernels.lane_superstep.kernel import (MAX_CAND_TILE_BYTES,
                                                     fused_lane_step)
    m, k, dmax, bv = 4, 20, 16, 4096      # one set: 1.25x the budget
    assert dmax * k * bv * 4 == 5 * MAX_CAND_TILE_BYTES // 4
    args = (jax.ShapeDtypeStruct((1, 1 << m, dmax * k, bv), jnp.float32),
            jax.ShapeDtypeStruct((1, 1 << m, k, bv), jnp.float32),
            jax.ShapeDtypeStruct((1, bv), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32))
    with pytest.raises(ValueError, match="candidate tile"):
        jax.eval_shape(lambda *a: fused_lane_step(*a, m=m, block_v=bv),
                       *args)


def test_fused_lane_superstep_matches_vmapped_superstep():
    """One fused kernel step == one vmapped jnp superstep, bit for bit,
    on a multi-lane state with a hub-split layout."""
    dg = _device_graph()
    csr = lane_csr_from_device_graph(dg, dmax=4)  # force hub splitting
    cfg_j = _DKSConfig(m=2, k=2, max_supersteps=8)
    cfg_p = _DKSConfig(m=2, k=2, max_supersteps=8,
                       relax_impl="pallas", combine_impl="pallas")
    rng = np.random.default_rng(0)
    masks = np.zeros((3, 2, dg.v_pad), bool)
    for lane in range(3):
        for kw in range(2):
            masks[lane, kw, rng.choice(dg.n_nodes, 4, replace=False)] = True
    st = _lane_init(dg, jnp.asarray(masks), cfg_j)
    ref = jax.vmap(lambda s: _superstep(dg, s, cfg_j))(st)
    out = fused_lane_superstep(dg, csr, st, cfg_p)
    np.testing.assert_array_equal(np.asarray(out.S), np.asarray(ref.S))
    np.testing.assert_array_equal(np.asarray(out.changed),
                                  np.asarray(ref.changed))
    np.testing.assert_array_equal(np.asarray(out.topk_w),
                                  np.asarray(ref.topk_w))
    np.testing.assert_array_equal(np.asarray(out.done),
                                  np.asarray(ref.done))


def test_fused_lane_superstep_freezes_done_lane():
    """A lane whose done flag is set must come out of the kernel with its
    table untouched (the in-kernel freeze mask), even though other lanes
    advance."""
    import dataclasses as dc

    dg = _device_graph()
    csr = lane_csr_from_device_graph(dg)
    cfg_p = _DKSConfig(m=2, k=1, max_supersteps=8,
                       relax_impl="pallas", combine_impl="pallas")
    rng = np.random.default_rng(1)
    masks = np.zeros((2, 2, dg.v_pad), bool)
    for lane in range(2):
        for kw in range(2):
            masks[lane, kw, rng.choice(dg.n_nodes, 3, replace=False)] = True
    st = _lane_init(dg, jnp.asarray(masks), cfg_p)
    done = jnp.asarray([True, False])
    st = dc.replace(st, done=done)
    out = fused_lane_superstep(dg, csr, st, cfg_p)
    np.testing.assert_array_equal(np.asarray(out.S[0]),
                                  np.asarray(st.S[0]))      # frozen
    assert not np.array_equal(np.asarray(out.S[1]),
                              np.asarray(st.S[1]))          # advanced


def _parity_state(dg, m, cfg, lanes=4, seed=4):
    """A lane-batched state two jnp supersteps in, with lane 1 done and
    lane 2's ``changed`` all false (a lane whose senders all fall
    silent)."""
    import dataclasses as dc
    from repro.core.driver import lane_superstep

    rng = np.random.default_rng(seed)
    masks = np.zeros((lanes, m, dg.v_pad), bool)
    for lane in range(lanes):
        for kw in range(m):
            masks[lane, kw, rng.choice(dg.n_nodes, 3, replace=False)] = True
    st = _lane_init(dg, jnp.asarray(masks), cfg)
    for _ in range(2):
        st = lane_superstep(dg, st, cfg)
    return dc.replace(
        st, done=st.done.at[1].set(True), changed=st.changed.at[2].set(False))


@pytest.mark.parametrize("layout", ["hub_split", "widened_block"])
@pytest.mark.parametrize("m,k", [(2, 1), (3, 5), (4, 2)])
def test_fused_lane_superstep_parity(layout, m, k):
    """The pallas lane superstep (row gather, kernel, tail) equals the
    vmapped jnp superstep bit for bit, through the driver's freeze, on
    4 lanes with one done and one whose senders are all silent; row
    lengths 2^m*K of 4 (not a multiple of 8), 40 and 32."""
    from repro.core.driver import lane_superstep

    if layout == "hub_split":
        dg = _device_graph()
        csr = lane_csr_from_device_graph(dg, dmax=4)
        assert csr.span > 1
    else:
        dg = _star(300)
        csr = lane_csr_from_device_graph(dg, dmax=1)
        assert csr.block_v > 128
    cfg_j = _DKSConfig(m=m, k=k, max_supersteps=8)
    cfg_p = _DKSConfig(m=m, k=k, max_supersteps=8,
                       relax_impl="pallas", combine_impl="pallas")
    st = _parity_state(dg, m, cfg_j)
    ref = lane_superstep(dg, st, cfg_j)
    out = lane_superstep(dg, st, cfg_p, csr)
    for name in ("S", "changed", "topk_w", "done", "msgs_bfs",
                 "msgs_deep"):
        np.testing.assert_array_equal(np.asarray(getattr(out, name)),
                                      np.asarray(getattr(ref, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(out.S[1]), np.asarray(st.S[1]))


@pytest.mark.parametrize("m,k", [(m, k) for m in (2, 3, 4)
                                 for k in (1, 2, 5, 10)])
def test_gather_chunks_one_at_cell_shapes(m, k):
    """At the benchmark cell's layout (3,840 virtual rows of 16 slots)
    every query shape gathers each lane in one chunk."""
    from repro.kernels.lane_superstep import gather_chunks
    assert gather_chunks(16, 3_840, (1 << m) * k) == 1


def test_gather_chunks_split_at_paper_scale():
    """At sec-rdfabout's 496,128 virtual rows, m=3 K=2, one slot's
    padded rows (254 MB) fill the chunk budget: one slot per chunk."""
    from repro.kernels.lane_superstep import gather_chunks
    from repro.kernels.lane_superstep.ops import MAX_GATHER_CHUNK_BYTES
    one_slot = 496_128 * 128 * 4
    assert one_slot <= MAX_GATHER_CHUNK_BYTES < 2 * one_slot
    assert gather_chunks(16, 496_128, 16) == 16
    # Chunks always divide dmax, and a row past the budget alone still
    # gathers one slot at a time.
    assert gather_chunks(16, 3 * one_slot // (128 * 4), 16) == 16
    assert gather_chunks(6, 100, 16) == 1


@pytest.mark.parametrize("slots_per_chunk", [2, 1])
def test_fused_lane_superstep_chunked_gather(monkeypatch, slots_per_chunk):
    """A gather forced into several chunks per lane gives the one-chunk
    result bit for bit."""
    from repro.kernels.lane_superstep import gather_chunks, ops

    m, k = 3, 2
    dg = _device_graph()
    csr = lane_csr_from_device_graph(dg, dmax=4)
    cfg = _DKSConfig(m=m, k=k, max_supersteps=8,
                     relax_impl="pallas", combine_impl="pallas")
    st = _parity_state(dg, m, cfg)
    assert gather_chunks(csr.dmax, csr.n_rows, 16) == 1
    whole = fused_lane_superstep(dg, csr, st, cfg)
    monkeypatch.setattr(ops, "MAX_GATHER_CHUNK_BYTES",
                        slots_per_chunk * csr.n_rows * 128 * 4)
    assert gather_chunks(csr.dmax, csr.n_rows, 16) == 4 // slots_per_chunk
    split = fused_lane_superstep(dg, csr, st, cfg)
    for name in ("S", "changed", "topk_w"):
        np.testing.assert_array_equal(np.asarray(getattr(split, name)),
                                      np.asarray(getattr(whole, name)),
                                      err_msg=name)


@pytest.mark.parametrize("m,k", [(5, 10), (6, 10)])
def test_fused_lane_superstep_wide_parity(m, k):
    """At m=5 and m=6, K=10 (90 and 301 split pairs; the 16-slot layout's
    m=6 tile in two set chunks), the pallas lane superstep equals the
    vmapped jnp superstep bit for bit, through the driver's freeze, on
    4 lanes with one done and one whose senders are all silent."""
    from repro.core.driver import lane_superstep
    from repro.kernels.lane_superstep import set_chunks

    dg = _device_graph(v=100, e=300)
    csr = lane_csr_from_device_graph(dg)
    assert set_chunks(1 << m, csr.dmax * k, csr.block_v) == m - 4
    cfg_j = _DKSConfig(m=m, k=k, max_supersteps=8)
    cfg_p = _DKSConfig(m=m, k=k, max_supersteps=8,
                       relax_impl="pallas", combine_impl="pallas")
    st = _parity_state(dg, m, cfg_j)
    ref = lane_superstep(dg, st, cfg_j)
    out = lane_superstep(dg, st, cfg_p, csr)
    for name in ("S", "changed", "topk_w", "done", "msgs_bfs",
                 "msgs_deep"):
        np.testing.assert_array_equal(np.asarray(getattr(out, name)),
                                      np.asarray(getattr(ref, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(out.S[1]), np.asarray(st.S[1]))


@pytest.mark.parametrize("chunks", [2, 4])
def test_fused_lane_superstep_set_chunks(monkeypatch, chunks):
    """A kernel forced to reduce its keyword sets in 2 or 4 chunks per
    row block gives the one-chunk result bit for bit."""
    from repro.kernels.lane_superstep import kernel, set_chunks

    m, k = 3, 2
    dg = _device_graph()
    csr = lane_csr_from_device_graph(dg, dmax=4)
    cfg = _DKSConfig(m=m, k=k, max_supersteps=8,
                     relax_impl="pallas", combine_impl="pallas")
    st = _parity_state(dg, m, cfg)
    one_set = csr.dmax * k * csr.block_v * 4
    assert set_chunks(8, csr.dmax * k, csr.block_v) == 1
    whole = fused_lane_superstep(dg, csr, st, cfg)
    monkeypatch.setattr(kernel, "MAX_CAND_TILE_BYTES", 8 // chunks * one_set)
    kernel.fused_lane_step.clear_cache()        # re-plan at trace time
    try:
        assert set_chunks(8, csr.dmax * k, csr.block_v) == chunks
        split = fused_lane_superstep(dg, csr, st, cfg)
    finally:
        kernel.fused_lane_step.clear_cache()
    for name in ("S", "changed", "topk_w"):
        np.testing.assert_array_equal(np.asarray(getattr(split, name)),
                                      np.asarray(getattr(whole, name)),
                                      err_msg=name)


@pytest.mark.parametrize("m,k", [(m, k) for m in (2, 3, 4)
                                 for k in (1, 2, 5, 10)])
def test_set_chunks_one_at_lod_exact_shapes(m, k):
    """At the cells' layout (128-row blocks of 16 slots) every shape of
    ``lod-exact`` keeps its whole candidate tile in one set chunk."""
    from repro.kernels.lane_superstep import set_chunks
    assert set_chunks(1 << m, 16 * k, 128) == 1


def test_set_chunks_split_wide_shapes_and_hub_blocks():
    """m=6 K=10 at 128-row blocks (a 5,242,880 B tile) splits in two;
    a hub-widened block splits its sets instead of refusing the shape;
    only a block where one set's tile alone is over the budget is
    refused."""
    from repro.kernels.lane_superstep import set_chunks
    from repro.kernels.lane_superstep.kernel import MAX_CAND_TILE_BYTES
    from repro.kernels.lane_superstep.ops import MAX_BLOCK_V

    assert (1 << 6) * 160 * 128 * 4 == 5_242_880 > MAX_CAND_TILE_BYTES
    assert set_chunks(1 << 6, 160, 128) == 2
    assert set_chunks(1 << 5, 160, 128) == 1
    assert set_chunks(1 << 6, 32, 128) == 1
    # Hub-widened blocks: m=4 K=2 at 4,096 rows (an 8 MiB tile) and m=4
    # K=10 at the widest block (one set 2.6 MB).
    assert set_chunks(1 << 4, 32, 4096) == 2
    assert set_chunks(1 << 4, 160, MAX_BLOCK_V) == 16
    with pytest.raises(ValueError, match="candidate tile"):
        set_chunks(1 << 4, 320, MAX_BLOCK_V)


def _bench_reference():
    import importlib.util
    import sys
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "bench" / "reference.py"
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod            # for its dataclasses
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("m", [5, 6])
def test_engine_wide_queries_match_references(m):
    """m=5 and m=6, K=10, through ``QueryEngine`` on the pallas path (its
    default 16-slot layout: two set chunks at m=6): the answer weights
    equal the jnp engine's bit for bit and the plain reference's
    (``bench/reference.py``); the best equals the Dreyfus-Wagner
    optimum; every answer tree's weight is one of the brute-force
    enumeration's minimal-tree weights, the first its least."""
    from repro.core.steiner_ref import brute_force_topk, dreyfus_wagner
    from repro.engine import ExecutionPolicy, QueryEngine
    from repro.graph.index import InvertedIndex
    from repro.kernels.lane_superstep import set_chunks

    reference = _bench_reference()
    k, n = 10, 10
    g = random_weighted_graph(n, 13, seed=m, max_w=4)
    rng = np.random.default_rng(m)
    tokens = np.full((n, m), -1, np.int64)
    for i in range(m):
        for v in rng.choice(n, 1 + i % 2, replace=False):
            tokens[v, int(np.argmax(tokens[v] < 0))] = i
    index = InvertedIndex.from_token_matrix(tokens)
    groups = [index.lookup(i) for i in range(m)]
    eng = {b: QueryEngine.build(g, index=index,
                                policy=ExecutionPolicy(backend=b))
           for b in ("pallas", "jnp")}
    csr = eng["pallas"].lane_csr
    assert set_chunks(1 << m, csr.dmax * k, csr.block_v) == m - 4
    got = {b: e.query_batch([list(range(m))], k=k)[0]
           for b, e in eng.items()}
    np.testing.assert_array_equal(got["pallas"].weights, got["jnp"].weights)
    assert [a.edges for a in got["pallas"].answers] == [
        a.edges for a in got["jnp"].answers]

    src, dst, w = [], [], []
    for v in range(n):
        nbrs, ws = g.neighbors(v)
        src += [v] * len(nbrs)
        dst += list(nbrs)
        w += list(ws)
    sym = reference.SymGraph.from_directed(n, np.asarray(src),
                                           np.asarray(dst), np.asarray(w))
    np.testing.assert_array_equal(
        got["pallas"].weights, reference.answer_weights(sym, groups, k))
    assert got["pallas"].weights[0] == dreyfus_wagner(g, groups) < INF
    achievable = [x for x in brute_force_topk(g, groups, 1000) if x < INF]
    trees = [a.weight for a in got["pallas"].answers]
    assert trees and trees[0] == achievable[0]
    assert all(any(abs(t - x) < 1e-3 for x in achievable) for t in trees)
