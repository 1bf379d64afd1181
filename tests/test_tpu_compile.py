"""Compile rehearsal: the main path's Pallas kernels at the paper's
``sec-rdfabout`` widths, compiled by Mosaic for a described (not
attached) TPU v5e.  Interpret mode cannot show what the chip's compiler
refuses (block shapes off the (8, 128) tiling, primitives Mosaic lacks);
these compiles can, at no chip time.  A compile is not a chip run: it
says nothing about results or time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lane_superstep.kernel import fused_lane_step
from repro.kernels.lane_superstep.ops import MAX_BLOCK_V
from repro.kernels.subset_combine.kernel import subset_combine_t

# sec-rdfabout (460,451 nodes, configs/dks_paper.py): its LaneCSR holds
# 496,128 virtual rows of 16 slots in 512-row blocks (one 4,445-degree
# hub widens the block from 128); the combine pads the nodes to
# 512-node blocks.
ROWS = 496_128
BLOCK_V = 512
NODES = 460_800
M, K, DMAX = 3, 2, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # The TPU library writes its logs to a fixed directory under /tmp
    # unless told otherwise; these compiles need none.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("lanes", [1, 4])
def test_fused_lane_step_compiles_for_v5e(one_chip, lanes):
    f = 1 << M
    args = (_spec((lanes, f, DMAX * K, ROWS), jnp.float32, one_chip),
            _spec((lanes, f, K, ROWS), jnp.float32, one_chip),
            _spec((1, ROWS), jnp.int32, one_chip),
            _spec((lanes,), jnp.int32, one_chip))
    compiled = fused_lane_step.lower(*args, m=M,
                                     block_v=BLOCK_V).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_lane_step_compiles_at_widest_block(one_chip):
    """The widest block a LaneCSR may choose (a hub of 16 * MAX_BLOCK_V
    in-edges) still fits the kernel's VMEM at m=3, K=2, with the hub
    scan over the whole block."""
    f, rows = 1 << M, -(-ROWS // MAX_BLOCK_V) * MAX_BLOCK_V
    args = (_spec((1, f, DMAX * K, rows), jnp.float32, one_chip),
            _spec((1, f, K, rows), jnp.float32, one_chip),
            _spec((1, rows), jnp.int32, one_chip),
            _spec((1,), jnp.int32, one_chip))
    compiled = fused_lane_step.lower(*args, m=M,
                                     block_v=MAX_BLOCK_V).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_subset_combine_compiles_for_v5e(one_chip):
    s_t = _spec((1 << M, K, NODES), jnp.float32, one_chip)
    compiled = subset_combine_t.lower(s_t, m=M).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes", [1, 8])
def test_fused_lane_superstep_fits_v5e(one_chip, lanes):
    """The whole pallas superstep (the chunked row gather, the kernel and
    the jnp tail) at sec-rdfabout's widths compiles for a v5e, and its
    temporaries leave room on the chip's 16 GB: the paper-scale memory
    the gather's chunking guards."""
    from repro.core.driver import lane_init
    from repro.engine import ExecutionPolicy
    from repro.graph.structure import DeviceGraph
    from repro.kernels.lane_superstep.ops import (LaneCSR,
                                                  fused_lane_superstep,
                                                  gather_chunks)

    n_nodes, n_edges = 460_451, 990_648       # symmetric edges, unpadded
    graph = DeviceGraph(
        src=_spec((n_edges,), jnp.int32, one_chip),
        dst=_spec((n_edges,), jnp.int32, one_chip),
        w=_spec((n_edges,), jnp.float32, one_chip),
        valid=_spec((n_edges,), jnp.bool_, one_chip),
        out_degree=_spec((n_nodes,), jnp.int32, one_chip),
        node_valid=_spec((n_nodes,), jnp.bool_, one_chip),
        n_nodes=n_nodes, n_edges=n_edges)
    csr = LaneCSR(
        src_pad=_spec((ROWS, DMAX), jnp.int32, one_chip),
        w_pad=_spec((ROWS, DMAX), jnp.float32, one_chip),
        gather_of=_spec((ROWS,), jnp.int32, one_chip),
        seg=_spec((ROWS,), jnp.int32, one_chip),
        tail_row=_spec((n_nodes,), jnp.int32, one_chip),
        dmax=DMAX, block_v=BLOCK_V, n_rows=ROWS, span=-(-4_445 // DMAX))
    cfg = ExecutionPolicy(backend="pallas").dks_config(M, K)
    state = jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda g, m: lane_init(g, m, cfg), graph,
                       jax.ShapeDtypeStruct((lanes, M, n_nodes), jnp.bool_)))
    assert gather_chunks(DMAX, ROWS, (1 << M) * K) > 1
    compiled = jax.jit(
        lambda g, c, s: fused_lane_superstep(g, c, s, cfg, interpret=False)
    ).lower(graph, csr, state).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 12e9


# The lod-m56-exact cell's graph (lod-sec-rdfabout at 1/128): 3,597
# nodes, 7,460 symmetric edges, a LaneCSR of 3,840 rows of 16 slots in
# 128-row blocks, at most 13 rows per node.
CELL_NODES, CELL_EDGES, CELL_ROWS, CELL_SPAN = 3_597, 7_460, 3_840, 13


def test_fused_lane_step_compiles_set_chunked_for_v5e(one_chip):
    """m=6, K=10 at the cell's layout: its 5,242,880 B candidate tile
    splits into two set chunks, and the chunked kernel compiles."""
    from repro.kernels.lane_superstep import set_chunks

    m, k, f = 6, 10, 1 << 6
    assert set_chunks(f, DMAX * k, 128) == 2
    args = (_spec((2, f, DMAX * k, CELL_ROWS), jnp.float32, one_chip),
            _spec((2, f, k, CELL_ROWS), jnp.float32, one_chip),
            _spec((1, CELL_ROWS), jnp.int32, one_chip),
            _spec((2,), jnp.int32, one_chip))
    compiled = fused_lane_step.lower(*args, m=m, block_v=128,
                                     span=CELL_SPAN).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_lane_superstep_m6_fits_v5e(one_chip):
    """The whole pallas superstep at m=6, K=10, 8 lanes, at the cell's
    sizes compiles for a v5e with temporaries under 12 GB."""
    from repro.core.driver import lane_init
    from repro.engine import ExecutionPolicy
    from repro.graph.structure import DeviceGraph
    from repro.kernels.lane_superstep.ops import (LaneCSR,
                                                  fused_lane_superstep)

    m, k, lanes = 6, 10, 8
    graph = DeviceGraph(
        src=_spec((CELL_EDGES,), jnp.int32, one_chip),
        dst=_spec((CELL_EDGES,), jnp.int32, one_chip),
        w=_spec((CELL_EDGES,), jnp.float32, one_chip),
        valid=_spec((CELL_EDGES,), jnp.bool_, one_chip),
        out_degree=_spec((CELL_NODES,), jnp.int32, one_chip),
        node_valid=_spec((CELL_NODES,), jnp.bool_, one_chip),
        n_nodes=CELL_NODES, n_edges=CELL_EDGES)
    csr = LaneCSR(
        src_pad=_spec((CELL_ROWS, DMAX), jnp.int32, one_chip),
        w_pad=_spec((CELL_ROWS, DMAX), jnp.float32, one_chip),
        gather_of=_spec((CELL_ROWS,), jnp.int32, one_chip),
        seg=_spec((CELL_ROWS,), jnp.int32, one_chip),
        tail_row=_spec((CELL_NODES,), jnp.int32, one_chip),
        dmax=DMAX, block_v=128, n_rows=CELL_ROWS, span=CELL_SPAN)
    cfg = ExecutionPolicy(backend="pallas").dks_config(m, k)
    state = jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda g, ms: lane_init(g, ms, cfg), graph,
                       jax.ShapeDtypeStruct((lanes, m, CELL_NODES),
                                            jnp.bool_)))
    compiled = jax.jit(
        lambda g, c, s: fused_lane_superstep(g, c, s, cfg, interpret=False)
    ).lower(graph, csr, state).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 12e9
