"""Frontier-compressed sharded DKS (the production multi-pod path).

The dense relax under plain pjit makes XLA replicate the whole ``S`` table
for the edge gather (measured 1.93 GiB/device/superstep on bluk-bnb — see
EXPERIMENTS.md §Perf).  But Pregel semantics only need the tables of
*active* vertices on the wire.  This module is that observation as a
shard_map:

  1. each shard packs (global id, table) for up to ``f_cap`` changed nodes;
  2. one all-gather moves only the packed frontier;
  3. edges are pre-partitioned by destination owner (host-side), so each
     shard relaxes its own edges against the gathered frontier via a
     sorted-id binary search, reducing locally with the K-round
     segment-top-K.

Frontier overflow (> f_cap active nodes on some shard) raises the
``budget_hit`` flag — precisely the paper's Sec. 5.4 forced stop: the run
finishes with the SPA bound instead of silently dropping messages.

The relax kernel is **lane-batched** (:func:`relax_frontier_lanes`): the
lane axis of the driver (:mod:`repro.core.driver`) lives *inside* the
shard_map body, so a whole bucket of concurrent queries shares one
frontier all-gather per superstep — shard_map under vmap (unsupported in
jax) is never needed.  The single-query entry points are its 1-lane case.

Combine stays node-local (node axis sharded over ALL mesh axes, keyword-set
axis replicated), so it needs no collectives at all.

The mesh is *explicit*: :func:`pack_frontier_graph` records it on the
:class:`FrontierGraph` (a static pytree field), and every executor reads it
from there — no ambient ``get_abstract_mesh()`` state — and places the
packed shards on it, one per device.  All shard_map/mesh API calls go
through :mod:`repro.shardmap`.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import INF, shardmap
from repro.core import semiring
from repro.core.dks import (
    DKSConfig,
    DKSState,
    combine,
    finish_superstep,
)
from repro.graph.structure import Graph

MESH_AXES = ("pod", "data", "model")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FrontierGraph:
    """Edges partitioned by destination owner; node arrays over all axes.

    edge_src:   i32[n_shards, e_cap]  global source ids (-1 pad)
    edge_dst_l: i32[n_shards, e_cap]  destination LOCAL index on its shard
    edge_w:     f32[n_shards, e_cap]  (INF pad)
    out_degree: i32[V_pad]; node_valid: bool[V_pad]
    mesh:       the device mesh the shards live on (static; executors read
                it from here instead of ambient ``get_abstract_mesh`` state)
    """

    edge_src: jax.Array
    edge_dst_l: jax.Array
    edge_w: jax.Array
    out_degree: jax.Array
    node_valid: jax.Array
    n_nodes: int = dataclasses.field(metadata=dict(static=True))
    n_edges: int = dataclasses.field(metadata=dict(static=True))
    n_shards: int = dataclasses.field(metadata=dict(static=True))
    mesh: Any = dataclasses.field(default=None, metadata=dict(static=True))

    @property
    def v_pad(self) -> int:
        return self.node_valid.shape[0]

    @property
    def n_loc(self) -> int:
        return self.v_pad // self.n_shards

    def e_min(self) -> jax.Array:
        return jnp.min(jnp.where(self.edge_w < INF, self.edge_w, INF))


def pack_frontier_graph(g: Graph, n_shards: int | None = None,
                        e_slack: float = 1.2,
                        mesh: Any = None) -> FrontierGraph:
    """Host-side: symmetrized edges grouped by dst owner, padded rows.

    ``mesh``: the mesh the shards will execute on; recorded on the result so
    the executors need no ambient mesh state, and the packed arrays are
    placed on it (shard ``s``'s edges and node slice on the mesh's ``s``-th
    device), so no dispatch re-lays them out.  ``n_shards`` defaults to
    the mesh's device count when a mesh is given.
    """
    if n_shards is None:
        if mesh is None:
            raise ValueError("pack_frontier_graph needs n_shards= or mesh=")
        n_shards = int(math.prod(mesh.shape.values()))
    v_pad = int(-(-g.n_nodes // n_shards) * n_shards)
    n_loc = v_pad // n_shards
    deg = np.diff(g.indptr)
    src = np.repeat(np.arange(g.n_nodes, dtype=np.int32), deg)
    dst = g.indices.astype(np.int32)
    w = g.ew.astype(np.float32)
    owner = dst // n_loc
    counts = np.bincount(owner, minlength=n_shards)
    e_cap = int(max(8, -(-int(counts.max() * 1.0) // 8) * 8))
    edge_src = np.full((n_shards, e_cap), -1, np.int32)
    edge_dst_l = np.zeros((n_shards, e_cap), np.int32)
    edge_w = np.full((n_shards, e_cap), INF, np.float32)
    order = np.argsort(owner, kind="stable")
    src, dst, w, owner = src[order], dst[order], w[order], owner[order]
    starts = np.concatenate([[0], np.cumsum(counts)])
    for s in range(n_shards):
        lo, hi = starts[s], starts[s + 1]
        n = hi - lo
        edge_src[s, :n] = src[lo:hi]
        edge_dst_l[s, :n] = dst[lo:hi] - s * n_loc
        edge_w[s, :n] = w[lo:hi]
    out_degree = np.zeros(v_pad, np.int32)
    out_degree[: g.n_nodes] = deg
    node_valid = np.zeros(v_pad, bool)
    node_valid[: g.n_nodes] = True
    put = jnp.asarray
    if mesh is not None:
        axes = _mesh_axes(mesh)

        def put(x):
            spec = P(axes, *([None] * (x.ndim - 1)))
            return jax.device_put(x, NamedSharding(mesh, spec))
    return FrontierGraph(
        edge_src=put(edge_src), edge_dst_l=put(edge_dst_l),
        edge_w=put(edge_w), out_degree=put(out_degree),
        node_valid=put(node_valid),
        n_nodes=g.n_nodes, n_edges=len(src), n_shards=n_shards, mesh=mesh)


def _mesh_axes(am) -> tuple[str, ...]:
    return tuple(a for a in MESH_AXES if a in am.axis_names)


def _graph_mesh(graph: FrontierGraph):
    """The graph's recorded mesh; ambient mesh_scope only as a legacy
    fallback for FrontierGraphs packed without one."""
    mesh = graph.mesh if graph.mesh is not None else shardmap.get_abstract_mesh()
    if mesh is None:
        raise ValueError(
            "sharded DKS needs a mesh: pack_frontier_graph(..., mesh=...) "
            "(or run under repro.shardmap.mesh_scope)")
    return mesh


def relax_frontier_lanes(graph: FrontierGraph, S: jax.Array,
                         changed: jax.Array, cfg: DKSConfig,
                         ) -> tuple[jax.Array, jax.Array]:
    """Lane-batched frontier-compressed relax — THE sharded relax kernel.

    ``S``: f32[L, V, 2^m, K]; ``changed``: bool[L, V].  The lane axis
    lives *inside* the ``shard_map`` body (lanes-per-shard): every lane's
    frontier is packed per shard and exchanged in ONE all-gather, so a
    batch of queries costs one device program and one collective per
    superstep instead of vmap-over-shard_map (which jax does not
    support).  Returns ``(R[L, V, 2^m, K], overflow bool[L])``.
    """
    am = _graph_mesh(graph)
    axes = _mesh_axes(am)
    n_shards = graph.n_shards
    n_loc = graph.n_loc
    f_cap = min(n_loc, max(1, int(n_loc * cfg.frontier_frac)))
    k = S.shape[3]
    f_tot = n_shards * f_cap

    def block(S_loc, changed_loc, src_g, dst_l, w, shard_arange):
        # S_loc: [L, n_loc, n_sets, k]; changed_loc: [L, n_loc]
        src_g = src_g[0]
        dst_l = dst_l[0]
        w = w[0]
        shard_id = shard_arange[0]
        offset = shard_id * n_loc
        # Pack each lane's local frontier (ids ascending; invalid slots
        # OOB-marked).  sort-of-keyed-arange == nonzero(size=f_cap,
        # fill_value=n_loc), but lane-batched without a vmapped nonzero.
        arange = jnp.arange(n_loc, dtype=jnp.int32)
        key = jnp.where(changed_loc, arange[None, :], jnp.int32(n_loc))
        idx = jnp.sort(key, axis=1)[:, :f_cap]              # [L, f_cap]
        fvalid = idx < n_loc
        tab = jnp.take_along_axis(
            S_loc, jnp.minimum(idx, n_loc - 1)[:, :, None, None], axis=1)
        tab = jnp.where(fvalid[:, :, None, None], tab, INF)
        gids = jnp.where(fvalid, idx + offset, jnp.int32(2**30) + idx)
        overflow = jnp.sum(changed_loc, axis=1) > f_cap     # [L]
        # Exchange only the frontiers — one collective for all lanes.
        all_gids = jax.lax.all_gather(
            gids, axes, tiled=True, axis=1)                 # [L, F_tot]
        all_tab = jax.lax.all_gather(
            tab, axes, tiled=True, axis=1)                  # [L,F_tot,S,K]

        def relax_lane(gids_l, tab_l):
            # Relax local edges against one lane's gathered frontier.
            order = jnp.argsort(gids_l)
            sg = gids_l[order]
            st = tab_l[order]
            pos = jnp.clip(jnp.searchsorted(sg, src_g), 0, f_tot - 1)
            hit = (sg[pos] == src_g) & (src_g >= 0)
            cand = st[pos] + w[:, None, None]
            cand = jnp.where(hit[:, None, None], cand, INF)
            cand = semiring.bump_to_inf(cand)
            return semiring.segment_topk_min(cand, dst_l, n_loc, k,
                                             pooled=True)

        r_loc = jax.vmap(relax_lane)(all_gids, all_tab)  # [L,n_loc,S,K]
        ov = jax.lax.pmax(overflow.astype(jnp.int32), axes)
        return r_loc, ov

    in_specs = (
        P(None, axes, None, None),  # S (node axis over all mesh axes)
        P(None, axes),              # changed
        P(axes, None),              # edge_src [n_shards, e_cap]
        P(axes, None),              # edge_dst_l
        P(axes, None),              # edge_w
        P(axes),                    # shard ids
    )
    out_specs = (P(None, axes, None, None), P(None))
    shard_arange = jnp.arange(n_shards, dtype=jnp.int32)
    r, ov = shardmap.shard_map(
        block, mesh=am, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(S, changed, graph.edge_src, graph.edge_dst_l, graph.edge_w,
      shard_arange)
    return r, ov > 0


def relax_frontier(graph: FrontierGraph, S: jax.Array, changed: jax.Array,
                   cfg: DKSConfig) -> tuple[jax.Array, jax.Array]:
    """Frontier-compressed relax, single-query: the 1-lane case of
    :func:`relax_frontier_lanes`.  Returns (R[V, 2^m, K], overflow bool)."""
    r, ov = relax_frontier_lanes(graph, S[None], changed[None], cfg)
    return r[0], ov[0]


def frontier_tail(graph: FrontierGraph, state: DKSState, R: jax.Array,
                  overflow: jax.Array, cfg: DKSConfig) -> DKSState:
    """Everything after the frontier relax, per lane: message accounting,
    top-K merge, subset combine, and the shared superstep finish (node
    axis sharded over the mesh, keyword-set axis replicated — no
    collectives).  The lane driver vmaps this over its lane axis."""
    S0 = state.S
    deg = graph.out_degree.astype(jnp.float32)
    n_bfs = jnp.sum(jnp.where(state.first_fire, deg, 0.0))
    n_deep = jnp.sum(jnp.where(state.changed & ~state.first_fire, deg, 0.0))

    S1 = semiring.topk_merge(S0, R)
    S1 = combine(S1, cfg)
    nxt = dataclasses.replace(
        state, S=S1,
        msgs_bfs=state.msgs_bfs + n_bfs, msgs_deep=state.msgs_deep + n_deep,
        step=state.step + 1,
    )
    return finish_superstep(graph, S0, nxt, cfg, overflow=overflow)


def superstep_frontier(graph: FrontierGraph, state: DKSState,
                       cfg: DKSConfig) -> DKSState:
    """One superstep with frontier-compressed communication (1 lane)."""
    R, overflow = relax_frontier(graph, state.S, state.changed, cfg)
    return frontier_tail(graph, state, R, overflow, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def run_dks_frontier(graph: FrontierGraph, kw_masks: jax.Array,
                     cfg: DKSConfig) -> DKSState:
    """Full frontier-sharded DKS run (jitted while-loop)."""
    from repro.core.dks import init_state

    state = init_state(graph, kw_masks, cfg)
    return jax.lax.while_loop(
        lambda st: ~st.done,
        lambda st: superstep_frontier(graph, st, cfg),
        state)


def run_dks_frontier_instrumented(
    graph: FrontierGraph,
    kw_masks: jax.Array,
    cfg: DKSConfig,
    exit_hook: Callable[[DKSState], bool] | None = None,
) -> tuple[DKSState, dict[str, Any]]:
    """Host-driven frontier-sharded loop with per-phase wall times — the
    sharded counterpart of :func:`repro.core.dks.run_dks_instrumented`
    (same ``timings`` keys, same ``history`` rows, same ``exit_hook``
    contract), so ``QueryEngine.query_instrumented`` serves both
    partitionings.

    Phase attribution differs from the dense path where the sharded
    dataflow forces it to: the frontier pack + all-gather + edge relax are
    fused inside one shard_map (:func:`relax_frontier_lanes`) and cannot
    be timed apart, so that whole exchange lands in "send_bfs"; "receive"
    is the per-node top-K merge of what arrived; "evaluate" (subset
    combine) and "send_agg" (aggregators + exit check) match the dense
    buckets.  Like the dense runner this is a 1-lane instance of the
    driver's instrumented host loop over the lane-batched phase kernels.
    """
    from repro.core.driver import host_instrumented_loop

    @jax.jit
    def _phase_relax(S, changed):
        return relax_frontier_lanes(graph, S, changed, cfg)

    @jax.jit
    def _phase_receive(S, aux):
        R, _overflow = aux
        return semiring.topk_merge(S, R)

    @jax.jit
    def _phase_combine(S):
        return jax.vmap(lambda s: combine(s, cfg))(S)

    @jax.jit
    def _phase_agg(S0, state, aux):
        _R, overflow = aux
        return jax.vmap(
            lambda s0, st, ov: finish_superstep(graph, s0, st, cfg,
                                                overflow=ov)
        )(S0, state, overflow)

    return host_instrumented_loop(
        graph, kw_masks, cfg, exit_hook,
        _phase_relax, _phase_receive, _phase_combine, _phase_agg)
