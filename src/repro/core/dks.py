"""DKS — Distributed Keyword Search (the paper's core algorithm) in JAX.

Vertex state is the dense table ``S[V, 2^m, K]`` (top-K distinct partial
answer weights per keyword-set — the paper's ``S_K``).  One superstep is:

  1. *Send/Receive* — min-plus edge relaxation from every node whose table
     changed last superstep (BFS messages; re-fires of previously visited
     nodes are exactly the paper's deep messages — see DESIGN.md §2),
     reduced per destination with an exact segment-top-K.
  2. *Combine* — per-node min-plus subset convolution over keyword-sets
     (the paper's local-tree S_K/V_K computation, Sec. 5.1), batched over
     ``ceil(log2 m)`` closure passes so it is one dense TPU-friendly op.
  3. *Aggregate* — frontier minima per keyword-set (aggregator ``A_S``) and
     the global top-K answer weights (aggregator ``A_A``).
  4. *Exit check* — sound on-device criterion ``nu[full] >= W_K`` (see
     spa.py), plus frontier exhaustion and the paper's message budget
     (Sec. 5.4 "system hangs at ~1M messages" — here a first-class config).

``run_dks`` executes the loop as a single jitted ``lax.while_loop`` and is
the unit that shards over the production mesh (node axis over data axes).
``run_dks_instrumented`` is a host loop around the same jitted phases with
per-phase wall times (paper Table 1) and literal Eq. 2 "paper" exit mode.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import INF
from repro.core import semiring, spa
from repro.graph.structure import DeviceGraph


# --------------------------------------------------------------------------
# Config / state
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DKSConfig:
    """Static configuration of a DKS run."""

    m: int                      # number of query keywords
    k: int = 1                  # top-K answers
    max_supersteps: int = 64
    message_budget: float = float("inf")  # paper: ~1e6 before Giraph hangs
    exit_mode: str = "sound"    # "sound" | "none" (run to frontier exhaustion)
    combine_impl: str = "jnp"   # "jnp" | "pallas"
    relax_impl: str = "jnp"     # "jnp" | "pallas"
    combine_passes: int | None = None  # default ceil(log2 m)
    frontier_frac: float = 0.25  # per-shard frontier cap (frontier relax);
    # overflow marks budget_hit — the paper's Sec. 5.4 forced-stop + SPA.

    @property
    def n_sets(self) -> int:
        return 1 << self.m

    @property
    def full(self) -> int:
        return (1 << self.m) - 1

    def n_combine_passes(self) -> int:
        if self.combine_passes is not None:
            return self.combine_passes
        if self.m <= 1:
            return 0
        return int(np.ceil(np.log2(self.m)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DKSState:
    """Per-superstep state (a pytree; node axis shards over the mesh).

    Shapes below are the un-batched single-query layout.  The lane-batched
    driver (:mod:`repro.core.driver`) runs the same pytree with an explicit
    leading **lane** axis on every field (``S[L, V, 2^m, K]``,
    ``done[L]``, ...): one lane per concurrent query, with per-lane
    freeze/exit flags (``done`` / ``budget_hit`` / ``capped``) so lanes
    stop individually while the driver keeps stepping the rest."""

    S: jax.Array            # f32[V, 2^m, K] top-K distinct partial weights
    changed: jax.Array      # bool[V] — Pregel "active" vertices
    first_fire: jax.Array   # bool[V] — active for the first time (BFS
                            # frontier; re-fires are deep messages, Fig. 11)
    visited: jax.Array      # bool[V] — ever active (paper Fig. 13)
    g: jax.Array            # f32[2^m] global running min per keyword-set
    s_front: jax.Array      # f32[2^m] min over current frontier (A_S aggr.)
    topk_w: jax.Array       # f32[K] global top-K answer weights (A_A aggr.)
    topk_root: jax.Array    # i32[K] their root nodes
    msgs_bfs: jax.Array     # f32[] cumulative BFS messages (first visits)
    msgs_deep: jax.Array    # f32[] cumulative deep messages (re-fires)
    step: jax.Array         # i32[]
    done: jax.Array         # bool[]
    budget_hit: jax.Array   # bool[] — stopped by message budget (Sec. 5.4)
    capped: jax.Array       # bool[] — stopped ONLY by the superstep cap
                            # (truncated: the answer is unproven)


# --------------------------------------------------------------------------
# Phases
# --------------------------------------------------------------------------


def init_state(graph: DeviceGraph, kw_masks: jax.Array, cfg: DKSConfig) -> DKSState:
    """Superstep 0: keyword-nodes hold weight-0 singletons and are active."""
    v_pad = graph.v_pad
    n, k = cfg.n_sets, cfg.k
    S = jnp.full((v_pad, n, k), INF, jnp.float32)
    for i in range(cfg.m):
        S = S.at[:, 1 << i, 0].set(jnp.where(kw_masks[i], 0.0, INF))
    changed = jnp.any(kw_masks, axis=0) & graph.node_valid
    S = combine(S, cfg)  # nodes holding several keywords already combine
    state = DKSState(
        S=S,
        changed=changed,
        first_fire=changed,
        visited=changed,
        g=jnp.full((n,), INF, jnp.float32),
        s_front=jnp.full((n,), INF, jnp.float32),
        topk_w=jnp.full((k,), INF, jnp.float32),
        topk_root=jnp.full((k,), -1, jnp.int32),
        msgs_bfs=jnp.float32(0.0),
        msgs_deep=jnp.float32(0.0),
        step=jnp.int32(0),
        done=jnp.bool_(False),
        budget_hit=jnp.bool_(False),
        capped=jnp.bool_(False),
    )
    return aggregate(graph, state, cfg)


def relax(graph: DeviceGraph, S: jax.Array, changed: jax.Array,
          cfg: DKSConfig) -> jax.Array:
    """Messages: every active node sends its table along every incident edge;
    destinations take the per-keyword-set top-K of what arrives.

    Returns R[V, 2^m, K] (INF where nothing arrived).
    """
    if cfg.relax_impl == "pallas":
        from repro.kernels.segment_minplus import ops as sm_ops
        return sm_ops.segment_minplus(
            S, graph.src, graph.dst, graph.w,
            changed, graph.v_pad, cfg.k,
        )
    send = changed[graph.src] & graph.valid
    # cand[e, ks, k] = S[src(e), ks, k] + w(e)
    cand = S[graph.src] + graph.w[:, None, None]
    cand = jnp.where(send[:, None, None], cand, INF)
    cand = semiring.bump_to_inf(cand)
    # Candidate axis = (edge, slot); segment by destination.
    return semiring.segment_topk_min(cand, graph.dst, graph.v_pad, cfg.k,
                                     pooled=True)  # [V, 2^m, K]


def combine(S: jax.Array, cfg: DKSConfig) -> jax.Array:
    """Per-node min-plus subset convolution:
    ``S[v, a|b] <- topk(S[v, a|b] ∪ (S[v,a] ⊕ S[v,b]))`` for disjoint a,b.

    Batched over all split pairs at once; ``ceil(log2 m)`` passes reach the
    popcount-doubling closure (DESIGN.md §3.1).
    """
    if cfg.m <= 1:
        return S
    if cfg.combine_impl == "pallas":
        from repro.kernels.subset_combine import ops as sc_ops
        return sc_ops.subset_combine(S, cfg.m, cfg.n_combine_passes())
    pairs = spa.split_pairs(cfg.m)
    t_ids = jnp.asarray([p[0] for p in pairs], jnp.int32)
    a_ids = jnp.asarray([p[1] for p in pairs], jnp.int32)
    b_ids = jnp.asarray([p[2] for p in pairs], jnp.int32)
    k = cfg.k
    n_pairs = len(pairs)

    def one_pass(S, _):
        a = jnp.take(S, a_ids, axis=1)          # [V, P, K]
        b = jnp.take(S, b_ids, axis=1)          # [V, P, K]
        cand = semiring.outer_combine(a, b)     # [V, P, K]
        #

        # Reduce candidates into their target keyword-sets: segment over the
        # pair axis, feature axes (V,) after folding K into the candidate
        # axis: rows (p, kslot) -> segment t_ids[p].
        vals = cand.transpose(1, 2, 0).reshape(n_pairs * k, -1)  # [(P K), V]
        seg = jnp.repeat(t_ids, k)
        red = semiring.segment_topk_min(vals, seg, cfg.n_sets, k)  # [2^m, V, K]
        red = red.transpose(1, 0, 2)            # [V, 2^m, K]
        return semiring.topk_merge(S, red), None

    S, _ = jax.lax.scan(one_pass, S, None, length=cfg.n_combine_passes())
    return S


def aggregate(graph: DeviceGraph, state: DKSState, cfg: DKSConfig) -> DKSState:
    """Aggregators A_S (frontier minima per keyword-set) and A_A (global
    top-K answers: smallest full-set values across all nodes)."""
    S, changed = state.S, state.changed
    masked = jnp.where(changed[:, None], S[:, :, 0], INF)  # [V, 2^m]
    s_front = jnp.min(masked, axis=0)
    g = jnp.minimum(state.g, jnp.min(S[:, :, 0], axis=0))
    topk_w, idx = semiring.smallest_k_2d(S[:, cfg.full, :], cfg.k)
    topk_root = idx // cfg.k
    topk_root = jnp.where(topk_w >= INF, -1, topk_root)
    return dataclasses.replace(
        state, s_front=s_front, g=g, topk_w=topk_w, topk_root=topk_root
    )


def exit_check(graph: DeviceGraph, state: DKSState, cfg: DKSConfig) -> DKSState:
    """Sound exit: stop when no future superstep can produce a new full-set
    value better than the current K-th best (nu[full] >= W_K), when the
    frontier is empty, or when the message budget is exhausted.  A run that
    stops for none of these reasons but hits ``max_supersteps`` is flagged
    ``capped`` — truncated, its answer unproven."""
    frontier_empty = ~jnp.any(state.changed)
    done = frontier_empty
    budget_hit = jnp.bool_(False)
    if cfg.exit_mode == "sound":
        nu = spa.nu_lower_bound(state.g, graph.e_min(), cfg.m)
        w_k = state.topk_w[cfg.k - 1]
        done = done | (nu[cfg.full] >= jnp.minimum(w_k, INF))
    msgs = state.msgs_bfs + state.msgs_deep
    if np.isfinite(cfg.message_budget):
        budget_hit = msgs > cfg.message_budget
        done = done | budget_hit
    capped = (state.step >= cfg.max_supersteps) & ~done
    done = done | capped
    return dataclasses.replace(state, done=done, budget_hit=budget_hit,
                               capped=capped)


def freeze_finished(old: DKSState, new: DKSState) -> DKSState:
    """Keep ``old`` wherever its exit criterion has already fired.

    Batched loops (the lane driver, :mod:`repro.core.driver`) keep
    stepping every lane until the whole batch finishes.  The lattice makes
    the extra steps idempotent on ``S``, but
    ``msgs_bfs``/``msgs_deep``/``step`` are counters, not lattice values —
    without this select, finished lanes keep accumulating them (and could
    even flip ``budget_hit``).  ``old.done`` may be any rank: a scalar
    under a per-lane vmap, or ``[L]`` on a state with an explicit lane
    axis — it broadcasts against each field from the left.  A single
    query's while-loop never runs the body once done, so the select only
    ever fires when some lanes finish before others.
    """
    done = old.done

    def sel(o, n):
        d = done.reshape(done.shape + (1,) * (o.ndim - done.ndim))
        return jnp.where(d, o, n)

    return jax.tree_util.tree_map(sel, old, new)


def finish_superstep(graph: Any, S0: jax.Array, state: DKSState,
                     cfg: DKSConfig, overflow: jax.Array | None = None,
                     ) -> DKSState:
    """The post-combine tail shared by every superstep flavor (dense,
    frontier-sharded, and their instrumented hosts): recompute the active
    set from the table delta, fold visit tracking, run the aggregators,
    and apply the exit check.  ``state.S`` must already hold the combined
    table; ``S0`` is the pre-relax table; counters/step are the caller's.

    ``overflow``: the frontier-sharded paths pass their frontier-overflow
    flag — it folds into ``budget_hit``/``done`` (frontier overflow == the
    paper's Sec. 5.4 message-budget forced stop).
    """
    changed = jnp.any(state.S < S0, axis=(1, 2)) & graph.node_valid
    st = dataclasses.replace(
        state,
        changed=changed,
        first_fire=changed & ~state.visited,
        visited=state.visited | changed,
    )
    st = aggregate(graph, st, cfg)
    st = exit_check(graph, st, cfg)
    if overflow is not None:
        st = dataclasses.replace(
            st, budget_hit=st.budget_hit | overflow,
            done=st.done | overflow)
    return st


def superstep(graph: DeviceGraph, state: DKSState, cfg: DKSConfig) -> DKSState:
    """One Pregel superstep (phases 1-4 above)."""
    S0 = state.S
    deg = graph.out_degree.astype(jnp.float32)
    # First-time fires are BFS messages; re-fires of visited vertices are
    # the deep messages (paper Fig. 11).
    n_bfs = jnp.sum(jnp.where(state.first_fire, deg, 0.0))
    n_deep = jnp.sum(jnp.where(state.changed & ~state.first_fire, deg, 0.0))

    R = relax(graph, S0, state.changed, cfg)
    S1 = semiring.topk_merge(S0, R)
    S1 = combine(S1, cfg)
    nxt = dataclasses.replace(
        state,
        S=S1,
        msgs_bfs=state.msgs_bfs + n_bfs,
        msgs_deep=state.msgs_deep + n_deep,
        step=state.step + 1,
    )
    return finish_superstep(graph, S0, nxt, cfg)


# --------------------------------------------------------------------------
# Drivers
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=())
def run_dks(graph: DeviceGraph, kw_masks: jax.Array, cfg: DKSConfig) -> DKSState:
    """Full DKS run as one jitted while-loop (the production path)."""
    state = init_state(graph, kw_masks, cfg)

    def cond(st: DKSState):
        return ~st.done

    def body(st: DKSState):
        return superstep(graph, st, cfg)

    return jax.lax.while_loop(cond, body, state)


def run_dks_batched(graph: DeviceGraph, kw_masks_batch: jax.Array,
                    cfg: DKSConfig) -> DKSState:
    """Serve a BATCH of queries in one device program.

    kw_masks_batch: bool[Q, m, V].  A thin alias for the lane-batched
    driver (:func:`repro.core.driver.run_lanes`): the query axis is the
    driver's lane axis, the fused while-loop steps until every lane's exit
    criterion fires, and finished lanes are frozen
    (:func:`freeze_finished`) so their counters stop with them.  Amortizes
    graph residency and kernel launches across the paper's 100-query
    workloads.
    """
    from repro.core.driver import run_lanes

    return run_lanes(graph, kw_masks_batch, cfg)


def run_dks_instrumented(
    graph: DeviceGraph,
    kw_masks: jax.Array,
    cfg: DKSConfig,
    exit_hook: Callable[[DKSState], bool] | None = None,
) -> tuple[DKSState, dict[str, Any]]:
    """Host-driven superstep loop with per-phase wall times (paper Table 1).

    A 1-lane instance of the driver's instrumented host loop
    (:func:`repro.core.driver.host_instrumented_loop`) over lane-batched
    phase kernels.  Phases timed: send_bfs (gather+add candidates),
    receive (segment top-K + merge), evaluate (subset combine = local-tree
    S_K computation), send_agg (aggregators + exit).  Deep messages share
    the relax kernel (DESIGN.md §2), so their share is attributed by
    message counts.

    ``exit_hook``: optional host-side exit criterion (e.g. the literal paper
    Eq. 2 check, fagin.paper_exit_hook) evaluated between supersteps.
    """
    from repro.core.driver import host_instrumented_loop

    def _relax_one(S, changed):
        send = changed[graph.src] & graph.valid
        cand = S[graph.src] + graph.w[:, None, None]
        cand = jnp.where(send[:, None, None], cand, INF)
        return semiring.bump_to_inf(cand)

    def _receive_one(S, cand):
        r = semiring.segment_topk_min(cand, graph.dst, graph.v_pad, cfg.k,
                                      pooled=True)
        return semiring.topk_merge(S, r)

    @jax.jit
    def _phase_relax(S, changed):
        return jax.vmap(_relax_one)(S, changed)

    @jax.jit
    def _phase_receive(S, cand):
        return jax.vmap(_receive_one)(S, cand)

    @jax.jit
    def _phase_combine(S):
        return jax.vmap(lambda s: combine(s, cfg))(S)

    @jax.jit
    def _phase_agg(S0, state, _aux):
        return jax.vmap(
            lambda s0, st: finish_superstep(graph, s0, st, cfg))(S0, state)

    return host_instrumented_loop(
        graph, kw_masks, cfg, exit_hook,
        _phase_relax, _phase_receive, _phase_combine, _phase_agg)


def extract_answer_weights(state: DKSState, cfg: DKSConfig) -> np.ndarray:
    """Global top-K distinct answer weights (INF-padded)."""
    return np.asarray(state.topk_w)
