"""The lane-batched superstep driver — ONE step kernel for every executor.

The paper's DKS algorithm is one Pregel superstep loop, and Pregel-style
systems win by running many concurrent computations through a single
synchronized step loop (Malewicz et al.; Giraph in the paper's own
experiments).  This module is that structure: a :class:`DKSState` whose
every field carries an explicit leading **lane** axis (``L`` concurrent
queries), and one ``lane_superstep(graph, state, cfg) -> state`` kernel
that advances all lanes together and is correct for both partitionings:

- **dense** (:class:`~repro.graph.structure.DeviceGraph`): the dense
  :func:`~repro.core.dks.superstep` vmapped over the lane axis;
- **sharded** (:class:`~repro.core.dks_sharded.FrontierGraph`): the lane
  axis lives *inside* the ``shard_map`` body (lanes-per-shard,
  :func:`~repro.core.dks_sharded.relax_frontier_lanes`), so batching no
  longer needs vmap-over-shard_map — one device program relaxes every
  lane's frontier in one collective exchange.

Per-lane exit flags (``done`` / ``budget_hit`` / ``capped``) freeze lanes
individually (:func:`freeze_lanes`): a lane that proves its exit stops
accumulating counters while the driver keeps stepping the rest.  Every
engine surface is a thin loop over this driver — ``query`` is the
degenerate 1-lane case, ``query_batch`` a fused while-loop over a bucket
of lanes, streaming/deadline surfaces host-step it — so there is exactly
one superstep formulation to test, shard, and optimize.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.dks import (
    DKSConfig,
    DKSState,
    freeze_finished,
    init_state,
    superstep,
)
from repro.obs.telemetry import (
    HostTelemetryCollector,
    N_COLS as TELEMETRY_COLS,
    TELEMETRY_MAX_SUPERSTEPS,
)


def is_frontier_graph(graph: Any) -> bool:
    """Sharded (FrontierGraph) vs dense (DeviceGraph) residency, without
    importing dks_sharded at module load (it imports from dks)."""
    return hasattr(graph, "edge_dst_l")


def lane_view(state: DKSState, i: int) -> DKSState:
    """One lane of a lane-batched state, as an unbatched DKSState."""
    return jax.tree_util.tree_map(lambda x: x[i], state)


def lane_init(graph: Any, kw_masks: jax.Array, cfg: DKSConfig) -> DKSState:
    """Superstep 0 for a batch of lanes.  ``kw_masks``: bool[L, m, V]."""
    with jax.named_scope("dks.init"):
        return jax.vmap(lambda m: init_state(graph, m, cfg))(kw_masks)


# Per-lane freeze: lanes whose exit criterion fired keep their state and
# counters while the driver steps the rest (rank-aware select on ``done``).
freeze_lanes = freeze_finished


def lane_superstep(graph: Any, state: DKSState, cfg: DKSConfig,
                   csr: Any = None) -> DKSState:
    """One Pregel superstep for every lane at once, finished lanes frozen.

    The single kernel behind every engine executor: dense lanes ride a
    vmapped :func:`~repro.core.dks.superstep`; sharded lanes share one
    frontier exchange inside the ``shard_map``
    (:func:`~repro.core.dks_sharded.relax_frontier_lanes`) with the
    node-local tail vmapped over lanes.

    ``csr``: a :class:`~repro.kernels.lane_superstep.LaneCSR` layout makes
    this the real ``backend="pallas"`` path on dense graphs — the whole
    inner loop (relax + hub merge + receive + combine + per-lane freeze)
    runs as ONE fused kernel launch over the lane axis
    (:func:`~repro.kernels.lane_superstep.fused_lane_superstep`),
    bit-identical to the vmapped jnp superstep.  The engine builds the
    layout once per graph (``QueryEngine.build``) and threads it here.
    Sharded graphs never take the fused path: the shard_map body keeps
    jnp (``ExecutionPolicy`` rejects the combination up front; see
    NotImplementedError there — fusing the sharded body is the remaining
    ROADMAP item).
    """
    if is_frontier_graph(graph):
        from repro.core.dks_sharded import frontier_tail, relax_frontier_lanes

        R, overflow = relax_frontier_lanes(graph, state.S, state.changed, cfg)
        nxt = jax.vmap(
            lambda st, r, ov: frontier_tail(graph, st, r, ov, cfg)
        )(state, R, overflow)
    elif csr is not None and cfg.relax_impl == "pallas":
        from repro.kernels.lane_superstep import fused_lane_superstep

        nxt = fused_lane_superstep(graph, csr, state, cfg)
    else:
        nxt = jax.vmap(lambda st: superstep(graph, st, cfg))(state)
    if state.done.shape[0] == 1:
        # Degenerate 1-lane case (engine.query, streams): every driving
        # loop stops at done, so the body never runs on a finished lane —
        # the freeze select would be a pure full-state where() per
        # superstep that XLA cannot fold (done is dynamic).  Lane count
        # is static at trace time, so this branch costs nothing.
        return nxt
    return freeze_lanes(state, nxt)


@functools.partial(jax.jit, static_argnames=("cfg",))
def run_lanes(graph: Any, kw_masks: jax.Array, cfg: DKSConfig) -> DKSState:
    """Full lane-batched DKS run as one jitted while-loop (the fused
    driver): steps until every lane's exit criterion fires.  Works on both
    partitionings; 1 lane is the single-query production path."""
    state = lane_init(graph, kw_masks, cfg)
    return jax.lax.while_loop(
        lambda st: ~jnp.all(st.done),
        lambda st: lane_superstep(graph, st, cfg),
        state)


# --------------------------------------------------------------------------
# Production superstep telemetry (paper §6's per-superstep curves, from
# the FUSED loop — no drop to the stepwise instrumented path)
# --------------------------------------------------------------------------


def telemetry_capacity(cfg: DKSConfig) -> int:
    """Device-buffer row count for a config: one row per superstep, capped
    at TELEMETRY_MAX_SUPERSTEPS (a capped run sets ``done`` anyway, so the
    cap only matters for configs with a larger max_supersteps)."""
    return max(1, min(int(cfg.max_supersteps), TELEMETRY_MAX_SUPERSTEPS))


def telemetry_row(state: DKSState) -> jax.Array:
    """One lane-summed counter row for the post-step state: ``[frontier,
    msgs_bfs (cumulative), msgs_deep (cumulative), frozen lanes]`` — the
    column order repro.obs.telemetry decodes.  Pure reads: computing the
    row cannot perturb the state, which is what makes telemetry-on
    bit-identical to telemetry-off."""
    return jnp.stack([
        jnp.sum(state.changed).astype(jnp.float32),
        jnp.sum(state.msgs_bfs).astype(jnp.float32),
        jnp.sum(state.msgs_deep).astype(jnp.float32),
        jnp.sum(state.done).astype(jnp.float32),
    ])


def run_lanes_telemetry(
    graph: Any, kw_masks: jax.Array, cfg: DKSConfig, csr: Any = None,
) -> tuple[DKSState, jax.Array, jax.Array]:
    """The fused driver with a telemetry carry: the while-loop threads
    ``(state, buf, i)`` and writes one :func:`telemetry_row` per superstep
    into a bounded ``[T, 4]`` f32 buffer (rows past T overwrite the last
    slot — the decoder flags truncation).  Returns ``(final state, buffer,
    supersteps run)``; same exit condition, same superstep kernel, so the
    state trajectory is exactly :func:`run_lanes`'s.

    Meant to be jitted by the caller (the engine caches it per config,
    like the plain fused executable).
    """
    T = telemetry_capacity(cfg)
    init = (lane_init(graph, kw_masks, cfg),
            jnp.zeros((T, TELEMETRY_COLS), jnp.float32),
            jnp.int32(0))

    def cond(carry):
        st, _, _ = carry
        return ~jnp.all(st.done)

    def body(carry):
        st, buf, i = carry
        nxt = lane_superstep(graph, st, cfg, csr=csr)
        buf = buf.at[jnp.minimum(i, T - 1)].set(telemetry_row(nxt))
        return nxt, buf, i + 1

    return jax.lax.while_loop(cond, body, init)


# --------------------------------------------------------------------------
# Instrumented host loop (per-phase wall times, paper Table 1)
# --------------------------------------------------------------------------


def host_instrumented_loop(
    graph: Any,
    kw_masks: jax.Array,
    cfg: DKSConfig,
    exit_hook: Callable[[DKSState], bool] | None,
    phase_relax: Callable,
    phase_receive: Callable,
    phase_combine: Callable,
    phase_agg: Callable,
) -> tuple[DKSState, dict[str, Any]]:
    """The host-driven per-phase superstep loop shared by the dense and
    sharded instrumented runners — one copy of the timing buckets, message
    accounting, history rows, and ``exit_hook`` contract.  The phases are
    the driver's lane-batched kernels run at ``L = 1`` (``kw_masks``:
    bool[m, V], un-batched; the final state is returned un-batched too).

    Phase signatures (each jitted by the caller, timed here; all on
    lane-batched arrays):
      phase_relax(S, changed) -> aux           "send_bfs"
      phase_receive(S, aux) -> S1              "receive"
      phase_combine(S1) -> S1                  "evaluate"
      phase_agg(S0, state, aux) -> state       "send_agg"
    ``aux`` is whatever relax must hand forward (per-edge candidates on the
    dense path; (R, overflow) on the sharded path).

    ``exit_hook`` sees an *un-batched* :class:`DKSState` (lane 0), so
    host-side criteria like ``fagin.paper_exit_hook`` keep working.
    """
    timings = {"send_bfs": 0.0, "receive": 0.0, "evaluate": 0.0,
               "send_agg": 0.0}
    state = jax.block_until_ready(lane_init(graph, kw_masks[None], cfg))
    deg = graph.out_degree.astype(jnp.float32)
    # One source of per-superstep truth: rows accumulate on the shared
    # collector (repro.obs) and the legacy ``history`` dicts are derived
    # from it — the fused telemetry path decodes the same columns.
    collector = HostTelemetryCollector()
    while not bool(state.done[0]):
        n_bfs = jnp.sum(jnp.where(state.first_fire, deg, 0.0), axis=1)
        n_deep = jnp.sum(
            jnp.where(state.changed & ~state.first_fire, deg, 0.0), axis=1)

        t0 = time.perf_counter()
        aux = jax.block_until_ready(phase_relax(state.S, state.changed))
        t1 = time.perf_counter()
        S1 = jax.block_until_ready(phase_receive(state.S, aux))
        t2 = time.perf_counter()
        S1 = jax.block_until_ready(phase_combine(S1))
        t3 = time.perf_counter()
        S0 = state.S
        state = dataclasses.replace(
            state,
            S=S1,
            msgs_bfs=state.msgs_bfs + n_bfs,
            msgs_deep=state.msgs_deep + n_deep,
            step=state.step + 1,
        )
        state = jax.block_until_ready(phase_agg(S0, state, aux))
        t4 = time.perf_counter()

        timings["send_bfs"] += t1 - t0
        timings["receive"] += t2 - t1
        timings["evaluate"] += t3 - t2
        timings["send_agg"] += t4 - t3
        lane = lane_view(state, 0)
        collector.record(
            frontier=int(jnp.sum(lane.changed)),
            msgs_bfs=float(lane.msgs_bfs),
            msgs_deep=float(lane.msgs_deep),
            frozen=int(jnp.sum(state.done)),
            best=float(lane.topk_w[0]),
        )
        if exit_hook is not None and exit_hook(lane):
            state = dataclasses.replace(
                state, done=jnp.ones_like(state.done))
    telemetry = collector.build()
    info = dict(timings=timings, history=telemetry.rows(),
                telemetry=telemetry)
    return lane_view(state, 0), info
