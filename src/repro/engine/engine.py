"""QueryEngine — the one front door for DKS relationship queries.

The paper's end-to-end flow (Fig. 2c) is: inverted-index lookup ->
keyword-node masks -> DKS supersteps -> aggregator-side answer trees.
Before this module, every driver re-stitched that flow by hand and chose
among four overlapping entry points (``run_dks``, ``run_dks_batched``,
``run_dks_instrumented``, ``dks_sharded``).  The engine owns:

- **graph device residency** — dense :class:`DeviceGraph` for the single-
  program path, frontier-partitioned :class:`FrontierGraph` for the
  ``shard_map`` mesh path, built once and reused by every query;
- **the inverted index** — token -> keyword-node masks, padded to the
  device layout (no ``np.pad`` dance at call sites);
- **the lane-batched driver** — every surface is a thin loop over ONE
  step kernel (:mod:`repro.core.driver`): a :class:`DKSState` with a
  leading lane axis, advanced by ``lane_superstep`` on either
  partitioning (for "sharded" the lane axis lives *inside* the
  ``shard_map`` body, so a batch of queries costs one device program and
  one collective per superstep — no vmap-over-shard_map needed);
- **a compiled-executable cache** — per ``(DKSConfig, partition)`` there
  are exactly two compiled things: the **fused** driver (the whole
  while-loop as one device program, used by ``query`` — the degenerate
  1-lane case — and ``query_batch``) and the **stepwise** driver (an
  ``(init, superstep)`` pair the host loops over, used by the streaming,
  deadline, and instrumented surfaces).  Repeated queries with the same
  ``(m, k)`` shape reuse the compiled program with zero re-tracing
  (asserted by tests via :meth:`QueryEngine.trace_count`).

Query surfaces::

    engine = QueryEngine.build(graph, tokens=tokens)
    result = engine.query(["paris", "piano"], k=3)     # ranked AnswerTrees
    results = engine.query_batch(queries, k=1)          # m-bucketed lanes
    for upd in engine.query_stream(query, k=1):         # per-superstep
        ...  # upd.weights + upd.spa_ratio: answers with a sound bound
    engine.query_deadline_batch(queries, deadline_s=.05)  # shared driver

``query_stream`` makes the paper's early-termination guarantee (Sec. 5.4 /
Fig. 12) a first-class API: after every superstep the caller sees the
current best answers together with a monotonically tightening lower bound
on the optimum, so it can stop as soon as the approximation suffices.
``query_deadline_batch`` extends that to a *bucket* of same-shape queries
riding one driver: lanes freeze individually as they prove exits, and on
expiry every lane gets its own best-so-far answer with per-lane bounds.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from pathlib import Path
from typing import Any, Callable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import INF, shardmap
from repro.core.dks import DKSConfig, DKSState, run_dks_instrumented
from repro.core.driver import (lane_init, lane_superstep, lane_view,
                               run_lanes_telemetry)
from repro.obs.telemetry import SuperstepTelemetry
from repro.obs.trace import Trace, timed_span
from repro.core.reconstruct import collect_answers
from repro.core.spa import nu_lower_bound, spa_cover_dp, spa_ratio
from repro.engine.policy import ExecutionPolicy
from repro.engine.result import QueryResult, StreamUpdate
from repro.graph.index import InvertedIndex
from repro.graph.structure import Graph


@dataclasses.dataclass(frozen=True)
class _StateBounds:
    """One DKS state's bound facts (see QueryEngine._state_bounds)."""

    best: float
    nu_full: float
    spa: float
    frontier: int
    opt_lb: float
    sound_lb: float


class QueryEngine:
    """Facade over index lookup, device residency, and the DKS executors.

    Build one per (graph, policy); serve many queries.  Thread-compatible
    for reads after build (the caches only grow).
    """

    # Monotone build ids: every built engine gets a fresh ``version``, so
    # result caches keyed on cache_token() can never serve answers computed
    # against a previous graph build.  Engines built from a persisted
    # artifact use the artifact's content hash instead — stable across
    # rebuilds of the SAME artifact (a serve restart keeps its cache
    # keys), necessarily different for any other graph content.
    _build_counter = itertools.count(1)

    def __init__(
        self,
        graph: Graph,
        index: InvertedIndex,
        policy: ExecutionPolicy,
        device_graph: Any,
        mesh: Any = None,
        graph_hash: str | None = None,
    ) -> None:
        self.graph = graph
        self.index = index
        self.policy = policy
        self.device_graph = device_graph
        self.mesh = mesh  # set for partition="sharded"; None otherwise
        self.graph_hash = graph_hash
        self.version: int | str = (
            f"artifact:{graph_hash}" if graph_hash is not None
            else next(QueryEngine._build_counter))
        self._e_min = float(device_graph.e_min())
        # Compiled-executable cache: (DKSConfig, partition, kind) -> callable.
        self._executables: dict[tuple, Any] = {}
        self._trace_counts: dict[tuple, int] = {}
        self._execute_count = 0
        # Answer subsystem hooks: the device-batched backtracer (lazy; its
        # kernels cache per bucket shape) and the artifact the engine was
        # built from (labels for answer rendering).  ``batched_extraction``
        # turns the device backtrace path of query_batch off (host-only
        # extraction) — a debugging escape hatch, not a serving knob.
        self._answer_backtracer: Any = None
        self.artifact: Any = None
        self.batched_extraction = True
        # backend="pallas": the fused lane-superstep kernel's padded-CSR
        # layout, built once per graph by ``build`` (None on jnp/sharded
        # engines).  Every executable takes it as an argument and threads
        # it into ``lane_superstep`` — layout cost is paid at build, not
        # per query.  ``lane_csr_build_s``: host seconds that build took.
        self.lane_csr: Any = None
        self.lane_csr_build_s = 0.0

    # ------------------------------------------------------------------
    # Construction
    # ------------------------------------------------------------------

    @classmethod
    def build(
        cls,
        graph: Graph | None = None,
        tokens: np.ndarray | None = None,
        index: InvertedIndex | None = None,
        policy: ExecutionPolicy | None = None,
        artifact: Any = None,
    ) -> "QueryEngine":
        """Build an engine: inverted index + device-resident graph.

        Two entry modes:

        - ``graph=`` plus exactly one of ``tokens`` (int[V, L] token
          matrix) or ``index`` — or neither, when ``graph.labels`` is set
          (then the index is built from the labels);
        - ``artifact=`` — a :class:`repro.store.GraphArtifact` (or a path
          to one), or a :class:`repro.store.GraphChain` (a base plus
          stacked delta artifacts — the live-graph path): graph, device
          layout, and the persisted inverted index all come straight off
          the mmapped buffers — no re-tokenizing, no edge re-sort — and
          the artifact's ``content_hash`` (for a chain, the *chained*
          hash) becomes the engine ``version`` (so ``cache_token`` keys
          are stable across rebuilds of the same artifact, and distinct
          for any other graph or chain depth — a cache can never serve a
          stale build).
        """
        policy = policy or ExecutionPolicy()
        graph_hash = None
        if artifact is not None:
            if graph is not None or tokens is not None or index is not None:
                raise ValueError(
                    "pass artifact= alone — it already carries the graph "
                    "and the persisted index")
            if isinstance(artifact, (str, Path)):
                from repro.store import open_artifact
                artifact = open_artifact(artifact)
            graph = artifact.graph()
            index = artifact.index()
            graph_hash = artifact.content_hash
        if graph is None:
            raise ValueError("QueryEngine.build needs graph= or artifact=")
        if index is not None and tokens is not None:
            raise ValueError(
                "pass either tokens= or index=, not both (the tokens would "
                "be ignored in favor of the prebuilt index)")
        if index is None:
            if tokens is not None:
                index = InvertedIndex.from_token_matrix(np.asarray(tokens))
            elif graph.labels is not None:
                index = InvertedIndex.from_labels(graph.labels)
            else:
                raise ValueError(
                    "QueryEngine.build needs tokens=, index=, or graph.labels")
        # Fold the weight policy into the weight vectors ONCE, before any
        # device packing: the dense DeviceGraph, the sharded FrontierGraph,
        # host answer backtrace, and rendering all read the same effective
        # weights — the relaxation kernels never know a policy existed.
        # The default policy is the identity (same Graph object), which is
        # what keeps pre-typed artifacts bit-identical.
        from repro.graph.weights import apply_weight_policy
        graph = apply_weight_policy(graph, policy.weights)
        mesh = None
        if policy.partition == "sharded":
            from repro.core.dks_sharded import pack_frontier_graph
            n_shards = policy.n_shards or len(jax.devices())
            mesh = shardmap.make_mesh((n_shards,), ("data",))
            device_graph = pack_frontier_graph(graph, n_shards, mesh=mesh)
        else:
            device_graph = graph.to_device()
        engine = cls(graph, index, policy, device_graph, mesh=mesh,
                     graph_hash=graph_hash)
        engine.artifact = artifact
        if policy.backend == "pallas":
            # Dense-only by construction (the policy rejects
            # sharded+pallas).  The layout reads the DeviceGraph's
            # *effective* weights, so any WeightPolicy above already
            # flowed into the kernel's weight table.
            from repro.kernels.lane_superstep import (
                lane_csr_from_device_graph)
            t0 = time.perf_counter()
            engine.lane_csr = lane_csr_from_device_graph(device_graph)
            engine.lane_csr_build_s = time.perf_counter() - t0
        return engine

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------

    @property
    def n_nodes(self) -> int:
        return self.graph.n_nodes

    @property
    def n_edges(self) -> int:
        """Symmetrized device edge count (the |E| of Fig. 14)."""
        return self.device_graph.n_edges

    @property
    def v_pad(self) -> int:
        return self.device_graph.v_pad

    # Executor kinds, collapsed by the lane driver: "fused" (the whole
    # while-loop as one device program; query and query_batch) and
    # "stepwise" ((init, superstep) pair the host loops over; streaming
    # and deadline surfaces).  Legacy kind names from the four-executor
    # era keep resolving for callers of trace_count.  An engine built
    # with ExecutionPolicy(telemetry=True) resolves "fused" to the
    # telemetry-carrying variant, so callers asserting warm-cache
    # behavior via trace_count need not know which one serves them.
    _KIND_ALIASES = {"single": "fused", "batch": "fused",
                     "stream": "stepwise", "driver": "stepwise"}

    def _resolve_kind(self, kind: str) -> str:
        kind = self._KIND_ALIASES.get(kind, kind)
        if kind == "fused" and self.policy.telemetry:
            return "fused-telemetry"
        return kind

    def trace_count(self, m: int, k: int, kind: str = "fused",
                    **overrides) -> int:
        """How many times the executable for this query shape was traced.
        1 after any number of same-shape *and same-lane-count* queries =
        the cache works (a new lane count is a new input shape, so it
        re-traces once, like any jit)."""
        return self._traces(self._config(m, k, **overrides), kind)

    def _traces(self, cfg: DKSConfig, kind: str) -> int:
        key = (cfg, self.policy.partition, self._resolve_kind(kind))
        return self._trace_counts.get(key, 0)

    @property
    def cache_stats(self) -> dict[str, int]:
        """{executables, traces}: cache size vs. total trace events."""
        return {
            "executables": len(self._executables),
            "traces": sum(self._trace_counts.values()),
        }

    @property
    def extraction_stats(self) -> dict[str, int]:
        """Device-batched backtracer counters — ``device_resolved`` lanes
        whose answer trees the batched device program reconstructed, vs
        ``host_fallbacks`` ragged stragglers that re-ran the host search.
        Zeros before the backtracer is first used (it builds lazily)."""
        bt = self._answer_backtracer
        if bt is None:
            return {"device_resolved": 0, "host_fallbacks": 0}
        return {"device_resolved": int(bt.device_resolved),
                "host_fallbacks": int(bt.host_fallbacks)}

    @property
    def execute_count(self) -> int:
        """Device dispatches made through the compiled-executable cache —
        the ``query`` / ``query_batch`` / ``query_stream(ed)`` surfaces
        (streaming queries count one per superstep).  A serving layer's
        result-cache hit must leave this untouched — that is what its
        tests assert.  ``query_instrumented`` runs its own host-driven
        per-phase jits and is not counted here."""
        return self._execute_count

    def cache_token(self, keywords: Sequence, k: int = 1,
                    **overrides) -> tuple:
        """Hashable result-cache key for a query against THIS engine build.

        Normalizes the keywords to a sorted multiset — DKS answers are
        keyword-order invariant (permuting keywords permutes subset-lattice
        bits; every reduction is a min/top-k over the same value sets) —
        and folds in everything else that determines the answer: ``k``, the
        effective :class:`ExecutionPolicy` including per-call overrides,
        and the engine build ``version`` (a rebuilt graph gets a fresh
        version, so stale cached results can never be served).  For an
        artifact-built engine the version IS the artifact's content hash:
        rebuilding from the same artifact keys the same (caches survive a
        restart), any other graph content keys differently.
        """
        norm = tuple(sorted((type(t).__name__, t) for t in keywords))
        policy = self.policy
        if overrides:
            self._check_overrides(overrides)
            policy = dataclasses.replace(policy, **overrides)
        # Telemetry observes the run without changing the answer, so it
        # must not fragment result caches: engines built from the same
        # artifact share cache keys whether or not one of them watches
        # its supersteps.
        if policy.telemetry:
            policy = dataclasses.replace(policy, telemetry=False)
        return (norm, int(k), policy, self.version)

    @staticmethod
    def _check_overrides(overrides: dict) -> None:
        """Per-call overrides must not change the weight policy (the
        device graph was packed with the build policy's effective weights,
        so a per-query ``weights=`` would silently rank on the wrong
        vector) nor toggle telemetry (the flag picks the compiled fused
        variant at build; flipping it per call would double every entry
        in the executable cache).  Build a second engine instead."""
        if "weights" in overrides:
            raise ValueError(
                "the weight policy is fixed at engine build (the device "
                "graph is packed with its effective weights) — build an "
                "engine with ExecutionPolicy(weights=...) instead of "
                "overriding per call")
        if "telemetry" in overrides:
            raise ValueError(
                "telemetry is fixed at engine build (it selects the "
                "compiled fused-driver variant) — build an engine with "
                "ExecutionPolicy(telemetry=True) instead of overriding "
                "per call")

    def node_label(self, v: int) -> str:
        """Entity string for a node: in-memory graph labels when present,
        else the artifact's label blob (decoded per node, off the mmap),
        else ``node:<id>`` — the label function answer rendering plugs in.
        """
        v = int(v)
        if self.graph.labels is not None:
            return str(self.graph.labels[v])
        if self.artifact is not None and self.artifact.has_labels:
            return self.artifact.label(v)
        return f"node:{v}"

    def edge_info(self, u: int, v: int) -> tuple[str | None, float] | None:
        """``(predicate_name, confidence)`` of the effective edge between
        ``u`` and ``v`` (the cheapest parallel entry — the one backtrace
        resolved), or None on untyped graphs.  Rendering uses this to
        label answer-tree edges with their provenance."""
        return self.graph.edge_channel(int(u), int(v))

    def _backtracer(self):
        """The lazily-built device-batched backtracer (repro.answers);
        shared across queries so its per-shape kernels compile once."""
        if self._answer_backtracer is None:
            from repro.answers import BatchedBacktracer
            self._answer_backtracer = BatchedBacktracer(self.graph)
        return self._answer_backtracer

    # ------------------------------------------------------------------
    # Queries
    # ------------------------------------------------------------------

    def query(
        self,
        keywords: Sequence,
        k: int = 1,
        *,
        extract: bool = True,
        extract_pool: int | None = None,
        keep_state: bool = False,
        strict: bool = True,
        **overrides,
    ) -> QueryResult:
        """Answer one relationship query.

        ``keywords``: tokens understood by the index (int ids or strings).
        ``extract``: reconstruct ranked :class:`AnswerTree`\\ s on the host
        (skip for stats-only runs — the weights are always populated).
        ``extract_pool``: reconstruct up to this many distinct trees (>=
        ``k``) onto ``QueryResult.answer_pool`` — the material diversified
        re-ranking / pagination works from; ``answers`` stays the top-k.
        ``keep_state``: retain the raw final :class:`DKSState` on the
        result (a dense ``[V, 2^m, K]`` table — off by default so served
        results don't pin device memory).
        ``strict``: raise :class:`KeyError` when a keyword matches no node
        in the index (the query could only return INF after burning the
        full superstep budget).  ``strict=False`` runs best-effort; the
        offending tokens are reported on ``QueryResult.unmatched``.
        ``overrides``: per-call policy overrides (``max_supersteps``,
        ``message_budget``, ``exit_mode``) — they key the executable cache,
        so a steady workload should keep them constant.
        """
        keywords = list(keywords)
        cfg = self._config(len(keywords), k, **overrides)
        masks, unmatched = self._masks(keywords, strict)
        t0 = time.perf_counter()
        # The degenerate 1-lane case of the lane driver.
        states, telemetry = self._run_fused(cfg, masks[None])
        dt = time.perf_counter() - t0
        return self._make_result(keywords, masks, lane_view(states, 0), cfg,
                                 dt, extract, keep_state,
                                 unmatched=unmatched, own_time_s=dt,
                                 extract_pool=extract_pool,
                                 telemetry=telemetry)

    def query_batch(
        self,
        queries: Sequence[Sequence],
        k: int = 1,
        *,
        extract: bool = True,
        extract_pool: int | None = None,
        keep_state: bool = False,
        strict: bool = True,
        n_real: int | None = None,
        trace: Trace | None = None,
        **overrides,
    ) -> list[QueryResult | None]:
        """Answer a batch of queries, amortizing graph residency and kernel
        launches (the paper's 100-query workloads).

        Queries are bucketed by keyword count ``m`` (the table shape is
        ``[V, 2^m, K]``, so only same-``m`` queries share an executable);
        each bucket rides the fused lane driver as ONE device program —
        on both partitionings.  On partition="sharded" the lanes live
        inside the ``shard_map`` body, so the whole bucket shares a
        single frontier exchange per superstep instead of degrading to
        sequential single-query runs.  Results come back in input order;
        ``wall_time_s`` is the shared bucket device time, and
        ``own_time_s`` is None inside a bucket (lanes advance in
        lockstep — there is no honest per-query time to report).

        ``n_real``: serving hook — queries at index >= ``n_real`` are
        padding lanes (added by a serving layer to stabilize the lane
        count the driver compiles for).  They still ride in their
        bucket's device program, but skip host-side result construction
        (answer-tree extraction is O(V·2^m) per lane) and come back as
        None.

        Answer-tree extraction for the whole bucket runs through the
        device-batched backtracer (:mod:`repro.answers`): one device
        program resolves the top-candidate decompositions of every real
        lane at once, and only ragged stragglers re-run the host search —
        bit-identical results, batched cost.

        ``trace``: a :class:`repro.obs.Trace` to record each bucket's
        spans on — ``masks``, ``device_dispatch`` (``lanes``,
        ``compiled``; ``gather_chunks`` and ``set_chunks`` on the pallas
        path; the same two clock reads as ``wall_time_s``) and
        ``extract`` with its children ``backtrace`` (the device program
        and its readback), ``trees`` (host tree collection;
        ``device_resolved``, ``host_fallbacks``) and ``results`` (per-lane
        result construction).  None runs untraced.
        """
        n_real = len(queries) if n_real is None else n_real
        results: list[QueryResult | None] = [None] * len(queries)
        buckets: dict[int, list[int]] = {}
        for i, q in enumerate(queries):
            buckets.setdefault(len(q), []).append(i)
        for m, idxs in sorted(buckets.items()):
            cfg = self._config(m, k, **overrides)
            with timed_span(trace, "masks"):
                pairs = [self._masks(list(queries[i]), strict) for i in idxs]
                masks = np.stack([p[0] for p in pairs])
            traces_before = self._traces(cfg, "fused")
            with timed_span(trace, "device_dispatch", lanes=len(idxs),
                            **self._chunk_plan(cfg)) as dispatch:
                states, telemetry = self._run_fused(cfg, masks)
                dispatch.set(
                    compiled=self._traces(cfg, "fused") > traces_before)
            dt = dispatch.t_end - dispatch.t_start
            with timed_span(trace, "extract"):
                pre: dict[int, tuple] = {}
                if extract and self.batched_extraction:
                    pre = self._extract_lanes(
                        trace, states, masks, idxs, n_real,
                        max(cfg.k, extract_pool or 0))
                with timed_span(trace, "results"):
                    for bi, i in enumerate(idxs):
                        if i >= n_real:
                            continue
                        results[i] = self._make_result(
                            list(queries[i]), masks[bi],
                            lane_view(states, bi), cfg, dt, extract,
                            keep_state, unmatched=pairs[bi][1],
                            extract_pool=extract_pool,
                            answers_pre=pre.get(bi), telemetry=telemetry)
        return results  # type: ignore[return-value]

    def _extract_lanes(self, trace: Trace | None, states: DKSState,
                       masks: np.ndarray, idxs: list[int], n_real: int,
                       k: int) -> dict[int, tuple]:
        """Answer trees of a bucket's real lanes with a finite answer, as
        ``{lane: (ranked, exhausted)}``: the batched device backtrace
        (``backtrace`` span), then the host collection (``trees``)."""
        bt = self._backtracer()
        with timed_span(trace, "backtrace"):
            topk = np.asarray(states.topk_w)
            lanes = [bi for bi in range(len(idxs))
                     if idxs[bi] < n_real and topk[bi, 0] < INF]
            S_lanes = states.S
            if lanes and self.mesh is not None:
                # Sharded runs leave S device-distributed; the backtrace
                # kernel is a plain single-device jit.
                S_lanes = np.asarray(S_lanes)
            batch = (bt.backtrace_lanes(S_lanes, masks, k) if lanes
                     else None)
        before = bt.stats()
        with timed_span(trace, "trees") as trees:
            out = {}
            if lanes:
                out = dict(zip(lanes, bt.extract_lanes(
                    S_lanes, masks, k=k, lanes=lanes, n_nodes=self.n_nodes,
                    batch=batch)))
            trees.set(**{name: n - before[name]
                         for name, n in bt.stats().items()})
        return out

    def query_stream(
        self,
        keywords: Sequence,
        k: int = 1,
        *,
        strict: bool = True,
        **overrides,
    ) -> Iterator[StreamUpdate]:
        """Yield per-superstep approximate answers with sound bounds.

        Every update carries the current top-k weights plus
        ``opt_lower_bound`` — the running max over supersteps of
        ``min(best_t, spa_t)`` and ``min(best_t, nu_full_t)``.  Any answer
        either appears by superstep ``t`` (weight >= ``best_t``) or later
        (weight >= the ``spa``/``nu`` bound at ``t``), so the optimum is
        >= every per-step ``min`` and hence >= their running max (``nu`` is
        provably sound; ``spa`` is the paper's Sec. 5.4 estimator).  The
        reported ``spa_ratio`` therefore never worsens as supersteps
        progress, and reaches 0 once the best answer cannot be improved per
        the bound (paper Fig. 12 convention).
        """
        keywords = list(keywords)
        cfg = self._config(len(keywords), k, **overrides)
        # Validate eagerly (this function is not a generator): strict-mode
        # KeyError fires at the call site, not at first iteration.
        masks, unmatched = self._masks(keywords, strict)

        def updates() -> Iterator[StreamUpdate]:
            for _state, update in self._stream(cfg, masks,
                                               unmatched=unmatched):
                yield update

        return updates()

    def query_streamed(
        self,
        keywords: Sequence,
        k: int = 1,
        *,
        on_update: Callable[[StreamUpdate], None] | None = None,
        until: Callable[[StreamUpdate], bool] | None = None,
        extract: bool = True,
        keep_state: bool = False,
        strict: bool = True,
        **overrides,
    ) -> QueryResult:
        """Run a streaming query to completion and return its result.

        Like :meth:`query_stream` but consumes the stream internally
        (invoking ``on_update`` per superstep) and builds the final
        :class:`QueryResult` from the last state — the run is not repeated.

        ``until``: optional host-side stop predicate evaluated on every
        update (after ``on_update``).  When it fires before the run's own
        exit criterion, the stream stops and the result is built from the
        best-so-far state *as a forced stop*: ``done=False`` and the SPA
        bound / ratio are computed exactly as for ``budget_hit`` — the
        paper's early-termination guarantee (Sec. 5.4) as a serving
        primitive (deadline-bounded answers route through this).
        """
        keywords = list(keywords)
        cfg = self._config(len(keywords), k, **overrides)
        masks, unmatched = self._masks(keywords, strict)
        t0 = time.perf_counter()
        state = None
        interrupted = False
        for state, update in self._stream(cfg, masks, unmatched=unmatched):
            if on_update is not None:
                on_update(update)
            if until is not None and not update.done and until(update):
                interrupted = True
                break
        dt = time.perf_counter() - t0
        assert state is not None
        return self._make_result(keywords, masks, state, cfg, dt, extract,
                                 keep_state, unmatched=unmatched,
                                 own_time_s=dt, interrupted=interrupted)

    def query_deadline(
        self,
        keywords: Sequence,
        k: int = 1,
        *,
        deadline_s: float,
        extract: bool = True,
        extract_pool: int | None = None,
        keep_state: bool = False,
        strict: bool = True,
        **overrides,
    ) -> tuple[QueryResult, dict[str, Any]]:
        """Serving hook: run under a wall-clock budget, bounds computed
        once at the end.

        Steps the streaming executor with a wall-clock check between
        supersteps but WITHOUT the per-superstep SPA/nu computation of
        :meth:`query_stream` — the cover DP is a host-driven O(3^m) loop
        of tiny dispatches that can cost many times a superstep, so under
        a tight budget it would eat the very time it is meant to bound.
        The lower bounds are computed once, from the final state (each
        per-step bound is individually valid, so the final one is too —
        it just isn't the running max a full stream would report).

        Returns ``(result, info)`` with ``info`` carrying
        ``opt_lower_bound`` (paper Sec. 5.4 reporting convention: max of
        min(best, spa) and min(best, nu), folded with the sound facts so
        it is never below the sound bound), ``sound_opt_lower_bound``
        (the provably sound part), and ``interrupted`` (True when the
        deadline expired before the run's own exit criterion).  On a
        proven exit both bounds equal the certified best answer and the
        cover DP is skipped entirely.

        The 1-lane case of :meth:`query_deadline_batch`.
        """
        out = self.query_deadline_batch(
            [list(keywords)], k, deadline_s=deadline_s, extract=extract,
            extract_pool=extract_pool, keep_state=keep_state, strict=strict,
            **overrides)
        assert out[0] is not None
        return out[0]

    def query_deadline_batch(
        self,
        queries: Sequence[Sequence],
        k: int = 1,
        *,
        deadline_s: float,
        extract: bool = True,
        extract_pool: int | None = None,
        keep_state: bool = False,
        strict: bool = True,
        n_real: int | None = None,
        trace: Trace | None = None,
        **overrides,
    ) -> list[tuple[QueryResult, dict[str, Any]] | None]:
        """Serve a BUCKET of same-shape queries under one shared wall-clock
        budget, riding a single lane driver.

        All queries must share the keyword count ``m`` (they share one
        compiled driver — the serving layer's shape buckets guarantee
        this).  The driver steps every lane together; a lane whose exit
        criterion fires freezes individually (its counters and answer
        stop with it) while the driver keeps stepping the rest.  When the
        budget expires, every still-running lane is interrupted at the
        same superstep and gets its own best-so-far answer with
        *per-lane* bounds — the paper's early-termination guarantee
        (Sec. 5.4), amortized over concurrent requests: N same-budget
        queries cost ~max supersteps instead of the sum.

        Returns one ``(result, info)`` per query (input order), with
        ``info`` as in :meth:`query_deadline` plus ``driver_supersteps``
        (the shared driver's step count — compare against the sum of
        per-lane ``result.supersteps`` to see the sharing win).
        ``result.own_time_s`` is the lane's own serve time: the wall
        clock when its exit was observed, or the full bucket time if it
        ran to the deadline.  ``n_real``: as in :meth:`query_batch`,
        queries at index >= ``n_real`` are padding lanes and come back as
        None.

        Tree extraction *overlaps* the driver: a lane that freezes has a
        final table, so its host-side reconstruction starts on a worker
        thread immediately (:class:`repro.answers.ExtractionOverlap`)
        while the device steps the remaining lanes — by loop exit most
        trees already exist.  Interrupted lanes extract best-so-far trees
        from their frozen state at the deadline, alongside their bounds.

        ``trace``: as in :meth:`query_batch` — ``masks``,
        ``device_dispatch`` (the whole stepped loop; ``lanes``,
        ``compiled``, ``driver_supersteps``, ``gather_chunks``,
        ``set_chunks``) and
        ``extract`` with ``trees`` (collecting the overlapped and inline
        extractions; ``overlapped``, ``inline``) and ``results``.
        """
        queries = [list(q) for q in queries]
        if not queries:
            return []
        ms = {len(q) for q in queries}
        if len(ms) != 1:
            raise ValueError(
                f"a deadline bucket shares one driver: all queries must "
                f"have the same keyword count (got m={sorted(ms)})")
        n_real = len(queries) if n_real is None else n_real
        cfg = self._config(ms.pop(), k, **overrides)
        with timed_span(trace, "masks"):
            pairs = [self._masks(q, strict) for q in queries]
            masks = np.stack([p[0] for p in pairs])
        init_fn, step_fn = self._executable(cfg, "stepwise")
        overlap = None
        if extract:
            from repro.answers import ExtractionOverlap
            overlap = ExtractionOverlap(
                self.graph, max(cfg.k, extract_pool or 0))
        traces_before = self._traces(cfg, "stepwise")
        with timed_span(trace, "device_dispatch", lanes=len(queries),
                        **self._chunk_plan(cfg)) as dispatch:
            t0 = dispatch.t_start
            deadline_t = t0 + max(deadline_s, 0.0)
            state = self._execute(init_fn, jnp.asarray(masks))
            own_t: list[float | None] = [None] * len(queries)
            driver_steps = 0
            while True:
                done = np.asarray(state.done)
                now = time.perf_counter()
                for i in range(n_real):
                    if done[i] and own_t[i] is None:
                        # The lane proved its exit here: that is ITS serve
                        # time, even while the driver keeps stepping others.
                        own_t[i] = now - t0
                        if overlap is not None and \
                                float(np.asarray(state.topk_w[i, 0])) < INF:
                            # Frozen lane => final table: reconstruct its
                            # trees NOW, under the remaining supersteps.
                            overlap.submit(i, state.S[i],
                                           masks[i][:, : self.n_nodes])
                if done[:n_real].all() or now >= deadline_t:
                    break
                state = self._execute(step_fn, state)
                driver_steps += 1
            dispatch.set(
                compiled=self._traces(cfg, "stepwise") > traces_before,
                driver_supersteps=driver_steps)
        dt = dispatch.t_end - dispatch.t_start
        out: list[tuple[QueryResult, dict[str, Any]] | None] = []
        with timed_span(trace, "extract"):
            pre: dict[int, tuple] = {}
            if overlap is not None:
                with timed_span(trace, "trees") as trees:
                    # Overlapped results for frozen lanes; inline
                    # best-so-far extraction for lanes the deadline
                    # interrupted.
                    topk = np.asarray(state.topk_w)
                    for i in range(n_real):
                        if topk[i, 0] < INF:
                            pre[i] = (overlap.result(i) if overlap.pending(i)
                                      else overlap.result(
                                          i, state.S[i],
                                          masks[i][:, : self.n_nodes]))
                    overlap.close()
                    trees.set(**overlap.stats())
            with timed_span(trace, "results"):
                for i, q in enumerate(queries):
                    if i >= n_real:
                        out.append(None)
                        continue
                    lane = lane_view(state, i)
                    interrupted = not bool(lane.done)
                    forced = bool(lane.budget_hit) or bool(lane.capped)
                    if interrupted or forced:
                        bounds = self._state_bounds(lane, cfg)
                        spa = bounds.spa
                        sound_lb = bounds.sound_lb
                        # Reported bound folds in the sound facts, so it
                        # can never sit below the guarantee it
                        # accompanies.
                        opt_lb = max(bounds.opt_lb, sound_lb)
                    else:
                        # Proven exit: the run certified its best answer
                        # — that IS the bound, and the O(3^m) cover DP is
                        # dead weight.
                        spa = None
                        opt_lb = sound_lb = min(float(lane.topk_w[0]), INF)
                    res = self._make_result(
                        q, masks[i], lane, cfg, dt, extract, keep_state,
                        unmatched=pairs[i][1],
                        own_time_s=own_t[i] if own_t[i] is not None else dt,
                        interrupted=interrupted, spa_hint=spa,
                        extract_pool=extract_pool, answers_pre=pre.get(i))
                    info = dict(
                        opt_lower_bound=min(opt_lb, INF),
                        sound_opt_lower_bound=min(sound_lb, INF),
                        interrupted=interrupted,
                        driver_supersteps=driver_steps,
                    )
                    out.append((res, info))
        if overlap is not None:
            # Bucket-wide extraction split (how many tree reconstructions
            # hid behind device supersteps) — shared by every lane's info,
            # like driver_supersteps.
            ext = overlap.stats()
            for pair in out:
                if pair is not None:
                    pair[1]["extraction"] = ext
        return out

    def _state_bounds(self, state: DKSState, cfg: DKSConfig):
        """One state's lower-bound facts, shared by the stream and
        deadline paths.

        ``opt_lb`` is the paper's reported bound — max of min(best, spa)
        and min(best, nu) — where ``nu`` is provably a lower bound on any
        future newly-appearing full-set value and ``spa`` is the Sec. 5.4
        estimator.  ``sound_lb`` keeps only the provable facts: the ``nu``
        component, plus ``best`` itself when an empty frontier (or an exit
        that is neither the budget nor the superstep cap) proves no future
        superstep changes anything.  Each is a valid bound on its own; a
        stream takes their running max across supersteps.
        """
        best = float(state.topk_w[0])
        nu = nu_lower_bound(state.g, jnp.float32(self._e_min), cfg.m)
        nu_full = float(nu[cfg.full])
        shat = jnp.minimum(state.s_front + self._e_min, INF)
        spa = float(spa_cover_dp(shat, cfg.m))
        frontier = int(jnp.sum(state.changed))
        opt_lb = max(min(best, spa), min(best, nu_full))
        sound_lb = min(best, nu_full)
        forced = bool(state.budget_hit) or bool(state.capped)
        if frontier == 0 or (bool(state.done) and not forced):
            sound_lb = max(sound_lb, best)
        return _StateBounds(best=best, nu_full=nu_full, spa=spa,
                            frontier=frontier, opt_lb=min(opt_lb, INF),
                            sound_lb=min(sound_lb, INF))

    def _stream(self, cfg: DKSConfig, masks: np.ndarray,
                unmatched: tuple = ()):
        """(state, StreamUpdate) pairs, one per superstep (incl. init) —
        a host loop over the 1-lane stepwise driver.  Yields un-batched
        lane views, so result construction stays lane-free."""
        init_fn, step_fn = self._executable(cfg, "stepwise")
        states = self._execute(init_fn, jnp.asarray(masks[None]))
        opt_lb = 0.0
        sound_lb = 0.0
        while True:
            state = lane_view(states, 0)
            bounds = self._state_bounds(state, cfg)
            best = bounds.best
            done = bool(state.done)
            opt_lb = max(opt_lb, bounds.opt_lb)
            sound_lb = max(sound_lb, bounds.sound_lb)
            if best >= INF:
                ratio = float("inf")
            elif best <= opt_lb or opt_lb >= INF:
                ratio = 0.0
            else:
                ratio = best / opt_lb if opt_lb > 0 else float("inf")
            yield state, StreamUpdate(
                step=int(state.step),
                weights=np.asarray(state.topk_w),
                roots=np.asarray(state.topk_root),
                frontier=bounds.frontier,
                msgs_bfs=float(state.msgs_bfs),
                msgs_deep=float(state.msgs_deep),
                nu_full=bounds.nu_full,
                spa=bounds.spa,
                opt_lower_bound=opt_lb,
                sound_opt_lower_bound=sound_lb,
                spa_ratio=ratio,
                done=done,
                unmatched=tuple(unmatched),
            )
            if done or int(state.step) >= cfg.max_supersteps:
                return
            states = self._execute(step_fn, states)

    def query_instrumented(
        self,
        keywords: Sequence,
        k: int = 1,
        *,
        exit_hook: Callable[[DKSState], bool] | None = None,
        extract: bool = True,
        keep_state: bool = False,
        strict: bool = True,
        **overrides,
    ) -> tuple[QueryResult, dict[str, Any]]:
        """Host-driven run with per-phase wall times (paper Table 1) and an
        optional host-side exit criterion (e.g. ``fagin.paper_exit_hook``).

        Works on both partitionings.  On partition="sharded" the frontier
        exchange and edge relax are fused inside one shard_map, so the
        "send_bfs" bucket covers both (see
        :func:`repro.core.dks_sharded.run_dks_frontier_instrumented` for
        the exact attribution)."""
        if self.policy.partition == "sharded":
            from repro.core.dks_sharded import run_dks_frontier_instrumented
            run_fn = run_dks_frontier_instrumented
        else:
            run_fn = run_dks_instrumented
        keywords = list(keywords)
        cfg = self._config(len(keywords), k, **overrides)
        masks, unmatched = self._masks(keywords, strict)
        t0 = time.perf_counter()
        with self._mesh_context():
            state, info = run_fn(
                self.device_graph, jnp.asarray(masks), cfg,
                exit_hook=exit_hook)
        dt = time.perf_counter() - t0
        res = self._make_result(keywords, masks, state, cfg, dt, extract,
                                keep_state, unmatched=unmatched,
                                own_time_s=dt,
                                telemetry=info.get("telemetry"))
        return res, info

    # ------------------------------------------------------------------
    # Internals
    # ------------------------------------------------------------------

    def _mesh_context(self):
        """Ambient-mesh scope for sharded execution.

        The sharded executors take their mesh *explicitly* (it rides on
        :class:`FrontierGraph`), so this scope is not load-bearing for
        correctness — it is kept so any auto-sharded ops around the
        shard_map (and user callbacks) see the engine's mesh, on every jax
        generation (:func:`repro.shardmap.mesh_scope`).
        """
        return shardmap.mesh_scope(self.mesh)

    def _execute(self, fn, x):
        """Run a compiled executor on the engine's device graph and lane
        layout under the engine's mesh (if any), and block until the
        result is materialized.  ``x``: the masks or the state."""
        self._execute_count += 1
        with self._mesh_context():
            return jax.block_until_ready(
                fn(self.device_graph, self.lane_csr, x))

    def _chunk_plan(self, cfg: DKSConfig) -> dict[str, int]:
        """``{"gather_chunks": n, "set_chunks": c}``: the slot groups per
        lane of the fused pallas superstep's candidate gather, and the
        keyword-set chunks per row block of its kernel, for this shape
        (static per program); empty off that path."""
        csr = self.lane_csr
        if csr is None or cfg.relax_impl != "pallas":
            return {}
        from repro.kernels.lane_superstep import gather_chunks, set_chunks
        return {"gather_chunks": gather_chunks(csr.dmax, csr.n_rows,
                                               cfg.n_sets * cfg.k),
                "set_chunks": set_chunks(cfg.n_sets, csr.dmax * cfg.k,
                                         csr.block_v)}

    def _run_fused(self, cfg: DKSConfig, masks: np.ndarray):
        """One fused-driver dispatch over lane-batched masks.  Returns
        ``(final states, telemetry)`` where telemetry is the decoded
        :class:`~repro.obs.SuperstepTelemetry` under
        ``ExecutionPolicy(telemetry=True)`` and None otherwise — the
        state trajectory is identical either way (the telemetry carry
        only reads the state)."""
        fn = self._executable(cfg, "fused")
        if not self.policy.telemetry:
            return self._execute(fn, jnp.asarray(masks)), None
        states, buf, steps = self._execute(fn, jnp.asarray(masks))
        telemetry = SuperstepTelemetry.from_buffer(np.asarray(buf),
                                                   int(steps))
        return states, telemetry

    def _config(self, m: int, k: int, **overrides) -> DKSConfig:
        if m < 1:
            raise ValueError("a query needs at least one keyword")
        policy = self.policy
        if overrides:
            self._check_overrides(overrides)
            policy = dataclasses.replace(policy, **overrides)
        return policy.dks_config(m, k)

    def _masks(self, keywords: list,
               strict: bool = True) -> tuple[np.ndarray, tuple]:
        """(masks, unmatched tokens).  ``strict`` raises on unmatched —
        and then guarantees ``unmatched == ()``, so the scan for them only
        runs in best-effort mode."""
        masks = self.index.keyword_masks(
            keywords, self.n_nodes, v_pad=self.v_pad,
            on_missing="raise" if strict else "ignore")
        unmatched = () if strict else tuple(
            self.index.missing_tokens(keywords))
        return masks, unmatched

    def _executable(self, cfg: DKSConfig, kind: str):
        """Fetch-or-compile the executor for a query shape.

        The four executor kinds of the pre-driver engine (single-query
        while-loop, vmapped batch, host-stepped stream, sequential
        sharded fallback) collapse to the lane driver plus a loop policy:

        - "fused": the whole driver as one jitted while-loop over the
          lane axis (``query`` runs it with 1 lane, ``query_batch`` with
          a bucket of lanes; on either partitioning it is ONE device
          execution per call);
        - "stepwise": the ``(init, superstep)`` pair of the same kernel,
          for surfaces that need host control between supersteps
          (streaming, deadline buckets).

        The trace counter increments at trace time only, so a cache hit
        leaves it untouched — that is the no-re-trace guarantee tests
        assert.  (jit itself re-traces per lane count, as for any new
        input shape; a serving layer pads buckets to keep the lane-count
        alphabet small.)
        """
        kind = self._resolve_kind(kind)
        key = (cfg, self.policy.partition, kind)
        fn = self._executables.get(key)
        if fn is not None:
            return fn

        # Every executor takes the graph and the fused pallas layout
        # (None on jnp/sharded engines) as arguments: closed over, they
        # would be embedded as constants in each compiled program.
        if kind == "fused":
            def _run(graph, csr, masks):
                self._trace_counts[key] = self._trace_counts.get(key, 0) + 1
                state = lane_init(graph, masks, cfg)
                return jax.lax.while_loop(
                    lambda st: ~jnp.all(st.done),
                    lambda st: lane_superstep(graph, st, cfg, csr=csr),
                    state)

            fn = jax.jit(_run)
        elif kind == "fused-telemetry":
            # Same loop, same kernel, plus the bounded counter-buffer
            # carry (repro.core.driver.run_lanes_telemetry).
            def _run_tel(graph, csr, masks):
                self._trace_counts[key] = self._trace_counts.get(key, 0) + 1
                return run_lanes_telemetry(graph, masks, cfg, csr=csr)

            fn = jax.jit(_run_tel)
        elif kind == "stepwise":
            def _init(graph, csr, masks):
                self._trace_counts[key] = self._trace_counts.get(key, 0) + 1
                return lane_init(graph, masks, cfg)

            def _step(graph, csr, st):
                self._trace_counts[key] = self._trace_counts.get(key, 0) + 1
                return lane_superstep(graph, st, cfg, csr=csr)

            # A cached stepwise pair counts 2 traces (init + superstep).
            fn = (jax.jit(_init), jax.jit(_step))
        else:
            raise ValueError(f"unknown executable kind {kind!r}")
        self._executables[key] = fn
        return fn

    def _make_result(
        self,
        keywords: list,
        masks: np.ndarray,
        state: DKSState,
        cfg: DKSConfig,
        wall_time_s: float,
        extract: bool,
        keep_state: bool = False,
        unmatched: tuple = (),
        own_time_s: float | None = None,
        interrupted: bool = False,
        spa_hint: float | None = None,
        extract_pool: int | None = None,
        answers_pre: tuple | None = None,
        telemetry: SuperstepTelemetry | None = None,
    ) -> QueryResult:
        weights = np.asarray(state.topk_w)
        roots = np.asarray(state.topk_root)
        budget_hit = bool(state.budget_hit)
        capped = bool(state.capped)
        # The SPA cover DP (a host-driven O(3^m) loop of tiny device ops)
        # only informs the ratio on forced early exit (budget, superstep
        # cap, or a deadline-interrupted run) — skip it on proven exits,
        # and reuse ``spa_hint`` when the caller already computed it on
        # this very state (query_deadline does).
        spa = None
        ratio = 0.0
        if budget_hit or capped or interrupted:
            if spa_hint is not None:
                spa = spa_hint
            else:
                shat = jnp.minimum(state.s_front + self._e_min, INF)
                spa = float(spa_cover_dp(shat, cfg.m))
            ratio = float(spa_ratio(state.topk_w[0], spa))
        # Tree extraction: ``answers_pre`` is a ready-made
        # ``(ranked, exhausted)`` pair from the device-batched backtracer
        # (query_batch) or the extraction overlap (deadline buckets); the
        # inline host collector covers the rest.  ``extract_pool`` widens
        # the collection target so ``answer_pool`` carries material for
        # diversified re-ranking, with ``answers`` staying its top-k.
        answers: list = []
        answers_exhausted = pool_exhausted = False
        answer_pool = None
        if extract and weights[0] < INF:
            if answers_pre is not None:
                ranked, exhausted = answers_pre
            else:
                ranked, exhausted = collect_answers(
                    np.asarray(state.S), self.graph,
                    masks[:, : self.n_nodes],
                    k=max(cfg.k, extract_pool or 0))
            answers = ranked[: cfg.k]
            answers_exhausted = len(ranked) < cfg.k
            if extract_pool:
                answer_pool = ranked
                pool_exhausted = exhausted
        elif extract:
            # No finite answer => no trees exist; the empty pool is a
            # definitive (cacheable) fact, not a skipped extraction.
            answers_exhausted = True
            if extract_pool:
                answer_pool, pool_exhausted = [], True
        return QueryResult(
            query=tuple(keywords),
            m=cfg.m,
            k=cfg.k,
            answers=answers,
            weights=weights,
            roots=roots,
            kw_nodes=int(masks.sum()),
            supersteps=int(state.step),
            msgs_bfs=float(state.msgs_bfs),
            msgs_deep=float(state.msgs_deep),
            explored_frac=float(jnp.mean(state.visited[: self.n_nodes])),
            done=bool(state.done),
            budget_hit=budget_hit,
            capped=capped,
            spa=spa,
            spa_ratio=ratio,
            wall_time_s=wall_time_s,
            state=state if keep_state else None,
            unmatched=tuple(unmatched),
            own_time_s=own_time_s,
            answers_exhausted=answers_exhausted,
            answer_pool=answer_pool,
            pool_exhausted=pool_exhausted,
            telemetry=telemetry,
        )
