"""Kernel micro-benchmarks (CPU wall-time for the jnp paths; the Pallas
variants are validated in interpret mode and their TPU characteristics are
derived structurally in EXPERIMENTS.md §Roofline).

Reported as name,us_per_call,derived rows for benchmarks.run."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import INF
from repro.core import semiring
from repro.core.dks import DKSConfig, combine
from repro.core.spa import split_pairs


def _time(fn, *args, iters=5):
    out = jax.block_until_ready(fn(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6, out


def random_table(v, m, k, seed=0):
    rng = np.random.default_rng(seed)
    s = rng.integers(1, 30, size=(v, 1 << m, k)).astype(np.float32)
    s[rng.random(s.shape) > 0.5] = INF
    s = np.array(semiring.sorted_unique_k(jnp.asarray(s), k))
    s[:, 0, :] = INF
    return jnp.asarray(s)


def bench_subset_combine(v=20_000, m=4, k=2):
    """Batched-pass jnp combine vs sequential-scan variant (the kernel's
    single-pass schedule, emulated) — shows the pass-count tradeoff."""
    s = random_table(v, m, k)
    cfg_batched = DKSConfig(m=m, k=k, combine_impl="jnp")

    us_batched, out_b = _time(
        jax.jit(lambda x: combine(x, cfg_batched)), s)

    # Sequential scan over pairs (one pass, k-round merge per pair).
    pairs = split_pairs(m)
    t_ids = jnp.asarray([p[0] for p in pairs])
    a_ids = jnp.asarray([p[1] for p in pairs])
    b_ids = jnp.asarray([p[2] for p in pairs])

    @jax.jit
    def sequential(s):
        def body(s, tab):
            t, a, b = tab
            cand = semiring.outer_combine(s[:, a, :], s[:, b, :])
            merged = semiring.topk_merge(
                jax.lax.dynamic_index_in_dim(s, t, 1, keepdims=False), cand)
            return jax.lax.dynamic_update_index_in_dim(
                s, merged, t, 1), None
        s, _ = jax.lax.scan(body, s, (t_ids, a_ids, b_ids))
        return s

    us_seq, out_s = _time(sequential, s)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_s),
                               atol=1e-4)
    return [
        {"name": f"subset_combine_batched_v{v}_m{m}_k{k}",
         "us_per_call": round(us_batched, 1),
         "derived": f"passes={cfg_batched.n_combine_passes()}"},
        {"name": f"subset_combine_sequential_v{v}_m{m}_k{k}",
         "us_per_call": round(us_seq, 1),
         "derived": f"pairs={len(pairs)}"},
    ]


def bench_segment_topk(e=200_000, v=20_000, f=16, k=2):
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.normal(size=(e, f)).astype(np.float32))
    seg = jnp.asarray(rng.integers(0, v, e).astype(np.int32))
    us, _ = _time(jax.jit(lambda x, s: semiring.segment_topk_min(x, s, v, k)),
                  vals, seg)
    return [{"name": f"segment_topk_e{e}_v{v}_f{f}_k{k}",
             "us_per_call": round(us, 1),
             "derived": f"rounds={k}"}]


def fig_lane_kernel(v=800, e=3200, m=3, k=2, lane_counts=(1, 4, 8)):
    """The fused pallas lane-superstep kernel vs the vmapped jnp
    superstep chain: per-superstep wall time at several lane counts,
    parity-checked bit-identically at every point.

    The timed unit is ONE jitted ``lane_superstep`` call — the body both
    the fused while-loop and the stepwise drivers repeat — so the ratio
    is the whole-query ratio minus host overhead.  On CPU the kernel
    runs in interpret mode (``interpret=True`` in the result): those
    wall times measure the emulation, not the kernel — the row is a
    trend/parity record there, and a device measurement on TPU/GPU.
    Structural economy is measured either way: ``jaxpr_eqns`` counts
    equations in each path's jaxpr and ``pallas_calls`` asserts the
    fused path is exactly one launch."""
    from repro.core.driver import lane_init, lane_superstep
    from repro.engine import ExecutionPolicy, QueryEngine
    from repro.graph.generators import lod_like_graph
    from repro.graph.index import InvertedIndex, mid_df_tokens
    from repro.kernels import interpret_mode

    g, tokens = lod_like_graph(v, e, seed=0, vocab=60, tau=1001)
    index = InvertedIndex.from_token_matrix(tokens)
    ej = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
        backend="jnp", max_supersteps=16))
    ep = QueryEngine.build(g, index=index, policy=ExecutionPolicy(
        backend="pallas", max_supersteps=16))
    cfg_j = ej.policy.dks_config(m, k)
    cfg_p = ep.policy.dks_config(m, k)
    mid = mid_df_tokens(index)
    queries = [list(mid[i:i + m]) for i in range(max(lane_counts))]

    def all_eqns(jaxpr):
        out = list(jaxpr.eqns)
        for eq in jaxpr.eqns:
            for p in eq.params.values():
                inner = getattr(p, "jaxpr", None)
                if inner is not None:
                    out += all_eqns(getattr(inner, "jaxpr", inner))
        return out

    step_j = jax.jit(lambda s: lane_superstep(ej.device_graph, s, cfg_j))
    step_p = jax.jit(lambda s: lane_superstep(
        ep.device_graph, s, cfg_p, csr=ep.lane_csr))

    rows = []
    jaxpr_eqns = pallas_calls = None
    for lanes in lane_counts:
        masks = jnp.asarray(np.stack(
            [ej._masks(q)[0] for q in queries[:lanes]]))
        st = lane_init(ej.device_graph, masks, cfg_j)
        if jaxpr_eqns is None:
            ej_eqns = all_eqns(jax.make_jaxpr(step_j)(st).jaxpr)
            ep_eqns = all_eqns(jax.make_jaxpr(step_p)(st).jaxpr)
            pallas_calls = sum(1 for q in ep_eqns
                               if q.primitive.name == "pallas_call")
            assert pallas_calls == 1, pallas_calls
            jaxpr_eqns = {"jnp": len(ej_eqns), "pallas": len(ep_eqns)}
        us_j, out_j = _time(step_j, st)
        us_p, out_p = _time(step_p, st)
        if not np.array_equal(np.asarray(out_j.S), np.asarray(out_p.S)):
            raise AssertionError(f"kernel parity broke at lanes={lanes}")
        rows.append({
            "lanes": lanes,
            "jnp_us_per_step": round(us_j, 1),
            "pallas_us_per_step": round(us_p, 1),
            "speedup": round(us_j / us_p, 3) if us_p else None,
            "parity": "bit-identical",
        })
    return {
        "graph": {"v": v, "e": e, "m": m, "k": k},
        "interpret": interpret_mode(),
        "jaxpr_eqns": jaxpr_eqns,
        "pallas_calls_per_superstep": pallas_calls,
        "rows": rows,
    }


def bench_attention(b=1, s=512, h=8, dh=64):
    from repro.models.attention import attention
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    kv = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    us_naive, o1 = _time(
        jax.jit(lambda q, k, v: attention(q, k, v, impl="naive")), q, kv, kv)
    us_c32, o2 = _time(
        jax.jit(lambda q, k, v: attention(q, k, v, impl="chunked_f32",
                                          block=128)), q, kv, kv)
    us_cbf, o3 = _time(
        jax.jit(lambda q, k, v: attention(q, k, v, impl="chunked",
                                          block=128)), q, kv, kv)
    us_fl, o4 = _time(
        jax.jit(lambda q, k, v: attention(q, k, v, impl="flash_jax",
                                          block=128)), q, kv, kv)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-3)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o3), atol=3e-2)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o4), atol=3e-2)
    return [
        {"name": f"attention_naive_s{s}", "us_per_call": round(us_naive, 1),
         "derived": "materialized SxS"},
        {"name": f"attention_chunked_f32_s{s}", "us_per_call": round(us_c32, 1),
         "derived": "online softmax f32"},
        {"name": f"attention_chunked_bf16_s{s}", "us_per_call": round(us_cbf, 1),
         "derived": "online softmax bf16 scores"},
        {"name": f"attention_flash_jax_s{s}", "us_per_call": round(us_fl, 1),
         "derived": "custom VJP"},
    ]
