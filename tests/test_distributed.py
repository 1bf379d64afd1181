"""Distribution tests requiring >1 device: run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (jax locks the device
count at first init, so the main pytest process stays single-device).

All mesh/shard_map plumbing goes through :mod:`repro.shardmap`.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_in_subprocess(body: str, devices: int = 8) -> dict:
    prog = textwrap.dedent(f"""
        import os, json
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import jax
        import jax.numpy as jnp
        import numpy as np
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("RESULT::" + json.dumps(out))
    """)
    res = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, f"stderr:\n{res.stderr[-4000:]}"
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT::")][-1]
    return json.loads(line.split("RESULT::", 1)[1])


def test_int8_ring_allreduce_with_error_feedback():
    out = run_in_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro import shardmap
        from repro.distributed import compressed_allreduce, init_compression
        mesh = shardmap.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        # Distinct per-device gradients: feed the function a sharded array
        # whose shards differ.
        g_global = rng.normal(size=(8, 64)).astype(np.float32)
        expect = g_global.mean(axis=0)
        sh = jax.sharding.NamedSharding(mesh, P("data", None))
        g = jax.device_put(g_global, sh)
        grads = {"w": g}
        state = init_compression(grads)

        # shard_map consumes the leading axis as the per-device shard.
        import repro.distributed.compression as comp
        def leaf(gl, el):
            x = gl.reshape(-1) + el.reshape(-1)
            pad = (-x.shape[0]) % 8
            xp = jnp.pad(x, (0, pad))
            red = comp._ring_allreduce_int8(xp, "data", 8)[: x.shape[0]]
            return red.reshape(gl.shape), (x - red).reshape(gl.shape)
        f = jax.jit(shardmap.shard_map(
            leaf, mesh=mesh,
            in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None)),
            check_vma=False))
        red, err = f(g, state.error["w"])
        red_np = np.asarray(red)
        # Every device row holds the (approximate) mean.
        err_vs_mean = np.abs(red_np - expect[None, :]).max()
        # int8 quantization error bound: a few scale quanta per hop.
        scale = np.abs(g_global).max() / 127.0
        out = {"err": float(err_vs_mean), "bound": float(scale * 16),
               "resid": float(np.abs(np.asarray(err)).max())}
    """)
    assert out["err"] <= out["bound"], out
    assert out["resid"] > 0.0  # error feedback captured the lost bits


def test_dks_sharded_matches_single_device():
    """The DKS superstep loop under an 8-device mesh produces identical
    top-K weights to the single-device run (SPMD correctness)."""
    out = run_in_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro import shardmap
        from repro.core import DKSConfig, run_dks
        from repro.graph.generators import random_weighted_graph

        g = random_weighted_graph(64, 160, seed=5)
        dg = g.to_device(pad_nodes_to=64, pad_edges_to=((g.n_edges_sym+7)//8)*8)
        masks = np.zeros((3, dg.v_pad), bool)
        masks[0, 3] = masks[1, 17] = masks[2, 41] = True
        cfg = DKSConfig(m=3, k=2, max_supersteps=48)

        single = run_dks(dg, jnp.asarray(masks), cfg)

        mesh = shardmap.make_mesh((8,), ("data",))
        with shardmap.mesh_scope(mesh):
            sharded_graph = jax.device_put(
                dg, jax.tree_util.tree_map(
                    lambda _: jax.sharding.NamedSharding(mesh, P("data")),
                    dg))
            sharded = run_dks(sharded_graph, jnp.asarray(masks), cfg)
        out = {
            "single": np.asarray(single.topk_w).tolist(),
            "sharded": np.asarray(sharded.topk_w).tolist(),
            "single_steps": int(single.step),
            "sharded_steps": int(sharded.step),
        }
    """)
    assert out["single"] == out["sharded"], out
    assert out["single_steps"] == out["sharded_steps"]


def test_dks_frontier_relax_matches_dense():
    """Frontier-compressed sharded DKS == dense single-device DKS when the
    frontier cap is not hit; overflow raises budget_hit instead of silently
    dropping messages.  The mesh is explicit on the FrontierGraph — no
    ambient mesh scope is active around the sharded runs."""
    out = run_in_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro import shardmap
        from repro.core import DKSConfig, run_dks
        from repro.core.dks_sharded import (
            pack_frontier_graph, run_dks_frontier)
        from repro.graph.generators import random_weighted_graph

        g = random_weighted_graph(64, 160, seed=5)
        dg = g.to_device(pad_nodes_to=64)
        masks = np.zeros((3, 64), bool)
        masks[0, 3] = masks[1, 17] = masks[2, 41] = True
        cfg = DKSConfig(m=3, k=2, max_supersteps=48, frontier_frac=1.0)

        dense = run_dks(dg, jnp.asarray(masks), cfg)

        mesh = shardmap.make_mesh((2, 4), ("data", "model"))
        fg = pack_frontier_graph(g, n_shards=8, mesh=mesh)
        fg = jax.device_put(fg, jax.tree_util.tree_map(
            lambda _: jax.sharding.NamedSharding(
                mesh, P(("data", "model"))), fg))
        m2 = np.zeros((3, fg.v_pad), bool)
        m2[:, :64] = masks
        frontier = run_dks_frontier(fg, jnp.asarray(m2), cfg)

        # Tiny cap -> overflow -> budget_hit (paper Sec. 5.4 semantics).
        cfg_tiny = DKSConfig(m=3, k=2, max_supersteps=48,
                             frontier_frac=0.01)
        capped = run_dks_frontier(fg, jnp.asarray(m2), cfg_tiny)
        out = {
            "dense": np.asarray(dense.topk_w).tolist(),
            "frontier": np.asarray(frontier.topk_w).tolist(),
            "budget_hit": bool(capped.budget_hit),
        }
    """)
    assert out["dense"] == out["frontier"], out
    assert out["budget_hit"] is True


def test_engine_sharded_query_matches_single_device():
    """QueryEngine end-to-end on partition="sharded" (8 host devices):
    query and query_stream serve identical top-K weights to the
    single-device engine, and the executable cache holds (1 trace for any
    number of same-shape queries)."""
    out = run_in_subprocess("""
        from repro.engine import ExecutionPolicy, QueryEngine
        from repro.graph.generators import lod_like_graph
        from repro.graph.index import InvertedIndex

        g, tokens = lod_like_graph(200, 600, seed=7, vocab=60)
        index = InvertedIndex.from_token_matrix(tokens)
        toks = [t for t in sorted(index.vocabulary(), key=index.df)
                if 2 <= index.df(t) <= 40]
        q2, q3 = toks[:2], toks[2:5]

        single = QueryEngine.build(
            g, index=index, policy=ExecutionPolicy(max_supersteps=32))
        # frontier_frac=1.0: no frontier cap, so the sharded run must match
        # the dense run superstep-for-superstep (no forced stop).
        sharded = QueryEngine.build(
            g, index=index,
            policy=ExecutionPolicy(partition="sharded", max_supersteps=32,
                                   frontier_frac=1.0))

        rs2 = single.query(q2, k=2, extract=False)
        rh2 = sharded.query(q2, k=2, extract=False)
        rs3 = single.query(q3, k=2, extract=False)
        rh3 = sharded.query(q3, k=2, extract=False)

        # Streaming on the sharded path: final update == query result.
        ups = list(sharded.query_stream(q3, k=2))
        ratios = [u.spa_ratio for u in ups]

        # Same-shape query again: compiled executable must be reused.
        sharded.query(q3, k=2, extract=False)
        out = {
            "w2_single": np.asarray(rs2.weights).tolist(),
            "w2_sharded": np.asarray(rh2.weights).tolist(),
            "w3_single": np.asarray(rs3.weights).tolist(),
            "w3_sharded": np.asarray(rh3.weights).tolist(),
            "steps": [rs3.supersteps, rh3.supersteps],
            "forced": bool(rh2.budget_hit or rh3.budget_hit),
            "stream_final_w": np.asarray(ups[-1].weights).tolist(),
            "stream_done": bool(ups[-1].done),
            "ratios_monotone": all(a >= b - 1e-9
                                   for a, b in zip(ratios, ratios[1:])),
            "traces_q3": sharded.trace_count(len(q3), 2),
        }
    """)
    assert out["w2_single"] == out["w2_sharded"], out
    assert out["w3_single"] == out["w3_sharded"], out
    assert out["forced"] is False
    assert out["steps"][0] == out["steps"][1]
    assert out["stream_final_w"] == out["w3_sharded"], out
    assert out["stream_done"] is True
    assert out["ratios_monotone"] is True
    assert out["traces_q3"] == 1, out


def test_engine_sharded_frontier_overflow_budget_hit():
    """A sharded run whose per-shard frontier exceeds f_cap must finish
    with budget_hit=True and a finite SPA ratio — the paper's Sec. 5.4
    forced stop, not silent message dropping."""
    out = run_in_subprocess("""
        from repro.engine import ExecutionPolicy, QueryEngine
        from repro.graph.generators import random_weighted_graph
        from repro.graph.index import InvertedIndex

        g = random_weighted_graph(64, 320, seed=3)
        # token v%16 -> every token matches 4 nodes spread over the shards.
        tokens = (np.arange(64, dtype=np.int64) % 16).reshape(64, 1)
        index = InvertedIndex.from_token_matrix(tokens)
        engine = QueryEngine.build(
            g, index=index,
            policy=ExecutionPolicy(partition="sharded", exit_mode="none",
                                   frontier_frac=0.01, max_supersteps=48))
        # Duplicated keyword: its 4 nodes hold both keywords, so the best
        # answer (weight 0) exists from superstep 0; the growing frontier
        # then overflows the tiny per-shard cap.
        res = engine.query([3, 3], k=1, extract=False)
        out = {
            "budget_hit": bool(res.budget_hit),
            "done": bool(res.done),
            "best": float(res.weights[0]),
            "spa_ratio": float(res.spa_ratio),
            "spa_is_none": res.spa is None,
        }
    """)
    assert out["budget_hit"] is True, out
    assert out["done"] is True
    assert out["best"] < 1e9  # an answer was found despite the forced stop
    assert np.isfinite(out["spa_ratio"]), out
    assert out["spa_is_none"] is False


def test_lm_train_step_sharded_runs():
    """A reduced LM train step executes correctly under a (2,4) mesh with
    the production sharding specs (numerics, not just lowering)."""
    out = run_in_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro import shardmap
        from repro.configs import get_arch
        from repro.models import lm as lm_lib
        from repro.models import transformer as tfm
        from repro.optim import AdamWConfig
        from repro.launch.mesh import sharding_tree
        import dataclasses as dc

        cfg = get_arch("chatglm3-6b").config.smoke()
        cfg = dc.replace(cfg, d_model=64, n_heads=4, n_kv_heads=2, vocab=256)
        mesh = shardmap.make_mesh((2, 4), ("data", "model"))
        b = tfm.build(cfg, tp=4)
        with shardmap.mesh_scope(mesh):
            state = lm_lib.init_train_state(jax.random.PRNGKey(0), b)
            specs = tfm.param_specs(b)
            from repro.optim import OptState
            st_spec = lm_lib.TrainState(
                params=specs,
                opt=OptState(mu=specs, nu=specs, count=P()), step=P())
            sh = sharding_tree(mesh, st_spec)
            state = jax.device_put(state, sh)
            step = jax.jit(lm_lib.make_train_step(
                b, AdamWConfig(), attn_impl="naive"), donate_argnums=0)
            toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 256)
            batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
            losses = []
            for _ in range(3):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
        out = {"losses": losses}
    """)
    ls = out["losses"]
    assert all(np.isfinite(l) for l in ls), ls
    assert ls[-1] < ls[0], f"loss did not improve: {ls}"


import numpy as np  # noqa: E402
