"""Shared model utilities: sharding constraints, init, dtype policy."""

from __future__ import annotations

import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import shardmap

# Canonical logical axes: data-parallel dims ("pod","data"), tensor dim
# ("model").  constrain() drops axes missing from the ambient mesh, so the
# same model code runs on 1 CPU device, a 16x16 pod, or a 2x16x16 multi-pod.
DP = ("pod", "data")
TP = ("model",)
FSDP = ("pod", "data")


def _filter_axes(entry, mesh_axes: tuple[str, ...]):
    if entry is None:
        return None
    if isinstance(entry, str):
        return entry if entry in mesh_axes else None
    kept = tuple(a for a in entry if a in mesh_axes)
    return kept if kept else None


def constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint against the ambient mesh; no-op when no mesh
    is installed (unit tests / single device); axes that are Manual in the
    current scope (e.g. "pod" inside the pipeline shard_map) are dropped
    from the spec (:func:`repro.shardmap.auto_axis_names`)."""
    am = shardmap.get_abstract_mesh()
    if am is None:
        return x
    axes = shardmap.auto_axis_names(am)
    if not axes:
        return x
    clean = tuple(_filter_axes(s, axes) for s in spec)
    return jax.lax.with_sharding_constraint(x, P(*clean))


def mesh_axis_size(*names: str) -> int:
    """Product of the sizes of the given axes in the ambient mesh (1 if none)."""
    return shardmap.mesh_axis_size(shardmap.get_abstract_mesh(), *names)


def pad_to(x: int, multiple: int) -> int:
    return int(-(-x // multiple) * multiple)


def dense_init(key, shape: Sequence[int], dtype=jnp.bfloat16, scale: float | None = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


def split_keys(key, names: Sequence[str]) -> dict[str, jax.Array]:
    keys = jax.random.split(key, len(names))
    return dict(zip(names, keys))


def param_count(params: Any) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


def cast_tree(params: Any, dtype) -> Any:
    return jax.tree_util.tree_map(
        lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params,
    )
