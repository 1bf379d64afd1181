"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS for 512 host devices *before* any jax
import and only then calls these.  Mesh construction goes through
:mod:`repro.shardmap`.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro import shardmap


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) single pod; 2x16x16 (pod, data, model) for two."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return shardmap.make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a 1D (data,) mesh (tests/CPU)."""
    n = len(jax.devices())
    return shardmap.make_mesh((n,), ("data",))


def filter_spec(spec: P, mesh) -> P:
    """Drop axis names not present in the mesh from a PartitionSpec."""
    names = set(mesh.axis_names)

    def f(entry):
        if entry is None:
            return None
        if isinstance(entry, str):
            return entry if entry in names else None
        kept = tuple(a for a in entry if a in names)
        return kept if kept else None

    return P(*(f(e) for e in spec))


def named_sharding(mesh, spec: P):
    return jax.sharding.NamedSharding(mesh, filter_spec(spec, mesh))


def sharding_tree(mesh, spec_tree):
    """Map a pytree of PartitionSpecs to NamedShardings (P treated as leaf)."""
    return jax.tree_util.tree_map(
        lambda s: named_sharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
