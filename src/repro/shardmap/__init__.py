"""The one place this repo touches the shard_map / mesh APIs.

Thin helpers over JAX's native surface: ``jax.shard_map``,
``jax.make_mesh(..., axis_types=...)``, ``jax.set_mesh`` and
``jax.sharding.get_abstract_mesh``.

``get_abstract_mesh`` normalizes "no mesh installed" to ``None`` (JAX
returns an *empty* ``AbstractMesh`` instead), so callers write
``mesh = shardmap.get_abstract_mesh(); if mesh is None: ...`` and never
touch ``axis_names`` of an empty mesh.  Whatever it returns can be passed
straight back to :func:`shard_map` as the ``mesh`` argument.

Prefer *explicit* meshes over the ambient lookup wherever a mesh can be
threaded through (e.g. ``FrontierGraph.mesh`` for the sharded DKS path);
``get_abstract_mesh`` exists for model code whose call signature cannot
carry one (sharding constraints deep inside a transformer block).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Iterable

import jax

__all__ = [
    "shard_map",
    "make_mesh",
    "mesh_scope",
    "get_abstract_mesh",
    "auto_axis_names",
    "mesh_axis_size",
    "manual_axes_scope",
    "constraints_supported_here",
]

# Axes that code tracing a body through a manual-mode entry point other
# than shard_map marks Manual (manual_axes_scope); auto_axis_names()
# subtracts them.
_tls = threading.local()


@contextlib.contextmanager
def manual_axes_scope(names: Iterable[str]):
    """Mark ``names`` as Manual for :func:`auto_axis_names` in this
    thread, for code that traces a body through a manual-mode entry point
    other than :func:`shard_map` (whose Manual axes JAX records in the
    mesh's axis types)."""
    prev = getattr(_tls, "manual_axes", frozenset())
    _tls.manual_axes = prev | frozenset(names)
    try:
        yield
    finally:
        _tls.manual_axes = prev


def shard_map(
    f: Callable,
    mesh: Any,
    in_specs: Any,
    out_specs: Any,
    *,
    check_vma: bool = True,
    axis_names: Iterable[str] | None = None,
) -> Callable:
    """``jax.shard_map``.  ``axis_names``: the mesh axes the body is
    *manual* over (all of them when None)."""
    kwargs: dict[str, Any] = dict(
        mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """A device mesh with Auto-typed axes.

    Unlike bare ``jax.make_mesh``, the product of ``axis_shapes`` may be
    smaller than the local device count — the first ``prod(axis_shapes)``
    devices are used.
    """
    import math

    if devices is None:
        n = math.prod(axis_shapes)
        local = jax.devices()
        if n < len(local):
            devices = local[:n]
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names), devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)))


def mesh_scope(mesh):
    """Context manager installing ``mesh`` as the ambient mesh
    (``jax.set_mesh``).  ``None`` is accepted and yields a null context,
    so callers can write ``with mesh_scope(self.mesh):`` unconditionally.
    """
    if mesh is None:
        return contextlib.nullcontext()
    return jax.set_mesh(mesh)


def get_abstract_mesh():
    """The ambient mesh installed by the enclosing :func:`mesh_scope`, or
    ``None`` when no mesh is active.

    The returned ``AbstractMesh`` exposes ``.axis_names`` / ``.shape`` and
    is a valid ``mesh=`` argument for :func:`shard_map`.
    """
    am = jax.sharding.get_abstract_mesh()
    if am is None or not am.axis_names:
        return None
    return am


def auto_axis_names(mesh) -> tuple[str, ...]:
    """Mesh axes usable in sharding constraints: the Auto-typed ones
    (``mesh.axis_types`` excludes axes an enclosing shard_map made
    Manual), less any :func:`manual_axes_scope` marks."""
    manual = getattr(_tls, "manual_axes", frozenset())
    return tuple(n for n, t in zip(mesh.axis_names, mesh.axis_types)
                 if "Auto" in str(t) and n not in manual)


def constraints_supported_here() -> bool:
    """Whether ``with_sharding_constraint`` is safe at this trace point:
    always, since JAX tells Manual axes apart through axis types."""
    return True


def mesh_axis_size(mesh, *names: str) -> int:
    """Product of the sizes of ``names`` present in ``mesh`` (1 if none)."""
    if mesh is None:
        return 1
    size = 1
    for n in names:
        if n in mesh.axis_names:
            size *= mesh.shape[n]
    return size
