"""repro.shardmap helpers over JAX's native shard_map / mesh API.
Single-device meshes here; multi-device behavior is covered by
tests/test_distributed.py."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import shardmap


def test_make_mesh_and_scope_roundtrip():
    mesh = shardmap.make_mesh((1,), ("data",))
    assert mesh.axis_names == ("data",)
    assert shardmap.get_abstract_mesh() is None
    with shardmap.mesh_scope(mesh):
        am = shardmap.get_abstract_mesh()
        assert am is not None
        assert tuple(am.axis_names) == ("data",)
        assert shardmap.mesh_axis_size(am, "data") == 1
        assert shardmap.mesh_axis_size(am, "model") == 1
    assert shardmap.get_abstract_mesh() is None
    # None mesh -> null scope, usable unconditionally.
    with shardmap.mesh_scope(None):
        pass


def test_shard_map_executes_with_collective():
    mesh = shardmap.make_mesh((1,), ("data",))

    def block(x):
        return jax.lax.psum(x, "data")

    f = jax.jit(shardmap.shard_map(
        block, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))
    y = f(jnp.arange(4.0))
    np.testing.assert_array_equal(np.asarray(y), np.arange(4.0))


def test_shard_map_axis_names_subset():
    """axis_names={...} (a partial-manual body) must trace and run, with
    sharding constraints allowed inside it."""
    mesh = shardmap.make_mesh((1,), ("data",))

    def block(x):
        assert shardmap.constraints_supported_here()
        return x * 2.0

    f = jax.jit(shardmap.shard_map(
        block, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        axis_names={"data"}, check_vma=False))
    np.testing.assert_array_equal(np.asarray(f(jnp.ones(4))), 2 * np.ones(4))


def test_auto_axis_names_respects_manual_scope():
    mesh = shardmap.make_mesh((1,), ("data",))
    assert shardmap.auto_axis_names(mesh) in (("data",), ())
    with shardmap.manual_axes_scope({"data"}):
        assert "data" not in shardmap.auto_axis_names(mesh)


def test_mesh_scope_enables_sharding_constraint():
    """constrain()-style bare-PartitionSpec constraints must work under
    mesh_scope (the models rely on this)."""
    from repro.models.common import constrain

    mesh = shardmap.make_mesh((1,), ("data",))
    # No mesh: identity.
    x = jnp.ones((4, 2))
    np.testing.assert_array_equal(np.asarray(constrain(x, "data", None)),
                                  np.asarray(x))
    with shardmap.mesh_scope(mesh):
        y = jax.jit(lambda v: constrain(v, "data", None))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
