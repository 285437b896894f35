"""The few JAX entry points this repo wraps, bound once for JAX 0.9.0.

The repo supports exactly one JAX, the version ``requirements.txt`` pins.
Every call site imports these names from here instead of reaching into
``jax`` directly, so a future upgrade touches one module.

``CompilerParams``
    ``pltpu.CompilerParams`` (``dimension_semantics``, ``vmem_limit_bytes``).

``shard_map``
    ``jax.shard_map``.  Callers that run ``pallas_call`` bodies inside pass
    ``check_vma=False`` (a Pallas kernel has no replication rule).

``get_abstract_mesh()``
    The ambient abstract mesh set by :func:`use_mesh`, or ``None`` when no
    mesh (or an empty one) is active.

``get_ambient_mesh()``
    Like :func:`get_abstract_mesh`, but also sees a mesh activated by a
    classic ``with mesh:`` block (the thread-local *physical* mesh), so
    mesh-sensitive callers behave the same under either idiom.

``make_mesh(axis_shapes, axis_names, axis_types=None)``
    ``jax.make_mesh`` with **Auto** axes by default.  JAX 0.9 defaults to
    Explicit axes, under which the model's sharding-agnostic code
    (``jnp.take``, ``jnp.repeat``, Pallas interpret loops) raises
    ``ShardingTypeError``; the repo's meshes all mean GSPMD-style Auto
    partitioning plus explicit ``shard_map`` regions.

``use_mesh(mesh)``
    ``jax.sharding.set_mesh`` as a context manager: makes ``mesh`` ambient.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as _pltpu

__all__ = [
    "CompilerParams", "get_abstract_mesh", "get_ambient_mesh", "make_mesh",
    "shard_map", "use_mesh",
]

CompilerParams = _pltpu.CompilerParams
shard_map = jax.shard_map
use_mesh = jax.sharding.set_mesh


def get_abstract_mesh():
    """The ambient mesh model code may shard over, or ``None``."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return mesh


def get_ambient_mesh():
    """The mesh the program is running under, however it was set: the
    abstract mesh first, then the thread-local physical mesh of a plain
    ``with mesh:`` block (``dispatch.default_serving_impl`` and the
    sharded wrappers must see both)."""
    mesh = get_abstract_mesh()
    if mesh is not None:
        return mesh
    from jax._src.mesh import thread_resources
    pm = thread_resources.env.physical_mesh
    return None if pm.empty else pm


def make_mesh(axis_shapes, axis_names, *, axis_types=None, **kw):
    """``jax.make_mesh`` whose axes default to ``AxisType.Auto``."""
    if axis_types is None:
        axis_types = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types, **kw)
