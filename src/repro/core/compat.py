"""The repo's one policy for JAX's mesh and shard_map APIs.

  * :func:`shard_map` turns off the varying-manual-axes check: the ring
    bodies build per-device accumulators with ``fori_loop`` and the
    sharded serving steps return all-gathered (replicated) logits under
    a ``P()`` out_spec, neither of which the checker can prove.
  * :func:`make_mesh` builds meshes with ``Auto`` axis types, so
    shardings propagate as they do for a plain ``Mesh``.

Code in ``core/``, ``models/`` and ``launch/`` goes through these instead
of calling the JAX APIs with their own settings.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the replication (vma) check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(axis_shapes, axis_names) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
