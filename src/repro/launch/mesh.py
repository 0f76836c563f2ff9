"""Mesh construction (production pods, host-local test meshes, serving).

Functions, not module-level constants: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
All construction goes through ``core/compat.make_mesh``, which fixes the
axis types.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core import compat


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over however many (virtual) devices exist — tests/examples."""
    n = len(jax.devices())
    assert n % model == 0
    return compat.make_mesh((n // model, model), ("data", "model"))


def make_serving_mesh(n_shards: Optional[int] = None) -> jax.sharding.Mesh:
    """The distributed serving engine's ``("shard",)`` mesh: one KV-pool
    shard per device.  ``n_shards=None`` takes every visible device (on
    CPU force them with ``XLA_FLAGS=--xla_force_host_platform_device_count
    =4``)."""
    if n_shards is None:
        n_shards = len(jax.devices())
    assert 1 <= n_shards <= len(jax.devices()), (
        n_shards, len(jax.devices()))
    return compat.make_mesh((n_shards,), ("shard",))
