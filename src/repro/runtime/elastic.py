"""Elastic scaling: re-shard live state onto a different device set.

Checkpoints are mesh-agnostic (full arrays + treedef), so shrink/grow is:
  1. snapshot state to host (or restore the latest checkpoint),
  2. build the new mesh from the surviving device set,
  3. derive shardings for the SAME ParamSpec tree under the new mesh
     (divisibility fallbacks re-resolve automatically — a dim that was
     16-way shardable may become 8-way or replicated),
  4. device_put every leaf with its new sharding.

``elastic_reshard`` does 2-4 in one call. Two callers exist today:

* the training-side ``Supervisor``'s ``on_restart`` hook, after evicting
  dead workers;
* the serve tier's elastic executor pool (``repro.serve.fleet``):
  ``scale_up`` consults :func:`available_mesh` for the device ceiling of
  a mesh-backed pool, and a session migrating off a **draining**
  executor has its extracted slot state passed through
  :func:`elastic_reshard` (spec tree from :func:`state_spec_tree`) so it
  lands placed for the devices that remain, not wherever the leaving
  executor happened to hold it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding as sh
from repro.mesh import make_mesh

__all__ = [
    "available_mesh",
    "elastic_reshard",
    "mesh_shape",
    "state_spec_tree",
]


def mesh_shape(num_devices: int, num_axes: int) -> tuple[int, ...]:
    """Largest power-of-2 mesh shape over ``num_devices`` devices.

    1 axis: ``(n,)`` with ``n`` the largest power of two ``<=``
    ``num_devices``. 2 axes: ``(n // m, m)`` with ``m`` the largest
    power of two whose square fits in ``n`` — as square as a power-of-2
    factorization gets, biased toward the first (data) axis. Pure
    arithmetic, factored out of :func:`available_mesh` so shrink/grow
    semantics are testable without multi-device hardware.
    """
    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    if num_axes not in (1, 2):
        raise ValueError(f"num_axes must be 1 or 2, got {num_axes}")
    n = 1
    while n * 2 <= num_devices:
        n *= 2
    if num_axes == 1:
        return (n,)
    m = 1  # largest power of 2 with m*m <= n
    while (m * 2) * (m * 2) <= n:
        m *= 2
    return (n // m, m)


def available_mesh(axis_names=("data", "model"), *, devices=None):
    """Largest power-of-2 mesh over the surviving devices."""
    devs = list(devices if devices is not None else jax.devices())
    shape = mesh_shape(len(devs), len(axis_names))
    return make_mesh(
        shape, axis_names, devices=devs[: int(np.prod(shape))]
    )


def state_spec_tree(state, *, axes: dict[int, str] | None = None):
    """ParamSpec tree mirroring a *concrete* pytree's leaves.

    Bridges runtime state (filter slot states, optimizer moments) into
    :func:`elastic_reshard`'s declarative world: each leaf becomes a
    ``ParamSpec`` of its own shape/dtype with every axis logical-``None``
    (replicate), except dims listed in ``axes`` (``{dim_index: name}`` —
    e.g. ``{0: "bank"}`` for a banked filter state, which the rules then
    map onto a mesh axis). A single-slot state extracted from a draining
    executor has no bank axis left, so the default all-``None`` spec —
    plain re-placement under the new device set — is exactly right.
    """
    axes = axes or {}

    def spec(leaf):
        arr = jnp.asarray(leaf)
        ax = tuple(axes.get(d) for d in range(arr.ndim))
        return sh.ParamSpec(
            shape=tuple(arr.shape), axes=ax, init="zeros", dtype=arr.dtype
        )

    return jax.tree_util.tree_map(spec, state)


def elastic_reshard(state, spec_tree, new_mesh, rules=None):
    """Move a (possibly sharded) pytree onto ``new_mesh``.

    ``spec_tree`` is the ParamSpec tree describing logical axes; shardings
    are re-derived under the new mesh with divisibility fallback.
    """
    shardings = sh.named_shardings(spec_tree, new_mesh, rules)

    def move(x, s):
        return jax.device_put(np.asarray(x), s)

    return jax.tree_util.tree_map(move, state, shardings)
