"""The repo's one device-mesh constructor.

Every mesh — the bank mesh of ``repro.core.banks``, the elastic pools of
``repro.runtime.elastic``, the launch meshes — is built here, with every
axis ``AxisType.Auto``. ``jax.make_mesh`` alone makes Explicit axes,
under which sharding-in-types rejects the serve tier's slot scatter and
gather on bank-sharded state (``.at[...]`` without ``out_sharding``).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh"]


def make_mesh(shape, axis_names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` over ``devices`` (default: all) with Auto axes."""
    return jax.make_mesh(
        tuple(shape),
        tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names),
        devices=devices,
    )
