"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state. Single-pod: (data=16, model=16) = 256 chips
(one v5e pod). Multi-pod: (pod=2, data=16, model=16) = 512 chips; the
``pod`` axis composes with ``data`` for the batch dimension (pure DP
across pods, so only gradient all-reduce crosses the DCN-class inter-pod
links).
"""

from __future__ import annotations

from repro.mesh import make_mesh

__all__ = ["make_production_mesh", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


class HW:
    """TPU v5e hardware constants used by the roofline analysis."""

    PEAK_BF16_FLOPS = 197e12     # per chip
    HBM_BW = 819e9               # bytes/s per chip
    ICI_BW = 50e9                # bytes/s per link
    HBM_BYTES = 16 * 2**30       # 16 GiB per chip
