"""Pallas memory-space placement for the denoise kernels.

``repro.tune.budget.FAMILY_PLACEMENTS`` describes *where each logical
operand of a kernel family should live* as plain strings ("vmem",
"smem", "any"); this module translates those strings into the Pallas
memory-space objects a ``pl.BlockSpec`` accepts, so the kernel files can
write::

    ms = spaces.operand_spaces("ema", placement)
    pl.BlockSpec((1, 1), lambda hb, k: (0, 0), memory_space=ms["prior"])

The paper's analogue is explicit BRAM-vs-LUTRAM-vs-DRAM binding in the
HLS pragmas: accumulators in BRAM next to the datapath, control scalars
in registers, bulk windows left in DRAM until needed. Here that maps to
VMEM accumulators, SMEM scalars (the EMA traced step counter), and
ANY/HBM for operands the kernel never reads (the median insert's aliased
donor slot).

Placement is numerics-neutral: ``None`` from :func:`memory_space` leaves
the BlockSpec unannotated and the compiler places the operand. The
autotuner searches scheme names (``budget.placement_schemes``) and caches
the measured winner in the plan; kernels receive the scheme name as a
static ``placement`` arg.
"""

from __future__ import annotations

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.tune import budget

__all__ = ["memory_space", "operand_spaces"]

_SPACES = {
    "vmem": pltpu.VMEM,
    "smem": pltpu.SMEM,
    "any": pl.ANY,
}


def memory_space(space: str | None):
    """Space string -> Pallas memory-space object (None = unannotated)."""
    if space is None:
        return None
    return _SPACES[space]


def operand_spaces(family: str, placement: str | None = None) -> dict:
    """Logical operand -> memory-space object for one placement scheme.

    Missing operands map to ``None`` via ``dict.get`` at the call site —
    the "compiler" scheme is an empty map, so every lookup degrades to an
    unannotated BlockSpec.
    """
    scheme = budget.resolve_placement(family, placement)
    return {op: memory_space(sp) for op, sp in scheme.items()}
