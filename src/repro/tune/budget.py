"""Shared VMEM budget model for every denoise Pallas kernel.

The paper's DRAM-optimized schedule sizes burst lengths and buffer
geometry against the FPGA's BRAM; the TPU analogue is block geometry
(``row_tile`` × ``pair_tile``) sized against VMEM. Before this module,
each kernel file carried its own picker and all of them reused the
Alg 3 working-set model (2 input tiles + 1 accumulator, 4 bytes each) —
wrong for the median kernel's K window slots, the EMA kernel's extra
per-pixel mean/M2 tiles, and the spatial kernel's halo views, and wrong
for u16 inputs everywhere. This module is the single budget model, with
one *operand description* per kernel family:

==================  ============================================================
family              block working set (per grid step)
==================  ============================================================
``stream``          pairs in (tp, 2, th, w) + sum in + sum out (tp, th, w)
``median_insert``   pairs in (tp, 2, th, w) + donor slot + slot out (tp, th, w)
``median_combine``  window in (K, tp, th, w) + median out (tp, th, w)
``ema``             pairs in + ema in/out (tp, th, w) + mean/M2 in/out (th, w)
``spatial``         3 halo views (me/up/dn) + out, all (tp, th, w), accum dtype
==================  ============================================================

``resolve_tiles(family, ...)`` is what the kernel files call: explicit
overrides are validated (must divide exactly — Mosaic-friendly blocks,
interpret-mode exactness), and the heuristic fills the budget with the
largest exact divisors, rows first (the paper's burst-length-first
ordering). Heuristic row tiles are multiples of the block dtypes' native
sublane tiling (8 rows of f32, 16 of u16, 32 of u8) or the full height,
so every pick is one Mosaic compiles without relayouts. The measured autotuner (``repro.tune.autotune``) uses the same
model to generate its candidate set, so tuned plans search *around* the
budget point instead of blindly.

The legacy 3-tile pickers (``legacy_pick_row_tile``/``legacy_pick_pair_tile``)
are kept verbatim: ``repro.kernels.denoise_stream`` re-exports them for
backward compatibility, and the tuner seeds its candidates with them so a
tuned plan can never regress below the pre-tuner heuristic.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = [
    "VMEM_BUDGET",
    "KERNEL_FAMILIES",
    "FAMILY_PLACEMENTS",
    "KernelBudget",
    "largest_divisor_leq",
    "block_bytes",
    "legal_row_tiles",
    "pick_row_tile",
    "pick_pair_tile",
    "resolve_tiles",
    "placement_schemes",
    "resolve_placement",
    "legacy_pick_row_tile",
    "legacy_pick_pair_tile",
]

#: ~2 MiB of the ~16 MiB/core VMEM for the block working set. Mosaic
#: double-buffers the HBM->VMEM DMA of block k+1 against compute on block
#: k, so the effective footprint is up to 2x this — still comfortably
#: inside VMEM with room for spills.
VMEM_BUDGET = 2**21


def largest_divisor_leq(n: int, cap: int) -> int:
    """Largest exact divisor of ``n`` that is <= ``cap`` (>= 1)."""
    cap = max(1, min(n, cap))
    best = 1
    d = 1
    while d * d <= n:
        if n % d == 0:
            for cand in (d, n // d):
                if cand <= cap:
                    best = max(best, cand)
        d += 1
    return best


@dataclasses.dataclass(frozen=True)
class KernelBudget:
    """Operand description of one kernel family's block working set.

    ``in_planes``     — (tp, th, w) planes of *input* dtype (each frame of
                        the (tp, 2, th, w) pairs block counts as one).
    ``acc_planes``    — (tp, th, w) planes of accumulator dtype.
    ``row_planes``    — (th, w) planes of accumulator dtype that have no
                        pair axis (the EMA kernel's mean/M2 in+out).
    ``window_planes`` — (tp, th, w) accumulator planes scaled by the
                        window length K (``median_combine``'s K slots).
    """

    in_planes: int = 0
    acc_planes: int = 0
    row_planes: int = 0
    window_planes: int = 0


KERNEL_FAMILIES: dict[str, KernelBudget] = {
    # alg3 one-shot/step + multibank step (sum in + sum out; the one-shot
    # kernel carries one plane fewer — the shared description is the
    # conservative superset so one plan serves both entry points)
    "stream": KernelBudget(in_planes=2, acc_planes=2),
    # diff into one donated window slot: pairs in + donor block + slot out
    "median_insert": KernelBudget(in_planes=2, acc_planes=2),
    # K window slots in + median out
    "median_combine": KernelBudget(acc_planes=1, window_planes=1),
    # pairs in + ema in/out with a pair axis + mean/M2 in/out without one
    "ema": KernelBudget(in_planes=2, acc_planes=2, row_planes=4),
    # me/up/dn halo views + out, input already in accumulator dtype
    "spatial": KernelBudget(acc_planes=4),
}

#: Per-family memory-space placement schemes: scheme name -> logical
#: operand -> space string (``"vmem"`` / ``"smem"`` / ``"any"``). The
#: first scheme of each family is the default ("auto"); ``"compiler"``
#: leaves every BlockSpec unannotated (pre-tier behaviour, the compiler
#: decides). ``repro.kernels.spaces`` translates the strings to Pallas
#: memory-space objects; the measured autotuner treats the scheme names
#: as a candidate axis and caches the winner in the plan next to the
#: block geometry. Placement never changes the numeric stream — only
#: where blocks live — so every scheme of a family is interchangeable
#: for correctness.
FAMILY_PLACEMENTS: dict[str, dict[str, dict[str, str]]] = {
    # pairs stream through VMEM, the running sum is a VMEM accumulator
    "stream": {
        "auto": {"pairs": "vmem", "acc": "vmem"},
        "compiler": {},
    },
    # the donated window-slot operand is never read (pure alias donor),
    # so by default it stays in ANY/HBM and only the written slot block
    # occupies VMEM; "vmem_donor" is the conservative alternative
    "median_insert": {
        "auto": {"pairs": "vmem", "donor": "any", "slot": "vmem"},
        "vmem_donor": {"pairs": "vmem", "donor": "vmem", "slot": "vmem"},
        "compiler": {},
    },
    # the K-slot window block dominates; it and the median live in VMEM
    "median_combine": {
        "auto": {"window": "vmem", "out": "vmem"},
        "compiler": {},
    },
    # the traced step counter is a (1,1) scalar -> SMEM by default
    # (paper's control scalars live beside the datapath, not in BRAM);
    # "vmem_scalar" keeps it with the vector operands instead
    "ema": {
        "auto": {"pairs": "vmem", "state": "vmem", "prior": "smem"},
        "vmem_scalar": {"pairs": "vmem", "state": "vmem", "prior": "vmem"},
        "compiler": {},
    },
    "spatial": {
        "auto": {"halo": "vmem", "out": "vmem"},
        "compiler": {},
    },
}


def _bytes(dtype) -> int:
    return int(np.dtype(dtype).itemsize)


def _sublane_rows(dtype) -> int:
    """Rows in one native (rows, 128) VMEM tile of ``dtype``: 8 for 32-bit,
    16 for 16-bit and 32 for 8-bit values."""
    return 8 * max(1, 4 // _bytes(dtype))


def legal_row_tiles(
    family: str, h: int, *, in_dtype="uint16", acc_dtype="float32"
) -> list[int]:
    """Row tiles Mosaic takes natively for ``family``, ascending: exact
    divisors of ``h`` that keep every operand on its native sublane tiling
    (the narrowest dtype in the block decides), and always the full height."""
    align = _sublane_rows(acc_dtype)
    if _family(family).in_planes:
        align = max(align, _sublane_rows(in_dtype))
    return [d for d in range(align, h, align) if h % d == 0] + [h]


def placement_schemes(family: str) -> tuple[str, ...]:
    """Valid placement scheme names for ``family``, default first."""
    _family(family)
    return tuple(FAMILY_PLACEMENTS[family])


def resolve_placement(family: str, placement: str | None = None) -> dict[str, str]:
    """Logical-operand -> space-string map for one scheme of ``family``.

    ``None`` selects the family default (first scheme). Unknown scheme
    names raise — a stale plan cache must fail loudly here, not silently
    mis-place operands.
    """
    _family(family)
    schemes = FAMILY_PLACEMENTS[family]
    if placement is None:
        placement = next(iter(schemes))
    try:
        return dict(schemes[placement])
    except KeyError:
        raise ValueError(
            f"placement for {family!r} must be one of {tuple(schemes)}, "
            f"got {placement!r}"
        ) from None


def _family(family: str) -> KernelBudget:
    try:
        return KERNEL_FAMILIES[family]
    except KeyError:
        raise ValueError(
            f"kernel family must be one of {tuple(KERNEL_FAMILIES)}, "
            f"got {family!r}"
        ) from None


def block_bytes(
    family: str,
    row_tile: int,
    pair_tile: int,
    w: int,
    *,
    in_dtype="uint16",
    acc_dtype="float32",
    window: int = 1,
    in_pixel_bytes: float | None = None,
) -> int:
    """VMEM bytes of one grid step's block working set for ``family``.

    ``in_pixel_bytes`` overrides the input-plane cost per *logical* pixel
    for quantized wire formats (1.0 for u8, 1.5 for packed-12-bit, whose
    wire block is narrower than the logical width). ``None`` keeps the
    exact pre-tier integer path from ``in_dtype``.
    """
    kb = _family(family)
    acc_b = _bytes(acc_dtype)
    in_b: float | int = (
        _bytes(in_dtype) if in_pixel_bytes is None else in_pixel_bytes
    )
    per_pair = row_tile * w * (
        kb.in_planes * in_b
        + kb.acc_planes * acc_b
        + kb.window_planes * window * acc_b
    )
    return int(pair_tile * per_pair + kb.row_planes * row_tile * w * acc_b)


def pick_row_tile(
    family: str,
    h: int,
    w: int,
    *,
    in_dtype="uint16",
    acc_dtype="float32",
    window: int = 1,
    in_pixel_bytes: float | None = None,
    vmem_budget: int = VMEM_BUDGET,
) -> int:
    """Largest legal row tile of ``h`` whose single-pair block fits the
    budget; the smallest legal one when none fits.

    Legal tiles are the sublane-aligned exact divisors of ``h`` and ``h``
    itself (:func:`legal_row_tiles`). Rows are sized first (at
    ``pair_tile=1``); ``pick_pair_tile`` then fills the remaining budget —
    the same order as the legacy pickers, so plans stay comparable.
    """
    per_row = block_bytes(
        family, 1, 1, w, in_dtype=in_dtype, acc_dtype=acc_dtype, window=window,
        in_pixel_bytes=in_pixel_bytes,
    )
    rows = max(1, vmem_budget // max(1, per_row))
    legal = legal_row_tiles(family, h, in_dtype=in_dtype, acc_dtype=acc_dtype)
    fitting = [t for t in legal if t <= rows]
    return fitting[-1] if fitting else legal[0]


def pick_pair_tile(
    family: str,
    p: int,
    row_tile: int,
    w: int,
    *,
    in_dtype="uint16",
    acc_dtype="float32",
    window: int = 1,
    in_pixel_bytes: float | None = None,
    vmem_budget: int = VMEM_BUDGET,
) -> int:
    """Frame pairs per block: fill what the row tile left of the budget."""
    kb = _family(family)
    fixed = kb.row_planes * row_tile * w * _bytes(acc_dtype)
    per_pair = block_bytes(
        family, row_tile, 1, w, in_dtype=in_dtype, acc_dtype=acc_dtype,
        window=window, in_pixel_bytes=in_pixel_bytes,
    ) - fixed
    budget = max(1, (vmem_budget - fixed) // max(1, per_pair))
    return largest_divisor_leq(p, budget)


def _check_divides(th: int, tp: int, *, p: int, h: int) -> tuple[int, int]:
    if h % th:
        raise ValueError(f"row_tile {th} must divide H={h}")
    if p % tp:
        raise ValueError(f"pair_tile {tp} must divide N/2={p}")
    return th, tp


def resolve_tiles(
    family: str,
    p: int,
    h: int,
    w: int,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    *,
    in_dtype="uint16",
    acc_dtype="float32",
    window: int = 1,
    in_pixel_bytes: float | None = None,
    vmem_budget: int = VMEM_BUDGET,
) -> tuple[int, int]:
    """(row_tile, pair_tile) for a (p, h, w) problem of ``family``.

    Explicit overrides win but must divide exactly (a non-dividing tile
    raises ``ValueError`` — on TPU it would force masked edge blocks, in
    interpret mode it would be silently wrong). Picked row tiles are
    always sublane-aligned or the full height (:func:`legal_row_tiles`).
    """
    kw = dict(
        in_dtype=in_dtype, acc_dtype=acc_dtype, window=window,
        in_pixel_bytes=in_pixel_bytes, vmem_budget=vmem_budget,
    )
    if family == "ema" and vmem_budget == VMEM_BUDGET:
        # The EMA kernel's Chan variance merge accumulates chunk-at-a-time
        # across pair blocks, so pair_tile is NUMERICS-VISIBLE (different
        # blocking => different float rounding). The default therefore
        # stays pinned to the exact pre-tuner pick — bit-identical
        # heuristic output — wherever that pick is a legal row tile and
        # its block stays within 2x the corrected budget (the old model
        # ignored the f32-vs-u16 input gap and the mean/M2 row planes).
        # Elsewhere the corrected model below picks, as for every family.
        th = row_tile or legacy_pick_row_tile(h, w)
        tp = pair_tile or legacy_pick_pair_tile(p, th, w)
        legal = row_tile or th in legal_row_tiles(
            family, h, in_dtype=in_dtype, acc_dtype=acc_dtype
        )
        within = block_bytes(
            family, th, tp, w, in_dtype=in_dtype, acc_dtype=acc_dtype,
            in_pixel_bytes=in_pixel_bytes,
        ) <= 2 * vmem_budget
        if legal and within:
            return _check_divides(th, tp, p=p, h=h)
    th = row_tile or pick_row_tile(family, h, w, **kw)
    tp = pair_tile or pick_pair_tile(family, p, th, w, **kw)
    return _check_divides(th, tp, p=p, h=h)


# ---------------------------------------------------------------------------
# Legacy pickers (pre-tune 3-tile model): kept verbatim for the
# denoise_stream re-exports and as the tuner's always-included baseline
# candidate. New code should use the family-aware functions above.
# ---------------------------------------------------------------------------


def legacy_pick_row_tile(
    h: int, w: int, *, dtype_bytes: int = 4, vmem_budget: int = VMEM_BUDGET
) -> int:
    """Rows per tile under the old 2-input+1-accum, 4-byte model."""
    rows = max(1, vmem_budget // max(1, 3 * w * dtype_bytes))
    if rows >= h:
        return h
    return largest_divisor_leq(h, rows)


def legacy_pick_pair_tile(
    p: int,
    row_tile: int,
    w: int,
    *,
    dtype_bytes: int = 4,
    vmem_budget: int = VMEM_BUDGET,
) -> int:
    """Frame pairs per block under the old 3-tile model."""
    per_pair = 3 * row_tile * w * dtype_bytes
    budget = max(1, vmem_budget // max(1, per_pair))
    return largest_divisor_leq(p, budget)
