"""Pallas TPU kernel for paper Algorithm 3 (+ v2): fused subtract-accumulate.

This is the paper's contribution re-expressed for the TPU memory hierarchy:

* FPGA BRAM running ``sumFrame``  -> the output block pinned in **VMEM**
  across the (sequential, innermost) group axis of the grid.
* AXI4 **burst-mode** DRAM access -> contiguous ``BlockSpec`` tiles; the
  Mosaic pipeline engine double-buffers the HBM->VMEM DMA of tile *k+1*
  against compute on tile *k* (the paper's `II=1` pipelined loops).
* Pipelined accumulation (Alg 3's key idea: never materialize individual
  difference frames) -> each input frame tile is read from HBM **exactly
  once**; the only HBM writes are the final averaged frames.

Traffic (elements):  reads = G*N*H*W inputs (each once), writes = (N/2)*H*W.
Compare ``denoise_tmpframe`` (Algorithms 1/2) which also move the
(G, N/2, H, W) intermediate array through HBM twice.

Layout note: W is the lane (minor) dimension; blocks are
(pair_tile, 2, rows_tile, W) with W padded to the 128-lane boundary by
Mosaic when needed. The grid is (pair_blocks, row_tiles, groups) — groups
innermost so the accumulator tile stays resident in VMEM for the whole
reduction (the matmul-K-loop pattern). ``pair_tile`` packs several frame
pairs into one block: the paper's frames are small (80×256 = one f32 tile
of 80 KiB), so single-pair blocks leave the grid dominated by per-step
overhead; pair-tiling amortizes it exactly like the paper's burst length
amortizes AXI beats.

Validated in interpret mode on CPU against ``ref.ref_subtract_average``;
on TPU the same ``pl.pallas_call`` lowers natively via Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import quant, spaces
from repro.tune.budget import resolve_tiles

__all__ = ["alg3_subtract_average", "alg3_stream_step"]

# Backwards-compatible re-exports: the tile pickers now live in the shared
# per-family budget model (repro.tune.budget). The legacy names keep the
# old 3-tile/4-byte semantics for callers that sized budgets against them.
from repro.tune.budget import (  # noqa: F401  (compat re-exports)
    VMEM_BUDGET as _VMEM_BUDGET,
    largest_divisor_leq as _largest_divisor_leq,
    legacy_pick_pair_tile as _pick_pair_tile,
    legacy_pick_row_tile as _pick_row_tile,
)


def _resolve_tiles(
    p: int,
    h: int,
    w: int,
    row_tile: int | None,
    pair_tile: int | None,
    *,
    in_dtype="uint16",
    acc_dtype="float32",
    stream_dtype: str = "u16",
) -> tuple[int, int]:
    """Alg 3 ("stream" family) tiles via the shared budget model.

    ``w`` is the *logical* width; narrow wire formats discount the input
    planes via ``in_pixel_bytes`` (u16 keeps the exact pre-tier path).
    """
    return resolve_tiles(
        "stream", p, h, w, row_tile, pair_tile,
        in_dtype=in_dtype, acc_dtype=acc_dtype,
        in_pixel_bytes=(
            None if stream_dtype == "u16"
            else quant.wire_pixel_bytes(stream_dtype)
        ),
    )


def _alg3_kernel(
    f_ref, o_ref, *, num_groups: int, offset: float, divide_first: bool,
    stream_dtype: str,
):
    g = pl.program_id(2)
    acc = o_ref.dtype
    # f_ref: (pair_tile, 2, th, wire_w) -> dequantized diff (pair_tile, th, w)
    diff = quant.pair_diff_block(
        f_ref[...], offset=offset, accum_dtype=acc, stream_dtype=stream_dtype,
        in_kernel=True,
    )
    if divide_first:
        diff = diff / jnp.asarray(num_groups, acc)

    @pl.when(g == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += diff

    if not divide_first:

        @pl.when(g == num_groups - 1)
        def _finalize():
            o_ref[...] = o_ref[...] / jnp.asarray(num_groups, acc)


@functools.partial(
    jax.jit,
    static_argnames=(
        "offset",
        "divide_first",
        "accum_dtype",
        "row_tile",
        "pair_tile",
        "stream_dtype",
        "placement",
        "interpret",
    ),
)
def alg3_subtract_average(
    frames: jnp.ndarray,
    *,
    offset: float = 0.0,
    divide_first: bool = False,
    accum_dtype=jnp.float32,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    stream_dtype: str = "u16",
    placement: str | None = None,
    interpret: bool,
):
    """frames (G, N, H, wire_W) -> averaged difference frames (N/2, H, W).

    One ``pallas_call``; each input element crosses HBM->VMEM exactly once
    — and for narrow ``stream_dtype`` wire formats each *pixel* crosses as
    1 or 1.5 bytes instead of 2, widening in-VMEM inside the kernel.
    ``divide_first=True`` is the paper's Alg 3 v2 (overflow-safe spread
    division).
    """
    g, n, h, wp = frames.shape
    assert n % 2 == 0, "N must be even"
    p = n // 2
    w = quant.logical_width(wp, stream_dtype)
    pairs = frames.reshape(g, p, 2, h, wp)
    th, tp = _resolve_tiles(
        p, h, w, row_tile, pair_tile,
        in_dtype=frames.dtype, acc_dtype=accum_dtype,
        stream_dtype=stream_dtype,
    )

    kernel = functools.partial(
        _alg3_kernel,
        num_groups=g,
        offset=float(offset),
        divide_first=divide_first,
        stream_dtype=stream_dtype,
    )
    ms = spaces.operand_spaces("stream", placement)
    return pl.pallas_call(
        kernel,
        grid=(p // tp, h // th, g),
        in_specs=[
            pl.BlockSpec(
                (None, tp, 2, th, wp), lambda k, hb, gi: (gi, k, 0, hb, 0),
                memory_space=ms.get("pairs"),
            )
        ],
        out_specs=pl.BlockSpec(
            (tp, th, w), lambda k, hb, gi: (k, hb, 0),
            memory_space=ms.get("acc"),
        ),
        out_shape=jax.ShapeDtypeStruct((p, h, w), jnp.dtype(accum_dtype)),
        interpret=interpret,
    )(pairs)


# ---------------------------------------------------------------------------
# Streaming single-group step (the camera-facing entry point).
# One group of N frames arrives; the running sum lives in HBM between calls
# and is donated (input/output aliased), so per step the HBM traffic is:
#   read N*H*W input + read (N/2)*H*W sum + write (N/2)*H*W sum
# exactly the paper's per-frame burst R + burst W schedule (Fig. 4).
# ---------------------------------------------------------------------------


def _alg3_step_kernel(
    f_ref, s_ref, o_ref, *, num_groups, offset, divide_first, final,
    stream_dtype,
):
    acc = o_ref.dtype
    diff = quant.pair_diff_block(
        f_ref[...], offset=offset, accum_dtype=acc, stream_dtype=stream_dtype,
        in_kernel=True,
    )
    if divide_first:
        diff = diff / jnp.asarray(num_groups, acc)
    total = s_ref[...] + diff
    if final and not divide_first:
        total = total / jnp.asarray(num_groups, acc)
    o_ref[...] = total


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_groups",
        "offset",
        "divide_first",
        "final",
        "row_tile",
        "pair_tile",
        "stream_dtype",
        "placement",
        "interpret",
    ),
    donate_argnums=(1,),
)
def alg3_stream_step(
    group_frames: jnp.ndarray,
    sum_frame: jnp.ndarray,
    *,
    num_groups: int,
    offset: float = 0.0,
    divide_first: bool = False,
    final: bool = False,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    stream_dtype: str = "u16",
    placement: str | None = None,
    interpret: bool,
):
    """Fold one group (N, H, wire_W) into the running sum (N/2, H, W) (donated)."""
    n, h, wp = group_frames.shape
    p = n // 2
    # the running sum carries the logical width; the wire may be narrower
    w = sum_frame.shape[-1]
    pairs = group_frames.reshape(p, 2, h, wp)
    th, tp = _resolve_tiles(
        p, h, w, row_tile, pair_tile,
        in_dtype=group_frames.dtype, acc_dtype=sum_frame.dtype,
        stream_dtype=stream_dtype,
    )
    kernel = functools.partial(
        _alg3_step_kernel,
        num_groups=num_groups,
        offset=float(offset),
        divide_first=divide_first,
        final=final,
        stream_dtype=stream_dtype,
    )
    ms = spaces.operand_spaces("stream", placement)
    return pl.pallas_call(
        kernel,
        grid=(p // tp, h // th),
        in_specs=[
            pl.BlockSpec(
                (tp, 2, th, wp), lambda k, hb: (k, 0, hb, 0),
                memory_space=ms.get("pairs"),
            ),
            pl.BlockSpec(
                (tp, th, w), lambda k, hb: (k, hb, 0),
                memory_space=ms.get("acc"),
            ),
        ],
        out_specs=pl.BlockSpec(
            (tp, th, w), lambda k, hb: (k, hb, 0),
            memory_space=ms.get("acc"),
        ),
        out_shape=jax.ShapeDtypeStruct(sum_frame.shape, sum_frame.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(pairs, sum_frame)
