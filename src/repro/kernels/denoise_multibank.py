"""Fused multi-bank Pallas kernel: every bank in ONE ``pallas_call``.

The paper scales by giving each 256×80 pixel bank its own FPGA and
observes flat latency because banks never communicate. On a single TPU
core the analogous resource is grid steps, not whole devices: this kernel
covers ``(banks, pair_blocks, row_tiles, groups)`` with one grid, groups
innermost, so

* each bank's accumulator tile stays VMEM-resident across the whole group
  reduction (the matmul-K-loop pattern, per bank);
* banks are outermost — fully independent grid slices, zero cross-bank
  traffic, mirroring the paper's communication-free bank partitioning;
* pair-tiling (see ``denoise_stream``) amortizes per-grid-step overhead
  over several of the paper's small frames per block.

Under ``shard_map`` over a ``bank`` device axis (``repro.core.banks``)
the same kernel runs with the *local* bank count, so one code path covers
single-device multi-bank and one-bank-per-device topologies.

Validated in interpret mode on CPU against a vmapped
``ref.ref_subtract_average``; lowers natively via Mosaic on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import quant, spaces
from repro.tune.budget import resolve_tiles

__all__ = ["multibank_subtract_average", "multibank_stream_step"]


def _in_pixel_bytes(stream_dtype: str) -> float | None:
    return None if stream_dtype == "u16" else quant.wire_pixel_bytes(stream_dtype)


def _mb_kernel(
    f_ref, o_ref, *, num_groups: int, offset: float, divide_first: bool,
    stream_dtype: str,
):
    g = pl.program_id(3)
    acc = o_ref.dtype
    # f_ref: (pair_tile, 2, th, wire_w) for this (bank, pair_block, row_block, group)
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
def multibank_subtract_average(
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
    """frames (B, G, N, H, wire_W) -> (B, N/2, H, W), one fused ``pallas_call``."""
    b, g, n, h, wp = frames.shape
    assert n % 2 == 0, "N must be even"
    p = n // 2
    w = quant.logical_width(wp, stream_dtype)
    pairs = frames.reshape(b, g, p, 2, h, wp)
    th, tp = resolve_tiles(
        "stream", p, h, w, row_tile, pair_tile,
        in_dtype=frames.dtype, acc_dtype=accum_dtype,
        in_pixel_bytes=_in_pixel_bytes(stream_dtype),
    )

    kernel = functools.partial(
        _mb_kernel,
        num_groups=g,
        offset=float(offset),
        divide_first=divide_first,
        stream_dtype=stream_dtype,
    )
    ms = spaces.operand_spaces("stream", placement)
    return pl.pallas_call(
        kernel,
        grid=(b, p // tp, h // th, g),
        in_specs=[
            pl.BlockSpec(
                (None, None, tp, 2, th, wp),
                lambda bi, k, hb, gi: (bi, gi, k, 0, hb, 0),
                memory_space=ms.get("pairs"),
            )
        ],
        out_specs=pl.BlockSpec(
            (None, tp, th, w), lambda bi, k, hb, gi: (bi, k, hb, 0),
            memory_space=ms.get("acc"),
        ),
        out_shape=jax.ShapeDtypeStruct((b, p, h, w), jnp.dtype(accum_dtype)),
        interpret=interpret,
    )(pairs)


def _mb_step_kernel(
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
def multibank_stream_step(
    group_frames: jnp.ndarray,
    sum_frames: jnp.ndarray,
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
    """Fold one group per bank (B, N, H, wire_W) into sums (B, N/2, H, W).

    ``sum_frames`` is donated (input/output aliased) — per step the HBM
    traffic is read in + read sum + write sum, the paper's burst R/W
    schedule, independently per bank.
    """
    b, n, h, wp = group_frames.shape
    p = n // 2
    w = sum_frames.shape[-1]
    pairs = group_frames.reshape(b, p, 2, h, wp)
    th, tp = resolve_tiles(
        "stream", p, h, w, row_tile, pair_tile,
        in_dtype=group_frames.dtype, acc_dtype=sum_frames.dtype,
        in_pixel_bytes=_in_pixel_bytes(stream_dtype),
    )
    kernel = functools.partial(
        _mb_step_kernel,
        num_groups=num_groups,
        offset=float(offset),
        divide_first=divide_first,
        final=final,
        stream_dtype=stream_dtype,
    )
    ms = spaces.operand_spaces("stream", placement)
    return pl.pallas_call(
        kernel,
        grid=(b, p // tp, h // th),
        in_specs=[
            pl.BlockSpec(
                (None, tp, 2, th, wp), lambda bi, k, hb: (bi, k, 0, hb, 0),
                memory_space=ms.get("pairs"),
            ),
            pl.BlockSpec(
                (None, tp, th, w), lambda bi, k, hb: (bi, k, hb, 0),
                memory_space=ms.get("acc"),
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, tp, th, w), lambda bi, k, hb: (bi, k, hb, 0),
            memory_space=ms.get("acc"),
        ),
        out_shape=jax.ShapeDtypeStruct(sum_frames.shape, sum_frames.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(pairs, sum_frames)
