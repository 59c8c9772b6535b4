"""Pallas TPU kernels for the temporal-median streaming filter.

The filter keeps a sliding window of the last K per-group difference
frames and outputs their per-pixel median — the classic impulse /
cosmic-ray rejector: a spike that corrupts one group's diff lands in one
window slot and is discarded by the rank statistic, where the
subtract-and-*average* path smears it over the output at 1/G amplitude.

Two kernels, both row- and pair-tiled like ``denoise_stream``:

* ``median_window_insert`` — fold one incoming group into the window:
  compute the pairwise diff (exc - ctl + offset, the same arithmetic as
  Alg 3's subtract) and write it into window slot ``slot``. ``slot`` is
  static and the window is donated (``input_output_aliases``), so the
  grid covers only that slot's blocks and the other K-1 slots of the
  aliased buffer are simply left untouched — per-step HBM traffic is
  read N·H·W input + write (N/2)·H·W slot, the same burst R/W schedule
  as Alg 3's running-sum step (not K× it).
* ``median_combine`` — per-pixel median over the leading window axis via
  an odd-even transposition sorting network of ``jnp.minimum``/``maximum``
  pairs (K is static and small, so the network is fully unrolled
  elementwise VPU work; no data-dependent control flow).

Validated in interpret mode on CPU against ``jnp.sort``-based XLA
fallbacks in ``repro.kernels.ops``; lowers natively via Mosaic on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import quant, spaces
from repro.tune.budget import resolve_tiles

__all__ = ["median_window_insert", "median_combine"]


def _insert_kernel(f_ref, w_ref, o_ref, *, offset: float, stream_dtype: str):
    del w_ref  # aliased donor only; never read (out block = slot's block)
    acc = o_ref.dtype
    # f_ref: (tp, 2, th, wire_w) -> diff (tp, th, w) = o_ref block (slot squeezed)
    diff = quant.pair_diff_block(
        f_ref[...], offset=offset, accum_dtype=acc, stream_dtype=stream_dtype,
        in_kernel=True,
    )
    o_ref[...] = diff


@functools.partial(
    jax.jit,
    static_argnames=(
        "slot",
        "offset",
        "row_tile",
        "pair_tile",
        "stream_dtype",
        "placement",
        "interpret",
    ),
    donate_argnums=(0,),
)
def median_window_insert(
    window: jnp.ndarray,
    group_frames: jnp.ndarray,
    *,
    slot: int,
    offset: float = 0.0,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    stream_dtype: str = "u16",
    placement: str | None = None,
    interpret: bool,
):
    """Write the group's diff frames into ``window[slot]`` (window donated).

    window: (K, N/2, H, W) accumulator-dtype ring of past diffs;
    group_frames: (N, H, wire_W). Returns the updated window: the grid
    touches only ``slot``'s blocks; the remaining K-1 slots ride through
    the aliased (donated) buffer untouched. The donor operand is never
    read, so the default placement leaves it in ANY/HBM (only the written
    slot blocks occupy VMEM).
    """
    k_slots, p, h, w = window.shape
    n = group_frames.shape[0]
    assert n == 2 * p, f"group has {n} frames for {p} window pairs"
    assert 0 <= slot < k_slots, f"slot {slot} outside window of {k_slots}"
    wp = group_frames.shape[-1]
    pairs = group_frames.reshape(p, 2, h, wp)
    th, tp = resolve_tiles(
        "median_insert", p, h, w, row_tile, pair_tile,
        in_dtype=group_frames.dtype, acc_dtype=window.dtype,
        in_pixel_bytes=(
            None if stream_dtype == "u16"
            else quant.wire_pixel_bytes(stream_dtype)
        ),
    )
    kernel = functools.partial(
        _insert_kernel, offset=float(offset), stream_dtype=stream_dtype
    )
    ms = spaces.operand_spaces("median_insert", placement)
    # aliased donor; the kernel never reads it. Left in ANY/HBM it is the
    # whole unblocked window (Mosaic takes no blocks there).
    if ms.get("donor") is pl.ANY:
        donor = pl.BlockSpec(memory_space=pl.ANY)
    else:
        donor = pl.BlockSpec(
            (None, tp, th, w), lambda k, hb: (slot, k, hb, 0),
            memory_space=ms.get("donor"),
        )
    return pl.pallas_call(
        kernel,
        grid=(p // tp, h // th),
        in_specs=[
            pl.BlockSpec(
                (tp, 2, th, wp), lambda k, hb: (k, 0, hb, 0),
                memory_space=ms.get("pairs"),
            ),
            donor,
        ],
        out_specs=pl.BlockSpec(
            (None, tp, th, w), lambda k, hb: (slot, k, hb, 0),
            memory_space=ms.get("slot"),
        ),
        out_shape=jax.ShapeDtypeStruct(window.shape, window.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(pairs, window)


def _median_kernel(w_ref, o_ref, *, count: int):
    # Odd-even transposition sort over the (static, small) window axis:
    # pure min/max elementwise passes, fully unrolled — no sort primitive.
    vals = [w_ref[i] for i in range(count)]
    for rnd in range(count):
        start = rnd % 2
        for i in range(start, count - 1, 2):
            lo = jnp.minimum(vals[i], vals[i + 1])
            hi = jnp.maximum(vals[i], vals[i + 1])
            vals[i], vals[i + 1] = lo, hi
    if count % 2:
        o_ref[...] = vals[count // 2]
    else:
        mid = vals[count // 2 - 1] + vals[count // 2]
        o_ref[...] = mid / jnp.asarray(2, o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("row_tile", "pair_tile", "placement", "interpret"),
)
def median_combine(
    window: jnp.ndarray,
    *,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    placement: str | None = None,
    interpret: bool,
):
    """(K, N/2, H, W) window -> (N/2, H, W) per-pixel median over K.

    Callers slice the window to its filled prefix first; K here is the
    number of *valid* entries. Even K averages the two middle ranks
    (matching ``jnp.sort``-based fallback arithmetic exactly).
    """
    k_slots, p, h, w = window.shape
    th, tp = resolve_tiles(
        "median_combine", p, h, w, row_tile, pair_tile,
        acc_dtype=window.dtype, window=k_slots,
    )
    kernel = functools.partial(_median_kernel, count=k_slots)
    ms = spaces.operand_spaces("median_combine", placement)
    return pl.pallas_call(
        kernel,
        grid=(p // tp, h // th),
        in_specs=[
            pl.BlockSpec(
                (k_slots, tp, th, w), lambda k, hb: (0, k, hb, 0),
                memory_space=ms.get("window"),
            ),
        ],
        out_specs=pl.BlockSpec(
            (tp, th, w), lambda k, hb: (k, hb, 0),
            memory_space=ms.get("out"),
        ),
        out_shape=jax.ShapeDtypeStruct((p, h, w), window.dtype),
        interpret=interpret,
    )(window)
