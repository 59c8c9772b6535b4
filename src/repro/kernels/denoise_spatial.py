"""Pallas TPU kernel for the post-average spatial box / bilateral-lite filter.

3×3 neighborhood smoothing applied to the *averaged* output frames —
the stage that repairs defects temporal filtering cannot (a stuck/hot
pixel is wrong in every frame, so its only good estimate is its spatial
neighbors). Two modes:

* ``box`` — plain 3×3 mean (uniform weights).
* ``bilateral`` — bilateral-lite: uniform spatial support with a
  Gaussian *range* kernel ``exp(-(x_i - x_c)^2 / (2 sigma_r^2))``, so
  smoothing stops at edges (the checkerboard pattern survives) while
  isolated outliers — far from all neighbors — are pulled to them.

The grid is (pair_blocks, row_tiles) and the halo problem is solved with
clamped *neighbor-tile* BlockSpecs: the same input is passed three times
with row-block index maps ``hb``, ``max(hb-1, 0)`` and
``min(hb+1, last)``, so the kernel sees the adjacent row tiles without
overlapping blocks; image edges replicate (``jnp.where`` on the block
id). Column neighbors are lane-shifted concats with edge replication.
Everything is elementwise VPU work — no gather, no data-dependent control
flow.

Validated in interpret mode on CPU against the padded-shift XLA fallback
in ``repro.kernels.ops``; lowers natively via Mosaic on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import spaces
from repro.tune.budget import resolve_tiles

__all__ = ["spatial_filter_3x3"]


def _shift_cols(x: jnp.ndarray, direction: int) -> jnp.ndarray:
    """Shift along the lane axis with edge replication. direction -1 gives
    the left neighbor (x[..., j-1]), +1 the right neighbor."""
    if direction == -1:
        return jnp.concatenate([x[..., :1], x[..., :-1]], axis=-1)
    if direction == 1:
        return jnp.concatenate([x[..., 1:], x[..., -1:]], axis=-1)
    return x


def _spatial_kernel(
    me_ref,
    up_ref,
    dn_ref,
    o_ref,
    *,
    mode: str,
    range_sigma: float,
    num_row_blocks: int,
):
    hb = pl.program_id(1)
    x = me_ref[...]  # (tp, th, w)
    # Halo rows from the neighbor tiles; replicate at the image edges.
    top = jnp.where(hb == 0, x[:, :1], up_ref[:, -1:])
    bot = jnp.where(hb == num_row_blocks - 1, x[:, -1:], dn_ref[:, :1])
    ext = jnp.concatenate([top, x, bot], axis=1)  # (tp, th + 2, w)
    th = x.shape[1]
    rows = [ext[:, r : r + th] for r in range(3)]
    neighbors = [_shift_cols(r, d) for r in rows for d in (-1, 0, 1)]
    if mode == "box":
        o_ref[...] = sum(neighbors) / jnp.asarray(9, x.dtype)
    else:  # bilateral-lite: uniform spatial support, Gaussian range kernel
        inv2s2 = jnp.asarray(1.0 / (2.0 * range_sigma * range_sigma), x.dtype)
        acc = jnp.zeros_like(x)
        wsum = jnp.zeros_like(x)
        for nb in neighbors:
            wgt = jnp.exp(-((nb - x) ** 2) * inv2s2)
            acc += wgt * nb
            wsum += wgt
        o_ref[...] = acc / wsum  # wsum >= 1: the center weight is exactly 1


@functools.partial(
    jax.jit,
    static_argnames=(
        "mode",
        "range_sigma",
        "row_tile",
        "pair_tile",
        "placement",
        "interpret",
    ),
)
def spatial_filter_3x3(
    frames: jnp.ndarray,
    *,
    mode: str = "box",
    range_sigma: float = 50.0,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    placement: str | None = None,
    interpret: bool,
):
    """(P, H, W) -> (P, H, W): 3×3 box or bilateral-lite smoothing per frame.

    ``row_tile`` must divide H; the default picks the largest divisor of H
    within the shared VMEM budget for the "spatial" family (three halo
    views + the output block — the old private picker under-counted this
    working set; 1-row tiles still work: the clamped neighbor specs
    deliver single-row halos).
    """
    p, h, w = frames.shape
    th, tp = resolve_tiles(
        "spatial", p, h, w, row_tile, pair_tile,
        in_dtype=frames.dtype, acc_dtype=frames.dtype,
    )
    nhb = h // th
    kernel = functools.partial(
        _spatial_kernel,
        mode=mode,
        range_sigma=float(range_sigma),
        num_row_blocks=nhb,
    )
    last = nhb - 1
    ms = spaces.operand_spaces("spatial", placement)
    return pl.pallas_call(
        kernel,
        grid=(p // tp, nhb),
        in_specs=[
            pl.BlockSpec(
                (tp, th, w), lambda k, hb: (k, hb, 0),
                memory_space=ms.get("halo"),
            ),
            pl.BlockSpec(
                (tp, th, w), lambda k, hb: (k, jnp.maximum(hb - 1, 0), 0),
                memory_space=ms.get("halo"),
            ),
            pl.BlockSpec(
                (tp, th, w), lambda k, hb: (k, jnp.minimum(hb + 1, last), 0),
                memory_space=ms.get("halo"),
            ),
        ],
        out_specs=pl.BlockSpec(
            (tp, th, w), lambda k, hb: (k, hb, 0),
            memory_space=ms.get("out"),
        ),
        out_shape=jax.ShapeDtypeStruct(frames.shape, frames.dtype),
        interpret=interpret,
        name="spatial_filter_3x3",
    )(frames, frames, frames)
