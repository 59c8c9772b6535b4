"""Pallas TPU kernels for paper Algorithms 1 and 2 (the baselines).

Both materialize the intermediate difference frames ``tmpFrame[G][N/2][H,W]``
in HBM (the paper's DRAM array) and reduce them in a second pass, so they
move ~``2 * G * (N/2) * H * W`` extra elements through HBM compared with the
fused Algorithm 3 kernel. They differ in *access granularity* — the TPU
analogue of the AXI4 burst flag:

* **Algorithm 1** ("no burst"): single-row blocks on BOTH passes. Each DMA
  moves one W-row — the closest well-formed TPU analogue of the paper's
  single-beat, per-pixel AXI transactions (a true 1-element DMA is not
  expressible; the per-row degenerate tile keeps the same
  many-small-transfers behaviour).
* **Algorithm 2** ("burst write"): the subtract pass writes tmpFrame with
  large contiguous tiles (burst), but the reduce pass still reads it
  row-at-a-time — matching the paper, where only the write side is burst
  enabled and final-group reads dominate (its Table 1 latency).

These kernels exist for benchmark parity with the paper's Tables 1-4 and to
make the traffic/granularity comparison concrete; production code always
uses ``denoise_stream.alg3_subtract_average``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.tune.budget import pick_row_tile

__all__ = ["alg1_subtract_average", "alg2_subtract_average"]


def _subtract_kernel(f_ref, t_ref, *, offset: float):
    acc = t_ref.dtype
    t_ref[...] = (
        f_ref[1].astype(acc) - f_ref[0].astype(acc) + jnp.asarray(offset, acc)
    )


def _reduce_kernel(t_ref, o_ref, *, num_groups: int):
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += t_ref[...]

    @pl.when(g == num_groups - 1)
    def _finalize():
        o_ref[...] = o_ref[...] / jnp.asarray(num_groups, o_ref.dtype)


def _two_pass(
    frames: jnp.ndarray,
    *,
    offset: float,
    accum_dtype,
    write_tile: int,
    read_tile: int,
    interpret: bool,
    name: str,
):
    g, n, h, w = frames.shape
    p = n // 2
    pairs = frames.reshape(g, p, 2, h, w)
    acc = jnp.dtype(accum_dtype)

    # Pass A: subtract -> tmpFrame in HBM (paper Alg 1/2 line 15 / line 28).
    n_wb = h // write_tile
    assert h % write_tile == 0, (h, write_tile)
    tmp = pl.pallas_call(
        functools.partial(_subtract_kernel, offset=float(offset)),
        grid=(g, p, n_wb),
        in_specs=[
            pl.BlockSpec(
                (None, None, 2, write_tile, w), lambda gi, k, hb: (gi, k, 0, hb, 0)
            )
        ],
        out_specs=pl.BlockSpec(
            (None, None, write_tile, w), lambda gi, k, hb: (gi, k, hb, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((g, p, h, w), acc),
        interpret=interpret,
        name=name,
    )(pairs)

    # Pass B: read tmpFrame back and average (paper line 21).
    n_rb = h // read_tile
    assert h % read_tile == 0, (h, read_tile)
    out = pl.pallas_call(
        functools.partial(_reduce_kernel, num_groups=g),
        grid=(p, n_rb, g),
        in_specs=[
            pl.BlockSpec(
                (None, None, read_tile, w), lambda k, hb, gi: (gi, k, hb, 0)
            )
        ],
        out_specs=pl.BlockSpec((None, read_tile, w), lambda k, hb, gi: (k, hb, 0)),
        out_shape=jax.ShapeDtypeStruct((p, h, w), acc),
        interpret=interpret,
        name=name,
    )(tmp)
    return out


@functools.partial(
    jax.jit, static_argnames=("offset", "accum_dtype", "interpret")
)
def alg1_subtract_average(
    frames: jnp.ndarray,
    *,
    offset: float = 0.0,
    accum_dtype=jnp.float32,
    interpret: bool,
):
    """Algorithm 1: tmpFrame in HBM, single-row (non-burst) R and W."""
    return _two_pass(
        frames,
        offset=offset,
        accum_dtype=accum_dtype,
        write_tile=1,
        read_tile=1,
        interpret=interpret,
        name="alg1_subtract_average",
    )


@functools.partial(
    jax.jit, static_argnames=("offset", "accum_dtype", "row_tile", "interpret")
)
def alg2_subtract_average(
    frames: jnp.ndarray,
    *,
    offset: float = 0.0,
    accum_dtype=jnp.float32,
    row_tile: int | None = None,
    interpret: bool,
):
    """Algorithm 2: burst-mode writes (large tiles), row-granular reads."""
    g, n, h, w = frames.shape
    th = row_tile or pick_row_tile(
        "stream", h, w, in_dtype=frames.dtype, acc_dtype=accum_dtype
    )
    return _two_pass(
        frames,
        offset=offset,
        accum_dtype=accum_dtype,
        write_tile=th,
        read_tile=1,
        interpret=interpret,
        name="alg2_subtract_average",
    )
