"""Pallas TPU kernel for the EMA + running-variance streaming filter.

One fused pass per incoming group:

* **EMA accumulation** — ``ema' = (1-alpha)*ema + alpha*diff`` per
  (pair, pixel), the recency-weighted alternative to the paper's flat
  group average (bias-corrected at finalize). O(N/2 · H · W) state,
  donated like Alg 3's running sum.
* **Welford/Chan running variance** — per-*pixel* mean and M2 pooled over
  every diff sample seen so far (all pairs × all groups): O(H · W) extra
  state, merged chunk-at-a-time with Chan's parallel update. The variance
  map drives finalize-time shot-noise masking: pixels whose temporal
  variance is far above the sensor-typical level are noise-dominated and
  get shrunk to the pooled long-run mean.

Grid is (row_tiles, pair_blocks) with the pair axis innermost, so the
per-pixel mean/M2 tiles stay VMEM-resident across the whole pair
reduction (the same accumulator-residency pattern as ``denoise_stream``'s
group axis). The merge accumulates through the *output* refs — reading
the aliased input block after the first pair step would reload a stale
HBM copy.

Validated in interpret mode on CPU against the one-pass XLA fallback in
``repro.kernels.ops``; lowers natively via Mosaic on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import quant, spaces
from repro.tune.budget import resolve_tiles

__all__ = ["ema_welford_step"]


def _ema_kernel(
    f_ref,
    ema_ref,
    mean_ref,
    m2_ref,
    prior_ref,
    o_ema,
    o_mean,
    o_m2,
    *,
    alpha: float,
    offset: float,
    pair_tile: int,
    stream_dtype: str,
):
    k = pl.program_id(1)
    acc = o_ema.dtype
    diff = quant.pair_diff_block(
        f_ref[...], offset=offset, accum_dtype=acc, stream_dtype=stream_dtype,
        in_kernel=True,
    )
    a = jnp.asarray(alpha, acc)
    o_ema[...] = ema_ref[...] * (1 - a) + a * diff

    @pl.when(k == 0)
    def _carry_in():
        o_mean[...] = mean_ref[...]
        o_m2[...] = m2_ref[...]

    # Chan's chunk merge: this block contributes pair_tile samples/pixel.
    # prior_ref carries the pre-step sample count as data (a traced value),
    # NOT a static arg — static would recompile the kernel every group.
    n = prior_ref[0, 0] + k.astype(acc) * pair_tile
    m = jnp.asarray(pair_tile, acc)
    chunk_mean = diff.mean(axis=0)
    chunk_m2 = ((diff - chunk_mean[None]) ** 2).sum(axis=0)
    delta = chunk_mean - o_mean[...]
    tot = n + m
    o_mean[...] += delta * (m / tot)
    o_m2[...] += chunk_m2 + delta * delta * (n * m / tot)


@functools.partial(
    jax.jit,
    static_argnames=(
        "alpha",
        "offset",
        "row_tile",
        "pair_tile",
        "stream_dtype",
        "placement",
        "interpret",
    ),
    donate_argnums=(0, 1, 2),
)
def ema_welford_step(
    ema: jnp.ndarray,
    wmean: jnp.ndarray,
    wm2: jnp.ndarray,
    group_frames: jnp.ndarray,
    *,
    alpha: float,
    offset: float = 0.0,
    prior_count=0,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    stream_dtype: str = "u16",
    placement: str | None = None,
    interpret: bool,
):
    """Fold one group into (ema, wmean, wm2); all three state arrays donated.

    ema: (N/2, H, W); wmean/wm2: (H, W) pooled over pairs and groups;
    group_frames: (N, H, wire_W). ``prior_count`` is the number of diff
    samples already folded into wmean/wm2 (= steps_so_far * N/2) — a
    *traced* scalar fed to the kernel as a (1, 1) block (SMEM under the
    default placement: it is control state, not datapath), so the
    per-group value never retraces or recompiles the streaming step.
    """
    p, h, w = ema.shape
    n = group_frames.shape[0]
    assert n == 2 * p, f"group has {n} frames for {p} state pairs"
    wp = group_frames.shape[-1]
    pairs = group_frames.reshape(p, 2, h, wp)
    th, tp = resolve_tiles(
        "ema", p, h, w, row_tile, pair_tile,
        in_dtype=group_frames.dtype, acc_dtype=ema.dtype,
        in_pixel_bytes=(
            None if stream_dtype == "u16"
            else quant.wire_pixel_bytes(stream_dtype)
        ),
    )
    prior = jnp.full((1, 1), prior_count, dtype=ema.dtype)
    kernel = functools.partial(
        _ema_kernel,
        alpha=float(alpha),
        offset=float(offset),
        pair_tile=tp,
        stream_dtype=stream_dtype,
    )
    ms = spaces.operand_spaces("ema", placement)
    return pl.pallas_call(
        kernel,
        grid=(h // th, p // tp),  # pairs innermost: mean/M2 tiles stay resident
        in_specs=[
            pl.BlockSpec(
                (tp, 2, th, wp), lambda hb, k: (k, 0, hb, 0),
                memory_space=ms.get("pairs"),
            ),
            pl.BlockSpec(
                (tp, th, w), lambda hb, k: (k, hb, 0),
                memory_space=ms.get("state"),
            ),
            pl.BlockSpec(
                (th, w), lambda hb, k: (hb, 0), memory_space=ms.get("state")
            ),
            pl.BlockSpec(
                (th, w), lambda hb, k: (hb, 0), memory_space=ms.get("state")
            ),
            pl.BlockSpec(
                (1, 1), lambda hb, k: (0, 0), memory_space=ms.get("prior")
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (tp, th, w), lambda hb, k: (k, hb, 0),
                memory_space=ms.get("state"),
            ),
            pl.BlockSpec(
                (th, w), lambda hb, k: (hb, 0), memory_space=ms.get("state")
            ),
            pl.BlockSpec(
                (th, w), lambda hb, k: (hb, 0), memory_space=ms.get("state")
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(ema.shape, ema.dtype),
            jax.ShapeDtypeStruct(wmean.shape, wmean.dtype),
            jax.ShapeDtypeStruct(wm2.shape, wm2.dtype),
        ],
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret,
    )(pairs, ema, wmean, wm2, prior)
