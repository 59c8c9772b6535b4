"""Public jit'd entry points for the denoise kernels.

Dispatch layers:

* ``backend='pallas'`` — the Pallas kernels (native Mosaic on TPU,
  ``interpret=True`` on CPU so the identical kernel body is validated here).
* ``backend='xla'``   — dataflow-faithful pure-XLA implementations. These
  preserve each algorithm's *memory behaviour* (Alg 1/2 materialize the
  (G, N/2, H, W) tmpFrame array — enforced with an optimization barrier so
  XLA cannot fuse the two passes; Alg 3 is a running-sum scan with O(N/2·H·W)
  state), which is what the paper's comparison measures.
* ``backend='auto'``  — pallas on TPU, xla elsewhere.

Multi-bank entry points (``multibank_*``) carry a leading bank axis
(B, ...) and take the fast path on every backend: one fused ``pallas_call``
whose grid covers (banks, pairs, rows, groups) on TPU, a fused
batched/vectorized XLA program elsewhere (NOT the per-group reference
scan — banks and pairs vectorize, subtract fuses into the reduction).
``repro.core.banks`` wraps these in ``shard_map`` so the same code runs
one-bank-per-device, matching the paper's one-FPGA-per-bank topology.

This module is the backend boundary: everything above it —
``repro.core.denoise`` (config + streaming state), the executors in
``repro.core.streaming`` (inline / ring-pipelined / buffered), and
``repro.core.banks`` — dispatches through these entry points and never
imports a kernel module directly. ``ALGORITHMS`` / ``BACKENDS`` /
``TILE_PLANS`` enumerate the valid ``algorithm`` / ``backend`` /
``tile_plan`` strings accepted everywhere a ``DenoiseConfig`` is
consumed. See docs/ARCHITECTURE.md for the full layer map.

**Block geometry** (``row_tile`` / ``pair_tile``) is static at every
entry point. Callers resolve it once at config time via the tuning layer
(``repro.tune``): ``tile_plan="heuristic"`` passes ``None`` through and
the kernels fall back to the shared per-family VMEM budget model
(``repro.tune.budget``); ``tile_plan="auto"`` passes a measured (or
plan-cache-replayed) geometry; an explicit path replays a pre-built plan
file. Either way the values arriving here are plain static ints — a
resolved plan can never retrace a jitted step mid-stream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import (
    denoise_ema,
    denoise_median,
    denoise_multibank,
    denoise_spatial,
    denoise_stream,
    denoise_tmpframe,
    quant,
)
from repro.kernels.quant import (  # noqa: F401  (shared dequant prologue)
    STREAM_DTYPES,
    dequant,
    pair_diff_block,
)
from repro.kernels.ref import ref_stream_finalize, ref_stream_init, ref_stream_step

__all__ = [
    "ALGORITHMS",
    "BACKENDS",
    "SPATIAL_MODES",
    "STREAM_DTYPES",
    "TILE_PLANS",
    "subtract_average",
    "stream_init",
    "stream_step",
    "stream_finalize",
    "multibank_subtract_average",
    "multibank_stream_init",
    "multibank_stream_step",
    "pair_diff",
    "dequant",
    "pair_diff_block",
    "median_window_insert",
    "median_combine",
    "ema_welford_step",
    "spatial_filter",
]

ALGORITHMS = ("alg1", "alg2", "alg3", "alg3_v2")
BACKENDS = ("auto", "pallas", "xla")
SPATIAL_MODES = ("box", "bilateral")
# tile-plan modes; any other (non-empty) string is a pre-built plan-file
# path replayed by repro.tune.resolve_plan
TILE_PLANS = ("heuristic", "auto")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(backend: str) -> str:
    if backend == "auto":
        return "pallas" if _on_tpu() else "xla"
    if backend not in ("pallas", "xla"):
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend}")
    return backend


# ---------------------------------------------------------------------------
# Dataflow-faithful XLA implementations.
# ---------------------------------------------------------------------------


def _xla_materialized(frames, *, offset, accum_dtype, stream_dtype="u16"):
    """Alg 1/2 dataflow: build tmpFrame fully, then reduce it (two passes)."""
    g = frames.shape[0]
    acc = jnp.dtype(accum_dtype)
    tmp = pair_diff(
        frames, offset=offset, accum_dtype=acc, stream_dtype=stream_dtype
    )
    # Force materialization: without this XLA fuses subtract+reduce into the
    # Alg-3 dataflow and the baseline measures nothing.
    tmp = jax.lax.optimization_barrier(tmp)
    return tmp.sum(axis=0) / jnp.asarray(g, acc)


def _xla_streaming(frames, *, offset, accum_dtype, divide_first, stream_dtype="u16"):
    """Alg 3 dataflow: scan groups, running sum, single pass over inputs.

    Narrow wire formats dequantize per group inside the scan body (the
    shared prologue), so the full-stream f32 copy is never materialized —
    the streaming dataflow this path exists to measure is preserved.
    """
    g = frames.shape[0]
    acc = jnp.dtype(accum_dtype)
    variant = "divide_first" if divide_first else "divide_last"

    def body(s, group):
        if stream_dtype != "u16":
            group = quant.dequant(group, stream_dtype, acc)
        return (
            ref_stream_step(
                s, group, offset=offset, variant=variant, num_groups=g
            ),
            None,
        )

    w = quant.logical_width(frames.shape[-1], stream_dtype)
    init = jnp.zeros((frames.shape[1] // 2, frames.shape[2], w), acc)
    total, _ = jax.lax.scan(body, init, frames)
    return ref_stream_finalize(total, g, variant=variant)


def _xla_materialized_banked(frames, *, offset, accum_dtype, stream_dtype="u16"):
    """Banked Alg 1/2 dataflow: materialize all diffs, reduce late.

    Written directly on the 5-D array (not vmap of the 4-D version:
    ``optimization_barrier`` has no batching rule on older JAX).
    """
    g = frames.shape[1]
    acc = jnp.dtype(accum_dtype)
    tmp = pair_diff(
        frames, offset=offset, accum_dtype=acc, stream_dtype=stream_dtype
    )
    tmp = jax.lax.optimization_barrier(tmp)
    return tmp.sum(axis=1) / jnp.asarray(g, acc)


def _xla_fused_banked(
    frames, *, offset, accum_dtype, divide_first, stream_dtype="u16"
):
    """Fused multi-bank path: (B, G, N, H, W) -> (B, N/2, H, W), one pass.

    Unlike the reference scan this lets XLA fuse the pair subtraction into
    the group reduction — no per-group dispatch, no materialized diffs.
    """
    g = frames.shape[1]
    acc = jnp.dtype(accum_dtype)
    diff = pair_diff(
        frames, offset=offset, accum_dtype=acc, stream_dtype=stream_dtype
    )
    gg = jnp.asarray(g, acc)
    if jnp.issubdtype(acc, jnp.integer):
        if divide_first:
            return (diff // gg).sum(axis=1, dtype=acc)
        return diff.sum(axis=1, dtype=acc) // gg
    if divide_first:
        return (diff / gg).sum(axis=1, dtype=acc)
    return diff.sum(axis=1, dtype=acc) / gg


@functools.partial(
    jax.jit,
    static_argnames=(
        "offset",
        "algorithm",
        "backend",
        "accum_dtype",
        "interpret",
        "row_tile",
        "pair_tile",
        "stream_dtype",
        "placement",
    ),
)
def subtract_average(
    frames: jnp.ndarray,
    *,
    offset: float = 0.0,
    algorithm: str = "alg3",
    backend: str = "auto",
    accum_dtype=jnp.float32,
    interpret: bool | None = None,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    stream_dtype: str = "u16",
    placement: str | None = None,
) -> jnp.ndarray:
    """PRISM denoise: (G, N, H, wire_W) frames -> (N/2, H, W) averaged diffs.

    ``row_tile`` / ``pair_tile`` override the Pallas block geometry (Alg 3
    kernels only; XLA has no tiles and ignores them). Narrow
    ``stream_dtype`` wire formats are dequantized in-VMEM by the Alg 3
    Pallas kernel; the Alg 1/2 *Pallas* baselines deliberately have no
    dequant path (they exist for dataflow comparison) — requesting one
    explicitly is an error, while the XLA fallbacks decode every format.
    """
    if algorithm not in ALGORITHMS:
        raise ValueError(f"algorithm must be one of {ALGORITHMS}, got {algorithm}")
    backend = _resolve(backend)
    interp = (not _on_tpu()) if interpret is None else interpret
    if backend == "pallas":
        if algorithm in ("alg1", "alg2"):
            if stream_dtype != "u16":
                raise ValueError(
                    f"no {stream_dtype!r} ingest for the {algorithm} pallas "
                    "baseline; use backend='xla' or stream_dtype='u16'"
                )
            if not interp:
                raise ValueError(
                    f"the {algorithm} pallas baseline moves single-row "
                    "blocks, which Mosaic refuses on TPU; use backend='xla' "
                    "or interpret=True"
                )
            fn = (
                denoise_tmpframe.alg1_subtract_average
                if algorithm == "alg1"
                else denoise_tmpframe.alg2_subtract_average
            )
            return fn(
                frames, offset=offset, accum_dtype=accum_dtype, interpret=interp
            )
        return denoise_stream.alg3_subtract_average(
            frames,
            offset=offset,
            divide_first=(algorithm == "alg3_v2"),
            accum_dtype=accum_dtype,
            interpret=interp,
            row_tile=row_tile,
            pair_tile=pair_tile,
            stream_dtype=stream_dtype,
            placement=placement,
        )
    if algorithm in ("alg1", "alg2"):
        return _xla_materialized(
            frames, offset=offset, accum_dtype=accum_dtype,
            stream_dtype=stream_dtype,
        )
    return _xla_streaming(
        frames,
        offset=offset,
        accum_dtype=accum_dtype,
        divide_first=(algorithm == "alg3_v2"),
        stream_dtype=stream_dtype,
    )


# ---------------------------------------------------------------------------
# Streaming API (one group per call — the production/camera entry point).
# ---------------------------------------------------------------------------


def stream_init(n: int, h: int, w: int, accum_dtype=jnp.float32) -> jnp.ndarray:
    return ref_stream_init(n, h, w, accum_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_groups",
        "offset",
        "variant",
        "backend",
        "interpret",
        "row_tile",
        "pair_tile",
        "stream_dtype",
        "placement",
    ),
    donate_argnums=(0,),
)
def stream_step(
    sum_frame: jnp.ndarray,
    group_frames: jnp.ndarray,
    *,
    num_groups: int,
    offset: float = 0.0,
    variant: str = "divide_last",
    backend: str = "auto",
    interpret: bool | None = None,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    stream_dtype: str = "u16",
    placement: str | None = None,
) -> jnp.ndarray:
    backend = _resolve(backend)
    interp = (not _on_tpu()) if interpret is None else interpret
    if backend == "pallas":
        return denoise_stream.alg3_stream_step(
            group_frames,
            sum_frame,
            num_groups=num_groups,
            offset=offset,
            divide_first=(variant == "divide_first"),
            interpret=interp,
            row_tile=row_tile,
            pair_tile=pair_tile,
            stream_dtype=stream_dtype,
            placement=placement,
        )
    if stream_dtype != "u16":
        group_frames = quant.dequant(group_frames, stream_dtype, sum_frame.dtype)
    return ref_stream_step(
        sum_frame,
        group_frames,
        offset=offset,
        variant=variant,
        num_groups=num_groups,
    )


def stream_finalize(sum_frame, num_groups, *, variant="divide_last"):
    return ref_stream_finalize(sum_frame, num_groups, variant=variant)


# ---------------------------------------------------------------------------
# Multi-bank API: leading bank axis, fast path on every backend. Called
# either directly (many banks on one device) or per-shard inside
# ``repro.core.banks``'s shard_map (one bank slice per device).
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "offset",
        "algorithm",
        "backend",
        "accum_dtype",
        "interpret",
        "row_tile",
        "pair_tile",
        "stream_dtype",
        "placement",
    ),
)
def multibank_subtract_average(
    frames: jnp.ndarray,
    *,
    offset: float = 0.0,
    algorithm: str = "alg3",
    backend: str = "auto",
    accum_dtype=jnp.float32,
    interpret: bool | None = None,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    stream_dtype: str = "u16",
    placement: str | None = None,
) -> jnp.ndarray:
    """(B, G, N, H, wire_W) -> (B, N/2, H, W), banks independent (zero traffic).

    Only the Alg 3 variants have a fused multi-bank Pallas kernel; the
    Alg 1/2 baselines exist for dataflow comparison and run the vmapped
    materialized XLA path under ``backend='auto'``. Requesting
    ``backend='pallas'`` for them explicitly is an error rather than a
    silent fallback.
    """
    if algorithm not in ALGORITHMS:
        raise ValueError(f"algorithm must be one of {ALGORITHMS}, got {algorithm}")
    if backend == "pallas" and algorithm in ("alg1", "alg2"):
        raise ValueError(
            f"no multibank pallas kernel for {algorithm}; use backend='auto'/"
            "'xla' (vmapped materialized baseline) or the single-bank "
            "subtract_average"
        )
    backend = _resolve(backend)
    interp = (not _on_tpu()) if interpret is None else interpret
    divide_first = algorithm == "alg3_v2"
    if backend == "pallas" and algorithm in ("alg3", "alg3_v2"):
        return denoise_multibank.multibank_subtract_average(
            frames,
            offset=offset,
            divide_first=divide_first,
            accum_dtype=accum_dtype,
            interpret=interp,
            row_tile=row_tile,
            pair_tile=pair_tile,
            stream_dtype=stream_dtype,
            placement=placement,
        )
    if algorithm in ("alg1", "alg2"):
        return _xla_materialized_banked(
            frames, offset=offset, accum_dtype=accum_dtype,
            stream_dtype=stream_dtype,
        )
    return _xla_fused_banked(
        frames, offset=offset, accum_dtype=accum_dtype,
        divide_first=divide_first, stream_dtype=stream_dtype,
    )


def multibank_stream_init(
    banks: int, n: int, h: int, w: int, accum_dtype=jnp.float32
) -> jnp.ndarray:
    """Running-sum state with a leading bank axis: (B, N/2, H, W) zeros."""
    return jnp.zeros((banks, n // 2, h, w), dtype=jnp.dtype(accum_dtype))


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_groups",
        "offset",
        "variant",
        "backend",
        "interpret",
        "row_tile",
        "pair_tile",
        "stream_dtype",
        "placement",
    ),
    donate_argnums=(0,),
)
def multibank_stream_step(
    sum_frames: jnp.ndarray,
    group_frames: jnp.ndarray,
    *,
    num_groups: int,
    offset: float = 0.0,
    variant: str = "divide_last",
    backend: str = "auto",
    interpret: bool | None = None,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    stream_dtype: str = "u16",
    placement: str | None = None,
) -> jnp.ndarray:
    """Fold one group per bank (B, N, H, wire_W) into donated sums (B, N/2, H, W)."""
    backend = _resolve(backend)
    interp = (not _on_tpu()) if interpret is None else interpret
    if backend == "pallas":
        return denoise_multibank.multibank_stream_step(
            group_frames,
            sum_frames,
            num_groups=num_groups,
            offset=offset,
            divide_first=(variant == "divide_first"),
            interpret=interp,
            row_tile=row_tile,
            pair_tile=pair_tile,
            stream_dtype=stream_dtype,
            placement=placement,
        )
    if stream_dtype != "u16":
        group_frames = quant.dequant(group_frames, stream_dtype, sum_frames.dtype)
    # vectorized over the bank axis; subtract fuses into the accumulate
    return ref_stream_step(
        sum_frames,
        group_frames,
        offset=offset,
        variant=variant,
        num_groups=num_groups,
    )


# ---------------------------------------------------------------------------
# Streaming-filter kernels (repro.denoise): each entry point pairs a Pallas
# kernel with a dataflow-faithful XLA fallback, dispatched exactly like the
# subtract-average paths above. The filter subsystem never imports a kernel
# module directly — this is its backend boundary too.
# ---------------------------------------------------------------------------


def pair_diff(
    group_frames: jnp.ndarray,
    *,
    offset: float,
    accum_dtype,
    stream_dtype: str = "u16",
) -> jnp.ndarray:
    """(..., N, H, wire_W) -> (..., N/2, H, W): exc - ctl + offset (pure XLA).

    The shared subtraction step of every filter's XLA fallback; the Pallas
    paths fuse the same prologue (``pair_diff_block``) into their kernels,
    so narrow wire formats decode identically on both backends.
    """
    acc = jnp.dtype(accum_dtype)
    shape = group_frames.shape
    pairs = group_frames.reshape(shape[:-3] + (shape[-3] // 2, 2) + shape[-2:])
    return quant.pair_diff_block(
        pairs, offset=offset, accum_dtype=acc, stream_dtype=stream_dtype
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "slot",
        "offset",
        "backend",
        "interpret",
        "row_tile",
        "pair_tile",
        "stream_dtype",
        "placement",
    ),
    donate_argnums=(0,),
)
def median_window_insert(
    window: jnp.ndarray,
    group_frames: jnp.ndarray,
    *,
    slot: int,
    offset: float = 0.0,
    backend: str = "auto",
    interpret: bool | None = None,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    stream_dtype: str = "u16",
    placement: str | None = None,
) -> jnp.ndarray:
    """Fold one group's diffs into slot ``slot`` of the (K, N/2, H, W) window."""
    backend = _resolve(backend)
    if backend == "pallas":
        interp = (not _on_tpu()) if interpret is None else interpret
        return denoise_median.median_window_insert(
            window,
            group_frames,
            slot=slot,
            offset=offset,
            row_tile=row_tile,
            pair_tile=pair_tile,
            stream_dtype=stream_dtype,
            placement=placement,
            interpret=interp,
        )
    diff = pair_diff(
        group_frames, offset=offset, accum_dtype=window.dtype,
        stream_dtype=stream_dtype,
    )
    return window.at[slot].set(diff)


@functools.partial(
    jax.jit,
    static_argnames=("backend", "interpret", "row_tile", "pair_tile", "placement"),
)
def median_combine(
    window: jnp.ndarray,
    *,
    backend: str = "auto",
    interpret: bool | None = None,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    placement: str | None = None,
) -> jnp.ndarray:
    """(K, N/2, H, W) -> (N/2, H, W): per-pixel median over the window axis.

    Callers slice the window to its filled prefix first. Even window
    lengths average the two middle ranks on both backends.
    """
    backend = _resolve(backend)
    if backend == "pallas":
        interp = (not _on_tpu()) if interpret is None else interpret
        return denoise_median.median_combine(
            window, row_tile=row_tile, pair_tile=pair_tile,
            placement=placement, interpret=interp,
        )
    k = window.shape[0]
    srt = jnp.sort(window, axis=0)
    if k % 2:
        return srt[k // 2]
    return (srt[k // 2 - 1] + srt[k // 2]) / jnp.asarray(2, window.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "alpha",
        "offset",
        "backend",
        "interpret",
        "row_tile",
        "pair_tile",
        "stream_dtype",
        "placement",
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
    backend: str = "auto",
    interpret: bool | None = None,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    stream_dtype: str = "u16",
    placement: str | None = None,
):
    """One fused EMA + Welford/Chan update; (ema, wmean, wm2) donated.

    ema: (N/2, H, W); wmean/wm2: (H, W) pooled over pairs × groups;
    ``prior_count`` = diff samples already folded in (steps * N/2) — a
    traced scalar, so the per-step value never retraces the jit (one
    compile serves the whole stream).
    """
    backend = _resolve(backend)
    if backend == "pallas":
        interp = (not _on_tpu()) if interpret is None else interpret
        return denoise_ema.ema_welford_step(
            ema,
            wmean,
            wm2,
            group_frames,
            alpha=alpha,
            offset=offset,
            prior_count=prior_count,
            row_tile=row_tile,
            pair_tile=pair_tile,
            stream_dtype=stream_dtype,
            placement=placement,
            interpret=interp,
        )
    acc = ema.dtype
    diff = pair_diff(
        group_frames, offset=offset, accum_dtype=acc, stream_dtype=stream_dtype
    )
    a = jnp.asarray(alpha, acc)
    new_ema = ema * (1 - a) + a * diff
    # Chan chunk merge with the whole group's N/2 samples per pixel at once
    # (the one-pass form; the Pallas kernel merges pair_tile at a time).
    m = jnp.asarray(diff.shape[0], acc)
    n = jnp.asarray(prior_count, acc)
    chunk_mean = diff.mean(axis=0)
    chunk_m2 = ((diff - chunk_mean[None]) ** 2).sum(axis=0)
    delta = chunk_mean - wmean
    tot = n + m
    new_mean = wmean + delta * (m / tot)
    new_m2 = wm2 + chunk_m2 + delta * delta * (n * m / tot)
    return new_ema, new_mean, new_m2


@functools.partial(
    jax.jit,
    static_argnames=(
        "mode",
        "range_sigma",
        "backend",
        "interpret",
        "row_tile",
        "pair_tile",
        "placement",
    ),
)
def spatial_filter(
    frames: jnp.ndarray,
    *,
    mode: str = "box",
    range_sigma: float = 50.0,
    backend: str = "auto",
    interpret: bool | None = None,
    row_tile: int | None = None,
    pair_tile: int | None = None,
    placement: str | None = None,
) -> jnp.ndarray:
    """(P, H, W) -> (P, H, W): 3×3 box or bilateral-lite smoothing."""
    if mode not in SPATIAL_MODES:
        raise ValueError(f"mode must be one of {SPATIAL_MODES}, got {mode}")
    backend = _resolve(backend)
    if backend == "pallas":
        interp = (not _on_tpu()) if interpret is None else interpret
        return denoise_spatial.spatial_filter_3x3(
            frames,
            mode=mode,
            range_sigma=range_sigma,
            row_tile=row_tile,
            pair_tile=pair_tile,
            placement=placement,
            interpret=interp,
        )
    p, h, w = frames.shape
    pad = jnp.pad(frames, ((0, 0), (1, 1), (1, 1)), mode="edge")
    neighbors = [
        pad[:, r : r + h, c : c + w] for r in range(3) for c in range(3)
    ]
    if mode == "box":
        return sum(neighbors) / jnp.asarray(9, frames.dtype)
    inv2s2 = jnp.asarray(1.0 / (2.0 * range_sigma * range_sigma), frames.dtype)
    acc = jnp.zeros_like(frames)
    wsum = jnp.zeros_like(frames)
    for nb in neighbors:
        wgt = jnp.exp(-((nb - frames) ** 2) * inv2s2)
        acc += wgt * nb
        wsum += wgt
    return acc / wsum
