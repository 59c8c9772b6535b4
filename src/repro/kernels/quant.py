"""Quantized-ingest wire formats: the HBM->VMEM half of the bandwidth tier.

The paper's central claim is bandwidth engineering — the denoise kernels
sit well below the HBM roofline, so the next lever is moving fewer bytes
per frame. This module defines the ``stream_dtype`` axis every ingest
kernel and the acquisition source share:

==========  =================  ==============================================
dtype       wire format        semantics
==========  =================  ==============================================
``"u16"``   uint16, W pixels   today's mono12-in-u16 containers (bit-exact)
``"u8"``    uint8,  W pixels   12->8-bit quantization, ``q = round(v/S)``
                               with ``S = MONO12_MAX/255`` so 0 and 4095
                               round-trip exactly; max abs error S/2 (lossy)
``"p12"``   uint8, 3W/2 bytes  two 12-bit pixels packed into 3 bytes along
                               W (W must be even); exact for all 0..4095
==========  =================  ==============================================

Layering: this module sits *below* both sides of the wire. The host side
(``repro.data.prism``) calls the numpy ``encode``/``decode`` pair; the
device side calls the traced ``dequant``/``pair_diff_block`` prologue —
the ONE dequantization implementation every Pallas kernel family and
every XLA fallback shares (re-exported through ``repro.kernels.ops``), so
a narrow container can never decode two different ways. ``dequant`` runs
on VMEM-resident block *values* inside the kernels: narrow bytes cross
HBM->VMEM, pixels widen on-chip — that is the entire point.

``MONO12_MAX`` lives here (not ``repro.core.denoise``, which re-exports
it) because both the kernels and the config layer need it and the config
layer already imports the kernels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "MONO12_MAX",
    "STREAM_DTYPES",
    "U8_SCALE",
    "validate_stream_dtype",
    "container_dtype",
    "container_name",
    "wire_pixel_bytes",
    "wire_width",
    "logical_width",
    "encode",
    "decode",
    "dequant",
    "pair_diff_block",
]

MONO12_MAX = 4095  # 12-bit pixels wrapped in u16 containers (paper §6)

#: valid ``DenoiseConfig.stream_dtype`` values, widest first
STREAM_DTYPES = ("u16", "u8", "p12")

#: u8 quantization step: 4095/255, so both range endpoints are exact
#: (``round(0/S)=0``, ``round(4095/S)=255``) and the bounded-error
#: property ``|dequant(encode(v)) - v| <= S/2`` holds for all of 0..4095.
U8_SCALE = MONO12_MAX / 255.0

_CONTAINERS = {"u16": np.uint16, "u8": np.uint8, "p12": np.uint8}
#: cache-key spellings (``repro.tune.plan.family_key``): "u16" maps to the
#: pre-tier "uint16" so existing plan caches stay valid
_NAMES = {"u16": "uint16", "u8": "uint8", "p12": "pack12"}
_PIXEL_BYTES = {"u16": 2.0, "u8": 1.0, "p12": 1.5}


def validate_stream_dtype(stream_dtype: str) -> str:
    if stream_dtype not in STREAM_DTYPES:
        raise ValueError(
            f"stream_dtype must be one of {STREAM_DTYPES}, got "
            f"{stream_dtype!r}"
        )
    return stream_dtype


def container_dtype(stream_dtype: str) -> np.dtype:
    """Numpy dtype of the wire container."""
    return np.dtype(_CONTAINERS[validate_stream_dtype(stream_dtype)])


def container_name(stream_dtype: str) -> str:
    """Plan-cache key spelling of the wire format (see ``family_key``)."""
    return _NAMES[validate_stream_dtype(stream_dtype)]


def wire_pixel_bytes(stream_dtype: str) -> float:
    """Wire bytes per logical pixel (1.5 for the packed-12-bit format)."""
    return _PIXEL_BYTES[validate_stream_dtype(stream_dtype)]


def wire_width(width: int, stream_dtype: str) -> int:
    """Wire-format minor-axis length for ``width`` logical pixels."""
    validate_stream_dtype(stream_dtype)
    if stream_dtype != "p12":
        return width
    if width % 2:
        raise ValueError(f"p12 packing needs an even width, got {width}")
    return width // 2 * 3


def logical_width(wire_w: int, stream_dtype: str) -> int:
    """Inverse of :func:`wire_width`."""
    validate_stream_dtype(stream_dtype)
    if stream_dtype != "p12":
        return wire_w
    if wire_w % 3:
        raise ValueError(f"p12 wire width must be a multiple of 3, got {wire_w}")
    return wire_w // 3 * 2


# ---------------------------------------------------------------------------
# Host side (numpy): what PrismSource emits / tests decode.
# ---------------------------------------------------------------------------


def encode(frames: np.ndarray, stream_dtype: str) -> np.ndarray:
    """u16 mono12 frames ``(..., W)`` -> wire containers.

    ``"u16"`` returns the input unchanged (byte-identical fast path, no
    copy), so every pre-tier caller keeps its exact stream.
    """
    validate_stream_dtype(stream_dtype)
    if stream_dtype == "u16":
        return frames
    frames = np.asarray(frames)
    if stream_dtype == "u8":
        return np.clip(
            np.round(frames.astype(np.float64) / U8_SCALE), 0, 255
        ).astype(np.uint8)
    # p12: two 12-bit pixels -> 3 bytes along the minor axis
    w = frames.shape[-1]
    wire_width(w, stream_dtype)  # validates even width
    pairs = frames.astype(np.uint16).reshape(frames.shape[:-1] + (w // 2, 2))
    lo, hi = pairs[..., 0], pairs[..., 1]
    b0 = lo & 0xFF
    b1 = ((lo >> 8) & 0xF) | ((hi & 0xF) << 4)
    b2 = hi >> 4
    return (
        np.stack([b0, b1, b2], axis=-1)
        .astype(np.uint8)
        .reshape(frames.shape[:-1] + (w // 2 * 3,))
    )


def decode(wire: np.ndarray, stream_dtype: str) -> np.ndarray:
    """Exact host-side inverse of :func:`encode` (tests / downstream use).

    Returns u16 pixel values for the exact formats and float32
    dequantized values for the lossy ``"u8"`` path.
    """
    validate_stream_dtype(stream_dtype)
    if stream_dtype == "u16":
        return wire
    wire = np.asarray(wire)
    if stream_dtype == "u8":
        # scale in float64 so the range endpoints come back exactly
        # (255 * S is 4095.0 in f64 but 4094.9998 in f32); the device-side
        # f32 dequant stays within the S/2 error bound either way
        return (wire.astype(np.float64) * U8_SCALE).astype(np.float32)
    wp = wire.shape[-1]
    logical_width(wp, stream_dtype)  # validates multiple of 3
    trip = wire.reshape(wire.shape[:-1] + (wp // 3, 3)).astype(np.uint16)
    b0, b1, b2 = trip[..., 0], trip[..., 1], trip[..., 2]
    lo = b0 | ((b1 & 0xF) << 8)
    hi = (b1 >> 4) | (b2 << 4)
    return np.stack([lo, hi], axis=-1).reshape(wire.shape[:-1] + (wp // 3 * 2,))


# ---------------------------------------------------------------------------
# Device side (traced): the shared in-VMEM dequantization prologue.
# ---------------------------------------------------------------------------


def dequant(x, stream_dtype: str, accum_dtype, *, in_kernel: bool = False) -> jnp.ndarray:
    """Wire values ``(..., wire_w)`` -> pixel values ``(..., W)`` in
    ``accum_dtype``.

    Pure elementwise/reshape jnp — valid both inside a Pallas kernel body
    (on block values already resident in VMEM) and in the XLA fallbacks.
    Unsigned containers widen through int32: Mosaic has no direct
    unsigned->float cast, and every 8- or 16-bit container value is exact
    in int32 and float32, so the ``"u16"`` path stays bit-identical to a
    plain cast (float frames are cast directly).

    ``in_kernel`` picks the ``"p12"`` unpack Mosaic compiles (selection
    matmuls, O(W^2) per row); the XLA fallbacks keep the O(W) triplet
    reshape. Both are exact, so the two give identical pixels.
    """
    acc = jnp.dtype(accum_dtype)
    validate_stream_dtype(stream_dtype)
    if stream_dtype == "p12":
        unpack = _unpack12_select if in_kernel else _unpack12_reshape
        return unpack(x).astype(acc)
    if jnp.issubdtype(x.dtype, jnp.unsignedinteger):
        x = x.astype(jnp.int32)
    if stream_dtype == "u8":
        return x.astype(acc) * jnp.asarray(U8_SCALE, acc)
    return x.astype(acc)


def _unpack12_reshape(x) -> jnp.ndarray:
    """Packed-12-bit bytes ``(..., 3W/2)`` -> uint16 pixels ``(..., W)``:
    pixel ``2i`` is ``b0 | (b1 & 0xF) << 8`` and pixel ``2i+1`` is
    ``b1 >> 4 | b2 << 4`` of byte triplet ``i``."""
    wp = x.shape[-1]
    w = logical_width(wp, "p12")
    trip = x.reshape(x.shape[:-1] + (wp // 3, 3)).astype(jnp.uint16)
    b0, b1, b2 = trip[..., 0], trip[..., 1], trip[..., 2]
    lo = b0 | ((b1 & 0xF) << 8)
    hi = (b1 >> 4) | (b2 << 4)
    return jnp.stack([lo, hi], axis=-1).reshape(x.shape[:-1] + (w,))


def _unpack12_select(x) -> jnp.ndarray:
    """:func:`_unpack12_reshape` in a form Mosaic compiles.

    Mosaic cannot split the lane axis into triplets, so the bytes are
    gathered to pixel positions by two 0/1 selection matmuls instead:
    ``prim`` holds each pixel's own byte (b0 for even pixels, b2 for odd
    ones) and ``mid`` the shared b1. Bytes are exact in bfloat16 and each
    output sums one product, so the gather is exact on every backend.
    """
    wp = x.shape[-1]
    w = logical_width(wp, "p12")
    src = jax.lax.broadcasted_iota(jnp.int32, (wp, w), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (wp, w), 1)
    base = 3 * (dst >> 1)
    sel_prim = (src == base + 2 * (dst & 1)).astype(jnp.bfloat16)
    sel_mid = (src == base + 1).astype(jnp.bfloat16)
    rows = x.reshape(-1, wp).astype(jnp.int32).astype(jnp.float32)
    rows = rows.astype(jnp.bfloat16)

    def gather(sel):
        return jnp.dot(rows, sel, preferred_element_type=jnp.float32).astype(
            jnp.int32
        )

    prim, mid = gather(sel_prim), gather(sel_mid)
    odd = jax.lax.broadcasted_iota(jnp.int32, prim.shape, 1) & 1
    lo = prim | ((mid & 0xF) << 8)
    hi = (mid >> 4) | (prim << 4)
    return jnp.where(odd == 1, hi, lo).reshape(x.shape[:-1] + (w,))


def pair_diff_block(
    block, *, offset: float, accum_dtype, stream_dtype: str = "u16",
    in_kernel: bool = False,
):
    """The shared kernel prologue: ``(..., 2, th, wire_w)`` pairs block ->
    dequantized ``(..., th, W)`` difference ``exc - ctl + offset``.

    Every ingest kernel family (stream, multibank, median insert, EMA) and
    every XLA fallback runs this exact sequence, so the subtraction
    arithmetic — and therefore the numeric stream — is identical across
    backends for each wire format. Kernel bodies pass ``in_kernel=True``
    (see :func:`dequant`).
    """
    acc = jnp.dtype(accum_dtype)
    ctl = dequant(block[..., 0, :, :], stream_dtype, acc, in_kernel=in_kernel)
    exc = dequant(block[..., 1, :, :], stream_dtype, acc, in_kernel=in_kernel)
    return exc - ctl + jnp.asarray(offset, acc)
