"""Quantized-ingest wire formats (repro.kernels.quant): exact round-trips
for u16/p12 including both 12-bit endpoints, the bounded-error contract
for u8, wire-width arithmetic and its validation errors, and host
encode/decode vs device dequant consistency (the one-decoder guarantee
every kernel family relies on)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import quant


def _mono12(shape, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, quant.MONO12_MAX + 1, shape).astype(np.uint16)


# ---------------------------------------------------------------------------
# Validation and wire-width arithmetic.
# ---------------------------------------------------------------------------


def test_validate_stream_dtype_rejects_unknown():
    with pytest.raises(ValueError, match="stream_dtype must be one of"):
        quant.validate_stream_dtype("u12")
    for sd in quant.STREAM_DTYPES:
        assert quant.validate_stream_dtype(sd) == sd


def test_container_metadata():
    assert quant.container_dtype("u16") == np.uint16
    assert quant.container_dtype("u8") == np.uint8
    assert quant.container_dtype("p12") == np.uint8
    # "u16" keeps the pre-tier cache-key spelling so old plans stay valid
    assert quant.container_name("u16") == "uint16"
    assert quant.container_name("u8") == "uint8"
    assert quant.container_name("p12") == "pack12"
    assert quant.wire_pixel_bytes("u16") == 2.0
    assert quant.wire_pixel_bytes("u8") == 1.0
    assert quant.wire_pixel_bytes("p12") == 1.5


def test_wire_width_round_trip():
    for sd in ("u16", "u8"):
        assert quant.wire_width(64, sd) == 64
        assert quant.logical_width(64, sd) == 64
    assert quant.wire_width(64, "p12") == 96  # 2 pixels -> 3 bytes
    assert quant.logical_width(96, "p12") == 64


def test_wire_width_validation_errors():
    with pytest.raises(ValueError, match="even width"):
        quant.wire_width(65, "p12")
    with pytest.raises(ValueError, match="multiple of 3"):
        quant.logical_width(64, "p12")


# ---------------------------------------------------------------------------
# Host encode/decode round trips.
# ---------------------------------------------------------------------------


def test_u16_encode_is_identity_no_copy():
    frames = _mono12((4, 8, 16))
    assert quant.encode(frames, "u16") is frames
    assert quant.decode(frames, "u16") is frames


def test_p12_round_trip_exact_all_values():
    """Every 12-bit value round-trips exactly, in both pair positions."""
    vals = np.arange(quant.MONO12_MAX + 1, dtype=np.uint16)  # 4096: even
    both = np.stack([vals, vals[::-1]]).reshape(2, -1)  # each value lo & hi
    wire = quant.encode(both, "p12")
    assert wire.dtype == np.uint8
    assert wire.shape == (2, 4096 // 2 * 3)
    np.testing.assert_array_equal(quant.decode(wire, "p12"), both)


def test_u8_round_trip_endpoints_exact_error_bounded():
    vals = np.arange(quant.MONO12_MAX + 1, dtype=np.uint16).reshape(1, -1)
    wire = quant.encode(vals, "u8")
    assert wire.dtype == np.uint8
    assert wire[0, 0] == 0 and wire[0, -1] == 255  # endpoints map to ends
    back = quant.decode(wire, "u8")
    assert back.dtype == np.float32
    # both range endpoints are exact by choice of S = 4095/255
    assert back[0, 0] == 0.0
    assert back[0, -1] == float(quant.MONO12_MAX)
    err = np.abs(back.astype(np.float64) - vals.astype(np.float64))
    assert err.max() <= quant.U8_SCALE / 2 + 1e-9


def test_random_frames_round_trip_properties():
    """numpy property sweep (hypothesis is a dev-only extra): random
    mono12 frames across shapes — p12 exact, u8 within S/2."""
    for seed, shape in enumerate([(2, 4, 6), (3, 5, 32), (1, 16, 64)]):
        frames = _mono12(shape, seed=seed)
        np.testing.assert_array_equal(
            quant.decode(quant.encode(frames, "p12"), "p12"), frames
        )
        err = np.abs(
            quant.decode(quant.encode(frames, "u8"), "u8").astype(np.float64)
            - frames
        )
        assert err.max() <= quant.U8_SCALE / 2 + 1e-9


# ---------------------------------------------------------------------------
# Device dequant agrees with the host decoder.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sd", quant.STREAM_DTYPES)
def test_dequant_matches_host_decode(sd):
    frames = _mono12((4, 8, 16), seed=3)
    wire = quant.encode(frames, sd)
    dev = np.asarray(quant.dequant(jnp.asarray(wire), sd, jnp.float32))
    host = quant.decode(wire, sd).astype(np.float32)
    if sd == "u8":
        # device dequant scales in f32, host in f64: both stay within the
        # quantization bound, and agree to f32 rounding of v*S
        np.testing.assert_allclose(dev, host, atol=1e-3, rtol=0)
    else:
        np.testing.assert_array_equal(dev, host)


def test_p12_kernel_unpack_matches_xla_unpack_on_every_value():
    """The selection-matmul unpack kernels use decodes every 12-bit value
    in both pixel positions exactly as the XLA fallbacks' reshape does."""
    lo = np.arange(quant.MONO12_MAX + 1, dtype=np.uint16)
    frames = np.stack([lo, lo[::-1]], axis=-1).reshape(32, 256)
    wire = jnp.asarray(quant.encode(frames, "p12"))
    kernel = quant.dequant(wire, "p12", jnp.float32, in_kernel=True)
    xla = quant.dequant(wire, "p12", jnp.float32)
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(xla))
    np.testing.assert_array_equal(np.asarray(xla), frames.astype(np.float32))


def test_pair_diff_block_u16_matches_plain_arithmetic():
    """The shared prologue on u16 wire IS the pre-tier astype arithmetic."""
    frames = _mono12((5, 2, 8, 16), seed=4)  # (pairs, 2, th, W)
    out = quant.pair_diff_block(
        jnp.asarray(frames), offset=100.0, accum_dtype=jnp.float32
    )
    ref = (
        frames[:, 1].astype(np.float32)
        - frames[:, 0].astype(np.float32)
        + 100.0
    )
    np.testing.assert_array_equal(np.asarray(out), ref)


@pytest.mark.parametrize("sd", ("u8", "p12"))
def test_pair_diff_block_narrow_matches_decoded_reference(sd):
    frames = _mono12((5, 2, 8, 16), seed=5)
    wire = quant.encode(frames, sd)
    out = np.asarray(
        quant.pair_diff_block(
            jnp.asarray(wire), offset=100.0, accum_dtype=jnp.float32,
            stream_dtype=sd,
        )
    )
    dec = quant.decode(wire, sd).astype(np.float32)
    ref = dec[:, 1] - dec[:, 0] + np.float32(100.0)
    if sd == "p12":
        np.testing.assert_array_equal(out, ref)
    else:
        # two dequants then a subtract: error bound is S (2x one pixel's S/2)
        assert np.abs(out - ref).max() <= 1e-3
