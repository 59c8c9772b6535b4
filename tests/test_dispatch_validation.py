"""Dispatch-boundary validation: unknown ``algorithm`` / ``backend`` /
``filter_name`` strings must raise ``ValueError`` whose message lists the
valid options (``ops.ALGORITHMS`` / ``ops.BACKENDS`` /
``repro.denoise.FILTERS``), at every entry point that accepts them."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.denoise import DenoiseConfig
from repro.data.prism import NOISE_REGIMES, PrismSource
from repro.denoise import FILTERS, get_filter
from repro.kernels import ops

FRAMES = jnp.asarray(np.zeros((2, 4, 8, 32), np.float32))
BANKED = jnp.asarray(np.zeros((2, 2, 4, 8, 32), np.float32))


def _assert_lists(excinfo, options):
    msg = str(excinfo.value)
    for opt in options:
        assert opt in msg, f"error message must list {opt!r}: {msg}"


# ---------------------------------------------------------------------------
# ops.py: algorithm / backend strings.
# ---------------------------------------------------------------------------


def test_subtract_average_unknown_algorithm_lists_algorithms():
    with pytest.raises(ValueError) as exc:
        ops.subtract_average(FRAMES, algorithm="alg9")
    _assert_lists(exc, ops.ALGORITHMS)


def test_subtract_average_unknown_backend_lists_backends():
    with pytest.raises(ValueError) as exc:
        ops.subtract_average(FRAMES, backend="fpga")
    _assert_lists(exc, ops.BACKENDS)


@pytest.mark.parametrize("algorithm", ["alg1", "alg2"])
def test_compiled_single_row_baselines_are_refused(algorithm):
    """The Alg 1/2 Pallas baselines move 1-row blocks, which Mosaic does
    not take: compiled (not interpreted) they fail loudly at dispatch."""
    with pytest.raises(ValueError, match="single-row"):
        ops.subtract_average(
            FRAMES, algorithm=algorithm, backend="pallas", interpret=False
        )


def test_multibank_unknown_algorithm_and_backend():
    with pytest.raises(ValueError) as exc:
        ops.multibank_subtract_average(BANKED, algorithm="alg0")
    _assert_lists(exc, ops.ALGORITHMS)
    with pytest.raises(ValueError) as exc:
        ops.multibank_subtract_average(BANKED, backend="hls")
    _assert_lists(exc, ops.BACKENDS)


def test_stream_step_unknown_backend_lists_backends():
    state = ops.stream_init(4, 8, 32)
    with pytest.raises(ValueError) as exc:
        ops.stream_step(state, FRAMES[0], num_groups=2, backend="verilog")
    _assert_lists(exc, ops.BACKENDS)


def test_filter_ops_unknown_backend_lists_backends():
    window = jnp.zeros((2, 2, 8, 32), jnp.float32)
    with pytest.raises(ValueError) as exc:
        ops.median_window_insert(window, FRAMES[0], slot=0, backend="axi")
    _assert_lists(exc, ops.BACKENDS)
    with pytest.raises(ValueError) as exc:
        ops.median_combine(window, backend="axi")
    _assert_lists(exc, ops.BACKENDS)
    ema = jnp.zeros((2, 8, 32), jnp.float32)
    px = jnp.zeros((8, 32), jnp.float32)
    with pytest.raises(ValueError) as exc:
        ops.ema_welford_step(ema, px, px, FRAMES[0], alpha=0.5, backend="axi")
    _assert_lists(exc, ops.BACKENDS)
    with pytest.raises(ValueError) as exc:
        ops.spatial_filter(ema, backend="axi")
    _assert_lists(exc, ops.BACKENDS)


def test_spatial_filter_unknown_mode_lists_modes():
    with pytest.raises(ValueError) as exc:
        ops.spatial_filter(jnp.zeros((2, 8, 32)), mode="median")
    _assert_lists(exc, ops.SPATIAL_MODES)


# ---------------------------------------------------------------------------
# DenoiseConfig / registry: filter_name and friends.
# ---------------------------------------------------------------------------


def _cfg(**kw):
    base = dict(num_groups=2, frames_per_group=8, height=8, width=32)
    base.update(kw)
    return DenoiseConfig(**base)


def test_config_unknown_filter_name_lists_filters():
    with pytest.raises(ValueError) as exc:
        _cfg(filter_name="wavelet")
    _assert_lists(exc, FILTERS)


def test_config_unknown_algorithm_lists_algorithms():
    with pytest.raises(ValueError) as exc:
        _cfg(algorithm="alg7")
    _assert_lists(exc, ops.ALGORITHMS)


def test_get_filter_unknown_lists_filters():
    with pytest.raises(ValueError) as exc:
        get_filter("bilinear")
    _assert_lists(exc, FILTERS)


def test_config_unknown_backend_fails_at_dispatch():
    # backend is validated at dispatch time (auto-resolution happens there)
    cfg = _cfg(backend="cuda")
    from repro.core.denoise import StreamingDenoiser

    den = StreamingDenoiser(cfg)
    with pytest.raises(ValueError) as exc:
        den.ingest(den.init(), FRAMES[0])
    _assert_lists(exc, ops.BACKENDS)


# ---------------------------------------------------------------------------
# PrismSource: noise_regime strings.
# ---------------------------------------------------------------------------


def test_prism_unknown_regime_lists_regimes():
    with pytest.raises(ValueError) as exc:
        PrismSource(_cfg(), noise_regime="salt")
    _assert_lists(exc, NOISE_REGIMES)
