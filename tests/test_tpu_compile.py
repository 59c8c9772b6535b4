"""Every Pallas kernel family compiles for a TPU v5e at the paper shape.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached, so these tests catch what interpret mode
cannot — unsupported casts and reshapes, blocks off the (8, 128) tiling,
VMEM overruns — before any chip time is spent. The kernel functions are
called with ``interpret=False`` directly: the ``ops`` layer sees the CPU
here and would take its XLA branch.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and it holds it until it exits.
The same described v5e:2x2 also carries the bank mesh: the banked step
compiles for four chips with no collective in the program.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.banks import banked_stream_step
from repro.core.denoise import DenoiseConfig
from repro.kernels import (
    denoise_ema,
    denoise_median,
    denoise_multibank,
    denoise_spatial,
    denoise_stream,
    ops,
    quant,
)
from repro.mesh import make_mesh

#: the paper's deployment: G=8 groups of N=1000 frames, 80x256 banks
G, N, H, W = 8, 1000, 80, 256
P_ = N // 2
OFFSET = 4096.0


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile_has_kernel(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _wire(stream_dtype):
    return quant.wire_width(W, stream_dtype), quant.container_dtype(stream_dtype)


@pytest.mark.parametrize("stream_dtype", quant.STREAM_DTYPES)
def test_stream_one_shot_compiles(one_chip, stream_dtype):
    wp, dt = _wire(stream_dtype)
    _compile_has_kernel(
        lambda f: denoise_stream.alg3_subtract_average(
            f, offset=OFFSET, stream_dtype=stream_dtype, interpret=False
        ),
        _spec(one_chip, (G, N, H, wp), dt),
    )


@pytest.mark.parametrize("stream_dtype", quant.STREAM_DTYPES)
def test_stream_step_compiles(one_chip, stream_dtype):
    wp, dt = _wire(stream_dtype)
    _compile_has_kernel(
        lambda f, s: denoise_stream.alg3_stream_step(
            f, s, num_groups=G, offset=OFFSET, stream_dtype=stream_dtype,
            interpret=False,
        ),
        _spec(one_chip, (N, H, wp), dt),
        _spec(one_chip, (P_, H, W), "float32"),
    )


def test_multibank_step_compiles(one_chip):
    _compile_has_kernel(
        lambda f, s: denoise_multibank.multibank_stream_step(
            f, s, num_groups=G, offset=OFFSET, interpret=False
        ),
        _spec(one_chip, (4, N, H, W), "uint16"),
        _spec(one_chip, (4, P_, H, W), "float32"),
    )


def test_median_insert_compiles(one_chip):
    _compile_has_kernel(
        lambda win, f: denoise_median.median_window_insert(
            win, f, slot=2, offset=OFFSET, interpret=False
        ),
        _spec(one_chip, (5, P_, H, W), "float32"),
        _spec(one_chip, (N, H, W), "uint16"),
    )


def test_median_combine_compiles(one_chip):
    _compile_has_kernel(
        lambda win: denoise_median.median_combine(win, interpret=False),
        _spec(one_chip, (5, P_, H, W), "float32"),
    )


def test_ema_compiles(one_chip):
    _compile_has_kernel(
        lambda e, m, v, f: denoise_ema.ema_welford_step(
            e, m, v, f, alpha=0.25, offset=OFFSET, prior_count=0,
            interpret=False,
        ),
        _spec(one_chip, (P_, H, W), "float32"),
        _spec(one_chip, (H, W), "float32"),
        _spec(one_chip, (H, W), "float32"),
        _spec(one_chip, (N, H, W), "uint16"),
    )


@pytest.mark.parametrize("mode", ["box", "bilateral"])
def test_spatial_compiles(one_chip, mode):
    _compile_has_kernel(
        lambda x: denoise_spatial.spatial_filter_3x3(x, mode=mode, interpret=False),
        _spec(one_chip, (P_, H, W), "float32"),
    )


def test_banked_step_compiles_for_four_chips_without_collectives(v5e_2x2, monkeypatch):
    # the ops layer sees the CPU here; steer it to its TPU branch
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = make_mesh((4,), ("bank",), devices=v5e_2x2.devices)
    cfg = DenoiseConfig(num_groups=G, frames_per_group=N, height=H, width=W)
    banked = NamedSharding(mesh, P("bank", None, None, None))
    text = _compile_has_kernel(
        lambda s, f: banked_stream_step(s, f, mesh, config=cfg),
        _spec(banked, (4, P_, H, W), "float32"),
        _spec(banked, (4, N, H, W), "uint16"),
    )
    for op in ("all-gather", "all-reduce", "all-to-all", "reduce-scatter",
               "collective-permute"):
        assert op not in text, op
