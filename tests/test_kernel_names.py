"""Every ``pallas_call`` carries an explicit, stable ``name=``.

On the TPU the name is the kernel's HLO instruction
(``%multibank_stream_step.1``), which the device trace and the benchmark's
roofline reader match on; without ``name=`` the instruction would follow
whatever Python function happens to enclose the call. Lowered in
interpret mode on the CPU, a named call leaves its name as a scope in
the ops' ``op_name`` metadata (``.../multibank_stream_step/while/...``),
which an unnamed call does not.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (
    denoise_ema,
    denoise_median,
    denoise_multibank,
    denoise_spatial,
    denoise_stream,
    denoise_tmpframe,
)

G, N, H, W = 4, 8, 16, 128
P_ = N // 2
OFFSET = 4096.0


def _u16(*shape):
    return jnp.zeros(shape, jnp.uint16)


def _f32(*shape):
    return jnp.zeros(shape, jnp.float32)


FAMILIES = {
    "multibank_stream_step": (
        lambda f, s: denoise_multibank.multibank_stream_step(
            f, s, num_groups=G, offset=OFFSET, interpret=True
        ),
        (_u16(2, N, H, W), _f32(2, P_, H, W)),
    ),
    "multibank_subtract_average": (
        lambda f: denoise_multibank.multibank_subtract_average(
            f, offset=OFFSET, interpret=True
        ),
        (_u16(2, G, N, H, W),),
    ),
    "alg3_stream_step": (
        lambda f, s: denoise_stream.alg3_stream_step(
            f, s, num_groups=G, offset=OFFSET, interpret=True
        ),
        (_u16(N, H, W), _f32(P_, H, W)),
    ),
    "alg3_subtract_average": (
        lambda f: denoise_stream.alg3_subtract_average(f, offset=OFFSET, interpret=True),
        (_u16(G, N, H, W),),
    ),
    "alg1_subtract_average": (
        lambda f: denoise_tmpframe.alg1_subtract_average(f, offset=OFFSET, interpret=True),
        (_u16(G, N, H, W),),
    ),
    "alg2_subtract_average": (
        lambda f: denoise_tmpframe.alg2_subtract_average(f, offset=OFFSET, interpret=True),
        (_u16(G, N, H, W),),
    ),
    "median_window_insert": (
        lambda win, f: denoise_median.median_window_insert(
            win, f, slot=1, offset=OFFSET, interpret=True
        ),
        (_f32(3, P_, H, W), _u16(N, H, W)),
    ),
    "median_combine": (
        lambda win: denoise_median.median_combine(win, interpret=True),
        (_f32(3, P_, H, W),),
    ),
    "ema_welford_step": (
        lambda e, m, v, f: denoise_ema.ema_welford_step(
            e, m, v, f, alpha=0.25, offset=OFFSET, prior_count=0, interpret=True
        ),
        (_f32(P_, H, W), _f32(H, W), _f32(H, W), _u16(N, H, W)),
    ),
    "spatial_filter_3x3": (
        lambda x: denoise_spatial.spatial_filter_3x3(x, mode="box", interpret=True),
        (_f32(P_, H, W),),
    ),
}


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_pallas_call_is_named_in_the_hlo(name):
    fn, args = FAMILIES[name]
    text = jax.jit(fn).lower(*args).compile().as_text()
    # the name as a scope of its own, not only as the enclosing jit(...)
    assert re.search(rf'op_name="[^"]*/{name}/', text), name
