"""The pool of camera groups every acquisition draws from.

A frame grabber leaves each camera's frames in host memory. The pool
stands in for it: ``pool_groups`` groups of (N, H, W) mono12-in-u16 PRISM
frames made from the seed on the device, in one jitted call, then copied
to host memory. Each acquisition takes its G groups from the pool in an
order drawn from the seed (``acquisition_groups``). The frames follow the
paper's validation rig (as ``repro.data.prism.PrismSource`` does): a fixed
test chart lit by a static LED and, on every excitation frame, a
sine-modulated one, plus shot noise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["make_pool", "acquisition_groups"]

MONO12_MAX = 4095


@functools.partial(jax.jit, static_argnames=("groups", "n", "h", "w"))
def _pool(key, *, groups: int, n: int, h: int, w: int):
    y = jnp.linspace(0.0, 1.0, h)[:, None]
    x = jnp.linspace(0.0, 1.0, w)[None, :]
    checker = (jnp.floor(y * 8) + jnp.floor(x * 16)) % 2
    chart = 0.5 + 0.35 * checker + 0.15 * x
    i = jnp.arange(n, dtype=jnp.float32)
    led = 300.0 * jnp.abs(jnp.sin(2 * jnp.pi * i / 50.0))
    level = 800.0 + 400.0 + jnp.where(i % 2 == 1, led, 0.0)

    def one(g):
        noise = jax.random.normal(jax.random.fold_in(key, g), (n, h, w), jnp.float32)
        frames = level[:, None, None] * chart[None] + 25.0 * noise
        return jnp.clip(jnp.round(frames), 0, MONO12_MAX).astype(jnp.uint16)

    return jax.lax.map(one, jnp.arange(groups))


def make_pool(seed: int, groups: int, n: int, h: int, w: int) -> np.ndarray:
    """(groups, N, H, W) u16 in host memory, the same for the same seed."""
    # a key holds 32 bits of seed: the rest is folded in
    key = jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)
    dev = _pool(key, groups=groups, n=n, h=h, w=w)
    host = np.asarray(dev)
    dev.delete()
    return host


def acquisition_groups(seed: int, camera: int, index: int, groups: int, pool: int):
    """Pool indices of the G groups of acquisition ``index`` of ``camera``."""
    rng = np.random.default_rng((seed, camera, index))
    return rng.integers(0, pool, groups)
