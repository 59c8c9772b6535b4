"""The plain reference for ``pair_average``, its lower-precision control,
and the comparison that decides ``correct``.

``diffs`` and ``pair_average`` are copies of ``chip_smoke.py``'s
``_diffs`` / ``ref_pair_average`` (the filter's semantics in plain numpy
float32), kept here so that no change to the program or to its smoke test
moves the yardstick. Nothing here imports the program.

Every value on the path is an integer below 2**24 until the final division
by G = 8, so float32 is exact and the program must match bit for bit: the
limit on ``max_abs_err`` is 0. The control computes the same reference in
bfloat16, the nearest precision below the float32 the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["diffs", "pair_average", "pair_average_bf16", "max_abs_err"]


def diffs(group: np.ndarray, offset: float) -> np.ndarray:
    """(N, H, W) u16 frames -> (N/2, H, W) f32 ``exc - ctl + offset``."""
    f = group.astype(np.float32).reshape(-1, 2, *group.shape[1:])
    return f[:, 1] - f[:, 0] + np.float32(offset)


def pair_average(group_diffs) -> np.ndarray:
    """Mean over groups of their ``diffs``, summed in order in float32."""
    total = np.zeros_like(group_diffs[0])
    for d in group_diffs:
        total += d
    return total / np.float32(len(group_diffs))


@jax.jit
def pair_average_bf16(groups, offset):
    """The control: ``pair_average`` of (G, N, H, W) u16 groups, every
    operation in bfloat16 on the device. Returns float32."""
    bf = jnp.bfloat16
    f = groups.astype(bf).reshape(groups.shape[0], -1, 2, *groups.shape[2:])
    d = f[:, :, 1] - f[:, :, 0] + jnp.asarray(offset, bf)
    total = jnp.zeros(d.shape[1:], bf)
    for g in range(d.shape[0]):
        total = total + d[g]
    return (total / jnp.asarray(d.shape[0], bf)).astype(jnp.float32)


def max_abs_err(out, ref: np.ndarray) -> float:
    """Largest |out - ref|; ``inf`` for a wrong shape or a non-finite value.

    Taken in float32: a float32 difference is 0 exactly when the two
    values are equal, which is all the limit of 0 asks.
    """
    out = np.asarray(out)
    if out.shape != ref.shape or not np.isfinite(out).all():
        return float("inf")
    return float(np.max(np.abs(out - ref)))
