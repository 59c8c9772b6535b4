"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

For clusters beyond one pod, DP×TP alone stops scaling (TP is ICI-bound,
DP batch is finite); the standard third axis is pipeline stages. This
module implements the schedule with ``shard_map`` + ``ppermute``:

* layers are partitioned contiguously across the ``stage`` axis
  (stage s owns layers [s·L/P, (s+1)·L/P));
* a microbatch stream flows stage→stage via ``jax.lax.ppermute``
  (TPU: collective-permute over ICI neighbours);
* the steady-state schedule overlaps stage s computing microbatch m with
  stage s+1 computing m-1 — the classic (P + M - 1) · t_stage makespan,
  bubble fraction (P-1)/(P+M-1).

The forward here is deliberately layer-generic: you pass ``stage_fn``
(params_for_stage, x) -> x, so it composes with any of the model families
in ``repro.models``. Used by ``examples/pipeline_demo.py`` and the perf
notes; the 40-cell dry-run uses DP×TP (+pod-DP) per DESIGN.md §5.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["pipeline_forward", "bubble_fraction"]


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_stages + num_microbatches - 1)


def pipeline_forward(
    stage_params,
    x_microbatches: jnp.ndarray,
    mesh: Mesh,
    stage_fn: Callable,
    *,
    axis: str = "stage",
):
    """Run a GPipe forward.

    stage_params: pytree with a leading ``num_stages`` dim on every leaf
                  (stage s uses slice s), sharded over ``axis``.
    x_microbatches: (M, mb, ...) microbatch stream, replicated.
    stage_fn(params_slice, x) -> x, applied by each stage.

    Returns (M, mb, ...) outputs after all stages.
    """
    num_stages = mesh.shape[axis]
    m = x_microbatches.shape[0]

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
    )
    def run(params, xs):
        # params: leading dim 1 (this stage's slice); xs: (M, mb, ...)
        local = jax.tree_util.tree_map(lambda p: p[0], params)
        stage_id = jax.lax.axis_index(axis)
        total = m + num_stages - 1

        def tick(carry, t):
            buf, outs = carry
            # stages 1.. receive from the left neighbour; stage 0 injects
            recv = jax.lax.ppermute(
                buf, axis, [(i, i + 1) for i in range(num_stages - 1)]
            )
            inject = jnp.where(t < m, t, 0)
            x_in = jnp.where(stage_id == 0, xs[inject], recv)
            y = stage_fn(local, x_in)
            # the last stage commits its result for microbatch t-(P-1)
            out_slot = t - (num_stages - 1)
            valid = (stage_id == num_stages - 1) & (out_slot >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.clip(out_slot, 0, m - 1), 0
            )
            outs = jnp.where(valid, updated, outs)
            return (y, outs), None

        buf0 = jax.lax.pcast(jnp.zeros_like(xs[0]), (axis,), to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")
        (buf, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(total)
        )
        # only the last stage holds real outputs; broadcast via masked psum
        outs = jnp.where(stage_id == num_stages - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis)
        return outs

    return run(stage_params, x_microbatches)
