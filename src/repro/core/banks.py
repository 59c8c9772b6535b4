"""Multi-bank scaling (paper Table 5): shard the pixel stream across devices.

The paper partitions the camera stream into banks of 256×80 pixels and runs
one FPGA per bank, observing flat latency from 1 -> 2 banks. The TPU
analogue shards the bank axis across devices of a 1-D ``bank`` mesh with
``shard_map``: each device owns its bank's running sum; no cross-device
communication is needed until (optionally) a final gather — the same
communication-free scaling the paper exploits.

The per-shard body dispatches through the ``ops`` backend layer, so each
device runs the *fast* path for its platform: the fused multi-bank Pallas
kernel on TPU (grid over the device's local banks), the fused batched XLA
program elsewhere — never the per-group reference scan. The bank mesh
comes from ``repro.mesh.make_mesh`` (Auto axes), so the serve tier's slot
scatter/gather runs on bank-sharded state. The shard bodies run with
``check_vma=False``: they are bank-local (no collectives), and a Pallas
kernel's output shape carries no varying-axes annotation.

Streaming ingest composes with the ring-buffer pipeline
(``repro.core.ringbuf``): ``run_pipelined_banked`` gives every bank shard
its own bounded ring, so each camera's acquisition thread stages
independently with backpressure, and the compute step gathers one chunk
per bank, lands the stack bank-sharded, and folds it through the
filter-generic ``banked_filter_step`` — the paper's
one-DRAM-pipeline-per-FPGA topology, hosting any ``repro.denoise`` filter
(``pair_average`` takes the fused multi-bank kernel path of
``banked_stream_step``; other filters shard their own state pytrees via
``StreamingFilter.state_pspec``).

On a CPU host the mesh has a single device unless the process starts with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the multi-device
tests spawn such subprocesses).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Iterator, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs, tune
from repro.core.denoise import DenoiseConfig
from repro.core.ringbuf import RingBuffer, RingClosed
from repro.core.streaming import _stream_report
from repro.denoise import get_filter
from repro.kernels import ops
from repro.mesh import make_mesh

__all__ = [
    "make_bank_mesh",
    "banked_subtract_average",
    "banked_stream_step",
    "banked_filter_init",
    "banked_filter_step",
    "run_pipelined_banked",
]


def make_bank_mesh(num_banks: int | None = None) -> Mesh:
    devs = jax.devices()
    n = num_banks or len(devs)
    if len(devs) < n:
        raise ValueError(f"need {n} devices for {n} banks, have {len(devs)}")
    return make_mesh((n,), ("bank",), devices=devs[:n])


def banked_subtract_average(
    frames,
    mesh: Mesh,
    *,
    config: DenoiseConfig,
):
    """frames (B, G, N, H, W), bank axis sharded -> (B, N/2, H, W) sharded.

    Pure data parallelism over banks — zero collectives, matching the
    paper's observation that 2-bank latency == 1-bank latency. Each shard
    runs the fused multi-bank kernel over its local banks.
    """
    spec = P("bank", None, None, None, None)
    tiles = tune.tile_args(config, "stream")  # once, before the shard body

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=spec,
        out_specs=P("bank", None, None, None), check_vma=False,
    )
    def _per_bank(local):  # local: (B/banks, G, N, H, W)
        return ops.multibank_subtract_average(
            local,
            offset=config.offset,
            algorithm=config.algorithm,
            backend=config.backend,
            **tiles,
        )

    sharded = jax.device_put(frames, NamedSharding(mesh, spec))
    return _per_bank(sharded)


def banked_stream_step(
    sum_frames,
    group_frames,
    mesh: Mesh,
    *,
    config: DenoiseConfig,
):
    """Streaming variant: one group per step, banks in parallel.

    sum_frames (B, N/2, H, W), group_frames (B, N, H, W), both bank-sharded.
    """
    tiles = tune.tile_args(config, "stream")  # once, before the shard body

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("bank", None, None, None), P("bank", None, None, None)),
        out_specs=P("bank", None, None, None),
        check_vma=False,
    )
    def _step(s, f):
        return ops.multibank_stream_step(
            s,
            f,
            num_groups=config.num_groups,
            offset=config.offset,
            variant=config.variant,
            backend=config.backend,
            **tiles,
        )

    return _step(sum_frames, group_frames)


# ---------------------------------------------------------------------------
# Filter-generic banked stepping (repro.denoise): the same shard_map
# topology for ANY registered filter. The filter state is an opaque pytree;
# each filter maps it to per-leaf PartitionSpecs via ``state_pspec`` ("bank"
# on the bank axis), and the per-shard body runs the filter's own banked
# ``step`` — ``pair_average`` hits the fused multi-bank ops path and is
# bit-identical to ``banked_stream_step``.
# ---------------------------------------------------------------------------


def _chunk_spec():
    return P("bank", None, None, None)


def banked_filter_init(
    config: DenoiseConfig, mesh: Mesh | None = None, *, banks: int | None = None
):
    """Create the filter's banked state, each leaf laid out bank-sharded.

    Returns ``(filter, state)``. With a ``mesh``, the state's bank axis
    matches ``mesh.shape["bank"]`` and every leaf is placed bank-sharded.
    With ``mesh=None`` (the session-scheduler topology: many slots, one
    shared device) ``banks`` sets the bank-axis length and the state stays
    wherever JAX puts it — same pytree, no sharding.
    """
    filt = get_filter(config.filter_name)(config)
    if mesh is None:
        if banks is None:
            raise ValueError("banked_filter_init needs a mesh or banks=")
        return filt, filt.init(banks=banks)
    if banks is not None and banks != mesh.shape["bank"]:
        raise ValueError(
            f"banks={banks} does not match mesh bank axis "
            f"{mesh.shape['bank']}"
        )
    state = filt.init(banks=mesh.shape["bank"])
    specs = filt.state_pspec(state)
    # PartitionSpec is tuple-like, so flatten the spec tree against the
    # STATE's treedef (specs must never be flattened as containers)
    leaves, treedef = jax.tree.flatten(state)
    spec_leaves = treedef.flatten_up_to(specs)
    placed = [
        jax.device_put(leaf, NamedSharding(mesh, spec))
        for leaf, spec in zip(leaves, spec_leaves)
    ]
    return filt, jax.tree.unflatten(treedef, placed)


def banked_filter_step(
    state,
    group_frames,
    mesh: Mesh | None = None,
    *,
    config: DenoiseConfig,
    step_index: int,
    filt=None,
):
    """One filter step, banks in parallel: state pytree and (B, N, H, W)
    chunk both bank-sharded; returns the updated sharded state.

    With ``mesh=None`` the step runs the filter's banked path directly on
    the current device (the batched session-scheduler step) — same
    numerics, no ``shard_map``.
    """
    filt = filt or get_filter(config.filter_name)(config)
    if mesh is None:
        return filt.step(state, group_frames, step_index=step_index)
    specs = filt.state_pspec(state)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(specs, _chunk_spec()),
        out_specs=specs,
        check_vma=False,
    )
    def _step(local_state, local_chunk):
        return filt.step(local_state, local_chunk, step_index=step_index)

    return _step(state, group_frames)


def run_pipelined_banked(
    config: DenoiseConfig,
    sources: Sequence[Iterator[np.ndarray]],
    mesh: Mesh,
    *,
    num_slots: int | None = None,
    policy: str | None = None,
):
    """Ring-pipelined multi-bank ingest: one bounded ring per bank shard.

    ``sources`` holds one chunk iterator per bank (e.g.
    ``PrismSource.bank_sources``), each yielding (N, H, W) groups. Every
    bank gets its own acquisition thread and its own ``RingBuffer`` —
    cameras stage independently, with per-bank backpressure, exactly like
    the paper's one-DRAM-pipeline-per-FPGA topology. Each compute step
    gathers one chunk from every ring (a per-group barrier across banks),
    lands the (B, N, H, W) stack bank-sharded on the mesh, and folds it
    with the fused ``banked_stream_step``. Only the lossless ``"block"``
    policy is accepted: asymmetric per-bank drops would misalign groups
    at the gather barrier, so ``"drop_oldest"`` raises.

    Returns ``(out, report)`` like ``run_pipelined``; ``out`` is the
    bank-sharded (B, N/2, H, W) result. In the report, ``transfer_s`` /
    ``produce_wait_s`` / ``drops`` are summed over the per-bank rings
    (bank staging overlaps, so ``transfer_s`` can exceed ``elapsed_s``),
    ``stall_s`` is the compute thread's total wait on the gather, and the
    occupancy fields aggregate mean/max depth across rings.
    """
    banks = mesh.shape["bank"]
    if len(sources) != banks:
        raise ValueError(f"mesh has {banks} banks but got {len(sources)} sources")
    num_slots = config.num_slots if num_slots is None else num_slots
    policy = config.overflow_policy if policy is None else policy
    if policy != "block":
        # asymmetric per-bank drops would silently fold bank i's group k
        # with bank j's group k+1 at the gather barrier
        raise ValueError(
            "run_pipelined_banked requires policy='block': the per-group "
            f"gather barrier cannot tolerate per-bank loss (got {policy!r})"
        )

    rings = [
        RingBuffer(num_slots, policy=policy, name=f"bank{i}") for i in range(banks)
    ]
    errors: list[BaseException] = []

    def _produce(ring: RingBuffer, source: Iterator[np.ndarray]) -> None:
        it = iter(source)
        try:
            while True:
                t0 = time.perf_counter()  # time the pull (camera) + the copy
                try:
                    with obs.span("stream.stage", "banks", ring=ring.name):
                        chunk = next(it)
                except StopIteration:
                    break
                staged = np.ascontiguousarray(chunk)
                ring.put((staged, time.perf_counter() - t0))
        except RingClosed:
            pass  # compute side shut down early (error path)
        except BaseException as e:
            errors.append(e)
        finally:
            ring.close()

    threads = [
        threading.Thread(
            target=_produce, args=(ring, src), name=f"prism-bank{i}", daemon=True
        )
        for i, (ring, src) in enumerate(zip(rings, sources))
    ]
    for t in threads:
        t.start()

    reg = obs.MetricsRegistry()
    c_frames = reg.counter("stream.frames")
    c_transfer = reg.counter("stream.transfer_s")
    c_stall = reg.counter("stream.stall_s")
    h_latency = reg.histogram("stream.latency_s")
    reg.gauge("stream.num_slots").set(num_slots)

    sharding = NamedSharding(mesh, _chunk_spec())
    c = config
    t_start = time.perf_counter()
    filt, state = banked_filter_init(c, mesh)
    step = 0
    try:
        while True:
            t_wait = time.perf_counter()
            try:
                items = [ring.get() for ring in rings]
            except RingClosed:
                break  # sources drained (or an error closed the rings)
            c_stall.inc(time.perf_counter() - t_wait)
            c_transfer.inc(sum(dt for _, dt in items))
            # each chunk's wait from staged to the gather barrier picking
            # it up — pooled across the per-bank rings
            h_latency.observe_many(r.stats.last_dwell_s for r in rings)
            with obs.span("banks.step", "banks", step=step, banks=banks):
                dev = jax.device_put(
                    np.stack([chunk for chunk, _ in items]), sharding
                )
                state = banked_filter_step(
                    state, dev, mesh, config=config, step_index=step, filt=filt
                )
            step += 1
            c_frames.inc(banks * items[0][0].shape[0])
    finally:
        for ring in rings:
            ring.close()
        for t in threads:
            t.join()

    if errors:
        raise errors[0]
    gets = {ring.stats.gets for ring in rings}
    if len(gets) > 1 or any(len(ring) for ring in rings):
        raise ValueError(
            "bank sources yielded unequal chunk counts: a per-group barrier "
            "needs one chunk per bank per step"
        )

    with obs.span("stream.finalize", "banks", steps=step):
        out = filt.finalize(state)
        jax.block_until_ready(out)
    elapsed = time.perf_counter() - t_start
    stats = [ring.stats for ring in rings]
    reg.counter("stream.bytes_in").inc(int(c_frames.value) * c.bytes_per_frame)
    reg.counter("stream.produce_wait_s").inc(sum(s.put_wait_s for s in stats))
    reg.counter("stream.drops").inc(sum(s.drops for s in stats))
    reg.gauge("stream.ring_occupancy_mean").set(
        sum(s.occupancy_mean for s in stats) / banks
    )
    reg.gauge("stream.ring_occupancy_max").set(max(s.occupancy_max for s in stats))
    return out, _stream_report(reg, elapsed)
