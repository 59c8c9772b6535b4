"""Paper Table 5: multi-bank scaling (1 vs 2 banks on separate devices).

The paper shows flat latency from 1 bank/1 FPGA to 2 banks/2 FPGAs. The
TPU analogue shards the bank axis over devices with shard_map (zero
cross-bank collectives). Runs in this process over ``jax.devices()`` and
raises when fewer than two devices exist; on a CPU host, give it two with
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` before starting.

This table also measures old-vs-new for the bank pipeline itself at the
paper's default config (G=8, N=1000, 80×256): the *reference* path (what
``banked_subtract_average`` ran before — host f32 staging + a per-group
``ref_stream_step`` scan per bank) against the *fused* path it dispatches
now (u16 straight to device, subtract fused into the group reduction, one
program for all banks). The ratio is recorded to BENCH_denoise.json.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.common import PAPER_G, PAPER_H, PAPER_N, PAPER_W, bench_record, emit
from repro.core.banks import banked_subtract_average, make_bank_mesh
from repro.core.denoise import DenoiseConfig
from repro.kernels import ops
from repro.kernels.ref import ref_stream_finalize, ref_stream_step


def _reference_banked(frames_u16, mesh, config):
    """The pre-fusion path: host f32 convert, then a per-group scan of the
    reference step per bank inside shard_map."""
    x = jnp.asarray(frames_u16.astype(np.float32))
    spec = P("bank", None, None, None, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=spec,
        out_specs=P("bank", None, None, None),
    )
    def _per_bank(local):
        def one(f):
            g = f.shape[0]

            def body(s, grp):
                return ref_stream_step(
                    s, grp, offset=config.offset, variant=config.variant,
                    num_groups=g,
                ), None

            init = jax.lax.pcast(
                jnp.zeros((f.shape[1] // 2, f.shape[2], f.shape[3]), jnp.float32),
                ("bank",), to="varying",
            )
            total, _ = jax.lax.scan(body, init, f)
            return ref_stream_finalize(total, g, variant=config.variant)

        return jax.vmap(one)(local)

    return _per_bank(jax.device_put(x, NamedSharding(mesh, spec)))


def _fused_banked(frames_u16, mesh, config):
    """The fused path: u16 straight to device, fused ops dispatch."""
    return banked_subtract_average(jnp.asarray(frames_u16), mesh, config=config)


def _bench(fn, x, mesh, config, iters=3):
    jax.block_until_ready(fn(x, mesh, config))  # compile
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x, mesh, config))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def run(quick: bool = True) -> None:
    # the fused-vs-reference point stays at paper scale even in quick mode:
    # the recorded trajectory point must be at the paper default config
    n = 100 if quick else 400
    rng = np.random.default_rng(0)
    cfg = DenoiseConfig(num_groups=8, frames_per_group=n, height=80, width=256)

    def scaling(banks):
        mesh = make_bank_mesh(banks)  # raises when fewer devices exist
        x = rng.integers(
            0, 4096, (banks, cfg.num_groups, cfg.frames_per_group, 80, 256)
        ).astype(np.uint16)
        return _bench(_fused_banked, x, mesh, cfg)

    t1, t2 = scaling(1), scaling(2)
    emit("table5/one_bank", t1 * 1e6, "elapsed_us_total")
    emit(
        "table5/two_banks",
        t2 * 1e6,
        f"scaling_ratio={t2 / t1:.3f} (paper: 1.00 flat; the shard_map "
        "program has zero cross-bank collectives, verified in "
        "tests/test_banks.py)",
    )

    pcfg = DenoiseConfig(
        num_groups=PAPER_G, frames_per_group=PAPER_N, height=PAPER_H,
        width=PAPER_W,
    )
    mesh1 = make_bank_mesh(1)
    xp = rng.integers(
        0, 4096, (1, pcfg.num_groups, pcfg.frames_per_group, PAPER_H, PAPER_W)
    ).astype(np.uint16)
    t_ref = _bench(_reference_banked, xp, mesh1, pcfg)
    t_fused = _bench(_fused_banked, xp, mesh1, pcfg)
    np.testing.assert_allclose(
        np.asarray(_reference_banked(xp, mesh1, pcfg)),
        np.asarray(_fused_banked(xp, mesh1, pcfg)),
        rtol=1e-5,
    )
    speedup = t_ref / t_fused
    emit(
        "table5/fused_vs_reference",
        t_fused * 1e6,
        f"reference_us={t_ref * 1e6:.1f};speedup={speedup:.3f}x "
        "(paper default G=8,N=1000,80x256, single bank)",
    )
    bench_record(
        "multibank_fused_vs_reference",
        kind="speedup",
        config={
            "G": PAPER_G,
            "N": PAPER_N,
            "H": PAPER_H,
            "W": PAPER_W,
            "banks": 1,
            "backend": ops._resolve(pcfg.backend),
        },
        baseline="reference (host f32 + per-group ref_stream_step scan)",
        candidate="fused (u16 in, subtract fused into group reduction)",
        baseline_s=float(t_ref),
        candidate_s=float(t_fused),
        speedup=float(speedup),
    )
