"""Run the served denoise path once on the TPU and check every output.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the bank mesh over four chips

One chip: at the paper's deployment size (G=8 groups of N=1000 frames,
80x256 mono12-in-u16 banks, ``backend="auto"``, heuristic tiles) four
concurrent ``pair_average`` sessions fold as one banked cohort of a
``SessionScheduler`` (the multibank kernel), and one session each of
``temporal_median``, ``ema_variance`` and ``spatial_box`` runs every other
Pallas kernel family. Each session's output is checked against a plain
float32 numpy computation of the filter's semantics written below, which
uses nothing from ``repro.kernels``.

``--chips 4`` runs only the multi-bank path: ``run_pipelined_banked`` over
``make_bank_mesh(4)`` and a mesh-backed ``SessionScheduler`` with four
sessions, both checked against the same reference, and checks that the
four bank shards sit on four distinct devices.

Without a TPU the script exits non-zero before it prints any result. The
last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro import compile_cache  # noqa: E402
from repro.core.banks import make_bank_mesh, run_pipelined_banked  # noqa: E402
from repro.core.denoise import MONO12_MAX, DenoiseConfig  # noqa: E402
from repro.data.prism import PrismSource  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serve import Session, SessionScheduler  # noqa: E402

#: the paper's deployment: one 256x80 bank, G=8, N=1000, u16 wire
PAPER = dict(num_groups=8, frames_per_group=1000, height=80, width=256)
#: results of the rounding-sensitive filters: relative error allowed
#: against the float32 numpy reference (exp on the chip, f32 EMA
#: recursion with fused multiply-adds, Chan-merged variance)
RTOL = 1e-4
TIMEOUT_S = 600.0
#: pixels of the ema_variance session that flicker over the whole mono12
#: range, so that its variance mask has pixels to replace
FLICKER_PIXELS = 16


# ---------------------------------------------------------------------------
# The reference: each filter's semantics in plain numpy float32.
# ---------------------------------------------------------------------------


def _diffs(group: np.ndarray, offset: float) -> np.ndarray:
    """(N, H, W) u16 frames -> (N/2, H, W) f32 ``exc - ctl + offset``."""
    f = group.astype(np.float32).reshape(-1, 2, *group.shape[1:])
    return f[:, 1] - f[:, 0] + np.float32(offset)


def ref_pair_average(groups, cfg) -> np.ndarray:
    total = np.zeros_like(_diffs(groups[0], cfg.offset))
    for g in groups:
        total += _diffs(g, cfg.offset)
    return total / np.float32(len(groups))


def ref_temporal_median(groups, cfg) -> np.ndarray:
    """Median over the last ``median_window`` groups' diffs (odd count)."""
    window = np.stack([_diffs(g, cfg.offset) for g in groups[-cfg.median_window:]])
    return np.median(window, axis=0).astype(np.float32)


def ref_ema_variance(groups, cfg) -> tuple[np.ndarray, int]:
    """Bias-corrected EMA, with high-variance pixels replaced by their mean.

    Returns the output and how many pixels the variance mask replaced.
    """
    a = np.float32(cfg.ema_alpha)
    ema = np.zeros_like(_diffs(groups[0], cfg.offset))
    for g in groups:
        ema = ema * (np.float32(1) - a) + a * _diffs(g, cfg.offset)
    est = ema / np.float32(1.0 - (1.0 - cfg.ema_alpha) ** len(groups))
    samples = np.concatenate([_diffs(g, cfg.offset) for g in groups]).astype(np.float64)
    var = samples.var(axis=0, ddof=1)
    mask = var > cfg.ema_mask_sigma**2 * np.median(var)
    out = np.where(mask[None], samples.mean(axis=0)[None], est).astype(np.float32)
    return out, int(mask.sum())


def ref_spatial_box(groups, cfg) -> np.ndarray:
    """3x3 bilateral-lite (uniform support, Gaussian range weight) with
    edge replication, applied to the pair average."""
    x = ref_pair_average(groups, cfg)
    _, h, w = x.shape
    pad = np.pad(x, ((0, 0), (1, 1), (1, 1)), mode="edge")
    inv2s2 = np.float32(1.0 / (2.0 * cfg.spatial_range_sigma**2))
    acc = np.zeros_like(x)
    wsum = np.zeros_like(x)
    for r in range(3):
        for c in range(3):
            nb = pad[:, r : r + h, c : c + w]
            wgt = np.exp(-((nb - x) ** 2) * inv2s2)
            acc += wgt * nb
            wsum += wgt
    return acc / wsum


def flicker(groups, seed: int) -> list[np.ndarray]:
    """Copies of ``groups`` in which ``FLICKER_PIXELS`` fixed pixels take a
    uniform random mono12 value in every frame: far above the sensor's
    variance, so ``ema_variance`` must replace each with its mean."""
    rng = np.random.default_rng(seed)
    n, h, w = groups[0].shape
    rows, cols = np.unravel_index(rng.choice(h * w, FLICKER_PIXELS, replace=False), (h, w))
    out = []
    for g in groups:
        g = g.copy()
        g[:, rows, cols] = rng.integers(0, MONO12_MAX + 1, (n, FLICKER_PIXELS), g.dtype)
        out.append(g)
    return out


# ---------------------------------------------------------------------------
# Checks.
# ---------------------------------------------------------------------------


def _require_tpu(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU, found {devices[0].platform!r}; "
            "this script has no CPU path"
        )
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, found {len(devices)}")
    return devices


def _check(name: str, out, ref: np.ndarray, *, exact: bool) -> None:
    out = np.asarray(out)
    if out.shape != ref.shape or not np.isfinite(out).all():
        raise AssertionError(f"{name}: shape {out.shape} (want {ref.shape}) or non-finite")
    err = float(np.max(np.abs(out.astype(np.float64) - ref)))
    if exact:
        np.testing.assert_array_equal(out, ref, err_msg=name)
    else:
        np.testing.assert_allclose(out, ref, rtol=RTOL, atol=0, err_msg=name)
    rule = "exact" if exact else f"rtol {RTOL:g}"
    print(f"  {name}: max |out - ref| = {err!r} ({rule}) ok")


def _assert_compiled(cfg: DenoiseConfig) -> float:
    """Every kernel family lowers to a Mosaic kernel; returns compile seconds.

    Lowers and compiles each ``ops`` entry point the served path calls, at
    the paper shape, and requires ``tpu_custom_call`` in each lowering: a
    kernel run by the Pallas interpreter would lower to plain HLO.
    """
    backend = ops._resolve(cfg.backend)
    if backend != "pallas":
        raise AssertionError(f"backend {cfg.backend!r} resolved to {backend!r}, not pallas")
    p, n, h, w = cfg.pairs_per_group, cfg.frames_per_group, cfg.height, cfg.width
    f32, u16 = jnp.float32, jnp.uint16

    def s(*shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype)

    k = cfg.median_window
    step = dict(num_groups=cfg.num_groups, offset=cfg.offset, variant=cfg.variant)
    lowered = {
        "stream_step": ops.stream_step.lower(s(p, h, w), s(n, h, w, dtype=u16), **step),
        "multibank_step": ops.multibank_stream_step.lower(
            s(4, p, h, w), s(4, n, h, w, dtype=u16), **step
        ),
        "median_insert": ops.median_window_insert.lower(
            s(k, p, h, w), s(n, h, w, dtype=u16), slot=0, offset=cfg.offset
        ),
        "median_combine": ops.median_combine.lower(s(k, p, h, w)),
        "ema": ops.ema_welford_step.lower(
            s(p, h, w), s(h, w), s(h, w), s(n, h, w, dtype=u16),
            alpha=cfg.ema_alpha, offset=cfg.offset, prior_count=0,
        ),
        "spatial": ops.spatial_filter.lower(
            s(p, h, w), mode=cfg.spatial_mode, range_sigma=cfg.spatial_range_sigma
        ),
    }
    t0 = time.perf_counter()
    for name, low in lowered.items():
        if "tpu_custom_call" not in low.as_text():
            raise AssertionError(f"{name}: no tpu_custom_call in the lowered step")
        low.compile()
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------


def _gated(groups, gate: threading.Event):
    """Yield ``groups`` once ``gate`` opens."""
    gate.wait()
    yield from groups


def _serve(sched: SessionScheduler, streams) -> list:
    """Submit one session per ``(config, groups, name)``; return outputs.

    No chunk flows until every session holds its slot, so the cohort of
    co-configured sessions forms from the first group on.
    """
    gate = threading.Event()
    handles = [
        sched.submit(Session(c, _gated(groups, gate), name=name))
        for c, groups, name in streams
    ]
    deadline = time.perf_counter() + TIMEOUT_S
    while any(h.status != "active" for h in handles):
        if time.perf_counter() > deadline:
            raise TimeoutError("sessions were not seated")
        time.sleep(0.01)
    gate.set()
    return [h.result(timeout=TIMEOUT_S)[0] for h in handles]


def one_chip() -> None:
    base = DenoiseConfig(**PAPER, backend="auto", tile_plan="heuristic", stream_dtype="u16")
    g = base.num_groups
    configs = {
        f: DenoiseConfig(**{**PAPER, "filter_name": f})
        for f in ("temporal_median", "ema_variance", "spatial_box")
    }
    print(f"config: {PAPER}, median_window={base.median_window}, "
          f"spatial_mode={configs['spatial_box'].spatial_mode}")
    compile_s = _assert_compiled(base)
    print(f"compile_s (six kernel steps, ahead of time): {compile_s!r}")

    t0 = time.perf_counter()
    cohort = [list(PrismSource(base, seed=s).groups()) for s in range(4)]
    others = {f: list(PrismSource(c, seed=10 + i).groups())
              for i, (f, c) in enumerate(configs.items())}
    others["ema_variance"] = flicker(others["ema_variance"], seed=20)
    print(f"data_s (host, {4 + len(others)} sessions x {g} groups): "
          f"{time.perf_counter() - t0!r}")

    streams = [(base, grp, f"pair{s}") for s, grp in enumerate(cohort)]
    streams += [(configs[f], grp, f) for f, grp in others.items()]
    with SessionScheduler(slots_per_executor=4, max_executors=4, coalesce_ms=2000) as sched:
        t0 = time.perf_counter()
        outs = _serve(sched, streams)
        first_s = time.perf_counter() - t0
        steps = {e["filter"]: e["cohort_steps"] for e in sched.stats()["executors"]}
    print(f"served_first_pass_s (7 sessions, compiles included): {first_s!r}")
    print(f"cohort_steps per executor: {steps}")
    if steps["pair_average"] != g:
        raise AssertionError(
            f"4 pair_average sessions took {steps['pair_average']} device steps, "
            f"not one banked cohort per group ({g})"
        )

    for s, out in enumerate(outs[:4]):
        _check(f"pair_average[{s}]", out, ref_pair_average(cohort[s], base), exact=True)
    outs = dict(zip(others, outs[4:]))
    mc = configs["temporal_median"]
    _check("temporal_median", outs["temporal_median"],
           ref_temporal_median(others["temporal_median"], mc), exact=True)
    ref, masked = ref_ema_variance(others["ema_variance"], configs["ema_variance"])
    print(f"  ema_variance: reference masks {masked} pixels")
    if masked < FLICKER_PIXELS:
        raise AssertionError(
            f"ema_variance: the reference masks {masked} pixels, fewer than the "
            f"{FLICKER_PIXELS} flickering ones: the mask branch goes unchecked"
        )
    _check("ema_variance", outs["ema_variance"], ref, exact=False)
    _check("spatial_box", outs["spatial_box"],
           ref_spatial_box(others["spatial_box"], configs["spatial_box"]), exact=False)

    # steady state: the same four-session cohort again, nothing to compile
    with SessionScheduler(slots_per_executor=4, max_executors=1, coalesce_ms=2000) as sched:
        t0 = time.perf_counter()
        again = _serve(sched, streams[:4])
        steady_s = time.perf_counter() - t0
    for s, out in enumerate(again):
        _check(f"pair_average[{s}] again", out, ref_pair_average(cohort[s], base), exact=True)
    print(f"steady_s_per_group (4-session pair_average cohort, served, "
          f"host feed included): {steady_s / g!r}")


def four_chips() -> None:
    cfg = DenoiseConfig(**PAPER, backend="auto", tile_plan="heuristic", stream_dtype="u16")
    mesh = make_bank_mesh(4)
    src = PrismSource(cfg, seed=0)
    banks = [list(src.bank_source(b)) for b in range(4)]
    refs = [ref_pair_average(grp, cfg) for grp in banks]

    t0 = time.perf_counter()
    out, _ = run_pipelined_banked(cfg, [iter(grp) for grp in banks], mesh)
    print(f"run_pipelined_banked_s (compiles included): {time.perf_counter() - t0!r}")
    devices = {shard.device for shard in out.addressable_shards}
    print(f"bank shards on devices: {sorted(d.id for d in devices)}")
    if len(devices) != 4:
        raise AssertionError(f"4 bank shards sit on {len(devices)} device(s)")
    for b in range(4):
        _check(f"run_pipelined_banked[{b}]", np.asarray(out)[b], refs[b], exact=True)

    with SessionScheduler(mesh=mesh, max_executors=1, coalesce_ms=2000) as sched:
        t0 = time.perf_counter()
        outs = _serve(sched, [(cfg, grp, f"bank{b}") for b, grp in enumerate(banks)])
        print(f"mesh_scheduler_s: {time.perf_counter() - t0!r}")
    for b, o in enumerate(outs):
        _check(f"mesh_scheduler[{b}]", o, refs[b], exact=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = _require_tpu(args.chips)
    cache = compile_cache.enable()
    dev = devices[0]
    print(f"device_kind: {dev.device_kind}, devices: {len(devices)}, jax {jax.__version__}")
    print(f"compile cache: {cache}")
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')!r}")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))


if __name__ == "__main__":
    main()
