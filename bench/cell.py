"""Run one cell: set up, warm up, measure a window, check every kept result.

The system under test is ``repro.serve.SessionScheduler`` driving
``core/banks.banked_filter_step`` and the Pallas kernels; the benchmark
gives it sessions and reads back results from the camera's side
(``cameras.py``). Set-up makes the frame pool, builds the scheduler and
pushes every cohort shape the traffic will use through the scheduler once,
so nothing the program compiles once per shape compiles inside the window.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import shutil
import sys
import threading
import time

import numpy as np

from bench import reference, spec
from bench.cameras import Acquisition, CameraFeed
from bench.pool import make_pool

__all__ = ["Run", "run_cell", "nearest_rank", "PEAKS"]

PEAKS = spec.ROOT / "bench" / "peaks.json"
TRACE_DIR = spec.ROOT / ".bench_out" / "trace"
#: an answer that has not come a minute after the window closed never comes
RESULT_TIMEOUT_S = 60.0
#: the limit on ``max_abs_err``: float32 is exact on this path (reference.py)
MAX_ABS_ERR_LIMIT = 0.0


def nearest_rank(values, q: float) -> float:
    """Nearest-rank percentile ``q`` (0-100) of ``values``."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    k = max(1, int(np.ceil(q / 100.0 * len(v))))
    return float(v[k - 1])


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader may read."""

    paced: bool
    window: tuple[float, float]
    acquisitions: list[Acquisition]   # the window's: last frame due inside it
    registry: object                  # the scheduler's MetricsRegistry
    snapshots: tuple[dict, dict]      # program counters at the window's ends
    trace: object | None              # xplane.TraceSummary of a --trace 1 run
    denoise: object                   # the DenoiseConfig served
    peak: dict                        # the device's row of peaks.json


class _Sample:
    """The window's outputs kept for the check: each acquisition is kept
    or not by a coin drawn from the seed and its (camera, index), so the
    same seed checks the same acquisitions whatever the timing."""

    def __init__(self, share: float, seed: int, window):
        self.share = share
        self.seed = seed
        self.window = window
        self.kept: list[Acquisition] = []
        self.lock = threading.Lock()

    def offer(self, acq: Acquisition, output: np.ndarray) -> None:
        due = acq.last_due
        if due is None or not self.window[0] <= due < self.window[1]:
            return
        coin = np.random.default_rng((self.seed, acq.camera, acq.index, 0xC4EC)).random()
        if coin < self.share:
            acq.output = output
            with self.lock:
                self.kept.append(acq)


def _devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, found {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def _gated(chunks, gate: threading.Event):
    gate.wait()
    yield from chunks


def _warm_up(sched, cfg, pool, *, capacity: int, executors: int, mesh) -> list[int]:
    """Serve one cohort of every size the traffic can form, through the
    scheduler itself; returns the sizes that folded as one cohort.

    Each round seats k one-group sessions whose chunks already sit on the
    device, then releases them together, so the executor folds them as one
    cohort of k (single-chip executors) or one gang step (mesh executors,
    every executor of the pool filled). The first round sends host chunks,
    which warms the transfer path.
    """
    import jax.numpy as jnp

    from repro.serve import Session

    sizes = [capacity * executors] if mesh is not None else range(1, capacity + 1)
    chunks = [jnp.asarray(pool[i % len(pool)]) for i in range(max(sizes))]
    folded = []
    for r, k in enumerate(sizes):
        for _attempt in range(3):
            steps0 = sum(e["cohort_steps"] for e in sched.stats()["executors"])
            gate = threading.Event()
            src = [pool[i % len(pool)] for i in range(k)] if r == 0 else chunks[:k]
            handles = [
                sched.submit(Session(cfg, _gated([c], gate), name=f"warm{k}.{i}"))
                for i, c in enumerate(src)
            ]
            while any(h.status != "active" for h in handles):
                time.sleep(0.001)
            gate.set()
            for h in handles:
                np.asarray(h.result(timeout=600)[0])
            steps = sum(e["cohort_steps"] for e in sched.stats()["executors"]) - steps0
            if steps == (executors if mesh is not None else 1):
                folded.append(k)
                break
    return folded


def _snapshot(sched, lowered: list[int]) -> dict:
    groups = 0
    for inst in sched.metrics.instruments():
        if inst.name == "serve.latency_s":
            groups += inst.count
    steps = sum(e["cohort_steps"] for e in sched.stats()["executors"])
    return {"t": time.perf_counter(), "groups": groups, "cohort_steps": steps,
            "lowered": lowered[0]}


def _sleep_until(t: float) -> None:
    while (d := t - time.perf_counter()) > 0:
        time.sleep(d)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True) -> tuple[dict, Run]:
    """Run ``cell`` once; returns the result object ``run.py`` prints and
    the ``Run`` its metrics were read from.

    ``t_start`` is the process's start on ``time.perf_counter``'s clock:
    set-up runs from it to the window's start.
    """
    import jax

    devices = _devices(cell.chips, require_tpu)
    lowered = [0]  # programs lowered (compiled or read from the cache)

    def _count(event: str, _duration: float, **_kw) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered[0] += 1

    jax.monitoring.register_event_duration_secs_listener(_count)
    try:
        return _run(cell, seed, seconds, trace, t_start, devices, lowered, require_tpu)
    finally:
        jax.monitoring.unregister_event_duration_listener(_count)


def _run(cell, seed, seconds, trace, t_start, devices, lowered, require_tpu):
    import jax

    from repro import obs
    from repro.core.banks import make_bank_mesh
    from repro.core.denoise import DenoiseConfig
    from repro.serve import Session, SessionScheduler

    with open(PEAKS) as f:
        peaks = json.load(f)
    kind = devices[0].device_kind
    if require_tpu and kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in {PEAKS.name}")
    cfg = DenoiseConfig(**cell.config["denoise"])
    traffic = cell.traffic
    g, n = cfg.num_groups, cfg.frames_per_group
    pool = make_pool(seed, traffic["pool_groups"], n, cfg.height, cfg.width)

    banks = cell.config.get("mesh_banks")
    mesh = make_bank_mesh(banks) if banks else None
    sched = SessionScheduler(mesh=mesh, **cell.config["scheduler"])
    folded = _warm_up(sched, cfg, pool, capacity=sched.slots_per_executor,
                      executors=sched.max_executors, mesh=mesh)

    if trace:
        obs.configure(enabled=True, annotate=True)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    t0 = time.perf_counter() + 0.05
    ws = t0 + traffic["lead_in_s"]
    we = ws + seconds
    sample = _Sample(traffic["check_share"], seed, (ws, we))

    def submit(name, source):
        return sched.submit(Session(cfg, source, name=name))

    feed = CameraFeed(
        submit, pool,
        cameras=traffic["cameras"], groups=g, frames_per_group=n,
        frame_interval_us=traffic["frame_interval_us"], seed=seed,
        t0=t0, close=we, keep=sample.offer, result_timeout_s=RESULT_TIMEOUT_S,
    )
    feed.start()
    _sleep_until(ws)
    snap0 = _snapshot(sched, lowered)
    with jax.profiler.TraceAnnotation("bench.window") if trace else contextlib.nullcontext():
        _sleep_until(we)
    snap1 = _snapshot(sched, lowered)
    feed.join(timeout=RESULT_TIMEOUT_S + g * n * 1e-3 + 30.0)

    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    summary = None
    if trace:
        from bench import xplane

        jax.profiler.stop_trace()
        obs.configure(enabled=False, annotate=False)
        summary = xplane.reduce_trace(xplane.load(xplane.find_xplane(str(TRACE_DIR))), cell.chips)
    sched.shutdown(wait=False)
    registry = sched.metrics
    del sched
    gc.collect()

    window = [
        a for a in feed.acquisitions
        if a.last_due is not None and ws <= a.last_due < we
    ]
    delivered_in_window = [
        a for a in feed.acquisitions if a.delivered is not None and ws <= a.delivered < we
    ]
    refused = sum(a.refused is not None for a in window)
    missing = sum(a.refused is None and a.delivered is None for a in window)

    # correctness: every kept output against the plain reference
    t_check = time.perf_counter()
    cache: dict[int, np.ndarray] = {}

    def ref(acq: Acquisition) -> np.ndarray:
        for i in acq.groups:
            if int(i) not in cache:
                cache[int(i)] = reference.diffs(pool[int(i)], cell.config["denoise"]["offset"])
        return reference.pair_average([cache[int(i)] for i in acq.groups])

    errs = [reference.max_abs_err(a.output, ref(a)) for a in sample.kept]
    max_err = max(errs) if errs else float("inf")
    checks = {
        "max_abs_err": {"value": max_err, "limit": MAX_ABS_ERR_LIMIT, "rule": "<="},
        "missing": {"value": missing, "limit": 0, "rule": "<="},
        "compared": {"value": len(errs), "limit": 1, "rule": ">="},
    }
    correct = max_err <= MAX_ABS_ERR_LIMIT and missing == 0 and len(errs) >= 1
    t_checked = time.perf_counter()

    run = Run(
        paced=traffic["frame_interval_us"] is not None,
        window=(ws, we),
        acquisitions=window,
        registry=registry,
        snapshots=(snap0, snap1),
        trace=summary,
        denoise=cfg,
        peak=peaks.get(kind, {}),
    )
    if trace:
        from bench.spec import per_layer_readers

        metrics = {}
        readers = per_layer_readers(cell)
        for m in cell.per_layer:
            value = readers[m["name"]](run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        lat_ms = [(a.delivered - a.last_due) * 1e3 for a in window if a.delivered is not None]
        e2e = {
            "frames_per_s": len(delivered_in_window) * g * n / seconds,
            "setup_s": ws - t_start,
        }
        if lat_ms:
            e2e["result_p50_ms"] = nearest_rank(lat_ms, 50)
        metrics = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in e2e
        }

    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": bool(correct),
        "attempted": len(window),
        "failed": refused + missing,
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": summary.device_ops(),
            "idle_gaps": summary.idle_gaps(),
        }
    result["warm_cohorts"] = folded
    result["lowered_in_window"] = snap1["lowered"] - snap0["lowered"]
    result["check_s"] = t_checked - t_check
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} {c['rule']} {c['limit']!r}", file=sys.stderr)
    return result, run
