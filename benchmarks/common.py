"""Shared benchmark helpers. Every table prints ``name,us_per_call,derived``
CSV rows via ``emit`` so ``benchmarks.run`` output is machine-readable;
executor tables additionally print full ``StreamReport`` rows via
``emit_report`` (transfer/stall/overlap and the per-stage ring fields —
the data PR 1's CSVs silently dropped).

``bench_record`` additionally appends structured trajectory points to
``BENCH_denoise.json`` (repo root; override with ``BENCH_DENOISE_PATH``) so
speedups of the fused/prefetched paths are tracked across PRs — see
docs/BENCHMARKS.md for the schema.
"""

from __future__ import annotations

import json
import os
import pathlib
import tempfile
import time

import jax
import numpy as np

from repro.core.denoise import DenoiseConfig
from repro.core.streaming import StreamReport

__all__ = [
    "emit",
    "emit_report",
    "timeit",
    "bench_config",
    "bench_record",
    "stream_pass_s",
    "PAPER_G",
    "PAPER_N",
    "PAPER_H",
    "PAPER_W",
]

PAPER_G, PAPER_N = 8, 1000  # paper §6 defaults
PAPER_H, PAPER_W = 80, 256  # one camera bank

_BENCH_PATH = pathlib.Path(__file__).resolve().parent.parent / "BENCH_denoise.json"

#: one-shot migration map: 59 legacy points predate the required ``kind``
#: field; their trajectory ``name`` determines the point shape exactly,
#: so ``_migrate_kinds`` backfills from it (unknown names fall back to
#: ``kind == name`` — present, greppable, and honest about provenance).
_KIND_FROM_NAME = {
    "denoise_fused_vs_reference": "speedup",
    "multibank_fused_vs_reference": "speedup",
    "streaming_prefetch_vs_presync": "speedup",
    "inline_prefetch_vs_sync": "speedup",
    "ring_depth_overlap": "speedup",
    "filter_zoo_median_vs_mean_impulse": "snr_gain",
    "multitenant": "multitenant",
    "snr": "snr",
}


def _migrate_kinds(records: list) -> bool:
    """Backfill ``kind`` on legacy points in place; True if anything changed."""
    changed = False
    for rec in records:
        if isinstance(rec, dict) and "kind" not in rec:
            name = rec.get("name")
            # a nameless/mistyped (even unhashable) record still gets a
            # typed string kind — readers can rely on kind being a str
            if isinstance(name, str) and name:
                rec["kind"] = _KIND_FROM_NAME.get(name, name)
            else:
                rec["kind"] = "unknown"
            changed = True
    return changed


def bench_record(name: str, kind: str, **fields) -> None:
    """Append one trajectory point to BENCH_denoise.json.

    Each point is ``{"name", "kind", "timestamp", **fields}``. ``name``
    is the trajectory (the stable identifier readers plot across PRs);
    ``kind`` is the required point shape discriminator (``"speedup"``,
    ``"throughput"``, ``"snr"``, ...) — see docs/BENCHMARKS.md. Loading a
    file that still contains pre-``kind`` legacy points triggers a
    one-shot in-file migration backfilling them from their ``name``.
    The file is a flat JSON list, append-only across runs.

    The append is crash- and concurrency-safe: the new list is written to
    a temp file in the same directory and ``os.replace``\\ d over the
    target (atomic on POSIX), so a benchmark process dying mid-write — or
    two overlapping benchmark runs — can never leave a truncated/corrupt
    file. Concurrent writers may still lose each other's *latest* point
    (last replace wins; there is deliberately no cross-process lock), but
    every reader always sees valid JSON.

    Every point is additionally stamped with a monotone ``run_seq``
    (``max`` over the file's existing stamps, plus one — derived from
    file contents inside the same read-modify-replace cycle, so it is
    exactly as crash-safe as the append itself), so a reader can order a
    family's points by it instead of trusting wall-clock timestamps,
    which CI runners skew freely.
    """
    if not kind or not isinstance(kind, str):
        raise ValueError(f"bench_record needs a non-empty kind, got {kind!r}")
    path = pathlib.Path(os.environ.get("BENCH_DENOISE_PATH", _BENCH_PATH))
    records = []
    if path.exists():
        try:
            records = json.loads(path.read_text())
        except (json.JSONDecodeError, OSError):
            records = []
        if not isinstance(records, list):
            records = []
    _migrate_kinds(records)
    seq = 0
    for rec in records:
        if isinstance(rec, dict):
            s = rec.get("run_seq")
            if isinstance(s, (int, float)) and not isinstance(s, bool):
                seq = max(seq, int(s))
    records.append(
        {
            "name": name,
            "kind": kind,
            "timestamp": time.time(),
            "run_seq": seq + 1,
            **fields,
        }
    )
    fd, tmp = tempfile.mkstemp(
        dir=path.parent, prefix=f".{path.name}.", suffix=".tmp"
    )
    try:
        with os.fdopen(fd, "w") as f:
            f.write(json.dumps(records, indent=2) + "\n")
        os.replace(tmp, path)
    except BaseException:
        # never leave temp droppings next to the target on a failed write
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def bench_config(quick: bool, **kw) -> DenoiseConfig:
    base = dict(
        num_groups=PAPER_G,
        frames_per_group=200 if quick else PAPER_N,
        height=PAPER_H,
        width=PAPER_W,
        algorithm="alg3",
        backend="xla",
    )
    base.update(kw)
    return DenoiseConfig(**base)


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


_report_headers_printed: set[str] = set()


def emit_report(name: str, report: StreamReport) -> None:
    """Print one full report CSV row (header once per report class).

    Carries every field ``report.row`` produces — elapsed/buffering/
    compute plus transfer_s, stall_s, overlap_frac and the ring-pipeline
    stage breakdown — so executor benchmarks never lose the overlap data
    to a truncated row again. The header comes from ``type(report)``, so
    subclasses with extra columns (``repro.serve.SessionReport``) emit
    *their* header rather than desyncing rows against the base one.
    Rows are prefixed ``report/`` to keep them distinguishable from the
    3-column ``emit`` rows in mixed output.
    """
    cls = type(report)
    if cls.__qualname__ not in _report_headers_printed:
        print(f"# {cls.header()}")
        _report_headers_printed.add(cls.__qualname__)
    print(f"report/{report.row(name)}")


def stream_pass_s(den, groups) -> float:
    """Wall seconds for one full ingest+finalize streaming pass over
    pre-staged device chunks — the shared timing body of the plan
    comparisons in ``table12_autotune`` and ``roofline_report`` (one
    implementation so their numbers stay comparable)."""
    t0 = time.perf_counter()
    state = den.init()
    for k, g in enumerate(groups):
        state = den.ingest(state, g, step=k)
    jax.block_until_ready(den.finalize(state))
    return time.perf_counter() - t0


def timeit(fn, *args, warmup: int = 1, iters: int = 3, **kw) -> float:
    """Median wall time (seconds) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))
